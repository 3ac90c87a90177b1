"""The readers of PR 25's per-layer metrics on synthetic ``facts``
(the window filter, the 5 x median rule, a reduction of ``None``), and
a rehearsal on the CPU that prints the four of them a CPU can read."""

import importlib.util
import json
import os
import types

import pytest

from test_rehearsal import BENCH, rehearse

SPAN_METRICS = ("frontend_p50_ms", "wal_fsync_p50_ms",
                "flush_stall_ms_per_s", "gc_pause_ms_per_s")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def facts(records, reduction=None, seconds=10.0, end=1000.0, t0=None):
    dump = {"lat_records": records}
    if reduction is not None:
        dump["trace"] = {"reduction": reduction}
    out = {"dump": dump, "seconds": seconds, "window_end_unix": end}
    if t0 is not None:
        out["log"] = types.SimpleNamespace(t0=t0)
    return out


def rec(unix, total, **marks):
    return {"clock": [5.0, unix], "total": total, "starts": {}, **marks}


WINDOW = [rec(990.5 + i, 0.010, gc=0.001) for i in range(9)]


@pytest.mark.parametrize("extra,expect", [
    ([], (0.0, 9)),
    # one flush of 300 ms is over 5 x the median of 10 ms
    ([rec(995.0, 0.300)], (30.0, 10)),
    # 50 ms is exactly 5 x the median: not over it
    ([rec(995.0, 0.050)], (0.0, 10)),
    # a stall before the window (the warm-up's) or after it (the
    # read-back's) is not the window's
    ([rec(989.9, 0.300), rec(1000.1, 0.300)], (0.0, 9)),
    # records without a stamp (the parent's) or without a total
    # (a compaction event) are not flush records of the window
    ([{"total": 0.3}, {"clock": [1.0, 995.0], "svc_compaction": 0.3}],
     (0.0, 9)),
])
def test_stall_rate(extra, expect):
    assert reader("stall_rate")(facts(WINDOW + extra)) == \
        pytest.approx(expect)


@pytest.mark.parametrize("records,marks,expect", [
    (WINDOW, ["gc"], (0.9, 9)),
    (WINDOW, ["gc", "wal_fsync"], (0.9, 9)),        # a mark no record has
    (WINDOW + [rec(980.0, 0.01, gc=5.0)], ["gc"], (0.9, 9)),
    (WINDOW + [rec(999.0, 0.01, gc=0.25)], ["gc"], (25.9, 10)),
    ([{"total": 0.01, "gc": 1.0}], ["gc"], None),   # the parent's records
    ([], ["gc"], None),
])
def test_mark_rate(records, marks, expect):
    got = reader("mark_rate")(facts(records), marks=marks)
    assert got == (pytest.approx(expect) if expect else None)


@pytest.mark.parametrize("t0,expect", [
    # the phase returned 20 s after the window (the tracer wrote its
    # trace out): the generator's own start of the window, 965.0 on
    # the wall clock through a record's pair (perf 5.0 = its unix),
    # puts the window where it was
    (5.0 + (965.0 - 990.5), (0.9, 9)),
    # a start that cannot be one (after end - seconds; long before):
    # the rule without a log
    (5.0 + 100.0, (0.0, 1)),
    (5.0 - 5000.0, (0.0, 1)),
    (None, (0.0, 1)),
])
def test_window_is_the_generators_when_the_phase_returned_late(
        t0, expect):
    early = [rec(965.5 + i, 0.010, gc=0.001) for i in range(9)]
    read_back = [rec(990.5, 0.010)]
    # every record's pair says perf 5.0 was that record's unix stamp;
    # the first record's (965.5) is the one used
    for r in early + read_back:
        r["clock"] = [5.0 + (r["clock"][1] - 990.5), r["clock"][1]]
    got = reader("mark_rate")(facts(early + read_back, t0=t0),
                              marks=["gc"])
    assert got == pytest.approx(expect)
    stall = reader("stall_rate")(facts(early + read_back, t0=t0))
    assert stall == pytest.approx((0.0, expect[1]))


@pytest.mark.parametrize("reduction,expect", [
    (None, None),                                   # no device: a CPU
    ({"idle_gaps": []}, None),
    ({"idle_gaps": [["unattributed", 0.3], ["unattributed", 0.1]]},
     (0.0, 2)),
    ({"idle_gaps": [["svc.wal_fsync", 0.3], ["unattributed", 0.1]]},
     (75.0, 2)),
    ({"idle_gaps": [["svc.between_flushes", 0.02], ["py.gc", 0.02]]},
     (100.0, 2)),
])
def test_idle_named(reduction, expect):
    got = reader("idle_named")(facts([], reduction))
    assert got == (pytest.approx(expect) if expect else None)


def recorded():
    """PR 27's traced run of the mesh cell on four chips: the records
    of the window, its drain and the read-back behind it."""
    with open(os.path.join(BENCH, "testdata",
                           "mesh4_window_records.json")) as f:
        rec = json.load(f)
    return rec, {"dump": {"lat_records": rec["lat_records"]},
                 "seconds": rec["seconds"],
                 "window_end_unix": rec["window_end_unix"],
                 "log": types.SimpleNamespace(t0=rec["t0"])}


def test_rounds_per_flush_reads_the_windows_records_only():
    rec, f = recorded()
    ks = [r["k"] for r in rec["lat_records"] if "k" in r]
    assert len(ks) == 773 and sum(ks) / len(ks) == pytest.approx(2.0492,
                                                                 abs=1e-4)
    # the window's 629 flushes launched 1.496 rounds each; the drain's
    # and the read-back's 144 were 4.5 deep
    assert reader("window_mean")(f, field="k") == \
        pytest.approx((1.4960254372019077, 629))
    # without the generator's own start the window is placed by the
    # phase's return, which the tracer delayed by 19 s here
    del f["log"]
    assert reader("window_mean")(f, field="k") == \
        pytest.approx((1.5248091603053435, 524))


@pytest.mark.parametrize("records,expect", [
    ([], None),
    ([{"total": 0.01, "k": 4}], None),              # no stamp
    ([rec(995.0, 0.01)], None),                     # no such field
    ([rec(995.0, 0.01, k=1), rec(996.0, 0.01, k=4),
      rec(1000.5, 0.01, k=64)], (2.5, 2)),          # the read-back's
])
def test_window_mean(records, expect):
    got = reader("window_mean")(facts(records), field="k")
    assert got == (pytest.approx(expect) if expect else None)


def test_layer_files_name_readers_that_exist():
    """Every per-layer metric of ``BENCHMARK.json``, wherever it stands
    in the list, has a ``layers/`` file that names a reader there is;
    no ``layers/`` file is left without its entry, but for the file of
    ``dispatch_pack_p50_ms`` (its entry went with PR 49; the file stays
    while ``docs/ARCHITECTURE.md``, which a benchmark PR may not edit,
    names it and tier-1's ``test_docs_paths`` looks for it)."""
    with open(os.path.join(os.path.dirname(BENCH),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert {*SPAN_METRICS, "idle_named_share", "rounds_per_flush"} <= set(
        names)
    for name in names:
        with open(os.path.join(BENCH, "layers", name + ".json")) as f:
            spec = json.load(f)
        assert callable(reader(spec["reader"])), name
    assert sorted(names + ["dispatch_pack_p50_ms"]) == sorted(
        n[:-len(".json")] for n in os.listdir(os.path.join(BENCH, "layers")))


def test_counter_ratio_reads_the_window_or_the_whole_run():
    """``read_fastpath_hit_share`` since PR 49: the counters between
    ``mark`` and the window's end (``window_counters``), so that the
    read-back's device reads leave the denominator; ``since_mark`` (to
    the ``dump``) is what ``ops_per_flush`` still reads."""
    dump = {"since_mark": {"read_fastpath_hits": 44_000,
                           "read_fastpath_misses": 35_000,
                           "flushes": 9_000, "ops_served": 150_000},
            "window_counters": {"read_fastpath_hits": 44_000,
                                "read_fastpath_misses": 600,
                                "flushes": 8_000, "ops_served": 90_000}}
    kw = {"num": ["read_fastpath_hits"],
          "den": ["read_fastpath_hits", "read_fastpath_misses"],
          "scale": 100.0}
    read = reader("counter_ratio")
    assert read({"dump": dump}, **kw) == (pytest.approx(55.696, rel=1e-4),
                                          79_000)
    assert read({"dump": dump}, over="window_counters", **kw) == (
        pytest.approx(98.655, rel=1e-4), 44_600)
    # a child from before PR 49 has no such reading: nothing to read
    del dump["window_counters"]
    assert read({"dump": dump}, over="window_counters", **kw) is None
    with open(os.path.join(BENCH, "layers",
                           "read_fastpath_hit_share.json")) as f:
        assert json.load(f)["args"]["over"] == "window_counters"
    # no attempt in the window: nothing to read, never a 0
    dump["window_counters"] = {"read_fastpath_hits": 0,
                               "read_fastpath_misses": 0}
    assert read({"dump": dump}, over="window_counters", **kw) is None


def test_rehearsal_prints_the_span_metrics_a_cpu_can_read():
    by = rehearse("ycsb-a.ring64-n3-deep")
    layer = by["per_layer"]
    assert set(SPAN_METRICS) <= set(layer)
    assert "idle_named_share" not in layer
    assert layer["frontend_p50_ms"]["value"] > 0.0
    assert layer["wal_fsync_p50_ms"]["value"] > 0.0
    assert layer["flush_stall_ms_per_s"]["samples"] > 0
    assert layer["gc_pause_ms_per_s"]["value"] >= 0.0
