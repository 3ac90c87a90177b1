"""The three readers of PR 41 (``req_rows``, ``outside_server``,
``launch_shape``) on a small hand-made dump whose answers are known
(``testdata/req_rows_window.json``): a window boundary, a kind with no
rows, a program whose records carry neither rows nor shape (the
parent's), and the CPU rehearsal printing every new metric."""

import json
import os
import types

import numpy as np
import pytest

from test_rehearsal import BENCH, rehearse
from test_span_readers import reader

UPDATES = ["kput", "kupdate", "kput_once", "kmodify", "kdelete",
           "ksafe_delete"]
NEW_METRICS = (
    "op_residence_update_p50_ms", "op_residence_read_p50_ms",
    "rx_hold_p50_ms", "outside_server_update_mean_ms",
    "outside_server_read_mean_ms", "launch_pad_share",
    "launch_width_mean", "dispatch_step_p50_ms", "h2d_put_p50_ms")


def facts(strip=()):
    with open(os.path.join(BENCH, "testdata",
                           "req_rows_window.json")) as f:
        d = json.load(f)
    nan = lambda xs: np.array(                      # noqa: E731
        [np.nan if x is None else x for x in xs], float)
    log = types.SimpleNamespace(
        t0=d["log"]["t0"], is_read=np.array(d["log"]["is_read"]),
        sent=nan(d["log"]["sent"]), done=nan(d["log"]["done"]))
    recs = [{k: v for k, v in r.items() if k not in strip}
            for r in d["lat_records"]]
    return {"dump": {"lat_records": recs}, "seconds": d["seconds"],
            "window_end_unix": d["window_end_unix"], "log": log}


@pytest.mark.parametrize("args,expect", [
    # the window's three update rows: 5, 7 and 9 ms (the warm-up's
    # 500 ms row lies before the window)
    (dict(field="residence_s", q=50, verbs=UPDATES), (7.0, 3)),
    # its three reads, leased and not: 0.1, 0.3 and 3 ms (the
    # read-back's 900 ms row lies after the window)
    (dict(field="residence_s", q=50, verbs=["kget"]), (0.3, 3)),
    (dict(field="residence_s", q=50, verbs=["kget"], direct=1),
     (0.2, 2)),
    (dict(field="residence_s", q=50, verbs=["kget"], direct=0),
     (3.0, 1)),
    (dict(field="residence_s", q=100, verbs=UPDATES), (9.0, 3)),
    # every verb; the row without a hold (a loop with no selector to
    # stamp) is left out: 0.5, 1, 2, 4, 6, 8 ms
    (dict(field="rx_hold_s", q=50), (3.0, 6)),
    (dict(field="rx_hold_s", q=50, verbs=["kmodify"]), None),
])
def test_req_rows(args, expect):
    got = reader("req_rows")(facts(), **args)
    assert got == (pytest.approx(expect) if expect else None)


@pytest.mark.parametrize("op,verbs,expect", [
    # the client saw 10, 12 and 14 ms (the fourth was never answered)
    # and the server held the three for 5, 7 and 9: 12 - 7
    ("update", UPDATES, (5.0, 3)),
    # 4, 2, 3 and 3 ms against 3, 0.1 and 0.3: 3 - 3.4 / 3
    ("read", ["kget"], (3.0 - 3.4 / 3.0, 3)),
    ("update", ["kmodify"], None),      # a kind with no rows
])
def test_outside_server(op, verbs, expect):
    got = reader("outside_server")(facts(), op=op, verbs=verbs)
    assert got == (pytest.approx(expect) if expect else None)


@pytest.mark.parametrize("ratio,expect", [
    # a > 0 in the window: 6 of 8, 2 of 8, 12 of 2 x 16
    ("pad", ((0.25 + 0.75 + 0.625) / 3.0, 3)),
    # records with columns: 6/6, 2/2, 9/12, 20/32 (the election flush
    # has none)
    ("busiest", ((1.0 + 1.0 + 0.75 + 0.625) / 4.0, 4)),
])
def test_launch_shape(ratio, expect):
    assert reader("launch_shape")(facts(), ratio=ratio) == \
        pytest.approx(expect)


def test_launch_width_is_a_window_mean():
    assert reader("window_mean")(facts(), field="a") == \
        pytest.approx(((8 + 8 + 16 + 0 + 0) / 5.0, 5))


def test_a_program_without_rows_or_shape_gives_nothing_to_read():
    """The parent's records: every new reader returns None and none
    raises (the traced runs of a check lay these files over it)."""
    f = facts(strip=("reqs", "a", "cols", "cols_max", "shards"))
    assert reader("req_rows")(f, field="residence_s", q=50) is None
    assert reader("outside_server")(f, op="read", verbs=["kget"]) is None
    assert reader("launch_shape")(f, ratio="pad") is None
    assert reader("launch_shape")(f, ratio="busiest") is None
    assert reader("window_mean")(f, field="a") is None
    assert reader("mark_p50")(f, marks=["dispatch_step"]) is None
    del f["log"]
    assert reader("outside_server")(facts() | {"log": None},
                                    op="read", verbs=["kget"]) is None


def test_new_layer_files_name_readers_that_exist():
    with open(os.path.join(os.path.dirname(BENCH),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS + ("busiest_shard_share",):
        with open(os.path.join(BENCH, "layers", name + ".json")) as f:
            spec = json.load(f)
        assert callable(reader(spec["reader"]))
        assert name in by
    assert by["busiest_shard_share"]["workloads"] == [
        "ycsb-a.ring40k-n5-mesh4", "ycsb-a.ring256-n3-h5-mesh4"]
    assert all("workloads" not in by[n] for n in NEW_METRICS)


def test_rehearsal_prints_every_new_metric():
    layer = rehearse("ycsb-a.ring64-n3-deep")["per_layer"]
    assert set(NEW_METRICS) <= set(layer), set(NEW_METRICS) - set(layer)
    assert "busiest_shard_share" not in layer       # the mesh cell's
    assert layer["op_residence_update_p50_ms"]["value"] > 0.0
    assert layer["rx_hold_p50_ms"]["value"] > 0.0
    assert 0.0 <= layer["launch_pad_share"]["value"] < 1.0
    assert layer["launch_width_mean"]["value"] >= 8.0
    assert layer["dispatch_step_p50_ms"]["value"] > 0.0


def test_mesh_rehearsal_prints_the_busiest_shards_share():
    layer = rehearse("ycsb-a.ring40k-n5-mesh4", devices=4)["per_layer"]
    assert set(NEW_METRICS) <= set(layer), set(NEW_METRICS) - set(layer)
    # four shards: a quarter when even, all of it when one shard works
    assert 0.25 <= layer["busiest_shard_share"]["value"] <= 1.0
    assert 0.0 <= layer["launch_pad_share"]["value"] < 1.0
