"""``ycsb-a.ring64-n3-h5`` (PR 43) is a configuration file, a traffic
file and an entry of BENCHMARK.json: the real cell, rehearsed on a CPU
at a height the source's own (five levels of interior nodes), and the
two readers it brought, on synthetic ``facts``."""

import json
import os

import pytest

from test_rehearsal import BENCH, ROOT, rehearse
from test_span_readers import WINDOW, facts, reader, rec

CELL = "ycsb-a.ring64-n3-h5"


def _json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def test_h5_deployment_is_the_sources_own_size():
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _json(ROOT, entry["file"])
    deep = _json(BENCH, "configs", "ring64-n3-deep.json")
    assert (cfg["n_ens"], cfg["n_peers"], cfg["n_slots"]) == (64, 3, 16 ** 5)
    assert cell["chips"] == cfg["chips"] == 1 and cfg["engine"] == "single"
    assert cfg["guarantees"] == deep["guarantees"]
    assert cfg["assumed"] == deep["assumed"]
    assert list(cfg["reduced"]) == entry["reduced"] == ["records_per_ens"]
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert len(cell["why"]) <= 200
    traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
    a = _json(BENCH, "traffic", "ycsb-a-r2000.json")
    for key in ("readproportion", "updateproportion", "requestdistribution",
                "zipfianconstant", "fieldcount", "fieldlength", "arrivals",
                "connections", "drain_seconds"):
        assert traffic[key] == a[key], key
    assert traffic["rate"] <= 2000 and traffic["rate"] % 100 == 0
    # load, warm-up (every burst of the grid a write, three attempts
    # at the pile-ups and the steady phase) and window stay under
    # wal_compact_records: no run folds the WAL
    grid = traffic["warm_grid"]
    writes = (cfg["records_per_ens"] * cfg["n_ens"]
              + grid["rounds"] * sum(d * w for d in grid["depths"]
                                     for w in grid["widths"]
                                     if d * w <= grid["max_ops"])
              + 3 * sum(traffic["warm_pileups"]) // 2
              + (3 * traffic["warm_seconds"] + 45) * traffic["rate"] // 2)
    assert writes < 1 << 18, writes


def test_h5_rehearsal_is_correct_over_five_levels():
    import needs

    cfg = _json(BENCH, "configs", "ring64-n3-h5.json")
    assert needs.tree_levels(cfg["n_slots"]) == 5
    assert needs.tree_levels(cfg["rehearse"]["n_slots"]) == 5
    by = rehearse(CELL)
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    g = by["checked"]["guarantees"]
    assert g.pop("tpu") is False and all(g.values())
    assert by["checked"]["keys_read_back"] > 0
    assert by["checked"]["processes_left"] == 0
    layer = by["per_layer"]
    assert layer["state_init_s"]["value"] > 0.0
    assert 0.0 < layer["step_wait_share"]["value"] < 1.0
    assert layer["rounds_per_flush"]["value"] >= 1.0


@pytest.mark.parametrize("records,marks,expect", [
    # nine flushes of 10 ms, 4 ms of each the device's
    ([dict(r, device_d2h=0.004) for r in WINDOW], ["device_d2h"], (0.4, 9)),
    ([dict(r, device_d2h=0.003, inflight_wait=0.002) for r in WINDOW],
     ["device_d2h", "inflight_wait"], (0.5, 9)),
    # a mark no record has counts 0; a record outside the window is
    # not the window's
    (WINDOW + [rec(980.0, 1.0, device_d2h=1.0)], ["device_d2h"], (0.0, 9)),
    ([{"total": 0.01, "device_d2h": 0.01}], ["device_d2h"], None),
    ([], ["device_d2h"], None),
])
def test_window_share(records, marks, expect):
    got = reader("window_share")(facts(records), marks=marks)
    assert got == (pytest.approx(expect) if expect else None)


@pytest.mark.parametrize("stats,expect", [
    ({"startup": {"state_init_s": 2.5, "host_init_s": 0.1}}, (2.5, 1)),
    ({"startup": {}}, None),            # the key is not there
    ({"flushes": 3}, None),             # the parent's stats()
    ({"startup": {"state_init_s": None}}, None),
    (None, None),
])
def test_stats_field(stats, expect):
    f = facts([])
    if stats is not None:
        f["dump"]["stats"] = stats
    assert reader("stats_field")(
        f, path=["startup", "state_init_s"]) == expect
