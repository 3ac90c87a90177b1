"""``ycsb-a.ring256-n3-h5-mesh4`` (PR 45) is a configuration file, a
traffic file, two ``layers/`` files and entries of BENCHMARK.json: the
ring at its source's size, the real cell rehearsed on four virtual CPU
devices at the source's height (five levels of interior nodes, four
shards), and the two metrics it brought over readers that were there."""

import json
import os

import pytest

from test_rehearsal import BENCH, ROOT, rehearse
from test_span_readers import WINDOW, facts, reader

CELL = "ycsb-a.ring256-n3-h5-mesh4"


def _json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def _cell():
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, entry, _json(ROOT, entry["file"])


def test_mesh_h5_deployment_is_the_sources_own_size():
    bench, cell, entry, cfg = _cell()
    h5 = _json(BENCH, "configs", "ring64-n3-h5.json")
    mesh = _json(BENCH, "configs", "ring40k-n5-mesh4.json")
    assert (cfg["n_ens"], cfg["n_peers"], cfg["n_slots"]) == (256, 3, 16 ** 5)
    assert cell["chips"] == cfg["chips"] == 4 and cfg["engine"] == "mesh"
    assert cfg["guarantees"] == h5["guarantees"]
    for key in ("record", "placement"):
        assert cfg["assumed"][key] == h5["assumed"][key]
    assert cfg["assumed"]["service"] == mesh["assumed"]["service"]
    assert {"ring_size", "layout"} <= set(cfg["assumed"])
    assert list(cfg["reduced"]) == entry["reduced"] == ["records_per_ens"]
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the last cell and the last configuration: nothing before them moved
    assert bench["workloads"][-1] is cell and bench["configs"][-1] is entry
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(
        bench["workloads"])
    # the same requests to the same number of keys as the one-chip h5 cell
    assert cfg["records_per_ens"] * cfg["n_ens"] == (
        h5["records_per_ens"] * h5["n_ens"])
    traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
    a = _json(BENCH, "traffic", "ycsb-a-h5-r2000.json")
    for key in ("readproportion", "updateproportion", "requestdistribution",
                "zipfianconstant", "fieldcount", "fieldlength", "arrivals",
                "connections", "drain_seconds", "warm_seconds"):
        assert traffic[key] == a[key], key
    assert traffic["rate"] <= 2000 and traffic["rate"] % 100 == 0
    assert cell["traffic"].endswith(f"-r{traffic['rate']}")
    # load, warm-up (every burst of the grid a write, three attempts
    # at the pile-ups and the steady phase) and window stay under
    # wal_compact_records: no run folds a 23.4 GB checkpoint
    grid = traffic["warm_grid"]
    bursts = [(d, w) for d in grid.get("depths", ())
              for w in grid.get("widths", ()) if d * w <= grid["max_ops"]]
    bursts += [tuple(b) for b in grid.get("bursts", ())]
    assert all(w <= cfg["n_ens"] for _, w in bursts)
    loaded = cfg["records_per_ens"] * cfg["n_ens"]
    walked = grid["rounds"] * sum(d * w for d, w in bursts)
    writes = (loaded + walked + 3 * sum(traffic["warm_pileups"]) // 2
              + (3 * traffic["warm_seconds"] + 45) * traffic["rate"] // 2)
    assert writes < 1 << 18, writes
    # the WAL store rewrites its snapshot every 65,536 appends
    # (native/treestore.cc): whichever warm-up attempts a run needs,
    # the one that follows the load's falls in the window's first
    # third, and no run reaches the next
    per_s = traffic["rate"] // 2
    least = loaded + walked + sum(traffic["warm_pileups"]) // 2 + (
        traffic["warm_seconds"] * per_s)
    most = loaded + walked + 3 * sum(traffic["warm_pileups"]) // 2 + (
        3 * traffic["warm_seconds"] * per_s)
    due = (most // 65536 + 1) * 65536
    assert least // 65536 == most // 65536
    assert 0 < (due - most) / per_s and (due - least) / per_s < 15
    assert due + 65536 == 1 << 18       # which no run reaches (above)


def test_mesh_h5_rehearsal_is_correct_over_five_levels_and_four_shards():
    import needs

    _, _, _, cfg = _cell()
    assert needs.tree_levels(cfg["n_slots"]) == 5
    assert needs.tree_levels(cfg["rehearse"]["n_slots"]) == 5
    assert cfg["rehearse"]["n_ens"] % 4 == 0
    by = rehearse(CELL, devices=4)
    assert by["serving"]["count"] == 4
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    g = by["checked"]["guarantees"]
    assert g.pop("tpu") is False and all(g.values())
    assert by["checked"]["keys_read_back"] > 0
    assert by["checked"]["processes_left"] == 0
    layer = by["per_layer"]
    assert layer["state_init_s"]["value"] > 0.0
    # two ensembles a shard: every launch the full-grid mesh step
    assert layer["sliced_launch_share"]["value"] == 0.0
    assert layer["rounds_per_flush"]["value"] >= 1.0
    # (a CPU keeps no allocator stats: `startup_device_peak_bytes` is
    # left out of a rehearsal's line, as of the parent's)
    assert "startup_device_peak_bytes" not in layer


@pytest.mark.parametrize("stats,expect", [
    ({"startup": {"state_init_s": 2.5, "device_peak_bytes": 5854312448,
                  "state_bytes_per_device": [5854312448] * 4}},
     (5854312448.0, 1)),
    ({"startup": {"state_init_s": 2.5}}, None),     # the parent, a CPU
    ({"startup": {"device_peak_bytes": None}}, None),
])
def test_startup_device_peak_bytes(stats, expect):
    spec = _json(BENCH, "layers", "startup_device_peak_bytes.json")
    f = facts([])
    f["dump"]["stats"] = stats
    assert reader(spec["reader"])(f, **spec["args"]) == expect


@pytest.mark.parametrize("records,expect", [
    ([dict(r, sliced=0) for r in WINDOW], (0.0, 9)),
    ([dict(r, sliced=i % 3 == 0) for i, r in enumerate(WINDOW)],
     (pytest.approx(1 / 3), 9)),
    (WINDOW, None),                 # a program whose records lack it
])
def test_sliced_launch_share(records, expect):
    spec = _json(BENCH, "layers", "sliced_launch_share.json")
    assert reader(spec["reader"])(facts(records), **spec["args"]) == expect
