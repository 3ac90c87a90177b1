"""``run.py --rehearse`` end to end on the CPU: every phase of a run
with the look for a chip skipped.  Sound, it ends with every comparison
inside its limit; with the timed path broken underneath (an answer
altered where it is produced, an acknowledged write undone, the WAL not
synced, a native half replaced by its Python fallback) the same run
comes out not correct.  And the four-device mesh cell is files and an
entry, not a code path."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DEVICE_METRICS = ("device_idle_share", "fused_step_roofline")


def rehearse(cell, *extra, seed=3_000_000_017, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    if devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", "4", "--trace", "1",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert lines and all(x.get("rehearsal") is True and
                         x.get("platform") == "cpu" for x in lines)
    # no result line in the contract's form, no device metric
    assert not any("correct" in x and "metrics" in x for x in lines)
    by = {x["what"]: x for x in lines}
    by["warmed_all"] = [x for x in lines if x["what"] == "warmed"]
    assert not any(m in by["per_layer"] for m in DEVICE_METRICS)
    assert {k for k in by["end_to_end"] if k.endswith(("_ms", "_s"))} == {
        "update_p50_ms", "read_p50_ms", "committed_ops_per_s", "setup_s"}
    assert {"update_p95_ms", "read_p95_ms"} <= set(by["per_layer"])
    return by


def compared(by):
    return {c["name"]: c["value"] for c in by["checked"]["compared"]}


@pytest.mark.parametrize("cell", ["ycsb-a.ring10k-n5",
                                  "ycsb-a.ring64-n3-deep"])
def test_sound_rehearsal_is_correct_but_for_the_device(cell):
    by = rehearse(cell)
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    assert not by["checked"]["correct"]          # a CPU is never correct
    g = by["checked"]["guarantees"]
    assert g.pop("tpu") is False and all(g.values())
    assert by["checked"]["keys_read_back"] > 0
    assert by["checked"]["reads_checked"] > 0


@pytest.mark.parametrize("control,number", [
    ("stale_read", "stale_reads"),
    ("lost_write", "lost_writes"),
])
def test_broken_timed_path_is_not_correct(control, number):
    by = rehearse("ycsb-a.ring10k-n5", "--control", control)
    assert by["rehearsed"]["correct_but_for_the_device"] is False
    assert compared(by)[number] > 0


@pytest.mark.parametrize("control,guarantee", [
    ("wal_buffer", "wal_fsync"),
    ("python_resolve", "native_resolve"),
])
def test_weaker_guarantee_is_not_correct(control, guarantee):
    by = rehearse("ycsb-a.ring10k-n5", "--control", control)
    assert by["checked"]["guarantees"][guarantee] is False
    assert not by["checked"]["correct"]


def test_mesh_deployment_is_a_file():
    """``ycsb-a.ring40k-n5-mesh4`` (engine mesh, chips 4) is a
    configuration file, a traffic file and an entry of BENCHMARK.json:
    the real cell, rehearsed on four virtual devices."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["chips"] == 4)
    assert cell["name"] == "ycsb-a.ring40k-n5-mesh4"
    assert (cell["config"], cell["traffic"]) == ("ring40k-n5-mesh4",
                                                 "ycsb-a-r400")
    by = rehearse(cell["name"], devices=4)
    assert by["serving"]["count"] == 4
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    assert by["checked"]["processes_left"] == 0
    assert by["per_layer"]["rounds_per_flush"]["value"] >= 1.0
    grid = next(x for x in by["warmed_all"] if x["phase"] == "grid 0")
    assert set(grid["first_met_by_fn"]) <= {"step", "pack"}
