"""Tier-1 rehearses the benchmark every PR is judged by.

Each cell of ``BENCHMARK.json`` runs once through ``benchmarks/run.py
--rehearse`` on the CPU (cut shapes, no look for a chip, exit 3): the
whole served path, the load generator, the read-back and the checker.
A renamed ``stats()`` key, a changed ``svcnode.serve`` signature, a dump
field the checker reads or a per-layer metric that stops being printed
fails HERE and not on the chip.  Two of the rehearsal's controls show
the other half: a run whose guarantees are broken underneath comes out
not correct.  Nothing here is a time or a rate."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONTROL_CELL = "ycsb-a.ring10k-n5"

#: per-layer metrics a ``--trace 0`` rehearsal is known not to print,
#: by cell (``None``: every cell), each with its reason.  A metric
#: outside this table that goes silent fails the cell's case.
KNOWN_ABSENT = {
    # nothing records the span since PR 46 folded the pack into the
    # step program; the next `benchmark` PR drops the entry and this row
    "dispatch_pack_p50_ms": None,
    # a CPU client reports no `peak_bytes_in_use`
    "startup_device_peak_bytes": None,
    # the rehearsal cuts these rings to 2 and 8 ensembles, no more than
    # the narrowest bucket (A_BUCKET_MIN), so every launch runs the
    # full grid: its `a` is 0 and there is no block to pad
    "launch_pad_share": ("ycsb-a.ring64-n3-h5",
                         "ycsb-a.ring256-n3-h5-mesh4"),
}


def rehearse(cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env.pop("XLA_FLAGS", None)
    if CELLS[cell]["chips"] > 1:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{CELLS[cell]['chips']}")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000017", "--seconds", "4",
         "--trace", "0", "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3, proc.stderr[-2000:]
    return {x["what"]: x for x in map(json.loads, filter(
        lambda ln: ln.startswith("{"), proc.stdout.splitlines()))}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearses_correct_but_for_the_device(cell):
    by = rehearse(cell)
    assert by["rehearsed"]["correct_but_for_the_device"] is True
    guarantees = dict(by["checked"]["guarantees"])
    assert guarantees.pop("tpu") is False and all(guarantees.values())
    assert by["checked"]["keys_read_back"] > 0
    assert by["checked"]["reads_checked"] > 0
    assert {m["name"] for m in BENCH["end_to_end"]} <= set(by["end_to_end"])
    owed = {m["name"] for m in BENCH["per_layer"]
            if m["source"] != "device_trace"
            and cell in m.get("workloads", (cell,))}
    absent = {name for name, cells in KNOWN_ABSENT.items()
              if cells is None or cell in cells}
    assert owed - set(by["per_layer"]) == absent & owed, (
        "per-layer metrics gone silent, or a KNOWN_ABSENT row to drop")


@pytest.mark.parametrize("control,caught", [
    ("lost_write", lambda by: not by["rehearsed"][
        "correct_but_for_the_device"] and any(
            c["name"] == "lost_writes" and c["value"] > 0
            for c in by["checked"]["compared"])),
    ("wal_buffer", lambda by: by["checked"]["guarantees"][
        "wal_fsync"] is False),
], ids=["lost_write", "wal_buffer"])
def test_broken_guarantee_reaches_the_checker(control, caught):
    assert caught(rehearse(CONTROL_CELL, "--control", control))
