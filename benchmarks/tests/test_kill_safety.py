"""No process a run starts outlives it, however ``run.py`` ends: by
itself, by SIGTERM, SIGINT or SIGHUP (one ``cut`` line, exit 128 + the
signal), or by SIGKILL, which runs no handler at all (the kernel ends
``server.py`` and, in a fresh checkout, the ``make`` of ``native/`` with
its compilers).  On a CPU, ``run.py --rehearse``."""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

import run as bench_run
from test_rehearsal import ROOT, rehearse

CELL = "ycsb-a.ring10k-n5"
GRACE_S = 1.0


def tagged(run_pid):
    """{pid: command line} of the live processes that ``run.py`` number
    ``run_pid`` started (they carry its tag in their environment)."""
    out = {}
    for pid in bench_run.tagged(f"{run_pid}."):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out[pid] = f.read().replace(b"\0", b" ").decode()
        except OSError:
            continue
    return out


def start(root=ROOT, seed=3_000_000_023):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.Popen(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "4",
         "--trace", "0", "--rehearse"],
        cwd=root, env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True)     # a stray group kill stays off pytest


def read_until(proc, what):
    """run.py's lines up to and with the first ``what``."""
    lines = []
    for raw in proc.stdout:
        if raw.startswith("{"):
            lines.append(json.loads(raw))
            if lines[-1].get("what") == what:
                return lines
    raise AssertionError(f"run.py ended before {what!r}: {lines[-3:]}")


def end_and_look(proc, sig):
    """End ``run.py`` with ``sig``; what it still said, its exit code,
    and what of its processes lives ``GRACE_S`` later."""
    os.kill(proc.pid, sig)
    rest = [json.loads(x) for x in proc.stdout if x.startswith("{")]
    code = proc.wait(timeout=60)
    time.sleep(GRACE_S)
    left = tagged(proc.pid)
    for pid in left:                # leave nothing behind a failure
        os.kill(pid, signal.SIGKILL)
    return rest, code, left


@pytest.mark.parametrize("sig,phase", [
    (signal.SIGTERM, "serving"),
    (signal.SIGINT, "serving"),
    (signal.SIGHUP, "loaded"),
    (signal.SIGKILL, "serving"),
    (signal.SIGKILL, "measuring"),
])
def test_no_server_outlives_a_run_ended_from_outside(sig, phase):
    proc = start()
    try:
        said = read_until(proc, phase)
        children = tagged(proc.pid)
        assert any("server.py" in c and f"--parent-pid {proc.pid}" in c
                   for c in children.values()), children
        rest, code, left = end_and_look(proc, sig)
    finally:
        proc.kill()
    assert left == {}
    if sig == signal.SIGKILL:
        assert code == -signal.SIGKILL
        assert not any(x.get("what") == "cut" for x in rest)
        return
    assert code == 128 + sig
    cut = rest[-1]
    assert cut["what"] == "cut" and cut["cell"] == CELL
    assert cut["signal"] == sig.name and cut["signum"] == int(sig)
    # the run said nothing between the phase waited for and the cut
    # (the load and the warm-up's first burst take longer than that),
    # or it names a later phase: never an earlier one
    later = [x["what"] for x in rest[:-1]]
    assert cut["phase"].split()[0] == (later[-1] if later else phase)
    assert cut["seconds_since_start"] > 0.0
    assert cut["platform"] == said[-1]["platform"]


@pytest.mark.parametrize("sig", [signal.SIGKILL, signal.SIGTERM])
def test_no_compiler_outlives_a_run_cut_in_its_native_build(sig, tmp_path):
    """A fresh checkout builds ``native/`` in its first seconds: a run
    cut there leaves no ``make`` and no compiler either."""
    root = str(tmp_path / "checkout")
    for name in ("benchmarks", "riak_ensemble_tpu", "native"):
        shutil.copytree(
            os.path.join(ROOT, name), os.path.join(root, name),
            ignore=shutil.ignore_patterns("*.so", "__pycache__", "tests",
                                          "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    proc = start(root)
    try:
        deadline = time.monotonic() + 120
        compiling = {}
        while not compiling and time.monotonic() < deadline:
            assert proc.poll() is None, "run.py ended before the build"
            compiling = {p: c for p, c in tagged(proc.pid).items()
                         if c.split()[0].rsplit("/", 1)[-1]
                         in ("cc1plus", "g++", "c++", "cc1")}
            time.sleep(0.02)
        assert compiling, "no compiler seen"
        assert any("make" in c for c in tagged(proc.pid).values())
        _, code, left = end_and_look(proc, sig)
    finally:
        proc.kill()
    assert left == {}
    assert code == (-sig if sig == signal.SIGKILL else 128 + sig)
    assert not os.listdir(os.path.join(root, "native")) \
        or not [n for n in os.listdir(os.path.join(root, "native"))
                if n.endswith(".so")]


def test_a_finished_run_reports_processes_left_0():
    by = rehearse(CELL)
    assert by["checked"]["processes_left"] == 0
    assert by["measuring"]["setup_s"] == \
        by["end_to_end"]["setup_s"]["value"]


def test_a_leftover_is_found_killed_and_counted():
    args = argparse.Namespace(benchmark=None, workload=CELL,
                              rehearse=True, rate=0.0, set=None)
    run = bench_run.Run(args)
    assert run.started() == [] and run.end_started() == 0
    stray = subprocess.Popen(
        ["sleep", "60"], env=dict(os.environ,
                                  **{bench_run.RUN_TAG: run.tag}))
    other = subprocess.Popen(
        ["sleep", "60"], env=dict(os.environ,
                                  **{bench_run.RUN_TAG: "0" + run.tag}))
    try:
        assert run.started() == [stray.pid]
        assert run.end_started() == 1
        assert stray.wait(timeout=10) == -signal.SIGKILL
        assert run.started() == []
        assert other.poll() is None         # another run's: not touched
    finally:
        stray.kill()
        other.kill()
        other.wait()
