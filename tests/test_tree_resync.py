"""Incremental (Merkle) catch-up for diverged repgroup replicas
(review r4 missing #3).

The reference heals peer divergence by tree exchange — cost
O(width·height·diffs), never O(keys) (synctree.erl:372-417,
riak_ensemble_exchange.erl:67-98).  Round 4's repgroup healed by full
snapshot install (every engine array + host mirror shipped per
re-sync).  These tests prove the round-5 tree-diff path:

- a restarted (briefly-dead) replica heals via the targeted patch,
  with measured re-sync bytes scaling with the DIFF, not the state,
- the healed replica then carries a quorum alone (zero acked loss),
- heavy divergence (a blank disk) falls back to the full snapshot.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import conftest  # noqa: F401

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import wire  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import WallRuntime  # noqa: E402

N_ENS = 8
N_SLOTS = 32


def _spawn_replica(data_dir: str, repl_port: int = 0,
                   client_port: int = 0):
    child = textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        from riak_ensemble_tpu.parallel import repgroup
        repgroup.main(["--n-ens", "{N_ENS}", "--group-size", "3",
                       "--n-slots", "{N_SLOTS}", "--fast",
                       "--repl-port", "{repl_port}",
                       "--client-port", "{client_port}",
                       "--data-dir", {data_dir!r}])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", child],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, env=env)
    line = p.stdout.readline()
    assert line, p.stderr.read()[-3000:]
    parts = dict(kv.split("=") for kv in line.split()[2:])
    return p, int(parts["repl"]), int(parts["client"])


def _make_leader(tmp_path, repl_ports):
    svc = repgroup.ReplicatedService(
        WallRuntime(), N_ENS, 1, N_SLOTS, group_size=3,
        peers=[("127.0.0.1", p) for p in repl_ports],
        ack_timeout=15.0, config=fast_test_config(),
        data_dir=str(tmp_path / "leader"))
    repgroup.warmup_kernels(svc)
    assert svc.takeover()
    return svc


def _settle(svc, futs, flushes=10):
    for _ in range(flushes):
        if all(f.done for f in futs):
            break
        svc.flush()
    assert all(f.done for f in futs)
    return [f.value for f in futs]


def _wait_synced(svc, n, deadline=120.0):
    end = time.monotonic() + deadline
    while time.monotonic() < end:
        svc.heartbeat()
        if svc.stats()["group"]["peers_synced"] >= n:
            return
        time.sleep(0.1)
    raise AssertionError(
        f"peers never re-synced: {svc.stats()['group']}")


def test_restarted_replica_heals_by_tree_patch(tmp_path):
    procs, dirs = {}, {}
    try:
        for name in ("r1", "r2"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        svc = _make_leader(tmp_path,
                           [procs["r1"][1], procs["r2"][1]])
        acked = {}

        def put_ok(phase, n, size=200):
            futs = []
            for i in range(n):
                e, key = i % N_ENS, f"{phase}-{i}"
                val = (b"%s/%d/" % (phase.encode(), i)).ljust(size,
                                                             b"x")
                futs.append((e, key, val, svc.kput(e, key, val)))
            _settle(svc, [f for *_, f in futs])
            for e, key, val, f in futs:
                assert f.value[0] == "ok", (phase, key, f.value)
                acked[(e, key)] = val

        # a meaty base state, fully replicated
        put_ok("base", 48)
        _wait_synced(svc, 2)
        base_stats = dict(svc.stats()["group"])

        # kill r1, advance the group by a FEW slots (>= 2 flushes so
        # the restarted replica is strictly behind and freezes)
        p1 = procs["r1"][0]
        p1.send_signal(signal.SIGKILL)
        p1.wait()
        put_ok("gap-a", 2)
        put_ok("gap-b", 2)

        # restart r1 from its data_dir: catch-up must take the TREE
        # path, and its traffic must scale with the 4-slot diff, not
        # the 52-key state
        _, repl, client = procs["r1"]
        procs["r1"] = _spawn_replica(dirs["r1"], repl_port=repl,
                                     client_port=client)
        _wait_synced(svc, 2)
        g = svc.stats()["group"]
        assert g["tree_resyncs"] >= base_stats["tree_resyncs"] + 1, g
        full_bytes = len(wire.encode(
            ("install", 0, 0, repgroup.dump_state(svc),
             svc.core.cfg)))
        patch_bytes = (g["tree_resync_bytes"]
                       - base_stats["tree_resync_bytes"])
        assert 0 < patch_bytes < full_bytes / 3, \
            (patch_bytes, full_bytes)

        # the healed replica carries the quorum alone: kill r2
        p2 = procs["r2"][0]
        p2.send_signal(signal.SIGKILL)
        p2.wait()
        put_ok("post", 4)
        futs = [(e, key, val, svc.kget(e, key))
                for (e, key), val in acked.items()]
        _settle(svc, [f for *_, f in futs], flushes=14)
        for e, key, val, f in futs:
            assert f.value == ("ok", val), \
                f"acked write lost at {(e, key)}: {f.value!r}"
        assert svc.stats()["group"]["quorum_failures"] == 0
        svc.stop()
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()


def test_blank_disk_falls_back_to_full_install(tmp_path):
    """A replacement host with an empty disk diverges in (almost)
    every ensemble: the probe's >50%-diff gate must route it to the
    full snapshot — the tree path is an optimization, never the only
    door."""
    import shutil

    procs, dirs = {}, {}
    try:
        for name in ("r1", "r2"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        svc = _make_leader(tmp_path,
                           [procs["r1"][1], procs["r2"][1]])
        futs = [svc.kput(e, f"k{i}", b"v%d" % i)
                for i in range(2 * N_ENS) for e in [i % N_ENS]]
        _settle(svc, futs)
        assert all(f.value[0] == "ok" for f in futs)
        _wait_synced(svc, 2)
        before = dict(svc.stats()["group"])

        # kill r1, WIPE its disk, advance, restart blank on its ports
        p1 = procs["r1"][0]
        p1.send_signal(signal.SIGKILL)
        p1.wait()
        shutil.rmtree(dirs["r1"])
        _settle(svc, [svc.kput(0, "extra", b"x")])
        _settle(svc, [svc.kput(1, "extra", b"x")])
        _, repl, client = procs["r1"]
        procs["r1"] = _spawn_replica(dirs["r1"], repl_port=repl,
                                     client_port=client)
        _wait_synced(svc, 2)
        g = svc.stats()["group"]
        assert g["resyncs"] > before["resyncs"], (before, g)
        assert g["tree_resyncs"] == before["tree_resyncs"], \
            (before, g)
        svc.stop()
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()
