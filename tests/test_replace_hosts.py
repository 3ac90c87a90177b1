"""Dynamic replication-group host membership (review r4 missing #1).

The reference reconfigures an ensemble's member set across machines at
runtime via joint consensus — add/remove/replace with multi-view
quorums until collapse (riak_ensemble_peer.erl:655-672 update_members,
:751-774 transition; acceptance shape: test/replace_members_test.erl
replacing root/2/3 -> 4/5/6).  These tests drive the host-granularity
analog on :mod:`riak_ensemble_tpu.parallel.repgroup`:

- grow a 3-host group to 5 LIVE under client load (zero failed acks),
- replace a kill -9'd host with a fresh blank one, zero acked-write
  loss, with the joiner proven to carry a quorum afterwards,
- a linearizability sweep green across the transition window,
- ``update_members`` on a repgroup no longer raises.
"""

import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import conftest  # noqa: F401

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.linearizability import KeyModel  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import WallRuntime  # noqa: E402
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402

N_ENS = 4
#: generous: the phase writes allocate ~12 distinct keys per ensemble
N_SLOTS = 32

#: the in-process leader's identity in member lists (a pure identity:
#: replicas only dial it for failover ranking, which these tests don't
#: enable)
LEADER_ADDR = ("leader.test", 1)


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


def _make_leader(tmp_path, repl_ports, ack_timeout=15.0):
    svc = repgroup.ReplicatedService(
        WallRuntime(), N_ENS, 1, N_SLOTS, group_size=3,
        peers=[("127.0.0.1", p) for p in repl_ports],
        ack_timeout=ack_timeout, config=fast_test_config(),
        data_dir=str(tmp_path / "leader"), self_addr=LEADER_ADDR)
    repgroup.warmup_kernels(svc)
    assert svc.takeover(), "takeover needs a majority of replicas"
    return svc


def _settle(svc, futs, flushes=8):
    for _ in range(flushes):
        if all(f.done for f in futs):
            break
        svc.flush()
    assert all(f.done for f in futs)
    return [f.value for f in futs]


def _drive_until(svc, cond, deadline=120.0, what="condition"):
    end = time.monotonic() + deadline
    while time.monotonic() < end:
        svc.heartbeat()
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"{what} never reached: "
                         f"{svc.membership_status()} / "
                         f"{svc.stats()['group']}")


def _collapsed_to(svc, hosts):
    def cond():
        st = svc.membership_status()
        return (not st["transition"] and st["joint"] is None
                and st["hosts"] is not None
                and set(map(tuple, st["hosts"])) == set(hosts))
    return cond


def _synced(svc, n):
    return lambda: svc.stats()["group"]["peers_synced"] >= n


def _kill(procs, name):
    p = procs[name][0]
    if p.poll() is None:
        p.send_signal(signal.SIGKILL)
        p.wait()


def test_grow_3_to_5_live_under_load(tmp_path):
    """Grow the host set 3 -> 5 while clients keep writing: no failed
    acks through the transition, both joiners sync and are counted —
    proven by killing BOTH original replicas afterwards (the remaining
    leader + 2 joiners are a majority of 5 only if the joiners carry
    full state) — and zero acked writes lost."""
    procs, dirs = {}, {}
    try:
        for name in ("r1", "r2"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        svc = _make_leader(tmp_path,
                           [procs["r1"][1], procs["r2"][1]])
        acked = {}

        def put_ok(phase, n=8):
            futs = []
            for i in range(n):
                e, key = i % N_ENS, f"{phase}-{i}"
                val = b"%s/%d" % (phase.encode(), i)
                futs.append((e, key, val, svc.kput(e, key, val)))
            _settle(svc, [f for *_, f in futs])
            for e, key, val, f in futs:
                assert f.value[0] == "ok", (phase, key, f.value)
                acked[(e, key)] = val

        put_ok("pre")

        for name in ("r3", "r4"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        old = [LEADER_ADDR, ("127.0.0.1", procs["r1"][1]),
               ("127.0.0.1", procs["r2"][1])]
        new = old + [("127.0.0.1", procs["r3"][1]),
                     ("127.0.0.1", procs["r4"][1])]
        svc.update_members(new)

        # client load DURING the transition: every ack must be real
        for wave in range(6):
            put_ok(f"mid{wave}", n=4)
        _drive_until(svc, _collapsed_to(svc, new), what="collapse")
        assert svc.stats()["group"]["quorum_failures"] == 0, \
            svc.stats()["group"]
        put_ok("post")
        _drive_until(svc, _synced(svc, 4), what="4 peers synced")

        # the joiners are REAL members: kill both original replicas —
        # leader + r3 + r4 is a majority of 5 only with synced joiners
        _kill(procs, "r1")
        _kill(procs, "r2")
        put_ok("final")
        futs = [(e, key, val, svc.kget(e, key))
                for (e, key), val in acked.items()]
        _settle(svc, [f for *_, f in futs], flushes=12)
        for e, key, val, f in futs:
            assert f.value == ("ok", val), \
                f"acked write lost at {(e, key)}: {f.value!r}"
        assert svc.group_size == 5
        svc.stop()
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()


def test_replace_dead_host_with_blank(tmp_path):
    """Replace a kill -9'd host with a fresh blank one: the transition
    commits on old/new majorities that never include the dead host,
    the blank joiner instals the full state before being counted, and
    after collapse it carries the quorum (the other replica killed) —
    zero acked-write loss end to end.  The acceptance shape of
    replace_members_test.erl at host granularity."""
    procs, dirs = {}, {}
    try:
        for name in ("r1", "r2"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        svc = _make_leader(tmp_path,
                           [procs["r1"][1], procs["r2"][1]])
        acked = {}

        def put_ok(phase, n=6):
            futs = []
            for i in range(n):
                e, key = i % N_ENS, f"{phase}-{i}"
                val = b"%s/%d" % (phase.encode(), i)
                futs.append((e, key, val, svc.kput(e, key, val)))
            _settle(svc, [f for *_, f in futs])
            for e, key, val, f in futs:
                assert f.value[0] == "ok", (phase, key, f.value)
                acked[(e, key)] = val

        put_ok("pre")
        _kill(procs, "r2")
        put_ok("one-down")  # 2/3 majority still commits

        dirs["r3"] = str(tmp_path / "r3")
        procs["r3"] = _spawn_replica(dirs["r3"])  # blank
        new = [LEADER_ADDR, ("127.0.0.1", procs["r1"][1]),
               ("127.0.0.1", procs["r3"][1])]
        svc.update_members(new)
        put_ok("during")
        _drive_until(svc, _collapsed_to(svc, new), what="collapse")
        _drive_until(svc, _synced(svc, 2), what="r1+r3 synced")
        put_ok("post")

        # the blank joiner now carries the quorum on its own
        _kill(procs, "r1")
        put_ok("final")
        futs = [(e, key, val, svc.kget(e, key))
                for (e, key), val in acked.items()]
        _settle(svc, [f for *_, f in futs], flushes=12)
        for e, key, val, f in futs:
            assert f.value == ("ok", val), \
                f"acked write lost at {(e, key)}: {f.value!r}"
        st = svc.membership_status()
        assert ("127.0.0.1", procs["r2"][1]) not in set(
            map(tuple, st["hosts"]))
        svc.stop()
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()


@pytest.mark.parametrize("seed", conftest.soak_seeds([2201]))
def test_linearizable_across_membership_transition(tmp_path, seed):
    """sc.erl across the transition window: random put/get load runs
    while the group grows 3 -> 5; every acked write must be readable
    afterwards (KeyModel raises Violation on lost/stale values);
    host-quorum 'failed' writes stay ambiguous via timeout_write."""
    rng = np.random.default_rng(seed)
    procs, dirs = {}, {}
    models = {}
    vals = iter(range(1, 100000))

    def model(e, k):
        return models.setdefault((e, k), KeyModel(f"{e}/k{k}"))

    try:
        for name in ("r1", "r2"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        svc = _make_leader(tmp_path,
                           [procs["r1"][1], procs["r2"][1]],
                           ack_timeout=6.0)
        started = False
        new = None
        for rnd in range(10):
            if rnd == 3:  # mid-run: start the grow transition
                for name in ("r3", "r4"):
                    dirs[name] = str(tmp_path / name)
                    procs[name] = _spawn_replica(dirs[name])
                new = [LEADER_ADDR,
                       ("127.0.0.1", procs["r1"][1]),
                       ("127.0.0.1", procs["r2"][1]),
                       ("127.0.0.1", procs["r3"][1]),
                       ("127.0.0.1", procs["r4"][1])]
                svc.update_members(new)
                started = True
            pending = []
            for _ in range(6):
                e = int(rng.integers(N_ENS))
                k = int(rng.integers(3))
                m = model(e, k)
                if rng.random() < 0.6:
                    v = next(vals)
                    op = m.invoke_write(v)
                    pending.append(("put", m, op,
                                    svc.kput(e, f"k{k}",
                                             v.to_bytes(4, "big"))))
                else:
                    pending.append(("get", m, None,
                                    svc.kget(e, f"k{k}")))
            for _ in range(10):
                if all(f.done for *_, f in pending):
                    break
                svc.flush()
            for kind, m, op, f in pending:
                assert f.done
                res = f.value
                if kind == "put":
                    if isinstance(res, tuple) and res[0] == "ok":
                        m.ack_write(op)
                    else:
                        m.timeout_write(op)
                else:
                    if isinstance(res, tuple) and res[0] == "ok":
                        v = res[1]
                        m.ack_read(v if v is NOTFOUND
                                   else int.from_bytes(v, "big"))
        assert started
        _drive_until(svc, _collapsed_to(svc, new), what="collapse")
        # read back every key through the post-transition group
        pending = [(m, svc.kget(e, f"k{k}"))
                   for (e, k), m in models.items()]
        for _ in range(12):
            if all(f.done for _, f in pending):
                break
            svc.flush()
        for m, f in pending:
            assert f.done and isinstance(f.value, tuple) \
                and f.value[0] == "ok", f.value
            v = f.value[1]
            m.ack_read(v if v is NOTFOUND
                       else int.from_bytes(v, "big"))
        svc.stop()
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()


def test_update_members_forms(tmp_path):
    """update_members on a repgroup no longer raises: the host form
    runs the transition machinery (no-op when the set is unchanged);
    the two-arg per-ensemble view form still works in single-lane
    mode and raises a TYPED, documented error on a real group."""
    rt = Runtime(seed=3)
    solo = repgroup.ReplicatedService(
        rt, N_ENS, 1, N_SLOTS, group_size=1,
        config=fast_test_config(), self_addr=("solo.test", 1))
    # two-arg view form delegates to the base class in single-lane mode
    sel = np.zeros((N_ENS,), bool)
    view = np.ones((N_ENS, 1), bool)
    solo.update_members(sel, view)  # no raise
    # host form: unchanged set is a no-op (requires leadership)
    solo._is_leader = True
    solo.update_members([("solo.test", 1)])
    assert solo.membership_status()["transition"] is False
    solo.stop()


def test_leader_transitions_itself_out(tmp_path):
    """The reference peer shuts down when it is not a member of the
    final view (transition, peer.erl:756-774): a leader may run a
    transition that REMOVES ITSELF.  The collapse commits under the
    joint rule, the ex-leader steps down (deposed), and a remaining
    member promotes and serves every acked write."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    procs, dirs = {}, {}
    try:
        for name in ("r1", "r2"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        svc = _make_leader(tmp_path,
                           [procs["r1"][1], procs["r2"][1]])
        acked = {}
        futs = []
        for i in range(8):
            e, key, val = i % N_ENS, f"k{i}", b"v%d" % i
            futs.append(svc.kput(e, key, val))
            acked[(e, key)] = val
        _settle(svc, futs)
        assert all(f.value[0] == "ok" for f in futs)

        # transition the leader OUT: new set = the two replicas only
        new = [("127.0.0.1", procs["r1"][1]),
               ("127.0.0.1", procs["r2"][1])]
        svc.update_members(new)
        try:
            _drive_until(svc, lambda: svc._deposed,
                         what="ex-member leader step-down")
        except repgroup.DeposedError:
            pass  # the step-down landed between cond checks
        assert svc._deposed, "ex-member leader never stepped down"
        st = svc.membership_status()
        assert st["joint"] is None and \
            set(map(tuple, st["hosts"])) == set(new), st

        # a remaining member promotes under the 2-host config and
        # serves every acked write
        r1_repl, r1_client = procs["r1"][1], procs["r1"][2]
        with socket.create_connection(
                ("127.0.0.1", r1_repl), timeout=120.0) as s:
            s.settimeout(120.0)
            repgroup.send_frame(
                s, ("promote", [("127.0.0.1", procs["r2"][1])]))
            resp = repgroup.recv_frame(s)
        assert resp[0] == "ok", resp

        async def read_all():
            c = svcnode.ServiceClient("127.0.0.1", r1_client)
            await c.connect()
            for (e, key), val in acked.items():
                r = await c.kget(e, key, timeout=120.0)
                assert r == ("ok", val), (key, r)
            r = await c.kput(0, "post", b"new", timeout=120.0)
            assert r[0] == "ok", r
            await c.close()
        asyncio.run(read_all())
        svc.stop()
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()
