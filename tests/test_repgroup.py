"""Replica quorum across OS-process failure domains (review r3 #1).

The reference survives machine death because every commit's quorum
crosses node boundaries (riak_ensemble_msg.erl:132-142;
doc/Readme.md:49-63).  These tests drive the scale-path analog —
:mod:`riak_ensemble_tpu.parallel.repgroup` — with REAL kill -9 and
SIGSTOP against replica host processes:

- commits keep succeeding while a replica host is dead,
- zero acked writes are lost (read-back after failover sweeps), and
- a restarted host catches up (snapshot re-sync) and then carries a
  quorum on its own,
- a superseded leader is fenced (the sc.erl partition premise,
  test/sc.erl:1012-1036).
"""

import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

import conftest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.linearizability import (  # noqa: E402
    KeyModel, Violation)
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import WallRuntime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402

N_ENS = 4
N_SLOTS = 8
GROUP = 3


def _free_port() -> int:
    with socket.create_server(("127.0.0.1", 0)) as s:
        return s.getsockname()[1]


def _spawn_replica(data_dir: str, repl_port: int = 0,
                   client_port: int = 0, extra=()):
    """One replica host process (CPU-pinned child: a replica process
    must never initialise an accelerator its launcher may hold).  A
    RESTART must reuse its old ports — the leader's links keep dialing the
    address a host registered with, exactly like a rebooted machine
    keeping its hostname."""
    child = textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        from riak_ensemble_tpu.parallel import repgroup
        repgroup.main(["--n-ens", "{N_ENS}", "--group-size", "{GROUP}",
                       "--n-slots", "{N_SLOTS}", "--fast",
                       "--repl-port", "{repl_port}",
                       "--client-port", "{client_port}",
                       "--data-dir", {data_dir!r}] + {list(extra)!r})
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", child],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, env=env)
    line = p.stdout.readline()
    assert line, p.stderr.read()[-3000:]
    parts = dict(kv.split("=") for kv in line.split()[2:])
    return p, int(parts["repl"]), int(parts["client"])


def _restart(procs, dirs, name):
    """Restart a dead replica on ITS OWN ports + data_dir."""
    _, repl, client = procs[name]
    procs[name] = _spawn_replica(dirs[name], repl_port=repl,
                                 client_port=client)
    return procs[name]


def _make_leader(tmp_path, repl_ports, ack_timeout=15.0):
    svc = repgroup.ReplicatedService(
        WallRuntime(), N_ENS, 1, N_SLOTS, group_size=GROUP,
        peers=[("127.0.0.1", p) for p in repl_ports],
        ack_timeout=ack_timeout, config=fast_test_config(),
        data_dir=str(tmp_path / "leader"))
    repgroup.warmup_kernels(svc)
    assert svc.takeover(), "takeover needs a majority of replicas"
    return svc


def _settle(svc, futs, flushes=5):
    for _ in range(flushes):
        if all(f.done for f in futs):
            break
        svc.flush()
    assert all(f.done for f in futs)
    return [f.value for f in futs]


def _control(port: int, frame, timeout=120.0):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        repgroup.send_frame(s, frame)
        return repgroup.recv_frame(s)


def _wait_synced(svc, n, deadline=60.0):
    """Heartbeat until n peers are connected AND re-synced (an idle
    leader drives liveness through empty applies)."""
    end = time.monotonic() + deadline
    while time.monotonic() < end:
        svc.heartbeat()
        g = svc.stats()["group"]
        if g["peers_synced"] >= n:
            return
        time.sleep(0.1)
    raise AssertionError(f"peers never re-synced: {svc.stats()['group']}")


@pytest.fixture
def group(tmp_path):
    procs = {}
    dirs = {}
    for name in ("r1", "r2"):
        dirs[name] = str(tmp_path / name)
        procs[name] = _spawn_replica(dirs[name])
    svc = _make_leader(tmp_path, [procs["r1"][1], procs["r2"][1]])
    yield svc, procs, dirs, tmp_path
    svc.stop()
    for p, _, _ in procs.values():
        if p.poll() is None:
            p.kill()


def test_replica_kill9_commits_continue_and_restart_catches_up(group):
    """THE verdict r3 #1 criterion: (a) kill -9 one of three replica
    hosts mid-load and commits keep succeeding without it, (b) zero
    acked writes lost, (c) the restarted host catches up — proven by
    then killing the OTHER replica, so the restarted one must carry
    the quorum (and hold every acked write) itself."""
    svc, procs, dirs, tmp_path = group
    acked = {}

    def put_ok(phase, n=6):
        futs = []
        for i in range(n):
            e = i % N_ENS
            key = f"{phase}-{i}"
            futs.append((e, key, b"%s/%d" % (phase.encode(), i),
                         svc.kput(e, key, b"%s/%d" % (phase.encode(),
                                                      i))))
        _settle(svc, [f for *_, f in futs])
        for e, key, val, f in futs:
            assert f.value[0] == "ok", (phase, key, f.value)
            acked[(e, key)] = val

    put_ok("pre")

    # -- (a) kill -9 replica 1 mid-load: commits keep succeeding ------
    p1, p1_repl, _ = procs["r1"]
    p1.send_signal(signal.SIGKILL)
    p1.wait()
    put_ok("during")
    g = svc.stats()["group"]
    assert g["quorum_failures"] == 0, g

    # -- (c) restart replica 1 from its data_dir: leader re-syncs -----
    _restart(procs, dirs, "r1")
    _wait_synced(svc, 2)

    # -- now kill replica 2: the restarted host must carry the quorum
    p2, _, _ = procs["r2"]
    p2.send_signal(signal.SIGKILL)
    p2.wait()
    put_ok("after")

    # -- (b) zero acked writes lost: every acked key reads back -------
    futs = [(e, key, val, svc.kget(e, key))
            for (e, key), val in acked.items()]
    _settle(svc, [f for *_, f in futs])
    for e, key, val, f in futs:
        assert f.value == ("ok", val), \
            f"acked write lost at {(e, key)}: {f.value!r}"


def test_no_host_quorum_fails_ops_never_false_acks(group):
    """With both replicas dead the leader alone is a minority: every
    op must resolve 'failed' (never a false ack), and service resumes
    once a replica returns."""
    svc, procs, dirs, _ = group
    _settle(svc, [svc.kput(0, "k", b"v")])

    for name in ("r1", "r2"):
        p, _, _ = procs[name]
        p.send_signal(signal.SIGKILL)
        p.wait()

    futs = [svc.kput(0, "k2", b"x"), svc.kget(0, "k")]
    _settle(svc, futs)
    assert futs[0].value == "failed"
    assert futs[1].value == "failed"  # reads need the quorum too
    assert svc.stats()["group"]["quorum_failures"] > 0

    _restart(procs, dirs, "r1")
    _wait_synced(svc, 1)
    r = _settle(svc, [svc.kput(0, "k3", b"y")])
    assert r[0][0] == "ok"
    # the pre-outage acked write is still there
    r = _settle(svc, [svc.kget(0, "k")])
    assert r[0] == ("ok", b"v")


def test_promotion_fences_old_leader_and_loses_nothing(group):
    """In-place promotion: replica r1 takes over (promise round to a
    majority), after which the old leader's applies are nacked at the
    stale epoch — it can commit nothing (the sc.erl partition
    premise) — and every write the old leader acked is readable
    through the new one."""
    svc, procs, dirs, _ = group
    acked = {}
    futs = []
    for i in range(8):
        e, key, val = i % N_ENS, f"k{i}", b"v%d" % i
        futs.append(svc.kput(e, key, val))
        acked[(e, key)] = val
    _settle(svc, futs)
    assert all(f.value[0] == "ok" for f in futs)

    _, r1_repl, r1_client = procs["r1"][1], procs["r1"][1], procs["r1"][2]
    _, r2_repl, _ = procs["r2"]
    resp = _control(r1_repl, ("promote", [("127.0.0.1", r2_repl)]))
    assert resp[0] == "ok", resp
    new_ge = resp[1]
    assert new_ge > svc._ge

    # the deposed leader cannot commit anything anymore
    f = svc.kput(0, "stale", b"stale")
    try:
        _settle(svc, [f], flushes=3)
    except repgroup.DeposedError:
        pass
    assert f.done and f.value == "failed"
    assert svc._deposed

    # every previously-acked write is readable through the new leader
    async def read_back():
        from riak_ensemble_tpu import svcnode
        c = svcnode.ServiceClient("127.0.0.1", r1_client)
        await c.connect()
        for (e, key), val in acked.items():
            r = await c.kget(e, key, timeout=60.0)
            assert r == ("ok", val), (key, r)
        # and the stale-fenced write never became visible
        r = await c.kget(0, "stale", timeout=60.0)
        assert r == ("ok", NOTFOUND), r
        # the new leader commits new writes
        r = await c.kput(1, "post-promote", b"new", timeout=60.0)
        assert r[0] == "ok", r
        await c.close()

    import asyncio
    asyncio.run(read_back())


def test_partition_sigstop_excludes_then_heals(group):
    """A SIGSTOP'd replica is a network partition, not a death: the
    socket stays open and frames back up.  The leader must commit
    without it (ack deadline), and after SIGCONT the replica re-syncs
    and rejoins the quorum."""
    svc, procs, dirs, _ = group
    svc.ack_timeout = 3.0
    p1, _, _ = procs["r1"]

    _settle(svc, [svc.kput(0, "a", b"1")])
    p1.send_signal(signal.SIGSTOP)
    try:
        futs = [svc.kput(0, "b", b"2"), svc.kput(1, "c", b"3")]
        _settle(svc, futs)
        assert all(f.value[0] == "ok" for f in futs), \
            [f.value for f in futs]
    finally:
        p1.send_signal(signal.SIGCONT)
    _wait_synced(svc, 2)
    p2, _, _ = procs["r2"]
    p2.send_signal(signal.SIGKILL)
    p2.wait()
    futs = [svc.kget(0, "a"), svc.kget(0, "b"), svc.kget(1, "c")]
    _settle(svc, futs)
    assert [f.value for f in futs] == \
        [("ok", b"1"), ("ok", b"2"), ("ok", b"3")]


@pytest.mark.parametrize("seed", conftest.soak_seeds([1101, 1102]))
def test_repgroup_linearizable_under_host_nemesis(tmp_path, seed):
    """sc.erl over host failure domains: random put/get/CAS load
    against the leader while a nemesis kill -9s, SIGSTOPs and
    restarts the replica hosts.  Every acked write must be readable
    (KeyModel raises Violation on lost/stale/resurrected values);
    'failed' writes whose batch lost the host quorum are ambiguous
    (they applied on the surviving lanes) and join the plausible set
    via timeout_write — the same discipline sc.erl uses for timeouts.
    """
    rng = np.random.default_rng(seed)
    procs = {}
    dirs = {}
    for name in ("r1", "r2"):
        dirs[name] = str(tmp_path / name)
        procs[name] = _spawn_replica(dirs[name])
    svc = _make_leader(tmp_path, [procs["r1"][1], procs["r2"][1]],
                       ack_timeout=4.0)
    models = {}
    stopped = set()
    vals = iter(range(1, 100000))

    def model(e, k):
        return models.setdefault((e, k), KeyModel(f"{e}/k{k}"))

    try:
        for rnd in range(12):
            # nemesis
            r = rng.random()
            if r < 0.25:
                name = ("r1", "r2")[int(rng.integers(2))]
                p, _, _ = procs[name]
                if p.poll() is None and name not in stopped:
                    if rng.random() < 0.5:
                        p.send_signal(signal.SIGSTOP)
                        stopped.add(name)
                    else:
                        p.send_signal(signal.SIGKILL)
                        p.wait()
            elif r < 0.5:
                # heal: restart dead / continue stopped
                for name in ("r1", "r2"):
                    p, _, _ = procs[name]
                    if name in stopped:
                        p.send_signal(signal.SIGCONT)
                        stopped.discard(name)
                    elif p.poll() is not None:
                        _restart(procs, dirs, name)

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
            for _ in range(8):
                if all(f.done for *_, f in pending):
                    break
                try:
                    svc.flush()
                except repgroup.DeposedError:  # pragma: no cover
                    raise
            for kind, m, op, f in pending:
                assert f.done
                res = f.value
                if kind == "put":
                    if isinstance(res, tuple) and res[0] == "ok":
                        m.ack_write(op)
                    else:
                        # host-quorum failure is ambiguous: the write
                        # applied on the surviving lanes
                        m.timeout_write(op)
                else:
                    if isinstance(res, tuple) and res[0] == "ok":
                        v = res[1]
                        m.ack_read(v if v is NOTFOUND
                                   else int.from_bytes(v, "big"))

        # quiesce: heal everything, then read back every key
        for name in ("r1", "r2"):
            p, _, _ = procs[name]
            if name in stopped:
                p.send_signal(signal.SIGCONT)
                stopped.discard(name)
            elif p.poll() is not None:
                _restart(procs, dirs, name)
        _wait_synced(svc, 2, deadline=120.0)
        pending = [(m, svc.kget(e, f"k{k}"))
                   for (e, k), m in models.items()]
        for _ in range(10):
            if all(f.done for _, f in pending):
                break
            svc.flush()
        for m, f in pending:
            assert f.done and isinstance(f.value, tuple) \
                and f.value[0] == "ok", f.value
            v = f.value[1]
            m.ack_read(v if v is NOTFOUND
                       else int.from_bytes(v, "big"))
    finally:
        svc.stop()
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGCONT)
                p.kill()


def test_leader_kill9_promote_replica_no_acked_loss(tmp_path):
    """The full machine-kill story with EVERY host a real OS process:
    promote r1 to leader, ack writes through its client port, kill -9
    the LEADER, promote r2 (promise round to the surviving majority +
    newest-state adoption), and every acked write must be readable —
    including the group-meta-in-the-commit-barrier property (review
    r4): the restarted/overtaken group can never mistake a
    data-bearing position for an older one."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    procs = {}
    dirs = {}
    try:
        for name in ("r1", "r2", "r3"):
            dirs[name] = str(tmp_path / name)
            procs[name] = _spawn_replica(dirs[name])
        _, r1_repl, r1_client = procs["r1"]
        _, r2_repl, r2_client = procs["r2"]
        _, r3_repl, _ = procs["r3"]

        resp = _control(r1_repl, ("promote", [("127.0.0.1", r2_repl),
                                              ("127.0.0.1", r3_repl)]))
        assert resp[0] == "ok", resp

        async def drive_writes():
            c = svcnode.ServiceClient("127.0.0.1", r1_client)
            await c.connect()
            acked = {}
            for i in range(10):
                e = i % N_ENS
                r = await c.kput(e, f"k{i}", b"v%d" % i, timeout=120.0)
                assert r[0] == "ok", r
                acked[(e, f"k{i}")] = b"v%d" % i
            await c.close()
            return acked

        acked = asyncio.run(drive_writes())

        # kill -9 the LEADER host
        p1, _, _ = procs["r1"]
        p1.send_signal(signal.SIGKILL)
        p1.wait()

        # promote r2: needs r3's grant (majority 2/3 with self)
        resp = _control(r2_repl, ("promote", [("127.0.0.1", r1_repl),
                                              ("127.0.0.1", r3_repl)]),
                        timeout=300.0)
        assert resp[0] == "ok", resp

        async def read_back_and_write():
            c = svcnode.ServiceClient("127.0.0.1", r2_client)
            await c.connect()
            for (e, key), val in acked.items():
                r = await c.kget(e, key, timeout=120.0)
                assert r == ("ok", val), (key, r)
            r = await c.kput(0, "post-failover", b"new", timeout=120.0)
            assert r[0] == "ok", r
            await c.close()

        asyncio.run(read_back_and_write())

        # the restarted OLD leader rejoins as a fenced replica and
        # re-syncs; after that, killing r3 leaves r2+r1 as the
        # quorum — the rejoined ex-leader carries its share
        _restart(procs, dirs, "r1")
        deadline = time.monotonic() + 120.0
        synced = False
        while time.monotonic() < deadline:
            st = _control(r2_repl, ("status",))
            # status: (status, role, promised, applied_ge, applied_seq)
            st1 = _control(r1_repl, ("status",))
            if st1[1] == "replica" and st1[3] == st[3] \
                    and st1[4] == st[4]:
                synced = True
                break
            time.sleep(1.0)
        assert synced, (st, st1)
        p3, _, _ = procs["r3"]
        p3.send_signal(signal.SIGKILL)
        p3.wait()

        async def final_check():
            c = svcnode.ServiceClient("127.0.0.1", r2_client)
            await c.connect()
            r = await c.kget(0, "post-failover", timeout=120.0)
            assert r == ("ok", b"new"), r
            r = await c.kput(1, "final", b"z", timeout=120.0)
            assert r[0] == "ok", r
            await c.close()

        asyncio.run(final_check())
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()


def test_auto_failover_elects_new_leader_without_operator(tmp_path):
    """Automatic leader failover (the reference's peers self-elect on
    follower timeout; no operator in the loop): a cold-started group
    elects exactly one leader by itself, survives kill -9 of that
    leader by electing another within the failover window, loses no
    acked write, and a restarted ex-leader settles back in as a
    fenced replica."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    names = ("r1", "r2", "r3")
    repl_ports = {n: _free_port() for n in names}
    client_ports = {n: _free_port() for n in names}
    procs = {}
    dirs = {}

    def spawn(name):
        # restarts preserve BOTH ports and the failover/peer config —
        # a respawned host that can't campaign (or moved its client
        # port) would break the self-healing story mid-test
        others = [f"--peer=127.0.0.1:{repl_ports[o]}"
                  for o in names if o != name]
        return _spawn_replica(
            dirs[name], repl_port=repl_ports[name],
            client_port=client_ports[name],
            extra=["--auto-failover", "3.0"] + others)

    def roles():
        out = {}
        for n in names:
            p = procs[n][0]
            if p.poll() is not None:
                continue
            try:
                st = _control(repl_ports[n], ("status",), timeout=10.0)
                out[n] = st[1]
            except (OSError, ConnectionError):
                pass
        return out

    def wait_one_leader(deadline=90.0, exclude=()):
        end = time.monotonic() + deadline
        while time.monotonic() < end:
            r = roles()
            leaders = [n for n, role in r.items() if role == "leader"]
            if len(leaders) == 1 and leaders[0] not in exclude:
                return leaders[0]
            time.sleep(1.0)
        raise AssertionError(f"no single leader emerged: {roles()}")

    try:
        for name in names:
            dirs[name] = str(tmp_path / name)
            procs[name] = spawn(name)

        # -- cold start: the group elects a leader BY ITSELF ----------
        leader = wait_one_leader()

        async def write(client_port, items):
            c = svcnode.ServiceClient("127.0.0.1", client_port)
            await c.connect()
            for (e, key), val in items.items():
                r = await c.kput(e, key, val, timeout=120.0)
                assert r[0] == "ok", (key, r)
            await c.close()

        acked = {(i % N_ENS, f"k{i}"): b"v%d" % i for i in range(8)}
        asyncio.run(write(procs[leader][2], acked))

        # -- kill -9 the elected leader: a successor self-promotes ----
        p, _, _ = procs[leader]
        p.send_signal(signal.SIGKILL)
        p.wait()
        new_leader = wait_one_leader(exclude=(leader,))
        assert new_leader != leader

        async def read_all(client_port):
            c = svcnode.ServiceClient("127.0.0.1", client_port)
            await c.connect()
            for (e, key), val in acked.items():
                r = await c.kget(e, key, timeout=120.0)
                assert r == ("ok", val), (key, r)
            r = await c.kput(0, "post", b"new", timeout=120.0)
            assert r[0] == "ok", r
            await c.close()

        asyncio.run(read_all(procs[new_leader][2]))

        # -- the restarted ex-leader (same auto-failover config)
        #    settles in as a fenced replica, not a duelist ------------
        procs[leader] = spawn(leader)
        end = time.monotonic() + 60.0
        while time.monotonic() < end:
            r = roles()
            if r.get(leader) == "replica" \
                    and r.get(new_leader) == "leader":
                break
            time.sleep(1.0)
        r = roles()
        assert r.get(leader) == "replica", r
        assert [n for n, role in r.items()
                if role == "leader"] == [new_leader], r
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()


def test_group_client_follows_the_leader(tmp_path):
    """The leader-routing client role: GroupClient discovers the
    elected leader among the hosts' client ports, sticks to it, and
    re-discovers across a leader kill — not-leader rejections retry
    transparently (never dispatched), ambiguous disconnections
    surface to the caller."""
    import asyncio

    names = ("r1", "r2", "r3")
    repl_ports = {n: _free_port() for n in names}
    client_ports = {n: _free_port() for n in names}
    procs = {}
    dirs = {}

    def spawn(name):
        # restarts preserve BOTH ports and the failover/peer config —
        # a respawned host that can't campaign (or moved its client
        # port) would break the self-healing story mid-test
        others = [f"--peer=127.0.0.1:{repl_ports[o]}"
                  for o in names if o != name]
        return _spawn_replica(
            dirs[name], repl_port=repl_ports[name],
            client_port=client_ports[name],
            extra=["--auto-failover", "3.0"] + others)

    try:
        for name in names:
            dirs[name] = str(tmp_path / name)
            procs[name] = spawn(name)
        hosts = [("127.0.0.1", procs[n][2]) for n in names]

        async def scenario():
            gc = repgroup.GroupClient(hosts, op_timeout=120.0,
                                      discover_timeout=180.0)
            # discovery alone elects nothing — the group self-elects;
            # the client just has to find whoever won
            r = await gc.kput(0, "a", b"1")
            assert r[0] == "ok", r
            leader_addr = gc._leader_addr
            assert leader_addr is not None

            # kill the discovered leader: the next ops re-discover
            # the successor and proceed (the in-flight loss, if any,
            # would surface as DISCONNECTED — ambiguous by contract)
            victim = [n for n in names
                      if procs[n][2] == leader_addr[1]][0]
            p, _, _ = procs[victim]
            p.send_signal(signal.SIGKILL)
            p.wait()

            r = await gc.kget(0, "a")
            if r == ("error", "disconnected"):
                # the loss raced the read — ambiguous per contract;
                # a retried READ is always safe (and reads also ride
                # out a fresh leader's re-sync via retryable)
                r = await gc.kget(0, "a")
            assert r == ("ok", b"1"), r
            assert gc._leader_addr != leader_addr
            # the write may hit the new leader mid-re-sync ('failed' =
            # definitive no-ack) or lose a connection (ambiguous);
            # retrying an idempotent overwrite is the TEST's choice
            for _ in range(30):
                r = await gc.kput(0, "b", b"2")
                if isinstance(r, tuple) and r[0] == "ok":
                    break
                import asyncio as _a
                await _a.sleep(1.0)
            assert r[0] == "ok", r
            await gc.close()

        asyncio.run(scenario())
    finally:
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()
