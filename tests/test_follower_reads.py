"""Follower-served leased reads (docs/ARCHITECTURE.md §16).

A replica answers ``kget*`` from its delta-maintained host mirrors
under an epoch-fenced read lease the leader grants/renews on
quorum-confirmed settles.  These tests drive the three properties the
protocol must hold:

- **serve**: a granted replica answers every read verb with the
  leader's committed values (notfound included), and the window
  expires within ``lease()`` of the last confirmed settle;
- **linearizability**: with a single writer bumping a counter key,
  no replica-served read ever returns a value older than the last
  write whose ack completed before the read started — through a
  one-way partition (acks blackholed) and its heal;
- **fencing**: a higher promise revokes the window immediately (the
  leader-handoff fence), regardless of remaining lease time.

The follower-reads-OFF arm ships byte-identical frames to HEAD and
rejects replica reads exactly as before — covered by the existing
repgroup/repl_delta suites, which run with the knob off.
"""

import os
import socket
import struct
import threading
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from riak_ensemble_tpu import faults, wire  # noqa: E402
from riak_ensemble_tpu.config import Config  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    WallRuntime)

N_ENS = 4
N_SLOTS = 8
GROUP = 3
#: long enough that a driven leader renews faster than expiry, short
#: enough that expiry tests stay quick
LEASE = 1.5

_HDR = struct.Struct(">I")


def _cfg() -> Config:
    return Config(ensemble_tick=0.05, lease_duration=LEASE,
                  probe_delay=0.1, storage_delay=0.005,
                  storage_tick=0.5, gossip_tick=0.2)


def _ask(port, *frame, timeout=30.0):
    """One svcnode-protocol round-trip on a fresh socket."""
    sock = socket.create_connection(("127.0.0.1", port),
                                    timeout=timeout)
    try:
        payload = wire.encode(frame)
        sock.sendall(_HDR.pack(len(payload)) + payload)
        hdr = b""
        while len(hdr) < 4:
            b = sock.recv(4 - len(hdr))
            if not b:
                raise ConnectionError("closed")
            hdr += b
        (n,) = _HDR.unpack(hdr)
        buf = b""
        while len(buf) < n:
            b = sock.recv(n - len(buf))
            if not b:
                raise ConnectionError("closed")
            buf += b
        return wire.decode(buf)[1]
    finally:
        sock.close()


def _settle(svc, futs, budget=30.0):
    end = time.time() + budget
    while not all(f.done for f in futs) and time.time() < end:
        svc.flush()
    assert all(f.done for f in futs), "futures never settled"
    return [f.value for f in futs]


def _renew(svc, rounds=3):
    """Grants ride the NEXT frame after the settle that issued them:
    a couple of heartbeats deliver + confirm them everywhere."""
    for _ in range(rounds):
        svc.heartbeat()
        svc._drain_pending(block_all=True)
        time.sleep(0.02)


def _wait_serving(svc, port, ens, key, deadline_s=15.0):
    """Heartbeat until the replica behind ``port`` serves — how many
    rounds a grant takes to land depends on ack arrival order (a
    settle counts whoever acked before its quorum fired)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        r = _ask(port, 0, "kget", ens, key)
        if r != ("error", "not-leader"):
            return r
        _renew(svc, rounds=1)
    raise AssertionError("replica never started serving")


@pytest.fixture(scope="module")
def group(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("flw")
    faults.clear()
    srvs = [repgroup.ReplicaServer(
        N_ENS, GROUP, N_SLOTS, data_dir=str(tmp / f"r{i}"),
        config=_cfg(), follower_reads=True) for i in (1, 2)]
    svc = repgroup.ReplicatedService(
        WallRuntime(), N_ENS, 1, N_SLOTS, group_size=GROUP,
        peers=[("127.0.0.1", s.repl_port) for s in srvs],
        ack_timeout=15.0, config=_cfg(),
        data_dir=str(tmp / "leader"), follower_reads=True)
    repgroup.warmup_kernels(svc)
    assert svc.takeover(), "takeover needs a replica majority"
    yield svc, srvs
    faults.clear()
    for s in srvs:
        s.stop()
    svc.stop()


def test_follower_serves_all_read_verbs_then_lease_expires(group):
    svc, srvs = group
    futs = [svc.kput(1, f"k{i}", f"v{i}".encode()) for i in range(4)]
    assert all(r[0] == "ok" for r in _settle(svc, futs))
    port = srvs[0].client_port
    assert _wait_serving(svc, port, 1, "k1") == ("ok", b"v1")
    assert _ask(port, 1, "kget", 1, "k1") == ("ok", b"v1")
    r = _ask(port, 2, "kget_vsn", 1, "k2")
    assert r[0] == "ok" and r[1] == b"v2" and len(r[2]) == 2
    assert _ask(port, 3, "kget_many", 1, ["k0", "k3"]) == \
        [("ok", b"v0"), ("ok", b"v3")]
    # slab verb through the same lease gate (little-endian int32
    # length table, the wire contract)
    import numpy as np
    keys = ["k0", "k1"]
    lens = np.asarray([len(k) for k in keys], "<i4").tobytes()
    arena = "".join(keys).encode("ascii")
    assert _ask(port, 4, "kget_slab", 1, lens, arena) == \
        [("ok", b"v0"), ("ok", b"v1")]
    # an absent key is an authoritative notfound, not a fallback
    assert _ask(port, 5, "kget", 1, "absent") == \
        ("ok", repgroup.NOTFOUND)
    assert srvs[0].svc.group_stats["follower_reads_served"] >= 5
    # both replicas hold grants once the pipeline settles fully (how
    # many rounds that takes depends on ack arrival order)
    deadline = time.monotonic() + 15.0
    while len(svc._flw_grants) < 2 and time.monotonic() < deadline:
        _renew(svc, rounds=1)
    assert len(svc._flw_grants) == 2
    # idle past the lease: the window lapses and reads re-route
    time.sleep(LEASE + 0.2)
    assert _ask(port, 6, "kget", 1, "k1") == ("error", "not-leader")
    assert srvs[0].svc.group_stats["follower_reads_blocked"] >= 1
    # a driven leader renews: serving resumes
    assert _wait_serving(svc, port, 1, "k1") == ("ok", b"v1")


def test_follower_reads_linearizable_through_one_way_partition(group):
    """Single-writer counter: no replica-served read may return a
    value older than the last ack the writer observed before the
    read started — including across an ack-blackhole partition of
    the serving replica (its window must lapse before its mirrors
    can go stale relative to new acks) and the heal."""
    svc, srvs = group
    port = srvs[0].client_port
    label = f"127.0.0.1:{srvs[0].repl_port}"
    state = {"floor": 0, "stop": False}
    errors = []

    def reader():
        last = 0
        while not state["stop"]:
            floor = state["floor"]
            r = _ask(port, 99, "kget", 2, "ctr")
            if r == ("error", "not-leader"):
                time.sleep(0.01)
                continue
            if r[0] != "ok" or r[1] is repgroup.NOTFOUND:
                errors.append(f"unexpected reply {r!r}")
                break
            v = int(r[1])
            if v < floor:
                errors.append(
                    f"stale read: got {v}, acked floor was {floor}")
                break
            if v < last:
                errors.append(f"non-monotonic read: {v} after {last}")
                break
            last = v
            time.sleep(0.005)

    _settle(svc, [svc.kput(2, "ctr", b"0")])
    _wait_serving(svc, port, 2, "ctr")
    t = threading.Thread(target=reader, daemon=True)
    t.start()
    val = 0

    def write_some(n):
        nonlocal val
        for _ in range(n):
            val += 1
            r = _settle(svc, [svc.kput(2, "ctr",
                                       str(val).encode())])[0]
            assert r[0] == "ok", r
            state["floor"] = val
            _renew(svc, rounds=1)

    write_some(8)
    # one-way partition: replica 0's ACKS blackhole (it still
    # receives and applies frames, the leader just can't count it —
    # so its grants freeze and its window must lapse)
    plan = faults.install(faults.FaultPlan(silent=True))
    plan.drop(label, faults.LOCAL)
    try:
        write_some(4)
        time.sleep(LEASE + 0.2)
        assert _ask(port, 98, "kget", 2, "ctr") == \
            ("error", "not-leader")
    finally:
        faults.clear()
    # heal: grants resume, serving resumes, floor invariant held
    write_some(4)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if _ask(port, 97, "kget", 2, "ctr") != ("error",
                                                "not-leader"):
            break
        _renew(svc, rounds=1)
    assert _ask(port, 96, "kget", 2, "ctr") == ("ok",
                                                str(val).encode())
    state["stop"] = True
    t.join(timeout=10.0)
    assert not errors, errors
    # the barrier accounting surfaced the stalls it took
    assert svc.group_stats["follower_lease_write_blocks"] >= 1


def test_higher_promise_fences_follower_window_immediately(group):
    """The leader-handoff fence: granting a higher promise revokes
    the replica's window BEFORE the grant is answered — a new
    leader's first write can never race a stale leased read.  (Runs
    last: the promise deposes the module leader.)"""
    svc, srvs = group
    port = srvs[0].client_port
    assert _wait_serving(svc, port, 1, "k1") == ("ok", b"v1")
    # the repl port speaks raw (op, args...) frames; _ask's leading
    # "op" slot doubles as the verb and the [1] it returns is the
    # granted flag of ("promised", granted, ...)
    granted = _ask(srvs[0].repl_port, "promise", svc._ge + 7)
    assert granted is True
    assert srvs[0].core.serve_until == 0.0
    assert _ask(port, 2, "kget", 1, "k1") == ("error", "not-leader")
