"""Delta replication transport (ISSUE 5 tentpole).

The leader→replica apply stream ships changed-slot DELTA frames (wire
cost proportional to what committed, not to the [K, E] grid), coalesced
into one raw frame per flush per link, applied by the replica IN PLACE
through one scatter + mirror/WAL pass.  These tests pin the load-
bearing contract: a replica lane fed deltas must be BIT-EQUAL to the
full-plane re-execution reference — which is exactly the leader's own
lane — across every keyed storage class, across elections (the
full-plane fallback), across re-syncs and install barriers, and across
arbitrary coalescing boundaries.  Plus the raw-buffer wire section the
frames ride on (zero-copy scatter-gather encode, memoryview decode,
native/python parity, hostile-frame rejection).
"""

import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

import conftest  # noqa: F401

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import funref, wire  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)

N_ENS = 4
N_SLOTS = 8
GROUP = 3


# -- harness -----------------------------------------------------------------


def _group(tmp_path, n_ens=N_ENS, n_slots=N_SLOTS, **leader_kw):
    """In-process group: leader + 2 threaded ReplicaServer hosts (one
    jit cache, no subprocess compile) — the delta/full equivalence
    harness, where both replica lanes are directly inspectable."""
    srvs = [repgroup.ReplicaServer(
        n_ens, GROUP, n_slots, data_dir=str(tmp_path / f"r{i}"),
        config=fast_test_config()) for i in (1, 2)]
    svc = repgroup.ReplicatedService(
        WallRuntime(), n_ens, 1, n_slots, group_size=GROUP,
        peers=[("127.0.0.1", s.repl_port) for s in srvs],
        ack_timeout=15.0, config=fast_test_config(),
        data_dir=str(tmp_path / "leader"), **leader_kw)
    repgroup.warmup_kernels(svc)
    assert svc.takeover(), "takeover needs a replica majority"
    return svc, srvs


def _settle(svc, futs, budget=30.0):
    end = time.time() + budget
    while not all(f.done for f in futs) and time.time() < end:
        svc.flush()
    assert all(f.done for f in futs), "futures never settled"
    return [f.value for f in futs]


def _canon(svc):
    """Canonical lane state: engine arrays verbatim + order-insensitive
    keyed mirrors (dict/list iteration order is process history, not
    replicated state)."""
    fields, host = repgroup.dump_state(svc)
    (key_slot, slot_handle, values, _nh, leader_b, dyn, live_b,
     free_rows, ens_names, member_b, inline) = host
    return (fields,
            [sorted(p) for p in key_slot],
            [sorted(p) for p in slot_handle],
            sorted(values),
            leader_b, dyn, member_b,
            [sorted(s) for s in inline])


def _assert_lanes_equal(svc, srvs):
    """THE acceptance invariant: the leader executed every launch for
    real (the full-plane reference); a delta-fed replica must hold the
    bit-identical lane."""
    for _ in range(2):
        svc.heartbeat()
    svc._drain_pending(block_all=True)
    # the commit barrier settles at MAJORITY: a replica that just
    # consumed a catch-up install may still be grinding the batch
    # backlog its link queued behind it (correct, just behind) —
    # equivalence is defined at the leader's applied position, so
    # wait for every lane to reach it before comparing
    want_pos = (svc.core.applied_ge, svc.core.applied_seq)
    end = time.monotonic() + 60.0
    while time.monotonic() < end:
        with_pos = []
        for s in srvs:
            with s._lock:
                with_pos.append((s.core.applied_ge,
                                 s.core.applied_seq))
        if all(p >= want_pos for p in with_pos):
            break
        time.sleep(0.02)
    else:
        raise AssertionError(
            f"replicas never reached the leader's applied position "
            f"{want_pos}: {with_pos}")
    want = _canon(svc)
    for i, s in enumerate(srvs):
        with s._lock:
            got = _canon(s.svc)
        for j, (w, g) in enumerate(zip(want, got)):
            assert w == g, (
                f"replica {i} lane diverged from the leader "
                f"(component {j})")


def _stop(svc, srvs):
    svc.stop()
    for s in srvs:
        s.stop()


# -- wire: raw-buffer section ------------------------------------------------


def test_wire_raw_roundtrip_and_native_parity():
    arr = np.arange(37, dtype=np.int32)
    small = np.asarray([7], np.int16)
    v = ("d", 12, wire.Raw(arr), [wire.Raw(b"payload"),
                                  wire.Raw(small)],
         {"k": wire.Raw(b"")}, None, True)
    parts = wire.encode_parts(v)
    assert isinstance(parts, list) and len(parts) == 5  # header + 4
    payload = b"".join(bytes(p) for p in parts)
    for decoder in (wire.decode_py, wire.decode):
        out = decoder(payload)
        assert out[0] == "d" and out[1] == 12
        assert (np.frombuffer(out[2], np.int32) == arr).all()
        assert bytes(out[3][0]) == b"payload"
        assert (np.frombuffer(out[3][1], np.int16) == small).all()
        assert bytes(out[4]["k"]) == b""
        assert out[5] is None and out[6] is True
    # native and python decode agree value-for-value
    assert wire.decode(payload) == wire.decode_py(payload)


def test_wire_raw_bufferless_and_plain_frames_unchanged():
    v = ("x", [1, 2], {"a": b"b"})
    payload = b"".join(bytes(p) for p in wire.encode_parts(v))
    assert wire.decode(payload) == v
    assert wire.decode_py(payload) == v
    # plain encode is byte-stable and rejects Raw (parts-only type)
    assert wire.decode(wire.encode(v)) == v
    with pytest.raises(wire.WireError):
        wire.encode_py(wire.Raw(b"zz"))


def test_wire_raw_hostile_frames_rejected():
    cases = [
        b"B\x00r\x00",          # ref with empty table
        b"B\x01\x05N",          # table claims 5 bytes, none follow
        b"B\x02\x7f\x7fN",      # table exceeds frame
        b"B\x01\x01NNx",        # trailing bytes before the buffer
    ]
    good = b"".join(bytes(p) for p in
                    wire.encode_parts(("ok", wire.Raw(b"abc"))))
    # a ref index past the table
    bad_ref = bytearray(good)
    assert bad_ref.count(b"r"[0])  # tag present
    for payload in cases:
        for decoder in (wire.decode_py, wire.decode):
            with pytest.raises(wire.WireError):
                decoder(payload)


def test_recv_frame_rejects_oversized_header():
    a, b = socket.socketpair()
    try:
        too_big = repgroup._MAX_FRAME + 1
        a.sendall(struct.pack(">I", too_big))
        with pytest.raises(wire.WireError):
            repgroup.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_record_digest_numpy_int_stable():
    """The repr()-CRC replacement (satellite): numpy scalars and
    python ints digest identically — the wire contract, not repr."""
    plain = repgroup.record_digest([(1, 2, 3, 4), (5, 6, 7, 8)])
    mixed = repgroup.record_digest(
        [(np.int32(1), np.int64(2), 3, np.int32(4)),
         (5, np.int64(6), np.int32(7), 8)])
    assert plain == mixed


# -- delta entry unit behavior ----------------------------------------------


def _plain_core(tmp_path):
    svc = BatchedEnsembleService(WallRuntime(), N_ENS, 1, N_SLOTS,
                                 config=fast_test_config(),
                                 data_dir=str(tmp_path / "lane"),
                                 tick=None)
    return svc, repgroup.ReplicaCore(svc)


def test_delta_crc_violation_nacks(tmp_path):
    """A flipped byte in a delta section must nack (and leave the
    lane untouched) — the frame CRC is the integrity contract."""
    svc, core = _plain_core(tmp_path)
    committed = np.zeros((2, N_ENS), bool)
    committed[0, 1] = True
    value = np.zeros((2, N_ENS), np.int32)
    kind = np.zeros((2, N_ENS), np.int32)
    kind[0, 1] = eng.OP_PUT
    slot = np.zeros((2, N_ENS), np.int32)
    slot[0, 1] = 3
    val = np.full((2, N_ENS), 9, np.int32)
    q = np.ones((N_ENS,), bool)
    entry, crc, nbytes = repgroup.build_delta_entry(
        1, 2, committed, value, kind, slot, val, q, [])
    assert nbytes > 0 and entry[0] == "d"
    # corrupt the vals section (index 10) but keep the shipped crc
    bad_vals = np.frombuffer(entry[10].buf, np.int32).copy()
    bad_vals[0] ^= 0xFF
    bad = entry[:10] + (wire.Raw(bad_vals),) + entry[11:]
    r = core.handle_abatch(("abatch", 0, [bad]))
    assert r[0] == "nack" and r[1] == "crc"
    assert core.applied_seq == 0
    r = core.handle_abatch(("abatch", 0, [entry]))
    assert r == ("applied", 0, 1, repgroup._crc_chain(0, crc))
    assert int(np.asarray(svc.state.obj_val)[1, 0, 3]) == 9
    svc.stop()


def test_delta_seq_gap_nacks(tmp_path):
    svc, core = _plain_core(tmp_path)
    q = np.ones((N_ENS,), bool)
    e1, _, _ = repgroup.build_delta_entry(
        1, 0, None, None, np.zeros((0, N_ENS), np.int32),
        np.zeros((0, N_ENS), np.int32), np.zeros((0, N_ENS), np.int32),
        q, [])
    e3, _, _ = repgroup.build_delta_entry(
        3, 0, None, None, np.zeros((0, N_ENS), np.int32),
        np.zeros((0, N_ENS), np.int32), np.zeros((0, N_ENS), np.int32),
        q, [])
    r = core.handle_abatch(("abatch", 0, [e1, e3]))
    assert r[0] == "nack" and r[1] == "seq"
    assert core.applied_seq == 1  # the in-order prefix applied
    svc.stop()


def test_batch_ack_gathers_at_majority_not_slowest():
    """Satellite: the shared-condition ack gather settles at majority
    time — a dead-slow link no longer holds the batch to its
    deadline (nor does list-order waiting sum slow prefixes)."""

    class _L:
        def __init__(self, i):
            self.host, self.port = "h", i
            self.needs_sync = False

    entry = repgroup._PendingEntry(1, 111, ("d",))
    batch = repgroup._PendingShip([entry], time.monotonic() + 30.0)
    crc = batch.crc
    links = [_L(0), _L(1), _L(2)]
    tickets = []
    for link in links:
        t = repgroup._Ticket(on_done=batch._notify)
        tickets.append(t)
        batch.sends.append((link, t))
    # the SLOW link (index 0, FIRST in list order) never answers;
    # links 1 and 2 ack after 50 ms
    def ack_later():
        time.sleep(0.05)
        for t in tickets[1:]:
            t.result = ("applied", 0, 1, crc)
            t._fire()
    threading.Thread(target=ack_later, daemon=True).start()
    t0 = time.monotonic()
    batch.wait_quorum(lambda acked: len(acked) + 1 >= 2)
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, f"waited {elapsed:.1f}s — not majority-gated"
    assert len(batch._acked_now()) == 2


# -- delta vs full-plane replica equivalence ---------------------------------


def test_delta_equivalence_scalar_sweep(tmp_path):
    svc, srvs = _group(tmp_path)
    try:
        futs = []
        for e in range(N_ENS):
            futs += [svc.kput(e, f"k{e}", b"v%d" % e),
                     svc.kget(e, f"k{e}"),
                     svc.kput(e, f"j{e}", b"w")]
        _settle(svc, futs)
        r = _settle(svc, [svc.kupdate(0, "k0", (1, 1), b"v0b")])
        assert r[0][0] == "ok"
        _settle(svc, [svc.kdelete(1, "k1"),
                      svc.kput_once(2, "once", b"o")])
        g = svc.stats()["group"]
        assert g["repl_delta_entries"] > 0, g
        assert g["quorum_failures"] == 0, g
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_delta_equivalence_keyed_rmw_inline(tmp_path):
    """Device RMW through the delta path: inline slots (value lives
    in the engine arrays), including the computed-0 tombstone drop."""
    svc, srvs = _group(tmp_path)
    try:
        futs = [svc.kmodify(e, f"ctr{e}", funref.ref("rmw:add", 5), 0)
                for e in range(N_ENS)]
        _settle(svc, futs)
        futs = [svc.kmodify(e, f"ctr{e}", funref.ref("rmw:add", 3), 0)
                for e in range(N_ENS)]
        _settle(svc, futs)
        r = _settle(svc, [svc.kget(0, "ctr0")])
        assert r[0] == ("ok", 8)
        # computed 0 = tombstone: reads see NOTFOUND (the engine-wide
        # 0-is-notfound encoding, test_rmw convention) on every lane
        _settle(svc, [svc.kmodify(1, "ctr1", funref.ref("rmw:sub", 8),
                                  0)])
        r = _settle(svc, [svc.kget(1, "ctr1")])
        assert r[0] == ("ok", wire.NOTFOUND), r
        g = svc.stats()["group"]
        assert g["repl_delta_entries"] > 0
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_delta_equivalence_batched_groups(tmp_path):
    svc, srvs = _group(tmp_path)
    try:
        keys = [f"key{j}" for j in range(6)]
        vals = [b"v%d" % j for j in range(6)]
        for _ in range(3):
            futs = []
            for e in range(N_ENS):
                futs.append(svc.kput_many(e, keys, vals))
                futs.append(svc.kget_many(e, keys[:3]))
            _settle(svc, futs)
        futs = [svc.kdelete_many(e, keys[::2]) for e in range(N_ENS)]
        _settle(svc, futs)
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_delta_across_elections_full_fallback(tmp_path):
    """An electing launch ships full-plane (the replica re-executes
    it — epoch bumps are kernel work); the delta stream resumes after
    and the post-election stale-epoch GET rewrites (commits on READ
    rounds) must replicate through deltas too."""
    svc, srvs = _group(tmp_path)
    try:
        futs = [svc.kput(e, f"k{e}", b"v") for e in range(N_ENS)]
        _settle(svc, futs)
        g0 = svc.stats()["group"]
        # depose the device-lane leaders: the next flush elects
        svc.leader_np[:] = -1
        svc._slot_vsn_ok[:] = False
        futs = [svc.kget(e, f"k{e}") for e in range(N_ENS)]
        _settle(svc, futs)
        g1 = svc.stats()["group"]
        assert g1["repl_full_entries"] > g0["repl_full_entries"], (
            "the electing launch must ship full-plane")
        # stale-epoch rewrites ride the delta stream on later reads
        futs = [svc.kget(e, f"k{e}") for e in range(N_ENS)]
        _settle(svc, futs)
        futs = [svc.kput(e, f"post{e}", b"p") for e in range(N_ENS)]
        _settle(svc, futs)
        assert svc.stats()["group"]["quorum_failures"] == 0
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_delta_across_resync_and_install_barrier(tmp_path):
    """A link marked stale mid-stream re-syncs via an install queued
    ahead of the batches (the install-barrier discipline) and lands
    bit-equal; the commit path never stalls on it."""
    svc, srvs = _group(tmp_path)
    try:
        _settle(svc, [svc.kput(0, "a", b"1")])
        # declare replica 0 diverged (as a CRC mismatch would)
        svc._links[0].needs_sync = True
        futs = [svc.kput(e, f"b{e}", b"2") for e in range(N_ENS)]
        _settle(svc, futs)
        end = time.monotonic() + 30.0
        while time.monotonic() < end:
            svc.heartbeat()
            if svc.stats()["group"]["peers_synced"] == 2:
                break
            time.sleep(0.05)
        g = svc.stats()["group"]
        assert g["peers_synced"] == 2, g
        assert g["resyncs"] + g["tree_resyncs"] >= 1, g
        futs = [svc.kput(e, f"c{e}", b"3") for e in range(N_ENS)]
        _settle(svc, futs)
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_delta_off_knob_full_plane_equivalence(tmp_path):
    """The RETPU_REPL_DELTA=0 arm: every entry ships full-plane and
    the lanes still converge (the full-plane baseline)."""
    svc, srvs = _group(tmp_path)
    try:
        svc._repl_delta = False  # what RETPU_REPL_DELTA=0 pins
        futs = []
        for e in range(N_ENS):
            futs += [svc.kput(e, f"k{e}", b"v"), svc.kget(e, f"k{e}")]
        _settle(svc, futs)
        g = svc.stats()["group"]
        assert g["repl_delta_entries"] == 0, g
        assert g["repl_full_entries"] > 0, g
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_skewed_write_set_ships_a_fraction_of_the_full_plane(tmp_path):
    """The stream's cost follows what committed, not the grid: with
    writes rotating over one of the four columns a round, an entry
    ships under 25% of its full-plane equivalent's bytes, and every
    replica lane still ends bit-equal."""
    n_ens, keys = N_ENS, [f"key{j}" for j in range(4)]
    svc, srvs = _group(tmp_path)
    try:
        # dense warm round: every column allocates its slots and
        # elects (full-plane entries) before the meter starts
        _settle(svc, [svc.kput_many(e, keys, [b"w"] * 4)
                      for e in range(n_ens)])
        g0 = dict(svc.stats()["group"])
        for rnd in range(8):
            _settle(svc, [svc.kput_many(e, keys, [b"v%d" % rnd] * 4)
                          for e in range(rnd % 4, n_ens, 4)])
        g = svc.stats()["group"]
        entries = g["repl_delta_entries"] - g0["repl_delta_entries"]
        assert entries > 0 and g["quorum_failures"] == 0, g
        assert g["repl_full_entries"] == g0["repl_full_entries"], g
        shipped = g["repl_bytes_sections"] - g0["repl_bytes_sections"]
        full = g["repl_bytes_full_equiv"] - g0["repl_bytes_full_equiv"]
        assert 0 < shipped < 0.25 * full, (shipped, full, entries)
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_coalesced_boundary_fuzz(tmp_path):
    """Randomized coalescing-boundary sweep: random op mixes, delta
    toggles and forced elections across many flushes, with the chain
    flush (host-path kmodify) producing multi-entry frames — every
    frame boundary must preserve the stream and the lanes must end
    bit-equal.  Seeded: failures reproduce."""
    rng = np.random.default_rng(7)
    svc, srvs = _group(tmp_path)
    try:
        if not hasattr(funref, "_delta_fuzz_reg"):
            funref._delta_fuzz_reg = True

            @funref.register("tests.delta_fuzz_incr")
            def _incr(cur, by):  # noqa: F811 — registry-addressed
                return (0 if cur in (None, repgroup.wire.NOTFOUND)
                        else int(cur)) + int(by)
        for rnd in range(12):
            futs = []
            for e in range(N_ENS):
                n = int(rng.integers(0, 4))
                for j in range(n):
                    which = int(rng.integers(0, 4))
                    key = f"f{e}_{int(rng.integers(0, 6))}"
                    if which == 0:
                        futs.append(svc.kput(e, key, b"x%d" % rnd))
                    elif which == 1:
                        futs.append(svc.kget(e, key))
                    elif which == 2:
                        futs.append(svc.kdelete(e, key))
                    else:
                        futs.append(svc.kmodify(
                            e, key,
                            funref.ref("rmw:add", int(
                                rng.integers(1, 9))), 0))
            if rnd == 4:
                svc._repl_delta = False
            if rnd == 6:
                svc._repl_delta = True
            if rnd == 8:
                svc.leader_np[:] = -1  # forced re-election
                svc._slot_vsn_ok[:] = False
            _settle(svc, futs)
        g = svc.stats()["group"]
        assert g["quorum_failures"] == 0, g
        assert g["repl_delta_entries"] > 0
        assert g["repl_full_entries"] > 0
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_multi_entry_frames_coalesce(tmp_path):
    """One flush settling several launches ships them as ONE frame
    (entries > frames), and the cumulative ack covers all of them."""
    svc, srvs = _group(tmp_path)
    try:
        _settle(svc, [svc.kput(0, "seed", b"s")])
        g0 = svc.stats()["group"]
        k = np.zeros((1, N_ENS), np.int32)
        s = np.zeros((1, N_ENS), np.int32)
        v = np.zeros((1, N_ENS), np.int32)
        k[0, :] = eng.OP_PUT
        s[0, :] = N_SLOTS - 1
        v[0, :] = 42
        f1 = svc.execute_async(k, s, v)
        v2 = v.copy()
        v2[0, :] = 43
        f2 = svc.execute_async(k, s, v2)
        _settle(svc, [f1, f2])
        svc._drain_pending(block_all=True)
        g1 = svc.stats()["group"]
        entries = (g1["repl_delta_entries"] + g1["repl_full_entries"]
                   - g0["repl_delta_entries"] - g0["repl_full_entries"])
        frames = g1["repl_frames"] - g0["repl_frames"]
        assert entries >= 2
        assert frames < entries, (
            f"{entries} entries rode {frames} frames — no coalescing")
        assert g1["quorum_failures"] == g0["quorum_failures"]
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)
