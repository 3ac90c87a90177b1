"""Active-column compaction: the packed d2h payload gathers down to
the live working set (O(K·|A|), not O(K·E)) while staying a PURE
re-indexing — every result a full-width pack would deliver arrives
bit-identically.  These tests pin:

- the pack/unpack layout round trip through an active index list
  (pow2 padding included),
- the skew-load equivalence sweep the issue demands: one hot ensemble
  at full depth + hundreds of idle/1-deep columns, seeded op mix
  including OP_RMW, compacted results element-equal
  to a full-width-pack reference service,
- corruption detected inside a heavily-compacted launch still reaches
  the exchange/scrub path (the corrupt mask stays full width),
- a replication-group replica applies a compacted leader stream
  across an active-set change between flushes (CRC + state equality,
  even with the two sides in DIFFERENT pack layouts),
- the (K, A) warmup grid, and
- WAL compaction deferred off the hot path (idle-flush scheduling,
  the hard 2x in-line bound, and the svc_compaction marks).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from riak_ensemble_tpu import funref  # noqa: E402
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel import batched_host as bh  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime,
)


def make_pair(n_ens, n_peers, n_slots, k):
    """(compacted service, full-width reference service) — identical
    but for the pack layout."""
    svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                 n_slots, tick=None,
                                 max_ops_per_tick=k)
    ref = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                 n_slots, tick=None,
                                 max_ops_per_tick=k)
    assert svc._compact  # default on
    ref._compact = False
    return svc, ref


def assert_engine_equal(a, b):
    for f, x, y in zip(eng.EngineState._fields, a.state, b.state):
        if x is None or y is None:      # no row plane at this shape
            assert x is None and y is None, f
            continue
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)


# -- layout round trip -------------------------------------------------------


def _random_result(rng, k, e, m, cols):
    """KvResult planes with client data only in the active columns
    (exactly what a real launch produces: inactive columns carry the
    all-false/zero NOOP results) but FULL-width quorum/corrupt/won
    planes."""
    def bplane():
        full = np.zeros((k, e), bool)
        full[:, cols] = rng.random((k, len(cols))) < 0.5
        return full

    value = np.zeros((k, e), np.int32)
    value[:, cols] = rng.integers(0, 1 << 20, (k, len(cols)))
    vsn = np.zeros((k, e, 2), np.int32)
    vsn[:, cols] = rng.integers(0, 100, (k, len(cols), 2))
    res = eng.KvResult(
        committed=jnp.asarray(bplane()), get_ok=jnp.asarray(bplane()),
        found=jnp.asarray(bplane()), value=jnp.asarray(value),
        obj_vsn=jnp.asarray(vsn),
        quorum_ok=jnp.asarray(rng.random((k, e)) < 0.5),
        tree_corrupt=jnp.asarray(rng.random((k, e, m)) < 0.1))
    won = jnp.asarray(rng.random((e,)) < 0.5)
    return won, res


_ROUNDTRIP_COLS = [
    ([2, 7, 8, 21], 4),       # exact pow2 fit
    ([0, 3, 9, 20, 30], 8),   # padded bucket (pad repeats index 0)
    ([31], 1),                # single hot column
]
_PLANES = ("won", "quorum", "corrupt", "committed", "get_ok", "found",
           "value", "vsn")


@pytest.mark.parametrize("cols,a_width", _ROUNDTRIP_COLS)
def test_pack_unpack_roundtrip_active(cols, a_width):
    rng = np.random.default_rng(7)
    k, e, m = 5, 32, 3
    cols = np.asarray(cols, np.int32)
    won, res = _random_result(rng, k, e, m, cols)

    # the pack as a program of its own (a launch runs it inside its
    # step program: tests/test_op_slab.py holds that one to this)
    pack = jax.jit(eng.pack_results, static_argnames=("want_vsn",))
    full_flat = np.asarray(pack(won, res, True))
    pad = np.zeros((a_width,), np.int32)
    pad[:len(cols)] = cols
    comp_flat = np.asarray(
        pack(won, res, True, active_idx=jnp.asarray(pad)))
    assert comp_flat.nbytes < full_flat.nbytes
    assert comp_flat.nbytes == bh.packed_nbytes(e, m, k, True, a_width)

    o_full = bh.unpack_results(full_flat, e, m, k, True)
    o_comp = bh.unpack_results(comp_flat, e, m, k, True,
                               active=cols, a_width=a_width)
    for name, a, b in zip(_PLANES, o_full, o_comp):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("want_vsn", [True, False],
                         ids=["vsn", "no-vsn"])
@pytest.mark.parametrize("cols,a_width", _ROUNDTRIP_COLS)
def test_pack_unpack_roundtrip_sliced(cols, a_width, want_vsn):
    """A SLICED launch's pack: ``won``, the corrupt mask and the
    client planes A wide (pads included), the quorum plane ONE row E
    wide (``engine._sliced_quorum``).  The numpy unpack and the native
    one give the same full-width planes: the A-wide ones scattered
    through the active list, the quorum row as it was packed, set
    bits of idle columns included."""
    from riak_ensemble_tpu.parallel import resolve_native

    rng = np.random.default_rng(11)
    k, e, m = 5, 32, 3
    cols = np.asarray(cols, np.int32)
    n = len(cols)

    def noise(shape, dtype=bool):
        if dtype is bool:
            return rng.random(shape) < 0.5
        return rng.integers(0, 1 << 20, shape).astype(dtype)

    res = eng.KvResult(
        committed=noise((k, a_width)), get_ok=noise((k, a_width)),
        found=noise((k, a_width)),
        value=noise((k, a_width), np.int32),
        obj_vsn=noise((k, a_width, 2), np.int32),
        quorum_ok=noise((1, e)),
        tree_corrupt=noise((k, a_width, m)))
    won = noise((a_width,))
    assert res.quorum_ok[0, np.setdiff1d(np.arange(e), cols)].any()

    pack = jax.jit(eng.pack_results, static_argnames=("want_vsn",))
    flat = np.asarray(pack(jnp.asarray(won),
                           jax.tree.map(jnp.asarray, res), want_vsn))
    assert flat.nbytes == bh.packed_nbytes(e, m, k, want_vsn, a_width,
                                           sliced=True)

    def wide(x, axis=0):
        """The A-wide plane's real columns at their places in E."""
        shape = list(x.shape)
        shape[axis] = e
        out = np.zeros(shape, x.dtype)
        out[(slice(None),) * axis + (cols,)] = np.take(
            x, np.arange(n), axis=axis)
        return out

    want = (wide(won), res.quorum_ok[0], wide(res.tree_corrupt.any(0)),
            wide(res.committed, 1), wide(res.get_ok, 1),
            wide(res.found, 1), wide(res.value, 1),
            wide(res.obj_vsn, 1) if want_vsn else None)
    arms = {"numpy": bh.unpack_results(flat, e, m, k, want_vsn,
                                       active=cols, a_width=a_width,
                                       sliced=True)}
    nr = resolve_native.get()
    if nr is not None:
        arms["native"] = nr.unpack(flat, e, m, k, want_vsn, cols,
                                   a_width, True)
        assert arms["native"] is not None
    for arm, got in arms.items():
        for name, g, w in zip(_PLANES, got, want):
            if w is None:
                assert g is None, (arm, name)
                continue
            np.testing.assert_array_equal(g, w, err_msg=f"{arm} {name}")


# -- the skew-load equivalence sweep ----------------------------------------

E_SWEEP = 512
K_SWEEP = 64


def _skew_planes(rng, n_ens, n_slots, k):
    """Seeded skewed op planes: column 0 hot at full depth k (mix of
    PUT / GET / CAS / RMW / tombstone-PUT), roughly a third of the
    other columns 1-deep, a few 2-3 deep, the rest idle."""
    kind = np.zeros((k, n_ens), np.int32)
    slot = np.zeros((k, n_ens), np.int32)
    val = np.zeros((k, n_ens), np.int32)
    exp_e = np.zeros((k, n_ens), np.int32)
    exp_s = np.zeros((k, n_ens), np.int32)

    def fill(col, depth):
        kinds = rng.choice(
            [eng.OP_PUT, eng.OP_GET, eng.OP_CAS, eng.OP_RMW,
             eng.OP_PUT], depth, p=[0.35, 0.25, 0.15, 0.15, 0.1])
        kind[:depth, col] = kinds
        slot[:depth, col] = rng.integers(0, n_slots, depth)
        val[:depth, col] = rng.integers(1, 1 << 20, depth)
        tomb = (kinds == eng.OP_PUT) & (rng.random(depth) < 0.2)
        val[:depth, col][tomb] = 0
        rmw = kinds == eng.OP_RMW
        exp_e[:depth, col][rmw] = rng.choice(
            [eng.RMW_ADD, eng.RMW_MAX, eng.RMW_BXOR], int(rmw.sum()))
        # CAS rows: create-if-missing on the first pass; later
        # passes feed real versions from the caller

    fill(0, k)
    # most of the grid idles: the hot column plus ~E/8 light columns
    # (bucketed active set well below E/4, so the payload cut is >4x)
    light = rng.permutation(np.arange(1, n_ens))[:n_ens // 8 - 1]
    for col in light[:-4]:
        fill(int(col), 1)
    for col in light[-4:]:  # a few middle-depth columns
        fill(int(col), int(rng.integers(2, 4)))
    return kind, slot, val, exp_e, exp_s


def test_skew_equivalence_sweep():
    """1 hot ensemble at depth 64 + ~60 one-to-three-deep + ~450 idle
    of 512: the compacted service's result planes are identical to
    the full-width reference over repeated seeded sweeps (versions
    advance, CAS rows start hitting committed state), while the d2h
    payload shrinks by > 4x."""
    svc, ref = make_pair(E_SWEEP, 3, 64, K_SWEEP)
    rng = np.random.default_rng(11)
    planes = [_skew_planes(np.random.default_rng(s), E_SWEEP, 64,
                           K_SWEEP) for s in rng.integers(0, 999, 3)]
    for i, (kind, slot, val, exp_e, exp_s) in enumerate(planes):
        out_c = svc.execute(kind, slot, val, exp_epoch=exp_e,
                            exp_seq=exp_s)
        out_f = ref.execute(kind, slot, val, exp_epoch=exp_e,
                            exp_seq=exp_s)
        for name, a, b in zip(("committed", "get_ok", "found",
                               "value"), out_c, out_f):
            np.testing.assert_array_equal(a, b, err_msg=name)
        if i == 0:
            # the first launch elects ALL E columns (its active set
            # is genuinely full width); the payload claim below is
            # about steady state, so count from the second launch
            for s in (svc, ref):
                s.payload_bytes = 0
                s.payload_bytes_full_width = 0
                s._occ_sum = 0.0
                s._occ_launches = 0
    assert_engine_equal(svc, ref)
    # the mix really exercised the op kinds
    kind = planes[0][0]
    assert all((kind == op).any() for op in
               (eng.OP_PUT, eng.OP_GET, eng.OP_CAS, eng.OP_RMW))
    # and the payload shrank: this is the whole point
    assert svc.payload_bytes < ref.payload_bytes / 4, (
        svc.payload_bytes, ref.payload_bytes)
    assert svc.stats()["grid_occupancy"] <= 0.25
    assert ref.stats()["grid_occupancy"] == 1.0


def test_keyed_equivalence_with_rmw():
    """The queued keyed path (futures, want_vsn results, the kmodify
    device fast path) resolves identically on a compacted and a
    full-width service — versions included."""
    svc, ref = make_pair(64, 3, 16, 8)
    results = []
    for s in (svc, ref):
        # elect every ensemble first (an election-only launch is
        # full width by design); the payload claim is steady-state
        w = [s.kput(e_, "warm", 1) for e_ in range(s.n_ens)]
        while any(s.queues):
            s.flush()
        assert all(f.value[0] == "ok" for f in w)
        s.payload_bytes = 0
        s.payload_bytes_full_width = 0
        futs = []
        for i in range(8):
            futs.append(s.kput(0, f"k{i}", 1000 + i))
        futs.append(s.kput(9, "x", 7))
        futs.append(s.kmodify(17, "ctr", funref.ref("rmw:add", 5), 0))
        futs.append(s.kmodify(17, "ctr", funref.ref("rmw:add", 5), 0))
        futs.append(s.kget_vsn(9, "x"))
        while any(s.queues):
            s.flush()
        # second wave: a DIFFERENT active set (ensembles 3, 17, 40)
        futs.append(s.kput(3, "y", 1))
        futs.append(s.kget(17, "ctr"))
        futs.append(s.kdelete(40, "nope"))
        while any(s.queues):
            s.flush()
        results.append([f.value for f in futs])
    assert results[0] == results[1]
    assert svc.rmw_device_fastpath > 0
    assert_engine_equal(svc, ref)
    assert svc.payload_bytes < ref.payload_bytes / 2


def test_corrupt_flag_reaches_scrub_under_compaction():
    """The corrupt mask stays FULL width: a launch compacted down to
    one active column still reports the integrity-gate failure and
    triggers the same exchange/repair a full-width pack would."""
    svc, ref = make_pair(32, 3, 8, 4)
    for s in (svc, ref):
        assert_done = []
        f = s.kput(5, "k", 42)
        while any(s.queues):
            s.flush()
        assert f.value[0] == "ok"
        # damage replica 1's leaf for ensemble 5's slot on device
        slot = s.key_slot[5]["k"]
        leaf = np.asarray(s.state.tree_leaf).copy()
        leaf[5, 1, slot] ^= 0xDEAD
        s.state = s.state._replace(tree_leaf=jnp.asarray(leaf))
        # leased fast reads never touch the device — expire the
        # leases so this read takes the (compacted) round and
        # exercises the full-width corrupt mask under test
        s.lease_until[:] = 0.0
        g = s.kget(5, "k")  # active set = {5}: maximally compacted
        while any(s.queues):
            s.flush()
        assert_done.append(g.value)
        assert g.value == ("ok", 42)
        assert s.corruptions >= 1
    assert svc.corruptions == ref.corruptions
    assert_engine_equal(svc, ref)  # exchange healed both identically
    assert svc.payload_bytes < ref.payload_bytes


# -- replication: compacted leader stream ------------------------------------


def test_repgroup_replica_applies_compacted_stream():
    """A replica lane applies a COMPACTED leader's flush stream —
    with the active set changing between flushes — and lands on the
    bit-identical state and ack CRCs, even though the replica itself
    runs the FULL-WIDTH pack layout (the layout is host-local; the
    frames ship op planes, not packed results)."""
    n_ens, n_slots, k = 16, 8, 4
    leader = BatchedEnsembleService(WallRuntime(), n_ens, 1, n_slots,
                                    tick=None, max_ops_per_tick=k)
    rsvc = BatchedEnsembleService(WallRuntime(), n_ens, 1, n_slots,
                                  tick=None, max_ops_per_tick=k)
    rsvc._compact = False  # cross-layout: leader compacts, lane not
    core = repgroup.ReplicaCore(rsvc)
    assert core.handle_promise(1)[1] is True

    frames = []
    crcs = []
    orig_enq = leader._launch_enqueue
    orig_res = leader._launch_resolve

    def spy_enqueue(kind, slot, val, k_, want_vsn, exp_e=None,
                    exp_s=None, entries=None, elect=None, cand=None,
                    lease_ok=None):
        if elect is None:
            elect, cand = leader._election_inputs()
        if lease_ok is None:
            lease_ok = leader.lease_until > leader.runtime.now
        meta = repgroup._entries_meta(entries, kind, slot,
                                      leader.values)
        frames.append(repgroup.build_apply_frame(
            1, len(frames) + 1, k_, want_vsn, elect, lease_ok,
            np.asarray(kind), np.asarray(slot), np.asarray(val),
            exp_e, exp_s, meta))
        return orig_enq(kind, slot, val, k_, want_vsn, exp_e, exp_s,
                        entries, elect, cand, lease_ok)

    def spy_resolve(fl, wait_key="device_d2h"):
        out = orig_res(fl, wait_key)
        crcs.append(repgroup.result_crc(out[0], out[4]))
        return out

    leader._launch_enqueue = spy_enqueue
    leader._launch_resolve = spy_resolve

    # flush 1: active set {0, 2} (put + device RMW)
    f1 = [leader.kput(0, "a", 11),
          leader.kmodify(2, "ctr", funref.ref("rmw:add", 3), 0)]
    while any(leader.queues):
        leader.flush()
    # flush 2: active set changes to {1, 3}
    f2 = [leader.kput(1, "b", 22), leader.kput(3, "c", 33)]
    while any(leader.queues):
        leader.flush()
    # flush 3: back to {0} with a read + overwrite
    f3 = [leader.kget(0, "a"), leader.kput(0, "a", 44)]
    while any(leader.queues):
        leader.flush()
    assert all(f.done for f in f1 + f2 + f3)
    assert f3[0].value == ("ok", 11)
    assert leader.payload_bytes < leader.payload_bytes_full_width

    for i, frame in enumerate(frames):
        ack = core.handle_apply(frame)
        assert ack[0] == "applied", ack
        assert ack[3] == crcs[i], f"CRC diverged on frame {i}"
    assert_engine_equal(leader, rsvc)
    for e in range(n_ens):
        assert leader.key_slot[e] == rsvc.key_slot[e], e
    # the committed RMW slot is device-native on BOTH lanes
    assert rsvc._inline_slots[2] == leader._inline_slots[2] != set()


# -- (K, A) warmup grid ------------------------------------------------------


def test_warmup_covers_ka_grid():
    svc = BatchedEnsembleService(WallRuntime(), 64, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    assert svc._a_ladder() == [None, 8, 16, 32]
    svc.warmup()  # full (K, A) grid; must not raise or touch state
    assert svc.flushes == 0 and not np.asarray(svc.state.obj_seq).any()
    # restricted bucket list (the surface entry points share)
    svc.warmup(buckets=[(4, 8), (4, None), (1, 8)])
    f = svc.kput(3, "k", 1)
    while any(svc.queues):
        svc.flush()
    assert f.value[0] == "ok"


def test_a_ladder_off_when_disabled():
    svc = BatchedEnsembleService(WallRuntime(), 16, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    svc._compact = False
    assert svc._a_ladder() == [None]
    svc.warmup()
    f = svc.kput(0, "k", 1)
    while any(svc.queues):
        svc.flush()
    assert f.value[0] == "ok"
    assert svc.stats()["grid_occupancy"] == 1.0


# -- WAL compaction off the hot path ----------------------------------------


def test_wal_compaction_deferred_to_idle_flush(tmp_path):
    """Under sustained load (queues never empty across a flush) the
    record bound does NOT trigger an in-line save(); the compaction
    runs on the first idle flush, with svc_compaction marks in
    stats() and the latency records."""
    svc = BatchedEnsembleService(
        WallRuntime(), 2, 1, 16, tick=None, max_ops_per_tick=2,
        data_dir=str(tmp_path), wal_compact_records=4)
    futs = [svc.kput(0, f"k{i}", i + 1) for i in range(10)]
    while any(svc.queues):
        before = svc.wal_compactions
        svc.flush()
        if any(svc.queues):
            # busy flush (work still queued): compaction must wait —
            # the old behavior saved synchronously right here
            assert svc.wal_compactions == before, \
                "compaction ran on the hot path"
    assert all(f.value[0] == "ok" for f in futs)
    # queues drained inside the last flush call -> it was idle at
    # maintenance time and past the bound, so compaction ran there
    assert svc.wal_compactions == 1
    st = svc.stats()["svc_compaction"]
    assert st["count"] == 1 and st["last_ms"] > 0
    lb = svc.latency_breakdown()
    assert lb["svc_compaction"]["p99_ms"] > 0  # visible, not averaged
    assert lb["svc_compaction"]["p50_ms"] > 0  # into launch records
    assert svc._wal.count == 0  # rotated into the checkpoint
    svc.stop()


def test_wal_compaction_hard_bound_inline(tmp_path):
    """Past the hard 2x record bound the compaction runs IN-LINE even
    while loaded — unbounded WAL growth (and restart replay time)
    must stay bounded."""
    svc = BatchedEnsembleService(
        WallRuntime(), 2, 1, 32, tick=None, max_ops_per_tick=2,
        data_dir=str(tmp_path), wal_compact_records=3)
    seen = []
    orig = svc._compact_wal
    svc._compact_wal = lambda idle: (seen.append(idle), orig(idle))
    futs = [svc.kput(0, f"k{i}", i + 1) for i in range(20)]
    while any(svc.queues):
        svc.flush()
    assert all(f.done for f in futs)
    # the first compaction fired through the 2x bound while LOADED
    # (not the idle path; save()'s own drain then emptied the queues)
    assert seen and seen[0] is False, seen
    assert svc.wal_compactions >= 1
    assert svc._wal.count <= 2 * svc.wal_compact_records
    svc.stop()


def test_restore_after_deferred_compaction(tmp_path):
    """The deferred compaction still subsumes the WAL correctly: a
    restore after idle-flush compaction sees every acked write."""
    svc = BatchedEnsembleService(
        WallRuntime(), 2, 1, 16, tick=None, max_ops_per_tick=4,
        data_dir=str(tmp_path), wal_compact_records=3)
    futs = [svc.kput(0, f"k{i}", bytes([i])) for i in range(6)]
    while any(svc.queues):
        svc.flush()
    assert all(f.value[0] == "ok" for f in futs)
    assert svc.wal_compactions >= 1
    svc.stop()
    svc2 = BatchedEnsembleService.restore(
        WallRuntime(), str(tmp_path), tick=None,
        data_dir=str(tmp_path))
    gets = [svc2.kget(0, f"k{i}") for i in range(6)]
    while any(svc2.queues):
        svc2.flush()
    assert [g.value for g in gets] == [("ok", bytes([i]))
                                       for i in range(6)]
    svc2.stop()
