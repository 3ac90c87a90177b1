"""Native single-pass resolve kernel: fuzz equivalence vs the Python
oracle (docs/ARCHITECTURE.md §12).

The contract under test is BYTE-IDENTITY: with the kernel on
(``RETPU_NATIVE_RESOLVE=1``, the default) and off, the same op stream
must produce bit-identical unpacked result planes, mirror slabs
(``_slot_vsn``/``_inline_value``), WAL store bytes, and delta-frame
sections.  The Python implementations are the oracle; the kernel is
an optimization, never a semantic.
"""

import contextlib
import os
import pickle
import zlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")

from riak_ensemble_tpu import funref
from riak_ensemble_tpu.ops import engine as eng
from riak_ensemble_tpu.parallel import repgroup, resolve_native
from riak_ensemble_tpu.parallel.batched_host import (
    BatchedEnsembleService, Future, WallRuntime, _PendingBatch,
    _PendingOp, unpack_results,
)

needs_kernel = pytest.mark.skipif(
    resolve_native.get() is None,
    reason="native resolve kernel unavailable (no toolchain)")


def _pack_reference(won, quorum, corrupt, committed, get_ok, found,
                    value, vsn, want_vsn):
    """Host-side replica of ``engine.pack_results``' layout (the d2h
    payload the kernel unpacks)."""
    flags = np.concatenate(
        [won.ravel(), quorum.ravel(), corrupt.ravel(),
         committed.ravel(), get_ok.ravel(),
         found.ravel()]).astype(bool)
    ints = [value.ravel().astype(np.int32)]
    if want_vsn:
        ints += [vsn[..., 0].ravel().astype(np.int32),
                 vsn[..., 1].ravel().astype(np.int32)]
    return np.concatenate([np.packbits(flags),
                           np.concatenate(ints).view(np.uint8)])


# -- 1) packed-result unpack -------------------------------------------------


@needs_kernel
@pytest.mark.parametrize("seed", range(3))
def test_unpack_fuzz_equivalence(seed):
    """Random packed planes through native vs Python unpack: every
    returned plane bit-identical across full-width, compacted
    (pack-gather) and sliced [K, A] layouts, want_vsn on and off.
    The quorum plane is E wide in all three (a sliced launch reports
    the columns it did not step too) and comes back as packed."""
    nr = resolve_native.get()
    rng = np.random.default_rng(seed)
    for trial in range(60):
        e = int(rng.integers(4, 48))
        m = int(rng.integers(1, 6))
        k = int(rng.integers(0, 10))
        want_vsn = bool(rng.integers(0, 2))
        mode = int(rng.integers(0, 3))  # full / pack-gather / sliced
        if mode == 0:
            active, aw, sliced = None, e, False
        else:
            na = int(rng.integers(1, e))
            active = np.sort(
                rng.choice(e, na, replace=False)).astype(np.int32)
            aw = 8
            while aw < na:
                aw <<= 1
            aw = max(min(aw, e), na)
            sliced = mode == 2
        hw = aw if (sliced and active is not None) else e
        won = rng.integers(0, 2, hw).astype(bool)
        quorum = rng.integers(0, 2, e).astype(bool)
        corrupt = rng.integers(0, 2, (hw, m)).astype(bool)
        committed = rng.integers(0, 2, (k, aw)).astype(bool)
        get_ok = rng.integers(0, 2, (k, aw)).astype(bool)
        found = rng.integers(0, 2, (k, aw)).astype(bool)
        value = rng.integers(-2**31, 2**31, (k, aw)).astype(np.int32)
        vsn = rng.integers(0, 2**31, (k, aw, 2)).astype(np.int32)
        flat = _pack_reference(won, quorum, corrupt, committed,
                               get_ok, found, value, vsn, want_vsn)
        a_width = 0 if active is None else aw
        ref = unpack_results(flat, e, m, k, want_vsn, active=active,
                             a_width=a_width, sliced=sliced)
        nat = nr.unpack(flat, e, m, k, want_vsn, active, a_width,
                        sliced)
        assert nat is not None
        for name, a, b in zip(
                ("won", "quorum", "corrupt", "committed", "get_ok",
                 "found", "value", "vsn"), ref, nat):
            if a is None:
                assert b is None, name
                continue
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                (seed, trial, name, mode)
        assert np.array_equal(nat[1], quorum), (seed, trial, mode)


@needs_kernel
@pytest.mark.parametrize("e,na,aw,k", [
    (10, 1, 8, 1),      # one hot column, a byte-straddling quorum row
    (300, 5, 8, 1),     # the benchmark's shape: K 1, A 8
    (257, 33, 64, 3),   # E not a multiple of 8
    (64, 16, 16, 0),    # election-only: control planes alone
])
def test_unpack_sliced_quorum_full_width(e, na, aw, k):
    """A sliced launch's payload: ``won`` and the corrupt mask A wide
    and scattered through the active list, the quorum plane between
    them E wide and returned as packed, idle columns' bits included;
    native and numpy bit-identical."""
    nr = resolve_native.get()
    rng = np.random.default_rng(e + na)
    m = 3
    active = np.sort(rng.choice(e, na, replace=False)).astype(np.int32)
    won = rng.integers(0, 2, aw).astype(bool)
    quorum = rng.integers(0, 2, e).astype(bool)
    quorum[np.setdiff1d(np.arange(e), active)[:2]] = True
    corrupt = rng.integers(0, 2, (aw, m)).astype(bool)
    bits = [rng.integers(0, 2, (k, aw)).astype(bool) for _ in range(3)]
    value = rng.integers(-2**31, 2**31, (k, aw)).astype(np.int32)
    vsn = rng.integers(0, 2**31, (k, aw, 2)).astype(np.int32)
    flat = _pack_reference(won, quorum, corrupt, *bits, value, vsn, True)
    ref = unpack_results(flat, e, m, k, True, active=active,
                         a_width=aw, sliced=True)
    nat = nr.unpack(flat, e, m, k, True, active, aw, True)
    assert nat is not None
    for name, a, b in zip(("won", "quorum", "corrupt", "committed",
                           "get_ok", "found", "value", "vsn"), ref, nat):
        if a is None:
            assert b is None, name
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert np.array_equal(nat[1], quorum)
    want_won = np.zeros(e, bool)
    want_won[active] = won[:na]
    assert np.array_equal(nat[0], want_won)
    # one bit short of the layout is refused, not misread
    assert nr.unpack(flat[:-1], e, m, k, True, active, aw, True) is None


@needs_kernel
def test_unpack_rejects_short_payload():
    """A truncated payload returns None (the caller falls back to the
    Python unpack, which raises the honest shape error)."""
    nr = resolve_native.get()
    assert nr.unpack(np.zeros((3,), np.uint8), 16, 3, 4, True, None,
                     0, False) is None


# -- 2/3) service-level equivalence (mirrors + WAL bytes) --------------------


def _workload(svc, rng, n_ens, k, rounds):
    """A mixed keyed op stream: batched puts/gets/CAS/deletes, scalar
    puts/gets (incl. want_vsn), device RMWs (inline slots) and
    RMW-to-zero tombstones.  Returns every future's resolved value in
    issue order (the client-visible half of the equivalence)."""
    out = []
    futs = []
    add1 = funref.ref("rmw:add", 1)
    set_zero = funref.ref("rmw:set", 0)
    for r in range(rounds):
        for e in range(n_ens):
            keys = [f"k{(r + i) % 11}" for i in range(k)]
            vals = [b"v%d.%d" % (r, i) for i in range(k)]
            if r == 2 and e == 0:
                # >= 64 KiB payload: CPython pickles it OUT of the
                # frame, so the native WAL arm must route this flush
                # to the Python encoder (byte-identity regression)
                vals[0] = b"P" * (1 << 16)
            pick = rng.integers(0, 7)
            if pick == 0:
                futs.append(svc.kput_many(e, keys, vals))
            elif pick == 1:
                futs.append(svc.kget_many(
                    e, keys, want_vsn=bool(rng.integers(0, 2))))
            elif pick == 2:
                futs.append(svc.kupdate_many(
                    e, keys[:2], [(0, 0), (0, 0)], vals[:2]))
            elif pick == 3:
                futs.append(svc.kdelete_many(e, keys[:3]))
            elif pick == 4:
                futs.append(svc.kmodify(e, f"ctr{r % 3}", add1, 0))
            elif pick == 5:
                # tombstone RMW: a computed 0 recycles the slot
                futs.append(svc.kmodify(e, f"ctr{r % 3}", set_zero, 0))
            else:
                futs.append(svc.kput(e, keys[0], vals[0]))
                futs.append(svc.kget(e, keys[1]))
        while any(svc.queues):
            svc.flush()
    svc.flush()
    for f in futs:
        assert f.done
        out.append(f.value)
    return out


def _run_arm(tmp_path, arm, seed, monkeypatch, wal=True):
    monkeypatch.setenv("RETPU_NATIVE_RESOLVE", arm)
    monkeypatch.setenv("RETPU_FAST_READS", "0")  # every read = round
    rng = np.random.default_rng(seed)
    kw = (dict(data_dir=str(tmp_path / f"arm{arm}"),
               wal_sync="buffer") if wal else {})
    svc = BatchedEnsembleService(WallRuntime(), 8, 3, 16, tick=None,
                                 max_ops_per_tick=8, **kw)
    if arm == "1" and resolve_native.get() is not None:
        assert svc._native_resolve is not None
    results = _workload(svc, rng, 8, 4, rounds=6)
    state = {
        "results": results,
        "vsn_ok": svc._slot_vsn_ok.copy(),
        "vsn_np": svc._slot_vsn_np.copy(),
        "inl_ok": svc._inline_value_ok.copy(),
        "inl_np": svc._inline_value_np.copy(),
        "inline_np": svc._inline_np.copy(),
        "inline_sets": [sorted(s) for s in svc._inline_slots],
        "native_flushes": svc.native_resolve_flushes,
        "fallback_flushes": svc.fallback_resolve_flushes,
    }
    if wal:
        state["wal_records"] = sorted(
            map(repr, svc._wal.records()))
        wal_dir = svc._wal.dir_path
        state["wal_files"] = {
            name: open(os.path.join(wal_dir, name), "rb").read()
            for name in sorted(os.listdir(wal_dir))}
    svc.stop()
    return state


@needs_kernel
@pytest.mark.parametrize("seed", range(2))
def test_service_equivalence_native_vs_fallback(tmp_path, seed,
                                                monkeypatch):
    """The whole resolve half, end to end: an identical mixed op
    stream through a native-arm and a fallback-arm service must yield
    identical client results, BIT-IDENTICAL mirror slabs, identical
    inline storage-class sets/slab, and byte-identical WAL files."""
    a = _run_arm(tmp_path, "1", seed, monkeypatch)
    b = _run_arm(tmp_path, "0", seed, monkeypatch)
    assert a["native_flushes"] > 0, "native arm never took the kernel"
    assert b["native_flushes"] == 0 and b["fallback_flushes"] > 0
    assert a["results"] == b["results"]
    assert np.array_equal(a["vsn_ok"], b["vsn_ok"])
    assert np.array_equal(a["vsn_np"][a["vsn_ok"]],
                          b["vsn_np"][b["vsn_ok"]])
    assert np.array_equal(a["inl_ok"], b["inl_ok"])
    assert np.array_equal(a["inl_np"][a["inl_ok"]],
                          b["inl_np"][b["inl_ok"]])
    assert np.array_equal(a["inline_np"], b["inline_np"])
    assert a["inline_sets"] == b["inline_sets"]
    assert a["wal_records"] == b["wal_records"]
    # byte-identity of the store files is the strongest form of the
    # WAL contract: the arena path appended the very same bytes
    assert a["wal_files"].keys() == b["wal_files"].keys()
    for name in a["wal_files"]:
        assert a["wal_files"][name] == b["wal_files"][name], name


@needs_kernel
def test_inline_set_slab_coherence(tmp_path, monkeypatch):
    """The `_inline_np` storage-class slab must mirror the
    `_inline_slots` sets exactly after a mixed workload (the kernel
    routes leased-GET refreshes through the slab)."""
    monkeypatch.setenv("RETPU_NATIVE_RESOLVE", "1")
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 16, tick=None,
                                 max_ops_per_tick=8)
    _workload(svc, np.random.default_rng(7), 4, 4, rounds=4)
    for e in range(4):
        assert set(np.flatnonzero(svc._inline_np[e]).tolist()) == \
            svc._inline_slots[e], e
    svc.stop()


@needs_kernel
def test_large_payload_falls_back_byte_identical(tmp_path,
                                                 monkeypatch):
    """A >= 64 KiB payload pickles out-of-frame in CPython; the
    native WAL arm must fall back for that flush and the store bytes
    must still match the oracle arm exactly."""
    files = {}
    for arm in ("1", "0"):
        monkeypatch.setenv("RETPU_NATIVE_RESOLVE", arm)
        d = str(tmp_path / f"big{arm}")
        svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8,
                                     tick=None, max_ops_per_tick=4,
                                     data_dir=d, wal_sync="buffer")
        futs = [svc.kput_many(0, ["big", "small"],
                              [b"B" * 70000, b"s"]),
                svc.kput_many(1, ["x"], [b"y"])]
        while any(svc.queues):
            svc.flush()
        assert all(r[0] == "ok" for f in futs for r in f.value)
        wal_dir = svc._wal.dir_path
        files[arm] = {
            name: open(os.path.join(wal_dir, name), "rb").read()
            for name in sorted(os.listdir(wal_dir))}
        svc.stop()
    assert files["1"].keys() == files["0"].keys()
    for name in files["1"]:
        assert files["1"][name] == files["0"][name], name


def test_exotic_keys_take_python_wal_path(tmp_path, monkeypatch):
    """Keys outside the kernel's pickle subset (tuples, non-ascii
    strs, ints) must fall back to the Python WAL encode — and restore
    correctly either way."""
    monkeypatch.setenv("RETPU_NATIVE_RESOLVE", "1")
    d = str(tmp_path / "svc")
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4, data_dir=d,
                                 wal_sync="buffer")
    futs = [svc.kput_many(0, [("tup", 1), "κλειδί", 7],
                          [b"a", b"b", b"c"]),
            svc.kput_many(1, ["plain"], [b"d"])]
    while any(svc.queues):
        svc.flush()
    assert all(r[0] == "ok" for f in futs for r in f.value)
    svc.stop()
    svc2 = BatchedEnsembleService.restore(WallRuntime(), d, tick=None)
    for e, key, want in ((0, ("tup", 1), b"a"), (0, "κλειδί", b"b"),
                         (0, 7, b"c"), (1, "plain", b"d")):
        f = svc2.kget(e, key)
        while not f.done:
            svc2.flush()
        assert f.value == ("ok", want), (key, f.value)
    svc2.stop()


# -- 4) delta-frame sections -------------------------------------------------


@needs_kernel
@pytest.mark.parametrize("seed", range(3))
def test_delta_entry_fuzz_equivalence(seed):
    """build_delta_entry with the kernel vs the numpy pipeline:
    identical section bytes, dtypes, CRC and byte count over random
    committed/kind/slot/value planes (wide and narrow index dtypes,
    empty planes included)."""
    nr = resolve_native.get()
    rng = np.random.default_rng(seed)
    for trial in range(40):
        e = int(rng.integers(2, 300))
        k = int(rng.integers(1, 18))
        n_slots = int(rng.choice([16, 300]))
        committed = rng.integers(0, 2, (k, e)).astype(bool)
        if trial % 6 == 0:
            committed[:] = False
        value = rng.integers(-1000, 1000, (k, e)).astype(np.int32)
        kind = rng.choice(
            [eng.OP_NOOP, eng.OP_PUT, eng.OP_GET, eng.OP_CAS,
             eng.OP_RMW], (k, e)).astype(np.int32)
        slot = rng.integers(0, n_slots, (k, e)).astype(np.int32)
        val = rng.integers(0, 1 << 20, (k, e)).astype(np.int32)
        quorum = rng.integers(0, 2, e).astype(bool)
        ref_e, ref_crc, ref_n = repgroup.build_delta_entry(
            3, k, committed, value, kind, slot, val, quorum, [],
            n_slots=n_slots, fid=9, native=None)
        nat_e, nat_crc, nat_n = repgroup.build_delta_entry(
            3, k, committed, value, kind, slot, val, quorum, [],
            n_slots=n_slots, fid=9, native=nr)
        assert nat_crc == ref_crc and nat_n == ref_n, (seed, trial)
        assert len(nat_e) == len(ref_e)
        for i, (x, y) in enumerate(zip(ref_e, nat_e)):
            if hasattr(x, "buf"):  # wire.Raw
                xa = np.frombuffer(x.buf, np.uint8)
                ya = np.frombuffer(y.buf, np.uint8)
                assert np.array_equal(xa, ya), (seed, trial, i)
            else:
                assert x == y, (seed, trial, i)


# -- 5) WAL pickle subset ----------------------------------------------------


@needs_kernel
def test_wal_encode_pickle_byte_identity():
    """The kernel's protocol-4 pickle templates vs pickle.dumps for
    the routed subset: short/long str keys, bytes/None payloads, the
    K/M/J int ranges, inline True/False."""
    nr = resolve_native.get()
    rng = np.random.default_rng(11)
    e_total, k = 9, 5
    cases = [
        ("a", b""), ("key%d" % 7, b"x" * 3), ("L" * 300, b"y" * 400),
        ("k", None), ("m" * 255, b"z"),
    ]
    n = len(cases)
    lane_j = rng.integers(0, k, n).astype(np.int32)
    lane_e = rng.integers(0, e_total, n).astype(np.int32)
    lane_slot = np.asarray([0, 255, 256, 65535, 65536], np.int32)
    lane_f2 = np.asarray([0, 1, 255, 65535, 2**31 - 1], np.int32)
    lane_inline = np.asarray([0, 1, 0, 1, 0], np.uint8)
    committed = np.ones((k, e_total), bool)
    value = rng.integers(-2**31, 2**31, (k, e_total)).astype(np.int32)
    vsn = rng.integers(0, 2**31, (k, e_total, 2)).astype(np.int32)
    keys = [c[0] for c in cases]
    pays = [c[1] for c in cases]
    key_len = np.asarray([len(s) for s in keys], np.int64)
    key_off = np.zeros((n,), np.int64)
    np.cumsum(key_len[:-1], out=key_off[1:])
    pay_len = np.asarray([-1 if p is None else len(p)
                          for p in pays], np.int64)
    pay_off = np.zeros((n,), np.int64)
    np.cumsum(np.maximum(pay_len, 0)[:-1], out=pay_off[1:])
    arena, idx = nr.wal_encode(
        e_total, lane_j, lane_e, lane_slot, lane_f2, lane_inline,
        np.zeros((n,), np.uint8), key_off, key_len,
        "".join(keys).encode(), pay_off, pay_len,
        b"".join(p for p in pays if p is not None),
        committed, value, vsn)
    raw = arena.tobytes()
    for i in range(n):
        j, e = int(lane_j[i]), int(lane_e[i])
        ko, kl, vo, vl = idx[i].tolist()
        kref = pickle.dumps(("kv", e, int(lane_slot[i])), protocol=4)
        f2 = int(value[j, e]) if lane_inline[i] else int(lane_f2[i])
        vref = pickle.dumps(
            (keys[i], f2, int(vsn[j, e, 0]), int(vsn[j, e, 1]),
             pays[i], bool(lane_inline[i])), protocol=4)
        assert raw[ko:ko + kl] == kref, i
        assert raw[vo:vo + vl] == vref, i
        assert pickle.loads(raw[vo:vo + vl]) == (
            keys[i], f2, int(vsn[j, e, 0]), int(vsn[j, e, 1]),
            pays[i], bool(lane_inline[i]))


# -- 5b) the Python WAL arm: the arena's oracle ------------------------------
#
# _log_wal's Python arm reads the result planes at the flush's own
# write lanes only.  What it hands ServiceWAL.log is compared with a
# reference built the plain way, from the planes' own tolist().


class _WalRecorder:
    """Stands in for ServiceWAL: keeps what each log() was handed."""

    def __init__(self):
        self.calls = []

    def log(self, records):
        self.calls.append(records)


def _scalar(kind, slot, handle=0, key=None):
    return _PendingOp(kind, slot, handle, Future(), key=key)


def _batch(kind, slots, handles, keys=None):
    return _PendingBatch(kind, list(slots), list(handles), Future(),
                         pos=list(range(len(slots))), keys=keys,
                         n=len(slots))


def _planes(rng, taken, n_ens, p_commit):
    k = max(sum(op.n for op in ops) for _e, ops in taken)
    committed = rng.random((k, n_ens)) < p_commit
    value = rng.integers(-2**31, 2**31, (k, n_ens)).astype(np.int32)
    vsn = rng.integers(0, 2**31, (k, n_ens, 2)).astype(np.int32)
    return committed, value, vsn


def _reference_records(svc, taken, committed, value, vsn):
    """Every committed write lane of the walk, in the walk's order,
    from full listings of the planes."""
    comm_l, value_l, vsn_l = (committed.tolist(), value.tolist(),
                              vsn.tolist())
    recs = []
    for e, ops in taken:
        j = -1
        for op in ops:
            batch = isinstance(op, _PendingBatch)
            for i in range(op.n):
                j += 1
                if op.kind == eng.OP_GET or not comm_l[j][e]:
                    continue
                key = op.keys[i] if batch else op.key
                slot = op.slot[i] if batch else op.slot
                ve, vs = vsn_l[j][e]
                if op.kind == eng.OP_RMW:
                    recs.append((("kv", e, slot),
                                 (key, value_l[j][e], ve, vs, None,
                                  True)))
                    continue
                h = op.handle[i] if batch else op.handle
                recs.append((("kv", e, slot),
                             (key, h, ve, vs,
                              svc.values.get(h) if h else None, False)))
    return recs


def _taken_scalars():
    """Scalar put / cas / rmw / get lanes over three ensembles, K=4."""
    P, C, R, G = eng.OP_PUT, eng.OP_CAS, eng.OP_RMW, eng.OP_GET
    return [
        (1, [_scalar(P, 0, 1, "a"), _scalar(G, 0, key="a"),
             _scalar(C, 1, 2, "b"), _scalar(R, 2, 7, "c")]),
        (5, [_scalar(R, 3, -4, "d"), _scalar(P, 3, 0, "d")]),
        (6, [_scalar(G, 1, key="e")]),
    ]


def _taken_interleaved():
    """Scalar and batch writes on the SAME (ens, slot), interleaved:
    latest-per-key rests on the walk's order."""
    P, C, R, G = eng.OP_PUT, eng.OP_CAS, eng.OP_RMW, eng.OP_GET
    return [
        (2, [_scalar(P, 3, 1, "k3"),
             _batch(P, [3, 4], [2, 0], ["k3", "k4"]),
             _scalar(R, 3, 9, "k3"),
             _batch(R, [3, 5], [1, 1], ["k3", "k5"]),
             _batch(G, [3, 4], [0, 0]),
             _scalar(C, 4, 3, ("tuple", "key")),
             _batch(C, [4], [1], [("tuple", "key")]),
             _scalar(P, 4, 0, "k4")]),
        (0, [_batch(P, [0, 1, 2], [3, 1, 2], ["x", "y", "z"]),
             _scalar(G, 0, key="x"), _scalar(P, 0, 3, "x")]),
    ]


def _taken_reads_only():
    G = eng.OP_GET
    return [(0, [_scalar(G, 0, key="x"), _batch(G, [1, 2], [0, 0])]),
            (3, [_scalar(G, 1, key="y")])]


@contextlib.contextmanager
def _python_wal_arm(native=False):
    """A service whose WAL is a recorder; ``native`` leaves the arena
    arm in place (it hands a flush with a scalar write lane back)."""
    svc = BatchedEnsembleService(WallRuntime(), 8, 3, 8, tick=None)
    try:
        if not native:
            svc._native_resolve = None
        svc._wal = wal = _WalRecorder()
        svc.values.update({1: b"one", 2: b"", 3: b"three" * 40})
        yield svc, wal
    finally:
        svc._wal = None
        svc.stop()


_WAL_ARM_CASES = {
    # name: (taken, share of lanes committed, extra records, native)
    "scalars": (_taken_scalars, 0.7, [], False),
    "scalars_all_committed": (_taken_scalars, 1.0, [], False),
    "interleaved": (_taken_interleaved, 0.7, [], False),
    "interleaved_all_committed": (_taken_interleaved, 1.0, [], False),
    "nothing_committed": (_taken_interleaved, 0.0, [], False),
    "reads_only": (_taken_reads_only, 1.0, [], False),
    "extra_records": (_taken_interleaved, 0.7,
                      [(("grp", "meta"), (7, 1, 42, None))], False),
    # the native arm hands a flush with a scalar write lane back
    "native_hands_back": (_taken_interleaved, 0.7, [], True),
}


@pytest.mark.parametrize("case", sorted(_WAL_ARM_CASES))
def test_python_wal_arm_matches_full_listing(case, monkeypatch):
    """The list handed to ServiceWAL.log: same records, same order,
    same Python types (each key and value pickles to the same bytes,
    as the store pickles them) as the full-listing reference, then
    the subclass's extra records, in ONE call; none when nothing
    committed."""
    make_taken, p_commit, extra, native = _WAL_ARM_CASES[case]
    if native and resolve_native.get() is None:
        pytest.skip("native resolve kernel unavailable")
    with _python_wal_arm(native) as (svc, wal):
        monkeypatch.setattr(svc, "_wal_extra_records",
                            lambda: list(extra))
        for seed in range(4):
            taken = make_taken()
            committed, value, vsn = _planes(
                np.random.default_rng(seed), taken, 8, p_commit)
            ref = _reference_records(svc, taken, committed, value, vsn)
            del wal.calls[:]
            svc._log_wal(taken, (committed, None, None, value, vsn))
            if not ref:
                assert wal.calls == []
                continue
            assert len(wal.calls) == 1
            got = wal.calls[0]
            assert got == ref + extra
            for (gk, gv), (rk, rv) in zip(got, ref + extra):
                assert pickle.dumps(gk, 4) == pickle.dumps(rk, 4)
                assert pickle.dumps(gv, 4) == pickle.dumps(rv, 4)


class _WatchedPlane(np.ndarray):
    """A result plane that notes the size of everything derived from
    it (views, gathers, copies) and of every listing or nonzero
    taken, while ``seen`` is a list."""

    seen = None

    def _note(self):
        if _WatchedPlane.seen is not None:
            _WatchedPlane.seen.append(self.size)

    def __array_finalize__(self, obj):
        self._note()

    def tolist(self):
        self._note()
        return super().tolist()

    def nonzero(self):
        self._note()
        return super().nonzero()


def test_python_wal_arm_never_materialises_a_plane(monkeypatch):
    """At E = 4,096 the arm touches nothing wider than the flush: no
    listing, nonzero, gather, view or copy larger than the flush's
    write lanes (x2: a lane's version is a pair)."""
    taken = [(e + 1000, ops)
             for e, ops in _taken_interleaved() + _taken_scalars()]
    write_lanes = sum(op.n for _e, ops in taken for op in ops
                      if op.kind != eng.OP_GET)
    with _python_wal_arm() as (svc, wal):
        committed, value, vsn = _planes(np.random.default_rng(3),
                                        taken, 4096, 0.8)
        ref = _reference_records(svc, taken, committed, value, vsn)
        watched = [a.view(_WatchedPlane) for a in (committed, value, vsn)]
        monkeypatch.setattr(_WatchedPlane, "seen", [])
        svc._log_wal(taken, (watched[0], None, None, watched[1],
                             watched[2]))
        seen = _WatchedPlane.seen
    assert wal.calls == [ref]
    assert seen and max(seen) <= 2 * write_lanes, (max(seen),
                                                   write_lanes)
    assert committed.size > 100 * write_lanes


# -- 6) degradation ----------------------------------------------------------


def test_knob_pins_fallback(monkeypatch):
    """RETPU_NATIVE_RESOLVE=0 pins the Python arm at construction."""
    monkeypatch.setenv("RETPU_NATIVE_RESOLVE", "0")
    assert resolve_native.get() is None
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    assert svc._native_resolve is None
    f = svc.kput(0, "k", b"v")
    while not f.done:
        svc.flush()
    assert f.value[0] == "ok"
    assert svc.fallback_resolve_flushes > 0
    assert svc.native_resolve_flushes == 0
    svc.stop()


def test_missing_so_degrades_to_python(monkeypatch):
    """A missing/unbuildable kernel .so must mean the Python fallback
    — never a crash, never a test failure (the satellite's graceful-
    degradation contract).  Simulated by pinning the loader's memo to
    'tried and failed'."""
    monkeypatch.setenv("RETPU_NATIVE_RESOLVE", "1")
    monkeypatch.setattr(resolve_native, "_instance", None)
    monkeypatch.setattr(resolve_native, "_instance_tried", True)
    assert resolve_native.get() is None
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    assert svc._native_resolve is None
    f = svc.kput(0, "k", b"v")
    g = svc.kget(0, "k")
    while not (f.done and g.done):
        svc.flush()
    assert f.value[0] == "ok" and g.value == ("ok", b"v")
    svc.stop()
