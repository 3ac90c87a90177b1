"""Commutative replication lane (ISSUE 18 tentpole).

Table-fun RMWs whose funs commute (add/sub) or form a semilattice
(max/min/band/bor) replicate as per-(ensemble, slot) COALESCED merge
cells — a "m" wire entry carrying a merge section next to the ordered
delta half — applied by replicas as a lattice merge against their own
current value, with a pure-merge frame acked after the WAL sync but
before the device scatter (the §18 early ack).  These tests pin the
load-bearing contracts:

- classification: set/bxor/put_if_absent stay ORDERED; the fold is
  int32-exact (sub normalizes into add of the negated operand);
- build_comm_entry qualification: a column ships merge cells only
  when EVERY committed cell is a mergeable RMW and each slot sees a
  single merge class — anything else falls to the ordered half,
  and the native C fold is byte-identical to the Python fold;
- the replica apply: merge sections carry their own CRC, all-or-
  nothing with the run; version vectors land bit-equal to the
  sequenced apply (the delta-lane equivalence harness);
- RETPU_COMM_REPL=0 is the ordered oracle arm: zero "m" entries,
  same results, same final KV state;
- kmodify_many enqueue-side coalescing: duplicate commutative keys
  fold into one device row whose shared version is CAS-usable;
- ServiceClient never auto-retries kmodify/kmodify_many on an
  ambiguous disconnect (early acks make RMW storms the hot
  ambiguous-drop shape — a silent retry would double-apply);
- randomized convergence: drop/RTT churn + a replica_apply_pre_ack
  crash-kill, with CounterModel holding the final-sum obligation
  across restart and handoff.
"""

import asyncio
import os
import struct
import time

import numpy as np
import pytest

import conftest  # noqa: F401

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import faults, funref, svcnode, wire  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.linearizability import (  # noqa: E402
    CounterModel, KeyModel)
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup, resolve_native  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402

from test_repl_delta import (  # noqa: E402
    _assert_lanes_equal, _group, _plain_core, _settle, _stop)

N_ENS = 4
N_SLOTS = 8


def _counter_val(res):
    """kget result -> int counter value (engine encodes 0 as
    NOTFOUND — the inline tombstone convention)."""
    assert res[0] == "ok", res
    return 0 if res[1] is NOTFOUND or res[1] == NOTFOUND else int(res[1])


# -- classification ----------------------------------------------------------


def test_funref_classification_table():
    """The §18 classification is a frozen contract: commutative and
    semilattice funs merge, everything order-sensitive stays ordered
    — set (last-writer), bxor (self-inverse: merging would lose
    parity of application count) and put_if_absent (first-writer)."""
    assert funref.merge_class(funref.RMW_ADD) == funref.MERGE_ADD
    assert funref.merge_class(funref.RMW_SUB) == funref.MERGE_ADD
    assert funref.merge_class(funref.RMW_MAX) == funref.MERGE_MAX
    assert funref.merge_class(funref.RMW_MIN) == funref.MERGE_MIN
    assert funref.merge_class(funref.RMW_BAND) == funref.MERGE_AND
    assert funref.merge_class(funref.RMW_BOR) == funref.MERGE_OR
    for code in (funref.RMW_SET, funref.RMW_BXOR, funref.RMW_PIA):
        assert funref.merge_class(code) is None
        assert funref.RMW_CLASS[code] == funref.ORDERED
    # the replication-side mergeable LUT agrees with the table
    for code in range(9):
        assert bool(repgroup._RMW_MERGEABLE[code]) == \
            (funref.merge_class(code) is not None), code


def test_fold_int32_exact_and_sub_normalization():
    """The fold lives in int32-wraparound arithmetic — bit-equal to
    chaining the ops through the engine — and sub folds as add of
    the negated operand (one merge class per slot)."""
    i32 = funref.i32
    # wraparound: INT32_MAX + 1 folds to INT32_MIN
    acc = funref.fold_seed(funref.RMW_ADD, 2 ** 31 - 1)
    acc = funref.fold_operand(funref.RMW_ADD, acc, 1)
    assert acc == -2 ** 31
    # sub seeds negated, folds negated: cur - a - b == cur + (-(a+b))
    acc = funref.fold_seed(funref.RMW_SUB, 5)
    assert acc == -5
    acc = funref.fold_operand(funref.RMW_SUB, acc, 7)
    assert acc == -12
    assert funref.merge_apply(funref.MERGE_ADD, 100, acc) == 88
    # INT32_MIN negation wraps onto itself — still exact
    assert funref.fold_seed(funref.RMW_SUB, -2 ** 31) == -2 ** 31
    # semilattice folds are idempotent
    acc = funref.fold_seed(funref.RMW_MAX, 9)
    acc = funref.fold_operand(funref.RMW_MAX, acc, 9)
    assert acc == 9
    assert funref.merge_apply(funref.MERGE_MAX, 12, acc) == 12
    assert funref.merge_apply(funref.MERGE_AND, 0b1101, 0b0111) == 0b0101
    assert funref.merge_apply(funref.MERGE_OR, 0b1000, 0b0011) == 0b1011
    assert i32(2 ** 31) == -2 ** 31


# -- build_comm_entry qualification ------------------------------------------


def _comm_arrays(k=4):
    committed = np.zeros((k, N_ENS), bool)
    kind = np.zeros((k, N_ENS), np.int32)
    slot = np.zeros((k, N_ENS), np.int32)
    val = np.zeros((k, N_ENS), np.int32)
    exp_e = np.zeros((k, N_ENS), np.int32)
    value = np.zeros((k, N_ENS), np.int32)
    q = np.ones((N_ENS,), bool)
    return committed, kind, slot, val, exp_e, value, q


def test_build_comm_entry_qualification_and_coalescing():
    """Column 1 (all adds, two slots) ships 2 merge cells for 4 ops;
    column 2 (add-then-max on ONE slot: mixed classes) and column 3
    (ordered set) stay in the ordered half byte-for-byte."""
    committed, kind, slot, val, exp_e, value, q = _comm_arrays()
    # col 1: adds — rows 0..2 hit slot 3, row 3 hits slot 5
    for j, (s, v) in enumerate([(3, 5), (3, 9), (3, -2), (5, 7)]):
        committed[j, 1] = True
        kind[j, 1] = eng.OP_RMW
        exp_e[j, 1] = funref.RMW_ADD
        slot[j, 1] = s
        val[j, 1] = v
    # col 2: add then max on the SAME slot — mixed classes, ordered
    for j, code in enumerate([funref.RMW_ADD, funref.RMW_MAX]):
        committed[j, 2] = True
        kind[j, 2] = eng.OP_RMW
        exp_e[j, 2] = code
        slot[j, 2] = 4
        val[j, 2] = 10 + j
    # col 3: a single ordered set — never a candidate
    committed[0, 3] = True
    kind[0, 3] = eng.OP_RMW
    exp_e[0, 3] = funref.RMW_SET
    slot[0, 3] = 2
    val[0, 3] = 77

    out = repgroup.build_comm_entry(
        1, 4, committed, value, kind, slot, val, exp_e, q, [],
        n_slots=N_SLOTS)
    assert out is not None
    entry, crc, nbytes, n_cells, n_ops = out
    assert entry[0] == "m" and n_cells == 2 and n_ops == 4
    # ordered half keeps the 3 disqualified cells (col 2 + col 3)
    assert int(entry[3]) == 3
    ord_cols = np.frombuffer(entry[6].buf, np.uint16)
    assert 1 not in ord_cols.tolist()
    # merge section: one column, two cells in first-seen slot order,
    # folded operands, rank/j of each slot's LAST committed op
    assert int(entry[14]) == 2
    assert np.frombuffer(entry[15].buf, np.uint16).tolist() == [1]
    assert np.frombuffer(entry[16].buf, np.uint16).tolist() == [2]
    assert np.frombuffer(entry[17].buf, np.uint16).tolist() == [4]
    assert np.frombuffer(entry[18].buf, np.uint8).tolist() == [3, 5]
    assert np.frombuffer(entry[19].buf, np.uint8).tolist() == \
        [funref.MERGE_ADD, funref.MERGE_ADD]
    assert np.frombuffer(entry[20].buf, np.int32).tolist() == [12, 7]
    assert np.frombuffer(entry[21].buf, np.uint8).tolist() == [2, 3]
    assert np.frombuffer(entry[22].buf, np.uint8).tolist() == [2, 3]
    # the ack CRC chains both halves
    assert crc == repgroup._crc_chain(int(entry[13]), int(entry[23]))

    # no mergeable column at all -> None (the caller ships plain "d",
    # which is what keeps the off arm byte-identical by construction)
    committed[:, 1] = False
    assert repgroup.build_comm_entry(
        1, 4, committed, value, kind, slot, val, exp_e, q, [],
        n_slots=N_SLOTS) is None
    # a put anywhere in an otherwise-mergeable column disqualifies it
    committed2, kind2, slot2, val2, exp_e2, value2, q2 = _comm_arrays()
    committed2[0, 0] = committed2[1, 0] = True
    kind2[0, 0] = eng.OP_RMW
    exp_e2[0, 0] = funref.RMW_ADD
    kind2[1, 0] = eng.OP_PUT
    assert repgroup.build_comm_entry(
        1, 4, committed2, value2, kind2, slot2, val2, exp_e2, q2, [],
        n_slots=N_SLOTS) is None


def _entry_canon(entry):
    return [bytes(x.buf) if isinstance(x, wire.Raw) else x
            for x in entry]


def test_build_comm_entry_native_python_parity():
    """The C fold (resolvekernel.cc retpu_comm_fold) and the Python
    fold must emit byte-identical entries over randomized committed
    planes — mixed fun codes, repeated slots, disqualified columns."""
    nat = resolve_native.get()
    if nat is None:
        pytest.skip("native resolve library unavailable")
    rng = np.random.default_rng(1808)
    built = 0
    for _ in range(60):
        k = int(rng.integers(1, 7))
        committed = rng.random((k, N_ENS)) < 0.6
        kind = np.where(rng.random((k, N_ENS)) < 0.85,
                        eng.OP_RMW, eng.OP_PUT).astype(np.int32)
        exp_e = rng.integers(0, 9, (k, N_ENS)).astype(np.int32)
        slot = rng.integers(0, N_SLOTS, (k, N_ENS)).astype(np.int32)
        val = rng.integers(-2 ** 31, 2 ** 31, (k, N_ENS),
                           dtype=np.int64).astype(np.int32)
        value = np.zeros((k, N_ENS), np.int32)
        q = np.ones((N_ENS,), bool)
        py = repgroup.build_comm_entry(
            1, k, committed, value, kind, slot, val, exp_e, q, [],
            n_slots=N_SLOTS, native=None)
        nv = repgroup.build_comm_entry(
            1, k, committed, value, kind, slot, val, exp_e, q, [],
            n_slots=N_SLOTS, native=nat)
        if py is None:
            assert nv is None
            continue
        assert nv is not None
        assert _entry_canon(py[0]) == _entry_canon(nv[0])
        assert py[1:] == nv[1:]
        built += 1
    assert built >= 10, "fuzz never produced a qualifying flush"


# -- replica apply of "m" entries --------------------------------------------


def _one_cell_entry(operand=14, nops=2):
    """A minimal qualifying flush: two adds on (ens 1, slot 3)."""
    committed, kind, slot, val, exp_e, value, q = _comm_arrays(k=2)
    for j, v in enumerate([operand - 9, 9] if nops == 2 else [operand]):
        committed[j, 1] = True
        kind[j, 1] = eng.OP_RMW
        exp_e[j, 1] = funref.RMW_ADD
        slot[j, 1] = 3
        val[j, 1] = v
    out = repgroup.build_comm_entry(
        1, 2, committed, value, kind, slot, val, exp_e, q, [],
        n_slots=N_SLOTS)
    assert out is not None
    return out


def test_merge_section_crc_violation_nacks(tmp_path):
    """A flipped byte in the MERGE section (its own CRC, separate
    from the ordered half's) must nack and leave the lane untouched;
    the replayed good entry applies the lattice merge and advances
    the slot's seq counter by the ops the cell absorbed."""
    svc, core = _plain_core(tmp_path)
    entry, crc, _nbytes, n_cells, n_ops = _one_cell_entry()
    assert entry[0] == "m" and int(entry[3]) == 0
    bad_ops = np.frombuffer(entry[20].buf, np.int32).copy()
    bad_ops[0] ^= 0xFF
    bad = entry[:20] + (wire.Raw(bad_ops),) + entry[21:]
    r = core.handle_abatch(("abatch", 0, [bad]))
    assert r[0] == "nack" and r[1] == "crc"
    assert core.applied_seq == 0
    assert int(np.asarray(svc.state.obj_val)[1, 0, 3]) == 0
    ctr0 = int(np.asarray(svc.state.obj_seq_ctr)[1])
    r = core.handle_abatch(("abatch", 0, [entry]))
    assert r == ("applied", 0, 1, repgroup._crc_chain(0, crc))
    assert int(np.asarray(svc.state.obj_val)[1, 0, 3]) == 14
    # seq discipline: the counter advances by the ABSORBED op count,
    # so version vectors land bit-equal to the sequenced apply
    assert int(np.asarray(svc.state.obj_seq_ctr)[1]) == ctr0 + n_ops
    svc.stop()


def test_merge_section_bounds_violations_nack(tmp_path):
    """Hostile merge sections (out-of-range slot, rank >= nops) nack
    all-or-nothing — CRC-valid but semantically broken frames must
    not partially apply."""
    import zlib

    svc, core = _plain_core(tmp_path)
    entry, _crc, _nb, _c, _o = _one_cell_entry()

    def rebuild(idx, arr):
        """Swap section idx and RESTAMP the merge CRC so only the
        semantic validation can reject it."""
        out = list(entry)
        out[idx] = wire.Raw(np.ascontiguousarray(arr))
        mcrc = 0
        for i in range(15, 23):
            mcrc = zlib.crc32(bytes(out[i].buf), mcrc)
        out[23] = mcrc
        return tuple(out)

    # slot out of range
    bad_slot = np.frombuffer(entry[18].buf, np.uint8).copy()
    bad_slot[0] = N_SLOTS + 3
    r = core.handle_abatch(("abatch", 0, [rebuild(18, bad_slot)]))
    assert r[0] == "nack", r
    # rank >= nops
    bad_rl = np.frombuffer(entry[21].buf, np.uint8).copy()
    bad_rl[0] = 9
    r = core.handle_abatch(("abatch", 0, [rebuild(21, bad_rl)]))
    assert r[0] == "nack", r
    assert core.applied_seq == 0
    assert int(np.asarray(svc.state.obj_val)[1, 0, 3]) == 0
    svc.stop()


# -- leader/replica end-to-end -----------------------------------------------


def _mixed_results(svc):
    """A deterministic mixed workload (commutative, semilattice,
    ordered, puts, deletes); returns (pre, many, post, gets) — the
    results before the duplicate-key kmodify_many (bit-equal across
    arms, versions included), the kmodify_many group itself plus the
    ops after it (status-equal: coalescing commits FEWER ops, so the
    ensemble's seq counter legitimately diverges downstream), and
    the final reads (value-equal — the converged KV state)."""
    pre = []
    pre += _settle(svc, [svc.kput(e, f"k{e}", b"v%d" % e)
                         for e in range(N_ENS)])
    pre += _settle(svc, [svc.kmodify(e, f"c{e}",
                                     funref.ref("rmw:add", 7), 0)
                         for e in range(N_ENS)])
    pre += _settle(svc, [svc.kmodify(0, "c0",
                                     funref.ref("rmw:sub", 3), 0),
                         svc.kmodify(1, "c1",
                                     funref.ref("rmw:max", 50), 0),
                         svc.kmodify(2, "c2",
                                     funref.ref("rmw:bxor", 5), 0)])
    many = _settle(svc, [svc.kmodify_many(
        3, ["c3", "d3", "c3", "c3"], funref.ref("rmw:add", 2), 0)])[0]
    post = _settle(svc, [svc.kdelete(3, "k3")])
    gets = _settle(svc, [svc.kget(e, f"c{e}") for e in range(N_ENS)])
    gets += _settle(svc, [svc.kget(3, "d3"), svc.kget(0, "k0"),
                          svc.kget(3, "k3")])
    return pre, many, post, gets


def test_comm_on_off_equivalence_and_metrics(tmp_path):
    """THE oracle arm: RETPU_COMM_REPL=0 runs the identical workload
    through the plain ordered delta lane — zero "m" entries, same
    client results, same final KV values — while the comm arm ships
    merge entries; both converge replica lanes bit-equal, and the
    §18 metric families are registered on BOTH arms."""
    svc_on, srvs_on = _group(tmp_path / "on")
    svc_off, srvs_off = _group(tmp_path / "off")
    svc_off._comm_repl = False
    try:
        pre_on, many_on, post_on, gets_on = _mixed_results(svc_on)
        pre_off, many_off, post_off, gets_off = _mixed_results(svc_off)
        # bit-equal up to the coalescing point, versions included
        assert pre_on == pre_off
        # the dup-key group and everything after: status-equal (the
        # comm arm committed fewer ops, so ensemble 3's seq counter
        # legitimately runs behind)
        assert [x[0] for x in many_on] == [x[0] for x in many_off]
        assert [x[0] for x in post_on] == [x[0] for x in post_off]
        # the converged KV state is value-identical
        assert gets_on == gets_off
        g_on = svc_on.stats()["group"]
        g_off = svc_off.stats()["group"]
        assert g_on["comm_repl"] is True
        assert g_off["comm_repl"] is False
        assert g_on["repl_merge_entries"] > 0, g_on
        assert g_on["repl_merge_ops"] >= g_on["repl_merge_cells"] > 0
        # the off arm never builds a merge section — bit-identity
        # with the pre-§18 stream is by construction
        assert g_off["repl_merge_entries"] == 0, g_off
        assert g_off["repl_merge_cells"] == 0
        assert g_off["repl_early_acks"] == 0
        # always-registered families (zeroed on the off arm)
        for s in (svc_on, svc_off):
            names = set(s.obs_registry.names())
            assert {"retpu_repl_merge_cells", "retpu_repl_early_acks",
                    "retpu_repl_merge_coalesce_ratio"} <= names
        _assert_lanes_equal(svc_on, srvs_on)
        _assert_lanes_equal(svc_off, srvs_off)
    finally:
        _stop(svc_on, srvs_on)
        _stop(svc_off, srvs_off)


def test_wire_coalescing_and_early_ack(tmp_path):
    """A hot-slot storm of SEPARATE scalar kmodifys queued into one
    flush ships fewer merge cells than committed ops (the wire-level
    coalescing ``repl_merge_cells`` counts) and settles through early acks on
    every replica — pure-merge frames ack after the WAL sync, before
    the device scatter."""
    svc, srvs = _group(tmp_path)
    try:
        # warm round: elections ship full-plane; the storm must not
        _settle(svc, [svc.kmodify(e, "warm", funref.ref("rmw:add", 1),
                                  0) for e in range(N_ENS)])
        for _ in range(3):
            futs = [svc.kmodify(0, "hot", funref.ref("rmw:add", 5), 0)
                    for _ in range(8)]
            futs += [svc.kmodify(1, "hot2",
                                 funref.ref("rmw:sub", 2), 0)
                     for _ in range(4)]
            _settle(svc, futs)
            assert all(f.value[0] == "ok" for f in futs)
        g = svc.stats()["group"]
        assert g["repl_merge_entries"] > 0, g
        # contended ops collapsed: N same-slot ops -> ONE cell
        assert g["repl_merge_cells"] < g["repl_merge_ops"], g
        assert g["repl_merge_coalesce_ratio"] > 1.0, g
        assert g["repl_early_acks"] > 0, g
        for s in srvs:
            assert s.core.early_acks > 0, \
                "replica never took the early-ack path"
        r = _settle(svc, [svc.kget(0, "hot"), svc.kget(1, "hot2")])
        assert _counter_val(r[0]) == 3 * 8 * 5
        assert _counter_val(r[1]) == funref.i32(3 * 4 * -2)
        _assert_lanes_equal(svc, srvs)
    finally:
        _stop(svc, srvs)


def test_merge_stream_undercuts_the_ordered_stream_per_entry(tmp_path):
    """A hot-slot counter storm (two keys, each four times a batch,
    every ensemble): the comm lane counts merge entries and early
    acks, ships fewer bytes an entry than the ordered delta stream
    does for the same storm, and both arms end in the same K/V
    state."""
    storm, fun = ["ctr0", "ctr1"] * 4, funref.ref("rmw:add", 1)
    per_entry, finals = {}, {}
    for arm, comm in (("comm", True), ("ordered", False)):
        svc, srvs = _group(tmp_path / arm)
        svc._comm_repl = comm
        try:
            warm = [svc.kmodify_many(e, storm, fun)
                    for e in range(N_ENS)]
            _settle(svc, warm)  # slots and elections, full-plane
            g0 = dict(svc.stats()["group"])
            for _ in range(4):
                _settle(svc, [svc.kmodify_many(e, storm, fun)
                              for e in range(N_ENS)])
            g = svc.stats()["group"]
            assert g["quorum_failures"] == 0, g
            entries = (g["repl_delta_entries"] + g["repl_full_entries"]
                       - g0["repl_delta_entries"]
                       - g0["repl_full_entries"])
            assert entries > 0, g
            per_entry[arm] = (g["repl_bytes_sections"]
                              - g0["repl_bytes_sections"]) / entries
            merged = g["repl_merge_entries"] - g0["repl_merge_entries"]
            early = g["repl_early_acks"] - g0["repl_early_acks"]
            assert (merged > 0 and early > 0) if comm \
                else (merged, early) == (0, 0), g
            finals[arm] = [_counter_val(r) for r in _settle(
                svc, [svc.kget(e, k) for e in range(N_ENS)
                      for k in storm[:2]])]
            _assert_lanes_equal(svc, srvs)
        finally:
            _stop(svc, srvs)
    assert finals["comm"] == finals["ordered"] == [20] * (2 * N_ENS)
    assert per_entry["comm"] < per_entry["ordered"], per_entry


# -- kmodify_many enqueue-side coalescing ------------------------------------


def _plain_svc(tmp_path, name, comm=True):
    svc = BatchedEnsembleService(WallRuntime(), N_ENS, 1, N_SLOTS,
                                 config=fast_test_config(),
                                 data_dir=str(tmp_path / name),
                                 tick=None)
    svc._comm_repl = comm
    return svc


def _drive(svc, futs, flushes=40):
    for _ in range(flushes):
        if all(f.done for f in futs):
            break
        svc.flush()
    assert all(f.done for f in futs)
    return [f.value for f in futs]


def test_kmodify_many_enqueue_coalescing_equivalence(tmp_path):
    """Duplicate commutative keys in one kmodify_many fold into a
    single device row: same final values as the un-coalesced arm,
    all members acked with the row's shared version, and that
    version is CAS-usable — while ordered funs never coalesce."""
    a = _plain_svc(tmp_path, "a", comm=True)
    b = _plain_svc(tmp_path, "b", comm=False)
    try:
        keys = ["x", "y", "x", "x"]
        fa = a.kmodify_many(0, keys, funref.ref("rmw:sub", 3), 0)
        fb = b.kmodify_many(0, keys, funref.ref("rmw:sub", 3), 0)
        _drive(a, [fa])
        _drive(b, [fb])
        assert [r[0] for r in fa.value] == ["ok"] * 4
        assert [r[0] for r in fb.value] == ["ok"] * 4
        # two duplicate "x" ops absorbed on the comm arm only
        assert a.rmw_enqueue_coalesced == 2
        assert b.rmw_enqueue_coalesced == 0
        # fastpath counts OPS on both arms (the meter stays honest)
        assert a.rmw_device_fastpath == 4
        assert b.rmw_device_fastpath == 4
        # all members of the coalesced group share the row's version
        vx = [tuple(r[1]) for r, k in zip(fa.value, keys) if k == "x"]
        assert len(set(vx)) == 1
        # final values identical across arms (int32-exact fold)
        for svc, who in ((a, "comm"), (b, "plain")):
            rx = _drive(svc, [svc.kget(0, "x")])[0]
            ry = _drive(svc, [svc.kget(0, "y")])[0]
            assert _counter_val(rx) == funref.i32(-9), who
            assert _counter_val(ry) == funref.i32(-3), who
        # the shared version is the slot's CURRENT version: a CAS
        # against it must succeed (the only token a client could use)
        fc = a.kupdate(0, "x", vx[0], b"swapped")
        _drive(a, [fc])
        assert fc.value[0] == "ok", fc.value
        # ordered funs (set) never coalesce — per-op rows
        coalesced0 = a.rmw_enqueue_coalesced
        fs = a.kmodify_many(0, ["z", "z", "z"],
                            funref.ref("rmw:set", 6), 0)
        _drive(a, [fs])
        assert [r[0] for r in fs.value] == ["ok"] * 3
        assert a.rmw_enqueue_coalesced == coalesced0
        rz = _drive(a, [a.kget(0, "z")])[0]
        assert _counter_val(rz) == 6
    finally:
        a.stop()
        b.stop()


def test_kmodify_many_coalesced_mixed_fresh_and_existing(tmp_path):
    """Coalescing against a slot with committed history: the folded
    group lands on the existing value exactly as the sequenced chain
    would (the merge-vs-chain equivalence the lane is built on)."""
    svc = _plain_svc(tmp_path, "m", comm=True)
    try:
        _drive(svc, [svc.kmodify(0, "c", funref.ref("rmw:add", 100),
                                 0)])
        f = svc.kmodify_many(0, ["c"] * 5, funref.ref("rmw:add", 7), 0)
        _drive(svc, [f])
        assert [r[0] for r in f.value] == ["ok"] * 5
        r = _drive(svc, [svc.kget(0, "c")])[0]
        assert _counter_val(r) == 135
        # semilattice: dup maxes collapse to one idempotent row
        f = svc.kmodify_many(0, ["c", "c"], funref.ref("rmw:max", 999),
                             0)
        _drive(svc, [f])
        r = _drive(svc, [svc.kget(0, "c")])[0]
        assert _counter_val(r) == 999
    finally:
        svc.stop()


# -- ServiceClient idempotency pin -------------------------------------------


def test_client_kmodify_never_silently_retried():
    """kmodify/kmodify_many are NOT in the idempotent-retry set (a
    read-modify-WRITE retried after an ambiguous drop double-applies
    — §18 early acks make RMW storms the hot ambiguous-drop shape),
    and a kmodify dropped mid-ack surfaces DISCONNECTED with the
    request dispatched exactly ONCE."""
    ops = svcnode.ServiceClient.IDEMPOTENT_OPS
    assert "kmodify" not in ops
    assert "kmodify_many" not in ops
    # the whole set stays write-free: only read/introspection verbs
    assert ops <= {"kget", "kget_vsn", "kget_many", "kget_slab",
                   "stats", "health", "metrics"}

    async def scenario():
        seen = []

        async def drop_mid_ack(reader, writer):
            # read ONE request, then die without answering — the
            # op may or may not have applied server-side (ambiguous)
            try:
                head = await reader.readexactly(4)
                (length,) = struct.unpack(">I", head)
                frame = await reader.readexactly(length)
                seen.append(wire.decode(frame)[1])
            except asyncio.IncompleteReadError:
                pass
            writer.close()

        server = await asyncio.start_server(drop_mid_ack,
                                            "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        c = svcnode.ServiceClient("127.0.0.1", port)
        await c.connect()
        model = CounterModel("ctr")
        r = await c.kmodify(0, "ctr", funref.ref("rmw:add", 5), 0,
                            timeout=10.0)
        assert r == c.DISCONNECTED, r
        model.unknown(5)  # applied at most once — never twice
        await asyncio.sleep(0.1)
        assert seen == ["kmodify"], \
            f"ambiguous kmodify was re-dispatched: {seen}"
        await c.close()
        server.close()
        await server.wait_closed()
        # both outcomes of the ambiguous op are plausible finals —
        # a silent retry (final 10) would be neither
        model.check_final(0)
        model.check_final(5)
        with pytest.raises(Exception):
            model.check_final(10)

    asyncio.run(scenario())


# -- deterministic convergence (tier-1) --------------------------------------


def test_comm_convergence_mixed_traffic_oneway_drop(tmp_path):
    """Mixed commutative+ordered traffic with a one-way link
    blackhole mid-stream (the PR 9 nemesis shape, deterministic):
    every acked operand lands exactly once — counter finals equal
    the acked-op sums — and the healed replica lanes converge
    bit-equal to the leader's."""
    svc, srvs = _group(tmp_path)
    plan = faults.install(faults.FaultPlan())
    ctrs = {e: CounterModel(f"{e}/cnt") for e in range(N_ENS)}
    keymods = {e: KeyModel(f"{e}/kv") for e in range(N_ENS)}
    try:
        def storm(round_no):
            futs = []
            for e in range(N_ENS):
                opd = 3 + 2 * e + round_no
                futs.append((ctrs[e], opd,
                             svc.kmodify(e, "cnt",
                                         funref.ref("rmw:add", opd),
                                         0)))
                if round_no < 2:
                    # host-payload puts ride the HEALTHY rounds: the
                    # re-sync patch restores their values/keys but
                    # handle numbering is lane-local across a patch,
                    # so the bit-equality canon below sticks to
                    # inline counters through the nemesis window
                    m = keymods[e]
                    v = b"r%d" % round_no
                    op = m.invoke_write(v)
                    futs.append((m, (op, v),
                                 svc.kput(e, "kv", v)))
            _settle(svc, [f for *_x, f in futs], budget=40.0)
            for m, tag, f in futs:
                if isinstance(m, CounterModel):
                    if isinstance(f.value, tuple) \
                            and f.value[0] == "ok":
                        m.ack(tag)
                    else:
                        m.unknown(tag)
                else:
                    op, _v = tag
                    if isinstance(f.value, tuple) \
                            and f.value[0] == "ok":
                        m.ack_write(op)
                    else:
                        m.timeout_write(op)

        storm(0)
        storm(1)
        # one-way blackhole: requests toward replica 0 die; the
        # leader + replica 1 quorum keeps committing
        plan.drop(faults.LOCAL, svc._links[0].label)
        storm(2)
        storm(3)
        plan.heal()
        storm(4)
        # ordered traffic interleaved on the same ensembles
        _settle(svc, [svc.kmodify(e, "cnt2",
                                  funref.ref("rmw:bxor", e + 1), 0)
                      for e in range(N_ENS)])
        _assert_lanes_equal(svc, srvs)
        finals = _settle(svc, [svc.kget(e, "cnt")
                               for e in range(N_ENS)])
        for e in range(N_ENS):
            ctrs[e].check_final(_counter_val(finals[e]))
            assert ctrs[e].n_acked > 0, "storm never acked anything"
        reads = _settle(svc, [svc.kget(e, "kv") for e in range(N_ENS)])
        for e in range(N_ENS):
            assert reads[e][0] == "ok"
            keymods[e].ack_read(reads[e][1])
    finally:
        faults.clear()
        _stop(svc, srvs)


# -- randomized convergence sweep (slow lane) --------------------------------


@pytest.mark.slow
def test_comm_randomized_convergence_crash_and_handoff(tmp_path):
    """THE §18 acceptance sweep on a live 3-host group: randomized
    commutative+ordered load under drop/RTT churn, replica r1 killed
    at the replica_apply_pre_ack barrier (its WAL holds applies past
    its last ack — the retransmit discipline must absorb them, not
    double-merge), restarted, re-synced, and finally carrying the
    quorum ALONE after r2 dies.  CounterModel holds the obligation:
    every final equals the acked-operand sum plus some subset of the
    ambiguous ops — a double-applied merge overshoots, an early-ack
    loss undershoots.  CAS tokens minted after the handoff must
    still swap."""
    import signal

    from test_repgroup import (_make_leader, _restart, _spawn_replica,
                               _wait_synced)

    rng = np.random.default_rng(20818)
    procs, dirs = {}, {}
    os.environ["RETPU_CRASHPOINT"] = "replica_apply_pre_ack:4"
    try:
        dirs["r1"] = str(tmp_path / "r1")
        procs["r1"] = _spawn_replica(dirs["r1"])
    finally:
        os.environ.pop("RETPU_CRASHPOINT", None)
    dirs["r2"] = str(tmp_path / "r2")
    procs["r2"] = _spawn_replica(dirs["r2"])
    svc = _make_leader(tmp_path, [procs["r1"][1], procs["r2"][1]],
                       ack_timeout=5.0)
    plan = faults.install(faults.FaultPlan(seed=20818))
    labels = [l.label for l in svc._links]
    ctrs = {(e, k): CounterModel(f"{e}/c{k}")
            for e in range(4) for k in range(2)}
    keymods = {e: KeyModel(f"{e}/ord") for e in range(4)}

    def settle(futs, budget=45.0):
        end = time.monotonic() + budget
        while not all(f.done for f in futs) \
                and time.monotonic() < end:
            svc.flush()
            time.sleep(0.005)
        assert all(f.done for f in futs), "futures never settled"

    def classify(pending):
        for m, tag, f in pending:
            ok = isinstance(f.value, tuple) and f.value[0] == "ok"
            if isinstance(m, CounterModel):
                m.ack(tag) if ok else m.unknown(tag)
            else:
                m.ack_write(tag) if ok else m.timeout_write(tag)

    restarted = False
    try:
        for rnd in range(10):
            # bounded nemesis: churn only on two rounds (ambiguity
            # must stay rare — the reachable-sum set is 2^n)
            if rnd in (2, 6):
                lab = labels[int(rng.integers(len(labels)))]
                if rng.random() < 0.5:
                    plan.drop(faults.LOCAL, lab)
                else:
                    plan.drop(lab, faults.LOCAL)
            elif rnd in (3, 7):
                plan.set_rtt(faults.LOCAL,
                             labels[int(rng.integers(len(labels)))],
                             float(rng.uniform(1.0, 3.0)))
            else:
                plan.heal()
            pending = []
            for _ in range(8):
                e = int(rng.integers(4))
                r = rng.random()
                if r < 0.7:
                    k = int(rng.integers(2))
                    opd = int(rng.integers(-50, 50))
                    name = "rmw:add" if rng.random() < 0.7 \
                        else "rmw:sub"
                    # retries=1: an internal retry of a quorum-
                    # failed round could re-land an operand that DID
                    # enter the replicated stream — the model's
                    # applied-at-most-once premise needs one attempt
                    fut = svc.kmodify(e, f"c{k}",
                                      funref.ref(name, abs(opd)), 0,
                                      retries=1)
                    signed = abs(opd) if name == "rmw:add" \
                        else -abs(opd)
                    pending.append((ctrs[(e, k)], signed, fut))
                else:
                    m = keymods[e]
                    v = b"o%d-%d" % (rnd, int(rng.integers(1000)))
                    op = m.invoke_write(v)
                    pending.append((m, op, svc.kput(e, "ord", v)))
            settle([f for *_x, f in pending])
            classify(pending)
            if not restarted and procs["r1"][0].poll() is not None:
                # the crashpoint fired mid-stream: bring r1 back on
                # its own ports/data and let the leader re-sync it
                assert procs["r1"][0].poll() == faults.CRASH_EXIT
                plan.heal()
                _restart(procs, dirs, "r1")
                _wait_synced(svc, 2)
                restarted = True
        plan.heal()
        if not restarted:
            # drive applies until the barrier fires (heartbeats are
            # empty applies), then recover the host
            end = time.monotonic() + 90.0
            while procs["r1"][0].poll() is None \
                    and time.monotonic() < end:
                svc.heartbeat()
                time.sleep(0.05)
            assert procs["r1"][0].poll() == faults.CRASH_EXIT, \
                "replica never died at replica_apply_pre_ack"
            _restart(procs, dirs, "r1")
            _wait_synced(svc, 2)
        # handoff: the once-crashed host carries the quorum alone
        p2, _, _ = procs["r2"]
        p2.send_signal(signal.SIGKILL)
        p2.wait()
        # post-handoff traffic still commits (r1's lane must hold
        # every early-acked merge it WAL-ed before the crash)
        post = []
        for (e, k), m in ctrs.items():
            fut = svc.kmodify(e, f"c{k}", funref.ref("rmw:add", 11),
                              0, retries=1)
            post.append((m, 11, fut))
        settle([f for *_x, f in post], budget=60.0)
        classify(post)
        finals = [svc.kget(e, f"c{k}") for (e, k) in ctrs]
        settle(finals, budget=60.0)
        for ((e, k), m), f in zip(ctrs.items(), finals):
            m.check_final(_counter_val(f.value))
        assert sum(m.n_acked for m in ctrs.values()) > 20
        # ordered keys: plausible per the KeyModel across the sweep
        reads = [svc.kget(e, "ord") for e in range(4)]
        settle(reads, budget=60.0)
        for e, f in zip(range(4), reads):
            if isinstance(f.value, tuple) and f.value[0] == "ok":
                keymods[e].ack_read(f.value[1])
        # CAS tokens minted through the comm lane survive the
        # handoff: read-version -> swap must succeed
        gv = svc.kget_vsn(0, "c0")
        settle([gv], budget=30.0)
        assert gv.value[0] == "ok"
        cu = svc.kupdate(0, "c0", tuple(gv.value[2]), b"swapped")
        settle([cu], budget=30.0)
        assert cu.value[0] == "ok", cu.value
    finally:
        faults.clear()
        try:
            svc.stop()
        except Exception:  # noqa: BLE001 — teardown best effort
            pass
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()
