"""Single-round device RMW: the fused kmodify (ISSUE 2 tentpole).

The reference runs kmodify's mod-fun inside the leader's FSM so a
read-modify-write commits in one consensus round (do_kmodify,
peer.erl:303-317).  The batched analog is the engine's ``OP_RMW`` op
kind: the round reads the slot's latest hash-valid value, applies a
registered mod-fun table entry (funref.RMW_*) and commits the result
under the same round's seq discipline — so device RMWs cost ONE flush
and can never CAS-conflict.  Pinned here:

- engine-level semantics of every table fun (vs an int32 numpy
  reference), including absence/tombstone-as-0 and put-if-absent;
- the service fast path: a table-resolvable kmodify commits in one
  flush round (asserted), N concurrent increments of one key converge
  to exactly +N with zero conflicts in that same flush;
- device-table vs host-fallback equivalence: the same fun sequence
  produces the same values AND the same (epoch, seq) versions;
- the host path's contention storm stays bounded (chained CAS +
  jittered backoff) and surfaces ``rmw_conflicts``;
- WAL durability of device-native (inline) keys across restore.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from riak_ensemble_tpu import funref  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402


def _elected(e=2, m=3, s=8):
    st = eng.init_state(e, m, s)
    up = jnp.ones((e, m), bool)
    st, won = eng.elect_step(st, jnp.ones((e,), bool),
                             jnp.zeros((e,), jnp.int32), up)
    assert np.asarray(won).all()
    return st, up


def _rmw(st, up, code, opd, slot=0):
    e = st.leader.shape[0]
    return eng.kv_step(
        st, jnp.full((e,), eng.OP_RMW, jnp.int32),
        jnp.full((e,), slot, jnp.int32),
        jnp.full((e,), opd, jnp.int32),
        jnp.zeros((e,), bool), up,
        exp_epoch=jnp.full((e,), code, jnp.int32),
        exp_seq=jnp.zeros((e,), jnp.int32))


def _get(st, up, slot=0):
    e = st.leader.shape[0]
    return eng.kv_step(
        st, jnp.full((e,), eng.OP_GET, jnp.int32),
        jnp.full((e,), slot, jnp.int32), jnp.zeros((e,), jnp.int32),
        jnp.zeros((e,), bool), up)


def test_engine_rmw_fun_table_semantics():
    """Every table fun against an int32 numpy oracle, chained over one
    slot (each round reads the previous round's commit)."""
    st, up = _elected()
    i32 = funref.i32  # int32 wraparound oracle
    cur = 0
    prog = [(eng.RMW_ADD, 5), (eng.RMW_ADD, 2 ** 31 - 1),  # wraps
            (eng.RMW_SUB, 7), (eng.RMW_MAX, 100), (eng.RMW_MIN, 42),
            (eng.RMW_BOR, 0b1010), (eng.RMW_BAND, 0b0110),
            (eng.RMW_BXOR, -1), (eng.RMW_SET, 1234)]
    ops = {eng.RMW_ADD: lambda c, o: i32(c + o),
           eng.RMW_SUB: lambda c, o: i32(c - o),
           eng.RMW_MAX: max, eng.RMW_MIN: min,
           eng.RMW_BOR: lambda c, o: c | o,
           eng.RMW_BAND: lambda c, o: c & o,
           eng.RMW_BXOR: lambda c, o: c ^ o,
           eng.RMW_SET: lambda c, o: o}
    for code, opd in prog:
        st, r = _rmw(st, up, code, opd)
        cur = ops[code](cur, opd)
        assert np.asarray(r.committed).all(), (code, opd)
        assert (np.asarray(r.value) == cur).all(), (code, opd)
    st, g = _get(st, up)
    assert (np.asarray(g.value) == int(cur)).all()


def test_engine_rmw_absent_and_put_if_absent():
    st, up = _elected()
    # arithmetic on an absent slot reads 0
    st, r = _rmw(st, up, eng.RMW_ADD, 7, slot=3)
    assert np.asarray(r.committed).all()
    assert (np.asarray(r.value) == 7).all()
    # put-if-absent over a live value: no commit, nothing written
    st, r = _rmw(st, up, eng.RMW_PIA, 99, slot=3)
    assert not np.asarray(r.committed).any()
    st, g = _get(st, up, slot=3)
    assert (np.asarray(g.value) == 7).all()
    # put-if-absent on a fresh slot commits the operand
    st, r = _rmw(st, up, eng.RMW_PIA, 99, slot=4)
    assert np.asarray(r.committed).all()
    # an RMW computing 0 writes the tombstone: reads are notfound
    st, r = _rmw(st, up, eng.RMW_SET, 0, slot=3)
    assert np.asarray(r.committed).all()
    st, g = _get(st, up, slot=3)
    assert np.asarray(g.get_ok).all()
    assert not np.asarray(g.found).any()
    # ...and put-if-absent succeeds over the tombstone
    st, r = _rmw(st, up, eng.RMW_PIA, 5, slot=3)
    assert np.asarray(r.committed).all()


def test_engine_rmw_needs_leader_quorum():
    e, m = 2, 3
    st = eng.init_state(e, m, 8)  # leaderless
    up = jnp.ones((e, m), bool)
    st, r = _rmw(st, up, eng.RMW_ADD, 1)
    assert not np.asarray(r.committed).any()


def _svc(n_ens=2, **kw):
    runtime = Runtime(seed=7)
    svc = BatchedEnsembleService(runtime, n_ens, 3, n_slots=8,
                                 tick=None,
                                 config=fast_test_config(), **kw)
    return runtime, svc


def _drive(svc, futs, flushes=60):
    n = 0
    while not all(f.done for f in futs):
        assert n < flushes, "futures did not resolve"
        svc.flush()
        n += 1
    return n


def test_kmodify_device_fastpath_single_flush():
    """Acceptance: a table-resolvable kmodify commits in ONE flush
    round — enqueue, one flush() call, resolved."""
    _rt, svc = _svc()
    f = svc.kmodify(0, "ctr", funref.ref("rmw:add", 5), 0)
    assert not f.done
    assert _drive(svc, [f]) == 1, "device kmodify took > 1 flush"
    assert f.value[0] == "ok"
    assert svc.rmw_device_fastpath == 1
    g = svc.kget(0, "ctr")
    _drive(svc, [g])
    assert g.value == ("ok", 5)
    # versions ride like any committed write (CAS tokens work)
    gv = svc.kget_vsn(0, "ctr")
    _drive(svc, [gv])
    assert gv.value == ("ok", 5, tuple(f.value[1]))


def test_kmodify_device_concurrent_increments_converge():
    """N concurrent increments of one key on the device path: one
    flush, zero CAS conflicts, exactly +N, distinct versions."""
    _rt, svc = _svc()
    n = 6
    futs = [svc.kmodify(0, "ctr", funref.ref("rmw:add", 1), 0)
            for _ in range(n)]
    assert _drive(svc, futs) == 1, "device RMWs took > 1 flush"
    assert all(f.value[0] == "ok" for f in futs)
    assert len({tuple(f.value[1]) for f in futs}) == n
    assert svc.rmw_conflicts == 0
    assert svc.rmw_device_fastpath == n
    g = svc.kget(0, "ctr")
    _drive(svc, [g])
    assert g.value == ("ok", n)


def test_kmodify_many_device_batch():
    _rt, svc = _svc()
    keys = [f"k{i}" for i in range(5)]
    f = svc.kmodify_many(0, keys, funref.ref("rmw:add", 3))
    assert _drive(svc, [f]) == 1
    assert [r[0] for r in f.value] == ["ok"] * 5
    g = svc.kget_many(0, keys)
    _drive(svc, [g])
    assert g.value == [("ok", 3)] * 5
    # second wave accumulates
    f = svc.kmodify_many(0, keys, funref.ref("rmw:add", 4))
    _drive(svc, [f])
    g = svc.kget_many(0, keys)
    _drive(svc, [g])
    assert g.value == [("ok", 7)] * 5


def test_kmodify_many_host_fallback_callable():
    """A non-table fun falls back to per-key kmodify chains under the
    one batch future — same results, host path."""
    _rt, svc = _svc()
    keys = [f"k{i}" for i in range(4)]
    f = svc.kmodify_many(0, keys, lambda vsn, cur: int(cur) + 2)
    _drive(svc, [f])
    assert [r[0] for r in f.value] == ["ok"] * 4
    g = svc.kget_many(0, keys)
    _drive(svc, [g])
    assert g.value == [("ok", 2)] * 4
    assert svc.rmw_device_fastpath == 0


def test_device_vs_host_equivalence_sweep():
    """The same fun/operand sequence through the device table and
    through host callables with identical int32 semantics must yield
    the same values AND the same (epoch, seq) versions — both paths
    commit exactly once per op, so the seq discipline lines up."""
    rng = np.random.default_rng(42)
    names = ["rmw:add", "rmw:sub", "rmw:max", "rmw:min", "rmw:set",
             "rmw:band", "rmw:bor", "rmw:bxor"]
    prog = [(names[rng.integers(len(names))],
             int(rng.integers(-1000, 1000)), f"key{rng.integers(3)}")
            for _ in range(30)]

    _rt, dev_svc = _svc()
    _rt2, host_svc = _svc()
    for name, opd, key in prog:
        fd = dev_svc.kmodify(0, key, funref.ref(name, opd), 0)
        host_fn = funref.resolve(funref.ref(name, opd))
        fh = host_svc.kmodify(0, key, lambda v, c, fn=host_fn: fn(v, c),
                              0)
        _drive(dev_svc, [fd])
        _drive(host_svc, [fh])
        assert fd.value == fh.value, (name, opd, key)
    assert dev_svc.rmw_device_fastpath == len(prog)
    assert host_svc.rmw_device_fastpath == 0
    for key in {k for _n, _o, k in prog}:
        gd = dev_svc.kget_vsn(0, key)
        gh = host_svc.kget_vsn(0, key)
        _drive(dev_svc, [gd])
        _drive(host_svc, [gh])
        assert gd.value == gh.value, key


def test_host_contention_storm_bounded_rounds():
    """Host-path stampede on one key: chained CAS + jittered backoff
    keep total rounds bounded and every increment lands."""
    _rt, svc = _svc()
    n = 6

    def incr(vsn, cur):
        return int(cur) + 1

    futs = [svc.kmodify(0, "ctr", incr, 0, retries=2 * n + 4)
            for _ in range(n)]
    rounds = _drive(svc, futs, flushes=6 * n)
    assert all(f.value[0] == "ok" for f in futs), [f.value for f in futs]
    g = svc.kget(0, "ctr")
    _drive(svc, [g])
    assert g.value == ("ok", n)
    # bounded: with same-flush chaining one flush call retires at
    # least one winner, so the storm converges in <= ~2 calls per op
    # plus backoff slack — far below the retry ceiling
    assert rounds <= 4 * n, rounds
    assert svc.rmw_conflicts >= n - 1


def test_mixed_storage_put_flips_inline_and_back():
    """kput over a device-native key flips it to handle storage (and
    makes RMW take the host path); a fresh RMW after delete flips it
    back."""
    _rt, svc = _svc()
    f = svc.kmodify(0, "k", funref.ref("rmw:add", 9), 0)
    _drive(svc, [f])
    p = svc.kput(0, "k", b"payload")
    _drive(svc, [p])
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", b"payload")
    # table fun over bytes: host fallback, contained failure
    f2 = svc.kmodify(0, "k", funref.ref("rmw:add", 1), 0)
    _drive(svc, [f2])
    assert f2.value == "failed"
    d = svc.kdelete(0, "k")
    _drive(svc, [d])
    f3 = svc.kmodify(0, "k", funref.ref("rmw:add", 4), 0)
    _drive(svc, [f3])
    assert f3.value[0] == "ok"
    g3 = svc.kget(0, "k")
    _drive(svc, [g3])
    assert g3.value == ("ok", 4)


def test_put_if_absent_service_semantics():
    _rt, svc = _svc()
    f = svc.kmodify(0, "k", funref.ref("rmw:put_if_absent", 11), 0)
    _drive(svc, [f])
    assert f.value[0] == "ok"
    f2 = svc.kmodify(0, "k", funref.ref("rmw:put_if_absent", 22), 0)
    _drive(svc, [f2])
    assert f2.value == "failed"
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", 11)


def test_rmw_computed_tombstone_reads_notfound():
    """A fun result of 0 IS the tombstone (engine-wide 0-is-notfound
    encoding): the key reads NOTFOUND, and a later RMW revives it
    from 0."""
    from riak_ensemble_tpu.types import NOTFOUND

    _rt, svc = _svc()
    f = svc.kmodify(0, "k", funref.ref("rmw:add", 9), 0)
    _drive(svc, [f])
    f2 = svc.kmodify(0, "k", funref.ref("rmw:set", 0), 0)
    _drive(svc, [f2])
    assert f2.value[0] == "ok"
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", NOTFOUND)
    # the tombstoned slot recycles like a committed delete (no slot
    # leak on the device arm — review regression)
    svc.flush()
    assert "k" not in svc.key_slot[0]
    assert len(svc.free_slots[0]) == svc.n_slots
    f3 = svc.kmodify(0, "k", funref.ref("rmw:add", 3), 0)
    _drive(svc, [f3])
    g2 = svc.kget(0, "k")
    _drive(svc, [g2])
    assert g2.value == ("ok", 3)


def test_put_if_absent_refuses_live_zero_payload():
    """Review regression: put-if-absent on a host-payload key holding
    the live int 0 must REFUSE (do_kput_once contract) — the host
    fallback routes through the (0,0)-CAS, never through the
    cur==0-is-absent int mirror."""
    _rt, svc = _svc()
    p = svc.kput(0, "k", 0)  # live host payload int 0
    _drive(svc, [p])
    f = svc.kmodify(0, "k", funref.ref("rmw:put_if_absent", 7), 0)
    _drive(svc, [f])
    assert f.value == "failed"
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", 0)


def test_host_fallback_table_fun_computing_zero_tombstones():
    """Review regression: a TABLE fun that computes 0 on a
    host-payload key mirrors the device path's 0-is-tombstone — the
    key reads NOTFOUND, not ('ok', 0)."""
    from riak_ensemble_tpu.types import NOTFOUND

    _rt, svc = _svc()
    p = svc.kput(0, "k", 5)  # handle storage: device path ineligible
    _drive(svc, [p])
    f = svc.kmodify(0, "k", funref.ref("rmw:sub", 5), 0)
    _drive(svc, [f])
    assert f.value[0] == "ok"
    assert svc.rmw_device_fastpath == 0
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", NOTFOUND)


def test_numpy_operand_takes_device_path():
    """Review regression: numpy integer operands/defaults must not
    silently demote to the host retry path."""
    import numpy as _np

    _rt, svc = _svc()
    f = svc.kmodify(0, "k", funref.ref("rmw:add", _np.int32(4)),
                    _np.int32(0))
    assert _drive(svc, [f]) == 1
    assert f.value[0] == "ok"
    assert svc.rmw_device_fastpath == 1


def test_put_if_absent_arbitrary_payload_routes_kput_once():
    """Review regression: put-if-absent routes by NAME, not by
    int32-operand resolvability — a non-int operand must still take
    the (0,0)-CAS (refusing live values, int 0 included), and it
    doubles as create-if-missing for arbitrary payloads."""
    _rt, svc = _svc()
    p = svc.kput(0, "k", 0)  # live host payload int 0
    _drive(svc, [p])
    f = svc.kmodify(0, "k", funref.ref("rmw:put_if_absent", b"cfg"), 0)
    _drive(svc, [f])
    assert f.value == "failed"
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", 0)
    f2 = svc.kmodify(0, "fresh",
                     funref.ref("rmw:put_if_absent", b"cfg"), 0)
    _drive(svc, [f2])
    assert f2.value[0] == "ok"
    g2 = svc.kget(0, "fresh")
    _drive(svc, [g2])
    assert g2.value == ("ok", b"cfg")


def test_device_put_if_absent_refusal_fails_fast():
    """Review regression: a device put-if-absent refused by a slot
    provably holding a live value must not burn ``retries`` device
    rounds on a deterministic outcome."""
    _rt, svc = _svc()
    f = svc.kmodify(0, "k", funref.ref("rmw:add", 5), 0)
    _drive(svc, [f])
    f2 = svc.kmodify(0, "k", funref.ref("rmw:put_if_absent", 9), 0,
                     retries=8)
    rounds = _drive(svc, [f2])
    assert f2.value == "failed"
    assert rounds <= 2, rounds
    assert svc.rmw_device_fastpath == 2  # one add + ONE pia attempt


def test_nonzero_default_keeps_host_path():
    """default != 0 cannot use the engine's absent-reads-as-0 rule —
    the host path honors it."""
    _rt, svc = _svc()
    f = svc.kmodify(0, "k", funref.ref("rmw:add", 1), 100)
    _drive(svc, [f])
    assert f.value[0] == "ok"
    assert svc.rmw_device_fastpath == 0
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", 101)


def test_inline_keys_survive_wal_restore(tmp_path):
    """Device-native values are continuously durable: kill the
    service after acked RMWs (no checkpoint) and restore from the
    WAL — values, versions and the inline marking survive."""
    d = str(tmp_path / "svc")
    rt, svc = _svc(data_dir=d, wal_sync="buffer")
    f = svc.kmodify(0, "ctr", funref.ref("rmw:add", 5), 0)
    f2 = svc.kmodify(0, "ctr", funref.ref("rmw:add", 6), 0)
    p = svc.kput(0, "blob", b"bytes")
    _drive(svc, [f, f2, p])
    assert f2.value[0] == "ok"
    svc._wal.close()

    rt2 = Runtime(seed=8)
    svc2 = BatchedEnsembleService.restore(
        rt2, d, tick=None, config=fast_test_config(), data_dir=d,
        wal_sync="buffer")
    g = svc2.kget_vsn(0, "ctr")
    gb = svc2.kget(0, "blob")
    _drive(svc2, [g, gb])
    # the restart's election re-versions on first read (the
    # update_key rewrite — same as any restored key), so only the
    # VALUE is pinned; the version must be a fresh, valid one
    assert g.value[:2] == ("ok", 11)
    assert tuple(g.value[2]) > (0, 0)
    assert gb.value == ("ok", b"bytes")
    # still device-native: the fast path resumes in one flush
    f3 = svc2.kmodify(0, "ctr", funref.ref("rmw:add", 1), 0)
    assert _drive(svc2, [f3]) == 1
    assert f3.value[0] == "ok"
    g2 = svc2.kget(0, "ctr")
    _drive(svc2, [g2])
    assert g2.value == ("ok", 12)


def test_bulk_execute_rmw_rows():
    """OP_RMW through the bulk array surface: fun codes ride the
    exp_epoch plane, the committed computed value comes back in the
    value plane."""
    _rt, svc = _svc()
    e = svc.n_ens
    kind = np.full((2, e), eng.OP_RMW, np.int32)
    slot = np.zeros((2, e), np.int32)
    val = np.asarray([[10] * e, [3] * e], np.int32)
    exp_e = np.asarray([[eng.RMW_ADD] * e, [eng.RMW_SUB] * e],
                       np.int32)
    exp_s = np.zeros((2, e), np.int32)
    committed, _get_ok, _found, value = svc.execute(
        kind, slot, val, exp_epoch=exp_e, exp_seq=exp_s)
    assert committed.all()
    assert (value[0] == 10).all() and (value[1] == 7).all()


def test_rmw_replicates_through_apply_stream(tmp_path):
    """The replica side of the replication group: an OP_RMW lane in a
    shipped apply frame lands as a keyed inline record + a
    device-native mirror on the replica — the kind plane tells it
    which rounds are RMW, and the committed value comes from its OWN
    result planes (bit-equal by determinism).  A later promotion of
    this lane serves the counter."""
    from riak_ensemble_tpu import wire
    from riak_ensemble_tpu.parallel import repgroup
    from riak_ensemble_tpu.parallel.batched_host import _PendingOp
    from riak_ensemble_tpu.runtime import Future
    from riak_ensemble_tpu.types import NOTFOUND

    rt = Runtime(seed=9)
    svc = BatchedEnsembleService(rt, 2, 1, n_slots=8, tick=None,
                                 config=fast_test_config(),
                                 data_dir=str(tmp_path / "r"),
                                 wal_sync="buffer")
    core = repgroup.ReplicaCore(svc)
    assert core.handle_promise(1)[1] is True
    e_n = svc.n_ens
    kind = np.full((1, e_n), eng.OP_RMW, np.int32)
    slot = np.zeros((1, e_n), np.int32)
    val = np.full((1, e_n), 7, np.int32)
    exp_e = np.full((1, e_n), eng.RMW_ADD, np.int32)
    exp_s = np.zeros((1, e_n), np.int32)
    entries = [(e, [_PendingOp(eng.OP_RMW, 0, 7, Future(), "ctr", 1)])
               for e in range(e_n)]
    meta = repgroup._entries_meta(entries, kind, slot, svc.values)
    frame = repgroup.build_apply_frame(
        1, 1, 1, True, np.ones((e_n,), bool), np.zeros((e_n,), bool),
        kind, slot, val, exp_e, exp_s, meta)
    # the frame must survive the restricted wire codec verbatim
    frame = wire.decode(wire.encode(frame))
    resp = core.handle_apply(frame)
    assert resp[0] == "applied", resp
    for e in range(e_n):
        assert svc.key_slot[e]["ctr"] == 0
        assert 0 in svc._inline_slots[e]
        assert svc.slot_handle[e][0] == -1
    # promoted-lane read serves the device-computed value
    g = svc.kget(0, "ctr")
    _drive(svc, [g])
    assert g.value == ("ok", 7)
    # a replicated RMW TOMBSTONE (computed 0) drops the replica's
    # keyed mapping like a delete — retaining it would alias the key
    # onto the slot's next tenant after the leader recycles it
    kind2 = np.full((1, e_n), eng.OP_RMW, np.int32)
    val2 = np.zeros((1, e_n), np.int32)
    exp_e2 = np.full((1, e_n), eng.RMW_SET, np.int32)
    entries2 = [(e, [_PendingOp(eng.OP_RMW, 0, 0, Future(), "ctr", 2)])
                for e in range(e_n)]
    meta2 = repgroup._entries_meta(entries2, kind2, slot, svc.values)
    frame2 = repgroup.build_apply_frame(
        1, 2, 1, True, np.zeros((e_n,), bool), np.zeros((e_n,), bool),
        kind2, slot, val2, exp_e2, exp_s, meta2)
    resp = core.handle_apply(wire.decode(wire.encode(frame2)))
    assert resp[0] == "applied", resp
    for e in range(e_n):
        assert "ctr" not in svc.key_slot[e]
        assert 0 not in svc.slot_handle[e]
    # ...and the WAL replay of the tombstone record agrees: the key
    # stays unmapped and the slot returns to the free pool
    svc._wal.close()
    svc2 = BatchedEnsembleService.restore(
        Runtime(seed=10), str(tmp_path / "r"), tick=None,
        config=fast_test_config(), data_dir=str(tmp_path / "r"),
        wal_sync="buffer")
    assert "ctr" not in svc2.key_slot[0]
    assert len(svc2.free_slots[0]) == svc2.n_slots
    g2 = svc2.kget(0, "ctr")
    _drive(svc2, [g2])
    assert g2.value == ("ok", NOTFOUND)


def test_kmodify_after_unflushed_kput_keeps_host_path():
    """Review regression: eligibility must see QUEUED host-payload
    writes, not just committed ones — a device RMW racing a
    same-flush kput would do int32 arithmetic on the put's payload
    HANDLE (silent corruption) and release the payload."""
    _rt, svc = _svc()
    p = svc.kput(0, "k", b"payload")  # queued, not yet flushed
    f = svc.kmodify(0, "k", funref.ref("rmw:add", 1), 0)
    _drive(svc, [p, f])
    assert p.value[0] == "ok"
    # host fallback it is: rmw:add over a bytes payload fails
    # contained instead of corrupting the handle
    assert f.value == "failed"
    assert svc.rmw_device_fastpath == 0
    g = svc.kget(0, "k")
    _drive(svc, [g])
    assert g.value == ("ok", b"payload")
    # ...and the queue-state bookkeeping drains with the ops
    assert not svc._queued_handle_writes[0]


def test_tenant_export_settles_pipeline_first():
    """Review regression: at pipeline_depth > 1 an export taken while
    a committed write is still in flight must settle the launch
    pipeline first — otherwise destroy's own drain would ACK a write
    the export omitted (acked write lost across the handoff)."""
    from riak_ensemble_tpu import service_manager as sm

    runtime = Runtime(seed=12)
    svc = BatchedEnsembleService(runtime, 2, 3, n_slots=8, tick=None,
                                 config=fast_test_config(),
                                 dynamic=True, pipeline_depth=2,
                                 max_ops_per_tick=1)
    ens = svc.create_ensemble("t")
    p1 = svc.kput(ens, "a", b"v1")
    p2 = svc.kput(ens, "b", b"v2")
    svc.flush()  # takes p1; the launch stays in flight at depth 2
    assert not p1.done
    rec = sm.ServiceReconciler(runtime, None, svc, "svc@x",
                               lambda _n: None, poll=None)
    by_key = {e[0]: e for e in rec._export(ens)}
    assert p1.done and p1.value[0] == "ok"
    assert by_key["a"][1] == b"v1"
    _drive(svc, [p2])


def test_destroy_purges_parked_kmodify_retries():
    """Review regression: a backed-off kmodify retry parked past
    destroy_ensemble must fail with the tenant, not fire later
    against the row's NEW tenant (its create-if-missing CAS would
    commit the dead tenant's value there)."""
    from riak_ensemble_tpu.runtime import Future

    runtime = Runtime(seed=13)
    svc = BatchedEnsembleService(runtime, 2, 3, n_slots=8, tick=None,
                                 config=fast_test_config(),
                                 dynamic=True)
    ens = svc.create_ensemble("t")
    fut = Future()
    fired = []
    svc._retry_at.append((svc._flush_calls + 1, ens, fut,
                          lambda: fired.append(1)))
    assert svc.destroy_ensemble("t")
    assert fut.done and fut.value == "failed"
    svc.create_ensemble("u")
    for _ in range(3):
        svc.flush()
    assert not fired


def test_tenant_export_carries_inline_values():
    """The tenant-handoff export reads payloads through slot_handle —
    device-native (inline RMW) slots must export their engine-array
    value, not trip over the -1 sentinel."""
    from riak_ensemble_tpu import service_manager as sm

    runtime = Runtime(seed=11)
    svc = BatchedEnsembleService(runtime, 2, 3, n_slots=8, tick=None,
                                 config=fast_test_config(),
                                 dynamic=True)
    ens = svc.create_ensemble("t1")
    f = svc.kmodify(ens, "ctr", funref.ref("rmw:add", 41), 0)
    p = svc.kput(ens, "blob", b"bytes")
    _drive(svc, [f, p])
    assert f.value[0] == "ok" and p.value[0] == "ok"
    rec = sm.ServiceReconciler(runtime, None, svc, "svc@x",
                               lambda _n: None, poll=None)
    by_key = {e[0]: e for e in rec._export(ens)}
    assert by_key["ctr"][1] == 41
    assert tuple(by_key["ctr"][2]) == tuple(f.value[1])
    assert by_key["blob"][1] == b"bytes"
    # version-preserving reinstall serves the value (handle storage
    # on the new owner; value + CAS-token continuity is the contract)
    ens2 = svc.create_ensemble("t2")
    res = svc.install_objs(ens2, [(k, v[2], v[1])
                                  for k, v in by_key.items()])
    assert all(r[0] == "ok" for r in res)
    g = svc.kget(ens2, "ctr")
    _drive(svc, [g])
    assert g.value == ("ok", 41)


def test_kmodify_device_over_the_wire():
    """svcnode ships the table funref as plain data; the SERVER
    fast-paths it (no code on the wire, one engine round
    server-side), and kmodify_many rides the same dispatch."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    async def scenario():
        server = await svcnode.serve(2, 3, 8, port=0,
                                     config=fast_test_config())
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        r = await c.kmodify(0, "ctr", funref.ref("rmw:add", 5), 0)
        assert r[0] == "ok", r
        r = await c.kmodify(0, "ctr", funref.ref("rmw:add", 6), 0)
        assert r[0] == "ok", r
        assert await c.kget(0, "ctr") == ("ok", 11)
        rm = await c.kmodify_many(0, ["a", "b"],
                                  funref.ref("rmw:set", 3))
        assert [x[0] for x in rm] == ["ok", "ok"], rm
        assert await c.kget_many(0, ["a", "b"]) == [("ok", 3)] * 2
        assert server.svc.rmw_device_fastpath == 4
        await c.close()
        await server.stop()

    asyncio.run(scenario())


def test_funref_device_entry_resolution():
    assert funref.device_entry(funref.ref("rmw:add", 3)) == \
        (funref.RMW_ADD, 3)
    # bools, wrong arity, out-of-range operands, unknown names: no
    # device entry (host path keeps them)
    assert funref.device_entry(("fn", "rmw:add", (True,))) is None
    assert funref.device_entry(("fn", "rmw:add", ())) is None
    assert funref.device_entry(("fn", "rmw:add", (1, 2))) is None
    assert funref.device_entry(("fn", "rmw:add", (1 << 31,))) is None
    assert funref.device_entry(("fn", "no:such", (1,))) is None
    assert funref.device_entry(lambda v, c: c) is None
    # registered host mirrors share the registry (wire-resolvable)
    fn = funref.resolve(funref.ref("rmw:add", 1))
    assert fn((0, 0), 41) == 42
    assert fn((0, 0), 2 ** 31 - 1) == -(2 ** 31)  # int32 wrap
