"""Slab enqueue half + per-flush completion slab: two-arm equivalence
vs the per-entry/per-op oracle (docs/ARCHITECTURE.md §12).

The contract under test mirrors test_native_resolve.py's: with the
slab path on (``RETPU_NATIVE_ENQUEUE=1``, the default) and off, the
same mixed op stream must produce BIT-IDENTICAL ``[K, E]`` op planes
at every launch, identical client results in issue order, identical
mirror slabs, and the fast-read gate must see slab-enqueued writes
exactly as it saw dict-noted ones.  The per-entry pack + per-op
future fan-out are the oracle; the slab path is an optimization,
never a semantic.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")

from riak_ensemble_tpu import funref
from riak_ensemble_tpu.parallel import enqueue_native
from riak_ensemble_tpu.parallel.batched_host import (
    BatchedEnsembleService, WallRuntime,
)

needs_kernel = pytest.mark.skipif(
    enqueue_native.get() is None,
    reason="native enqueue kernel unavailable (no toolchain)")


def _workload(svc, rng, n_ens, k, rounds):
    """A mixed keyed op stream covering every lane shape the pack
    must carry: batched puts/gets/CAS/tombstone-deletes, scalar
    puts/gets/updates (CAS expectations in the exp planes), device
    RMW batches (exp_e carries the mod-fun code) and RMW-to-zero
    tombstones.  Returns every future's resolved value in issue
    order."""
    out = []
    futs = []
    add1 = funref.ref("rmw:add", 1)
    set_zero = funref.ref("rmw:set", 0)
    for r in range(rounds):
        for e in range(n_ens):
            keys = [f"k{(r + i) % 11}" for i in range(k)]
            vals = [b"v%d.%d" % (r, i) for i in range(k)]
            pick = rng.integers(0, 8)
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
                futs.append(svc.kmodify_many(
                    e, [f"ctr{r % 3}", f"ctr{(r + 1) % 3}"], add1, 0))
            elif pick == 5:
                # tombstone RMW: a computed 0 recycles the slot
                futs.append(svc.kmodify(e, f"ctr{r % 3}", set_zero, 0))
            elif pick == 6:
                futs.append(svc.kupdate(e, keys[0], (0, 0), vals[0]))
                futs.append(svc.kdelete(e, keys[2]))
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


def _run_arm(arm, seed, monkeypatch, pipeline_depth=1):
    """One service per arm; captures every launch's op planes (the
    bit-identity surface) plus results + mirror/index slabs."""
    monkeypatch.setenv("RETPU_NATIVE_ENQUEUE", arm)
    monkeypatch.setenv("RETPU_FAST_READS", "0")  # every read = round
    rng = np.random.default_rng(seed)
    svc = BatchedEnsembleService(WallRuntime(), 6, 3, 16, tick=None,
                                 max_ops_per_tick=8,
                                 pipeline_depth=pipeline_depth)
    planes = []
    orig = svc._launch_enqueue

    def spy(kind, slot, val, k, want_vsn, exp_e=None, exp_s=None,
            **kw):
        planes.append((np.array(kind, np.int32),
                       np.array(slot, np.int32),
                       np.array(val, np.int32),
                       None if exp_e is None
                       else np.array(exp_e, np.int32),
                       None if exp_s is None
                       else np.array(exp_s, np.int32)))
        return orig(kind, slot, val, k, want_vsn, exp_e=exp_e,
                    exp_s=exp_s, **kw)

    monkeypatch.setattr(svc, "_launch_enqueue", spy)
    results = _workload(svc, rng, 6, 4, rounds=6)
    state = {
        "results": results,
        "planes": planes,
        "vsn_ok": svc._slot_vsn_ok.copy(),
        "vsn_np": svc._slot_vsn_np.copy(),
        "inl_ok": svc._inline_value_ok.copy(),
        "inl_np": svc._inline_value_np.copy(),
        "inline_np": svc._inline_np.copy(),
        "inline_sets": [sorted(s) for s in svc._inline_slots],
        "pending_writes": [dict(r) for r in svc._pending_writes],
        "queued_handle": [dict(r)
                          for r in svc._queued_handle_writes],
        "slot_handle": [dict(d) for d in svc.slot_handle],
        "stats": svc.stats(),
    }
    svc.stop()
    return state


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("depth", (1, 2))
def test_two_arm_equivalence(seed, depth, monkeypatch):
    """The whole enqueue half, end to end, at pipeline depths 1 and
    2: identical client results, BIT-IDENTICAL op planes launch by
    launch, identical mirror slabs and storage-class sets, and both
    write-noting slabs drained to zero."""
    a = _run_arm("1", seed, monkeypatch, pipeline_depth=depth)
    b = _run_arm("0", seed, monkeypatch, pipeline_depth=depth)
    na = a["stats"]["native_enqueue"]
    nb = b["stats"]["native_enqueue"]
    assert na["slab_path"] and not nb["slab_path"]
    assert na["flushes"] + na["fallback_flushes"] > 0, \
        "slab arm never packed through lanes"
    assert nb["flushes"] == nb["fallback_flushes"] == 0
    assert a["stats"]["completion_slab"]["wakes"] > 0
    assert b["stats"]["completion_slab"]["wakes"] == 0
    assert a["results"] == b["results"]
    assert len(a["planes"]) == len(b["planes"])
    for i, (pa, pb) in enumerate(zip(a["planes"], b["planes"])):
        for name, x, y in zip(("kind", "slot", "val", "exp_e",
                               "exp_s"), pa, pb):
            if x is None:
                assert y is None, (i, name)
                continue
            assert np.array_equal(x, y), (seed, depth, i, name)
    for fld in ("vsn_ok", "inl_ok", "inline_np"):
        assert np.array_equal(a[fld], b[fld]), fld
    assert a["pending_writes"] == b["pending_writes"]
    assert a["queued_handle"] == b["queued_handle"]
    assert np.array_equal(a["vsn_np"][a["vsn_ok"]],
                          b["vsn_np"][b["vsn_ok"]])
    assert np.array_equal(a["inl_np"][a["inl_ok"]],
                          b["inl_np"][b["inl_ok"]])
    assert a["inline_sets"] == b["inline_sets"]
    assert a["slot_handle"] == b["slot_handle"]
    # every queued write was un-noted by exactly one resolve/fail arm
    assert not any(a["pending_writes"])
    assert not any(a["queued_handle"])


@needs_kernel
def test_kernel_arm_actually_ran(monkeypatch):
    """With the toolchain present the slab arm's pack must run the
    C++ kernel, not the numpy fallback."""
    monkeypatch.setenv("RETPU_NATIVE_ENQUEUE", "1")
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    f = svc.kput_many(0, ["a", "b"], [b"1", b"2"])
    while not f.done:
        svc.flush()
    assert svc.native_enqueue_flushes > 0
    assert svc.fallback_enqueue_flushes == 0
    svc.stop()


def test_completion_slab_one_wake_per_flush(monkeypatch):
    """One wake per settled op-carrying flush, rounds conserved —
    under pipeline_depth=2 AND a batch split across three flushes
    (the K cap lands inside it twice)."""
    monkeypatch.setenv("RETPU_NATIVE_ENQUEUE", "1")
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 64, tick=None,
                                 max_ops_per_tick=4,
                                 pipeline_depth=2)
    keys = [f"k{i}" for i in range(10)]
    f = svc.kput_many(0, keys, [b"v%d" % i for i in range(10)])
    while not f.done:
        svc.flush()
    svc.flush()  # drain the pipeline tail
    assert [r[0] for r in f.value] == ["ok"] * 10
    # 10 rounds through a K cap of 4 = 3 launches, each exactly one
    # wake; every taken round appears in exactly one slab
    assert svc.completion_wakes == 3
    assert svc.completion_rows == 10
    svc.stop()


def test_knob_pins_oracle(monkeypatch):
    """RETPU_NATIVE_ENQUEUE=0 pins the per-entry pack + per-op
    fan-out at construction: no lanes, no wakes, same answers."""
    monkeypatch.setenv("RETPU_NATIVE_ENQUEUE", "0")
    assert enqueue_native.get() is None
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    assert not svc._enq_slab
    f = svc.kput_many(0, ["k"], [b"v"])
    g = svc.kget(0, "k")
    while not (f.done and g.done):
        svc.flush()
    assert f.value == [("ok", (1, 1))]
    assert svc.completion_wakes == 0
    assert svc.native_enqueue_flushes == 0
    assert svc.fallback_enqueue_flushes == 0
    svc.stop()


def test_missing_so_degrades_to_numpy_pack(monkeypatch):
    """A missing/unbuildable kernel .so keeps the SLAB path (it is
    numpy, not C++) with the fancy-index pack arm — never a crash,
    never the per-op oracle by accident.  Simulated by pinning the
    loader's memo to 'tried and failed'."""
    monkeypatch.setenv("RETPU_NATIVE_ENQUEUE", "1")
    monkeypatch.setattr(enqueue_native, "_instance", None)
    monkeypatch.setattr(enqueue_native, "_instance_tried", True)
    assert enqueue_native.get() is None
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    assert svc._enq_slab and svc._native_enqueue is None
    f = svc.kput_many(0, ["k"], [b"v"])
    g = svc.kget_many(0, ["k"])
    while not (f.done and g.done):
        svc.flush()
    assert f.value == [("ok", (1, 1))]
    assert svc.fallback_enqueue_flushes > 0
    assert svc.native_enqueue_flushes == 0
    assert svc.completion_wakes > 0
    svc.stop()


def test_leased_read_racing_slab_write_falls_back(monkeypatch):
    """PR 4 fast-read gate regression (the satellite's contract): a
    slab-enqueued write must be visible to the gate at _push time —
    a leased read of the slot falls back to the device round, which
    orders it after the write."""
    monkeypatch.setenv("RETPU_NATIVE_ENQUEUE", "1")
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    f = svc.kput_many(0, ["k"], [b"v0"])
    while not f.done:
        svc.flush()  # first round pays the XLA compile (lease lapses)
    f = svc.kput_many(0, ["k"], [b"v1"])
    while not f.done:
        svc.flush()  # warm round: quorum confirms, lease renews in ms
    # leased mirror hit while nothing is pending
    g0 = svc.kget(0, "k")
    assert g0.done and g0.value == ("ok", b"v1")
    assert svc.read_fastpath_hits >= 1
    # slab-enqueued write, not yet flushed: the gate must see it NOW
    f2 = svc.kput_many(0, ["k"], [b"v2"])
    g = svc.kget(0, "k")
    assert not g.done, "read served around a pending slab write"
    assert svc.read_fastpath_miss_reasons.get("pending_write", 0) >= 1
    while not (f2.done and g.done):
        svc.flush()
    assert g.value == ("ok", b"v2")
    svc.stop()
