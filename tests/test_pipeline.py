"""Pipelined async service execution (the two-phase launch split):
overlap of device rounds with host resolve, ordering guarantees,
deferred corruption semantics, the execute_async surface, and the
donated-state step variants.

The overlap test injects d2h latency through the ``_fetch_packed``
seam (the packed vector "arrives" DELAY after its enqueue, like a
transfer riding a slow link): at depth 1 every flush eats the full
delay; at depth 2 the delay of batch N runs under batch N+1's
enqueue + dwell, roughly halving wall time.  A regression that
silently serializes the pipeline (settle-before-enqueue) collapses
the ratio to ~1 and fails fast.
"""

import time
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402


def make_service(n_ens=4, n_peers=3, n_slots=8, depth=2, max_k=2,
                 runtime=None, **kw):
    runtime = runtime if runtime is not None else Runtime(seed=77)
    svc = BatchedEnsembleService(runtime, n_ens, n_peers, n_slots,
                                 tick=None, max_ops_per_tick=max_k,
                                 config=fast_test_config(),
                                 pipeline_depth=depth, **kw)
    return runtime, svc


def drain(svc):
    while any(svc.queues):
        svc.flush()
    svc.flush()  # idle flush settles the in-flight tail


class _DelayedService(BatchedEnsembleService):
    """Injected d2h latency: the packed result is 'on the host' only
    DELAY seconds after its enqueue — the transfer-time model the
    pipeline exists to hide."""

    DELAY = 0.04

    def __init__(self, *a, **kw):
        self._ready_at = {}
        super().__init__(*a, **kw)

    def _launch_enqueue(self, *a, **kw):
        fl = super()._launch_enqueue(*a, **kw)
        self._ready_at[id(fl)] = time.perf_counter() + self.DELAY
        return fl

    def _fetch_packed(self, fl):
        ready = self._ready_at.pop(id(fl), 0.0)
        while time.perf_counter() < ready:
            time.sleep(0.001)
        return super()._fetch_packed(fl)


def _timed_burst(depth: int, batches: int = 8) -> float:
    """Wall time to serve `batches` single-round flushes of queued
    keyed puts under injected d2h delay."""
    runtime = Runtime(seed=7)
    svc = _DelayedService(runtime, 2, 3, 8, tick=None,
                          max_ops_per_tick=1,
                          config=fast_test_config(),
                          pipeline_depth=depth)
    # election launch outside the timed region
    svc.flush()
    svc.flush()
    futs = [svc.kput(0, f"k{j}", b"v") for j in range(batches)]
    t0 = time.perf_counter()
    drain(svc)
    elapsed = time.perf_counter() - t0
    assert all(f.done and f.value[0] == "ok" for f in futs)
    return elapsed


def test_depth2_overlaps_injected_d2h_delay():
    """THE serialization guard: depth 2 must genuinely overlap batch
    N's in-flight transfer with batch N+1's enqueue — wall time well
    under the depth-1 serial sum.  Generous margin (0.75) over the
    ideal ~0.5x keeps slow-CI noise out."""
    t1 = _timed_burst(depth=1)
    t2 = _timed_burst(depth=2)
    assert t2 < 0.75 * t1, (t1, t2)


def test_pipelined_results_resolve_in_submission_order():
    runtime, svc = make_service(max_k=1, n_slots=16)
    order = []
    futs = []
    for j in range(10):
        f = svc.kput(0, f"k{j}", b"v%d" % j)
        f.add_waiter(lambda _r, j=j: order.append(j))
        futs.append(f)
    drain(svc)
    assert all(f.done and f.value[0] == "ok" for f in futs)
    assert order == sorted(order), order
    # and the data is right
    g = svc.kget(0, "k3")
    drain(svc)
    assert g.value == ("ok", b"v3")


def test_latency_marks_split_by_mode():
    """Depth-1 records keep the serial device_d2h mark; pipelined
    records carry enqueue/inflight_wait (+ the flush-side resolve),
    the fields the overlap analysis needs."""
    _rt, svc1 = make_service(depth=1)
    svc1.kput(0, "k", b"v")
    drain(svc1)
    keys1 = {k for r in svc1.lat_records for k in r}
    assert "device_d2h" in keys1 and "inflight_wait" not in keys1
    assert {"enqueue", "resolve", "wal", "queue_wait"} <= keys1

    _rt, svc2 = make_service(depth=2, max_k=1)
    for j in range(4):
        svc2.kput(0, f"k{j}", b"v")
    drain(svc2)
    keys2 = {k for r in svc2.lat_records for k in r}
    assert {"enqueue", "inflight_wait", "resolve",
            "queue_wait"} <= keys2
    assert "device_d2h" not in keys2
    bd = svc2.latency_breakdown()
    assert "inflight_wait" in bd and "enqueue" in bd
    assert svc2.stats()["pipeline_depth"] == 2
    assert svc2.stats()["launches_in_flight"] == 0


class _TracedService(BatchedEnsembleService):
    """Event-order probe: enqueue/resolve boundaries of every launch."""

    def __init__(self, *a, **kw):
        self.events = []
        self._seq = 0
        super().__init__(*a, **kw)

    def _launch_enqueue(self, *a, **kw):
        fl = super()._launch_enqueue(*a, **kw)
        self._seq += 1
        self.events.append(("enq", self._seq))
        return fl

    def _launch_resolve(self, fl, wait_key="device_d2h"):
        out = super()._launch_resolve(fl, wait_key)
        self.events.append(("res", None))
        return out


def test_corruption_deferral_repairs_before_next_ack():
    """The corrupt planes are inspected one round late under the
    pipeline (batch N+1's enqueue precedes batch N's resolve), but
    the exchange still lands BEFORE batch N+1's results are acked —
    the flagged-ensemble-repaired-before-its-next-ack contract."""
    runtime = Runtime(seed=9)
    svc = _TracedService(runtime, 4, 3, 8, tick=None,
                         max_ops_per_tick=1,
                         config=fast_test_config(), pipeline_depth=2)

    def trace(kind, _payload):
        if kind == "svc_exchange":
            svc.events.append(("trace", kind))
    runtime.trace = trace
    futs = {}
    for e in range(4):
        futs[e] = svc.kput(e, "k", b"v")
    drain(svc)
    assert all(f.done and f.value[0] == "ok" for f in futs.values())

    # out-of-band damage on peer 2's copy of "k" in ensemble 0 (only
    # ensemble 0 is read below, so only its damage can be detected)
    slot_k = svc.key_slot[0]["k"]
    svc.state = svc.state._replace(
        obj_val=svc.state.obj_val.at[0, 2, slot_k].set(424242))

    # two read batches through the pipeline: batch 1's read trips the
    # integrity gate; its corrupt plane is inspected at resolve —
    # after batch 2's enqueue — and the exchange dispatches before
    # batch 2's futures resolve.  Expire the leases first: a leased
    # fast read would serve the host mirror and never take the device
    # round whose integrity gate this test exercises.
    svc.lease_until[:] = 0.0
    svc.events.clear()

    def on_ack(j):
        return lambda _r: svc.events.append(("ack", j))
    g1 = svc.kget(0, "k")
    g1.add_waiter(on_ack(1))
    g2 = svc.kget(0, "k")
    g2.add_waiter(on_ack(2))
    drain(svc)
    assert g1.value == ("ok", b"v") and g2.value == ("ok", b"v")
    assert svc.corruptions > 0
    ev = svc.events
    kinds = [k for k, _v in ev]
    # pipeline really ran: both enqueues before the first resolve
    assert kinds.index("res") > 1 and kinds[0] == "enq"
    exch = next(i for i, (k, v) in enumerate(ev)
                if (k, v) == ("trace", "svc_exchange"))
    ack2 = next(i for i, (k, v) in enumerate(ev) if (k, v) == ("ack", 2))
    assert exch < ack2, ev
    # the sweep healed the replica
    node_bad, leaf_bad = eng.verify_trees(svc.state)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def _exec_planes(n_ens, n_slots, k, seed=0):
    rng = np.random.default_rng(seed)
    kind = rng.choice([eng.OP_PUT, eng.OP_GET], (k, n_ens)).astype(np.int32)
    slot = rng.integers(0, n_slots, (k, n_ens)).astype(np.int32)
    val = rng.integers(1, 1 << 20, (k, n_ens)).astype(np.int32)
    return kind, slot, val


def test_execute_async_pipeline_and_sync_interleave():
    svc = BatchedEnsembleService(WallRuntime(), 8, 3, 8, tick=None,
                                 max_ops_per_tick=4,
                                 config=fast_test_config(),
                                 pipeline_depth=2)
    kind, slot, val = _exec_planes(8, 8, 4)
    futs = [svc.execute_async(kind, slot, val) for _ in range(5)]
    # depth bound: at most pipeline_depth launches unsettled
    assert len(svc._inflight_launches) <= 2
    # a synchronous execute settles everything in flight first, so
    # every earlier async result resolves before it returns
    committed, get_ok, _f, _v = svc.execute(kind, slot, val)
    assert all(f.done for f in futs)
    assert (committed | get_ok).all()
    for f in futs:
        c, g, _fo, _va = f.value
        assert (c | g).all()
    # idle flush settles a lone trailing async batch
    tail = svc.execute_async(kind, slot, val)
    svc.flush()
    assert tail.done
    assert svc.stats()["launches_in_flight"] == 0
    svc.stop()


def test_execute_async_matches_execute_results():
    """Same op stream through a depth-2 async service and a depth-1
    sync service lands identical result planes (the pipeline is pure
    scheduling, not semantics)."""
    outs = {}
    for depth in (1, 2):
        svc = BatchedEnsembleService(WallRuntime(), 6, 3, 8, tick=None,
                                     max_ops_per_tick=4,
                                     config=fast_test_config(),
                                     pipeline_depth=depth)
        res = []
        for i in range(4):
            kind, slot, val = _exec_planes(6, 8, 4, seed=i)
            if depth == 1:
                res.append(svc.execute(kind, slot, val))
            else:
                res.append(svc.execute_async(kind, slot, val))
        svc.flush()
        if depth == 2:
            assert all(f.done for f in res)
            res = [f.value for f in res]
        outs[depth] = res
        svc.stop()
    for a, b in zip(outs[1], outs[2]):
        for pa, pb in zip(a, b):
            np.testing.assert_array_equal(np.asarray(pa),
                                          np.asarray(pb))


def test_full_step_donate_matches_plain():
    """The donated-state step program computes the same protocol math
    as the plain one (donation only changes buffer aliasing)."""
    e, m, s, k = 6, 3, 8, 4
    up = jax.numpy.ones((e, m), bool)
    elect = jax.numpy.ones((e,), bool)
    cand = jax.numpy.zeros((e,), jax.numpy.int32)
    rng = np.random.default_rng(3)
    kind = jax.numpy.asarray(
        rng.choice([eng.OP_PUT, eng.OP_GET], (k, e)), jax.numpy.int32)
    slot = jax.numpy.asarray(rng.integers(0, s, (k, e)), jax.numpy.int32)
    val = jax.numpy.asarray(rng.integers(1, 99, (k, e)), jax.numpy.int32)
    lease = jax.numpy.zeros((k, e), bool)

    st_a = eng.init_state(e, m, s)
    st_b = eng.init_state(e, m, s)
    for _ in range(3):
        st_a, won_a, res_a = eng.full_step(
            st_a, elect, cand, kind, slot, val, lease, up)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # CPU may ignore donation
            st_b, won_b, res_b = eng.full_step_donate(
                st_b, elect, cand, kind, slot, val, lease, up)
        elect = jax.numpy.zeros((e,), bool)
    np.testing.assert_array_equal(np.asarray(won_a), np.asarray(won_b))
    for fa, fb in zip(res_a, res_b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    for fa, fb in zip(st_a, st_b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_service_with_donation_enabled(monkeypatch):
    """RETPU_DONATE=1 routes launches through the donated programs;
    the keyed surface stays correct (CPU backends may fall back to a
    copy — the warning is the fallback, not an error)."""
    monkeypatch.setenv("RETPU_DONATE", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        runtime, svc = make_service(depth=2, max_k=2)
        assert svc._donate
        futs = [svc.kput(e, "k", b"v%d" % e) for e in range(svc.n_ens)]
        drain(svc)
        assert all(f.done and f.value[0] == "ok" for f in futs)
        gets = [svc.kget(e, "k") for e in range(svc.n_ens)]
        drain(svc)
        assert [g.value for g in gets] == \
            [("ok", b"v%d" % e) for e in range(svc.n_ens)]


def test_pipelined_flush_with_timer_runtime():
    """The tick-driven service composes with the pipeline: futures
    resolve through timer flushes exactly as at depth 1."""
    runtime = Runtime(seed=21)
    svc = BatchedEnsembleService(runtime, 4, 3, 8, tick=0.005,
                                 config=fast_test_config(),
                                 pipeline_depth=2)
    futs = [svc.kput(e, "k", b"x") for e in range(4)]
    for f in futs:
        assert runtime.await_future(f, 5.0)[0] == "ok"
    g = svc.kget(2, "k")
    assert runtime.await_future(g, 5.0) == ("ok", b"x")
    svc.stop()


def test_single_lane_replicated_service_pipelines():
    """A link-less ReplicatedService (replica role / single lane)
    forwards through the split halves unchanged at depth 2."""
    from riak_ensemble_tpu.parallel.repgroup import ReplicatedService

    runtime = WallRuntime()
    svc = ReplicatedService(runtime, 4, 1, 8, group_size=1,
                            config=fast_test_config(),
                            pipeline_depth=2, max_ops_per_tick=1)
    futs = [svc.kput(0, f"k{j}", b"v%d" % j) for j in range(4)]
    drain(svc)
    assert all(f.done and f.value[0] == "ok" for f in futs)
    g = svc.kget(0, "k2")
    drain(svc)
    assert g.value == ("ok", b"v2")
    svc.stop()


def test_wal_error_does_not_abandon_later_launches(tmp_path):
    """A WAL-append failure settling launch N must not poison launch
    N+1: N's device commits are real (its clients get 'failed' — the
    allowed unacked outcome), but N+1's chain is healthy and its ops
    must settle normally once the disk recovers; abandoning it would
    recycle slots the device still populates."""
    runtime = Runtime(seed=5)
    svc = BatchedEnsembleService(runtime, 2, 3, 8, tick=None,
                                 max_ops_per_tick=1,
                                 config=fast_test_config(),
                                 pipeline_depth=2,
                                 data_dir=str(tmp_path))
    svc.flush()  # election round out of the way
    svc.flush()

    real_log = svc._wal.log
    fail_next = {"n": 1}

    def flaky_log(recs):
        if fail_next["n"]:
            fail_next["n"] -= 1
            raise OSError("disk full")
        return real_log(recs)
    svc._wal.log = flaky_log

    f1 = svc.kput(0, "a", b"v1")
    f2 = svc.kput(0, "b", b"v2")
    with pytest.raises(OSError):
        drain(svc)
    # f1's commit could not be acked (WAL failed) — allowed outcome
    assert f1.done and f1.value == "failed"
    # f2 rode a healthy chain and a healthy disk: it must be acked
    assert f2.done and f2.value[0] == "ok", f2.value
    g = svc.kget(0, "b")
    drain(svc)
    assert g.value == ("ok", b"v2")
    svc.stop()
