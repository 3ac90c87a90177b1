"""Per-op SLO tracing (docs/ARCHITECTURE.md §11, round 9).

Covers the tentpole contracts end to end: stamp monotonicity and the
op→flush_id join on the pipelined (depth 2) keyed path, the join
surviving a batch split across flushes, ack-after-quorum on a LIVE
replication group, the injected-slow-op demo (the op's tail
attributed to its dominating stage via ``obs.timeline``), and the
compile-event hook catching a deliberately un-warmed (K, A) bucket.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import obs  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.obs import opslo  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)


def _acked_rows(ring):
    return [r for r in range(ring.cap) if ring.t_ack[r] > 0.0]


def test_op_spans_depth2_pipelined():
    """Every keyed op on a depth-2 pipelined service gets the five
    monotone stamps and a flush_id that joins a recorded leader
    timeline; the per-kind histogram counts every op exactly once."""
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 max_ops_per_tick=4,
                                 pipeline_depth=2)
    futs = []
    for rnd in range(3):
        for e in range(4):
            futs.append(svc.kput_many(
                e, [f"k{rnd}a", f"k{rnd}b"], [b"1", b"2"]))
        while any(svc.queues):
            svc.flush()
    assert all(f.done for f in futs)
    ring = svc._slo
    rows = _acked_rows(ring)
    assert rows, "no acked ring rows recorded"
    for r in rows:
        assert ring.t_submit[r] <= ring.t_enq[r] <= ring.t_join[r] \
            <= ring.t_settle[r] <= ring.t_ack[r], \
            ring.row_view(r)
        assert ring.fid[r] > 0, "acked op without a flush_id join"
        # the joined flush has a leader span record under the SAME id
        tl = obs.timeline(int(ring.fid[r]))
        assert tl is not None and "leader" in tl
    # per-kind histogram: every put counted once (3 rounds x 4 ens x
    # 2 keys), the entry's latency nonzero
    put = svc._h_op.labels("put")
    assert put.count == 24
    assert put.percentile(0.99) >= put.percentile(0.5) >= 0
    # reads join too, including the kind split
    f = svc.kget_many(0, ["k0a"])
    # leased fast read: no flush — lands as get_fast
    assert f.done
    assert svc._h_op.labels("get_fast").count >= 1
    svc.stop()


def test_op_flush_join_survives_batch_split():
    """A kput_many wider than the flush's K cap splits: the head
    settles with flush N, the tail re-enters the ring and settles
    with flush N+1 — two rows, two DIFFERENT flush_ids, op counts
    conserved."""
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=2)
    ring = svc._slo
    first_row = ring._next
    fut = svc.kput_many(0, ["a", "b", "c", "d"],
                        [b"1", b"2", b"3", b"4"])
    while not fut.done:
        svc.flush()
    assert [r[0] for r in fut.value] == ["ok"] * 4
    rows = [r for r in range(first_row, ring._next)
            if ring.kind[r & ring.mask] != 0]
    acked = [r & ring.mask for r in rows
             if ring.t_ack[r & ring.mask] > 0.0]
    assert len(acked) == 2, "split batch must occupy two ring rows"
    fids = {int(ring.fid[r]) for r in acked}
    assert len(fids) == 2, f"head and tail joined the same flush: {fids}"
    assert sum(int(ring.n[r]) for r in acked) == 4, \
        "op weight not conserved across the split"
    # both halves' flushes are queryable timelines (a structured
    # miss — the store's not-found shape since round 13 — would mean
    # the join broke)
    for fid in fids:
        tl = obs.timeline(fid)
        assert tl and not tl.get("miss"), tl
    svc.stop()


def test_op_ack_lands_after_quorum_settle(tmp_path):
    """Replication-group mode: client futures resolve only at the
    host-quorum settle, and the ring's ack stamps land at (or after)
    that settle — never at the device resolve that precedes it."""
    from riak_ensemble_tpu.parallel import repgroup

    servers = [repgroup.ReplicaServer(4, 3, 8,
                                      data_dir=str(tmp_path / f"r{i}"),
                                      config=fast_test_config())
               for i in (1, 2)]
    svc = repgroup.ReplicatedService(
        WallRuntime(), 4, 1, 8, group_size=3,
        peers=[("127.0.0.1", s.repl_port) for s in servers],
        ack_timeout=30.0, max_ops_per_tick=4,
        config=fast_test_config(),
        data_dir=str(tmp_path / "leader"))
    repgroup.warmup_kernels(svc)
    assert svc.takeover()
    settle_t: list = []
    orig_settle = svc._settle_batch

    def tracked_settle(batch):
        settle_t.append(time.perf_counter())
        return orig_settle(batch)

    svc._settle_batch = tracked_settle
    ring = svc._slo
    first_row = ring._next
    futs = [svc.kput_many(e, ["a", "b"], [b"1", b"2"])
            for e in range(4)]
    while any(svc.queues):
        svc.flush()
    svc._drain_pending(block_all=True)
    assert all(f.done for f in futs)
    assert settle_t, "no quorum settle observed"
    rows = [r & ring.mask for r in range(first_row, ring._next)]
    acked = [r for r in rows if ring.t_ack[r] > 0.0]
    assert acked, "no acked ring rows on the replicated leader"
    for r in acked:
        assert ring.t_join[r] <= ring.t_settle[r] <= ring.t_ack[r]
        # the ack stamp postdates the FIRST quorum settle — the
        # device resolve ran earlier, but no op acked before a
        # host-quorum decision existed
        assert ring.t_ack[r] >= settle_t[0], \
            (ring.row_view(r), settle_t)
        tl = obs.timeline(int(ring.fid[r]))
        assert tl is not None and "leader" in tl
    # the health verb's group section reflects the live quorum plane
    h = svc.health()
    assert h["schema"] == "retpu-health-v1"
    grp = h["group"]
    assert grp["leader"] is True and grp["size"] == 3
    assert grp["peers_connected"] == 2
    assert grp["pipeline_pending"] == 0
    assert h["ensembles_with_leader"] == 4
    svc.stop()
    for s in servers:
        s.stop()


def test_injected_slow_op_tail_attribution(monkeypatch):
    """Acceptance demo: one injected-slow op's tail is correctly
    attributed by the ring's rows of its flush id
    (``OpSloRing.rows_of``) beside ``obs.timeline`` — a queue-stalled
    op shows ``queue_wait`` dominating its stage split, a d2h-stalled
    op shows the flush stage dominating AND the flush's own span
    record naming ``device_d2h`` as its longest mark."""
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 max_ops_per_tick=2)
    # steady state first (compiles out of the way)
    for i in range(4):
        f = svc.kput(i % 4, "w", b"x")
        while not f.done:
            svc.flush()

    # (1) queue-wait domination: enqueue, stall the flush driver
    fut = svc.kput_many(0, ["slow"], [b"v"])
    time.sleep(0.06)
    while not fut.done:
        svc.flush()
    ring = svc._slo
    # the stalled op is the newest settled entry (the warm-up ops'
    # first flush is slower still — it ate the first-use compile,
    # itself correctly attributed to its 'flush' stage)
    row = max(_acked_rows(ring), key=lambda r: ring.t_ack[r])
    fid = int(ring.fid[row])
    slow = ring.rows_of(fid)[0]
    assert slow["flush_id"] == fid and slow["ms"] >= 55.0, slow
    st = slow["stages_ms"]
    assert st["queue_wait"] > max(st["flush"], st["ack"],
                                  st["assign"]), slow

    # (2) device/d2h domination: stall the packed-result fetch
    orig = svc._fetch_packed

    def slow_fetch(fl):
        time.sleep(0.08)
        return orig(fl)

    monkeypatch.setattr(svc, "_fetch_packed", slow_fetch)
    fut = svc.kput_many(1, ["slow2"], [b"v"])
    while not fut.done:
        svc.flush()
    monkeypatch.undo()
    rows2 = [r for r in _acked_rows(ring)
             if ring.kind[r] and ring.ens[r] == 1
             and ring.t_ack[r] - ring.t_submit[r] > 0.07]
    assert rows2, "stalled op not found in the ring"
    fid2 = int(ring.fid[rows2[-1]])
    slow2 = ring.rows_of(fid2)[0]
    st2 = slow2["stages_ms"]
    assert st2["flush"] > max(st2["queue_wait"], st2["ack"],
                              st2["assign"]), slow2
    # the same flush id names the flush's own span record, whose
    # longest mark says where the stall sat: the d2h wait
    marks = dict(obs.timeline(fid2)["leader"]["spans"])
    assert max(marks, key=marks.get) == "device_d2h", marks
    svc.stop()


def test_compile_events_catch_unwarmed_bucket():
    """Acceptance: a deliberately un-warmed (K, A) bucket pays
    its first-use compile at SERVE time — and the compile-event hook
    names it (``retpu_compile_events_total{phase="serve"}``) instead
    of leaving a dispatch-p99 mystery.  E=24 is unique to this test
    (process-wide jit caches are shared), so the miss is
    deterministic."""
    svc = BatchedEnsembleService(WallRuntime(), 24, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    # warm ONLY the k=1 bucket (and the election-only k=0 launch): the
    # k=2 flush below hits an un-warmed program
    svc.warmup(buckets=[(1, None)])
    assert svc._c_compile.labels("warmup").value > 0, \
        "warmup compiles must be counted under phase=warmup"
    serve0 = svc._c_compile.labels("serve").value
    fut = svc.kput_many(0, ["a", "b"], [b"1", b"2"])  # k bucket 2
    while not fut.done:
        svc.flush()
    served = svc._c_compile.labels("serve").value - serve0
    assert served >= 1, "un-warmed bucket compile not caught"
    ev = [e for e in svc._compile_log if e["phase"] == "serve"]
    assert ev, "serve-phase compile left no log entry"
    assert ev[-1]["fn"] == "step", ev[-1]
    assert ev[-1]["compile_ms"] > 0
    # the un-warmed bucket's shape signature is recorded: its slab
    # (K=2: 3 + 5 * 2 rows of 24 columns) and its static arguments
    assert "[13,24]" in ev[-1]["shapes"].replace(" ", ""), ev[-1]
    assert "want_vsn" in ev[-1]["shapes"], ev[-1]
    # and the events ride the flight-dump extras section
    extras = svc._flight_extras()
    assert extras["compile_events"], extras
    assert any(e["phase"] == "serve" for e in extras["compile_events"])
    svc.stop()


def test_ring_bounded_and_obs_off_short_circuit(monkeypatch):
    """The ring is bounded (overwrites, never grows) and RETPU_OBS=0
    constructs no ring at all — zero stamp work on the hot path."""
    ring = opslo.OpSloRing(capacity=64)
    for i in range(200):
        t = float(i + 1)
        ring.record_flush([2], [0], [1], [0.0], [0.0], [t], i + 1, t,
                          t + 1.0, t + 2.0)
    assert ring.cap == 64 and ring._next == 200
    monkeypatch.setenv("RETPU_OBS", "0")
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 max_ops_per_tick=2)
    assert svc._slo is None
    f = svc.kput(0, "k", b"v")
    while not f.done:
        svc.flush()
    assert f.value[0] == "ok"
    assert svc._h_op.count == 0 and not svc._h_op._children
    svc.stop()


def test_ring_capacity_knob(monkeypatch):
    monkeypatch.setenv("RETPU_SLO_RING", "100")
    assert opslo.ring_capacity() == 128
    monkeypatch.setenv("RETPU_SLO_RING", "junk")
    assert opslo.ring_capacity() == 4096
