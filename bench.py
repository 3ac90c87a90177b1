"""Headline benchmark: the END-TO-END service, plus the raw kernel.

Scenario 3 of the BASELINE.md ladder: 10k ensembles x 5 peers of mixed
kput/kget.  Two numbers:

1. ``engine_kernel_rounds_per_sec`` — raw ``kv_step_scan`` launches,
   device math only (ballots, quorum reduce, store, Merkle maintenance;
   no host bridge).  An honest kernel number, not a service claim.
2. ``service_linearizable_kv_ops_per_sec`` — the HEADLINE:
   ``BatchedEnsembleService.execute`` end to end (election fold-in,
   host lease check/renewal, device launch, result transfer, corruption
   watch), with client-observed per-batch commit latency recorded —
   p50/p99 reported against the BASELINE.md targets (>= 1M ops/s,
   p99 < 5 ms).

The reference publishes no numbers (BASELINE.md); the driver north-star
target of 1M linearizable ops/sec is the ``vs_baseline`` denominator.

The orchestrator runs each stage in a subprocess under a hard timeout
and falls back from the full shape to a smaller one, recording the
platform and shape actually measured.  Without a TPU the default run
exits non-zero: there is no CPU rung (``--smoke`` is the CPU
correctness run).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ops/sec", "vs_baseline": N,
   "p50_commit_latency_ms": ..., "p99_commit_latency_ms": ...,
   "engine_kernel_rounds_per_sec": ..., "platform": ...}

``--smoke`` shrinks shapes for a CPU sanity run (single process).
``--stage ...`` runs one stage in-process (the orchestrator's worker).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np


def _setup_jax(force_cpu: bool) -> None:
    """Per-stage JAX config: persistent compile cache (retries and
    re-runs skip the 20-40 s compiles) and an optional CPU pin."""
    import jax

    from riak_ensemble_tpu.utils.jaxcache import setup_compile_cache

    setup_compile_cache()
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")


def run_pipelined_service(n_ens: int, n_peers: int, n_slots: int,
                          k: int, seconds: float,
                          depth: int = 2, engine=None) -> dict:
    """Pipelined closed loop — the two-phase async service execution
    (HEADLINE): up to ``depth`` batches in flight via
    ``execute_async``, so batch N's packed d2h transfer + host
    resolve (unpack, mirrors, corruption watch) overlap batch N+1's
    device step instead of serializing after it.  Reports the
    overlapped throughput AND the client-observed per-op commit
    latency (submit → future resolved — each op's real ack time,
    which includes the in-flight dwell the overlap buys throughput
    with)."""
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                 n_slots, tick=None,
                                 max_ops_per_tick=k,
                                 pipeline_depth=depth, engine=engine)
    if engine is not None:
        # mesh arm: pre-compile the mesh step/pack grid so the loop
        # below measures serving, not first-use compiles (asserted
        # via the serve-phase CompileWatch counter after the run)
        svc.warmup()
    rng = np.random.default_rng(0)
    kind = jnp.asarray(rng.choice([eng.OP_PUT, eng.OP_GET], (k, n_ens)),
                       jnp.int32)
    slot = jnp.asarray(rng.integers(0, n_slots, (k, n_ens)), jnp.int32)
    val = jnp.asarray(rng.integers(1, 1 << 20, (k, n_ens)), jnp.int32)
    jax.block_until_ready((kind, slot, val))

    # Warm: compile + first elections, then settle everything.
    for _ in range(depth + 1):
        svc.execute_async(kind, slot, val)
    svc.flush()
    svc.lat_records.clear()

    lat: list = []
    pending: list = []
    ops = 0
    t_end = time.perf_counter() + max(seconds, 1e-3)
    t_start = time.perf_counter()
    while time.perf_counter() < t_end or not lat:
        t0 = time.perf_counter()
        fut = svc.execute_async(kind, slot, val)
        fut.add_waiter(
            lambda _r, t0=t0: lat.append(time.perf_counter() - t0))
        pending.append(fut)
        ops += k * n_ens
    svc.flush()  # idle flush settles the in-flight tail
    elapsed = time.perf_counter() - t_start

    assert all(f.done for f in pending), "pipelined bench: unsettled"
    committed, get_ok, _found, _value = pending[-1].value
    assert (committed | get_ok).all(), "pipelined bench: ops failed"
    lat_ms = np.asarray(lat) * 1000.0
    out = {
        "ops_per_sec": ops / elapsed,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "batches": len(lat),
        "pipeline_depth": depth,
        "latency_breakdown": {
            c: {"p50": round(v["p50_ms"], 3),
                "p99": round(v["p99_ms"], 3)}
            for c, v in svc.latency_breakdown().items()},
    }
    if engine is not None:
        serve_compiles = int(svc._c_compile.labels("serve").value)
        out["serve_compiles"] = serve_compiles
        assert serve_compiles == 0, (
            "warmed mesh arm paid serve-phase compiles: "
            f"{[e for e in svc._compile_log if e['phase'] == 'serve']}")
    return out


def run_service(n_ens: int, n_peers: int, n_slots: int, k: int,
                seconds: float) -> dict:
    """End-to-end service throughput + client-observed commit latency.

    Two closed loops over the same device-resident workload: the
    PIPELINED loop (depth 2, ``execute_async`` — the headline; see
    :func:`run_pipelined_service`) and the serial loop (each
    iteration blocks on ``execute`` — the depth-1 reference the
    ``serial_*`` keys report, and the A/B that catches a silently
    serialized pipeline).  Per-batch wall time in the serial loop IS
    each op's commit latency: ops enqueue at batch start and resolve
    when the batch returns.
    """
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers, n_slots,
                                 tick=None, max_ops_per_tick=k)
    rng = np.random.default_rng(0)
    # Device-resident op planes (execute's fast path): a TPU-native
    # caller keeps its op queues on device, so the timed loop pays
    # h2d for none of the [K, E] planes — only the packed results
    # come back.  Built + transferred once, outside the timed region.
    kind = jnp.asarray(rng.choice([eng.OP_PUT, eng.OP_GET], (k, n_ens)),
                       jnp.int32)
    slot = jnp.asarray(rng.integers(0, n_slots, (k, n_ens)), jnp.int32)
    val = jnp.asarray(rng.integers(1, 1 << 20, (k, n_ens)), jnp.int32)
    jax.block_until_ready((kind, slot, val))

    # Warm up: compile + first elections fold into the launch.
    svc.execute(kind, slot, val)
    svc.execute(kind, slot, val)
    # The warmup records carry the 20-40 s first-compile inside their
    # 'dispatch' component; quoting them as the service's latency
    # breakdown is what made r3's dispatch p99 read 749 ms against a
    # 2.4 ms p50 (VERDICT r3 weak #2 / directive #4).  The breakdown
    # below is STEADY-STATE by construction; mid-run compiles can't
    # occur in this loop (fixed shapes), and flush-path services warm
    # their pow2 depth ladder via repgroup.warmup_kernels.
    svc.lat_records.clear()

    lat = []
    ops = 0
    t_end = time.perf_counter() + max(seconds, 1e-3)
    t_start = time.perf_counter()
    while time.perf_counter() < t_end or not lat:  # >= 1 iteration
        t0 = time.perf_counter()
        committed, get_ok, found, value = svc.execute(kind, slot, val)
        lat.append(time.perf_counter() - t0)
        ops += k * n_ens
    elapsed = time.perf_counter() - t_start

    # Correctness on the final batch: every op acked.
    ok = committed | get_ok
    assert ok.all(), "service bench: ops failed"
    assert (np.asarray(svc.state.leader) >= 0).all()
    lat_ms = np.asarray(lat) * 1000.0
    serial = {
        "serial_ops_per_sec": ops / elapsed,
        "serial_p50_ms": float(np.percentile(lat_ms, 50)),
        "serial_p99_ms": float(np.percentile(lat_ms, 99)),
        # Per-component breakdown (queue_wait/h2d/dispatch/device_d2h/
        # unpack/wal/resolve, p50 AND p99) — where the p99 target's
        # budget actually goes on the serial path.
        "serial_latency_breakdown": {
            c: {"p50": round(v["p50_ms"], 3),
                "p99": round(v["p99_ms"], 3)}
            for c, v in svc.latency_breakdown().items()},
    }
    svc.stop()
    # The HEADLINE: the depth-2 pipelined loop (ops_per_sec/p50/p99 +
    # the enqueue/inflight_wait/resolve breakdown come from it).
    out = run_pipelined_service(n_ens, n_peers, n_slots, k, seconds)
    out.update(serial)
    keyed = run_keyed_service(
        min(n_ens, 1000), n_peers, n_slots, min(k, 16), seconds)
    out["keyed_ops_per_sec"] = keyed["scalar"]
    out["keyed_batched_ops_per_sec"] = keyed["batched"]
    mixed = run_mixed_service(n_ens, n_peers, n_slots, k, seconds)
    out.update(mixed)
    out.update(run_rmw_service(
        min(n_ens, 256), n_peers, n_slots, min(k, 8), seconds))
    out.update(run_skewed_service(
        min(n_ens, 512), n_peers, min(n_slots, 64), min(k, 16),
        seconds))
    # read-heavy rung at the 512-ens shape with the fastpath-off A/B
    # arm (the lease-protected read fast path's headline)
    out.update(run_read_service(
        min(n_ens, 512), n_peers, min(n_slots, 64), min(k, 16),
        seconds))
    # observability-plane A/B (interleaved obs-on/off windows of the
    # headline pipelined loop): the round JSON records the overhead
    # as a measurement, not a claim
    out.update(run_obs_overhead(n_ens, n_peers, n_slots, k, seconds))
    # per-op SLO tracing A/B on the keyed rung (the surface that
    # pays the ring stamps; acceptance bound 2%)
    out.update(run_op_trace_overhead(
        min(n_ens, 512), n_peers, min(n_slots, 64), min(k, 16),
        seconds))
    # native-resolve A/B (interleaved on/off batches of the keyed
    # batched rung with a live WAL — the full resolve half the C
    # kernel replaces; same batch-granular methodology as the obs A/B)
    out.update(run_native_resolve_ab(
        min(n_ens, 512), n_peers, min(n_slots, 64), min(k, 16),
        seconds))
    # native-enqueue A/B (the other half: slab-resident pending ops +
    # per-flush completion slab vs the per-entry pack + per-op future
    # fan-out — same interleaved batch-granular methodology)
    out.update(run_native_enqueue_ab(
        min(n_ens, 512), n_peers, min(n_slots, 64), min(k, 16),
        seconds))
    return out


def run_native_resolve_ab(n_ens: int, n_peers: int, n_slots: int,
                          k: int, seconds: float) -> dict:
    """The native single-pass resolve kernel's A/B
    (``resolve_native_speedup``): the keyed BATCHED workload
    (kput_many/kget_many futures through flush()) with a buffer-sync
    WAL, so one measured batch exercises the whole resolve half the
    kernel replaces — packed-result unpack, mirror-slab scatter, WAL
    record encode — against the pure-Python oracle arm
    (``RETPU_NATIVE_RESOLVE=0``).

    Methodology is PR 6's obs_overhead_pct batch-granular interleave:
    one live service per arm (the knob binds at construction), one
    stream of alternating on/off batches with the pair order flipping
    per iteration, scored by per-arm medians — wall-clock windows on
    a small shared box measure scheduler noise, not the kernel.  The
    native arm's latency breakdown rides along so the JSON shows
    where the batch time actually goes (`resolve`, and the derived
    `resolve_native` kernel share) rather than just a ratio."""
    import shutil
    import tempfile

    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    from riak_ensemble_tpu.parallel import resolve_native

    if resolve_native.get() is None:
        # no toolchain (or knob off in the environment): record the
        # absence instead of a fake 1.0x — and build no services
        return {"resolve_native_speedup": None,
                "resolve_native_available": False}

    keys = [f"key{j}" for j in range(k)]
    vals = [b"v%d" % j for j in range(k // 2)]
    tmp = tempfile.mkdtemp(prefix="bench_native_resolve_")

    def make(env: str) -> BatchedEnsembleService:
        old = os.environ.get("RETPU_NATIVE_RESOLVE")
        os.environ["RETPU_NATIVE_RESOLVE"] = env
        try:
            svc = BatchedEnsembleService(
                WallRuntime(), n_ens, n_peers, n_slots, tick=None,
                max_ops_per_tick=k,
                data_dir=os.path.join(tmp, f"arm{env}"),
                wal_sync="buffer")
        finally:
            if old is None:
                os.environ.pop("RETPU_NATIVE_RESOLVE", None)
            else:
                os.environ["RETPU_NATIVE_RESOLVE"] = old
        batch(svc)  # warm: slots allocate, elections fold in
        svc.lat_records.clear()
        return svc

    def batch(svc: BatchedEnsembleService) -> float:
        t0 = time.perf_counter()
        futs = []
        for e in range(n_ens):
            futs.append(svc.kput_many(e, keys[:k // 2], vals))
            futs.append(svc.kget_many(e, keys[k // 2:]))
        while any(svc.queues):
            svc.flush()
        dt = time.perf_counter() - t0
        assert all(f.done for f in futs), "native A/B: unsettled"
        return dt

    on_svc = off_svc = None
    try:
        on_svc, off_svc = make("1"), make("0")
        assert on_svc._native_resolve is not None, \
            "kernel vanished between availability probe and arm build"
        probe = batch(on_svc)
        n = int(max(seconds, 1.0) * 3.0 / max(probe, 1e-7) / 2)
        n = max(30, min(n, 120))
        on_t: list = []
        off_t: list = []
        for i in range(n):
            order = ((on_svc, on_t), (off_svc, off_t))
            for svc, sink in (order if i % 2 == 0 else order[::-1]):
                sink.append(batch(svc))
        assert on_svc.stats()["native_resolve"]["flushes"] > 0, \
            "native arm never took the kernel"
        breakdown = {
            c: {"p50": round(v["p50_ms"], 3),
                "p99": round(v["p99_ms"], 3)}
            for c, v in on_svc.latency_breakdown().items()}
    finally:
        # stop BEFORE the rmtree: the WAL stores hold open handles
        # into tmp, and an exception mid-loop must not leak services
        for svc in (on_svc, off_svc):
            if svc is not None:
                try:
                    svc.stop()
                except Exception:
                    pass
        shutil.rmtree(tmp, ignore_errors=True)
    on_med = float(np.median(on_t))
    off_med = float(np.median(off_t))
    ops = k * n_ens
    return {
        "resolve_native_available": True,
        "resolve_native_ops_per_sec": ops / on_med,
        "resolve_fallback_ops_per_sec": ops / off_med,
        "resolve_native_speedup": round(off_med / on_med, 3),
        "resolve_ab_samples_per_arm": n,
        "resolve_ab_spread_ms": {
            "on": [round(float(np.percentile(on_t, q)) * 1e3, 1)
                   for q in (10, 90)],
            "off": [round(float(np.percentile(off_t, q)) * 1e3, 1)
                    for q in (10, 90)]},
        # the native arm's per-component breakdown: 'resolve' (future
        # fan-out), 'unpack', 'wal', and the derived 'resolve_native'
        # kernel share — the honest answer to "did the bottleneck
        # move off resolve"
        "resolve_native_latency_breakdown": breakdown,
    }


def run_native_enqueue_ab(n_ens: int, n_peers: int, n_slots: int,
                          k: int, seconds: float) -> dict:
    """The slab enqueue half's A/B (``enqueue_native_speedup``): the
    WAL'd keyed batched rung with ``RETPU_NATIVE_ENQUEUE`` on
    (slab-resident pending ops, one-traversal op-plane pack, per-flush
    completion slab — docs/ARCHITECTURE.md §12) against the per-entry
    pack + per-op future fan-out oracle arm (``=0``).

    Methodology is the PR 6/7 batch-granular interleave verbatim: one
    live service per arm (the knob binds at construction), one stream
    of alternating batches with the pair order flipping per
    iteration, per-arm medians.  The round JSON gets the on arm's
    component breakdown (``queue_wait``/``resolve`` plus the derived
    ``enqueue_native``/``enqueue_fallback`` pack marks), BOTH arms'
    ``queue_wait + resolve`` p50 — the acceptance criterion is that
    combined share cut >= 2x — and the completion-slab ledger, whose
    wakes must equal the op-carrying flush count (one wake per
    flush, observable)."""
    import shutil
    import tempfile

    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    keys = [f"key{j}" for j in range(k)]
    vals = [b"v%d" % j for j in range(k // 2)]
    tmp = tempfile.mkdtemp(prefix="bench_native_enqueue_")

    def make(env: str) -> BatchedEnsembleService:
        svc = _env_scoped(
            "RETPU_NATIVE_ENQUEUE", env,
            lambda: BatchedEnsembleService(
                WallRuntime(), n_ens, n_peers, n_slots, tick=None,
                max_ops_per_tick=k,
                data_dir=os.path.join(tmp, f"arm{env}"),
                wal_sync="buffer"))
        batch(svc)  # warm: slots allocate, elections fold in
        svc.lat_records.clear()
        return svc

    def batch(svc: BatchedEnsembleService) -> float:
        t0 = time.perf_counter()
        futs = []
        for e in range(n_ens):
            futs.append(svc.kput_many(e, keys[:k // 2], vals))
            futs.append(svc.kget_many(e, keys[k // 2:]))
        while any(svc.queues):
            svc.flush()
        dt = time.perf_counter() - t0
        assert all(f.done for f in futs), "enqueue A/B: unsettled"
        return dt

    def qw_res_p50(svc: BatchedEnsembleService) -> float:
        """The acceptance criterion's quantity: the arm's p50
        queue_wait + resolve (enqueue-side wait + settle fan-out)."""
        br = svc.latency_breakdown()
        return round(sum(br.get(c, {}).get("p50_ms", 0.0)
                         for c in ("queue_wait", "resolve")), 3)

    on_svc = off_svc = None
    try:
        on_svc, off_svc = make("1"), make("0")
        assert on_svc._enq_slab and not off_svc._enq_slab
        on_t, off_t, n = _interleaved_ab(on_svc, off_svc, batch,
                                         seconds, 3)
        stats_on = on_svc.stats()
        slab = stats_on["completion_slab"]
        breakdown = {
            c: {"p50": round(v["p50_ms"], 3),
                "p99": round(v["p99_ms"], 3)}
            for c, v in on_svc.latency_breakdown().items()}
        on_qw, off_qw = qw_res_p50(on_svc), qw_res_p50(off_svc)
    finally:
        for svc in (on_svc, off_svc):
            if svc is not None:
                try:
                    svc.stop()
                except Exception:
                    pass
        shutil.rmtree(tmp, ignore_errors=True)
    on_med = float(np.median(on_t))
    off_med = float(np.median(off_t))
    ops = k * n_ens
    return {
        "enqueue_native_available": (
            stats_on["native_enqueue"]["kernel"]),
        "enqueue_native_ops_per_sec": ops / on_med,
        "enqueue_fallback_ops_per_sec": ops / off_med,
        "enqueue_native_speedup": round(off_med / on_med, 3),
        "enqueue_ab_samples_per_arm": n,
        "enqueue_ab_spread_ms": {
            "on": [round(float(np.percentile(on_t, q)) * 1e3, 1)
                   for q in (10, 90)],
            "off": [round(float(np.percentile(off_t, q)) * 1e3, 1)
                    for q in (10, 90)]},
        # the acceptance criterion's two sides: combined queue_wait +
        # fan-out p50 per arm (>= 2x cut is the claim under test)
        "enqueue_queue_wait_resolve_p50_ms": {
            "on": on_qw, "off": off_qw,
            "cut_x": (round(off_qw / on_qw, 2) if on_qw else None)},
        "enqueue_native_latency_breakdown": breakdown,
        # one wake per op-carrying flush, rounds conserved — the
        # completion slab's own ledger rides the round JSON
        "enqueue_completion_slab": {
            **slab,
            "pack_flushes": (
                stats_on["native_enqueue"]["flushes"]
                + stats_on["native_enqueue"]["fallback_flushes"]),
        },
    }


def run_escale_point(n_ens: int, n_peers: int, n_slots: int, k: int,
                     seconds: float, mesh_devices: int = 0) -> dict:
    """One E-scaling datapoint (ROADMAP carried debt: the 1k/2k-ens
    CPU rungs): the headline pipelined device-resident loop plus the
    keyed batched surface at [K, n_ens], so the curve covers both the
    kernel scaling and the host resolve scaling.

    ``mesh_devices`` > 0 serves from a mesh engine sharded over that
    many devices along the 'ens' axis (the shard-wise pack path).
    The mesh arm is WARMED first and CompileWatch-asserts zero
    serve-phase compile events — a mesh number that quietly paid
    mid-serving compiles would not be a serving-path measurement.
    """
    import jax

    engine = None
    if mesh_devices:
        from riak_ensemble_tpu.parallel.mesh import mesh_engine
        engine = mesh_engine(mesh_devices)
    pip = run_pipelined_service(n_ens, n_peers, n_slots, k, seconds,
                                engine=engine)
    n_dev = mesh_devices or 1
    out = {
        "n_ens": n_ens,
        "mesh_devices": mesh_devices,
        "ops_per_sec": round(pip["ops_per_sec"], 1),
        "ops_per_sec_per_device": round(pip["ops_per_sec"] / n_dev, 1),
        "p50_ms": round(pip["p50_ms"], 3),
        "p99_ms": round(pip["p99_ms"], 3),
        "batches": pip["batches"],
    }
    if mesh_devices:
        out["serve_compiles"] = pip["serve_compiles"]
    keyed = run_keyed_batched_only(n_ens, n_peers, n_slots, k,
                                   seconds, engine=engine)
    out["keyed_batched_ops_per_sec"] = round(keyed, 1)
    return out


def run_keyed_batched_only(n_ens: int, n_peers: int, n_slots: int,
                           k: int, seconds: float,
                           engine=None) -> float:
    """The vectorized keyed surface alone (kput_many/kget_many) — the
    E-scaling stage's host-path point without the slow scalar loop."""
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                 n_slots, tick=None,
                                 max_ops_per_tick=k, engine=engine)
    keys = [f"key{j}" for j in range(k)]
    vals = [b"v%d" % j for j in range(k // 2)]
    ops = 0
    warm = True
    t0 = time.perf_counter()
    t_end = t0 + 2 * max(seconds, 1e-3)  # warm round rides inside
    while time.perf_counter() < t_end or not ops:
        futs = []
        for e in range(n_ens):
            futs.append(svc.kput_many(e, keys[:k // 2], vals))
            futs.append(svc.kget_many(e, keys[k // 2:]))
        while any(svc.queues):
            svc.flush()
        assert all(f.done for f in futs), "escale keyed: unsettled"
        if warm:  # first round compiled + elected: restart the clock
            warm = False
            t0 = time.perf_counter()
            t_end = t0 + max(seconds, 1e-3)
            continue
        ops += n_ens * k
    svc.stop()
    return ops / (time.perf_counter() - t0)


def _env_scoped(knob: str, value: str, ctor):
    """Construct a service with ``knob=value`` in the environment
    (the RETPU_* knobs bind at service construction), restoring the
    prior value either way."""
    old = os.environ.get(knob)
    os.environ[knob] = value
    try:
        return ctor()
    finally:
        if old is None:
            os.environ.pop(knob, None)
        else:
            os.environ[knob] = old


def _interleaved_ab(on_svc, off_svc, batch, seconds: float,
                    rounds: int):
    """THE A/B methodology both overhead runners share (fixed work
    at BATCH granularity — see run_obs_overhead's docstring for why
    window estimators lie on a small box): one long stream of
    settled batches alternating on/off with the pair order flipping
    every iteration.  Returns (on_times, off_times, n_per_arm);
    scoring is the caller's (per-arm median + p10/p90 spread via
    :func:`_ab_scores`)."""
    probe = batch(on_svc)
    # sample count per arm from the time budget, clamped so the
    # median is meaningful at the fast shapes (floor: the resolution
    # collapses under ~40 samples on a noisy box) and the slow shapes
    # don't blow the stage budget
    n = int(max(seconds, 1.0) * max(rounds, 1) * 2.0
            / max(probe, 1e-7) / 2)
    n = max(40, min(n, 160))
    on_t: list = []
    off_t: list = []
    for i in range(n):
        # pair order flips every iteration so a monotone box drift
        # cannot masquerade as an arm effect
        order = ((on_svc, on_t), (off_svc, off_t))
        for svc, sink in (order if i % 2 == 0 else order[::-1]):
            sink.append(batch(svc))
    return on_t, off_t, n


def _ab_scores(prefix: str, on_t, off_t, n: int, ops: int) -> dict:
    """Per-arm medians + overhead + p10/p90 spread, under
    ``{prefix}_on_...``/``{prefix}_off_...`` keys."""
    on_med = float(np.median(on_t))
    off_med = float(np.median(off_t))
    return {
        f"{prefix}_on_ops_per_sec": ops / on_med,
        f"{prefix}_off_ops_per_sec": ops / off_med,
        f"{prefix}_on_batch_ms": round(on_med * 1e3, 3),
        f"{prefix}_off_batch_ms": round(off_med * 1e3, 3),
        f"{prefix}_overhead_pct": round(
            (on_med - off_med) / off_med * 100.0, 2),
        f"{prefix}_ab_samples_per_arm": n,
        # p90/p10 spread per arm: how much the box wobbled while
        # measuring — read the overhead number against this
        f"{prefix}_ab_spread_ms": {
            "on": [round(float(np.percentile(on_t, q)) * 1e3, 1)
                   for q in (10, 90)],
            "off": [round(float(np.percentile(off_t, q)) * 1e3, 1)
                    for q in (10, 90)]},
    }


def run_obs_overhead(n_ens: int, n_peers: int, n_slots: int, k: int,
                     seconds: float, rounds: int = 3) -> dict:
    """The observability-plane A/B arm (acceptance bound: the obs-on
    headline pipelined loop within 3% of ``RETPU_OBS=0`` on the same
    box).

    Methodology: FIXED WORK at BATCH granularity.  One live service
    per arm (the knob is read at construction), then one long stream
    of settled batches alternating on/off/on/off with the pair order
    flipping every iteration, scored by each arm's per-batch MEDIAN.
    Wall-clock windows cannot do this job on a small shared box: a
    window at the 512-ens CPU shape holds ~8 batches and back-to-back
    identical runs swing ±50%, while scheduler spikes hit single
    windows, so window-level best-of/paired-delta estimators measured
    phantom overheads of 13-50% where the batch-granular median
    reproduces at ~1%.  Interleaving at the batch level gives both
    arms the same drift and ~100 samples each; the median kills the
    spikes.  Negative overhead is box noise in the bound's favor."""
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    rng = np.random.default_rng(0)
    kind = jnp.asarray(rng.choice([eng.OP_PUT, eng.OP_GET],
                                  (k, n_ens)), jnp.int32)
    slot = jnp.asarray(rng.integers(0, n_slots, (k, n_ens)), jnp.int32)
    val = jnp.asarray(rng.integers(1, 1 << 20, (k, n_ens)), jnp.int32)
    jax.block_until_ready((kind, slot, val))

    def make(env: str) -> BatchedEnsembleService:
        """One live service per arm (the knob is read at service
        construction); warmed outside every timed window."""
        svc = _env_scoped(
            "RETPU_OBS", env,
            lambda: BatchedEnsembleService(WallRuntime(), n_ens,
                                           n_peers, n_slots,
                                           tick=None,
                                           max_ops_per_tick=k,
                                           pipeline_depth=2))
        for _ in range(3):
            svc.execute_async(kind, slot, val)
        svc.flush()
        return svc

    def batch(svc: BatchedEnsembleService) -> float:
        t0 = time.perf_counter()
        svc.execute_async(kind, slot, val)
        svc.flush()  # settle: the measured unit is one full batch
        return time.perf_counter() - t0

    on_svc, off_svc = make("1"), make("0")
    on_t, off_t, n = _interleaved_ab(on_svc, off_svc, batch,
                                     seconds, rounds)
    on_svc.stop()
    off_svc.stop()
    return _ab_scores("obs", on_t, off_t, n, k * n_ens)


def run_op_trace_overhead(n_ens: int, n_peers: int, n_slots: int,
                          k: int, seconds: float,
                          rounds: int = 3) -> dict:
    """Per-op SLO tracing A/B on the KEYED rung (acceptance bound:
    the ring within 2% of ``RETPU_SLO_RING=0``).

    The per-op ring fold lives on the kput_many/kget_many settle
    path, which the device-resident pipelined loop of
    ``run_obs_overhead`` never exercises — so the tracing overhead
    needs its own arm on the surface that actually pays it.  Both
    arms keep the FULL obs plane on (flush spans, tenant counters,
    flight ring — whose keyed-rung cost predates this round); the
    off arm disables the per-op ring ALONE via ``RETPU_SLO_RING=0``,
    so the delta isolates the tracing this A/B is accountable for.
    Same methodology as run_obs_overhead: one live service per arm,
    one long interleaved stream of settled keyed batches with the
    pair order flipping, per-arm MEDIAN per-batch time (window
    estimators lie on a small box)."""
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    keys = [f"key{j}" for j in range(k)]
    vals = [b"v%d" % j for j in range(k // 2)]

    def make(env: str) -> BatchedEnsembleService:
        svc = _env_scoped(
            "RETPU_SLO_RING", env,
            lambda: BatchedEnsembleService(WallRuntime(), n_ens,
                                           n_peers, n_slots,
                                           tick=None,
                                           max_ops_per_tick=k))
        for _ in range(2):  # compile + first elections, outside timing
            batch(svc)
        return svc

    def batch(svc: BatchedEnsembleService) -> float:
        t0 = time.perf_counter()
        futs = []
        for e in range(n_ens):
            futs.append(svc.kput_many(e, keys[:k // 2], vals))
            futs.append(svc.kget_many(e, keys[k // 2:]))
        while any(svc.queues):
            svc.flush()
        assert all(f.done for f in futs), "op-trace A/B: unsettled"
        return time.perf_counter() - t0

    on_svc, off_svc = make("4096"), make("0")
    on_t, off_t, n = _interleaved_ab(on_svc, off_svc, batch,
                                     seconds, rounds)
    # sanity: the traced arm really recorded per-op samples
    snap = on_svc.obs_registry.snapshot()
    op_lat = snap.get("retpu_op_latency_ms", {})
    traced = int(op_lat.get("count", 0)) + sum(
        int(ch.get("count", 0))
        for ch in op_lat.get("by_label", {}).values())
    on_svc.stop()
    off_svc.stop()
    out = _ab_scores("op_trace", on_t, off_t, n, k * n_ens)
    out["op_trace_samples_recorded"] = traced
    return out


def _non_marks():
    """Flight-record fields that are shape/identity metadata, not
    latency marks — the recorder's own list, so tail attribution and
    the dump's dominant-mark argmax can never drift apart."""
    from riak_ensemble_tpu.obs.flightrec import META_FIELDS
    return META_FIELDS


def run_mixed_service(n_ens: int, n_peers: int, n_slots: int, k: int,
                      seconds: float) -> dict:
    """The REALISTIC-mix rung (VERDICT r3 #5): every iteration builds
    FRESH host-side op planes — random slots, a
    PUT/GET/CAS/RMW/tombstone mix per batch — with plane construction
    INSIDE the timed loop, and
    feeds them through the host-array ``execute`` path (per-batch h2d
    included).  This is what a host-fed client actually pays per
    batch; the device-resident headline above is the TPU-native
    caller's number.  CAS rows carry real expected versions (half
    fresh-create (0,0), half against the previous batch's committed
    versions), RMW rows run table funs (add/max/xor — the fused
    kmodify's op kind, so mixed_p99 tracks the device RMW cost),
    tombstone writes are puts of 0, and tombstone READS are
    gets of slots a delete just cleared."""
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers, n_slots,
                                 tick=None, max_ops_per_tick=k)
    rng = np.random.default_rng(1)

    def build(prev_vsn):
        kind = rng.choice(
            [eng.OP_PUT, eng.OP_GET, eng.OP_CAS, eng.OP_RMW,
             eng.OP_PUT],
            (k, n_ens), p=[0.35, 0.3, 0.15, 0.1, 0.1]).astype(np.int32)
        slot = rng.integers(0, n_slots, (k, n_ens)).astype(np.int32)
        val = rng.integers(1, 1 << 20, (k, n_ens)).astype(np.int32)
        # last PUT band is tombstone writes (val 0 = delete)...
        tomb = (kind == eng.OP_PUT) & (rng.random((k, n_ens)) < 0.2)
        val[tomb] = 0
        exp_e = np.zeros((k, n_ens), np.int32)
        exp_s = np.zeros((k, n_ens), np.int32)
        # RMW rows: fun code rides the exp_epoch plane, operand the
        # val plane (the single-round device kmodify)
        rmw = kind == eng.OP_RMW
        exp_e[rmw] = rng.choice(
            [eng.RMW_ADD, eng.RMW_MAX, eng.RMW_BXOR],
            int(rmw.sum())).astype(np.int32)
        if prev_vsn is not None:
            # half the CAS rows guard against versions committed by
            # the PREVIOUS batch (real conflict behavior: some match,
            # some lost a race to this batch's earlier rounds)
            cas = kind == eng.OP_CAS
            use_prev = cas & (rng.random((k, n_ens)) < 0.5)
            pe, ps = prev_vsn
            exp_e[use_prev] = pe[use_prev]
            exp_s[use_prev] = ps[use_prev]
        return kind, slot, val, exp_e, exp_s

    # warm (compile both the exp and no-exp shapes)
    kind, slot, val, exp_e, exp_s = build(None)
    svc.execute(kind, slot, val, exp_epoch=exp_e, exp_seq=exp_s)
    svc.lat_records.clear()  # tail attribution wants steady state

    lat = []
    recs = []  # per-batch launch-latency record, aligned with lat
    ops = commits = gets_ok = 0
    prev_vsn = None
    t_end = time.perf_counter() + max(seconds, 1e-3)
    t_start = time.perf_counter()
    while time.perf_counter() < t_end or not lat:
        t0 = time.perf_counter()
        kind, slot, val, exp_e, exp_s = build(prev_vsn)
        committed, get_ok, found, value = svc.execute(
            kind, slot, val, exp_epoch=exp_e, exp_seq=exp_s)
        lat.append(time.perf_counter() - t0)
        # tail attribution rides the obs flight recorder (per-flush
        # record incl. flush_id — the same ring an anomaly dump
        # snapshots); lat_records is the RETPU_OBS=0 fallback
        recs.append(dict(svc.flight.records[-1])
                    if svc.flight.records
                    else (dict(svc.lat_records[-1])
                          if svc.lat_records else {}))
        ops += k * n_ens
        commits += int(committed.sum())
        gets_ok += int(get_ok.sum())
        # feed committed versions to the next batch's CAS rows: one
        # extra launch-free approximation — versions advance per
        # commit, so "previous batch's version" means exp planes built
        # from the device state would need a d2h; instead CAS guards
        # mix (0,0) creates with stale guesses, exercising BOTH CAS
        # outcomes (the point is mixed-kernel cost, not CAS hit rate)
        prev_vsn = (exp_e, exp_s)
    elapsed = time.perf_counter() - t_start

    # sanity: the mix must exercise all three kernel families
    assert commits > 0 and gets_ok > 0, "mixed bench: degenerate mix"
    lat_ms = np.asarray(lat) * 1000.0
    p50 = float(np.percentile(lat_ms, 50))
    # TAIL ATTRIBUTION: for every batch slower than 5x the rung's own
    # p50, name the latency mark that dominated its launch record —
    # so the mixed p99 points at a cause (d2h stall, exchange sweep,
    # plane build outside the record → 'untracked') instead of being
    # an unexplained number in the round JSON.
    tail_causes: dict = {}
    n_tail = 0
    for ms, rec in zip(lat_ms.tolist(), recs):
        if ms <= 5 * p50:
            continue
        n_tail += 1
        comps = {c: v for c, v in rec.items()
                 if c not in _non_marks()}
        tracked = sum(comps.values()) * 1e3
        if not comps or tracked < ms / 2:
            # the launch record explains under half the batch time:
            # the stall was outside the launch (host plane build, GC,
            # scheduler) — say so rather than blaming a component
            cause = "untracked_host"
        else:
            cause = max(comps, key=comps.get)
        tail_causes[cause] = tail_causes.get(cause, 0) + 1
    return {
        "mixed_ops_per_sec": ops / elapsed,
        "mixed_p50_ms": p50,
        "mixed_p99_ms": float(np.percentile(lat_ms, 99)),
        "mixed_commit_fraction": round(commits / max(ops, 1), 3),
        "mixed_tail_batches": n_tail,
        "mixed_tail_causes": tail_causes,
        "mixed_tail_top_cause": (max(tail_causes, key=tail_causes.get)
                                 if tail_causes else None),
        # flight-recorder evidence: trigger firings during the rung
        # (an anomaly here comes with a ring+fingerprint dump when
        # RETPU_OBS_DUMP_DIR is set — the diagnosable mixed-p99)
        "mixed_flight_anomalies": svc.flight.anomalies,
    }


def run_rmw_service(n_ens: int, n_peers: int, n_slots: int, k: int,
                    seconds: float) -> dict:
    """The RMW rung: a counter-increment STORM — k concurrent
    kmodify(rmw:add 1) of ONE key per ensemble per iteration — as a
    device vs host-fallback A/B.

    The device arm resolves the funref against the mod-fun table: all
    k increments fuse into k one-round OP_RMW ops in a single flush
    and can never CAS-conflict.  The host arm runs the same int32
    semantics as a plain callable (table-unresolvable), taking the
    classic read → fn → CAS cycle: every attempt in a contended flush
    shares one read version, one CAS wins, the rest conflict and
    retry under jittered backoff — rounds per op grow with contention
    instead of staying at 1.  Reports ops/s, flushes per converged
    iteration for both arms, and the speedup."""
    from riak_ensemble_tpu import funref
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    out: dict = {}
    for arm in ("device", "host"):
        svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                     n_slots, tick=None,
                                     max_ops_per_tick=k)
        if arm == "device":
            fn = funref.ref("rmw:add", 1)
        else:
            def fn(vsn, cur):  # same int32 semantics, host-only
                return funref.i32(int(cur) + 1)

        def one_round():
            futs = [svc.kmodify(e, "ctr", fn, 0,
                                retries=2 * k + 4)
                    for e in range(n_ens) for _ in range(k)]
            flushes0 = svc._flush_calls
            while not all(f.done for f in futs):
                svc.flush()
            assert all(f.value[0] == "ok" for f in futs), \
                f"rmw bench ({arm}): increments failed"
            return len(futs), svc._flush_calls - flushes0

        one_round()  # warm: compile, elections, slot allocation
        ops = flushes = iters = 0
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while time.perf_counter() < t_end or not iters:
            n, fl = one_round()
            ops += n
            flushes += fl
            iters += 1
        elapsed = time.perf_counter() - t0
        if arm == "device":
            assert svc.rmw_device_fastpath > 0, \
                "device arm never took the RMW fast path"
            assert svc.rmw_conflicts == 0, \
                "device RMWs must not CAS-conflict"
        out[f"rmw_{arm}_ops_per_sec"] = ops / elapsed
        out[f"rmw_{arm}_flushes_per_round"] = flushes / iters
        out[f"rmw_{arm}_conflicts"] = svc.rmw_conflicts
        svc.stop()
    out["rmw_device_speedup"] = (out["rmw_device_ops_per_sec"]
                                 / out["rmw_host_ops_per_sec"])
    return out


def run_skewed_service(n_ens: int, n_peers: int, n_slots: int, k: int,
                       seconds: float, warm: bool = True,
                       baseline: bool = True) -> dict:
    """The SKEWED-load rung — active-column compaction's target
    shape: zipf-distributed ensemble pick, so a handful of hot
    ensembles carry deep queues while most of the [K, E] grid idles
    (the partial-load shape a production front-end actually sees; one
    hot ensemble still forces the full K bucket across all E
    columns).  Keyed kput/kget futures through flush().

    ``warm`` pre-compiles the (K, A) bucket grid first (the dispatch
    p99 fix — without it, first-use compiles of each new bucket land
    inside the timed loop).  ``baseline`` also runs the identical
    loop with compaction disabled (RETPU_COMPACT=0 semantics), so the
    JSON carries the compaction speedup as an A/B, not a claim.
    Reports payload_bytes_per_flush and grid_occupancy so the
    trajectory tracks a regression that re-inflates the transfer."""
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    def arm(compact: bool) -> dict:
        svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                     n_slots, tick=None,
                                     max_ops_per_tick=k)
        svc._compact = compact
        if warm:
            svc.warmup()
        rng = np.random.default_rng(3)
        n_draw = 4 * k

        def one_round():
            ens = np.minimum(rng.zipf(1.5, n_draw) - 1, n_ens - 1)
            futs = []
            for i, e in enumerate(ens.tolist()):
                if i % 2:
                    futs.append(svc.kget(e, f"key{i % 4}"))
                else:
                    futs.append(svc.kput(e, f"key{i % 4}", i + 1))
            while any(svc.queues):
                svc.flush()
            assert all(f.done for f in futs), "skewed bench: unsettled"
            return len(futs)

        one_round()  # slots allocate; elections fold in
        svc.payload_bytes = 0
        svc.payload_bytes_full_width = 0
        svc._occ_sum = 0.0
        svc._occ_launches = 0
        f0 = svc.flushes
        ops = 0
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while time.perf_counter() < t_end or not ops:
            ops += one_round()
        elapsed = time.perf_counter() - t0
        flushes = max(svc.flushes - f0, 1)
        st = svc.stats()
        svc.stop()
        return {
            "ops_per_sec": ops / elapsed,
            "payload_bytes_per_flush": svc.payload_bytes / flushes,
            "payload_bytes_full_width_per_flush":
                svc.payload_bytes_full_width / flushes,
            "grid_occupancy": round(st["grid_occupancy"], 4),
        }

    a = arm(True)
    out = {
        "skewed_ops_per_sec": a["ops_per_sec"],
        "payload_bytes_per_flush": round(
            a["payload_bytes_per_flush"], 1),
        "payload_bytes_full_width_per_flush": round(
            a["payload_bytes_full_width_per_flush"], 1),
        "grid_occupancy": a["grid_occupancy"],
    }
    if baseline:
        b = arm(False)
        out["skewed_baseline_ops_per_sec"] = b["ops_per_sec"]
        out["skewed_compaction_speedup"] = round(
            a["ops_per_sec"] / b["ops_per_sec"], 2)
    return out


def run_read_service(n_ens: int, n_peers: int, n_slots: int, k: int,
                     seconds: float, warm: bool = True,
                     baseline: bool = True) -> dict:
    """The READ-HEAVY rung (90/10 kget/kput over pre-populated keys)
    — the lease-protected read fast path's target shape, as a
    fastpath-on vs fastpath-off A/B.

    With the fast path on, the 90% reads are answered from the
    leader's committed host mirror (no OP_GET row, no flush) and only
    the writes launch; the off arm routes every read through the
    device round — write rounds and read rounds compete for the same
    [K, E] grid.  Reports both arms' ops/sec, the speedup, the
    fast-path hit rate + miss reasons, per-round latency, and an
    EQUIVALENCE sweep: after the timed loop every key is read through
    the fast path AND through a forced device round, and the values
    must agree (the linearizable-read contract, cheap form)."""
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    # disjoint read/write key sets: the rung's reads are UNCONTENDED
    # (the hit-rate tripwire's premise) — writes land on their own
    # keys, so no read parks on a pending same-slot write
    n_keys = max(1, min(n_slots // 2, 8))
    keys = [f"key{j}" for j in range(n_keys)]
    wkeys = [f"wkey{j}" for j in range(n_keys)]

    def arm(fast: bool) -> dict:
        svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                     n_slots, tick=None,
                                     max_ops_per_tick=k)
        svc.set_fast_reads(fast)
        if warm:
            svc.warmup()
        # populate every key so the off arm's reads genuinely launch
        # (absent keys short-circuit NOTFOUND in both arms)
        futs = [svc.kput(e, kk, b"r%d" % j)
                for e in range(n_ens)
                for j, kk in enumerate(keys + wkeys)]
        while any(svc.queues):
            svc.flush()
        assert all(f.done and f.value[0] == "ok" for f in futs), \
            "read bench: populate failed"

        # EXACTLY ceil(k/10) writes per ensemble per round — the
        # 90/10 mix with a STABLE flush K bucket, so the warm round
        # compiles every shape the timed loop uses (varying write
        # draws would bounce the pow2 bucket and bill fresh XLA
        # compiles to random rounds).  Both arms ride the VECTORIZED
        # surface (kget_many/kput_many): the scalar path's per-op
        # Python would cap the fast arm long before the device does,
        # understating exactly the device-round cost this rung
        # measures.
        n_writes = max(1, (k + 9) // 10)
        read_keys = [keys[j % n_keys] for j in range(k - n_writes)]
        wvals = [b"w%d" % j for j in range(n_writes)]

        # failed results accumulate across EVERY round (not just the
        # final one) so a mid-run blip can't hide inside the
        # throughput number
        failed = [0]

        def one_round(shift: int = 0):
            futs = []
            wk = [wkeys[(shift + j) % n_keys] for j in range(n_writes)]
            for e in range(n_ens):
                futs.append(svc.kget_many(e, read_keys))
                futs.append(svc.kput_many(e, wk, wvals))
            while any(svc.queues):
                svc.flush()
            svc.flush()  # settle any in-flight tail
            assert all(f.done for f in futs), "read bench: unsettled"
            failed[0] += sum(1 for f in futs for r in f.value
                             if r[0] != "ok")
            return futs, n_ens * len(read_keys)

        # TWO warm rounds: the first's reads may still miss (the
        # populate flush's compile outlived its own lease grant), so
        # it re-leases and serves full-grid; the second exercises the
        # real steady state — fast reads + the write-only small-K
        # flush — compiling that shape outside the measured window
        # and outside the hit-rate tripwire
        one_round()
        one_round()
        svc.read_fastpath_hits = 0
        svc.read_fastpath_misses = 0
        svc.read_fastpath_miss_reasons.clear()
        failed[0] = 0  # warm rounds excluded, like the counters

        # -- phase 1: the 90/10 MIXED loop (write-coupled number:
        # every round still pays its write flush, now K=ceil(k/10)
        # instead of K=k — the reclaimed-grid write win rides here)
        lat: list = []
        ops = reads = rounds = 0
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while time.perf_counter() < t_end or not lat:
            tb = time.perf_counter()
            futs, n_reads = one_round(shift=rounds)
            lat.append(time.perf_counter() - tb)
            ops += n_ens * k
            reads += n_reads
            rounds += 1
        elapsed = time.perf_counter() - t0
        assert failed[0] == 0, \
            f"read bench: {failed[0]} op(s) failed across the mix"

        # -- phase 2: the UNCONTENDED read-only loop — the
        # decoupling headline.  Fast-path rounds never launch (reads
        # answer from the mirror; the periodic lease-renewal round
        # when the margin trips is part of the honest steady state);
        # the off arm pays a full device round per batch.
        ro_reads = 0
        ro_lat: list = []
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while time.perf_counter() < t_end or not ro_lat:
            tb = time.perf_counter()
            futs = [svc.kget_many(e, read_keys)
                    for e in range(n_ens)]
            while any(svc.queues):
                svc.flush()
            svc.flush()
            assert all(f.done for f in futs), "read bench: unsettled"
            failed[0] += sum(1 for f in futs for r in f.value
                             if r[0] != "ok")
            ro_lat.append(time.perf_counter() - tb)
            ro_reads += n_ens * len(read_keys)
        ro_elapsed = time.perf_counter() - t0
        assert failed[0] == 0, \
            f"read bench: {failed[0]} read(s) failed (read-only phase)"
        # counters snapshot BEFORE the equivalence sweep (its forced
        # device reads must not pollute the hit-rate number)
        hits = svc.read_fastpath_hits
        misses = svc.read_fastpath_misses
        miss_reasons = dict(svc.read_fastpath_miss_reasons)

        # equivalence sweep: fast-path answers == forced device-round
        # answers for every key (run on the FAST arm; trivially true
        # on the off arm)
        equiv = 0
        if fast:
            for e in range(0, n_ens, max(1, n_ens // 16)):
                fast_futs = [svc.kget(e, kk) for kk in keys]
                svc.set_fast_reads(False)
                dev_futs = [svc.kget(e, kk) for kk in keys]
                while any(svc.queues):
                    svc.flush()
                svc.set_fast_reads(True)
                for kk, ff, df in zip(keys, fast_futs, dev_futs):
                    assert ff.value == df.value, (
                        "fast/device read divergence at "
                        f"({e}, {kk}): {ff.value!r} vs {df.value!r}")
                    equiv += 1
        flushes = svc.stats()["flushes"]
        svc.stop()
        lat_ms = np.asarray(lat) * 1e3
        return {
            "ops_per_sec": ops / elapsed,
            "read_ops_per_sec": reads / elapsed,
            "read_only_ops_per_sec": ro_reads / ro_elapsed,
            "read_only_p50_ms": float(
                np.percentile(np.asarray(ro_lat) * 1e3, 50)),
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            "hits": hits, "misses": misses,
            "hit_rate": hits / max(hits + misses, 1),
            "miss_reasons": miss_reasons,
            "flushes": flushes,
            "equivalence_checked": equiv,
        }

    a = arm(True)
    out = {
        "read_service_ops_per_sec": a["ops_per_sec"],
        "read_only_ops_per_sec": a["read_only_ops_per_sec"],
        "read_only_p50_ms": round(a["read_only_p50_ms"], 3),
        "read_p50_ms": round(a["p50_ms"], 3),
        "read_p99_ms": round(a["p99_ms"], 3),
        "read_fastpath_hits": a["hits"],
        "read_fastpath_misses": a["misses"],
        "read_hit_rate": round(a["hit_rate"], 4),
        "read_miss_reasons": a["miss_reasons"],
        "read_flushes": a["flushes"],
        "read_equivalence_checked": a["equivalence_checked"],
        "read_equivalence_ok": True,  # the sweep asserts on mismatch
    }
    if baseline:
        b = arm(False)
        # the 90/10 loop's A/B: write-coupled (every round keeps its
        # write flush) — the reclaimed-grid mixed-throughput win
        out["read_baseline_ops_per_sec"] = b["ops_per_sec"]
        out["read_mixed_speedup"] = round(
            a["ops_per_sec"] / b["ops_per_sec"], 2)
        # the read-only A/B: the decoupling headline — mirror-served
        # reads vs a device round per batch
        out["read_baseline_only_ops_per_sec"] = \
            b["read_only_ops_per_sec"]
        out["read_baseline_flushes"] = b["flushes"]
        out["read_fastpath_speedup"] = round(
            a["read_only_ops_per_sec"] / b["read_only_ops_per_sec"],
            2)
    return out


def run_keyed_service(n_ens: int, n_peers: int, n_slots: int, k: int,
                      seconds: float) -> float:
    """The FUTURE-BASED keyed path: kput/kget client futures queued
    per ensemble, resolved through flush() against the real host
    payload store (values are Python bytes behind int32 handles).
    This measures what a keyed client observes — per-op Python
    bookkeeping included — as distinct from the bulk array surface.
    """
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers, n_slots,
                                 tick=None, max_ops_per_tick=k)
    # Warm up: allocate slots, compile the flush shape, elect.
    futs = [svc.kput(e, f"key{j}", b"w%d" % j)
            for e in range(n_ens) for j in range(k)]
    while any(svc.queues):
        svc.flush()
    assert all(f.done and f.value[0] == "ok" for f in futs)

    ops = 0
    t_end = time.perf_counter() + max(seconds, 1e-3)
    t0 = time.perf_counter()
    while time.perf_counter() < t_end or not ops:
        futs = []
        for e in range(n_ens):
            for j in range(k // 2):
                futs.append(svc.kput(e, f"key{j}", b"v%d" % j))
            for j in range(k // 2, k):
                futs.append(svc.kget(e, f"key{j}"))
        while any(svc.queues):
            svc.flush()
        ops += len(futs)
    elapsed = time.perf_counter() - t0
    assert all(f.done and f.value[0] == "ok" for f in futs), \
        "keyed bench: ops failed"
    scalar_rate = ops / elapsed

    # The VECTORIZED keyed surface (kput_many/kget_many): same keyed
    # semantics, struct-of-arrays queue entries, one future per batch.
    keys = [f"key{j}" for j in range(k)]
    vals = [b"v%d" % j for j in range(k // 2)]
    ops = 0
    t_end = time.perf_counter() + max(seconds, 1e-3)
    t0 = time.perf_counter()
    while time.perf_counter() < t_end or not ops:
        futs = []
        for e in range(n_ens):
            futs.append(svc.kput_many(e, keys[:k // 2], vals))
            futs.append(svc.kget_many(e, keys[k // 2:]))
        while any(svc.queues):
            svc.flush()
        ops += n_ens * k
        # same parity check as the scalar phase: EVERY batch op acked
        assert all(f.done and all(r[0] == "ok" for r in f.value)
                   for f in futs), "keyed_many bench: ops failed"
    elapsed = time.perf_counter() - t0
    return {"scalar": scalar_rate, "batched": ops / elapsed}


def run_repgroup(seconds: float, smoke: bool,
                 baseline: bool = True) -> dict:
    """Cross-host replication-group rung: a 3-host group, fsync WALs,
    host-majority commit barrier.  Measures the keyed client surface
    end to end — what the availability story costs per op vs the
    single-process service.

    Round 6: the main arm ships changed-slot DELTA frames (one
    coalesced raw frame per flush per link, batched replica apply);
    the ``baseline`` arm re-runs the identical workload with
    ``RETPU_REPL_DELTA=0`` semantics (full-plane frames) and reports
    ``repl_delta_speedup``.  Both arms meter shipped bytes per entry
    against the full-plane equivalent and break the leader's
    replication cost into build/encode/ack components.  The smoke
    shape runs the replica hosts IN PROCESS (threaded servers, shared
    jit cache) and additionally verifies delta/full equivalence: every
    replica lane's engine state must be bit-equal to the leader's."""
    n_ens, n_slots, k = (16, 16, 8) if smoke else (64, 32, 16)
    out = _repgroup_arm(seconds, smoke, n_ens, n_slots, k, delta=True)
    res = {
        "repgroup_ops_per_sec": out["ops_per_sec"],
        "repgroup_p50_ms": out["p50_ms"],
        "repgroup_p99_ms": out["p99_ms"],
        "repl_bytes_per_entry": out["bytes_per_entry"],
        "repl_bytes_per_entry_full_plane": out["bytes_full_equiv"],
        "repl_delta_entries": out["delta_entries"],
        "repl_full_entries": out["full_entries"],
        "repl_ship_breakdown_ms": out["breakdown_ms"],
    }
    if "equivalence_ok" in out:
        res["repl_equivalence_ok"] = out["equivalence_ok"]
    if baseline:
        base = _repgroup_arm(seconds, smoke, n_ens, n_slots, k,
                             delta=False)
        res["repgroup_baseline_ops_per_sec"] = base["ops_per_sec"]
        res["repl_bytes_per_entry_baseline"] = base["bytes_per_entry"]
        res["repl_delta_speedup"] = round(
            out["ops_per_sec"] / max(base["ops_per_sec"], 1e-9), 3)
    return res


def _repgroup_spawn_subprocess(n_ens, n_slots, tmp, i, procs):
    """One replica host OS process (the full-shape arm: real failure
    domains, real sockets, real fsync).  The child lands in ``procs``
    the moment it exists — BEFORE the ready-line parse — so a
    malformed ready line can't leak a live replica past the caller's
    SIGKILL sweep."""
    import subprocess
    import textwrap

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {repo!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        # replica warmup compiles the same pow2 ladder as the
        # leader: share the persistent compile cache or each
        # child pays minutes of XLA compile on a 1-core box
        jax.config.update("jax_compilation_cache_dir",
                          {repo!r} + "/.jax_cache")
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0)
        from riak_ensemble_tpu.parallel import repgroup
        repgroup.main(["--n-ens", "{n_ens}", "--group-size", "3",
                       "--n-slots", "{n_slots}", "--fast",
                       "--data-dir", {tmp!r} + "/r{i}"])
    """)
    # stderr → DEVNULL and stdout drained by a daemon thread after
    # the ready line: replicas live for the whole bench, and a chatty
    # child blocking on a full 64 KiB pipe would stop acking and
    # stall the quorum (review r4)
    p = subprocess.Popen([sys.executable, "-c", child],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True,
                         env=env)
    procs.append(p)
    line = p.stdout.readline()
    assert line, "repgroup replica died before ready line"
    parts = dict(kv.split("=") for kv in line.split()[2:])
    import threading
    threading.Thread(target=lambda f=p.stdout: [None for _ in f],
                     daemon=True).start()
    return int(parts["repl"])


def _repgroup_arm(seconds: float, smoke: bool, n_ens: int,
                  n_slots: int, k: int, delta: bool) -> dict:
    import shutil
    import signal
    import tempfile

    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.parallel import repgroup
    from riak_ensemble_tpu.parallel.batched_host import WallRuntime

    tmp = tempfile.mkdtemp(prefix="bench_repgroup_")
    procs = []
    servers = []
    try:
        ports = []
        if smoke:
            for i in (1, 2):
                servers.append(repgroup.ReplicaServer(
                    n_ens, 3, n_slots, data_dir=f"{tmp}/r{i}",
                    config=fast_test_config()))
            ports = [s.repl_port for s in servers]
        else:
            for i in (1, 2):
                ports.append(_repgroup_spawn_subprocess(
                    n_ens, n_slots, tmp, i, procs))
        svc = repgroup.ReplicatedService(
            WallRuntime(), n_ens, 1, n_slots, group_size=3,
            peers=[("127.0.0.1", p) for p in ports],
            ack_timeout=60.0, max_ops_per_tick=k,
            config=fast_test_config(), data_dir=tmp + "/leader",
            # the PR-1 async launch pipeline: overlap round N+1's
            # device step with round N's resolve/build/ship (the
            # repl_window ack pipeline stacks on top — settles stay
            # quorum-barriered either way)
            pipeline_depth=2)
        if not delta:
            svc._repl_delta = False  # the RETPU_REPL_DELTA=0 arm
        repgroup.warmup_kernels(svc)
        assert svc.takeover(), "repgroup bench: takeover failed"

        keys = [f"key{j}" for j in range(k)]
        vals = [b"v%d" % j for j in range(k // 2)]

        # smoke: writes rotate over a QUARTER of the columns per
        # round — the skewed serving shape (§7/§10 premise: the live
        # write set is sparse relative to the grid), so the byte
        # meter exercises the payload-proportional-to-change property
        # the tier-1 tripwire guards.  The full shape keeps the
        # seed's dense round unchanged, for ops_per_sec comparability
        # across bench rounds.
        stride = 4 if smoke else 1
        rnd = [0]

        def one_round():
            # dense warm round regardless of skew: every column
            # allocates its slots and elects BEFORE the meter starts
            futs = []
            for e in range(n_ens):
                futs.append(svc.kput_many(e, keys[:k // 2], vals))
                futs.append(svc.kget_many(e, keys[k // 2:]))
            while any(svc.queues):
                svc.flush()
            assert all(f.done for f in futs)
            return n_ens * k

        one_round()  # warm (slots, remote compile, sync settled)
        svc.ack_timeout = 10.0
        g0 = dict(svc.stats()["group"])

        # Pipelined measured loop (VERDICT r4 weak #5): keep up to 4
        # rounds in flight so flush N+1's build/ship/local-launch
        # overlaps flush N's replica acks (the windowed PeerLink +
        # deferred commit barrier).  Latency is client-observed:
        # submit -> every future of the round resolved.
        def submit():
            futs = []
            rnd[0] += 1
            for e in range(n_ens):
                if e % stride == rnd[0] % stride:
                    futs.append(svc.kput_many(e, keys[:k // 2], vals))
                futs.append(svc.kget_many(e, keys[k // 2:]))
            return futs

        lat = []
        ops = 0
        inflight = []
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now < t_end and len(inflight) < 4:
                inflight.append((now, submit()))
            svc.flush()
            while inflight and all(f.done for f in inflight[0][1]):
                tb, _futs = inflight.pop(0)
                lat.append(time.perf_counter() - tb)
                # each future is a many-batch of k//2 keys (dense:
                # 2*n_ens batches/round = the seed's n_ens*k count)
                ops += len(_futs) * (k // 2)
            if now >= t_end and (not inflight and lat):
                break
            assert now < t_end + 120.0, "repgroup bench wedged"
        elapsed = time.perf_counter() - t0
        g = svc.stats()["group"]
        assert g["quorum_failures"] == 0, g
        assert g["peers_synced"] == 2, g
        entries = max((g["repl_delta_entries"] + g["repl_full_entries"])
                      - (g0["repl_delta_entries"]
                         + g0["repl_full_entries"]), 1)
        frames = max(g["repl_frames"] - g0["repl_frames"], 1)
        acked = max(g["repl_acked_batches"] - g0["repl_acked_batches"],
                    1)
        out = {
            "ops_per_sec": round(ops / elapsed, 1),
            "p50_ms": round(float(np.percentile(
                np.asarray(lat) * 1e3, 50)), 3),
            "p99_ms": round(float(np.percentile(
                np.asarray(lat) * 1e3, 99)), 3),
            "bytes_per_entry": round(
                (g["repl_bytes_sections"] - g0["repl_bytes_sections"])
                / entries, 1),
            "bytes_full_equiv": round(
                (g["repl_bytes_full_equiv"]
                 - g0["repl_bytes_full_equiv"]) / entries, 1),
            "delta_entries": g["repl_delta_entries"]
            - g0["repl_delta_entries"],
            "full_entries": g["repl_full_entries"]
            - g0["repl_full_entries"],
            "breakdown_ms": {
                "build": round((g["repl_build_s"] - g0["repl_build_s"])
                               / entries * 1e3, 3),
                "encode": round(
                    (g["repl_encode_s"] - g0["repl_encode_s"])
                    / frames * 1e3, 3),
                "ack": round((g["repl_ack_s"] - g0["repl_ack_s"])
                             / acked * 1e3, 3),
            },
        }
        if smoke:
            # delta/full equivalence tripwire: every replica lane's
            # engine state bit-equal to the leader's after drain.
            # Quorum settles at majority, so first wait for every
            # lane to reach the leader's applied position (a slow
            # replica may still be draining its link backlog).
            for _ in range(3):
                svc.heartbeat()
            svc._drain_pending(block_all=True)
            want_pos = (svc.core.applied_ge, svc.core.applied_seq)
            end = time.monotonic() + 60.0
            while time.monotonic() < end:
                done = True
                for s in servers:
                    with s._lock:
                        done = done and ((s.core.applied_ge,
                                          s.core.applied_seq)
                                         >= want_pos)
                if done:
                    break
                time.sleep(0.02)
            d_l = repgroup.dump_state(svc)
            ok = True
            for s in servers:
                with s._lock:
                    d_r = repgroup.dump_state(s.svc)
                ok = ok and d_l[0] == d_r[0]
            out["equivalence_ok"] = ok
        svc.stop()
        return out
    finally:
        for s in servers:
            s.stop()
        for p in procs:
            try:
                p.send_signal(signal.SIGKILL)
            except ProcessLookupError:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def run_fleet_obs_overhead(seconds: float, n_ens: int = 16,
                           n_slots: int = 16, k: int = 8,
                           rounds: int = 3) -> dict:
    """Fleet-federation overhead A/B on the replicated smoke rung
    (acceptance bound: federation pull ON within 2% of OFF — the
    PR 8 op-trace bar).

    The standing watchdog pull is the only fleet-obs cost a serving
    leader pays continuously: every cadence it posts one ``obsq``
    timeline request per link (riding the SAME FIFO socket as the
    apply stream) and harvests the previous window's responses.  The
    A/B: two identical in-process 3-host groups, the ON arm with
    ``RETPU_WATCHDOG=1`` and a deliberately aggressive cadence (8
    flushes — serving defaults evaluate 8x less often, so the bound
    measured here is conservative), the OFF arm ``RETPU_WATCHDOG=0``;
    one long interleaved stream of settled keyed rounds at batch
    granularity with the pair order flipping (the PR 6 methodology —
    window estimators lie on a small box), per-arm medians."""
    import shutil
    import tempfile

    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.parallel import repgroup
    from riak_ensemble_tpu.parallel.batched_host import WallRuntime

    tmp = tempfile.mkdtemp(prefix="bench_fleetobs_")
    packs = []
    keys = [f"key{j}" for j in range(k)]
    vals = [b"v%d" % j for j in range(k // 2)]

    def make(tag: str, env: str):
        servers = [repgroup.ReplicaServer(
            n_ens, 3, n_slots, data_dir=f"{tmp}/{tag}_r{i}",
            config=fast_test_config()) for i in (1, 2)]
        svc = _env_scoped(
            "RETPU_WATCHDOG", env,
            lambda: repgroup.ReplicatedService(
                WallRuntime(), n_ens, 1, n_slots, group_size=3,
                peers=[("127.0.0.1", s.repl_port) for s in servers],
                ack_timeout=60.0, max_ops_per_tick=k,
                config=fast_test_config(),
                data_dir=f"{tmp}/{tag}_leader"))
        repgroup.warmup_kernels(svc)
        assert svc.takeover(), "fleet-obs bench: takeover failed"
        if env == "1":
            # aggressive cadence: the measured arm pulls 8x more
            # often than the serving default — the bound stays
            # conservative
            svc.watchdog.cadence = 8
        pack = {"svc": svc, "servers": servers}
        packs.append(pack)
        batch(pack)  # warm: slots, remote compile, first sync
        svc.ack_timeout = 10.0
        return pack

    def batch(pack) -> float:
        svc = pack["svc"]
        t0 = time.perf_counter()
        futs = []
        for e in range(n_ens):
            futs.append(svc.kput_many(e, keys[:k // 2], vals))
            futs.append(svc.kget_many(e, keys[k // 2:]))
        while any(svc.queues):
            svc.flush()
        assert all(f.done for f in futs), "fleet-obs A/B: unsettled"
        return time.perf_counter() - t0

    try:
        on_pack, off_pack = make("on", "1"), make("off", "0")
        on_t, off_t, n = _interleaved_ab(on_pack, off_pack, batch,
                                         seconds, rounds)
        on_svc, off_svc = on_pack["svc"], off_pack["svc"]
        out = _ab_scores("fleet_obs", on_t, off_t, n, k * n_ens)
        # sanity: the ON arm really pulled (posted obsq sidebands and
        # refreshed at least one link's clock estimate), the OFF arm
        # really didn't — otherwise the A/B measured nothing
        out["fleet_obs_pulls"] = int(on_svc.watchdog.pulls)
        out["fleet_obs_watchdog_evals"] = int(on_svc.watchdog.evals)
        clk = [l.clock.samples for l in on_svc._links]
        out["fleet_obs_clock_samples"] = int(sum(clk))
        assert on_svc.watchdog.pulls > 0, \
            "fleet-obs ON arm never pulled — cadence plumbing broken"
        assert off_svc.watchdog.pulls == 0, \
            "fleet-obs OFF arm pulled despite RETPU_WATCHDOG=0"
        return out
    finally:
        for pack in packs:
            try:
                pack["svc"].stop()
            except Exception:
                pass
            for s in pack["servers"]:
                s.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def run_faultsweep(seconds: float, smoke: bool) -> dict:
    """Adversarial fault-injection rungs (docs/ARCHITECTURE.md §13):
    what the system does when the NETWORK or the DISK misbehaves,
    measured instead of asserted.

    1. **RTT sweep** — a live leader + replica host (group of 2, so
       every commit's quorum crosses the injected link) under 0/1/5 ms
       of injected per-link ack RTT, at launch ``pipeline_depth`` 1
       (with a 1-deep ack window and a serial client loop — the
       pre-pipelining world) vs 2 (4-deep window, windowed client).
       The depth-2 arm must WIN once the link is slow: the PR 1/PR 5
       pipelining claims, finally falsifiable on one box.
    2. **Fsync-delay rung** — the keyed WAL'd closed loop with the
       fsync barrier delayed (the slow-disk nemesis): what a slow
       disk costs per op with the flush-batched WAL amortizing it.
    3. **Noisy-tenant rung** — one hot tenant hammering a few rows
       next to many near-idle tenants; the per-tenant attribution
       plane reports the QUIET tenants' p99 with active-column
       compaction on vs off (`quiet_p99_ratio` < 1 = compaction is
       isolating the quiet tenants from the hot tenant's launch
       grid).

    The injected fault config is embedded in the result next to the
    stage's box fingerprint, so a round JSON can never present a
    nemesis number as a clean-box number."""
    n_ens, n_slots, k = (8, 8, 8) if smoke else (32, 16, 16)
    rtts = (0.0, 1.0) if smoke else (0.0, 1.0, 5.0)
    sweep = []
    for rtt in rtts:
        point = {"rtt_ms": rtt}
        for depth in (1, 2):
            r = _faultsweep_rtt_arm(n_ens, n_slots, k, seconds,
                                    depth, rtt)
            point[f"depth{depth}_ops_per_sec"] = r["ops_per_sec"]
            point[f"depth{depth}_p50_ms"] = r["p50_ms"]
            point[f"depth{depth}_p99_ms"] = r["p99_ms"]
        point["depth2_speedup"] = round(
            point["depth2_ops_per_sec"]
            / max(point["depth1_ops_per_sec"], 1e-9), 3)
        sweep.append(point)

    fsync_ms = 2.0
    base = _faultsweep_fsync_arm(n_ens, n_slots, k, seconds, 0.0)
    slow = _faultsweep_fsync_arm(n_ens, n_slots, k, seconds,
                                 fsync_ms)
    fsync = {
        "fsync_delay_ms": fsync_ms,
        "ops_per_sec": slow["ops_per_sec"],
        "baseline_ops_per_sec": base["ops_per_sec"],
        "slowdown": round(base["ops_per_sec"]
                          / max(slow["ops_per_sec"], 1e-9), 3),
        "injected_fsync_delays": slow["fsync_delays"],
    }

    nshape = (16, 8, 8) if smoke else (512, 16, 32)
    noisy_on = _noisy_tenant_arm(*nshape, seconds, compact=True)
    noisy_off = _noisy_tenant_arm(*nshape, seconds, compact=False)
    noisy = {
        "n_ens": nshape[0],
        "hot_ops": noisy_on["hot_ops"],
        "quiet_ops": noisy_on["quiet_ops"],
        "quiet_p99_ms_compact": noisy_on["quiet_p99_ms"],
        "quiet_p99_ms_nocompact": noisy_off["quiet_p99_ms"],
        "hot_p99_ms_compact": noisy_on["hot_p99_ms"],
        "ops_per_sec_compact": noisy_on["ops_per_sec"],
        "ops_per_sec_nocompact": noisy_off["ops_per_sec"],
        "quiet_p99_ratio": round(
            noisy_on["quiet_p99_ms"]
            / max(noisy_off["quiet_p99_ms"], 1e-9), 3),
    }

    # Mesh rung (one shape): the SAME depth-1/2 A/B at the deepest
    # injected-RTT point with the LEADER's engine sharded over the
    # 8-device 'ens' mesh — the pipelining claim must survive sharded
    # serving, not just the single-shard lane.  Gated on the stage
    # environment actually exposing 8 devices (the driver injects
    # XLA_FLAGS for this stage); recorded beside, not folded into,
    # the single-shard headline speedup.
    import jax
    mesh = None
    if not smoke and jax.device_count() >= 8:
        from riak_ensemble_tpu.parallel.mesh import mesh_engine
        engine = mesh_engine(8)
        mrtt = max(rtts)
        mesh = {"rtt_ms": mrtt, "mesh_devices": 8}
        for depth in (1, 2):
            r = _faultsweep_rtt_arm(n_ens, n_slots, k, seconds,
                                    depth, mrtt, engine=engine)
            mesh[f"depth{depth}_ops_per_sec"] = r["ops_per_sec"]
            mesh[f"depth{depth}_p99_ms"] = r["p99_ms"]
        mesh["depth2_speedup"] = round(
            mesh["depth2_ops_per_sec"]
            / max(mesh["depth1_ops_per_sec"], 1e-9), 3)

    # headline = the DEEPEST injected-RTT point (>=1 ms): the claim
    # is "depth 2 wins once the link is slow", and the slowest link
    # is where the overlap signal clears this box's noise floor (at
    # 1 ms the injected delay is under 10% of a batch p50 on the
    # 1-core CPU rung — cross-run noise dominates there; the full
    # per-point sweep rides the JSON either way)
    speedup_deep = next((p["depth2_speedup"] for p in reversed(sweep)
                         if p["rtt_ms"] >= 1.0), None)
    return {
        "faultsweep": {
            "shape": {"n_ens": n_ens, "n_slots": n_slots, "k": k},
            "rtt_sweep": sweep,
            "mesh_rtt": mesh,
            "fsync": fsync,
            "noisy_tenant": noisy,
            # the nemesis that produced these numbers, embedded so
            # the round JSON carries fault config + box fingerprint
            # side by side (acceptance requirement)
            "fault_config": {
                "rtt_ms_points": list(rtts),
                "rtt_side": "ack (replica→leader)",
                "fsync_ms": fsync_ms,
                "knobs": {"RETPU_FAULT_RTT_MS": "<per-link>",
                          "RETPU_FAULT_FSYNC_MS": str(fsync_ms)},
            },
        },
        "faultsweep_depth2_speedup": speedup_deep,
    }


def _faultsweep_rtt_arm(n_ens: int, n_slots: int, k: int,
                        seconds: float, depth: int,
                        rtt_ms: float, engine=None) -> dict:
    """One (pipeline_depth, injected-ack-RTT) point: leader + ONE
    in-process replica host (group of 2 — the replica's ack is on
    every commit path), keyed closed loop, client window matched to
    the depth (1 = fully serial, the pre-PR1 arm).  ``engine`` shards
    the LEADER's lane (the replica host re-executes op planes
    single-shard — host replication is placement-agnostic)."""
    import shutil
    import tempfile

    from riak_ensemble_tpu import faults
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.parallel import repgroup
    from riak_ensemble_tpu.parallel.batched_host import WallRuntime

    tmp = tempfile.mkdtemp(prefix="bench_faultsweep_")
    server = None
    svc = None
    try:
        server = repgroup.ReplicaServer(
            n_ens, 2, n_slots, data_dir=f"{tmp}/r1",
            config=fast_test_config())
        svc = repgroup.ReplicatedService(
            WallRuntime(), n_ens, 1, n_slots, group_size=2,
            peers=[("127.0.0.1", server.repl_port)],
            ack_timeout=60.0, max_ops_per_tick=k,
            config=fast_test_config(), data_dir=tmp + "/leader",
            pipeline_depth=depth,
            repl_window=(1 if depth == 1 else 4),
            engine=engine)
        repgroup.warmup_kernels(svc)
        assert svc.takeover(), "faultsweep: takeover failed"
        keys = [f"key{j}" for j in range(k)]
        vals = [b"v%d" % j for j in range(k // 2)]

        def submit():
            futs = []
            for e in range(n_ens):
                futs.append(svc.kput_many(e, keys[:k // 2], vals))
                futs.append(svc.kget_many(e, keys[k // 2:]))
            return futs

        futs = submit()  # warm: slots, elections, remote ladder
        while any(svc.queues):
            svc.flush()
        assert all(f.done for f in futs)
        svc.ack_timeout = 30.0

        plan = faults.install(faults.FaultPlan())
        if rtt_ms > 0.0:
            for link in svc._links:
                plan.set_rtt(link.label, faults.LOCAL, rtt_ms)

        window = 1 if depth == 1 else 4
        lat = []
        ops = 0
        inflight = []
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now < t_end and len(inflight) < window:
                inflight.append((now, submit()))
            svc.flush()
            while inflight and all(f.done for f in inflight[0][1]):
                tb, done = inflight.pop(0)
                lat.append(time.perf_counter() - tb)
                ops += len(done) * (k // 2)
            if now >= t_end and not inflight and lat:
                break
            assert now < t_end + 120.0, "faultsweep arm wedged"
        elapsed = time.perf_counter() - t0
        injected = dict(plan.counters())
        faults.clear()
        out = {
            "ops_per_sec": round(ops / elapsed, 1),
            "p50_ms": round(float(np.percentile(
                np.asarray(lat) * 1e3, 50)), 3),
            "p99_ms": round(float(np.percentile(
                np.asarray(lat) * 1e3, 99)), 3),
            "injected": injected,
        }
        svc.stop()
        svc = None
        return out
    finally:
        faults.clear()
        if svc is not None:
            try:
                svc.stop()
            except Exception:
                pass
        if server is not None:
            server.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _faultsweep_fsync_arm(n_ens: int, n_slots: int, k: int,
                          seconds: float, fsync_ms: float) -> dict:
    """Keyed WAL'd closed loop under injected fsync delay (0 = the
    clean baseline arm)."""
    import shutil
    import tempfile

    from riak_ensemble_tpu import faults
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime)

    tmp = tempfile.mkdtemp(prefix="bench_fsync_")
    svc = None
    try:
        svc = BatchedEnsembleService(WallRuntime(), n_ens, 1,
                                     n_slots, tick=None,
                                     max_ops_per_tick=k,
                                     data_dir=tmp)
        keys = [f"key{j}" for j in range(k // 2)]
        vals = [b"v%d" % j for j in range(k // 2)]

        def round_once():
            futs = [svc.kput_many(e, keys, vals)
                    for e in range(n_ens)]
            while not all(f.done for f in futs):
                svc.flush()
            return n_ens * (k // 2)

        round_once()  # warm
        plan = faults.install(faults.FaultPlan())
        if fsync_ms > 0.0:
            plan.set_fsync_delay(fsync_ms)
        ops = 0
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while time.perf_counter() < t_end or ops == 0:
            ops += round_once()
        elapsed = time.perf_counter() - t0
        delays = plan.fsync_delays
        faults.clear()
        out = {"ops_per_sec": round(ops / elapsed, 1),
               "fsync_delays": int(delays)}
        svc.stop()
        svc = None
        return out
    finally:
        faults.clear()
        if svc is not None:
            try:
                svc.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _noisy_tenant_arm(n_ens: int, n_slots: int, k: int,
                      seconds: float, compact: bool,
                      guard: bool = False) -> dict:
    """One hot tenant hammering 8 rows every round vs 8 near-idle
    quiet tenants (one small op per round, rotating) — the
    noisy-neighbor shape.  Reports the per-tenant p99s from the
    attribution plane; the caller A/Bs compaction on/off (and, for
    the autotune rung, the controller's admission guard on/off)."""
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime)

    svc = BatchedEnsembleService(WallRuntime(), n_ens, 1, n_slots,
                                 tick=None, max_ops_per_tick=k)
    try:
        if not compact:
            svc._compact = False  # the RETPU_COMPACT=0 arm
        if guard:
            # arm the controller's tenant-admission actuator with a
            # bench-tight cadence/threshold (the svc._compact idiom)
            svc.set_autotune(True)
            svc.controller.cadence = 8
            svc.controller.guard.min_ops = 16
        hot_n = min(8, n_ens // 2)
        hot_rows = list(range(hot_n))
        quiet_rows = list(range(hot_n, min(hot_n + 8, n_ens)))
        for e in hot_rows:
            svc.set_tenant_label(e, "hot")
        for i, e in enumerate(quiet_rows):
            svc.set_tenant_label(e, f"quiet{i}")
        keys = [f"key{j}" for j in range(k // 2)]
        vals = [b"v%d" % j for j in range(k // 2)]
        qi = [0]

        def round_once():
            futs = [svc.kput_many(e, keys, vals) for e in hot_rows]
            qe = quiet_rows[qi[0] % len(quiet_rows)]
            qi[0] += 1
            futs.append(svc.kput(qe, "qk", b"qv"))
            futs.append(svc.kget(qe, "qk"))
            while not all(f.done for f in futs):
                svc.flush()
            return hot_n * (k // 2) + 2

        for _ in range(3):
            round_once()  # warm: slots + the compiled (K, A) shapes
        # zero the attribution planes so warmup compiles don't ride
        # the measured p99 (bench-local reset; the plane itself has
        # no reset verb by design — recycle clears per-row)
        svc._tenant_lat[:] = 0
        svc.tenant_ops[:] = 0
        ops = 0
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while time.perf_counter() < t_end or ops == 0:
            ops += round_once()
        elapsed = time.perf_counter() - t0
        ts = svc.tenant_stats(top=32)
        quiet = [v for lbl, v in ts.items()
                 if lbl.startswith("quiet") and v["ops"] > 0]
        assert quiet, ts
        out = {
            "ops_per_sec": round(ops / elapsed, 1),
            "hot_ops": ts.get("hot", {}).get("ops", 0),
            "quiet_ops": int(sum(v["ops"] for v in quiet)),
            "hot_p99_ms": ts.get("hot", {}).get("p99_ms"),
            "quiet_p99_ms": round(float(np.median(
                [v["p99_ms"] for v in quiet])), 3),
        }
        if guard:
            out["guard_decisions"] = [
                ev for ev in svc.controller.journal.snapshot()
                if ev["actuator"] == "tenant_guard"]
            out["throttled_rows"] = {
                lbl: rows for lbl, rows in
                svc.controller.guard.throttled.items()}
        return out
    finally:
        svc.stop()


def run_autotune(seconds: float, smoke: bool) -> dict:
    """The controller A/B (docs/ARCHITECTURE.md §14): does the
    obs-actuated runtime controller FIND the link-dependent optimum
    the PR 9 faultsweep proved exists, and is every knob change it
    makes reconstructible from its journal alone?

    Per injected-ack-RTT point (0 ms = the clean link, 5 ms = the
    slow link where depth 2 measured 1.222x): two STATIC arms
    (depth 1 / window 1 and depth 2 / window 4 — the candidate
    optima) and one CONTROLLER arm that starts at depth 1 / window 1
    with ``RETPU_AUTOTUNE`` armed, adapts for the first part of the
    budget, then measures steady state.  Acceptance (round time, not
    smoke): the controller arm within 5% of the best static arm at
    EVERY point.  Both modes assert the journal property: replaying
    the decision journal over the initial knobs must land exactly on
    the live knobs, and the ``retpu_autotune_*`` gauges must agree —
    the self-tuning is auditable, not just present.

    Plus the tenant-guard rung: the PR 9 noisy-tenant shape with the
    guard armed vs not — the journal must show the admission
    decision and the quiet tenants' p99 must not degrade."""
    n_ens, n_slots, k = (8, 8, 8) if smoke else (32, 16, 16)
    rtts = (0.0, 2.0) if smoke else (0.0, 5.0)
    points = []
    worst_ratio = None
    for rtt in rtts:
        statics = {}
        for depth, window in ((1, 1), (2, 4)):
            r = _faultsweep_rtt_arm(n_ens, n_slots, k, seconds,
                                    depth, rtt)
            statics[f"depth{depth}_win{window}"] = r["ops_per_sec"]
        ctrl = _autotune_controller_arm(n_ens, n_slots, k, seconds,
                                        rtt)
        best = max(statics.values())
        ratio = round(ctrl["ops_per_sec"] / max(best, 1e-9), 3)
        worst_ratio = (ratio if worst_ratio is None
                       else min(worst_ratio, ratio))
        points.append({
            "rtt_ms": rtt,
            "static_ops_per_sec": statics,
            "controller_ops_per_sec": ctrl["ops_per_sec"],
            "controller_final": ctrl["final"],
            "controller_decisions": ctrl["decisions"],
            "journal_reconstructed": ctrl["journal_reconstructed"],
            "vs_best_static": ratio,
        })
    # Mesh point (one shape): the controller vs the static candidates
    # at the slow-link RTT with the leader's engine sharded over the
    # 8-device 'ens' mesh — the depth actuator must find the same
    # optimum when the lane it tunes is mesh-sharded.  Recorded
    # beside, not folded into, the single-shard worst_ratio headline.
    import jax
    mesh = None
    if not smoke and jax.device_count() >= 8:
        from riak_ensemble_tpu.parallel.mesh import mesh_engine
        engine = mesh_engine(8)
        mrtt = max(rtts)
        statics = {}
        for depth, window in ((1, 1), (2, 4)):
            r = _faultsweep_rtt_arm(n_ens, n_slots, k, seconds,
                                    depth, mrtt, engine=engine)
            statics[f"depth{depth}_win{window}"] = r["ops_per_sec"]
        ctrl = _autotune_controller_arm(n_ens, n_slots, k, seconds,
                                        mrtt, engine=engine)
        mesh = {
            "rtt_ms": mrtt,
            "mesh_devices": 8,
            "static_ops_per_sec": statics,
            "controller_ops_per_sec": ctrl["ops_per_sec"],
            "controller_final": ctrl["final"],
            "journal_reconstructed": ctrl["journal_reconstructed"],
            "vs_best_static": round(
                ctrl["ops_per_sec"]
                / max(max(statics.values()), 1e-9), 3),
        }

    guard = _autotune_guard_arm(
        *((16, 8, 8) if smoke else (512, 16, 32)), seconds)
    return {
        "autotune": {
            "shape": {"n_ens": n_ens, "n_slots": n_slots, "k": k},
            "points": points,
            "mesh_point": mesh,
            "tenant_guard": guard,
        },
        "autotune_vs_best_static": worst_ratio,
    }


def _autotune_controller_arm(n_ens: int, n_slots: int, k: int,
                             seconds: float, rtt_ms: float,
                             engine=None) -> dict:
    """The controller arm of the autotune A/B: the faultsweep
    leader + replica-host shape, starting at depth 1 / window 1 with
    the controller armed (tight cadence so it converges inside a
    bench budget), adaptation phase then steady-state measurement.
    Asserts the journal-reconstruction property before returning."""
    import shutil
    import tempfile

    from riak_ensemble_tpu import faults
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.obs.controller import replay
    from riak_ensemble_tpu.parallel import repgroup
    from riak_ensemble_tpu.parallel.batched_host import WallRuntime

    tmp = tempfile.mkdtemp(prefix="bench_autotune_")
    server = None
    svc = None
    try:
        server = repgroup.ReplicaServer(
            n_ens, 2, n_slots, data_dir=f"{tmp}/r1",
            config=fast_test_config())
        svc = repgroup.ReplicatedService(
            WallRuntime(), n_ens, 1, n_slots, group_size=2,
            peers=[("127.0.0.1", server.repl_port)],
            ack_timeout=60.0, max_ops_per_tick=k,
            config=fast_test_config(), data_dir=tmp + "/leader",
            pipeline_depth=1, repl_window=1, engine=engine)
        repgroup.warmup_kernels(svc)
        assert svc.takeover(), "autotune arm: takeover failed"
        svc.set_autotune(True)
        # bench-local controller tuning (the svc._compact idiom):
        # a tight cadence so convergence fits a bench budget
        svc.controller.cadence = 8
        initial = {"pipeline_depth": svc.pipeline_depth,
                   "repl_window": svc.repl_window}
        keys = [f"key{j}" for j in range(k)]
        vals = [b"v%d" % j for j in range(k // 2)]

        def submit():
            futs = []
            for e in range(n_ens):
                futs.append(svc.kput_many(e, keys[:k // 2], vals))
                futs.append(svc.kget_many(e, keys[k // 2:]))
            return futs

        futs = submit()  # warm: slots, elections, remote ladder
        while any(svc.queues):
            svc.flush()
        assert all(f.done for f in futs)
        svc.ack_timeout = 30.0
        plan = faults.install(faults.FaultPlan())
        if rtt_ms > 0.0:
            for link in svc._links:
                plan.set_rtt(link.label, faults.LOCAL, rtt_ms)

        def closed_loop(budget_s: float) -> tuple:
            # window follows the LIVE depth so a controller step
            # changes the offered concurrency exactly like the
            # matching static arm's client would
            lat = []
            ops = 0
            inflight = []
            t_end = time.perf_counter() + max(budget_s, 1e-3)
            t0 = time.perf_counter()
            while True:
                now = time.perf_counter()
                window = 1 if svc.pipeline_depth == 1 else 4
                if now < t_end and len(inflight) < window:
                    inflight.append((now, submit()))
                svc.flush()
                while inflight and all(f.done
                                       for f in inflight[0][1]):
                    tb, done = inflight.pop(0)
                    lat.append(time.perf_counter() - tb)
                    ops += len(done) * (k // 2)
                if now >= t_end and not inflight and lat:
                    break
                assert now < t_end + 120.0, "autotune arm wedged"
            return ops, time.perf_counter() - t0

        # adaptation phase: give the controller a few cadence
        # windows to converge, then measure steady state
        closed_loop(max(seconds * 0.6, 0.2))
        ops, elapsed = closed_loop(max(seconds, 1e-3))
        faults.clear()
        journal = svc.controller.journal.snapshot()
        final = {"pipeline_depth": svc.pipeline_depth,
                 "repl_window": svc.repl_window}
        # the acceptance property: the journal ALONE reconstructs
        # the live knobs, and the gauges tell the same story
        reconstructed = replay(
            [ev for ev in journal
             if ev.get("knob") in ("pipeline_depth", "repl_window")],
            initial)
        assert reconstructed == final, (reconstructed, final, journal)
        snap = svc.obs_registry.snapshot()
        assert snap["retpu_autotune_pipeline_depth"] \
            == final["pipeline_depth"], snap
        assert snap["retpu_autotune_repl_window"] \
            == final["repl_window"], snap
        assert snap["retpu_autotune_decisions_total"] \
            == svc.controller.journal.total
        out = {
            "ops_per_sec": round(ops / elapsed, 1),
            "final": final,
            "decisions": journal,
            "journal_reconstructed": True,
        }
        svc.stop()
        svc = None
        return out
    finally:
        faults.clear()
        if svc is not None:
            try:
                svc.stop()
            except Exception:
                pass
        if server is not None:
            server.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _autotune_guard_arm(n_ens: int, n_slots: int, k: int,
                        seconds: float) -> dict:
    """The tenant-guard rung: the PR 9 noisy-tenant shape with the
    controller's admission guard armed vs the unguarded baseline.
    The guard must journal an admission decision against the hot
    tenant, and the quiet tenants' p99 must not degrade under it."""
    base = _noisy_tenant_arm(n_ens, n_slots, k, seconds,
                             compact=True)
    guarded = _noisy_tenant_arm(n_ens, n_slots, k, seconds,
                                compact=True, guard=True)
    assert guarded["guard_decisions"], \
        "tenant guard armed but never journaled a decision"
    return {
        "quiet_p99_ms_guarded": guarded["quiet_p99_ms"],
        "quiet_p99_ms_unguarded": base["quiet_p99_ms"],
        "quiet_p99_ratio": round(
            guarded["quiet_p99_ms"]
            / max(base["quiet_p99_ms"], 1e-9), 3),
        "hot_ops_guarded": guarded["hot_ops"],
        "hot_ops_unguarded": base["hot_ops"],
        "ops_per_sec_guarded": guarded["ops_per_sec"],
        "ops_per_sec_unguarded": base["ops_per_sec"],
        "guard_decisions": guarded["guard_decisions"],
        "throttled_rows": guarded["throttled_rows"],
    }


def _make_workload(n_ens: int, n_peers: int, n_slots: int, k: int):
    """Shared kernel-stage workload: elected engine state + one fixed
    [K, E] op plane (seed 0).  Used by BOTH the throughput stage and
    the stepprobe so the stepprobe's budget calibration measures the
    same computation the stages will run."""
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng

    state = eng.init_state(n_ens, n_peers, n_slots)
    up = jnp.ones((n_ens, n_peers), bool)
    state, won = eng.elect_step(
        state, jnp.ones((n_ens,), bool), jnp.zeros((n_ens,), jnp.int32), up)
    jax.block_until_ready(state)

    rng = np.random.default_rng(0)
    kind = jnp.asarray(rng.choice([eng.OP_PUT, eng.OP_GET], (k, n_ens)),
                       jnp.int32)
    slot = jnp.asarray(rng.integers(0, n_slots, (k, n_ens)), jnp.int32)
    val = jnp.asarray(rng.integers(1, 1 << 20, (k, n_ens)), jnp.int32)
    lease_ok = jnp.ones((k, n_ens), bool)
    return eng, state, won, up, kind, slot, val, lease_ok


def run(n_ens: int, n_peers: int, n_slots: int, k: int,
        seconds: float) -> float:
    import jax

    eng, state, won, up, kind, slot, val, lease_ok = _make_workload(
        n_ens, n_peers, n_slots, k)

    # Compile + warm up.  NOTE: no device→host transfers before or
    # inside the timed region; correctness checks run AFTER the timed
    # loop instead.
    state2, _res = eng.kv_step_scan(state, kind, slot, val, lease_ok, up)
    jax.block_until_ready(state2)

    # Calibrate per-step time (blocked, so it includes sync overhead —
    # a conservative estimate) to bound the enqueue depth: async
    # dispatch outruns the device by orders of magnitude, and an
    # unbounded wall-clock enqueue loop would queue minutes of drain.
    t0 = time.perf_counter()
    ncal = 3
    for _ in range(ncal):
        state, res = eng.kv_step_scan(state, kind, slot, val, lease_ok, up)
        jax.block_until_ready(state)
    step_est = (time.perf_counter() - t0) / ncal

    # Timed loop: a bounded number of chained steps; ops advance real
    # protocol state.  The final block waits for every queued step, so
    # `elapsed` covers full execution, not just enqueue.
    iters = max(10, int(seconds / step_est))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, res = eng.kv_step_scan(state, kind, slot, val, lease_ok, up)
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0

    # Post-loop correctness: elections all won; every op in the last
    # step acked (puts committed / gets served or lease-bypassed).
    assert bool(np.asarray(won).all()), "bench: elections failed"
    ok = np.asarray(res.committed | res.get_ok | (np.asarray(kind) == 0))
    assert ok.all(), "bench: ops failed"
    return n_ens * k * iters / elapsed


def run_stepprobe(n_ens: int, n_peers: int, n_slots: int, k: int,
                  n_steps: int = 5) -> dict:
    """Single-launch latency evidence for a slow accelerator.

    The calibrate-then-loop stages need tens of sequential launches;
    this stage instead times INDIVIDUAL kv_step_scan launches and persists
    each measurement the moment it exists (``RETPU_STEPPROBE_OUT``),
    so even ONE completed step inside an alive-window yields an
    honest, conservative (sync-overhead-included) throughput figure:
    ``n_ens * k / step_s``.
    """
    import jax

    out_path = os.environ.get("RETPU_STEPPROBE_OUT")
    partial: dict = {"n_ens": n_ens, "k": k,
                     "platform": jax.devices()[0].platform}

    def persist() -> None:
        # Atomic replace: the parent kills this process with SIGKILL
        # on timeout, and a torn in-place write would corrupt the very
        # measurements this file exists to save.
        if out_path:
            tmp = out_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(partial, f)
            os.replace(tmp, out_path)

    persist()
    t0 = time.perf_counter()
    eng, state, _won, up, kind, slot, val, lease_ok = _make_workload(
        n_ens, n_peers, n_slots, k)
    partial["init_elect_s"] = time.perf_counter() - t0
    persist()

    t0 = time.perf_counter()
    state, _ = eng.kv_step_scan(state, kind, slot, val, lease_ok, up)
    jax.block_until_ready(state)
    partial["first_step_s"] = time.perf_counter() - t0  # includes compile
    persist()

    steps: list = []
    partial["steps_s"] = steps
    for _ in range(n_steps):
        t0 = time.perf_counter()
        state, _ = eng.kv_step_scan(state, kind, slot, val, lease_ok, up)
        jax.block_until_ready(state)
        steps.append(time.perf_counter() - t0)
        persist()
    med = sorted(steps)[len(steps) // 2]
    partial["median_step_s"] = med
    partial["single_step_ops_per_sec"] = n_ens * k / med
    persist()
    return partial


#: the shape single-launch TPU evidence is gathered at (matches the
#: full ladder's headline shape).
STEPPROBE_SHAPES = dict(n_ens=10_000, n_peers=5, n_slots=128, k=64)


def _run_stepprobe(timeout: float, shapes: dict) -> "dict | None":
    """Run the stepprobe stage in a killable subprocess, recovering
    PARTIAL measurements (steps persisted before a timeout kill) via
    the RETPU_STEPPROBE_OUT side file.  A subprocess that silently
    landed on CPU (not TPU evidence) comes back as
    ``{"error": ..., "cpu_fallback": True}``."""
    import tempfile

    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--stage", "stepprobe"]
        for f, v in shapes.items():
            cmd += [f"--{f.replace('_', '-')}", str(v)]
        result, err = _spawn_stage(
            cmd, timeout, env=dict(os.environ, RETPU_STEPPROBE_OUT=path))
        if result is not None:
            if result.get("platform") == "cpu":
                return {"error": "stepprobe subprocess landed on cpu "
                                 "(accelerator gone)",
                        "cpu_fallback": True}
            return result
        try:
            with open(path) as f:
                partial = json.load(f)
            partial["spawn_error"] = err
        except (OSError, json.JSONDecodeError):
            # No side file at all — preserve WHY (timeout vs crash) so
            # a dead round is triageable from the emitted JSON.
            return {"error": err}
    finally:
        try:
            os.remove(path)
        except OSError:
            pass
    if partial.get("platform") == "cpu":
        return {"error": "stepprobe subprocess landed on cpu "
                         "(accelerator gone)", "cpu_fallback": True}
    steps = partial.get("steps_s") or []
    if not steps and "first_step_s" not in partial:
        return partial  # died before any launch completed; keep why
    partial["partial"] = True
    if steps:
        med = sorted(steps)[len(steps) // 2]
        partial["median_step_s"] = med
        partial["single_step_ops_per_sec"] = (
            partial["n_ens"] * partial["k"] / med)
    return partial


#: internal wall budget for the tpuprobe stage — under the driver's
#: 600 s stage timeout so the probe trims its own tail (ladder rungs,
#: A/B arms) instead of being SIGKILLed mid-measurement.
_TPUPROBE_BUDGET_S = 520.0


def run_tpuprobe(seconds: float) -> dict:
    """Staged live-window probe (ROADMAP TPU re-attempt staging).

    A flickering accelerator window must be spent in strict order so
    even a short window yields evidence: (a) ONE tiny fused step,
    individually timed; (b) the CompileWatch ledger from a full
    service warmup — a blown budget then reads "N named compiles cost
    X s", not "timeout"; (c) the ascending step ladder toward the
    headline shape; (d) the Pallas-quorum A/B with its mechanical
    keep/kill verdict (KEEP iff >= 10% fused-step win at any ladder
    shape with bit-equal results — TPU-gated, so a CPU box reports
    "pending-tpu" alongside its measured numbers; the wiring itself is
    rehearsed end to end).

    The Pallas arms run as SUBPROCESSES: ``RETPU_PALLAS_QUORUM`` binds
    at engine-module import, so an in-process A/B would silently
    compare the same path against itself.
    """
    import jax

    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime)

    platform = jax.devices()[0].platform
    deadline = time.perf_counter() + _TPUPROBE_BUDGET_S

    def remaining() -> float:
        return deadline - time.perf_counter()

    out: dict = {"staging": ["tiny_step", "compile_ledger", "ladder",
                             "pallas_ab"]}

    # (a) one tiny fused step, each launch timed individually — the
    # cheapest possible "is the chip actually executing" evidence.
    tiny = run_stepprobe(64, 3, 16, 4, n_steps=3)
    out["tiny_step"] = {k: tiny[k] for k in
                        ("init_elect_s", "first_step_s",
                         "median_step_s", "single_step_ops_per_sec")}

    # (b) the compile ledger: a full small-shape service warmup with
    # every named compile's cost captured via CompileWatch.
    svc = BatchedEnsembleService(WallRuntime(), 256, 5, 32, tick=None)
    try:
        t0 = time.perf_counter()
        svc.warmup()
        ledger = list(svc._compile_log)
        out["compile_ledger"] = {
            "warmup_s": round(time.perf_counter() - t0, 3),
            "compiles": len(ledger),
            "compile_ms_total": round(
                sum(e["compile_ms"] for e in ledger), 1),
            "slowest": [
                {"fn": e["fn"], "ms": round(e["compile_ms"], 1)}
                for e in sorted(ledger, key=lambda e: e["compile_ms"],
                                reverse=True)[:5]],
        }
    finally:
        svc.stop()

    # (c) ascending ladder toward the headline stepprobe shape; each
    # rung gated on remaining budget so a slow chip still reports the
    # rungs it finished.
    out["ladder"] = []
    for shape in ((1024, 5, 64, 16), (4096, 5, 64, 32),
                  tuple(STEPPROBE_SHAPES.values())):
        if remaining() < 90.0:
            out["ladder_truncated"] = True
            break
        p = run_stepprobe(*shape, n_steps=3)
        out["ladder"].append({k: p[k] for k in
                              ("n_ens", "k", "first_step_s",
                               "median_step_s",
                               "single_step_ops_per_sec")})

    # (d1) Pallas-quorum A/B: kernel-stage subprocesses with the knob
    # in the environment, plus an in-process bit-equality check.  The
    # kernel compiles for the TPU only (its interpreter equality is
    # tests/test_pallas_quorum.py's), so a CPU box runs neither arm.
    ab_shape = dict(n_ens=4096, n_peers=5, n_slots=64, k=16)
    arm_secs = min(seconds, 3.0)
    pallas_ab: dict = {}
    arms = (() if platform == "cpu"
            else (("pallas", "1"), ("jnp", "0")))
    for name, knob in arms:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--stage", "kernel", "--seconds", str(arm_secs)]
        for f, v in ab_shape.items():
            cmd += [f"--{f.replace('_', '-')}", str(v)]
        r, err = _spawn_stage(
            cmd, max(30.0, min(remaining(), 240.0)),
            env=dict(os.environ, RETPU_PALLAS_QUORUM=knob))
        pallas_ab[f"{name}_rounds_per_sec"] = (
            r["kernel_rounds_per_sec"] if r else None)
        if err is not None:
            pallas_ab[f"{name}_error"] = err
    try:
        if platform == "cpu":
            raise RuntimeError("no TPU: the Mosaic kernel was not run")
        import jax.numpy as jnp

        from riak_ensemble_tpu.ops.pallas_quorum import (
            quorum_met_epallas)
        from riak_ensemble_tpu.ops.quorum import quorum_met_batch

        rng = np.random.default_rng(7)
        e, v, m = 512, 2, 5
        ack = jnp.asarray(rng.random((e, m)) < 0.5)
        heard = ack | jnp.asarray(rng.random((e, m)) < 0.3)
        vm = np.zeros((e, v, m), bool)
        vm[:, 0, :] = True
        vm[::3, 1, :3] = True  # a second active (joint) view
        vm = jnp.asarray(vm)
        nack = heard & ~ack
        ref = quorum_met_batch(ack, nack, vm,
                               jnp.full((e,), -1, jnp.int32),
                               required="quorum", axis_name=None)
        pal = quorum_met_epallas(ack, nack, vm)
        pallas_ab["bitequal"] = bool(
            (np.asarray(ref) == np.asarray(pal)).all())
    except Exception as exc:  # honest: record, don't crash the probe
        pallas_ab["bitequal"] = None
        pallas_ab["bitequal_error"] = f"{type(exc).__name__}: {exc}"
    p_on = pallas_ab.get("pallas_rounds_per_sec")
    p_off = pallas_ab.get("jnp_rounds_per_sec")
    pallas_ab["speedup"] = (round(p_on / p_off, 3)
                            if p_on and p_off else None)
    out["pallas_ab"] = pallas_ab
    if platform == "cpu":
        out["pallas_verdict"] = "pending-tpu"
        out["pallas_verdict_reason"] = (
            "KEEP iff >=10% fused-step win with bit-equal results, "
            "on TPU; CPU numbers recorded above")
    elif pallas_ab["speedup"] is None:
        out["pallas_verdict"] = "kill"
        out["pallas_verdict_reason"] = ("an A/B arm failed on the "
                                        "live accelerator")
    else:
        keep = (pallas_ab["speedup"] >= 1.10
                and pallas_ab.get("bitequal") is True)
        out["pallas_verdict"] = "keep" if keep else "kill"
        out["pallas_verdict_reason"] = (
            f"speedup={pallas_ab['speedup']} "
            f"bitequal={pallas_ab.get('bitequal')} vs the "
            ">=1.10-with-bit-equality bar")
    return out


def run_recovery(seconds: float, smoke: bool) -> dict:
    """``--stage recovery`` (docs/ARCHITECTURE.md §15): restart-to-
    serving time at the 512-ens rung — the RTO half of the crash
    contract, measured, not asserted.

    Build a durable (fsync-WAL) service, ack a keyed working set,
    checkpoint it, ack a WAL tail BEYOND the checkpoint, then release
    the handles with no cleanup (the crash analog) and time the
    restart: ``restore()`` (orbax checkpoint load + host-blob read +
    WAL replay) and the first served read (first-flush warmup /
    compile) are reported separately so a regression names its phase.
    ``recovery_ms`` is the headline the round JSON and the
    ``bench_trend`` ``recov_ms`` column carry.  ``seconds`` scales
    the WAL-tail depth (~seconds/3 rounds of tail keys), so the
    default 3 s budget reproduces the recorded shape exactly and a
    deeper budget measures a deeper replay."""
    import shutil
    import tempfile

    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime,
    )

    n_ens, n_peers, n_slots, k = ((16, 3, 8, 4) if smoke
                                  else (512, 5, 64, 16))
    ckpt_keys = tail_keys = 2 if smoke else 16
    # distinct keys per round; bounded by the slot grid (ckpt keys +
    # tail rounds must all fit per ensemble)
    tail_rounds = min(max(1, int(round(seconds / 3.0))),
                      (n_slots - ckpt_keys) // tail_keys)
    d = tempfile.mkdtemp(prefix="retpu_recovery_")
    try:
        svc = BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                     n_slots, tick=None,
                                     max_ops_per_tick=k, data_dir=d)

        def put_round(tag: str, n: int) -> None:
            keys = [f"{tag}{j}" for j in range(n)]
            vals = [b"v-%s-%d" % (tag.encode(), j) for j in range(n)]
            futs = [svc.kput_many(e, keys, vals)
                    for e in range(n_ens)]
            while any(svc.queues):
                svc.flush()
            assert all(f.done for f in futs), "recovery: unsettled"

        put_round("c", ckpt_keys)
        svc.save()
        for r in range(tail_rounds):
            put_round("t" if r == 0 else f"t{r}x", tail_keys)
        wal_records = svc._wal.count
        svc.stop()
        svc._wal.close()

        t0 = time.perf_counter()
        svc2 = BatchedEnsembleService.restore(
            WallRuntime(), d, tick=None, max_ops_per_tick=k,
            data_dir=d)
        t_restore = time.perf_counter()
        f = svc2.kget(0, "t0")
        while not f.done:
            svc2.flush()
        t_serve = time.perf_counter()
        assert f.value == ("ok", b"v-t-0"), f.value
        svc2.stop()
        return {
            "recovery_ms": round((t_serve - t0) * 1e3, 3),
            "recovery_restore_ms": round((t_restore - t0) * 1e3, 3),
            "recovery_first_op_ms": round((t_serve - t_restore) * 1e3,
                                          3),
            "recovery_wal_records": int(wal_records),
            "recovery_shape": {"n_ens": n_ens, "n_peers": n_peers,
                               "n_slots": n_slots},
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run_merkle(seconds: float, smoke: bool) -> dict:
    """BASELINE ladder #4: incremental updates into a 1M-segment
    Merkle tree (the always-up-to-date write-path hashing)."""
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import hash as hashk

    segs = 16 ** 3 if smoke else 16 ** 5
    batch = 256 if smoke else 4096
    rng = np.random.default_rng(0)
    leaves = jnp.zeros((segs, hashk.LANES), jnp.uint32)
    levels = hashk.build(leaves, width=16)
    ids = jnp.asarray(rng.integers(0, segs, batch))
    new = jnp.asarray(rng.integers(0, 2 ** 32, (batch, hashk.LANES),
                                   dtype=np.uint32))
    levels = hashk.update(levels, ids, new, width=16)
    jax.block_until_ready(levels)

    t0 = time.perf_counter()
    ncal = 3
    for _ in range(ncal):
        levels = hashk.update(levels, ids, new, width=16)
        jax.block_until_ready(levels)
    step_est = (time.perf_counter() - t0) / ncal
    iters = max(10, int(seconds / step_est))
    t0 = time.perf_counter()
    for _ in range(iters):
        levels = hashk.update(levels, ids, new, width=16)
    jax.block_until_ready(levels)
    elapsed = time.perf_counter() - t0
    rate = batch * iters / elapsed
    return {
        "metric": f"merkle_key_updates_per_sec_{segs}_segments",
        "value": round(rate, 1),
        "unit": "updates/sec",
        "vs_baseline": round(rate / 1_000_000.0, 3),
    }


def run_reconfig(seconds: float, smoke: bool) -> dict:
    """BASELINE ladder #5: joint-consensus reconfig cycles under churn
    (install joint views + collapse), batched over all ensembles."""
    import jax
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng

    n_ens, m = (64, 5) if smoke else (10_000, 5)
    state = eng.init_state(n_ens, m, 8)
    up = jnp.ones((n_ens, m), bool)
    state, won = eng.elect_step(state, jnp.ones((n_ens,), bool),
                                jnp.zeros((n_ens,), jnp.int32), up)
    rng = np.random.default_rng(0)
    keep = np.ones((n_ens, m), bool)
    keep[np.arange(n_ens), rng.integers(0, m, n_ens)] = False
    shrink = jnp.asarray(keep)
    full = jnp.ones((n_ens, m), bool)
    yes = jnp.ones((n_ens,), bool)
    no = jnp.zeros((n_ens,), bool)

    def cycle(st):
        st, _, _ = eng.reconfig_step(st, yes, shrink, up)
        st, _, _ = eng.reconfig_step(st, no, shrink, up)
        st, _, _ = eng.reconfig_step(st, yes, full, up)
        st, _, _ = eng.reconfig_step(st, no, full, up)
        return st

    state = cycle(state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    ncal = 3
    for _ in range(ncal):
        state = cycle(state)
        jax.block_until_ready(state)
    step_est = (time.perf_counter() - t0) / ncal
    iters = max(5, int(seconds / step_est))
    t0 = time.perf_counter()
    for _ in range(iters):
        state = cycle(state)
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    assert bool(np.asarray(won).all())
    # 2 full membership changes (4 reconfig phases) per cycle per ens
    rate = 2 * n_ens * iters / elapsed
    return {
        "metric": f"membership_changes_per_sec_{n_ens}_ens",
        "value": round(rate, 1),
        "unit": "changes/sec",
        "vs_baseline": round(rate / 1_000_000.0, 3),
    }


# -- §16 compartmentalized serving plane: the ingress rung -------------------

#: loadgen child source (run via ``python -c`` with one JSON argv):
#: an asyncio herd of simulated client connections importing ONLY the
#: wire codec — no jax — so thousands of connections cost a subprocess
#: fork, not an XLA init.  Each connection keeps one slab batch in
#: flight (closed-loop per connection, open-loop across the herd) and
#: the child prints ONE JSON tally line.
_INGRESS_LOADGEN = r'''
import asyncio, json, struct, sys, time

cfg = json.loads(sys.argv[1])
sys.path.insert(0, cfg["repo"])
try:  # the 10k-connection shape needs headroom past the soft FD cap
    import resource
    _h = resource.getrlimit(resource.RLIMIT_NOFILE)[1]
    if _h != resource.RLIM_INFINITY:
        resource.setrlimit(resource.RLIMIT_NOFILE, (_h, _h))
except Exception:
    pass
from riak_ensemble_tpu import wire

HDR = struct.Struct(">I")
addrs = [tuple(a) for a in cfg["addrs"]]
n_ens, k = cfg["n_ens"], cfg["k"]
mode = cfg["mode"]
write_every = cfg.get("write_every", 8)
stagger = cfg.get("stagger", 0.002)
ramp = cfg.get("ramp", 0.0)


def slab(keys):
    lens = struct.pack("<%di" % len(keys), *[len(s) for s in keys])
    return lens, "".join(keys).encode("ascii")


rlens, rarena = slab(["r%d" % j for j in range(k)])
wlens, warena = slab(["w%d" % j for j in range(k)])
vals = [b"v%03d" % j for j in range(k)]
vlens = struct.pack("<%di" % k, *[len(v) for v in vals])
varena = b"".join(vals)

tally = {"batches": 0, "read_ops": 0, "write_ops": 0, "rerouted": 0,
         "soft_errors": 0, "errors": 0}
lats = []
t0 = time.monotonic()
t_start = t0 + ramp
t_end = t_start + cfg["seconds"]


async def one(i):
    await asyncio.sleep(min(ramp, i * stagger))
    reader = writer = None
    for _ in range(200):  # the proxy tier may still be booting
        try:
            reader, writer = await asyncio.open_connection(
                *addrs[i % len(addrs)])
            break
        except OSError:
            await asyncio.sleep(0.05)
    if writer is None:
        tally["errors"] += 1
        return
    rid = 0
    try:
        while time.monotonic() < t_end:
            rid += 1
            wr = mode == "mixed" and rid % write_every == 0
            ens = (i + rid) % n_ens
            frame = ((rid, "kput_slab", ens, wlens, warena, vlens,
                      varena) if wr
                     else (rid, "kget_slab", ens, rlens, rarena))
            payload = wire.encode(frame)
            ts = time.monotonic()
            writer.write(HDR.pack(len(payload)) + payload)
            await writer.drain()
            head = await asyncio.wait_for(reader.readexactly(4), 60.0)
            (n,) = HDR.unpack(head)
            resp = wire.decode(await asyncio.wait_for(
                reader.readexactly(n), 60.0))
            te = time.monotonic()
            res = resp[1]
            if not isinstance(res, list):
                if res == ("error", "not-leader"):
                    tally["rerouted"] += 1  # replica lease lapsed
                    await asyncio.sleep(0.005)
                else:  # whole-batch soft failure (leader re-sync)
                    tally["soft_errors"] += 1
                    await asyncio.sleep(0.01)
                continue
            ok = sum(1 for r in res
                     if isinstance(r, tuple) and r and r[0] == "ok")
            tally["soft_errors"] += len(res) - ok
            if te < t_start:
                continue  # ramp: connections still piling on
            tally["batches"] += 1
            tally["write_ops" if wr else "read_ops"] += ok
            if len(lats) < 200000:
                lats.append(te - ts)
    except (asyncio.TimeoutError, asyncio.IncompleteReadError,
            ConnectionError, OSError):
        tally["errors"] += 1
    finally:
        if writer is not None:
            try:
                writer.close()
            except Exception:
                pass


async def herd():
    await asyncio.gather(*(one(i) for i in range(cfg["conns"])))


asyncio.run(herd())
tally["window"] = max(time.monotonic() - t_start, 1e-9)
lats.sort()


def pct(q):
    if not lats:
        return None
    return round(lats[min(len(lats) - 1, int(q * len(lats)))] * 1e3, 3)


tally["p50_ms"] = pct(0.50)
tally["p99_ms"] = pct(0.99)
print(json.dumps(tally), flush=True)
'''


def _ingress_ask(addr, *frame, timeout=60.0):
    """One svcnode-protocol round-trip on a fresh socket — the
    bench's sync control lane (prewrite, serving gates, the fleet
    scrape)."""
    import socket as _socket
    import struct as _struct

    from riak_ensemble_tpu import wire

    hdr = _struct.Struct(">I")
    with _socket.create_connection(addr, timeout=timeout) as s:
        s.settimeout(timeout)
        payload = wire.encode(frame)
        s.sendall(hdr.pack(len(payload)) + payload)
        buf = b""
        while len(buf) < 4:
            b = s.recv(4 - len(buf))
            if not b:
                raise ConnectionError("closed")
            buf += b
        (n,) = hdr.unpack(buf)
        buf = b""
        while len(buf) < n:
            b = s.recv(min(1 << 16, n - len(buf)))
            if not b:
                raise ConnectionError("closed")
            buf += b
        return wire.decode(buf)[1]


def _ingress_control(port, frame, timeout=180.0):
    """Raw repl-port control round-trip (``("promote", peers)``)."""
    import socket as _socket

    from riak_ensemble_tpu.parallel import repgroup

    with _socket.create_connection(("127.0.0.1", port),
                                   timeout=timeout) as s:
        s.settimeout(timeout)
        repgroup.send_frame(s, frame)
        return repgroup.recv_frame(s)


def _ingress_prewrite(leader, n_ens, k, budget=240.0):
    """Seed every ensemble's read keys through the fresh leader —
    doubling as the serving gate (the first writes retry through the
    post-promote host-quorum heal)."""
    import struct as _struct

    keys = ["r%d" % j for j in range(k)]
    lens = _struct.pack("<%di" % k, *[len(s) for s in keys])
    arena = "".join(keys).encode("ascii")
    vals = [b"v%03d" % j for j in range(k)]
    vlens = _struct.pack("<%di" % k, *[len(v) for v in vals])
    varena = b"".join(vals)
    deadline = time.monotonic() + budget
    for e in range(n_ens):
        while True:
            try:
                rs = _ingress_ask(leader, 1, "kput_slab", e, lens,
                                  arena, vlens, varena)
            except (ConnectionError, OSError):
                rs = None
            if isinstance(rs, list) and all(
                    isinstance(r, tuple) and r and r[0] == "ok"
                    for r in rs):
                break
            assert time.monotonic() < deadline, \
                f"ingress prewrite never converged: {rs!r}"
            time.sleep(0.25)


def _ingress_spawn_host(n_ens, n_slots, tmp, i, procs):
    """One group host OS process for the full-shape arm (its own
    GIL — ingress scaling is invisible when every tier shares one
    interpreter), follower reads on, the rung's lease/heartbeat
    config.  The ready line carries both ports; the child lands in
    ``procs`` before the parse so it can never leak past the
    caller's kill sweep."""
    import textwrap
    import threading

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = textwrap.dedent(f"""
        import os, sys, time
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {repo!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_compilation_cache_dir",
                          {repo!r} + "/.jax_cache")
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0)
        from riak_ensemble_tpu.config import Config
        from riak_ensemble_tpu.parallel import repgroup
        srv = repgroup.ReplicaServer(
            {n_ens}, 3, {n_slots}, data_dir={tmp!r} + "/r{i}",
            config=Config(ensemble_tick=0.05, lease_duration=1.5,
                          probe_delay=0.1, storage_delay=0.005,
                          storage_tick=0.5, gossip_tick=0.2),
            follower_reads=True)
        print("ready repl=%d client=%d"
              % (srv.repl_port, srv.client_port), flush=True)
        while True:
            time.sleep(60)
    """)
    p = subprocess.Popen([sys.executable, "-c", child],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True, env=env)
    procs.append(p)
    line = p.stdout.readline()
    assert line.startswith("ready"), f"ingress host died: {line!r}"
    parts = dict(kv.split("=") for kv in line.split()[1:])
    threading.Thread(target=lambda f=p.stdout: [None for _ in f],
                     daemon=True).start()
    return int(parts["repl"]), int(parts["client"])


def _ingress_spawn_proxies(count, hosts, procs):
    """``count`` proxy OS processes fronting the same group; returns
    (children, client-facing addrs).  Spawned concurrently — each
    pays a jax import — ready lines parsed after."""
    import threading

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    up = ",".join(f"{h}:{p}" for h, p in hosts)
    px = []
    for _ in range(count):
        p = subprocess.Popen(
            [sys.executable, "-m", "riak_ensemble_tpu.proxy",
             "--port", "0", "--upstream", up,
             "--discover-timeout", "120"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        procs.append(p)
        px.append(p)
    addrs = []
    for p in px:
        line = p.stdout.readline()
        assert line.startswith("proxy serving on "), \
            f"ingress proxy died: {line!r}"
        host, _, port = line.split()[3].rpartition(":")
        addrs.append((host, int(port)))
        threading.Thread(target=lambda f=p.stdout: [None for _ in f],
                         daemon=True).start()
    return px, addrs


def _ingress_loadgens(cfgs, procs, budget):
    """Run the loadgen herd children to completion; one parsed tally
    per child."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    kids = []
    for c in cfgs:
        p = subprocess.Popen(
            [sys.executable, "-c", _INGRESS_LOADGEN, json.dumps(c)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)
        procs.append(p)
        kids.append(p)
    out = []
    for p in kids:
        stdout, stderr = p.communicate(timeout=budget)
        assert p.returncode == 0, \
            f"ingress loadgen died: {stderr[-400:]}"
        out.append(json.loads(stdout.strip().splitlines()[-1]))
    return out


def _ingress_tally(results):
    """Fold per-child tallies into one arm record: counts sum, the
    window is the slowest child's (rates stay conservative), the
    latency columns are the worst any child observed."""
    window = max(r["window"] for r in results)
    agg = {key: sum(r[key] for r in results)
           for key in ("batches", "read_ops", "write_ops",
                       "rerouted", "soft_errors", "errors")}
    p50 = [r["p50_ms"] for r in results if r["p50_ms"] is not None]
    p99 = [r["p99_ms"] for r in results if r["p99_ms"] is not None]
    return {
        "batches_per_sec": round(agg["batches"] / window, 1),
        "read_ops_per_sec": round(agg["read_ops"] / window, 1),
        "write_ops_per_sec": round(agg["write_ops"] / window, 1),
        "client_p50_ms": max(p50) if p50 else None,
        "client_p99_ms": max(p99) if p99 else None,
        "rerouted": agg["rerouted"],
        "soft_errors": agg["soft_errors"],
        "errors": agg["errors"],
    }


def _ingress_engine_p99(fm):
    """Worst engine-tier ``retpu_op_latency_ms`` p99 across the fleet
    snapshot (the PR 8 op rings; base series plus labeled tenants)."""
    best = None
    hosts = fm.get("hosts") if isinstance(fm, dict) else None
    for snap in (hosts or {}).values():
        h = snap.get("retpu_op_latency_ms") \
            if isinstance(snap, dict) else None
        if not isinstance(h, dict):
            continue
        for hh in [h] + list((h.get("by_label") or {}).values()):
            v = hh.get("p99") if isinstance(hh, dict) else None
            if isinstance(v, (int, float)) and v == v \
                    and (best is None or v > best):
                best = float(v)
    return best


def _ingress_follower_served(fm):
    """Every host's ``retpu_group_follower_reads_served`` summed out
    of the fleet snapshot — the replicas' own proof the spread arm
    was served from mirrors, riding the same single pull."""
    total = 0
    hosts = fm.get("hosts") if isinstance(fm, dict) else None
    for snap in (hosts or {}).values():
        v = snap.get("retpu_group_follower_reads_served") \
            if isinstance(snap, dict) else None
        if isinstance(v, dict):
            v = sum(x for x in v.values()
                    if isinstance(x, (int, float)))
        if isinstance(v, (int, float)):
            total += int(v)
    return total


def run_ingress(seconds: float, smoke: bool) -> dict:
    """§16 serving-plane rung: proxy-count ingress scaling and the
    follower-read A/B against ONE promoted 3-host replication group.

    Two interleaved A/Bs ride the round JSON:

    - **ingress scaling** — an open-loop herd of simulated client
      connections drives mixed slab batches through 1 vs N stateless
      proxies (each its own OS process, svcnode wire protocol, one
      scatter-gather hop per batch); acceptance wants the
      client-batch ingestion rate to scale >= 1.5x from 1 -> 4
      proxies at the round shape while write throughput (quorum-
      bound at the leader — proxies can't help it) holds within 10%.
    - **follower reads** — the same read workload aimed at the
      leader alone vs spread over all three hosts with replica-
      served leased reads answering from delta-maintained mirrors;
      acceptance wants >= 1.8x read throughput on the 3-host group.

    Per-tier evidence: client-observed p50/p99 from the herd (the
    ingress tier) and the engine-tier ``retpu_op_latency_ms`` p99
    from the PR 8 op rings — every host's registry scraped in ONE
    ``("fleet", "metrics")`` pull off the leader (§11), which also
    carries the replicas' follower-read counters.

    The smoke shape keeps the GROUP in process (threaded hosts,
    shared jit cache — the tier-1 budget) with proxies and loadgens
    as real subprocesses; its ratios are structural sanity, not a
    measure (every smoke host shares one GIL).  The full shape runs
    3 host processes, (1, 4) proxy processes and an 8-child herd
    sized 10k+ connections (capped to the box's FD budget)."""
    import shutil
    import statistics
    import tempfile

    if smoke:
        n_ens, n_slots, k = 8, 16, 4
        proxy_counts, reps, gens, gens_flw = (1, 2), 1, 2, 1
        conns, flw_conns = 16, 9
        measure = max(0.5, min(seconds, 1.0))
    else:
        n_ens, n_slots, k = 32, 32, 8
        proxy_counts, reps, gens, gens_flw = (1, 4), 2, 8, 2
        try:
            import resource
            hard = resource.getrlimit(resource.RLIMIT_NOFILE)[1]
            cap = 10_000 if hard == resource.RLIM_INFINITY \
                else max(512, (hard - 512) // 2)
        except Exception:
            cap = 10_000
        conns, flw_conns = min(10_000, cap), 48
        measure = max(5.0, seconds)

    tmp = tempfile.mkdtemp(prefix="bench_ingress_")
    procs: list = []
    srvs: list = []
    try:
        # -- one 3-host group, host 0 promoted -------------------------
        if smoke:
            from riak_ensemble_tpu.config import Config
            from riak_ensemble_tpu.parallel import repgroup
            cfg = Config(ensemble_tick=0.05, lease_duration=1.5,
                         probe_delay=0.1, storage_delay=0.005,
                         storage_tick=0.5, gossip_tick=0.2)
            srvs = [repgroup.ReplicaServer(
                n_ens, 3, n_slots, data_dir=f"{tmp}/r{i}",
                config=cfg, follower_reads=True) for i in range(3)]
            ports = [(s.repl_port, s.client_port) for s in srvs]
        else:
            ports = [_ingress_spawn_host(n_ens, n_slots, tmp, i,
                                         procs) for i in range(3)]
        repl_ports = [r for r, _c in ports]
        hosts = [("127.0.0.1", c) for _r, c in ports]
        leader = hosts[0]
        resp = _ingress_control(
            repl_ports[0],
            ("promote", [("127.0.0.1", p) for p in repl_ports[1:]]))
        assert resp[0] == "ok", f"ingress promote failed: {resp!r}"
        _ingress_prewrite(leader, n_ens, k)

        repo = os.path.dirname(os.path.abspath(__file__))

        def herd_cfg(addrs, n, mode, ramp):
            return dict(repo=repo, addrs=[list(a) for a in addrs],
                        conns=n, seconds=measure, mode=mode,
                        write_every=8, n_ens=n_ens, k=k, ramp=ramp,
                        stagger=0.002)

        # -- A/B 1: ingress scaling, arm order mirrored per rep --------
        order = []
        for r in range(reps):
            order += list(proxy_counts if r % 2 == 0
                          else tuple(reversed(proxy_counts)))
        arm_recs = {p: [] for p in proxy_counts}
        for count in order:
            px, paddrs = _ingress_spawn_proxies(count, hosts, procs)
            per = max(1, conns // gens)
            ramp = min(2.0, per * 0.002)
            res = _ingress_loadgens(
                [herd_cfg(paddrs, per, "mixed", ramp)
                 for _ in range(gens)],
                procs, budget=measure + ramp + 180.0)
            arm = _ingress_tally(res)
            arm["conns"] = per * gens
            arm_recs[count].append(arm)
            for p in px:
                p.kill()

        arms = {}
        for count in proxy_counts:
            a = dict(arm_recs[count][-1])
            for key in ("batches_per_sec", "read_ops_per_sec",
                        "write_ops_per_sec"):
                a[key] = statistics.median(
                    rec[key] for rec in arm_recs[count])
            arms[str(count)] = a
        lo, hi = str(min(proxy_counts)), str(max(proxy_counts))
        ingress_x = round(arms[hi]["batches_per_sec"]
                          / max(arms[lo]["batches_per_sec"], 1e-9), 3)
        w_lo = arms[lo]["write_ops_per_sec"]
        write_hold = round(arms[hi]["write_ops_per_sec"] / w_lo, 3) \
            if w_lo > 0 else None

        # -- A/B 2: follower-served reads, arm order mirrored ----------
        # gate: both replicas must hold a live lease before the
        # spread arm measures (grants rode the prewrite settles; the
        # idle leader's heartbeats renew them)
        deadline = time.monotonic() + 60.0
        for addr in hosts[1:]:
            while _ingress_ask(addr, 0, "kget", 0, "r0") == \
                    ("error", "not-leader"):
                assert time.monotonic() < deadline, \
                    "follower lease never arrived"
                time.sleep(0.25)
        flw_recs = {"leader_only": [], "followers": []}
        flw_order = []
        for r in range(reps):
            pair = ["leader_only", "followers"]
            flw_order += pair if r % 2 == 0 else pair[::-1]
        for name in flw_order:
            addrs = [leader] if name == "leader_only" else hosts
            per = max(1, flw_conns // gens_flw)
            res = _ingress_loadgens(
                [herd_cfg(addrs, per, "read", 0.1)
                 for _ in range(gens_flw)],
                procs, budget=measure + 180.0)
            flw_recs[name].append(_ingress_tally(res))
        flw = {}
        for name, recs in flw_recs.items():
            rec = dict(recs[-1])
            rec["read_ops_per_sec"] = statistics.median(
                r["read_ops_per_sec"] for r in recs)
            rec["conns"] = max(1, flw_conns // gens_flw) * gens_flw
            flw[name] = rec
        follower_x = round(
            flw["followers"]["read_ops_per_sec"]
            / max(flw["leader_only"]["read_ops_per_sec"], 1e-9), 3)

        # -- per-tier evidence: ONE fleet pull off the leader ----------
        fm = _ingress_ask(leader, 1, "fleet", "metrics", timeout=120.0)
        return {
            "ingress_x": ingress_x,
            "ingress_write_hold": write_hold,
            "ingress_arms": arms,
            "ingress_conns": conns,
            "ingress_engine_p99_ms": _ingress_engine_p99(fm),
            "follower_read_x": follower_x,
            "follower_read_arms": flw,
            "follower_reads_served_total": _ingress_follower_served(fm),
            "ingress_shape": {
                "n_ens": n_ens, "n_slots": n_slots, "k": k,
                "proxies": list(proxy_counts), "reps": reps,
                "measure_s": measure, "smoke": smoke},
        }
    finally:
        for p in procs:
            try:
                p.kill()
            except Exception:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        for s in srvs:
            try:
                s.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _commrepl_arm(seconds: float, smoke: bool, n_ens: int,
                  n_slots: int, n_keys: int, dup: int,
                  comm: bool) -> dict:
    """One arm of the commrepl A/B: a 3-host group driven by a
    contended-counter kmodify_many storm (every hot key duplicated
    ``dup`` times per batch).  ``comm`` flips the leader's
    ``RETPU_COMM_REPL`` lane — replicas apply whichever entry kind
    arrives, so only the leader's flag differs between arms."""
    import shutil
    import signal
    import tempfile

    from riak_ensemble_tpu import funref
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.parallel import repgroup
    from riak_ensemble_tpu.parallel.batched_host import WallRuntime

    tmp = tempfile.mkdtemp(prefix="bench_commrepl_")
    procs: list = []
    servers: list = []
    try:
        ports = []
        if smoke:
            for i in (1, 2):
                servers.append(repgroup.ReplicaServer(
                    n_ens, 3, n_slots, data_dir=f"{tmp}/r{i}",
                    config=fast_test_config()))
            ports = [s.repl_port for s in servers]
        else:
            for i in (1, 2):
                ports.append(_repgroup_spawn_subprocess(
                    n_ens, n_slots, tmp, i, procs))
        svc = repgroup.ReplicatedService(
            WallRuntime(), n_ens, 1, n_slots, group_size=3,
            peers=[("127.0.0.1", p) for p in ports],
            ack_timeout=60.0, max_ops_per_tick=n_keys * dup,
            config=fast_test_config(), data_dir=tmp + "/leader",
            pipeline_depth=2)
        svc._comm_repl = comm  # the A/B flip (RETPU_COMM_REPL)
        repgroup.warmup_kernels(svc)
        assert svc.takeover(), "commrepl bench: takeover failed"

        fun = funref.ref("rmw:add", 1)
        storm = [f"ctr{j}" for j in range(n_keys)] * dup

        futs = [svc.kmodify_many(e, storm, fun)
                for e in range(n_ens)]
        while any(svc.queues):  # warm: slots, elections, compile
            svc.flush()
        assert all(f.done for f in futs)
        svc.ack_timeout = 10.0
        g0 = dict(svc.stats()["group"])

        lat = []
        ops = 0
        inflight = []
        t_end = time.perf_counter() + max(seconds, 1e-3)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now < t_end and len(inflight) < 4:
                inflight.append((now, [
                    svc.kmodify_many(e, storm, fun)
                    for e in range(n_ens)]))
            svc.flush()
            while inflight and all(f.done for f in inflight[0][1]):
                tb, fl = inflight.pop(0)
                lat.append(time.perf_counter() - tb)
                ops += len(fl) * len(storm)
            if now >= t_end and not inflight and lat:
                break
            assert now < t_end + 120.0, "commrepl bench wedged"
        elapsed = time.perf_counter() - t0
        g = svc.stats()["group"]
        assert g["quorum_failures"] == 0, g
        entries = max((g["repl_delta_entries"] + g["repl_full_entries"])
                      - (g0["repl_delta_entries"]
                         + g0["repl_full_entries"]), 1)
        out = {
            "ops_per_sec": round(ops / elapsed, 1),
            "ack_p50_ms": round(float(np.percentile(
                np.asarray(lat) * 1e3, 50)), 3),
            "ack_p99_ms": round(float(np.percentile(
                np.asarray(lat) * 1e3, 99)), 3),
            "bytes_per_entry": round(
                (g["repl_bytes_sections"] - g0["repl_bytes_sections"])
                / entries, 1),
            "merge_entries": (g["repl_merge_entries"]
                              - g0["repl_merge_entries"]),
            "merge_cells": (g["repl_merge_cells"]
                            - g0["repl_merge_cells"]),
            "early_acks": (g["repl_early_acks"]
                           - g0["repl_early_acks"]),
            "coalesce_ratio": g["repl_merge_coalesce_ratio"],
        }
        if smoke:
            # comm/ordered convergence tripwire: every replica lane's
            # engine state bit-equal to the leader's after drain
            for _ in range(3):
                svc.heartbeat()
            svc._drain_pending(block_all=True)
            want_pos = (svc.core.applied_ge, svc.core.applied_seq)
            end = time.monotonic() + 60.0
            while time.monotonic() < end:
                done = True
                for s in servers:
                    with s._lock:
                        done = done and ((s.core.applied_ge,
                                          s.core.applied_seq)
                                         >= want_pos)
                if done:
                    break
                time.sleep(0.02)
            d_l = repgroup.dump_state(svc)
            ok = True
            for s in servers:
                with s._lock:
                    d_r = repgroup.dump_state(s.svc)
                ok = ok and d_l[0] == d_r[0]
            out["convergence_ok"] = ok
        svc.stop()
        return out
    finally:
        for s in servers:
            s.stop()
        for p in procs:
            try:
                p.send_signal(signal.SIGKILL)
            except ProcessLookupError:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def run_commrepl(seconds: float, smoke: bool) -> dict:
    """Commutative-replication rung (ARCHITECTURE §18): the contended-
    counter storm — hot keys duplicated per batch, rmw:add only — on a
    3-host group, comm lane vs ordered A/B.  The comm arm coalesces
    duplicates at enqueue, ships merge sections and early-acks on
    merge-durable quorum receipt; the ordered arm (``svc._comm_repl =
    False``, the ``RETPU_COMM_REPL=0`` semantics) pays full per-entry
    sequencing.  ``rmw_comm_x`` = ordered ack p50 / comm ack p50
    (higher is better; ``tools/bench_trend.py --check`` rides it), and
    the bytes-per-entry pair feeds the test_bench_smoke tripwire
    (merge section < ordered delta bytes on the hot-slot shape)."""
    n_ens, n_slots, n_keys, dup = ((8, 16, 2, 4) if smoke
                                   else (32, 32, 4, 8))
    comm = _commrepl_arm(seconds, smoke, n_ens, n_slots, n_keys,
                         dup, True)
    plain = _commrepl_arm(seconds, smoke, n_ens, n_slots, n_keys,
                          dup, False)
    out = {
        "commrepl_ops_per_sec": comm["ops_per_sec"],
        "commrepl_ack_p50_ms": comm["ack_p50_ms"],
        "commrepl_ack_p99_ms": comm["ack_p99_ms"],
        "commrepl_ordered_ack_p50_ms": plain["ack_p50_ms"],
        "commrepl_ordered_ack_p99_ms": plain["ack_p99_ms"],
        "commrepl_bytes_per_entry": comm["bytes_per_entry"],
        "commrepl_ordered_bytes_per_entry": plain["bytes_per_entry"],
        "commrepl_merge_entries": comm["merge_entries"],
        "commrepl_merge_cells": comm["merge_cells"],
        "commrepl_early_acks": comm["early_acks"],
        "commrepl_coalesce_ratio": comm["coalesce_ratio"],
        "commrepl_shape": {
            "n_ens": n_ens, "n_slots": n_slots, "n_keys": n_keys,
            "dup": dup, "smoke": smoke},
        "rmw_comm_x": round(
            plain["ack_p50_ms"] / max(comm["ack_p50_ms"], 1e-9), 3),
    }
    if "convergence_ok" in comm:
        out["commrepl_convergence_ok"] = (comm["convergence_ok"]
                                          and plain["convergence_ok"])
    return out


#: fallback ladder: (label, shapes, per-stage subprocess timeout).
#: Full TPU shapes first; smaller shapes if the backend is too slow to
#: compile/run the big ones.  No CPU rung: a run that finds no TPU
#: fails (``--smoke`` is the CPU correctness run).
_ATTEMPTS = (
    ("10k_ens_5_peers",
     dict(n_ens=10_000, n_peers=5, n_slots=128, k=64), 420.0),
    ("1k_ens_5_peers",
     dict(n_ens=1_000, n_peers=5, n_slots=128, k=32), 300.0),
)


def _spawn_stage(cmd, timeout: float, env=None):
    """One killable worker subprocess: own session (the whole process
    GROUP is killed on timeout — a wedged grandchild holding the
    inherited stdout pipe would otherwise block the drain forever),
    last-JSON-line result parse.  Returns (parsed, error_string)."""
    import signal

    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            pass
        return None, f"timeout after {timeout}s"
    if proc.returncode != 0:
        return None, f"rc={proc.returncode} {err[-400:]}"
    for line in reversed(out.strip().splitlines()):
        try:
            return json.loads(line), None
        except json.JSONDecodeError:
            continue
    return None, "no json line"


def _run_stage(stage: str, label: str, shapes: dict, seconds: float,
               timeout: float, force_cpu: bool, env=None):
    """Run one stage in a subprocess; parse its JSON line; None on
    timeout/crash (a wedged TPU RPC ignores signals — only a
    subprocess kill reliably unsticks the bench).

    The budget scales with the requested measurement time (the
    constant part covers compile + warmup + transfers).  ``env``
    (full environment dict) lets mesh stages inject XLA_FLAGS —
    device-count flags bind at jax import, so they can only enter a
    stage through its subprocess environment.
    """
    timeout = timeout + max(0.0, (seconds - 3.0) * 4.0)
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage,
           "--seconds", str(seconds)]
    for f, v in shapes.items():
        cmd += [f"--{f.replace('_', '-')}", str(v)]
    if force_cpu:
        cmd.append("--force-cpu")
    result, err = _spawn_stage(cmd, timeout, env=env)
    if err is not None:
        print(f"# stage {stage}@{label}: {err}", file=sys.stderr)
    return result


def _mesh_cpu_env(n_devices: int = 8) -> dict:
    """Stage environment with the virtual CPU device count forced (a
    no-op on a real accelerator platform — the flag only affects the
    host CPU client).  Merged with any existing XLA_FLAGS."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    return env


def _stage_entry(args) -> None:
    """Worker mode: one stage, one process, one JSON line on stdout."""
    _setup_jax(args.force_cpu)
    if args.stage == "probe":
        # Accelerator preflight: one tiny compiled op.  A dead/wedged
        # device hangs here (and only costs the probe's short budget)
        # instead of burning every full-shape attempt's timeout.
        import jax
        import jax.numpy as jnp
        x = jnp.ones((8, 128)) @ jnp.ones((128, 8))
        jax.block_until_ready(x)
        print(json.dumps({"platform": jax.devices()[0].platform}))
        return
    shapes = dict(n_ens=args.n_ens, n_peers=args.n_peers,
                  n_slots=args.n_slots, k=args.k)
    if args.stage == "kernel":
        out = {"kernel_rounds_per_sec": run(seconds=args.seconds, **shapes)}
    elif args.stage == "escale":
        out = {"escale": run_escale_point(
            seconds=args.seconds, mesh_devices=args.mesh_devices,
            **shapes)}
    elif args.stage == "tpuprobe":
        out = run_tpuprobe(args.seconds)
    elif args.stage == "stepprobe":
        out = run_stepprobe(**shapes)
    elif args.stage == "repgroup":
        out = run_repgroup(args.seconds, smoke=False)
    elif args.stage == "faultsweep":
        out = run_faultsweep(args.seconds, smoke=False)
    elif args.stage == "autotune":
        out = run_autotune(args.seconds, smoke=False)
    elif args.stage == "fleetobs":
        out = run_fleet_obs_overhead(args.seconds)
    elif args.stage == "recovery":
        out = run_recovery(args.seconds, smoke=False)
    elif args.stage == "ingress":
        out = run_ingress(args.seconds, smoke=False)
    elif args.stage == "commrepl":
        out = run_commrepl(args.seconds, smoke=False)
    elif args.stage == "merkle":
        m = run_merkle(args.seconds, smoke=False)
        out = {"ladder_metric": m["metric"], "ladder_value": m["value"]}
    elif args.stage == "reconfig":
        m = run_reconfig(args.seconds, smoke=False)
        out = {"ladder_metric": m["metric"], "ladder_value": m["value"]}
    else:
        out = run_service(seconds=args.seconds, **shapes)
    import jax
    out["platform"] = jax.devices()[0].platform
    # every stage's JSON carries the box fingerprint (cpu count,
    # loadavg, jax versions, RETPU_* knobs) — cross-round comparisons
    # check the box before believing a delta (the r4→r5 lesson).
    # Taken after jax init, so it carries the device (platform, kind,
    # count): escale points from different mesh widths must never
    # ratchet against each other.
    from riak_ensemble_tpu.obs import box_fingerprint
    out["box"] = box_fingerprint()
    print(json.dumps(out))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for a CPU sanity run")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--scenario", default="kv",
                    choices=("kv", "merkle", "reconfig"),
                    help="kv = headline (driver default); merkle / "
                         "reconfig = BASELINE.md ladder #4 / #5")
    ap.add_argument("--stage",
                    choices=("kernel", "service", "merkle", "reconfig",
                             "probe", "stepprobe", "repgroup",
                             "escale", "faultsweep",
                             "autotune", "fleetobs", "recovery",
                             "ingress", "commrepl", "tpuprobe"),
                    help="internal: run one stage in-process")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="escale stage: shard the engine over this "
                         "many devices along the 'ens' axis (0 = "
                         "single-shard; CPU needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count "
                         "in the stage environment)")
    ap.add_argument("--n-ens", type=int, default=10_000)
    ap.add_argument("--n-peers", type=int, default=5)
    ap.add_argument("--n-slots", type=int, default=128)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--force-cpu", action="store_true")
    args = ap.parse_args()

    if args.stage:
        _stage_entry(args)
        return
    if args.scenario == "merkle":
        _setup_jax(False)
        print(json.dumps(run_merkle(args.seconds, args.smoke)))
        return
    if args.scenario == "reconfig":
        _setup_jax(False)
        print(json.dumps(run_reconfig(args.seconds, args.smoke)))
        return

    if args.smoke:
        _setup_jax(force_cpu=True)  # smoke = sanity check, not a measure
        # bench-trend ratchet rides the smoke path: a malformed or
        # headline-less BENCH round fails the smoke run LOUDLY (the
        # TrendError propagates) instead of shipping an unreadable
        # trajectory into the next round
        from tools import bench_trend
        trend = bench_trend.check(
            os.path.dirname(os.path.abspath(__file__)))
        shapes = dict(n_ens=64, n_peers=5, n_slots=32, k=4)
        secs = min(args.seconds, 1.0)
        kernel_rounds = run(seconds=secs, **shapes)
        svc = run_service(seconds=secs, **shapes)
        svc["kernel_rounds_per_sec"] = kernel_rounds
        svc.update(run_repgroup(secs, smoke=True))
        svc.update(run_faultsweep(secs, smoke=True))
        svc.update(run_autotune(secs, smoke=True))
        svc.update(run_fleet_obs_overhead(secs))
        svc.update(run_recovery(secs, smoke=True))
        svc.update(run_ingress(secs, smoke=True))
        svc.update(run_commrepl(secs, smoke=True))
        svc["platform"] = "smoke"
        svc["bench_trend"] = trend
        label = "64_ens_5_peers_smoke"
    else:
        # Within a label the kernel stage runs FIRST.  Both stages get
        # the fallback ladder — the first label where the service (the
        # headline) succeeds wins, and the kernel keeps falling back
        # independently if its attempt at that label failed.
        # Preflight: one tiny compiled op in a child (this parent
        # never touches JAX, so the child can have the chip).  No
        # accelerator, no measurement: the run fails.
        attempts = _ATTEMPTS
        force_cpu = False
        probe = _run_stage("probe", "preflight", {}, 0.0, 240.0, False)
        if probe is None or probe.get("platform") == "cpu":
            print("# accelerator preflight: "
                  + ("failed" if probe is None else "found only a CPU")
                  + "; nothing measured (--smoke is the CPU "
                  "correctness run)", file=sys.stderr)
            sys.exit(1)
        svc = kern = None
        kern_label = None
        for label, shapes, budget in attempts:
            if kern is None:
                kern = _run_stage("kernel", label, shapes, args.seconds,
                                  budget, force_cpu)
                if kern is not None:
                    kern_label = label
            svc = _run_stage("service", label, shapes, args.seconds,
                             budget, force_cpu)
            if svc is not None:
                break
        if svc is not None and kern is None:
            # The headline landed but the kernel attempt at (or
            # before) that label wedged: keep walking the remaining
            # smaller/CPU rungs for the kernel number alone.
            start = next(i for i, a in enumerate(attempts)
                         if a[0] == label)
            for label2, shapes2, budget2 in attempts[start + 1:]:
                kern = _run_stage("kernel", label2, shapes2,
                                  args.seconds, budget2, force_cpu)
                if kern is not None:
                    kern_label = label2
                    break
        if svc is not None:
            svc["kernel_rounds_per_sec"] = (
                kern["kernel_rounds_per_sec"] if kern else None)
            svc["kernel_label"] = kern_label
            # BASELINE ladder #4 (1M-segment incremental Merkle
            # updates) on whatever platform the headline landed on.
            # BASELINE ladder #4 (Merkle) and #5 (reconfig churn),
            # keyed by the runner's OWN metric string so the reported
            # shape can never drift from the measured one.
            svc["ladder"] = {}
            for stage in ("merkle", "reconfig"):
                r = _run_stage(stage, label, {}, args.seconds,
                               300.0, force_cpu)
                if r is not None:
                    svc["ladder"][r["ladder_metric"]] = r["ladder_value"]
            # cross-host replication-group rung (3 OS processes,
            # fsync WALs, host-majority barrier) — CPU-bound sockets
            # + disk, so it runs whatever platform the headline took
            r = _run_stage("repgroup", label, {}, args.seconds,
                           420.0, force_cpu)
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith(("repgroup_", "repl_"))})
            # adversarial fault-injection rungs (ARCHITECTURE §13):
            # RTT sweep (depth 1 vs 2 under a slow link), fsync-delay
            # rung, noisy-tenant isolation — sockets + disk + CPU, so
            # it rides whatever platform the headline took.  The
            # 8-device env arms the stage's mesh rung (the same A/B
            # with the leader's lane sharded along 'ens').
            r = _run_stage("faultsweep", label, {}, args.seconds,
                           700.0, force_cpu, env=_mesh_cpu_env(8))
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith("faultsweep")})
            # autotune A/B (ARCHITECTURE §14): the controller arm vs
            # the best static (depth, window) at 0/5 ms injected ack
            # RTT, plus the tenant-guard rung — same socket/disk
            # profile as the faultsweep, same platform rule (8-device
            # env arms its mesh point)
            r = _run_stage("autotune", label, {}, args.seconds,
                           700.0, force_cpu, env=_mesh_cpu_env(8))
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith("autotune")})
            # fleet-federation overhead A/B (ARCHITECTURE §11): the
            # standing watchdog pull on vs off over an in-process
            # 3-host group — bound < 2%, the PR 8 op-trace bar
            r = _run_stage("fleetobs", label, {}, args.seconds,
                           420.0, force_cpu)
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith("fleet_obs")})
            # restart-to-serving rung (ARCHITECTURE §15): checkpoint
            # restore + WAL replay + first-op warmup at the 512-ens
            # shape — disk + host + compile, so it rides whatever
            # platform the headline took
            r = _run_stage("recovery", label, {}, args.seconds,
                           420.0, force_cpu)
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith("recovery_")})
            # §16 serving-plane rung: proxy-count ingress scaling +
            # the follower-read A/B over a real 3-process group with
            # subprocess proxies and a 10k-connection client herd —
            # sockets + GIL-bound parsing, so it rides whatever
            # platform the headline took
            r = _run_stage("ingress", label, {}, args.seconds,
                           600.0, force_cpu)
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith(("ingress_",
                                             "follower_"))})
            # §18 commutative-replication rung: contended-counter
            # storm, comm vs ordered A/B over a real 3-process group
            # — sockets + disk + host resolve, so it rides whatever
            # platform the headline took
            r = _run_stage("commrepl", label, {}, args.seconds,
                           600.0, force_cpu)
            if r is not None:
                svc.update({k: v for k, v in r.items()
                            if k.startswith(("commrepl_",
                                             "rmw_comm_x"))})
            # E-scaling datapoints (ROADMAP carried debt item 2): the
            # 1k-ens CPU rung always rides the round JSON; the 2k-
            # and 4k-ens points land when the box completes them
            # inside their own budgets (each point is its own
            # killable stage, so a slow deep attempt can never cost
            # the shallower numbers)
            svc["escale_cpu"] = {}
            for ee in (1024, 2048, 4096):
                r = _run_stage("escale", f"{ee}_ens_cpu",
                               dict(n_ens=ee, n_peers=5, n_slots=64,
                                    k=16), args.seconds, 360.0, True)
                if r is None:
                    break
                svc["escale_cpu"][str(ee)] = r["escale"]
            # Mesh E-scaling ladder (ROADMAP open item 2): the fused
            # step sharded over 8 virtual CPU devices along 'ens',
            # 10k and 32k required rungs plus a best-effort 100k.
            # Each mesh point pairs with a SINGLE-SHARD reference at
            # E/8 — equal per-shard load — and scaling efficiency is
            # mesh ops/s over 8x the reference: honest numbers,
            # whatever they are, with device count in each stage's
            # box fingerprint.  Both arms run in the same 8-device
            # environment so their fingerprints match.
            env8 = _mesh_cpu_env(8)
            svc["escale_mesh"] = {}
            for ee in (10_240, 32_768, 102_400):
                r = _run_stage("escale", f"{ee}_ens_mesh8",
                               dict(n_ens=ee, n_peers=5, n_slots=64,
                                    k=16, mesh_devices=8),
                               args.seconds, 600.0, True, env=env8)
                if r is None:
                    break
                point = r["escale"]
                ref = _run_stage("escale", f"{ee // 8}_ens_ref",
                                 dict(n_ens=ee // 8, n_peers=5,
                                      n_slots=64, k=16),
                                 args.seconds, 360.0, True, env=env8)
                if ref is not None:
                    ref_ops = ref["escale"]["ops_per_sec"]
                    point["single_ref_n_ens"] = ee // 8
                    point["single_ref_ops_per_sec"] = ref_ops
                    point["escale_eff"] = (
                        round(point["ops_per_sec"] / (8 * ref_ops), 3)
                        if ref_ops else None)
                svc["escale_mesh"][str(ee)] = point
            # headline efficiency for the trend ratchet: the >=10k
            # acceptance rung (device count rides the fingerprint)
            p10k = svc["escale_mesh"].get("10240")
            if p10k is not None:
                svc["escale_eff"] = p10k.get("escale_eff")
            # Staged TPU-probe script (ROADMAP: the one-command live
            # window).  On a CPU-only box it still runs the staging
            # end to end and reports verdicts as pending-tpu.
            r = _run_stage("tpuprobe", label, {}, args.seconds,
                           600.0, force_cpu)
            if r is not None:
                svc["tpuprobe"] = {k2: v for k2, v in r.items()
                                   if k2 not in ("box", "platform")}
        # The preflight saw an accelerator but no headline landed:
        # the chip is answering yet too slow for the throughput
        # loops.  Time single launches with a generous budget; each
        # completed launch is persisted.
        stepprobe = None
        if svc is None:
            stepprobe = _run_stepprobe(600.0, STEPPROBE_SHAPES)
        if svc is None:
            print(json.dumps({
                "metric": "service_linearizable_kv_ops_per_sec",
                "value": 0, "unit": "ops/sec", "vs_baseline": 0.0,
                "error": "every stage attempt timed out or crashed "
                         "(TPU backend unreachable?)",
                "tpu_stepprobe": stepprobe,
            }))
            sys.exit(1)
        if stepprobe is not None:
            svc["tpu_stepprobe"] = stepprobe

    baseline = 1_000_000.0  # north-star target (BASELINE.md)
    print(json.dumps({
        "metric": f"service_linearizable_kv_ops_per_sec_{label}",
        "value": round(svc["ops_per_sec"], 1),
        "unit": "ops/sec",
        "vs_baseline": round(svc["ops_per_sec"] / baseline, 3),
        "p50_commit_latency_ms": round(svc["p50_ms"], 3),
        "p99_commit_latency_ms": round(svc["p99_ms"], 3),
        "latency_batches": svc["batches"],
        # the headline loop's launch pipeline depth + the depth-1
        # serial reference (the silently-serialized-pipeline A/B)
        "pipeline_depth": svc.get("pipeline_depth"),
        "serial_ops_per_sec": (
            round(svc["serial_ops_per_sec"], 1)
            if svc.get("serial_ops_per_sec") else None),
        "serial_p50_ms": (round(svc["serial_p50_ms"], 3)
                          if svc.get("serial_p50_ms") else None),
        "serial_p99_ms": (round(svc["serial_p99_ms"], 3)
                          if svc.get("serial_p99_ms") else None),
        "serial_latency_breakdown_ms": svc.get(
            "serial_latency_breakdown"),
        "engine_kernel_rounds_per_sec": (
            round(svc["kernel_rounds_per_sec"], 1)
            if svc.get("kernel_rounds_per_sec") else None),
        "kernel_label": svc.get("kernel_label", label),
        "keyed_service_ops_per_sec": (
            round(svc["keyed_ops_per_sec"], 1)
            if svc.get("keyed_ops_per_sec") else None),
        "keyed_batched_ops_per_sec": (
            round(svc["keyed_batched_ops_per_sec"], 1)
            if svc.get("keyed_batched_ops_per_sec") else None),
        "mixed_ops_per_sec": (
            round(svc["mixed_ops_per_sec"], 1)
            if svc.get("mixed_ops_per_sec") else None),
        "mixed_p50_ms": (round(svc["mixed_p50_ms"], 3)
                         if svc.get("mixed_p50_ms") else None),
        "mixed_p99_ms": (round(svc["mixed_p99_ms"], 3)
                         if svc.get("mixed_p99_ms") else None),
        "mixed_commit_fraction": svc.get("mixed_commit_fraction"),
        # mixed-rung tail attribution: which latency mark dominated
        # each >5x-p50 batch (the formerly unexplained mixed_p99)
        "mixed_tail_batches": svc.get("mixed_tail_batches"),
        "mixed_tail_causes": svc.get("mixed_tail_causes"),
        "mixed_tail_top_cause": svc.get("mixed_tail_top_cause"),
        "rmw_device_ops_per_sec": (
            round(svc["rmw_device_ops_per_sec"], 1)
            if svc.get("rmw_device_ops_per_sec") else None),
        "rmw_host_ops_per_sec": (
            round(svc["rmw_host_ops_per_sec"], 1)
            if svc.get("rmw_host_ops_per_sec") else None),
        "rmw_device_speedup": (
            round(svc["rmw_device_speedup"], 2)
            if svc.get("rmw_device_speedup") else None),
        "rmw_device_flushes_per_round": svc.get(
            "rmw_device_flushes_per_round"),
        "rmw_host_flushes_per_round": svc.get(
            "rmw_host_flushes_per_round"),
        "skewed_service_ops_per_sec": (
            round(svc["skewed_ops_per_sec"], 1)
            if svc.get("skewed_ops_per_sec") else None),
        "skewed_baseline_ops_per_sec": (
            round(svc["skewed_baseline_ops_per_sec"], 1)
            if svc.get("skewed_baseline_ops_per_sec") else None),
        "skewed_compaction_speedup": svc.get(
            "skewed_compaction_speedup"),
        "payload_bytes_per_flush": svc.get("payload_bytes_per_flush"),
        "payload_bytes_full_width_per_flush": svc.get(
            "payload_bytes_full_width_per_flush"),
        "grid_occupancy": svc.get("grid_occupancy"),
        # lease-protected read fast path: the read-heavy rung with
        # its fastpath-off A/B arm
        "read_service_ops_per_sec": (
            round(svc["read_service_ops_per_sec"], 1)
            if svc.get("read_service_ops_per_sec") else None),
        "read_only_ops_per_sec": (
            round(svc["read_only_ops_per_sec"], 1)
            if svc.get("read_only_ops_per_sec") else None),
        "read_baseline_only_ops_per_sec": (
            round(svc["read_baseline_only_ops_per_sec"], 1)
            if svc.get("read_baseline_only_ops_per_sec") else None),
        "read_fastpath_speedup": svc.get("read_fastpath_speedup"),
        "read_hit_rate": svc.get("read_hit_rate"),
        "read_fastpath_hits": svc.get("read_fastpath_hits"),
        "read_fastpath_misses": svc.get("read_fastpath_misses"),
        "read_miss_reasons": svc.get("read_miss_reasons"),
        "read_p50_ms": svc.get("read_p50_ms"),
        "read_p99_ms": svc.get("read_p99_ms"),
        "repgroup_ops_per_sec": svc.get("repgroup_ops_per_sec"),
        "repgroup_p50_ms": svc.get("repgroup_p50_ms"),
        "repgroup_p99_ms": svc.get("repgroup_p99_ms"),
        "repgroup_baseline_ops_per_sec":
            svc.get("repgroup_baseline_ops_per_sec"),
        "repl_delta_speedup": svc.get("repl_delta_speedup"),
        "repl_bytes_per_entry": svc.get("repl_bytes_per_entry"),
        "repl_bytes_per_entry_full_plane":
            svc.get("repl_bytes_per_entry_full_plane"),
        "repl_ship_breakdown_ms": svc.get("repl_ship_breakdown_ms"),
        "latency_breakdown_ms": svc.get("latency_breakdown"),
        "tpu_stepprobe": svc.get("tpu_stepprobe"),
        # observability plane: the obs-on/off A/B (acceptance: on
        # within 3% of off on the same box) + flight-recorder
        # evidence for the mixed rung
        "obs_on_ops_per_sec": (
            round(svc["obs_on_ops_per_sec"], 1)
            if svc.get("obs_on_ops_per_sec") else None),
        "obs_off_ops_per_sec": (
            round(svc["obs_off_ops_per_sec"], 1)
            if svc.get("obs_off_ops_per_sec") else None),
        "obs_overhead_pct": svc.get("obs_overhead_pct"),
        # per-op SLO tracing A/B on the keyed rung (acceptance: on
        # within 2% of off — the ring stamps live on this path)
        "op_trace_on_ops_per_sec": (
            round(svc["op_trace_on_ops_per_sec"], 1)
            if svc.get("op_trace_on_ops_per_sec") else None),
        "op_trace_off_ops_per_sec": (
            round(svc["op_trace_off_ops_per_sec"], 1)
            if svc.get("op_trace_off_ops_per_sec") else None),
        "op_trace_overhead_pct": svc.get("op_trace_overhead_pct"),
        "mixed_flight_anomalies": svc.get("mixed_flight_anomalies"),
        # native single-pass resolve kernel: the interleaved on/off
        # A/B on the WAL'd keyed batched rung, plus the native arm's
        # component breakdown (where the batch time goes after the
        # kernel — the honest form of the 'bottleneck moved off
        # resolve' claim)
        "resolve_native_available": svc.get(
            "resolve_native_available"),
        "resolve_native_speedup": svc.get("resolve_native_speedup"),
        "resolve_native_ops_per_sec": (
            round(svc["resolve_native_ops_per_sec"], 1)
            if svc.get("resolve_native_ops_per_sec") else None),
        "resolve_fallback_ops_per_sec": (
            round(svc["resolve_fallback_ops_per_sec"], 1)
            if svc.get("resolve_fallback_ops_per_sec") else None),
        "resolve_native_latency_breakdown_ms": svc.get(
            "resolve_native_latency_breakdown"),
        # slab enqueue half (ARCHITECTURE §12): the interleaved
        # on/off A/B on the same WAL'd keyed rung, the acceptance
        # criterion's queue_wait+resolve p50 cut per arm, the on
        # arm's breakdown (with the derived enqueue_native/
        # enqueue_fallback pack marks), and the completion slab's
        # one-wake-per-flush ledger
        "enqueue_native_available": svc.get(
            "enqueue_native_available"),
        "enqueue_native_speedup": svc.get("enqueue_native_speedup"),
        "enqueue_native_ops_per_sec": (
            round(svc["enqueue_native_ops_per_sec"], 1)
            if svc.get("enqueue_native_ops_per_sec") else None),
        "enqueue_fallback_ops_per_sec": (
            round(svc["enqueue_fallback_ops_per_sec"], 1)
            if svc.get("enqueue_fallback_ops_per_sec") else None),
        "enqueue_queue_wait_resolve_p50_ms": svc.get(
            "enqueue_queue_wait_resolve_p50_ms"),
        "enqueue_native_latency_breakdown_ms": svc.get(
            "enqueue_native_latency_breakdown"),
        "enqueue_completion_slab": svc.get(
            "enqueue_completion_slab"),
        # adversarial fault-injection rungs (ARCHITECTURE §13): the
        # RTT sweep's depth-1/2 points, the fsync-delay rung and the
        # noisy-tenant isolation A/B, with the injected fault config
        # embedded next to the box fingerprint
        "faultsweep": svc.get("faultsweep"),
        "faultsweep_depth2_speedup": svc.get(
            "faultsweep_depth2_speedup"),
        # E-scaling CPU datapoints (1k always, 2k when the box
        # allows) — the curve alongside the 512-ens headline rung
        "escale_cpu": svc.get("escale_cpu"),
        # mesh E-scaling ladder (10k/32k/best-effort 100k on the
        # 8-device mesh) + the single-shard equal-per-shard-load
        # references; escale_eff is the >=10k rung's scaling
        # efficiency — the bench_trend ratchet column
        "escale_mesh": svc.get("escale_mesh"),
        "escale_eff": svc.get("escale_eff"),
        # staged TPU probe (--stage tpuprobe): compile ledger, ladder
        # and the Pallas-quorum keep/kill verdict (pending-tpu
        # until a live window executes them on a real accelerator)
        "tpuprobe": svc.get("tpuprobe"),
        # bench-trend ratchet (smoke path): the trajectory check's
        # report — rounds folded, newest headline, same-box band
        "bench_trend": svc.get("bench_trend"),
        **{k: round(v, 1) for k, v in svc.get("ladder", {}).items()},
        "platform": svc.get("platform", "unknown"),
        # the box this round's numbers were captured on — embedded so
        # cross-round deltas are checked against the box first
        "box": svc.get("box", _main_box()),
    }))


def _main_box():
    from riak_ensemble_tpu.obs import box_fingerprint
    return box_fingerprint()


if __name__ == "__main__":
    sys.exit(main())
