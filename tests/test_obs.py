"""The unified observability plane (docs/ARCHITECTURE.md §11).

Covers the four pieces end to end: registry/histogram correctness
against a numpy oracle, flight-recorder trigger + ring bound + dump
schema round-trip, leader→replica flush_id correlation on a LIVE
replication group (every replica apply span names a leader flush
span), and per-tenant counter attribution under a two-tenant
workload — plus the satellite contracts (Tracer's bounded finished
ring folding into a registry, the RETPU_OBS=0 short-circuit, and the
svcnode ``metrics`` verb)."""

import gc
import json
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import obs, wire  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.obs.flightrec import DUMP_SCHEMA  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)


# -- registry ---------------------------------------------------------------

def test_histogram_matches_numpy_oracle():
    """Fixed-bucket counts must agree exactly with a searchsorted
    oracle over the same edges, and the quantile estimate must land
    inside the true quantile's bucket."""
    h = obs.Histogram("retpu_test_ms")
    rng = np.random.default_rng(7)
    vals = rng.lognormal(1.0, 1.5, 4000)
    for v in vals:
        h.record(float(v))
    edges = np.asarray(h.buckets)
    oracle = np.bincount(np.searchsorted(edges, vals, side="left"),
                         minlength=len(edges) + 1)
    assert oracle.tolist() == h.counts
    assert h.count == len(vals)
    assert np.isclose(h.sum, vals.sum())
    for q in (0.5, 0.9, 0.99):
        est = h.percentile(q)
        true = float(np.percentile(vals, q * 100))
        i = int(np.searchsorted(edges, true, side="left"))
        lo = 0.0 if i == 0 else float(edges[i - 1])
        hi = float(edges[i]) if i < len(edges) else float("inf")
        assert lo <= est <= min(hi, float(edges[-1])), (q, est, true)


def test_histogram_empty_and_overflow():
    h = obs.Histogram("retpu_test_ms", buckets=(1.0, 10.0))
    assert h.percentile(0.5) == 0.0
    h.record(5000.0)  # overflow bucket
    assert h.counts == [0, 0, 1]
    # the overflow bucket has no honest upper edge: report its floor
    assert h.percentile(0.99) == 10.0


def test_registry_counters_gauges_labels_and_export():
    r = obs.MetricsRegistry()
    c = r.counter("retpu_x_total", "a counter")
    c.inc()
    c.labels("hot").inc(3)
    r.gauge("retpu_g", "a gauge", fn=lambda: 42)
    r.histogram("retpu_h_ms").record(2.0)
    r.collect(lambda: {"retpu_fam": {
        "type": "counter", "help": "fam",
        "values": {"a": 1, "b": 2}}})
    snap = r.snapshot()
    assert snap["retpu_x_total"]["hot"] == 3
    assert snap["retpu_g"] == 42
    assert snap["retpu_h_ms"]["count"] == 1
    assert snap["retpu_fam"] == {"a": 1, "b": 2}
    # the snapshot is wire-encodable (the svcnode metrics verb ships
    # it through the restricted codec)
    assert wire.decode(wire.encode(snap)) == snap
    txt = r.render_prometheus()
    assert '# TYPE retpu_x_total counter' in txt
    assert 'retpu_x_total{tenant="hot"} 3' in txt
    assert 'retpu_h_ms_bucket' in txt and 'retpu_h_ms_count 1' in txt
    assert 'retpu_fam{tenant="a"} 1' in txt
    assert sorted(r.names()) == ["retpu_fam", "retpu_g", "retpu_h_ms",
                                 "retpu_x_total"]
    # the unlabeled sample of a labeled family exports under "" (not
    # a forged tenant named "None")
    assert snap["retpu_x_total"][""] == 1
    assert "None" not in snap["retpu_x_total"]


def test_prometheus_label_escaping():
    """Tenant labels are arbitrary user strings; one unescaped quote
    would make Prometheus reject the entire scrape."""
    r = obs.MetricsRegistry()
    r.counter("retpu_x_total").labels('a"b\\c\nd').inc()
    txt = r.render_prometheus()
    assert 'tenant="a\\"b\\\\c\\nd"' in txt
    assert '\n' not in txt.split("retpu_x_total{")[1].split("}")[0]


# -- flight recorder --------------------------------------------------------

def _feed(fr, n, total=0.01, start=0):
    for i in range(n):
        out = fr.record({"flush_id": start + i, "total": total,
                         "unpack": total / 2})
        assert out is None, "healthy flush must not trigger"


def test_flight_trigger_ring_bound_and_dump_roundtrip(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("RETPU_OBS_DUMP_DIR", str(tmp_path))
    fr = obs.FlightRecorder(capacity=64, min_samples=16,
                            refresh_every=4, min_dump_interval_s=0.0,
                            name="t")
    _feed(fr, 32)
    snap = fr.record({"flush_id": 999, "total": 0.2,
                      "device_d2h": 0.19, "unpack": 0.01})
    assert snap is not None and fr.anomalies == 1
    trig = snap["trigger"]
    assert trig["flush_id"] == 999
    assert trig["ratio"] >= trig["threshold"] == 5.0
    assert trig["dominant_mark"] == "device_d2h"
    # ring bound holds under sustained load
    _feed(fr, 300, start=1000)
    assert len(fr.records) == 64
    # the dump file round-trips: schema, the ring (trigger flush
    # included), and the box fingerprint
    with open(snap["path"]) as f:
        data = json.load(f)
    assert data["schema"] == DUMP_SCHEMA
    assert data["trigger"]["flush_id"] == 999
    assert any(r.get("flush_id") == 999 for r in data["ring"])
    # schema v2 sections present even without an extras provider
    assert data["slow_ops"] == [] and data["compile_events"] == []
    box = data["box"]
    assert box["schema"] == "retpu-box-fingerprint-v1"
    assert box["cpu_count"] == os.cpu_count()
    assert "jax" in box and "retpu_knobs" in box
    assert "loadavg" in box


def test_flight_trigger_unarmed_before_min_samples():
    fr = obs.FlightRecorder(min_samples=32, refresh_every=4,
                            min_dump_interval_s=0.0)
    _feed(fr, 8)
    assert fr.record({"flush_id": 9, "total": 5.0}) is None
    assert fr.anomalies == 0


def test_flight_trigger_rate_limited():
    """The rate limit bounds DUMPS, not the anomaly counter: during
    a sustained incident every trigger firing still counts."""
    fr = obs.FlightRecorder(min_samples=8, refresh_every=2,
                            min_dump_interval_s=3600.0)
    _feed(fr, 16)
    assert fr.record({"flush_id": 1, "total": 1.0}) is not None
    assert fr.record({"flush_id": 2, "total": 1.0}) is None
    assert fr.anomalies == 2
    assert len(fr.dumps) == 1


def test_injected_slow_flush_dumps_on_live_service(tmp_path,
                                                   monkeypatch):
    """Acceptance: an injected >5x-p50 flush on a REAL service
    produces a flight dump with the per-flush ring and the box
    fingerprint."""
    monkeypatch.setenv("RETPU_OBS_DUMP_DIR", str(tmp_path))
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 max_ops_per_tick=2)
    svc.flight = obs.FlightRecorder(min_samples=8, refresh_every=2,
                                    min_dump_interval_s=0.0,
                                    name="svc")
    # the collector is held off the healthy flushes: a full pass of
    # a test process's heap (80 ms here) inside one of these ~2 ms
    # flushes IS a >5x flush, and where it lands is chance
    gc.collect()
    gc.disable()
    try:
        for i in range(12):
            fut = svc.kput(i % 4, "k", b"v%d" % i)
            while not fut.done:
                svc.flush()
    finally:
        gc.enable()
    assert svc.flight.anomalies == 0, \
        "healthy flushes must not trigger"
    # inject the stall at the d2h seam (the deterministic injection
    # point the pipeline tests use) — 6x the recorder's own rolling
    # p50 guarantees the trigger fires regardless of box speed
    stall = max(6.0 * svc.flight._p50, 0.05)
    orig = svc._fetch_packed

    def slow_fetch(fl):
        time.sleep(stall)
        return orig(fl)

    monkeypatch.setattr(svc, "_fetch_packed", slow_fetch)
    fut = svc.kput(0, "k", b"slow")
    while not fut.done:
        svc.flush()
    assert svc.flight.anomalies >= 1
    snap = svc.flight.dumps[-1]
    assert snap["schema"] == DUMP_SCHEMA
    assert snap["box"]["cpu_count"] == os.cpu_count()
    assert len(snap["ring"]) >= 8
    assert os.path.exists(snap["path"])
    # schema v2: the live service's dump carries the per-op ring
    # tail (slowest acked ops, stage splits, flush-id joins).  The
    # very slowest row is the first-compile-era op (its queue wait
    # ate the XLA compile — itself a correct attribution); the
    # STALLED op appears in the tail with its flush stage dominating
    assert snap["slow_ops"], "per-op tail section missing"
    assert all(o["flush_id"] > 0 for o in snap["slow_ops"])
    stalled = [o for o in snap["slow_ops"]
               if o["ms"] >= stall * 1e3 * 0.9
               and o["stages_ms"]["flush"]
               >= o["stages_ms"]["queue_wait"]]
    assert stalled, snap["slow_ops"]
    # compile-event section present and well-formed (entries only
    # when THIS process's jit caches were cold for these shapes —
    # earlier tests may have warmed them; the deterministic
    # un-warmed-bucket catch lives in test_opslo with a unique E)
    assert isinstance(snap["compile_events"], list)
    for e in snap["compile_events"]:
        assert e["phase"] in ("serve", "warmup") and e["fn"], e
    # the anomalous flush is queryable through the obs span API too
    tl = obs.timeline(snap["trigger"]["flush_id"])
    assert tl is not None and "leader" in tl
    svc.stop()


# -- cross-process flush tracing (live repgroup) ----------------------------

def test_flush_id_correlation_on_live_repgroup(tmp_path):
    """Acceptance: given a flush_id, the obs API returns the JOINED
    leader + replica timeline — and every replica apply span recorded
    during the run names a leader flush span."""
    from riak_ensemble_tpu.parallel import repgroup

    before = set(obs.SPANS.flush_ids())
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
    futs = [svc.kput_many(e, ["a", "b"], [b"1", b"2"])
            for e in range(4)]
    while any(svc.queues):
        svc.flush()
    assert svc.heartbeat()
    assert all(f.done for f in futs)
    # acks settle at MAJORITY time — wait until BOTH lanes actually
    # reached the leader's applied position before reading their
    # span records (the straggler lane records when it lands)
    svc._drain_pending(block_all=True)
    want = (svc.core.applied_ge, svc.core.applied_seq)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        with servers[0]._lock, servers[1]._lock:
            if all((s.core.applied_ge, s.core.applied_seq) >= want
                   for s in servers):
                break
        time.sleep(0.02)

    def replica_sides(tl):
        # replica roles carry the lane tag ("replica@host:port") so
        # in-process lanes don't merge; match by prefix
        return {k: v for k, v in tl.items()
                if isinstance(k, str) and k.startswith("replica")}

    new = [fid for fid in obs.SPANS.flush_ids() if fid not in before]
    assert new, "the run recorded no flush timelines"
    joined = 0
    for fid in new:
        tl = obs.timeline(fid)
        reps = replica_sides(tl) if tl else {}
        if not reps:
            continue
        # every replica apply span names a leader flush span: the
        # SAME id carries both halves of the timeline
        assert "leader" in tl, f"replica-only timeline for {fid}"
        joined += 1
        for side in reps.values():
            r_spans = dict(side["spans"])
            assert "apply" in r_spans, tl
            if side.get("kind") == "delta":
                assert "validate" in r_spans, tl
        assert dict(tl["leader"]["spans"]), tl
    assert joined >= 1, "no flush joined leader and replica spans"
    # at least one data-bearing delta flush shows the full causal
    # chain on BOTH lanes: leader enqueue/build/ack + per-lane
    # replica scatter/rebuild/WAL (the lane tags keep the 2
    # in-process replicas' spans separate)
    full = []
    for fid in new:
        t = obs.timeline(fid)
        if not t:
            continue
        reps = {k: v for k, v in replica_sides(t).items()
                if v.get("kind") == "delta"}
        if reps and "repl_ack" in dict(t["leader"]["spans"]):
            full.append((t, reps))
    assert full, "no delta flush carries the end-to-end timeline"
    # both lanes drained above, so some delta flush must carry BOTH
    # lane-tagged replica records (distinct roles, not merged)
    both = [(t, r) for t, r in full if len(r) == 2]
    assert both, f"no flush tagged both lanes: {[list(r) for _, r in full]}"
    _some, reps = both[-1]
    for side in reps.values():
        for name in ("validate", "scatter", "rebuild", "wal_sync"):
            assert name in dict(side["spans"])
    svc.stop()
    for s in servers:
        s.stop()


# -- per-tenant attribution -------------------------------------------------

def test_two_tenant_attribution():
    """Acceptance: a hot and a quiet tenant are separable in the
    per-tenant ledger — ops, bytes, device-round share, p50/p99."""
    svc = BatchedEnsembleService(WallRuntime(), 8, 3, 8, tick=None,
                                 max_ops_per_tick=4)
    svc.set_tenant_label(0, "hot")
    svc.set_tenant_label(1, "quiet")
    futs = []
    for i in range(40):
        futs.append(svc.kput(0, f"k{i % 4}", b"x" * 32))
    for i in range(4):
        futs.append(svc.kput(1, "q", b"y"))
    while any(svc.queues):
        svc.flush()
    assert all(f.done and f.value[0] == "ok" for f in futs)
    ts = svc.tenant_stats()
    hot, quiet = ts["hot"], ts["quiet"]
    assert hot["ops"] == 40 and quiet["ops"] == 4
    assert hot["commits"] == 40 and quiet["commits"] == 4
    assert hot["put_bytes"] == 40 * 32 and quiet["put_bytes"] == 4
    assert hot["device_rounds"] >= quiet["device_rounds"] > 0
    assert 0 < hot["device_round_share"] <= 1.0
    assert hot["p99_ms"] >= hot["p50_ms"] >= 0
    # leased fast reads count into the tenant ledger without a flush
    f = svc.kget(0, "k0")
    assert f.done and f.value[0] == "ok"
    assert svc.read_fastpath_hits >= 1
    assert svc.tenant_stats()["hot"]["ops"] == 41
    # the labels surface in every export: stats(), the registry
    # snapshot, and the Prometheus text
    assert "hot" in svc.stats()["tenants"]
    snap = svc.obs_registry.snapshot()
    assert snap["retpu_tenant_ops_total"]["hot"] == 41
    assert 'retpu_tenant_ops_total{tenant="hot"} 41' in \
        svc.obs_registry.render_prometheus()
    # a tenant spanning several rows is ONE tenant: rows sharing a
    # label aggregate instead of overwriting each other
    svc.set_tenant_label(2, "hot")
    f = svc.kput(2, "x", b"zz")
    while not f.done:
        svc.flush()
    agg = svc.tenant_stats()["hot"]
    assert agg["rows"] == [0, 2]
    assert agg["ops"] == 42 and agg["put_bytes"] == 40 * 32 + 2
    svc.stop()


def test_tenant_ledger_resets_on_row_recycle():
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 max_ops_per_tick=2, dynamic=True)
    row = svc.create_ensemble("t1")
    fut = svc.kput(row, "k", b"v")
    while not fut.done:
        svc.flush()
    assert svc.tenant_stats()["t1"]["ops"] == 1
    assert svc.destroy_ensemble("t1")
    row2 = svc.create_ensemble("t2")
    assert row2 == row  # recycled
    assert svc.tenant_ops[row] == 0, \
        "a recycled row must start with a clean tenant ledger"
    assert "t1" not in svc.tenant_stats()
    svc.stop()


# -- RETPU_OBS=0 short-circuit ---------------------------------------------

def test_obs_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("RETPU_OBS", "0")
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 max_ops_per_tick=2)
    fut = svc.kput(0, "k", b"v")
    while not fut.done:
        svc.flush()
    assert fut.value[0] == "ok"
    assert svc.stats()["obs_enabled"] is False
    assert not svc.flight.records
    assert int(svc.tenant_ops.sum()) == 0
    assert int(svc._tenant_lat.sum()) == 0
    svc.stop()


# -- Tracer: bounded finished ring + registry fold --------------------------

def test_tracer_finished_ring_bounded_and_registry_fold():
    from riak_ensemble_tpu.utils.trace import Tracer

    class _RT:
        now = 0.0
        trace = None

    rt = _RT()
    reg = obs.MetricsRegistry()
    tr = Tracer(rt, max_finished=16, registry=reg).install()
    for i in range(100):
        rt.now = float(i)
        sid = tr.begin("op", 0)
        rt.now = float(i) + 0.5
        tr.finish(sid, "ok")
        tr._on_event("tick", {})
    # the finished ring is bounded; the counters stay exact
    assert len(tr.finished) == 16
    assert tr.counters["span:op"] == 100
    assert tr.counters["tick"] == 100
    # the registry mirror: event counts + span duration histogram
    snap = reg.snapshot()
    assert snap["retpu_trace_events_total"]["tick"] == 100
    h = reg.histogram("retpu_trace_span_ms").labels("op")
    assert h.count == 100
    assert tr.percentiles("op")[0.5] == 0.5
    tr.uninstall()


# -- svcnode health verb ----------------------------------------------------

def test_svcnode_health_verb():
    """The ensemble-health verb over the wire: service summary and
    per-row detail, host-mirror-sourced (no flush needed to answer),
    with hostile ensemble indices rejected."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    async def run():
        server = await svcnode.serve(4, 3, 8, port=0, tick=0.002,
                                     config=fast_test_config())
        client = svcnode.ServiceClient(server.host, server.port)
        await client.connect()
        try:
            r = await client.kput(1, "k", b"v")
            assert r[0] == "ok"
            h = await client.health()
            assert h["schema"] == "retpu-health-v1"
            assert h["n_ens"] == 4
            assert h["ensembles_with_leader"] >= 1
            assert h["queued_ops"] == 0
            assert isinstance(h["pending_writes"], int)
            row = await client.health(1)
            assert row["ens"] == 1 and row["leader"] >= 0
            assert row["committed_epoch"] >= 1
            assert row["elections"] >= 1
            assert row["corrupt"] is False
            assert row["lease_valid"] in (True, False)
            # flushes advance the flush counter, not the verb: a
            # health read is zero-device-round (flushes unchanged
            # modulo the server's own tick loop serving real ops)
            bad = await client.call("health", 99)
            assert bad == ("error", "bad-request")
            bad2 = await client.call("health", -1)
            assert bad2 == ("error", "bad-request")
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())


# -- svcnode metrics verb ---------------------------------------------------

def test_svcnode_metrics_verb():
    import asyncio

    from riak_ensemble_tpu import svcnode

    async def run():
        server = await svcnode.serve(4, 3, 8, port=0, tick=0.002,
                                     config=fast_test_config())
        client = svcnode.ServiceClient(server.host, server.port)
        await client.connect()
        try:
            r = await client.kput(0, "k", b"v")
            assert r[0] == "ok"
            snap = await client.metrics()
            assert isinstance(snap, dict)
            assert snap["retpu_flushes_total"] >= 1
            assert snap["retpu_ops_served_total"] >= 1
            assert "retpu_flush_total_ms" in snap
            txt = await client.metrics("prometheus")
            assert isinstance(txt, str)
            assert "# TYPE retpu_flushes_total counter" in txt
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())


# -- the span primitive (obs.spans; docs/ARCHITECTURE.md §11) ---------------

ADDITIVE_D1 = ("h2d", "dispatch", "device_d2h", "unpack", "wal",
               "resolve", "queue_wait")
WAL_PARTS = ("wal_encode", "wal_append", "wal_fsync")


@pytest.mark.parametrize("form", ["with", "begin_end", "open_record"])
def test_span_stamps_start_and_duration(form):
    """A span puts its seconds under ``rec[name]`` and its start on
    ``perf_counter`` under ``rec["starts"][name]``; a record carries
    one (perf_counter, time.time()) pair; a span that names no record
    lands in the recorder's open one."""
    sp = obs.spans.SpanRecorder()
    before = time.perf_counter(), time.time()
    rec = sp.begin()
    if form == "with":
        with sp.span("wal", rec):
            pass
    elif form == "begin_end":
        s = sp.span("wal", rec).begin()
        assert s.end() == s.seconds == rec["wal"]
    else:
        sp.settling(rec)
        with sp.span("wal"):
            pass
        sp.settling(None)
        with sp.span("fe_decode"):
            pass
        assert "fe_decode" in sp.loop and "fe_decode" not in rec
    after = time.perf_counter(), time.time()
    assert isinstance(rec["wal"], float) and rec["wal"] >= 0.0
    assert before[0] <= rec["clock"][0] <= rec["starts"]["wal"] \
        <= after[0]
    assert before[1] <= rec["clock"][1] <= after[1]


def test_span_accumulates_repeats_and_renames():
    """A mark that runs twice sums its seconds and keeps its FIRST
    start; a span may be named at its end (the arm that ran)."""
    sp = obs.spans.SpanRecorder()
    rec = sp.begin()
    with sp.span("resolve_fallback", rec) as arm:
        pass
    first = rec["starts"]["resolve_fallback"]
    with sp.span("resolve_fallback", rec) as arm:
        arm.name = "resolve_native"
    with sp.span("resolve_fallback", rec) as again:
        pass
    assert rec["starts"]["resolve_fallback"] == first
    assert rec["resolve_native"] == arm.seconds
    assert rec["resolve_fallback"] >= again.seconds


def test_loop_record_is_taken_by_the_next_close():
    sp = obs.spans.SpanRecorder()
    sp.between_begin()
    with sp.span("fe_decode"):
        pass
    sp.between_end()
    rec = sp.begin()
    rec["wal"] = 1.0
    sp.close(rec)
    assert {"fe_decode", "between_flushes"} <= set(rec["starts"])
    assert rec["fe_decode"] <= rec["between_flushes"]
    assert sp.loop == {"starts": {}}
    rec2 = sp.begin()
    sp.close(rec2)              # nothing happened since: nothing taken
    assert set(rec2) == {"starts", "clock"}


@pytest.fixture(scope="module", params=[1, 2])
def served_records(request, tmp_path_factory):
    """The records of a few durable batch flushes (tick=None)."""
    d = tmp_path_factory.mktemp(f"spans{request.param}")
    svc = BatchedEnsembleService(
        WallRuntime(), 8, 3, 8, tick=None, config=fast_test_config(),
        data_dir=str(d), pipeline_depth=request.param,
        max_ops_per_tick=2)
    svc.flush()                 # elections
    futs = [svc.kput_many(e, ["a", "b"], [b"1", b"2"])
            for e in range(4)]
    futs += [svc.kget_many(e, ["a", "b"]) for e in range(4)]
    for _ in range(8):
        svc.flush()
    assert all(f.done for f in futs)
    recs = [r for r in svc.lat_records if r.get("k") and "wal" in r]
    assert recs
    flight = list(svc.flight.records)
    bd = svc.latency_breakdown()
    svc.stop()
    return request.param, recs, flight, bd


@pytest.mark.parametrize("mark", ADDITIVE_D1 + (
    "enqueue", "total", "k", "resolve_arm", "enqueue_arm"))
def test_served_flush_keeps_every_mark(served_records, mark):
    """Every mark a served flush's record carried before the primitive
    is still there, a float of seconds under its own name."""
    depth, recs, _flight, bd = served_records
    if mark == "device_d2h" and depth > 1:
        mark = "inflight_wait"
    names = {"resolve_arm": ("resolve_native", "resolve_fallback"),
             "enqueue_arm": ("enqueue_native", "enqueue_fallback")
             }.get(mark, (mark,))
    for r in recs:
        got = [r[n] for n in names if n in r]
        assert got, (mark, sorted(r))
        assert all(isinstance(v, (float, int)) for v in got)
    if mark != "k":
        assert any(n in bd for n in names)


def test_served_flush_total_is_the_sum_of_the_same_marks(
        served_records):
    depth, recs, flight, _bd = served_records
    wait = "device_d2h" if depth == 1 else "inflight_wait"
    additive = set(ADDITIVE_D1) - {"device_d2h"} | {wait}
    for r in recs:
        marks = {c for c in r if c not in obs.flightrec.META_FIELDS}
        assert marks == additive, marks ^ additive
        assert r["total"] == pytest.approx(sum(r[c] for c in marks))
        # the subdivisions lie inside their parents
        assert sum(r.get(p, 0.0) for p in WAL_PARTS) <= r["wal"]
        assert r["obs"] > 0.0 and r["pack"] >= 0.0
        # every span has a start stamp, the record a wall-clock anchor
        timed = {c for c in r
                 if c not in obs.flightrec.SHAPE_FIELDS + (
                     "total", "enqueue", "starts", "clock", "reqs")
                 and not c.startswith(("enqueue_", ))}
        assert timed <= set(r["starts"]), timed - set(r["starts"])
        assert len(r["clock"]) == 2
        if "wal_fsync" in r:    # a flush that wrote
            assert r["starts"]["wal"] <= r["starts"]["wal_fsync"] \
                <= r["starts"]["resolve"]
    # the flight ring and the span store saw the same records
    assert {f["flush_id"] for f in flight if f.get("k")} and all(
        "starts" in f and "clock" in f for f in flight)
    wal_recs = [r for r in recs if "wal_fsync" in r]
    assert wal_recs and all(set(WAL_PARTS) <= set(r) for r in wal_recs)


def test_gc_pause_lands_in_the_open_record_and_hook_goes_with_stop():
    """A collection forced inside a flush is that flush's mark ``gc``
    (a derived mark: it overlaps whichever mark it interrupted), is
    counted in ``stats()["gc"]``, and the ``gc.callbacks`` hook is
    the service's own: installed with the flush timer, removed by
    ``stop()``."""
    import gc

    from riak_ensemble_tpu.runtime import Runtime

    rt = Runtime(seed=11)
    hooks = len(gc.callbacks)
    svc = BatchedEnsembleService(rt, 4, 3, 8, tick=0.005,
                                 config=fast_test_config())
    assert len(gc.callbacks) == hooks + 1
    assert svc.stats()["gc"]["installed"] is True
    rt.run_for(0.05)
    fetch = svc._fetch_packed

    def fetch_and_collect(fl):
        gc.collect()
        return fetch(fl)
    svc._fetch_packed = fetch_and_collect
    fut = svc.kput(0, "k", b"v")
    rt.run_until(lambda: fut.done, 60)
    svc._fetch_packed = fetch
    recs = [r for r in svc.lat_records if r.get("k")]
    assert recs and recs[-1]["gc"] > 0.0
    assert "gc" in recs[-1]["starts"]
    assert recs[-1]["total"] == pytest.approx(sum(
        v for c, v in recs[-1].items()
        if c not in obs.flightrec.META_FIELDS))
    g = svc.stats()["gc"]
    assert g["by_generation"]["2"]["pauses"] >= 1
    assert g["seconds"] >= recs[-1]["gc"]
    svc.stop()
    assert len(gc.callbacks) == hooks
    assert svc.stats()["gc"]["installed"] is False


@pytest.mark.parametrize("obs_on", [True, False])
def test_frontend_counts_frames_and_times_the_loop(monkeypatch,
                                                   obs_on, tmp_path):
    """N requests over loopback: frames in = frames out = N, bytes
    counted both ways, the ``retpu_frontend_*`` families exported;
    with obs on the records carry ``fe_decode``/``fe_dispatch``
    (what the loop spent on requests since the previous flush),
    ``fe_reply`` inside ``resolve`` and ``between_flushes``; with
    ``RETPU_OBS=0`` the counters still count and no ``fe_*`` mark is
    taken."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    monkeypatch.setenv("RETPU_OBS", "1" if obs_on else "0")
    n = 25

    async def run():
        server = await svcnode.serve(4, 3, 8, port=0, tick=0.002,
                                     config=fast_test_config(),
                                     data_dir=str(tmp_path))
        svc = server.svc
        svc.spans.DETAIL_EVERY = 1      # every cycle, not one in 8
        client = svcnode.ServiceClient(server.host, server.port)
        await client.connect()
        try:
            rs = await asyncio.gather(*[
                client.kput(i % 4, f"k{i}", b"v%d" % i)
                for i in range(12)])
            rs += await asyncio.gather(*[
                client.kget(i % 4, f"k{i}") for i in range(12)])
            # one more write: its flush takes what the loop did for
            # the reads (a leased read is answered at once)
            rs.append(await client.kput(0, "last", b"w"))
            assert all(r[0] == "ok" for r in rs)
            fe = dict(svc.frontend)
            assert fe["frames_in"] == fe["frames_out"] == n
            assert fe["bytes_in"] > 4 * n and fe["bytes_out"] > 4 * n
            stats_fe = svc.stats()["frontend"]
            assert {c: stats_fe[c] for c in fe} == fe
            # the loop-lag figure rides the same switch as the marks
            assert ("rx_hold_ms" in stats_fe) == obs_on
            snap = svc.obs_registry.snapshot()
            assert snap["retpu_frontend_frames_total"] == {
                "in": n, "out": n}
            assert snap["retpu_frontend_bytes_total"]["in"] \
                == fe["bytes_in"]
            assert set(snap["retpu_gc_pause_seconds_total"]) == {
                "0", "1", "2"}
            recs = [r for r in svc.lat_records if r.get("k")]
            assert recs
            marks = {c for r in recs for c in r}
            fe_marks = {"fe_decode", "fe_dispatch", "fe_reply",
                        "between_flushes"}
            if not obs_on:
                assert not fe_marks & marks
                return
            assert fe_marks <= marks
            # a reply written at once is not a flush's: its own mark
            assert ("fe_reply_direct" in marks) == (
                svc.read_fastpath_hits > 0)
            for r in recs:
                assert r.get("fe_reply", 0.0) <= r["resolve"]
                assert r["total"] == pytest.approx(sum(
                    v for c, v in r.items()
                    if c not in obs.flightrec.META_FIELDS))
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())


def test_frontend_is_timed_in_one_cycle_in_eight(tmp_path):
    """Outside a profiler session the front end's spans are taken in
    one loop cycle in ``DETAIL_EVERY``: a sampled cycle's record
    carries its decode, dispatch and replies whole, the others none,
    so a median over the records that have them is unbiased and the
    three spans a request cost an eighth."""
    import asyncio

    from riak_ensemble_tpu import svcnode

    async def run():
        server = await svcnode.serve(4, 3, 8, port=0, tick=0.002,
                                     config=fast_test_config(),
                                     data_dir=str(tmp_path))
        svc = server.svc
        assert svc.spans.DETAIL_EVERY == 8
        client = svcnode.ServiceClient(server.host, server.port)
        await client.connect()
        try:
            for i in range(40):     # one flush each
                assert (await client.kput(i % 4, f"k{i % 24}", b"v"))[0] \
                    == "ok"
            recs = [r for r in svc.lat_records if r.get("k")]
            timed = [r for r in recs if "fe_decode" in r]
            assert len(recs) >= 40
            assert 0 < len(timed) <= len(recs) // 4
            for r in timed:
                assert {"fe_dispatch", "fe_reply"} <= set(r)
                assert r["fe_reply"] <= r["resolve"]
            assert all("between_flushes" in r for r in recs[1:])
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())


SESSION_SPANS = ("svc.wal", "svc.wal_encode", "svc.wal_append",
                 "svc.wal_fsync", "svc.resolve", "svc.between_flushes",
                 "svc.h2d", "svc.dispatch", "svc.device_d2h",
                 "svc.unpack", "svc.pack", "svc.obs", "svc.fe_decode",
                 "svc.fe_dispatch", "svc.fe_reply", "py.gc",
                 "svc.h2d_put", "svc.dispatch_step")


@pytest.fixture(scope="module")
def session_host_events(tmp_path_factory):
    """A CPU profiler session (Python tracer off, as the benchmark's
    ``server.py`` starts it) around a few served flushes, reduced by
    the benchmark's own unchanged ``trace_reduce.load``."""
    import asyncio
    import gc
    import sys

    from riak_ensemble_tpu import svcnode

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    import trace_reduce
    trace_dir = str(tmp_path_factory.mktemp("session"))

    async def run():
        server = await svcnode.serve(
            4, 3, 8, port=0, tick=0.002, config=fast_test_config(),
            data_dir=str(tmp_path_factory.mktemp("session_data")))
        client = svcnode.ServiceClient(server.host, server.port)
        await client.connect()
        try:
            await client.kput_many(0, ["w"], [b"warm"])
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                for j in range(4):
                    r = await client.kput_many(
                        j, ["a", "b"], [b"1", b"2"])
                    assert all(x[0] == "ok" for x in r)
                    gc.collect()
                    await asyncio.sleep(0.01)
            finally:
                jax.profiler.stop_trace()
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())
    return trace_reduce.load(trace_dir)["host"]


@pytest.mark.parametrize("name", SESSION_SPANS)
def test_profiler_session_host_plane_holds_the_span(
        session_host_events, name):
    assert any(n == name and dur > 0
               for n, _start, dur in session_host_events), sorted(
        {n for n, _s, _d in session_host_events
         if n.startswith(("svc.", "py."))})


def test_profiler_session_nests_the_barrier_inside_wal(
        session_host_events):
    """On the trace's own clock every ``svc.wal_fsync`` lies inside a
    ``svc.wal``, and one held open across loop callbacks
    (``svc.between_flushes``) is recorded whole."""
    by = {}
    for n, start, dur in session_host_events:
        by.setdefault(n, []).append((start, start + dur))
    for lo, hi in by["svc.wal_fsync"]:
        assert any(a <= lo and hi <= b for a, b in by["svc.wal"])
    for lo, hi in by["svc.fe_reply"]:
        assert any(a <= lo and hi <= b for a, b in by["svc.resolve"])
    assert any(a <= lo and hi <= b
               for lo, hi in by["svc.fe_decode"]
               for a, b in by["svc.between_flushes"])


def _lowered(program):
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng

    e, m, s, k, a = 512, 3, 16, 2, 16
    st = eng.init_state(e, m, s)
    up = jnp.ones((e, m), bool)
    if program == "step_sliced":
        low = eng.full_step_sliced_slab.lower(
            st, jnp.zeros((4 + 5 * k, a), jnp.int32), up, want_vsn=True)
    elif program == "step":     # full width, its pack gathered at a
        low = eng.full_step_slab.lower(
            st, jnp.zeros((4 + 5 * k, e), jnp.int32), up, want_vsn=True,
            gather=a)
    else:
        z = jnp.zeros((k, e), jnp.int32)
        low = eng.full_step.lower(
            st, jnp.zeros((e,), bool), jnp.zeros((e,), jnp.int32),
            z, z, z, jnp.zeros((k, e), bool), up, exp_epoch=z, exp_seq=z)
    return low.as_text(debug_info=True)


STEP_SCOPES = ("elect", "quorum", "slot_gather", "merkle_verify",
               "apply", "merkle_write", "slot_scatter")


@pytest.mark.parametrize("program,scope", [
    *[("step_sliced", s) for s in STEP_SCOPES + ("slice_columns",
                                                 "scatter_columns",
                                                 "idle_quorum")],
    *[("step", s) for s in STEP_SCOPES],
    # the pack is inside the served programs, the per-plane reference
    # step has none
    ("step", "result_pack"), ("step_sliced", "result_pack"),
    *[("per_plane", s) for s in STEP_SCOPES[:1]],
])
def test_lowered_step_names_every_scope(program, scope):
    """``jax.named_scope`` on the step's phases reaches the lowered
    text's locations (and from there the HLO's ``op_name`` and a
    profiler trace): metadata only."""
    from riak_ensemble_tpu.ops import engine as eng

    import re

    assert scope in eng.SCOPES
    # an operation inside the scope is located as "<scope>/<op>"
    assert re.search(r'loc\("(?:[^"]*/)?%s/' % scope,
                     _lowered(program))


# -- the stats-schema ratchet -------------------------------------------------

def test_obs_metric_names_documented():
    """The test_env_knobs pattern applied to metric names: every
    metric a service registry can export is listed in
    docs/ARCHITECTURE.md §11, and every `retpu_*` name the §11 tables
    document still exists — so a new metric can't ship undocumented
    and a renamed one can't haunt the docs."""
    import re

    from riak_ensemble_tpu.parallel.repgroup import ReplicatedService
    from riak_ensemble_tpu.utils.trace import Tracer

    svc = BatchedEnsembleService(WallRuntime(), 2, 1, 4, tick=None,
                                 max_ops_per_tick=2)
    grp = ReplicatedService(WallRuntime(), 2, 1, 4, group_size=1)

    # the tracer's registry-fold names register on first use
    class _RT:
        now = 0.0
        trace = None
    tr = Tracer(_RT(), registry=svc.obs_registry).install()
    tr._on_event("probe", {})
    tr.finish(tr.begin("probe", 0), "ok")
    code_names = set(svc.obs_registry.names()) \
        | set(grp.obs_registry.names())
    svc.stop()
    grp.stop()
    assert code_names, "metric-name scan found nothing"

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "docs", "ARCHITECTURE.md"),
              encoding="utf-8") as fh:
        arch = fh.read()
    documented = set(re.findall(r"`(retpu_[a-z0-9_]+)`", arch))
    missing = code_names - documented
    assert not missing, (
        f"undocumented metric name(s) {sorted(missing)}: add them to "
        "docs/ARCHITECTURE.md §11 'Observability plane'")
    stale = documented - code_names
    assert not stale, (
        f"ARCHITECTURE.md documents removed metric(s) "
        f"{sorted(stale)}: drop the row or restore the metric")
