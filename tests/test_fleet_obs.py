"""Fleet-scope observability (docs/ARCHITECTURE.md §11, round 13).

Unit coverage for the fleet primitives (NTP-midpoint clock offsets
with their asymmetry-proof bound, the Prometheus multi-host merge,
the span store's structured misses, flight-dump rotation), a
deterministic TWO-PROCESS federation smoke — in-process leader plus
a SUBPROCESS replica host, so the span stores are genuinely separate
processes joined only by fids and offsets — and a ``slow``-marked
live 3-host merge under a 5 ms injected one-way RTT (the PR 9 fault
plane as the skew generator) asserting alignment stays within the
estimated offset bound."""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import faults, obs, wire  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.obs import fleet  # noqa: E402
from riak_ensemble_tpu.obs.flightrec import DUMP_SCHEMA  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- clock offsets -----------------------------------------------------------

def test_clock_offset_bound_holds_under_any_asymmetry():
    """The NTP-midpoint invariant: for ANY split of a round-trip
    into request/response delay, |estimate − truth| <= bound.  This
    is the property every alignment assertion downstream leans on."""
    true_offset = 37.5  # remote clock runs this far ahead
    for d_req, d_resp in ((0.001, 0.001), (0.005, 0.0005),
                          (0.0001, 0.008), (0.01, 0.0)):
        c = fleet.ClockOffset()
        t0 = 100.0
        t_remote = t0 + d_req + true_offset
        t1 = t0 + d_req + d_resp
        c.update(t0, t_remote, t1)
        off, bound = c.estimate(now=t1)
        assert abs(off - true_offset) <= bound + 1e-12, \
            (d_req, d_resp, off, bound)


def test_clock_offset_prefers_tight_samples_and_ages_bound():
    c = fleet.ClockOffset()
    c.update(0.0, 50.05, 0.1)    # sloppy: ±50 ms
    c.update(1.0, 51.001, 1.002)  # tight: ±1 ms
    off, bound = c.estimate(now=1.002)
    assert bound < 0.002 and abs(off - 50.0) < 0.001
    # the tight sample's bound widens with age (drift allowance);
    # the estimator must never claim yesterday's precision today
    _off2, bound2 = c.estimate(now=1000.0)
    assert bound2 > bound
    s = c.section()
    assert s["samples"] == 2 and "offset_ms" in s
    assert fleet.ClockOffset().section() == {"samples": 0}
    # a nonsensical window (t1 < t0) is dropped, not folded
    c.update(5.0, 55.0, 4.0)
    assert c.samples == 2


# -- prometheus merge --------------------------------------------------------

def test_merge_prometheus_groups_families_and_labels_hosts():
    r1, r2 = obs.MetricsRegistry(), obs.MetricsRegistry()
    r1.counter("retpu_x_total", "a counter").inc(3)
    r1.histogram("retpu_h_ms").record(2.0)
    r2.counter("retpu_x_total", "a counter").labels('we"ird').inc(5)
    r2.gauge("retpu_g", "a gauge").set(7)
    txt = fleet.merge_prometheus(
        {"a:1": r1.render_prometheus(),
         "b:2": r2.render_prometheus(),
         "dead:3": None})  # unreachable host: skipped, not crashed
    # ONE header block per family even though both hosts export it
    assert txt.count("# TYPE retpu_x_total counter") == 1
    assert 'retpu_x_total{host="a:1"} 3' in txt
    # host label composes with existing (hostile) labels
    assert 'retpu_x_total{host="b:2",tenant="we\\"ird"} 5' in txt
    assert 'retpu_h_ms_bucket{host="a:1",le="0.05"}' in txt
    assert 'retpu_h_ms_count{host="a:1"} 1' in txt
    assert 'retpu_g{host="b:2"} 7' in txt
    # samples of a family merge under one block: no second TYPE line
    # between the two hosts' retpu_x_total samples
    block = txt.split("# TYPE retpu_x_total counter")[1]
    block = block.split("# ")[0]
    assert 'host="a:1"' in block and 'host="b:2"' in block
    # idempotent injection: an already host-labeled sample (a
    # re-merged fleet section, or a family whose own label is host)
    # must NOT grow a duplicate host label — Prometheus rejects the
    # whole document on duplicate label names
    pre = 'retpu_y{host="x:9",peer="p"} 1'
    assert fleet.inject_host_label(pre, "z:1") == pre
    merged2 = fleet.merge_prometheus({"z:1": pre + "\n"})
    assert merged2.count('host="') == 1


def test_registry_render_prometheus_host_kwarg():
    r = obs.MetricsRegistry()
    r.counter("retpu_x_total").inc()
    txt = r.render_prometheus(host="h:9")
    assert 'retpu_x_total{host="h:9"} 1' in txt
    # header lines pass through unlabeled
    assert "# TYPE retpu_x_total counter" in txt


# -- span store structured misses -------------------------------------------

def test_span_store_structured_miss_and_counters():
    s = obs.SpanStore(max_flushes=2)
    s.record(1, "leader", [("a", 0.1)])
    s.record(2, "leader", [("a", 0.1)])
    s.record(3, "leader", [("a", 0.1)])  # evicts fid 1
    hit = s.timeline(2)
    assert "miss" not in hit and hit["leader"]
    assert s.timeline(1) == {"flush_id": 1, "miss": "evicted"}
    assert s.timeline(99) == {"flush_id": 99, "miss": "unknown"}
    assert s.misses == {"evicted": 1, "unknown": 1}
    # span_values: absent fids count a miss and contribute nothing
    vals = s.span_values([2, 1, 99], "leader", "a")
    assert vals == [0.1]
    assert s.misses == {"evicted": 2, "unknown": 2}
    # the service registry exports the process-global store's counts
    svc = BatchedEnsembleService(WallRuntime(), 2, 1, 4, tick=None,
                                 max_ops_per_tick=2)
    snap = svc.obs_registry.snapshot()
    assert set(snap["retpu_span_misses_total"]) == {"evicted",
                                                    "unknown"}
    svc.stop()


# -- flight-dump rotation ----------------------------------------------------

def test_flight_dump_rotation_bounds_dir(tmp_path, monkeypatch):
    """A long soak with a flapping trigger must not fill the disk:
    the dump dir retains at most RETPU_OBS_DUMP_KEEP files,
    oldest-first unlinked, newest (the live evidence) kept."""
    monkeypatch.setenv("RETPU_OBS_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("RETPU_OBS_DUMP_KEEP", "3")
    fr = obs.FlightRecorder(capacity=32, min_samples=8,
                            min_dump_interval_s=0.0, name="t",
                            max_dumps=64)
    for i in range(16):
        assert fr.record({"flush_id": i, "total": 0.01}) is None
    paths = []
    for i in range(8):
        snap = fr.record({"flush_id": 100 + i, "total": 1.0})
        assert snap is not None and "path" in snap
        paths.append(snap["path"])
        # distinct mtimes so oldest-first is deterministic on
        # coarse-mtime filesystems
        t = time.time() - (8 - i)
        os.utime(snap["path"], (t, t))
        fr._rotate(str(tmp_path))
    left = sorted(p for p in os.listdir(tmp_path)
                  if p.endswith(".json"))
    assert len(left) == 3, left
    # the newest dumps survived; the oldest were unlinked
    assert os.path.basename(paths[-1]) in left
    assert os.path.basename(paths[0]) not in left
    # keep<=0 disables rotation
    monkeypatch.setenv("RETPU_OBS_DUMP_KEEP", "0")
    snap = fr.record({"flush_id": 999, "total": 1.0})
    assert snap is not None
    assert len([p for p in os.listdir(tmp_path)
                if p.endswith(".json")]) == 4


# -- the standing pull --------------------------------------------------------

@pytest.mark.parametrize("armed", [True, False])
def test_standing_pull_runs_only_while_armed(tmp_path, monkeypatch,
                                             armed):
    """A leading three-host group with the watchdog armed posts obsq
    pulls on its links, evaluates them and refreshes the links' clock
    estimates; one built under ``RETPU_WATCHDOG=0`` pulls nothing."""
    from test_repl_delta import _group, _settle, _stop

    monkeypatch.setenv("RETPU_WATCHDOG", "1" if armed else "0")
    svc, srvs = _group(tmp_path)
    try:
        svc.watchdog.cadence = 2
        for rnd in range(8):
            _settle(svc, [svc.kput(e, "k", b"v%d" % rnd)
                          for e in range(4)])
        wd = svc.watchdog
        if armed:
            assert wd.pulls > 0 and wd.evals > 0
            assert sum(ln.clock.samples for ln in svc._links) > 0
        else:
            assert (wd.pulls, wd.evals) == (0, 0)
    finally:
        _stop(svc, srvs)


# -- watchdog pending-pull expiry -------------------------------------------

def test_watchdog_expires_orphaned_pulls():
    """A silent fault plan consumes obsq frames without ever firing
    their tickets; the watchdog must EXPIRE such orphans (counted as
    failures) instead of letting them hit the pending cap and wedge
    the standing pull forever — liveness past the heal."""
    import threading

    from riak_ensemble_tpu.obs.watchdog import AnomalyWatchdog

    class _Tk:
        def __init__(self):
            self.event = threading.Event()  # never fires

    class _Svc:
        pipeline_depth = 1
        _links = ()

    wd = AnomalyWatchdog(_Svc(), cadence=1)
    old = time.monotonic() - wd.PULL_EXPIRE_S - 1.0
    fresh = time.monotonic()
    wd._pending = [(None, [1], _Tk(), old),
                   (None, [2], _Tk(), fresh)]
    wd.evaluate()
    # the stale orphan dropped (a failure); the fresh one survives
    assert wd.pull_failures == 1
    assert len(wd._pending) == 1 and wd._pending[0][1] == [2]


# -- fleet trace export ------------------------------------------------------

def test_fleet_trace_export_per_host_tracks(tmp_path):
    """Aligned fleet timelines render as ONE merged Chrome trace with
    per-HOST tracks at their clock-aligned times (not the ordinal
    layout the single-store exporter uses), and the CLI round-trips
    a JSON file of them."""
    from tools import trace_export

    def tl(fid, base, lead_start, rep_start):
        return {
            "flush_id": fid, "schema": "retpu-fleet-timeline-v1",
            "base_s": base,
            "clock": {"h:1": {"offset_ms": 0.1, "bound_ms": 0.2,
                              "samples": 3}},
            "roles": {
                "leader": {"host": "me:0", "aligned": True,
                           "bound_ms": 0.0,
                           "spans": [["enqueue", lead_start, 0.001],
                                     ["repl_ack", lead_start + 0.001,
                                      0.002]]},
                "replica@h:1": {"host": "h:1", "aligned": True,
                                "bound_ms": 0.2,
                                "spans": [["apply", rep_start,
                                           0.0015]]},
            },
        }

    tls = [tl(7, 100.0, 0.0, 0.0005), tl(8, 100.01, 0.0, 0.0004)]
    events = trace_export.fleet_trace_events(tls)
    pids = {e["pid"] for e in events}
    assert pids == {"me:0", "h:1"}
    rep = [e for e in events if e["pid"] == "h:1"
           and e["args"]["flush_id"] == 7][0]
    # aligned placement: the replica span sits at its aligned start
    # (µs), inside the leader's flush window — not stacked ordinally
    assert abs(rep["ts"] - 500.0) < 1e-6
    assert rep["args"]["bound_ms"] == 0.2
    # the second flush's events shift by the base delta (10 ms)
    rep2 = [e for e in events if e["pid"] == "h:1"
            and e["args"]["flush_id"] == 8][0]
    assert abs(rep2["ts"] - (10_000.0 + 400.0)) < 1e-6
    # CLI round-trip
    src = tmp_path / "fleet.json"
    src.write_text(json.dumps(tls))
    out = tmp_path / "trace.json"
    assert trace_export.main(["--fleet-timelines", str(src),
                              "-o", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert len(doc["traceEvents"]) == len(events)
    # empty/missing-role inputs degrade to an empty event list
    assert trace_export.fleet_trace_events([{}]) == []


# -- standalone fleet surfaces ----------------------------------------------

def test_fleet_verbs_standalone_service_and_svcnode():
    """On a linkless service the fleet IS this host: the verbs answer
    the same shapes (one host, trivial clock) so a dashboard works
    before the group does — and they ride the svcnode wire."""
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
            fh = await client.fleet_health()
            assert fh["schema"] == "retpu-fleet-health-v1"
            (label,) = fh["hosts"]
            assert fh["hosts"][label]["schema"] == "retpu-health-v1"
            fm = await client.fleet_metrics()
            assert fm["schema"] == "retpu-fleet-metrics-v1"
            assert fm["hosts"][label]["retpu_flushes_total"] >= 1
            txt = await client.fleet_metrics("prometheus")
            assert f'host="{label}"' in txt
            assert txt.count("# TYPE retpu_flushes_total counter") == 1
            # a real fid aligns trivially; a bogus one is a
            # structured miss, and hostile fids are rejected
            st = await client.call("stats")
            assert st["flushes"] >= 1
            tl = await client.fleet_timeline(1)
            assert tl["schema"] == "retpu-fleet-timeline-v1"
            bad = await client.call("fleet", "timeline", "x")
            assert bad == ("error", "bad-request")
            bad2 = await client.call("fleet", "nope")
            assert bad2 == ("error", "bad-request")
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())


# -- two-process federation smoke -------------------------------------------

def _spawn_replica(n_ens, n_slots, tmp, procs):
    """One SUBPROCESS replica host (a genuinely separate span store —
    the federation smoke's whole point); registered in ``procs``
    before the ready-line parse so it can never leak."""
    child = textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_compilation_cache_dir",
                          {REPO!r} + "/.jax_cache")
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0)
        from riak_ensemble_tpu.parallel import repgroup
        repgroup.main(["--n-ens", "{n_ens}", "--group-size", "2",
                       "--n-slots", "{n_slots}", "--fast",
                       "--data-dir", {tmp!r} + "/r1"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", child],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True,
                         env=env)
    procs.append(p)
    line = p.stdout.readline()
    assert line, "replica subprocess died before its ready line"
    parts = dict(kv.split("=") for kv in line.split()[2:])
    import threading
    threading.Thread(target=lambda f=p.stdout: [None for _ in f],
                     daemon=True).start()
    return int(parts["repl"])


def test_two_process_federation_smoke(tmp_path, monkeypatch):
    """Acceptance (deterministic tier-1 shape): in-process leader +
    subprocess replica.  Fleet metrics/health merge both hosts, the
    fleet timeline joins the subprocess's replica spans onto the
    leader's axis within the estimated offset bound, and a triggered
    slow flush writes ONE correlated dump (schema v4) carrying the
    replica's matching span records — round-tripped through JSON."""
    import signal

    monkeypatch.setenv("RETPU_OBS_DUMP_DIR", str(tmp_path / "dumps"))
    procs = []
    svc = None
    try:
        repl_port = _spawn_replica(4, 8, str(tmp_path), procs)
        svc = repgroup.ReplicatedService(
            WallRuntime(), 4, 1, 8, group_size=2,
            peers=[("127.0.0.1", repl_port)], ack_timeout=60.0,
            max_ops_per_tick=4, config=fast_test_config(),
            data_dir=str(tmp_path / "leader"))
        repgroup.warmup_kernels(svc)
        assert svc.takeover()
        futs = [svc.kput_many(e, ["a", "b"], [b"1", b"2"])
                for e in range(4)]
        while any(svc.queues):
            svc.flush()
        assert svc.heartbeat()
        svc._drain_pending(block_all=True)
        assert all(f.done for f in futs)

        # fleet metrics: BOTH processes under host labels, one scrape
        fm = svc.fleet_metrics()
        assert len(fm["hosts"]) == 2, sorted(fm["hosts"])
        (link,) = svc._links
        assert link.label in fm["hosts"]
        assert fm["hosts"][link.label]["retpu_flushes_total"] >= 1
        txt = svc.fleet_metrics("prometheus")
        assert txt.count("# TYPE retpu_flushes_total counter") == 1
        assert f'host="{link.label}"' in txt
        # a valid exposition document: no sample may carry two
        # host labels (the clock gauges label their dimension
        # `peer` for exactly this reason)
        for ln in txt.splitlines():
            assert ln.count('host="') <= 1, ln
        fh = svc.fleet_health()
        assert len(fh["hosts"]) == 2
        rep_health = fh["hosts"][link.label]
        assert rep_health["schema"] == "retpu-health-v1"
        assert rep_health["group"]["leader"] is False
        # every fleet answer rides the restricted wire codec
        wire.encode(fm)
        wire.encode(fh)

        # clock: same machine, so truth is ~0 — the estimate must
        # honor its own bound (the NTP invariant, live)
        est = link.clock.section()
        assert est["samples"] >= 1
        assert abs(est["offset_ms"]) <= est["bound_ms"] + 0.5, est

        # aligned cross-host timeline: the subprocess's replica side
        # joins the leader's on ONE axis
        joined = None
        for fid in reversed(obs.SPANS.flush_ids()):
            tl = svc.fleet_timeline(fid)
            reps = [r for r in tl.get("roles", ())
                    if str(r).startswith("replica")]
            if reps and "leader" in tl["roles"]:
                joined = (tl, reps)
                break
        assert joined, "no flush joined leader + subprocess spans"
        tl, reps = joined
        wire.encode(tl)
        lead = tl["roles"]["leader"]
        assert lead["aligned"] and lead["host"] == \
            svc._fleet_self_label()
        for r in reps:
            side = tl["roles"][r]
            assert side["aligned"], tl
            assert side["host"] == link.label
            assert side["bound_ms"] > 0.0
            names = [n for n, _s, _d in side["spans"]]
            assert "apply" in names
            # spans are laid out on the shared axis: start offsets
            # are non-negative and within the flush's neighborhood
            assert all(s >= 0.0 for _n, s, _d in side["spans"])

        # correlated flight dump: a >5x-p50 flush pulls the
        # replica's matching records into ONE schema-v4 file
        svc.flight = obs.FlightRecorder(min_samples=8,
                                        refresh_every=2,
                                        min_dump_interval_s=0.0,
                                        name="svc")
        for i in range(10):
            fut = svc.kput(i % 4, "w", b"v%d" % i)
            while not fut.done:
                svc.flush()
        stall = max(6.0 * svc.flight._p50, 0.05)
        orig = svc._fetch_packed

        def slow_fetch(fl):
            time.sleep(stall)
            return orig(fl)

        monkeypatch.setattr(svc, "_fetch_packed", slow_fetch)
        fut = svc.kput(0, "w", b"slow")
        while not fut.done:
            svc.flush()
        monkeypatch.setattr(svc, "_fetch_packed", orig)
        assert svc.flight.anomalies >= 1
        snap = svc.flight.dumps[-1]
        assert snap["schema"] == DUMP_SCHEMA == "retpu-flight-dump-v4"
        with open(snap["path"]) as f:
            data = json.load(f)
        assert link.label in data["hosts"], sorted(data["hosts"])
        spans = data["hosts"][link.label].get("spans") or {}
        real = {int(f): tl for f, tl in spans.items()
                if isinstance(tl, dict) and not tl.get("miss")}
        assert real, "correlated dump carries no replica records"
        some = next(iter(real.values()))
        assert any(str(r).startswith("replica") for r in some)
        assert data["clock_offsets"][link.label]["samples"] >= 1
        assert isinstance(data["watchdog_findings"], list)
        # the structured misses distinguish lag from loss: fids the
        # replica never saw answer "unknown", never bare None
        for f, tl_ in spans.items():
            if isinstance(tl_, dict) and tl_.get("miss"):
                assert tl_["miss"] in ("evicted", "unknown")
    finally:
        if svc is not None:
            svc.stop()
        for p in procs:
            try:
                p.send_signal(signal.SIGKILL)
            except ProcessLookupError:
                pass


# -- live 3-host merge under injected skew (slow lane) -----------------------

@pytest.mark.slow
def test_three_host_merge_under_injected_rtt(tmp_path, monkeypatch):
    """Acceptance (live): a 3-host group under a 5 ms injected
    ONE-WAY RTT (the PR 9 fault plane as the skew generator).  One
    ``fleet_timeline(fid)`` call returns leader and replica spans on
    a single aligned axis with skew within the estimated offset
    bound; one Prometheus scrape carries all three hosts; a
    triggered slow flush produces ONE correlated dump with all
    hosts' records for its fids."""
    monkeypatch.setenv("RETPU_OBS_DUMP_DIR", str(tmp_path / "dumps"))
    servers = [repgroup.ReplicaServer(4, 3, 8,
                                      data_dir=str(tmp_path / f"r{i}"),
                                      config=fast_test_config())
               for i in (1, 2)]
    svc = repgroup.ReplicatedService(
        WallRuntime(), 4, 1, 8, group_size=3,
        peers=[("127.0.0.1", s.repl_port) for s in servers],
        ack_timeout=60.0, max_ops_per_tick=4,
        config=fast_test_config(), data_dir=str(tmp_path / "leader"))
    repgroup.warmup_kernels(svc)
    try:
        assert svc.takeover()
        # the skew generator: 5 ms one-way on every replica→leader
        # RESPONSE — the PR 9 injected-ack-RTT scenario, and the
        # WORST case for a midpoint estimator (a fully asymmetric
        # window: error -> one-way/2, still inside the bound by
        # construction).  Leader→request injection would land before
        # the ticket's wire re-stamp (queue dwell, by design), so
        # the return path is where a slow wire is visible.
        plan = faults.install(faults.FaultPlan())
        plan.set_rtt("*", faults.LOCAL, 5.0)
        try:
            futs = [svc.kput_many(e, ["a", "b"], [b"1", b"2"])
                    for e in range(4)]
            while any(svc.queues):
                svc.flush()
            assert svc.heartbeat()
            svc._drain_pending(block_all=True)
            assert all(f.done for f in futs)

            # one scrape, three hosts
            txt = svc.fleet_metrics("prometheus")
            hosts = {ln.split('host="')[1].split('"')[0]
                     for ln in txt.splitlines()
                     if ln.startswith("retpu_flushes_total{")}
            assert len(hosts) == 3, hosts

            # alignment within the estimated bound: same box, so the
            # TRUE offset is ~0 — the estimator's claim must cover it
            # even under the asymmetric 5 ms injection
            for link in svc._links:
                est = link.clock.section()
                assert est["samples"] >= 1
                assert abs(est["offset_ms"]) <= est["bound_ms"], est
                # the injected asymmetry really stretched the bound
                assert est["bound_ms"] >= 2.0, est

            joined = None
            for fid in reversed(obs.SPANS.flush_ids()):
                tl = svc.fleet_timeline(fid)
                reps = [r for r in tl.get("roles", ())
                        if str(r).startswith("replica")]
                if len(reps) == 2 and "leader" in tl["roles"]:
                    joined = tl
                    break
            assert joined, "no flush joined all three hosts"
            assert all(i["aligned"] for i in joined["roles"].values())

            # correlated dump under skew
            svc.flight = obs.FlightRecorder(min_samples=8,
                                            refresh_every=2,
                                            min_dump_interval_s=0.0,
                                            name="svc")
            for i in range(10):
                fut = svc.kput(i % 4, "w", b"v%d" % i)
                while not fut.done:
                    svc.flush()
            stall = max(6.0 * svc.flight._p50, 0.05)
            orig = svc._fetch_packed
            monkeypatch.setattr(
                svc, "_fetch_packed",
                lambda fl: (time.sleep(stall), orig(fl))[1])
            fut = svc.kput(0, "w", b"slow")
            while not fut.done:
                svc.flush()
            monkeypatch.setattr(svc, "_fetch_packed", orig)
            assert svc.flight.anomalies >= 1
            snap = svc.flight.dumps[-1]
            assert snap["schema"] == "retpu-flight-dump-v4"
            assert len(snap["hosts"]) == 2  # + the leader's own ring
            for label, sect in snap["hosts"].items():
                assert sect.get("spans"), (label, sect)
        finally:
            faults.clear()
    finally:
        svc.stop()
        for s in servers:
            s.stop()
