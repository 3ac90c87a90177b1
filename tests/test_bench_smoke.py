"""bench.py regression smoke (tier-1, fast): exercise the RMW rung
and the mixed runner in-process at tiny shapes, so a bench.py break
(signature drift, a renamed stats key, an op-kind mix that can't
commit) fails HERE instead of only at round time.

Deliberately small: sub-second measured windows over tiny [K, E]
planes — this pins that the runners RUN and report sane shapes, not
what the numbers are.
"""

import pytest

jax = pytest.importorskip("jax")

import bench  # noqa: E402


def test_rmw_rung_smoke():
    out = bench.run_rmw_service(n_ens=2, n_peers=3, n_slots=8, k=3,
                                seconds=0.05)
    assert out["rmw_device_ops_per_sec"] > 0
    assert out["rmw_host_ops_per_sec"] > 0
    assert out["rmw_device_speedup"] > 0
    # the device arm's contract: one flush per storm round, zero
    # conflicts; the host arm pays the read→CAS retry cycle
    assert out["rmw_device_flushes_per_round"] == 1.0
    assert out["rmw_device_conflicts"] == 0
    assert out["rmw_host_flushes_per_round"] >= 1.0


def test_mixed_rung_smoke():
    out = bench.run_mixed_service(n_ens=4, n_peers=3, n_slots=8, k=4,
                                  seconds=0.05)
    assert out["mixed_ops_per_sec"] > 0
    assert out["mixed_p99_ms"] >= out["mixed_p50_ms"] >= 0
    assert 0 < out["mixed_commit_fraction"] <= 1


def test_skewed_rung_smoke():
    """The compaction-regression tripwire: at the smoke shape the
    skewed rung's per-flush packed payload must stay under 25% of the
    full-width K·E layout's — a change that silently re-inflates the
    d2h transfer (compaction bypassed, active set mis-computed, pack
    layout regressed) fails tier-1 here.  warm/baseline off: the
    smoke pins shapes and the payload ratio, not the speedup."""
    out = bench.run_skewed_service(n_ens=128, n_peers=3, n_slots=8,
                                   k=8, seconds=0.05, warm=False,
                                   baseline=False)
    assert out["skewed_ops_per_sec"] > 0
    assert 0 < out["grid_occupancy"] < 0.25
    assert out["payload_bytes_per_flush"] > 0
    assert (out["payload_bytes_per_flush"]
            < 0.25 * out["payload_bytes_full_width_per_flush"]), out


def test_read_rung_smoke():
    """The read fast-path regression tripwire: on the uncontended
    read workload (disjoint read/write key sets) the fast-path
    hit-rate must exceed 90%, and the fastpath-off A/B arm must pass
    the fast-vs-device equivalence check (run_read_service asserts
    value equality internally and reports the count)."""
    out = bench.run_read_service(n_ens=32, n_peers=3, n_slots=8, k=8,
                                 seconds=0.2, warm=False)
    assert out["read_hit_rate"] > 0.9, out
    assert out["read_fastpath_hits"] > 0
    assert out["read_equivalence_ok"] is True
    assert out["read_equivalence_checked"] > 0
    # both arms measured, sane rates; the headline speedup is pinned
    # at round time (512-ens shape), not at smoke scale
    assert out["read_baseline_only_ops_per_sec"] > 0
    assert out["read_only_ops_per_sec"] > 0
    assert out["read_fastpath_speedup"] > 0


def test_mixed_tail_attribution_smoke():
    """The mixed rung names a dominant latency mark for every
    >5x-p50 batch (the tail-attribution satellite): keys present and
    internally consistent — cause counts sum to the tail count."""
    out = bench.run_mixed_service(n_ens=4, n_peers=3, n_slots=8, k=4,
                                  seconds=0.05)
    assert "mixed_tail_batches" in out
    causes = out["mixed_tail_causes"]
    assert sum(causes.values()) == out["mixed_tail_batches"]
    if out["mixed_tail_batches"]:
        assert out["mixed_tail_top_cause"] in causes
    else:
        assert out["mixed_tail_top_cause"] is None


def test_obs_overhead_smoke():
    """The obs-plane overhead tripwire: the headline pipelined loop
    with recording ON must stay within shouting distance of the
    RETPU_OBS=0 arm even at smoke shapes.  The tier-1 bound is
    deliberately loose (smoke samples are tiny batches on a noisy
    CI box — the measured per-batch delta is ~0); the 3% acceptance
    bound is pinned at round time on the real shape via the
    batch-granular interleaved-median A/B this same runner
    performs."""
    out = bench.run_obs_overhead(16, 3, 8, 4, seconds=0.4)
    assert out["obs_on_ops_per_sec"] > 0
    assert out["obs_off_ops_per_sec"] > 0
    assert (out["obs_on_ops_per_sec"]
            > 0.4 * out["obs_off_ops_per_sec"]), out


def test_op_trace_overhead_smoke():
    """The per-op SLO tracing A/B on the keyed rung: both arms run,
    the traced arm really recorded per-op samples, and tracing
    doesn't crater throughput even at smoke shapes (the 2% bound is
    pinned at round time on the real shape — smoke batches on a CI
    box measure noise, so the tier-1 bound stays loose)."""
    out = bench.run_op_trace_overhead(16, 3, 8, 4, seconds=0.4)
    assert out["op_trace_on_ops_per_sec"] > 0
    assert out["op_trace_off_ops_per_sec"] > 0
    assert out["op_trace_samples_recorded"] > 0, \
        "traced arm recorded no per-op samples"
    assert (out["op_trace_on_ops_per_sec"]
            > 0.4 * out["op_trace_off_ops_per_sec"]), out


def test_fleet_obs_overhead_smoke():
    """The fleet-federation A/B (ARCHITECTURE §11): both replicated
    arms run, the ON arm really posted obsq pulls and refreshed the
    per-link clock estimates, the OFF arm pulled nothing.  The 2%
    acceptance bound is pinned at round time on the real shape —
    smoke batches on a CI box measure noise, so the tier-1 bound
    stays loose."""
    out = bench.run_fleet_obs_overhead(0.4)
    assert out["fleet_obs_on_ops_per_sec"] > 0
    assert out["fleet_obs_off_ops_per_sec"] > 0
    assert out["fleet_obs_pulls"] > 0
    assert out["fleet_obs_watchdog_evals"] > 0
    assert out["fleet_obs_clock_samples"] > 0
    assert (out["fleet_obs_on_ops_per_sec"]
            > 0.4 * out["fleet_obs_off_ops_per_sec"]), out


def test_bench_trend_check():
    """The bench-trend ratchet rides tier-1 (the CI/tooling
    satellite): a missing/malformed BENCH round JSON, an empty
    trajectory, or an out-of-band same-box regression in the
    recorded rounds fails HERE instead of shipping an unreadable
    trajectory into the next round."""
    import os

    from tools import bench_trend

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = bench_trend.check(repo)
    assert report["rounds"] >= 4, report
    assert report["newest_ops_per_sec"] > 0
    # the trajectory table renders every recorded round
    rows = bench_trend.trajectory(bench_trend.load_rounds(repo))
    assert len(rows) == report["rounds"]
    assert all(isinstance(r["value"], (int, float)) for r in rows)


def test_bench_trend_check_rejects_malformed(tmp_path):
    """The ratchet is loud: a torn/headline-less round file raises,
    it does not read as an empty trajectory."""
    import json

    import pytest as _pytest

    from tools import bench_trend

    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path))  # no rounds at all
    (tmp_path / "BENCH_r01.json").write_text("{not json")
    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path))
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"n": 1, "parsed": {"no_value": True}}))
    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path))
    # a same-box regression below the band trips the ratchet
    box = {"cpu_count": 2, "jax": "j", "jaxlib": "jl",
           "platform": "p"}
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"n": 1, "parsed": {"value": 100.0, "box": box}}))
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"n": 2, "parsed": {"value": 10.0, "box": box}}))
    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path), tolerance=0.5)
    # within the band: ok, and the report names the comparison
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"n": 2, "parsed": {"value": 80.0, "box": box}}))
    rep = bench_trend.check(str(tmp_path), tolerance=0.5)
    assert rep["comparable_rounds"] == 1
    assert rep["best_same_box_ops_per_sec"] == 100.0


def test_recovery_rung_smoke():
    """The --stage recovery runner (ARCHITECTURE §15): checkpoint +
    WAL tail + restart really measure, the phases decompose the
    headline, and the tail write replayed from the WAL is served —
    the restart-to-serving number can never be a restore that lost
    the tail."""
    out = bench.run_recovery(0.2, smoke=True)
    assert out["recovery_ms"] > 0
    assert out["recovery_restore_ms"] > 0
    assert out["recovery_first_op_ms"] > 0
    assert out["recovery_ms"] >= out["recovery_restore_ms"]
    assert out["recovery_wal_records"] > 0, \
        "no WAL tail: the rung measured a checkpoint-only restart"
    assert out["recovery_shape"]["n_ens"] == 16


def test_bench_trend_polices_recovery_ms(tmp_path):
    """The recov_ms column's ratchet (ISSUE 15): lower-is-better, so
    a same-box restart-to-serving blowup past 1/tolerance x the best
    earlier round trips --check; rounds predating the stage neither
    ratchet nor fail."""
    import json

    import pytest as _pytest

    from tools import bench_trend

    box = {"cpu_count": 2, "jax": "j", "jaxlib": "jl",
           "platform": "p"}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box,
                    "recovery_ms": 500.0}}))
    # regression: 1200 ms vs best 500 ms at tolerance 0.5 (2x band)
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box,
                    "recovery_ms": 1200.0}}))
    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path), tolerance=0.5)
    # inside the band: ok, and the report names the comparison
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box,
                    "recovery_ms": 800.0}}))
    rep = bench_trend.check(str(tmp_path), tolerance=0.5)
    assert rep["best_same_box_recovery_ms"] == 500.0
    assert rep["newest_recovery_ms"] == 800.0
    # a newest round predating the stage (no recovery_ms) passes
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box}}))
    bench_trend.check(str(tmp_path), tolerance=0.5)
    # the column renders in the trajectory
    rows = bench_trend.trajectory(bench_trend.load_rounds(
        str(tmp_path)))
    assert rows[0]["recovery_ms"] == 500.0
    assert rows[2]["recovery_ms"] is None


def test_ingress_rung_smoke():
    """The --stage ingress runner (§16): a promoted 3-host group
    behind real subprocess proxies and a subprocess client herd.
    Both A/Bs produce nonzero rates at the tiny shape (the RATIOS
    are round-time claims — every smoke host shares one GIL), the
    spread arm really was served from replica mirrors, and the
    per-tier evidence rode ONE fleet pull off the leader."""
    out = bench.run_ingress(0.5, smoke=True)
    arms = out["ingress_arms"]
    assert set(arms) == {"1", "2"}, arms
    for arm in arms.values():
        assert arm["batches_per_sec"] > 0
        assert arm["read_ops_per_sec"] > 0
        assert arm["write_ops_per_sec"] > 0
        assert arm["errors"] == 0, arm
    assert out["ingress_x"] > 0
    assert out["ingress_write_hold"] is not None
    flw = out["follower_read_arms"]
    assert flw["leader_only"]["read_ops_per_sec"] > 0
    assert flw["followers"]["read_ops_per_sec"] > 0
    assert flw["followers"]["write_ops_per_sec"] == 0
    # the replicas' own counters prove mirror-served reads (scraped
    # through the single ("fleet", "metrics") pull)
    assert out["follower_reads_served_total"] > 0
    assert out["ingress_engine_p99_ms"] is not None
    assert out["ingress_shape"]["smoke"] is True


def test_bench_trend_polices_ingress_x(tmp_path):
    """The ingress_x column's ratchet (ISSUE 16): higher-is-better,
    so a same-box proxy-scaling collapse below tolerance x the best
    earlier round trips --check; rounds predating the stage neither
    ratchet nor fail."""
    import json

    import pytest as _pytest

    from tools import bench_trend

    box = {"cpu_count": 2, "jax": "j", "jaxlib": "jl",
           "platform": "p"}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box, "ingress_x": 2.0}}))
    # regression: 0.6x vs best 2.0x at tolerance 0.5 (half-of-best)
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box, "ingress_x": 0.6}}))
    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path), tolerance=0.5)
    # inside the band: ok, and the report names the comparison
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box, "ingress_x": 1.5}}))
    rep = bench_trend.check(str(tmp_path), tolerance=0.5)
    assert rep["best_same_box_ingress_x"] == 2.0
    assert rep["newest_ingress_x"] == 1.5
    # a newest round predating the stage (no ingress_x) passes
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box}}))
    bench_trend.check(str(tmp_path), tolerance=0.5)
    # the column renders in the trajectory
    rows = bench_trend.trajectory(bench_trend.load_rounds(
        str(tmp_path)))
    assert rows[0]["ingress_x"] == 2.0
    assert rows[2]["ingress_x"] is None


def test_commrepl_rung_smoke():
    """The --stage commrepl runner (§18): the contended-counter
    storm, comm lane vs ordered A/B on an in-process 3-host group.
    The smoke pins that both arms RUN, the comm arm really shipped
    merge entries and settled early acks, both arms converge to the
    identical final KV state, and the bytes-per-entry tripwire: on
    the hot-slot shape the coalesced merge stream must undercut the
    ordered delta stream per entry — a layout regression that
    re-inflates the merge section fails tier-1 here."""
    out = bench.run_commrepl(0.5, smoke=True)
    assert out["commrepl_ops_per_sec"] > 0
    assert out["commrepl_ack_p99_ms"] >= out["commrepl_ack_p50_ms"] \
        >= 0
    assert out["commrepl_merge_entries"] > 0, out
    assert out["commrepl_merge_cells"] > 0, out
    assert out["commrepl_early_acks"] > 0, out
    assert out["commrepl_coalesce_ratio"] >= 1.0
    assert out["rmw_comm_x"] > 0
    assert out["commrepl_convergence_ok"] is True, out
    assert (out["commrepl_bytes_per_entry"]
            < out["commrepl_ordered_bytes_per_entry"]), out
    assert out["commrepl_shape"]["smoke"] is True


def test_bench_trend_polices_rmw_comm_x(tmp_path):
    """The rmw_comm_x column's ratchet (ISSUE 18): higher-is-better,
    so a same-box comm-lane collapse below tolerance x the best
    earlier round trips --check; rounds predating the stage neither
    ratchet nor fail."""
    import json

    import pytest as _pytest

    from tools import bench_trend

    box = {"cpu_count": 2, "jax": "j", "jaxlib": "jl",
           "platform": "p"}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box, "rmw_comm_x": 2.0}}))
    # regression: 0.6x vs best 2.0x at tolerance 0.5 (half-of-best)
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box, "rmw_comm_x": 0.6}}))
    with _pytest.raises(bench_trend.TrendError):
        bench_trend.check(str(tmp_path), tolerance=0.5)
    # inside the band: ok, and the report names the comparison
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box, "rmw_comm_x": 1.5}}))
    rep = bench_trend.check(str(tmp_path), tolerance=0.5)
    assert rep["best_same_box_rmw_comm_x"] == 2.0
    assert rep["newest_rmw_comm_x"] == 1.5
    # a newest round predating the stage (no rmw_comm_x) passes
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"parsed": {"value": 100.0, "box": box}}))
    bench_trend.check(str(tmp_path), tolerance=0.5)
    # the column renders in the trajectory
    rows = bench_trend.trajectory(bench_trend.load_rounds(
        str(tmp_path)))
    assert rows[0]["rmw_comm_x"] == 2.0
    assert rows[2]["rmw_comm_x"] is None


def test_bench_smoke_trend_tripwire():
    """The current smoke rung vs the best same-fingerprint recorded
    point (BENCH_SMOKE_TREND.json), within a tolerance band: a
    host-path regression that halves the keyed rung on the SAME box
    fails tier-1 here.  A different box (no matching fingerprint)
    skips — cross-box comparisons are weather, not regressions."""
    import os

    from riak_ensemble_tpu.obs import box_fingerprint
    from tools import bench_trend

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shape = {"n_ens": 32, "n_peers": 3, "n_slots": 8, "k": 8}
    best = bench_trend.smoke_best(
        repo, bench_trend.fingerprint_key(box_fingerprint()), shape)
    if best is None:
        pytest.skip("no same-fingerprint smoke point recorded in "
                    "BENCH_SMOKE_TREND.json")
    rate = bench.run_keyed_batched_only(seconds=0.5, **shape)
    # 4x band: wide enough for loadavg weather on a shared box,
    # tight enough to catch a real host-path cliff
    assert rate > best / 4.0, (
        f"keyed smoke rung {rate:.0f} ops/s fell out of band vs the "
        f"recorded same-box best {best:.0f} (tolerance 4x)")


def test_native_resolve_ab_smoke():
    """The native-resolve A/B runner: both arms run, the native arm
    really takes the kernel (or the runner says the toolchain is
    absent), the breakdown carries the resolve components, and the
    WAL tempdir is cleaned up.  Ratio bounds stay loose — smoke
    shapes on a CI box measure noise; the real number is pinned at
    round time on the 512-ens rung."""
    out = bench.run_native_resolve_ab(16, 3, 8, 4, seconds=0.4)
    if not out.get("resolve_native_available"):
        pytest.skip("native resolve kernel unavailable")
    assert out["resolve_native_ops_per_sec"] > 0
    assert out["resolve_fallback_ops_per_sec"] > 0
    assert out["resolve_native_speedup"] > 0.4, out
    bd = out["resolve_native_latency_breakdown"]
    assert "resolve" in bd and "wal" in bd, bd
    assert "resolve_native" in bd, bd


def test_escale_point_smoke():
    """The E-scaling stage runner at a tiny shape: reports the
    pipelined and keyed-batched points with sane fields (the 1k/2k
    CPU points in the round JSON come from this exact runner)."""
    out = bench.run_escale_point(8, 3, 8, 4, seconds=0.2)
    assert out["n_ens"] == 8
    assert out["ops_per_sec"] > 0
    assert out["keyed_batched_ops_per_sec"] > 0
    assert out["p99_ms"] >= out["p50_ms"] >= 0


def test_obs_metric_names_documented():
    """The stats-schema ratchet (the test_env_knobs pattern applied
    to metric names): every metric a service registry can export must
    be listed in docs/ARCHITECTURE.md §11, and every `retpu_*` name
    the §11 tables document must still exist — so a new metric can't
    ship undocumented and a renamed one can't haunt the docs."""
    import os
    import re

    from riak_ensemble_tpu import obs
    from riak_ensemble_tpu.parallel.batched_host import (
        BatchedEnsembleService, WallRuntime)
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


def test_repgroup_rung_smoke():
    """The delta-replication regression tripwire (ARCHITECTURE §10):
    at the smoke shape (in-process replica hosts, skewed write set)
    the apply stream must (a) leave every replica lane bit-equal to
    the leader's — delta/full equivalence — and (b) ship under 25% of
    the full-plane figure per entry, so a change that silently
    re-inflates the stream (delta path bypassed, sections widened,
    fallback over-triggering) fails tier-1 here.  baseline off: the
    smoke pins the contract, not the speedup (that's round time's
    RETPU_REPL_DELTA=0 A/B arm)."""
    out = bench.run_repgroup(1.0, smoke=True, baseline=False)
    assert out["repgroup_ops_per_sec"] > 0
    assert out["repl_equivalence_ok"] is True, out
    assert out["repl_delta_entries"] > 0
    assert (out["repl_bytes_per_entry"]
            < 0.25 * out["repl_bytes_per_entry_full_plane"]), out


def test_faultsweep_cheap_arms_smoke():
    """Tier-1 tripwire for the faultsweep plumbing at the cheap end:
    the fsync-delay arm really delays the WAL barrier (counted,
    slower than baseline within noise), and the noisy-tenant arm
    attributes hot vs quiet ops with a real quiet p99.  The RTT
    depth-sweep arms spin up replica groups (seconds each) — they run
    in the slow lane and at round time."""
    from riak_ensemble_tpu import faults

    base = bench._faultsweep_fsync_arm(8, 8, 8, 0.3, 0.0)
    slow = bench._faultsweep_fsync_arm(8, 8, 8, 0.3, 3.0)
    assert faults.active_plan() is None  # the arms clean up
    assert base["ops_per_sec"] > 0 and slow["ops_per_sec"] > 0
    assert base["fsync_delays"] == 0
    assert slow["fsync_delays"] > 0, \
        "fsync arm ran but the barrier was never delayed"
    nt = bench._noisy_tenant_arm(16, 8, 8, 0.3, compact=True)
    assert nt["hot_ops"] > nt["quiet_ops"] > 0
    assert nt["quiet_p99_ms"] is not None
    assert nt["ops_per_sec"] > 0


@pytest.mark.slow
def test_faultsweep_smoke():
    """The full fault-injection rung runner (ARCHITECTURE §13): both
    RTT arms and depths run, the injected-delay counters prove the
    fault plane really fired inside the measured loops, the fsync arm
    shows a real (bounded-from-below) slowdown, the noisy-tenant A/B
    reports both compaction arms, and the fault config is embedded.
    Ratio bounds stay loose: smoke shapes on a CI box measure noise —
    the depth-2-wins-under-RTT acceptance is pinned at round time on
    the full shape."""
    from riak_ensemble_tpu import faults

    out = bench.run_faultsweep(0.4, smoke=True)
    fs = out["faultsweep"]
    assert faults.active_plan() is None  # the runner cleans up
    sweep = fs["rtt_sweep"]
    assert [p["rtt_ms"] for p in sweep] == [0.0, 1.0]
    for p in sweep:
        assert p["depth1_ops_per_sec"] > 0
        assert p["depth2_ops_per_sec"] > 0
        assert p["depth2_speedup"] > 0.4, p
    assert fs["fsync"]["baseline_ops_per_sec"] > 0
    assert fs["fsync"]["injected_fsync_delays"] > 0, \
        "fsync arm ran but the barrier was never delayed"
    assert fs["fsync"]["slowdown"] > 0.8, fs["fsync"]
    nt = fs["noisy_tenant"]
    assert nt["hot_ops"] > nt["quiet_ops"] > 0
    assert nt["quiet_p99_ms_compact"] is not None
    assert nt["quiet_p99_ms_nocompact"] is not None
    assert nt["quiet_p99_ratio"] > 0
    assert fs["fault_config"]["fsync_ms"] == 2.0
    assert out["faultsweep_depth2_speedup"] is not None


def test_autotune_guard_arm_smoke():
    """Tier-1 tripwire for the controller's tenant-guard plumbing at
    the cheap end (ARCHITECTURE §14): the guarded noisy-tenant arm
    must journal a real admission decision against the hot tenant
    and report both tenants' latencies.  The RTT convergence arms
    spin up replica groups (seconds each) — slow lane + round time."""
    nt = bench._noisy_tenant_arm(16, 8, 8, 0.3, compact=True,
                                 guard=True)
    assert nt["ops_per_sec"] > 0
    assert nt["hot_ops"] > nt["quiet_ops"] > 0
    assert nt["guard_decisions"], "guard armed but never decided"
    ev = nt["guard_decisions"][0]
    assert ev["actuator"] == "tenant_guard"
    assert ev["cause"] == "tenant_ops_share"
    assert ev["observed"] >= 0.7
    assert nt["throttled_rows"].get("hot"), nt["throttled_rows"]


@pytest.mark.slow
def test_autotune_smoke():
    """The full autotune A/B runner (ARCHITECTURE §14): static and
    controller arms run at both smoke RTT points, the journal
    reconstruction holds (asserted INSIDE the runner per arm), and
    the guard rung reports both arms.  Ratio bounds stay loose —
    smoke shapes on a CI box measure noise; the within-5%-of-best-
    static acceptance is pinned at round time on the full shape."""
    from riak_ensemble_tpu import faults

    out = bench.run_autotune(0.4, smoke=True)
    assert faults.active_plan() is None  # the arms clean up
    at = out["autotune"]
    assert [p["rtt_ms"] for p in at["points"]] == [0.0, 2.0]
    for p in at["points"]:
        assert p["controller_ops_per_sec"] > 0
        assert all(v > 0 for v in p["static_ops_per_sec"].values())
        assert p["journal_reconstructed"] is True
        assert p["vs_best_static"] > 0.3, p
    assert out["autotune_vs_best_static"] > 0.3
    tg = at["tenant_guard"]
    assert tg["guard_decisions"]
    assert tg["quiet_p99_ms_guarded"] > 0
    assert tg["quiet_p99_ms_unguarded"] > 0
