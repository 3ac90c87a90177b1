"""The obs-actuated runtime controller (docs/ARCHITECTURE.md §14).

Covers: the decision journal (bounded ring, replay reconstruction),
the ack-RTT depth/window tuner under deterministic injected RTT
changes on virtual time (steps up at 5 ms, back down on heal,
hysteresis prevents flapping, and journal/gauges/health agree on
EVERY transition), the tenant-admission token bucket (both the
guard's install/release decisions and the service-side flush
admission), the chaos-gate schedule on a virtual clock, the runtime
knob setters, the flight-recorder windowed-p50 re-arm fix, the
registry ``remove_labeled`` recycle fix, and the acceptance
equivalence: ``RETPU_AUTOTUNE=0`` is bit-identical to a
controller-armed service whose actuation thresholds are unreachable.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from riak_ensemble_tpu import faults, obs  # noqa: E402
from riak_ensemble_tpu.obs import controller as ctl  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)


# -- decision journal ---------------------------------------------------------

def test_journal_ring_bounded_and_replay():
    j = ctl.DecisionJournal(capacity=4)
    for i in range(10):
        j.note("ack_rtt", "repl_ack_ms_p50", float(i),
               knob="pipeline_depth", old=i, new=i + 1, flush_id=i)
    assert j.total == 10
    evs = j.snapshot()
    assert len(evs) == 4  # ring bound
    assert [e["seq"] for e in evs] == [7, 8, 9, 10]  # seq survives
    # replay over the FULL history reconstructs the final knob
    full = ctl.DecisionJournal()
    for i in range(3):
        full.note("ack_rtt", "repl_ack_ms_p50", 5.0,
                  knob="pipeline_depth", old=1 + i, new=2 + i)
    state = ctl.replay(full.snapshot(), {"pipeline_depth": 1})
    assert state == {"pipeline_depth": 4}


def test_journal_replay_mismatch_is_loud():
    j = ctl.DecisionJournal()
    j.note("ack_rtt", "repl_ack_ms_p50", 5.0,
           knob="pipeline_depth", old=2, new=3)
    with pytest.raises(ValueError, match="replay mismatch"):
        ctl.replay(j.snapshot(), {"pipeline_depth": 1})


# -- the ack-RTT tuner on deterministic synthetic spans -----------------------

class _StubGroup:
    """Duck-typed replicated leader: just the surface the controller
    actuates and reads."""

    class _Link:
        label = "stub:1"

    def __init__(self):
        self.pipeline_depth = 1
        self.repl_window = 1
        self.max_k = 8
        self.is_leader = True
        self._links = [self._Link()]
        self.tenant_ops = np.zeros((4,), np.int64)

    def tenant_label(self, e):
        return f"ens{e}"

    def set_pipeline_depth(self, d):
        old, self.pipeline_depth = self.pipeline_depth, max(1, int(d))
        return old

    def set_repl_window(self, w):
        old, self.repl_window = self.repl_window, max(1, int(w))
        return old

    def set_admission_caps(self, caps):
        self.caps = caps


def _controller(stub) -> ctl.RuntimeController:
    c = ctl.RuntimeController(stub)
    c.enabled = True
    c.cadence = 4
    return c


def _drive_window(c, ack_ms):
    """One cadence window of flushes whose repl_ack spans measure
    ``ack_ms`` — deterministic synthetic samples in the real span
    store, exactly where the live leader records them."""
    for _ in range(c.cadence):
        fid = obs.next_flush_id()
        obs.SPANS.record(fid, "leader", [("repl_ack", ack_ms / 1e3)])
        c.tick(fid)


def _check_surfaces_agree(c, stub):
    """Journal, gauges and health must tell the same story after
    every transition."""
    fam = c.collect()
    assert fam["retpu_autotune_pipeline_depth"]["values"][None] \
        == stub.pipeline_depth
    assert fam["retpu_autotune_repl_window"]["values"][None] \
        == stub.repl_window
    assert fam["retpu_autotune_decisions_total"]["values"][None] \
        == c.journal.total
    h = c.health_section()
    assert h["pipeline_depth"] == stub.pipeline_depth
    assert h["repl_window"] == stub.repl_window
    assert h["decisions"] == c.journal.total
    replayed = ctl.replay(
        [e for e in c.journal.snapshot()
         if e.get("knob") in ("pipeline_depth", "repl_window")],
        {"pipeline_depth": stub._base_depth,
         "repl_window": stub._base_window})
    assert replayed == {"pipeline_depth": stub.pipeline_depth,
                        "repl_window": stub.repl_window}


def test_tuner_steps_up_at_5ms_down_on_heal():
    stub = _StubGroup()
    c = _controller(stub)
    stub._base_depth, stub._base_window = 1, 1
    # window 1: 5 ms injected ack RTT -> one bounded step up
    _drive_window(c, 5.0)
    assert stub.pipeline_depth == 2
    assert stub.repl_window == 4  # widened to 2 x depth
    assert c.journal.total == 2  # depth + window, each journaled
    _check_surfaces_agree(c, stub)
    last = c.journal.snapshot()[-1]
    assert last["cause"] == "repl_ack_ms_p50"
    assert last["observed"] == pytest.approx(5.0)
    # the step is BOUNDED: 5 ms again moves one more unit, not a jump
    _drive_window(c, 5.0)
    assert stub.pipeline_depth == 3
    _check_surfaces_agree(c, stub)
    # heal: sub-threshold RTT walks back down toward the baseline,
    # one bounded step per window, window restored at base depth
    _drive_window(c, 0.3)
    assert stub.pipeline_depth == 2
    _drive_window(c, 0.3)
    assert stub.pipeline_depth == 1
    assert stub.repl_window == 1
    _check_surfaces_agree(c, stub)
    # fully healed: further quiet windows change nothing (never
    # below the operator's baseline)
    _drive_window(c, 0.3)
    assert stub.pipeline_depth == 1
    assert c.journal.snapshot()[-1]["direction"] == "down"


def test_tuner_hysteresis_prevents_flapping():
    stub = _StubGroup()
    c = _controller(stub)
    stub._base_depth, stub._base_window = 1, 1
    _drive_window(c, 5.0)  # up to depth 2 (heal reference = 5 ms)
    n = c.journal.total
    # the dead band: p50 hovering between the heal condition
    # (max(down_ms 1.0, 0.5 x the 5 ms that stepped up) = 2.5) and
    # the up threshold (4.0) must HOLD the knob, not flap it
    for ms in (3.0, 3.5, 2.8, 3.9, 2.6):
        _drive_window(c, ms)
        assert stub.pipeline_depth == 2, f"flapped at {ms} ms"
    assert c.journal.total == n, "hold windows journaled decisions"
    _check_surfaces_agree(c, stub)
    # the RELATIVE heal clause: 2 ms is above down_ms (1.0) but at
    # 40% of the up-step's 5 ms reference — the ack floor (replica
    # apply cost) never reaches an absolute threshold on every box
    _drive_window(c, 2.0)
    assert stub.pipeline_depth == 1
    _check_surfaces_agree(c, stub)


def test_tuner_needs_samples_and_leadership():
    stub = _StubGroup()
    c = _controller(stub)
    stub._base_depth, stub._base_window = 1, 1
    # a quiet window (too few ack samples) is not evidence
    fid = obs.next_flush_id()
    obs.SPANS.record(fid, "leader", [("repl_ack", 0.005)])
    for i in range(c.cadence):
        c.tick(fid if i == 0 else 0)
    assert stub.pipeline_depth == 1 and c.journal.total == 0
    # a deposed lane must not grow in-flight state
    stub.is_leader = False
    _drive_window(c, 5.0)
    assert stub.pipeline_depth == 1 and c.journal.total == 0


# -- the tenant guard ---------------------------------------------------------

def test_tenant_guard_install_release_with_hysteresis():
    stub = _StubGroup()
    c = _controller(stub)
    c.guard.min_ops = 10
    stub.caps = "unset"
    # hot row 0 at 90% share -> capped
    stub.tenant_ops = np.array([90, 5, 5, 0], np.int64)
    for _ in range(c.cadence):
        c.tick(obs.next_flush_id())
    assert stub.caps == {0: stub.max_k // 2}
    assert c.guard.throttled == {"ens0": [0]}
    ev = c.journal.snapshot()[-1]
    assert ev["actuator"] == "tenant_guard"
    assert ev["cause"] == "tenant_ops_share"
    assert ev["observed"] == pytest.approx(0.9)
    assert c.collect()[
        "retpu_autotune_tenant_throttled_rows"]["values"][None] == 1
    # mid-band share (between low 0.45 and high 0.7): HOLD
    stub.tenant_ops += np.array([60, 20, 20, 0], np.int64)
    for _ in range(c.cadence):
        c.tick(obs.next_flush_id())
    assert c.guard.throttled, "guard released inside the dead band"
    # share collapses below the low threshold -> released
    stub.tenant_ops += np.array([10, 45, 45, 0], np.int64)
    for _ in range(c.cadence):
        c.tick(obs.next_flush_id())
    assert c.guard.throttled == {}
    assert stub.caps is None
    assert c.journal.snapshot()[-1]["new"] is None  # the release


def test_admission_token_bucket_caps_flush_take():
    """The service-side half: a capped row's queue stops forcing the
    flush depth to its own max — quiet rows flush at their own small
    k while the hot backlog drains at the bucket rate."""
    svc = BatchedEnsembleService(WallRuntime(), 4, 1, 16, tick=None,
                                 max_ops_per_tick=8)
    try:
        svc.set_admission_caps({0: 2})
        futs = [svc.kput_many(0, [f"k{i}" for i in range(8)],
                              [b"v"] * 8),
                svc.kput(1, "q", b"qv")]
        svc.flush()
        # burst (2x cap) admits 4 of the hot row's 8 rounds; the
        # quiet row's single op rides the same flush
        assert svc._queue_rounds[0] == 4
        assert futs[1].done
        flushes = 1
        while any(svc.queues):
            svc.flush()
            flushes += 1
            assert flushes < 20
        assert all(f.done for f in futs)
        assert flushes >= 3  # bucket-rate drain, not one mega-flush
        res = futs[0].value
        assert all(r[0] == "ok" for r in res)
        # clearing the caps restores the uncapped single-flush take
        svc.set_admission_caps(None)
        f2 = svc.kput_many(0, [f"n{i}" for i in range(8)],
                           [b"w"] * 8)
        svc.flush()
        assert f2.done
    finally:
        svc.stop()


def test_guard_caps_a_hot_tenant_on_a_live_service():
    """The two halves joined: on a live service whose `hot` tenant
    sends nearly all the traffic, the armed controller journals a
    `tenant_guard` decision on the observed share and throttles that
    tenant's rows, and the quiet tenant's ops still commit."""
    svc = BatchedEnsembleService(WallRuntime(), 8, 1, 8, tick=None,
                                 max_ops_per_tick=8)
    try:
        svc.set_autotune(True)
        svc.controller.cadence = 4
        svc.controller.guard.min_ops = 16
        for e in range(4):
            svc.set_tenant_label(e, "hot")
        svc.set_tenant_label(4, "quiet")
        keys = [f"k{j}" for j in range(4)]
        for _ in range(12):
            futs = [svc.kput_many(e, keys, [b"v"] * 4)
                    for e in range(4)]
            futs.append(svc.kput(4, "q", b"qv"))
            while not all(f.done for f in futs):
                svc.flush()
            assert futs[-1].value[0] == "ok"
        evs = [ev for ev in svc.controller.journal.snapshot()
               if ev["actuator"] == "tenant_guard"]
        assert evs, "guard armed but never decided"
        assert evs[0]["cause"] == "tenant_ops_share"
        assert evs[0]["observed"] >= 0.7
        assert svc.controller.guard.throttled.get("hot")
    finally:
        svc.stop()


# -- the chaos gate -----------------------------------------------------------

def test_soak_schedule_virtual_clock():
    now = [0.0]
    ran = []

    def runner(target):
        ran.append(target)
        return {"ok": len(ran) != 2, "detect_s": 0.1}

    s = faults.SoakSchedule(10.0, runner=runner, clock=lambda: now[0])
    assert not s.due()
    assert s.maybe_run("svc") is None
    now[0] = 10.5
    r = s.maybe_run("svc")
    assert r is not None and r["ok"] and ran == ["svc"]
    assert s.maybe_run("svc") is None  # re-armed, not due yet
    now[0] = 21.0
    r = s.maybe_run("svc")
    assert r is not None and not r["ok"]
    assert (s.runs, s.failures) == (2, 1)

    def bad(_t):
        raise RuntimeError("soak crashed")

    s2 = faults.SoakSchedule(1.0, runner=bad, clock=lambda: now[0])
    now[0] += 2.0
    r = s2.maybe_run("svc")
    assert r is not None and not r["ok"] and "error" in r
    assert s2.failures == 1  # a crashing soak is a verdict, not a
    # serving-loop crash
    assert faults.SoakSchedule(0.0).due() is False  # disarmed


def test_wedge_soak_restores_plan_and_bounds_detection():
    class _Link:
        IO_TIMEOUT = 1.0
        label = "peer:9"

    class _Svc:
        _links = [_Link()]

        def __init__(self):
            self.beats = []

        def heartbeat(self):
            # first beat runs under the blackhole: quorum lost
            self.beats.append(faults.plan())
            return len(self.beats) != 1

    prev = faults.install(faults.FaultPlan())
    try:
        svc = _Svc()
        r = faults.wedge_soak(svc)
        assert r["ok"], r
        assert r["bound_s"] == pytest.approx(2.0)
        assert r["detect_s"] <= r["bound_s"]
        # the blackhole beat saw the SILENT soak plan; the heal beat
        # ran with the outer plan restored
        assert svc.beats[0].silent is True
        assert svc.beats[0] is not prev
        assert svc.beats[1] is prev
        assert faults.plan() is prev
    finally:
        faults.clear()
    # a lane without links has no ack path to wedge: skipped, ok
    class _NoLinks:
        _links = []
    assert faults.wedge_soak(_NoLinks())["ok"] is True


def test_controller_journals_soak_results():
    stub = _StubGroup()
    c = _controller(stub)
    now = [100.0]
    c.arm_soak(5.0, runner=lambda t: {"ok": True, "detect_s": 0.2},
               clock=lambda: now[0])
    now[0] = 106.0
    for _ in range(c.cadence):
        c.tick(obs.next_flush_id())
    evs = [e for e in c.journal.snapshot()
           if e["actuator"] == "chaos"]
    assert len(evs) == 1 and evs[0]["ok"] is True
    assert evs[0]["cause"] == "wedge_soak_detect_s"
    assert c.collect()[
        "retpu_autotune_soak_runs_total"]["values"][None] == 1


@pytest.mark.slow
def test_live_wedge_soak_on_replicated_group(tmp_path):
    """The standing chaos gate on a REAL 2-host group: a silent ack
    blackhole (the RETPU_FAULT_SILENT=1 mode) must be OBSERVED as a
    lost quorum within 2 x IO_TIMEOUT, the group must heal, and the
    controller must journal the verdict."""
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.parallel import repgroup

    server = repgroup.ReplicaServer(4, 2, 8,
                                    data_dir=str(tmp_path / "r1"),
                                    config=fast_test_config())
    svc = repgroup.ReplicatedService(
        WallRuntime(), 4, 1, 8, group_size=2,
        peers=[("127.0.0.1", server.repl_port)],
        ack_timeout=3.0, max_ops_per_tick=4,
        config=fast_test_config(), data_dir=str(tmp_path / "leader"))
    try:
        repgroup.warmup_kernels(svc)
        assert svc.takeover()
        f = svc.kput(0, "k", b"v")
        while not f.done:
            svc.flush()
        r = faults.wedge_soak(svc)
        assert r["ok"], r
        assert r["quorum_ok_under_blackhole"] is False
        assert r["detect_s"] <= r["bound_s"]
        assert r["healed_quorum_ok"] is True
        assert faults.plan() is None  # outer (no-plan) state restored
        # the controller journals the same soak when scheduled
        svc.set_autotune(True)
        now = [0.0]
        svc.controller.arm_soak(1.0, clock=lambda: now[0])
        now[0] = 2.0
        decisions = svc.controller.evaluate()
        chaos = [e for e in decisions if e["actuator"] == "chaos"]
        assert len(chaos) == 1 and chaos[0]["ok"] is True
        # and the group still serves
        f2 = svc.kput(1, "k2", b"v2")
        while not f2.done:
            svc.flush()
        assert f2.value[0] == "ok"
    finally:
        svc.stop()
        server.stop()


# -- knob setters on a live service ------------------------------------------

def test_set_pipeline_depth_safe_mid_stream():
    svc = BatchedEnsembleService(WallRuntime(), 4, 1, 8, tick=None,
                                 max_ops_per_tick=4)
    try:
        futs = [svc.kput(e, "a", b"1") for e in range(4)]
        assert svc.set_pipeline_depth(2) == 1
        futs += [svc.kput(e, "b", b"2") for e in range(4)]
        while any(svc.queues):
            svc.flush()
        svc.flush()  # settle the tail of the deeper pipeline
        assert all(f.done for f in futs)
        assert svc.set_pipeline_depth(1) == 2
        assert not svc._inflight_launches  # drained at the change
        got = svc.kget(0, "b")
        while not got.done:
            svc.flush()
        assert got.value == ("ok", b"2")
    finally:
        svc.stop()


# -- flight recorder: windowed p50 re-arms after a load shift -----------------

def test_flightrec_windowed_p50_rearms_after_spike():
    fr = obs.FlightRecorder(window=8, min_samples=8,
                            trigger_ratio=5.0,
                            min_dump_interval_s=0.0)
    for i in range(8):
        fr.record({"flush_id": i, "total": 0.01})
    # a sustained slow phase, then back to quiet: once the spike
    # slides out of the window the baseline must decay with it
    for i in range(8):
        fr.record({"flush_id": 100 + i, "total": 0.5})
    for i in range(8):
        fr.record({"flush_id": 200 + i, "total": 0.01})
    assert fr._p50 == pytest.approx(0.01)  # fully decayed
    # ... so a 5x-of-quiet flush triggers at the RIGHT threshold
    snap = fr.record({"flush_id": 300, "total": 0.06})
    assert snap is not None, "post-spike anomaly missed: stale p50"
    assert snap["trigger"]["rolling_p50_s"] == pytest.approx(0.01)
    assert "controller_decisions" in snap  # dump schema v3 section


# -- registry label recycle ---------------------------------------------------

def test_remove_labeled_drops_series():
    reg = obs.MetricsRegistry()
    h = reg.histogram("retpu_test_ms")
    c = reg.counter("retpu_test_total")
    h.labels("tenantA").record(1.0)
    c.labels("tenantA").inc()
    c.labels("tenantB").inc()
    assert reg.remove_labeled("tenantA") == 2
    snap = reg.snapshot()
    assert "tenantA" not in snap["retpu_test_ms"].get("by_label", {})
    assert snap["retpu_test_total"] == {"tenantB": 1}
    assert "tenantA" not in reg.render_prometheus()
    assert reg.remove_labeled("tenantA") == 0  # idempotent


def test_row_recycle_drops_tenant_labeled_series():
    svc = BatchedEnsembleService(WallRuntime(), 4, 1, 8, tick=None,
                                 max_ops_per_tick=4, dynamic=True)
    try:
        row = svc.create_ensemble("acme")
        assert row is not None
        f = svc.kput(row, "k", b"v")
        while not f.done:
            svc.flush()
        # a labeled series recorded under the tenant's label (the
        # registry's label dimension exists for exactly this)
        svc.obs_registry.histogram("retpu_op_latency_ms") \
            .labels("acme").record(1.0)
        assert svc.destroy_ensemble("acme")
        snap = svc.obs_registry.snapshot()
        assert "acme" not in snap["retpu_op_latency_ms"] \
            .get("by_label", {}), "recycled tenant's series leaked"
        assert "acme" not in snap.get("retpu_tenant_ops_total", {})
    finally:
        svc.stop()


def test_multi_row_tenant_series_survive_sibling_recycle():
    """A tenant spanning several ensemble rows is ONE tenant in every
    export: recycling one of its rows must not reset the survivors'
    labeled series — only the LAST row's recycle drops them."""
    svc = BatchedEnsembleService(WallRuntime(), 4, 1, 8, tick=None,
                                 max_ops_per_tick=4, dynamic=True)
    try:
        r1 = svc.create_ensemble("t1")
        r2 = svc.create_ensemble("t2")
        svc.set_tenant_label(r1, "acme")
        svc.set_tenant_label(r2, "acme")
        svc.obs_registry.histogram("retpu_op_latency_ms") \
            .labels("acme").record(1.0)
        assert svc.destroy_ensemble("t1")
        snap = svc.obs_registry.snapshot()
        assert "acme" in snap["retpu_op_latency_ms"] \
            .get("by_label", {}), \
            "live multi-row tenant's series dropped on sibling recycle"
        assert svc.destroy_ensemble("t2")  # the last 'acme' row
        snap = svc.obs_registry.snapshot()
        assert "acme" not in snap["retpu_op_latency_ms"] \
            .get("by_label", {})
    finally:
        svc.stop()


def test_arm_time_baseline_recaptured_on_set_autotune():
    """The tuner's heal floor is the ARM-time configuration: knobs an
    operator moved between construction and arming must become the
    new baseline, never be walked back down to the constructed one."""
    svc = BatchedEnsembleService(WallRuntime(), 4, 1, 8, tick=None,
                                 max_ops_per_tick=4)
    try:
        assert svc._autotune_base_depth == 1
        svc.set_pipeline_depth(3)
        svc.set_autotune(True)
        assert svc._autotune_base_depth == 3
        # a fully-healed window must NOT step below the armed floor
        tuner = ctl.AckRttTuner()
        j = ctl.DecisionJournal()
        assert tuner.evaluate(svc, [0.0001] * 8, j, flush_id=1) == []
        assert svc.pipeline_depth == 3
        svc.set_autotune(False)
    finally:
        svc.stop()


# -- acceptance: RETPU_AUTOTUNE=0 is bit-identical ---------------------------

def _controller_equiv_run(tmp_path, tag, armed):
    """One arm of the equivalence sweep: a mixed keyed stream on a
    fresh service; returns (results, mirror slabs)."""
    env_before = os.environ.get("RETPU_AUTOTUNE")
    os.environ["RETPU_AUTOTUNE"] = "1" if armed else "0"
    try:
        svc = BatchedEnsembleService(
            WallRuntime(), 8, 1, 16, tick=None, max_ops_per_tick=8,
            data_dir=str(tmp_path / tag))
        if armed:
            # armed, but every actuation threshold unreachable: the
            # controller runs its cadence and decides NOTHING — the
            # acceptance arm whose behavior must be bit-identical
            svc.controller.cadence = 2
            svc.controller.tuner.up_ms = 1e12
            svc.controller.tuner.down_ms = -1.0
            svc.controller.guard.share_high = 2.0
            svc.controller.guard.share_low = 1.5
    finally:
        if env_before is None:
            os.environ.pop("RETPU_AUTOTUNE", None)
        else:
            os.environ["RETPU_AUTOTUNE"] = env_before
    results = []
    try:
        futs = []
        for e in range(8):
            futs.append(svc.kput_many(
                e, [f"k{j}" for j in range(6)],
                [b"v%d" % j for j in range(6)]))
        while any(svc.queues):
            svc.flush()
        from riak_ensemble_tpu import funref
        futs.append(svc.kmodify(0, "ctr", funref.ref("rmw:add", 7),
                                0))
        futs.append(svc.kdelete(1, "k3"))
        futs.append(svc.kget_many(2, [f"k{j}" for j in range(6)]))
        while any(svc.queues) or not all(f.done for f in futs):
            svc.flush()
        results = [f.value for f in futs]
        slabs = (svc._slot_vsn_np.copy(), svc._slot_vsn_ok.copy(),
                 svc._inline_np.copy())
        if armed:
            assert svc.controller.evals > 0, \
                "armed arm never evaluated — the sweep proved nothing"
            assert svc.controller.journal.total == 0
        return results, slabs
    finally:
        svc.stop()


def test_autotune_off_bit_identical_to_unreachable_thresholds(
        tmp_path):
    """The §14 oracle discipline: the controller-armed service with
    unreachable actuation thresholds produces bit-identical results
    and mirror slabs to RETPU_AUTOTUNE=0 — so the off arm (the
    default for one release) is provably the same service."""
    res_off, slabs_off = _controller_equiv_run(tmp_path, "off", False)
    res_on, slabs_on = _controller_equiv_run(tmp_path, "on", True)
    assert res_off == res_on
    for a, b in zip(slabs_off, slabs_on):
        assert np.array_equal(a, b), "mirror slabs diverged"
