"""Chrome-trace export of span timelines + controller decisions
(tools/trace_export.py, docs/ARCHITECTURE.md §14).

Unit round trip on a canned store, the documented timeline semantics
(stamped records laid out by their start stamps; records without
stamps sequential per flush, ordinal across flushes), the flight-dump
CLI path, and the acceptance round trip: a timeline RECORDED on a
live 3-host replication group (leader + two in-process replica
lanes) exports to a JSON every span of which matches the store."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import pytest  # noqa: E402

from riak_ensemble_tpu import obs  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)
from tools import trace_export  # noqa: E402


def _events_by_tid(events):
    out = {}
    for ev in events:
        out.setdefault(ev["tid"], []).append(ev)
    return out


def test_unit_round_trip_canned_store(tmp_path):
    store = obs.SpanStore()
    store.record(7, "leader", [("queue_wait", 0.001),
                               ("device_d2h", 0.004),
                               ("repl_ack", 0.002)], k=4)
    store.record(7, "replica@h:1", [("validate", 0.0005),
                                    ("apply", 0.003)], kind="delta")
    store.record(9, "leader", [("queue_wait", 0.002)])
    decisions = [{"seq": 1, "flush_id": 7, "actuator": "ack_rtt",
                  "cause": "repl_ack_ms_p50", "observed": 5.0,
                  "knob": "pipeline_depth", "old": 1, "new": 2}]
    path = str(tmp_path / "trace.json")
    doc = trace_export.export(path, [7, 9, 12345], decisions,
                              store=store)
    with open(path, encoding="utf-8") as fh:
        loaded = json.load(fh)
    assert loaded == doc  # the written JSON round-trips exactly
    evs = loaded["traceEvents"]
    by_tid = _events_by_tid(evs)
    # every span in the store is an "X" event with its measured
    # duration (microseconds), under its role track
    leader = [e for e in by_tid["leader"] if e["ph"] == "X"]
    assert [(e["name"], e["dur"]) for e in leader
            if e["args"]["flush_id"] == 7] == [
        ("queue_wait", 1000.0), ("device_d2h", 4000.0),
        ("repl_ack", 2000.0)]
    # within a flush the spans stack sequentially from the base
    assert leader[1]["ts"] == leader[0]["ts"] + leader[0]["dur"]
    rep = [e for e in by_tid["replica@h:1"] if e["ph"] == "X"]
    assert [e["name"] for e in rep] == ["validate", "apply"]
    # roles of one flush share the base tick
    assert rep[0]["ts"] == leader[0]["ts"]
    # cross-flush: flush 9 starts after flush 7's widest role ends
    f7 = [e for e in leader if e["args"]["flush_id"] == 7]
    f9 = [e for e in leader if e["args"]["flush_id"] == 9]
    assert f9 and f9[0]["ts"] > f7[-1]["ts"] + f7[-1]["dur"]
    # the controller decision is an instant event on its own track,
    # anchored at its flush's base, carrying the full journal entry
    ctrl = by_tid["controller"]
    assert len(ctrl) == 1 and ctrl[0]["ph"] == "i"
    assert ctrl[0]["ts"] == leader[0]["ts"]
    assert ctrl[0]["args"]["new"] == 2
    # the never-recorded fid contributed nothing (skipped, not fake)
    assert not [e for e in evs
                if e.get("args", {}).get("flush_id") == 12345]


def _stamped(fid, t0, k=1):
    """A record as the span primitive leaves it: durations under their
    names, starts on the recorder's clock, the subdivisions of
    ``wal`` inside it."""
    starts = {"h2d": t0, "dispatch": t0 + 0.003,
              "device_d2h": t0 + 0.004, "wal": t0 + 0.006,
              "wal_fsync": t0 + 0.0065, "resolve": t0 + 0.009}
    return {"flush_id": fid, "k": k, "t": 1.7e9 + t0,
            "clock": [t0, 1.7e9 + t0], "starts": starts,
            "h2d": 0.003, "dispatch": 0.001, "device_d2h": 0.002,
            "wal": 0.003, "wal_fsync": 0.002, "resolve": 0.001,
            "enqueue": 0.004, "total": 0.010}


@pytest.mark.parametrize("source", ["store", "flight_dump"])
def test_stamped_records_lay_out_by_their_start_stamps(source):
    """Spans sit where they ran: zero is the earliest stamp exported,
    the gap between two flushes is the real one (18 ms here, not the
    sum of the first one's spans), and an unstamped replica role
    stacks from its flush's base."""
    recs = [_stamped(7, 100.0), _stamped(9, 100.018)]
    names = ("h2d", "dispatch", "device_d2h", "wal", "resolve")
    if source == "store":
        store = obs.SpanStore()
        for r in recs:
            store.record(r["flush_id"], "leader",
                         [(n, r[n]) for n in names],
                         starts=r["starts"])
        store.record(9, "replica@h:1", [("validate", 0.001),
                                        ("apply", 0.002)])
        evs = trace_export.trace_events([7, 9], store=store)
    else:
        evs = trace_export.flight_dump_events({"ring": recs})
    by = {(e["args"]["flush_id"], e["tid"], e["name"]): e
          for e in evs if e["ph"] == "X"}
    assert by[7, "leader", "h2d"]["ts"] == 0.0
    assert by[7, "leader", "wal"]["ts"] == pytest.approx(6000.0)
    assert by[7, "leader", "wal"]["dur"] == pytest.approx(3000.0)
    assert by[9, "leader", "h2d"]["ts"] == pytest.approx(18000.0)
    assert by[9, "leader", "resolve"]["ts"] == pytest.approx(27000.0)
    marks = {e["name"]: e["ts"] for e in evs if e["tid"] == "flush"}
    assert marks["flush 9"] == pytest.approx(18000.0)
    if source == "store":
        rep = by[9, "replica@h:1", "validate"]
        assert rep["ts"] == pytest.approx(18000.0)
        assert by[9, "replica@h:1", "apply"]["ts"] == \
            pytest.approx(19000.0)
    else:
        # a dump carries the subdivisions: on their own track, inside
        # their parent's extent
        sub = by[7, "leader/sub", "wal_fsync"]
        wal = by[7, "leader", "wal"]
        assert wal["ts"] <= sub["ts"] and \
            sub["ts"] + sub["dur"] <= wal["ts"] + wal["dur"]
        assert (7, "leader", "wal_fsync") not in by


def test_served_flushes_export_at_their_stamps(tmp_path):
    """End to end: the records of a served service carry stamps, and
    the export places each flush's ``wal`` after its ``h2d`` by the
    measured distance."""
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 config=fast_test_config(),
                                 data_dir=str(tmp_path))
    before = set(obs.SPANS.flush_ids())
    svc.flush()
    fut = svc.kput_many(0, ["a", "b"], [b"1", b"2"])
    svc.flush()
    svc.flush()
    assert fut.done
    fids = [f for f in obs.SPANS.flush_ids() if f not in before]
    evs = [e for e in trace_export.trace_events(fids)
           if e["ph"] == "X" and e["tid"] == "leader"]
    rec = [r for r in svc.flight.records if r.get("k")][-1]
    mine = {e["name"]: e for e in evs
            if e["args"]["flush_id"] == rec["flush_id"]}
    assert mine["wal"]["ts"] - mine["h2d"]["ts"] == pytest.approx(
        (rec["starts"]["wal"] - rec["starts"]["h2d"]) * 1e6)
    # zero is the earliest stamp exported (the first 'pack')
    assert 0.0 <= min(e["ts"] for e in evs) < 1e6
    svc.stop()


def test_flight_dump_cli_path(tmp_path, capsys):
    dump = {
        "schema": "retpu-flight-dump-v3",
        "ring": [{"flush_id": 3, "t": time.time(), "k": 2,
                  "queue_wait": 0.001, "device_d2h": 0.002,
                  "total": 0.003, "a_width": 8,
                  "payload_bytes": 64, "queued_rounds": 0,
                  "in_flight": 0}],
        "controller_decisions": [
            {"seq": 4, "flush_id": 3, "actuator": "tenant_guard",
             "cause": "tenant_ops_share", "observed": 0.9,
             "knob": "admission_cap[hot]", "old": None, "new": 4}],
    }
    src = tmp_path / "dump.json"
    src.write_text(json.dumps(dump))
    out = tmp_path / "trace.json"
    assert trace_export.main(["--flight-dump", str(src),
                              "-o", str(out)]) == 0
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    # marks render as spans (derived/meta fields excluded), the
    # journal entry as an autotune instant
    assert {"queue_wait", "device_d2h",
            "autotune admission_cap[hot]"} <= names
    assert "total" not in names  # META field, not a span
    assert doc["otherData"]["source_dump_schema"] \
        == "retpu-flight-dump-v3"


def test_live_3host_recorded_timeline_round_trip(tmp_path):
    """Acceptance: record a real 3-host flush timeline (leader + two
    in-process replica lanes over the delta wire), export it, and
    verify every exported span matches the store's record — the
    tool renders what the obs plane measured, nothing else."""
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
    try:
        repgroup.warmup_kernels(svc)
        assert svc.takeover()
        futs = [svc.kput_many(e, ["a", "b"], [b"1", b"2"])
                for e in range(4)]
        while any(svc.queues):
            svc.flush()
        svc._drain_pending(block_all=True)
        assert all(f.done for f in futs)
        # a journaled decision to ride along (the journal is data
        # here; actuation is exercised in test_controller)
        fids = [f for f in obs.SPANS.flush_ids() if f not in before]
        assert fids
        ev = svc.controller.journal.note(
            "ack_rtt", "repl_ack_ms_p50", 5.0,
            knob="pipeline_depth", old=1, new=2, flush_id=fids[-1])
        path = str(tmp_path / "trace.json")
        doc = trace_export.export(
            path, fids, svc.controller.journal.snapshot())
        loaded = json.loads(open(path, encoding="utf-8").read())
        assert loaded == doc
        evs = loaded["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert spans, "no spans exported from a live run"
        # ROUND TRIP: every exported span re-finds its (name,
        # duration) in the store's timeline for its flush and role
        for e in spans:
            tl = obs.timeline(e["args"]["flush_id"])
            assert tl is not None
            side = tl[e["tid"]]
            match = [d for n, d in side["spans"]
                     if n == e["name"]
                     and abs(d * 1e6 - e["dur"]) < 0.5]
            assert match, (e, side["spans"])
        # at least one flush exported both leader and a lane-tagged
        # replica side (the 3-host join, not a leader-only render)
        by_fid = {}
        for e in spans:
            by_fid.setdefault(e["args"]["flush_id"],
                              set()).add(e["tid"])
        assert any("leader" in roles
                   and any(t.startswith("replica") for t in roles)
                   for roles in by_fid.values()), by_fid
        # the decision instant rode along with its journal payload
        ctrl = [e for e in evs if e["tid"] == "controller"]
        assert len(ctrl) == 1
        assert ctrl[0]["args"]["seq"] == ev["seq"]
    finally:
        svc.stop()
        for s in servers:
            s.stop()
