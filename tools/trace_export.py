"""Chrome-trace / Perfetto export of flush timelines + controller
decisions.

``obs.timeline(fid)`` answers one flush's joined leader + replica
span record as a dict; this tool renders MANY of them — plus the
runtime controller's decision journal — as a Chrome trace-event JSON
(the ``chrome://tracing`` / Perfetto ``traceEvents`` array format),
so "where did the last N flushes' time go, and when did the
controller move a knob" becomes a picture instead of a dict-reading
exercise.

Timeline semantics: a record of the span primitive (``obs.spans``)
carries, beside each mark's duration, its START stamp
(``starts[mark]``, ``time.perf_counter()`` of the recording process),
and such a record is laid out by its stamps: every span sits where it
ran, flushes are spaced as they ran, and the derived subdivisions
(``wal_fsync`` inside ``wal``, ``fe_reply`` inside ``resolve``, ...)
ride a ``<role>/sub`` track under their parents; the trace's zero is
the earliest stamp exported.  A mark that ran in several stretches
(``between_flushes`` over idle ticks, ``gc``) is one bar from its
first start, as long as their sum.  A role WITHOUT stamps (a replica
lane, a flight dump older than the primitive) keeps the sequential
layout: its spans stacked from the flush's base in record order,
extents exact, the base of an unstamped flush ordinal.  Controller
journal events render as instant events on a ``controller`` track at
the base of the flush they were journaled against.

Two entry points:

- In-process API (tests, bench, a REPL next to a live service):
  ``trace_events(fids)`` / ``export(path, fids, decisions=...)``
  read the process-global span store directly.
- CLI over a flight-recorder dump (the cross-process path — dumps
  are JSON files, the span store is not)::

      python tools/trace_export.py --flight-dump dump.json \
          -o trace.json

  renders the dump's per-flush ring records (their latency marks
  are the same spans, minus replica sides) and its
  ``controller_decisions`` section; a correlated (schema v4) dump's
  per-host fleet sections render as additional per-host tracks.

Round 13 adds the FLEET path: :func:`fleet_trace_events` (and the
``--fleet-timelines`` CLI input) renders clock-ALIGNED fleet
timelines — ``svc.fleet_timeline(fid)`` answers — as one merged
trace with per-HOST tracks placed at their aligned leader-axis
times (the one case where cross-track positions ARE wall-clock,
honest to each role's ``bound_ms``).

Load the output in Perfetto (ui.perfetto.dev) or chrome://tracing.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Optional

__all__ = ["trace_events", "flight_dump_events", "export", "main"]

_US = 1e6  # seconds -> trace microseconds


def _span_events(role: str, spans, base_us: float, fid: int,
                 pid: str, starts: Optional[Dict[str, float]] = None,
                 origin_s: float = 0.0) -> List[Dict[str, Any]]:
    """One role's spans as complete ("X") events: each at its start
    stamp where ``starts`` has one (seconds on the recorder's clock,
    ``origin_s`` the trace's zero), the rest stacked sequentially
    from the flush base."""
    out: List[Dict[str, Any]] = []
    t = base_us
    for name, dur_s in spans:
        dur_us = max(float(dur_s), 0.0) * _US
        at = (starts or {}).get(name)
        ts = t if at is None else (float(at) - origin_s) * _US
        out.append({"name": str(name), "ph": "X", "ts": ts,
                    "dur": dur_us, "pid": pid, "tid": str(role),
                    "args": {"flush_id": fid}})
        if at is None:
            t += dur_us
    return out


def _origin(all_starts: Iterable[Optional[Dict[str, float]]]) -> float:
    """The earliest start stamp of the records to export (0.0 where
    none is stamped)."""
    firsts = [min(st.values()) for st in all_starts if st]
    return min(firsts) if firsts else 0.0


def _flush_extent(sides: List[Dict[str, Any]], origin_s: float,
                  cursor_us: float):
    """(base, cursor after) of one flush, in trace microseconds, from
    its roles' ``{"spans", "starts"}`` sides, the leader's first.  A
    flush whose leader is stamped starts at its earliest stamp, else
    at the cursor; it ends with its latest stamped span, or past its
    widest unstamped role (times 1.25: the ordinal spacing)."""
    starts = sides[0].get("starts") if sides else None
    base = ((min(starts.values()) - origin_s) * _US if starts
            else cursor_us)
    after = cursor_us
    for side in sides:
        st, spans = side.get("starts") or {}, side.get("spans", [])
        for name, dur in spans:
            if name in st:
                after = max(after, (st[name] - origin_s
                                    + max(float(dur), 0.0)) * _US)
        width = sum(max(float(d), 0.0) for n, d in spans
                    if n not in st) * _US
        if width or not st:
            after = max(after, base + max(width, 1.0) * 1.25)
    return base, after


def trace_events(flush_ids: Iterable[int],
                 decisions: Iterable[Dict[str, Any]] = (),
                 store: Optional[Any] = None,
                 pid: str = "retpu") -> List[Dict[str, Any]]:
    """Render ``obs.timeline(fid)`` records for ``flush_ids`` (plus
    controller journal ``decisions``) as a trace-event list.  Flushes
    missing from the store are skipped; decisions whose flush never
    recorded a timeline anchor at the end of the rendered range."""
    from riak_ensemble_tpu import obs

    store = store if store is not None else obs.SPANS
    events: List[Dict[str, Any]] = []
    base_of: Dict[int, float] = {}
    tls = []
    for fid in sorted(set(int(f) for f in flush_ids)):
        tl = store.timeline(fid)
        if tl and not tl.get("miss"):
            tls.append((fid, tl))
        # else evicted/unknown fid: a structured miss, not a record
        # (the store counted it)
    origin = _origin((tl.get("leader") or {}).get("starts")
                     for _fid, tl in tls)
    cursor = 0.0
    for fid, tl in tls:
        sides = sorted(((role, side) for role, side in tl.items()
                        if role != "flush_id"),
                       key=lambda rs: rs[0] != "leader")
        base, after = _flush_extent([side for _r, side in sides],
                                    origin, cursor)
        base_of[fid] = base
        for role, side in sides:
            events.extend(_span_events(
                role, side.get("spans", []), base, fid, pid,
                side.get("starts"), origin))
        # one metadata marker per flush so the viewer can jump by id
        events.append({"name": f"flush {fid}", "ph": "i", "s": "t",
                       "ts": base, "pid": pid, "tid": "flush",
                       "args": {k: v for k, v in
                                (tl.get("leader") or {}).items()
                                if k not in ("spans", "starts")}})
        cursor = after
    base = cursor
    for ev in decisions:
        ts = base_of.get(int(ev.get("flush_id", 0)), base)
        knob = ev.get("knob") or ev.get("actuator", "decision")
        events.append({"name": f"autotune {knob}", "ph": "i",
                       "s": "g", "ts": ts, "pid": pid,
                       "tid": "controller", "args": dict(ev)})
    return events


def flight_dump_events(dump: Dict[str, Any],
                       pid: str = "retpu") -> List[Dict[str, Any]]:
    """The cross-process path: render a flight-recorder dump's ring
    records (their latency marks, leader-side only — a dump has no
    replica store) + its ``controller_decisions`` section."""
    from riak_ensemble_tpu.obs import flightrec

    events: List[Dict[str, Any]] = []
    base_of: Dict[int, float] = {}
    ring = dump.get("ring", [])
    origin = _origin(rec.get("starts") for rec in ring)
    cursor = 0.0
    for rec in ring:
        fid = int(rec.get("flush_id", 0))
        starts = rec.get("starts")
        spans = [(c, v) for c, v in rec.items()
                 if isinstance(v, (int, float))
                 and c not in flightrec.META_FIELDS]
        # the stamped subdivisions of those marks, on their own track
        sub = [(c, rec[c]) for c in flightrec.DERIVED_MARKS
               if c in rec and c in (starts or {})]
        base, cursor = _flush_extent(
            [{"spans": spans, "starts": starts}], origin, cursor)
        base_of[fid] = base
        events.extend(_span_events("leader", spans, base, fid, pid,
                                   starts, origin))
        events.extend(_span_events("leader/sub", sub, base, fid, pid,
                                   starts, origin))
        events.append({"name": f"flush {fid}", "ph": "i", "s": "t",
                       "ts": base, "pid": pid, "tid": "flush",
                       "args": {k: rec.get(k) for k in
                                ("k", "a_width", "payload_bytes",
                                 "queued_rounds", "in_flight")}})
    base = cursor
    for ev in dump.get("controller_decisions", []):
        ts = base_of.get(int(ev.get("flush_id", 0)), base)
        knob = ev.get("knob") or ev.get("actuator", "decision")
        events.append({"name": f"autotune {knob}", "ph": "i",
                       "s": "g", "ts": ts, "pid": pid,
                       "tid": "controller", "args": dict(ev)})
    return events


def fleet_trace_events(timelines: Iterable[Dict[str, Any]],
                       pid_prefix: str = "") -> List[Dict[str, Any]]:
    """Render ALIGNED fleet timelines (``svc.fleet_timeline(fid)``
    dicts — the ``retpu-fleet-timeline-v1`` shape) as ONE merged
    Chrome/Perfetto trace with per-HOST tracks.

    Unlike :func:`trace_events`' ordinal layout, fleet timelines
    carry absolute starts on the leader's clock (each role's spans
    aligned through its link's offset estimate), so events here are
    placed at their ALIGNED times: ``pid`` = host label (one Perfetto
    track group per host), ``tid`` = role, and each role carries its
    ``bound_ms`` in args so a reader knows how much to trust a
    cross-track comparison.  Timelines of several flushes merge onto
    one axis by their own ``base_s`` deltas (all bases are
    leader-clock seconds)."""
    events: List[Dict[str, Any]] = []
    tls = [t for t in timelines
           if isinstance(t, dict) and t.get("roles")]
    if not tls:
        return events
    base0 = min(float(t.get("base_s", 0.0)) for t in tls)
    for tl in tls:
        fid = int(tl.get("flush_id", 0))
        shift = (float(tl.get("base_s", 0.0)) - base0) * _US
        for role, info in tl["roles"].items():
            host = info.get("host") or "?"
            pid = f"{pid_prefix}{host}"
            for name, start_s, dur_s in info.get("spans", []):
                events.append({
                    "name": str(name), "ph": "X",
                    "ts": shift + max(float(start_s), 0.0) * _US,
                    "dur": max(float(dur_s), 0.0) * _US,
                    "pid": pid, "tid": str(role),
                    "args": {"flush_id": fid,
                             "aligned": bool(info.get("aligned")),
                             "bound_ms": info.get("bound_ms", 0.0)}})
    return events


def export(path: str, flush_ids: Iterable[int],
           decisions: Iterable[Dict[str, Any]] = (),
           store: Optional[Any] = None) -> Dict[str, Any]:
    """Write the Chrome-trace JSON for ``flush_ids`` (+ journal
    ``decisions``) to ``path``; returns the written document."""
    doc = {
        "traceEvents": trace_events(flush_ids, decisions, store),
        "displayTimeUnit": "ms",
        "otherData": {
            "source": "riak_ensemble_tpu tools/trace_export.py",
            "timeline_semantics":
                "stamped spans at their start stamps (zero = the "
                "earliest exported); unstamped roles sequential "
                "from their flush's base",
        },
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--flight-dump",
                     help="a flight-recorder dump JSON "
                          "(RETPU_OBS_DUMP_DIR file) to render; a "
                          "schema-v4 dump's per-host fleet sections "
                          "render as additional per-host tracks")
    src.add_argument("--fleet-timelines",
                     help="a JSON file holding one (or a list of) "
                          "clock-ALIGNED fleet timeline dict(s) — "
                          "the ('fleet','timeline',fid) verb's "
                          "answer — rendered with per-host tracks "
                          "at aligned times")
    ap.add_argument("-o", "--out", default="trace.json",
                    help="output trace path (default trace.json)")
    args = ap.parse_args(argv)
    path = args.flight_dump or args.fleet_timelines
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"trace_export: unreadable input: {exc}",
              file=sys.stderr)
        return 1
    if args.fleet_timelines:
        tls = data if isinstance(data, list) else [data]
        doc = {
            "traceEvents": fleet_trace_events(tls),
            "displayTimeUnit": "ms",
            "otherData": {
                "source": "riak_ensemble_tpu tools/trace_export.py",
                "timeline_semantics":
                    "per-host tracks at clock-aligned leader-axis "
                    "times; trust cross-track deltas to each role's "
                    "bound_ms",
            },
        }
    else:
        events = flight_dump_events(data)
        # a correlated (schema v4) dump carries per-host span
        # sections: render them as their own host tracks next to the
        # leader ring (ordinal layout — a dump has no aligned axis,
        # only the clock_offsets section to read them against)
        for host, section in (data.get("hosts") or {}).items():
            if not isinstance(section, dict):
                continue
            hbase = 0.0
            # JSON stringified the flush-id keys: order numerically
            # (lexicographic would put fid 9 after 10); roles of one
            # flush share its base like trace_events, and the base
            # advances once per flush by its widest role
            for fid, tl in sorted(
                    (section.get("spans") or {}).items(),
                    key=lambda kv: int(kv[0])):
                if not isinstance(tl, dict) or tl.get("miss"):
                    continue
                widest = 0.0
                for role, side in tl.items():
                    if role == "flush_id" or not isinstance(side,
                                                            dict):
                        continue
                    spans = side.get("spans", [])
                    events.extend(_span_events(
                        role, spans, hbase, int(fid), str(host)))
                    widest = max(widest,
                                 sum(max(float(d), 0.0)
                                     for _n, d in spans))
                hbase += max(widest * _US, 1.0) * 1.25
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"source_dump_schema": data.get("schema"),
                          "clock_offsets":
                              data.get("clock_offsets") or {}},
        }
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    print(f"trace_export: {len(doc['traceEvents'])} events -> "
          f"{args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
