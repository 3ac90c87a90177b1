"""Unified observability plane (round 6).

SURVEY §5 marks real tracing as the reference's gap to fill —
riak_ensemble ships only compiled-out ``?OUT`` macros and the
get_info/count_quorum introspection calls.  Before this round the
scale path answered that gap piecemeal: ``stats()`` dicts hand-built
per service, ``perf_counter()`` pairs scattered through the flush
path, and bench-side one-off attribution that could not be asked
anything after the run.  This package is the single plane the whole
stack reports into:

- :mod:`.registry` — a low-overhead metrics registry (counters,
  gauges, fixed-bucket histograms with O(log B) record; a label
  dimension for per-tenant attribution), exported as plain JSON and
  Prometheus text format (svcnode's ``metrics`` verb).
- :mod:`.spans` — the monotonic per-process ``flush_id`` allocator
  and a bounded store of per-flush span timelines.  Every launch is
  stamped at enqueue; the id rides the replication wire (a
  trailing field of each ``abatch`` entry), so leader-side
  enqueue/step/d2h/unpack/WAL/delta-build spans and replica-side
  validate/scatter/rebuild/WAL spans join into ONE causal timeline
  per flush (the Dapper propagation model, scoped to the flush).
  It also holds the SPAN PRIMITIVE (``SpanRecorder.span``), the one
  way the served path times host work: each span stamps its start
  and duration (``perf_counter``) into the flush's record —
  ``rec[mark]`` seconds, ``rec["starts"][mark]``, one
  ``rec["clock"] = (perf_counter, time.time())`` pair per record —
  and, with ``RETPU_OBS`` on, is a ``jax.profiler.TraceAnnotation``
  ``svc.<mark>`` on the device trace's own clock.  The flush's marks,
  the inside of the WAL barrier (``wal_encode``/``wal_append``/
  ``wal_fsync``), the front end (``fe_decode``/``fe_dispatch``/
  ``fe_reply``/``fe_reply_direct``: three spans a request, so taken
  in one loop cycle in eight and in every cycle of a profiler
  session), the loop's ``between_flushes``, the collector's
  pauses (``GcWatch``: mark ``gc``, annotation ``py.gc``,
  ``stats()["gc"]``) and the plane's own cost (``obs``) all go
  through it; ``stats()["frontend"]`` counts the wire's frames and
  bytes.  docs/ARCHITECTURE.md §11 has the span table.
- :mod:`.flightrec` — a flight recorder: bounded ring of complete
  per-flush records (marks, batch shape, active-set occupancy,
  payload bytes, queue depths) with an anomaly trigger — any flush
  slower than ``trigger_ratio`` × the rolling p50 snapshots the ring
  plus a box fingerprint to a dump file, so the next mixed-rung
  anomaly is diagnosable instead of a shrug.
- :mod:`.fingerprint` — the box fingerprint (cpu count, loadavg,
  jax/jaxlib versions, ``RETPU_*`` knobs) every flight dump and every
  bench JSON embeds, so cross-round comparisons stop being faith.
- :mod:`.opslo` — per-op SLO tracing (round 9): every keyed op's
  rx→submit→enqueue→flush-join→settle→ack stamps in bounded numpy
  slab rings keyed by ``flush_id``, feeding the latency histograms
  per op kind and per tenant (arrival at the server's loop to ack);
  ``rows_of(fid)`` resolves a flush's tail op down to its stage split
  beside ``timeline(fid)``.
- :mod:`.compilewatch` — compile-event hooks around every jitted
  step/pack/scatter variant (executable-cache-size deltas, exact, not
  a latency heuristic): warmup coverage gaps surface as
  ``retpu_compile_events_total{phase="serve"}`` instead of a
  dispatch-p99 mystery.
- :mod:`.controller` — the obs-ACTUATED runtime controller (round
  12): consumes the surfaces above on a flush-count cadence and
  drives ``pipeline_depth``/``repl_window``/tenant admission, with a
  bounded decision journal exported back through this same plane
  (``retpu_autotune_*`` gauges, the ``health()`` ``controller``
  section, flight-dump ``controller_decisions``, Chrome-trace export
  via ``tools/trace_export.py``).  ``RETPU_AUTOTUNE=0`` (the default)
  keeps it observe-only-constructed and bit-identical to the
  pre-controller service.

- :mod:`.fleet` — fleet-scope joining (round 13): per-link NTP-style
  clock-offset estimation (every ``obsq`` sideband round-trip feeds
  it), Prometheus multi-host merge under a ``host`` label, and the
  clock-aligned cross-host timeline (``svc.fleet_timeline(fid)`` —
  leader and replica spans on ONE axis, honest to the offset bound).
- :mod:`.watchdog` — the standing anomaly watchdog (round 13):
  leader-side, controller-cadence, walks pulled fleet timelines for
  ack-before-apply skew, persistently slow replica spans, and clock
  drift; findings journal through the PR 12 ``DecisionJournal``
  export surfaces.  ``RETPU_WATCHDOG=0`` disarms the standing pull.

Knobs: ``RETPU_OBS=0`` disables hot-path recording (instruments stay
constructed; record calls short-circuit — the tests' OFF arm);
``RETPU_OBS_DUMP_DIR`` directs flight-recorder dumps (unset keeps
them in memory only).  Stores are PER PROCESS: in-process replica
servers share the span store with their leader, subprocess replicas
export their half through their own ``metrics``/dump surface and the
join happens on ``flush_id``.
"""

from __future__ import annotations

import os

from riak_ensemble_tpu.obs.compilewatch import (COMPILE_EVENTS,
                                                CompileWatch)
from riak_ensemble_tpu.obs.controller import (DecisionJournal,
                                              RuntimeController)
from riak_ensemble_tpu.obs.fingerprint import box_fingerprint
from riak_ensemble_tpu.obs.fleet import (ClockOffset, align_timeline,
                                         merge_prometheus)
from riak_ensemble_tpu.obs.flightrec import FlightRecorder
from riak_ensemble_tpu.obs.opslo import OpSloRing
from riak_ensemble_tpu.obs.registry import (Counter, Gauge, Histogram,
                                            MetricsRegistry,
                                            MS_BUCKETS)
from riak_ensemble_tpu.obs.spans import (SPANS, SpanStore,
                                         next_flush_id, timeline)
from riak_ensemble_tpu.obs.watchdog import AnomalyWatchdog

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram",
           "MS_BUCKETS", "FlightRecorder", "SpanStore", "SPANS",
           "next_flush_id", "timeline", "box_fingerprint", "enabled",
           "dump_dir", "OpSloRing", "CompileWatch", "COMPILE_EVENTS",
           "RuntimeController", "DecisionJournal", "ClockOffset",
           "align_timeline", "merge_prometheus", "AnomalyWatchdog"]


def enabled() -> bool:
    """Whether hot-path recording is on (``RETPU_OBS=0`` opts out).

    Read the environment each call — services CACHE the answer at
    construction (one attribute test per flush beats an environ
    lookup), so an A/B arm flips the knob and builds a fresh
    service."""
    return os.environ.get("RETPU_OBS", "1") != "0"


def dump_dir():
    """Flight-recorder dump directory (``RETPU_OBS_DUMP_DIR``); None
    keeps anomaly snapshots in memory only."""
    return os.environ.get("RETPU_OBS_DUMP_DIR") or None
