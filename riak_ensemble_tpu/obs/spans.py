"""Cross-process flush tracing: flush ids + the span store.

Every device launch is stamped with a process-monotonic ``flush_id``
at enqueue.  The id propagates through the two-phase launch pipeline
(enqueue half → resolve half ride the same ``_InFlightLaunch``) and
over the replication wire (a trailing field of every ``abatch``
entry), so one id names the SAME flush on the leader and on every
replica — the Dapper trace-id discipline, scoped to the flush (the
unit of causality in this system: one flush = one device round = one
replicated entry).

The store is append-cheap and bounded: per flush id, a dict of
``role -> [(span_name, seconds), ...]`` plus whatever shape metadata
the recorder attached.  Roles are ``"leader"`` and ``"replica"``
(replica spans carry the recording service's lane tag when several
share the process).  :func:`timeline` answers the joined record —
the obs API a test or bench asks "where did flush N's time go,
end to end?".

Per-process scope: in-process replica servers (the tests'
threaded hosts) share this store with their leader, so the join is
immediate.  Subprocess replicas record into their own process's
store; the leader's id still names their spans, and the join happens
wherever both exports land.

The span primitive
------------------

:class:`SpanRecorder` is the ONE way the served path times a stretch
of host work (one recorder per service).  ``with spans.span("wal",
rec):`` stamps the stretch's START and DURATION on
``time.perf_counter()`` into the flush's record — ``rec["wal"]`` is
the mark (float seconds, summed over repeats), ``rec["starts"]
["wal"]`` the first start stamp — and, when the recorder annotates
(``RETPU_OBS`` on), holds a ``jax.profiler.TraceAnnotation`` named
``svc.wal`` open over the same stretch, so that in a profiler session
the program's spans lie in the ``/host:CPU`` plane on the device
trace's own clock.  A record is born in :meth:`SpanRecorder.begin`
with ``rec["clock"] = (perf_counter, time.time())``, one pair read
back to back, so a reader in another process places every stamp on
the wall clock: ``unix = clock[1] + (start - clock[0])``.

Code that times work for a flush but has no record at hand (the WAL
barrier's inside, the front end's reply, the collector's pauses)
passes no record and lands in :attr:`SpanRecorder.open` — the record
of the launch being settled, else :attr:`SpanRecorder.loop`, which
collects what the loop thread did between flushes (front-end decode
and dispatch, ``between_flushes``, pauses of the collector) until
:meth:`SpanRecorder.close` folds it into the record of the flush that
settles next.

A request's life
----------------

The front end (``svcnode.ServiceServer``) stamps every frame where it
has it whole (``t_rx``) and asks :class:`PollWatch` how long the loop
had not looked at its sockets by then (``rx_hold``): the wait a request
spends in its socket while a flush holds the loop, which no span of
the service can see.  In the loop cycles the recorder samples
(:attr:`SpanRecorder.detail`) each request leaves one row ``[op,
direct, t_rx, rx_hold_s, residence_s]`` under ``reqs`` in the record
that is open when its reply is written: the settling flush's, or the
loop's, which :meth:`SpanRecorder.close` folds into the next flush's
(lists extend where floats add).
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["next_flush_id", "SpanStore", "SPANS", "timeline",
           "SpanRecorder", "Span", "NULL_SPAN", "GcWatch", "PollWatch"]

_now = time.perf_counter


class Span:
    """One timed stretch: a context manager, or ``begin()`` /
    ``end()`` where the stretch spans loop callbacks.  A span that
    names no record may be kept and entered again (one stretch at a
    time): the front end times every request through four of them
    and makes no object per request."""

    __slots__ = ("_owner", "_rec", "name", "seconds", "_label",
                 "_ann", "_t0")

    def __init__(self, owner: "SpanRecorder", rec: Optional[dict],
                 name: str, label: Optional[str]) -> None:
        self._owner = owner
        self._rec = rec
        #: the mark; may be set before the end, where only the
        #: stretch itself tells which arm ran
        self.name = name
        self.seconds = 0.0
        self._label = label or "svc." + name
        self._ann = None

    def __enter__(self) -> "Span":
        # a TraceAnnotation decides at CONSTRUCTION whether a profiler
        # session records it, so one is made per stretch, and only
        # while a session is on (the check costs a tenth of making one)
        make = self._owner._annotation
        if make is not None and make.is_enabled():
            self._ann = make(self._label)
            self._ann.__enter__()
        self._t0 = _now()
        return self

    def end(self) -> float:
        """Close the stretch; its seconds."""
        t0 = self._t0
        dt = _now() - t0
        rec = self._rec
        if rec is None:
            rec = self._owner.open
        self.seconds = dt
        name = self.name
        rec[name] = rec.get(name, 0.0) + dt
        starts = rec.get("starts")
        if starts is None:
            starts = rec["starts"] = {}
        if name not in starts:
            starts[name] = t0
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        return dt

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    begin = __enter__


#: what a call site holds where spans are off (``RETPU_OBS=0``)
NULL_SPAN = contextlib.nullcontext()


class SpanRecorder:
    """Per-service span recorder (module docstring, "The span
    primitive").  ``annotate`` is the service's ``RETPU_OBS`` gate:
    off, spans still stamp the record (the marks every reader of
    ``lat_records`` expects) and open no profiler annotation."""

    #: The front end's spans run three to a REQUEST, not a handful to
    #: a flush, and at 2,000 requests a second that showed end to end
    #: (``read_p50_ms`` of ``ring64-n3-deep`` +10%, PERF.md §6), so
    #: they are taken in one loop cycle in this many, whole (a sampled
    #: cycle's record carries its front end complete, the others carry
    #: none), and in every cycle of a profiler session.
    DETAIL_EVERY = 8

    def __init__(self, annotate: bool = False) -> None:
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        #: what the loop thread did outside any flush record
        self.loop: Dict[str, Any] = {"starts": {}}
        #: where a span that names no record lands
        self.open: Dict[str, Any] = self.loop
        self._between: Optional[Span] = None
        #: whether the cycle under way is one the front end is timed in
        self.detail = False
        self._cycles = 0

    @staticmethod
    def begin() -> Dict[str, Any]:
        """A new flush record, anchored on both clocks."""
        return {"starts": {}, "clock": (_now(), time.time())}

    def span(self, name: str, rec: Optional[dict] = None,
             label: Optional[str] = None) -> Span:
        return Span(self, rec, name, label)

    @property
    def is_settling(self) -> bool:
        """Whether a launch's record is open (a span that names no
        record then belongs to that flush, not to the loop)."""
        return self.open is not self.loop

    def settling(self, rec: Optional[dict]) -> None:
        """Point :attr:`open` at the launch being settled (None:
        back at the loop's own record)."""
        self.open = self.loop if rec is None else rec

    def close(self, rec: dict) -> None:
        """Fold what the loop did since the last close into ``rec``,
        the record of the flush that settles now."""
        loop = self.loop
        if len(loop) == 1:
            return
        starts = rec["starts"]
        for name, t0 in loop.pop("starts").items():
            starts.setdefault(name, t0)
        for name, v in loop.items():
            # seconds add, rows (``reqs``) extend
            if name in rec:
                rec[name] += v
            else:
                rec[name] = v
        loop.clear()
        loop["starts"] = {}

    def between_begin(self) -> None:
        """The loop thread leaves a flush: everything until
        :meth:`between_end` is ``between_flushes`` (the front end's
        spans nest inside it)."""
        if self._between is None:
            self._cycles += 1
            make = self._annotation
            self.detail = (self._cycles % self.DETAIL_EVERY == 0
                           or (make is not None and make.is_enabled()))
            self._between = self.span("between_flushes", self.loop)
            self._between.begin()

    def between_end(self) -> None:
        sp, self._between = self._between, None
        if sp is not None:
            sp.end()


class GcWatch:
    """The collector's pauses, through ``gc.callbacks``: each pause is
    a span ``gc`` (annotation ``py.gc``) into the recorder's open
    record, a count and seconds per generation, and, at 1 ms or more,
    an entry ``(generation, start_unix, ms)`` in :attr:`recent`."""

    SLOW_MS = 1.0

    def __init__(self, recorder: SpanRecorder) -> None:
        self._recorder = recorder
        self._span = recorder.span("gc", label="py.gc")
        self._collecting = False
        self.pauses = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.longest_ms = 0.0
        self.recent: "deque[Tuple[int, float, float]]" = deque(maxlen=64)
        self.installed = False

    def install(self) -> None:
        if not self.installed:
            gc.callbacks.append(self._on_gc)
            self.installed = True

    def remove(self) -> None:
        if self.installed:
            self.installed = False
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._collecting = True
            self._span.begin()
            return
        if not self._collecting:    # installed inside a collection
            return
        self._collecting = False
        dt = self._span.end()
        gen = min(int(info.get("generation", 2)), 2)
        self.pauses[gen] += 1
        self.seconds[gen] += dt
        ms = dt * 1e3
        if ms > self.longest_ms:
            self.longest_ms = ms
        if ms >= self.SLOW_MS:
            self.recent.append((gen, time.time() - dt, round(ms, 3)))

    def stats(self) -> Dict[str, Any]:
        return {
            "installed": self.installed,
            "pauses": sum(self.pauses),
            "seconds": sum(self.seconds),
            "by_generation": {
                str(g): {"pauses": self.pauses[g],
                         "seconds": self.seconds[g]}
                for g in range(3)},
            "longest_ms": self.longest_ms,
            "recent": [list(p) for p in self.recent],
        }


class PollWatch:
    """When a selector event loop last looked at its sockets.

    One per loop, shared by the servers on it (:meth:`of` /
    :meth:`release`): the selector's ``select`` is wrapped to stamp
    each poll's entry and return.  A ``StreamReader`` wakes its reader
    one turn after ``data_received``, so when a frame is read in turn
    N+1 its bytes were delivered by poll N, and the request reached
    the socket somewhere after poll N-1 returned.  :attr:`base` is
    that instant, moved forward by the seconds the loop has since
    spent INSIDE ``select`` (looking), so ``t_rx - base`` is how long
    the loop had not looked when the frame was read: a hard upper
    bound on the frame's wait in its socket and its reader's buffer,
    near zero on an idle loop and a flush and a turn long under
    load."""

    __slots__ = ("base", "_sel", "_inner", "_users", "_looked", "_ring")

    def __init__(self, selector) -> None:
        self._sel = selector
        self._inner = selector.select
        self._users = 0
        self._looked = 0.0
        now = _now()
        #: (return stamp, seconds looked by then) of the last two polls
        self._ring = ((now, 0.0),) * 2
        self.base = now
        selector.select = self._select

    def _select(self, timeout=None):
        t0 = _now()
        events = self._inner(timeout)
        t1 = _now()
        looked = self._looked = self._looked + (t1 - t0)
        # this is poll N+1 of the docstring: the older of the two
        # polls before it is poll N-1
        before, last = self._ring
        self._ring = (last, (t1, looked))
        self.base = before[0] + (looked - before[1])
        return events

    @classmethod
    def of(cls, loop) -> Optional["PollWatch"]:
        """The loop's watch, made at its first use; None where the
        loop has no selector to stamp (then ``rx_hold`` has no
        source and is not reported)."""
        sel = getattr(loop, "_selector", None)
        if sel is None or not hasattr(sel, "select"):
            return None
        watch = getattr(sel.select, "__self__", None)
        if not isinstance(watch, cls):
            try:
                watch = cls(sel)
            except AttributeError:  # a selector that takes no attribute
                return None
        watch._users += 1
        return watch

    def release(self) -> None:
        self._users -= 1
        if self._users <= 0 and getattr(
                self._sel.select, "__self__", None) is self:
            del self._sel.select    # the class's own again


#: process-wide monotonic flush ids — shared by every service in the
#: process so leader and in-process replica launches never collide
_flush_ids = itertools.count(1)


def next_flush_id() -> int:
    return next(_flush_ids)


class SpanStore:
    """Bounded per-process store of per-flush span timelines."""

    def __init__(self, max_flushes: int = 4096) -> None:
        self.max_flushes = max_flushes
        self._lock = threading.Lock()
        self._flushes: "OrderedDict[int, Dict[str, Any]]" = OrderedDict()
        #: highest flush id the ring has ever evicted — the
        #: evicted/unknown miss boundary (a fid at or below it that
        #: is absent ROLLED OFF; above it, it was never recorded
        #: here — "the replica hasn't seen it yet" vs "too late")
        self._evict_high = 0
        #: lookup misses by reason (exported as
        #: ``retpu_span_misses_total{reason=...}``) — the fleet
        #: puller's signal for distinguishing lag from loss
        self.misses: Dict[str, int] = {"evicted": 0, "unknown": 0}

    def record(self, flush_id: int, role: str,
               spans: List[Tuple[str, float]],
               **info: Any) -> None:
        """Append one side's spans for a flush.  ``spans`` is a list
        of ``(name, seconds)``; ``info`` (batch shape, seq, lane, ...)
        merges into the role's metadata.  Thread-safe: replica server
        threads and the leader's flush loop share the store."""
        if not flush_id:
            return
        with self._lock:
            rec = self._flushes.get(flush_id)
            if rec is None:
                rec = self._flushes[flush_id] = {}
                while len(self._flushes) > self.max_flushes:
                    old_fid, _old = self._flushes.popitem(last=False)
                    if old_fid > self._evict_high:
                        self._evict_high = old_fid
            side = rec.setdefault(role, {"spans": []})
            side["spans"].extend(
                (str(n), float(d)) for n, d in spans)
            for k, v in info.items():
                side[k] = v

    def _miss_reason(self, flush_id: int) -> str:
        """Why a lookup missed (call under the lock): ``evicted`` for
        ids at or below the ring's eviction high-water (recorded once,
        rolled off — includes never-recorded ids in that range, the
        honest limit of a bounded ring), ``unknown`` above it (never
        seen HERE — on a replica that usually means "hasn't arrived
        yet")."""
        reason = ("evicted" if 0 < flush_id <= self._evict_high
                  else "unknown")
        self.misses[reason] += 1
        return reason

    def timeline(self, flush_id: int) -> Dict[str, Any]:
        """The joined per-flush record: ``{"flush_id": N, "leader":
        {...}, "replica": {...}}`` with per-role span lists.  A flush
        the store cannot answer returns a STRUCTURED miss —
        ``{"flush_id": N, "miss": "evicted"|"unknown"}`` — instead of
        bare None, and counts into :attr:`misses`: the fleet puller
        must distinguish "rolled off the ring" from "this host never
        saw it"."""
        with self._lock:
            rec = self._flushes.get(flush_id)
            if rec is None:
                return {"flush_id": int(flush_id),
                        "miss": self._miss_reason(flush_id)}
            out: Dict[str, Any] = {"flush_id": flush_id}
            for role, side in rec.items():
                out[role] = {"spans": list(side["spans"]),
                             **{k: v for k, v in side.items()
                                if k != "spans"}}
            return out

    def flush_ids(self) -> List[int]:
        with self._lock:
            return list(self._flushes)

    def span_values(self, flush_ids, role: str,
                    name: str) -> List[float]:
        """Every recorded duration (seconds) of span ``name`` under
        ``role`` across ``flush_ids``, one lock acquisition for the
        whole batch — the runtime controller's bulk read (e.g. the
        ``repl_ack`` samples of the last cadence window's flushes).
        Missing roles/spans contribute nothing: a flush whose ack is
        still pending simply isn't a sample yet.  A flush id entirely
        absent from the store counts a structured miss (evicted vs
        unknown) like :meth:`timeline` — and still contributes no
        sample."""
        out: List[float] = []
        with self._lock:
            for fid in flush_ids:
                rec = self._flushes.get(fid)
                if rec is None:
                    self._miss_reason(fid)
                    continue
                side = rec.get(role)
                if side is None:
                    continue
                out.extend(d for n, d in side["spans"] if n == name)
        return out


#: the process-global store every service records into
SPANS = SpanStore()


def timeline(flush_id: int) -> Dict[str, Any]:
    """Module-level convenience over the global store (misses come
    back structured — check ``tl.get("miss")``, not ``is None``)."""
    return SPANS.timeline(flush_id)
