"""Milliseconds per second of the window that the service spent inside
the given per-flush marks: their sum over the window's flush records,
over the window's seconds.  A mark a record lacks counts 0; a program
whose records carry no stamp gives nothing to read.

:func:`window_records` chooses the window's records (``stall_rate.py``
uses it too).  A flush record is one with a ``total``; its wall-clock
stamp is ``clock[1]`` of the ``(perf_counter, time.time())`` pair read
when the record was opened.  The window is ``[window_end_unix -
seconds, window_end_unix]``; but ``window_end_unix`` is read when the
phase returns, and in a traced run that is after the tracer has
written its trace out (25 s after the window on ``ring64-n3-deep``,
my chip run, PR 25), which would leave half the window out and count
the read-back in.  So where the generator's log says when the window
began (``log.t0``, on ``perf_counter``: one clock for every process
of a machine), that instant is placed on the wall clock through a
record's own pair and the window is the ``seconds`` from there; it is
taken only if it lies where a window's start can lie (not after
``window_end_unix - seconds``, at most ten minutes before it)."""

LATE_S = 600.0


def window_records(facts):
    recs = [r for r in facts["dump"]["lat_records"]
            if "total" in r and "clock" in r]
    if not recs:
        return []
    seconds = facts["seconds"]
    start = facts["window_end_unix"] - seconds
    t0 = getattr(facts.get("log"), "t0", None)
    if t0 is not None:
        perf, unix = recs[0]["clock"]
        began = unix + (t0 - perf)
        if start - LATE_S <= began <= start:
            start = began
    return [r for r in recs if start <= r["clock"][1] <= start + seconds]


def read(facts, marks):
    recs = window_records(facts)
    if not recs:
        return None
    ms = sum(r.get(m, 0.0) for r in recs for m in marks) * 1e3
    return ms / facts["seconds"], len(recs)
