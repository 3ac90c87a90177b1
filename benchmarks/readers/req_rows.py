"""A percentile, in ms, of one field of the window's request rows.

In the loop cycles it samples (one in eight; every cycle of a profiler
session) the server leaves one row per request in the record of the
flush that answers it, ``rec["reqs"]``: ``[op, direct, t_rx,
rx_hold_s, residence_s]``: the wire verb, 1 where the reply was
written at once (a leased read, an error) and 0 where a flush's
resolve wrote it, the instant the front end had the frame whole
(``perf_counter``), how long the loop had not looked at its sockets by
then, and the seconds from ``t_rx`` to the reply's bytes handed to the
transport.  :func:`rows` chooses the rows of the WINDOW's records
(``mark_rate.py``'s ``window_records``), of the given verbs (None:
every verb) and ``direct`` flag (None: both); ``outside_server.py``
uses it too.  A program whose records carry no rows, or a window with
none of the kind, gives nothing to read."""

import importlib.util
import os

FIELDS = {"rx_hold_s": 3, "residence_s": 4}


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rows(facts, verbs=None, direct=None):
    return [row for r in _sibling("mark_rate").window_records(facts)
            for row in r.get("reqs", ())
            if (verbs is None or row[0] in verbs)
            and (direct is None or row[1] == direct)]


def read(facts, field, q, verbs=None, direct=None):
    col = FIELDS[field]
    vals = sorted(row[col] * 1e3 for row in rows(facts, verbs, direct)
                  if row[col] is not None)
    if not vals:
        return None
    # linear interpolation between the two nearest ranks
    at = (len(vals) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (at - lo), len(vals)
