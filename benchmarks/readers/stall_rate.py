"""Milliseconds per second of the window spent in stalled flushes: the
sum of ``total`` over the window's flush records whose ``total`` is
more than ``ratio`` times the window's median ``total`` (5: the flight
recorder's own ``trigger_ratio``), over the window's seconds.  The
window's records are chosen by ``mark_rate.py``'s ``window_records``;
a program whose records carry no stamp gives nothing to read."""

import importlib.util
import os


def _window_records(facts):
    spec = importlib.util.spec_from_file_location(
        "reader_mark_rate",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "mark_rate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.window_records(facts)


def read(facts, ratio=5.0):
    totals = sorted(r["total"] for r in _window_records(facts))
    if not totals:
        return None
    mid = len(totals) // 2
    p50 = (totals[mid] if len(totals) % 2
           else (totals[mid - 1] + totals[mid]) / 2)
    stalled = [t for t in totals if t > ratio * p50]
    return sum(stalled) * 1e3 / facts["seconds"], len(totals)
