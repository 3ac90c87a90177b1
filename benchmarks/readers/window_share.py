"""The share of the window's flush time spent inside the given marks:
their sum over the window's flush records (``mark_rate.py``'s
``window_records``, chosen by their stamp) over the sum of those
records' ``total``.  ``step_wait_share`` reads ``device_d2h`` +
``inflight_wait``: how much of a flush is the wait for the device.  A
mark a record lacks counts 0; a program whose records carry no stamp,
or a window whose flushes took no time, gives nothing to read."""

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


def read(facts, marks):
    recs = _window_records(facts)
    whole = sum(r["total"] for r in recs)
    if whole <= 0.0:
        return None
    return sum(r.get(m, 0.0) for r in recs for m in marks) / whole, len(recs)
