"""Mean of one field of the window's flush records (``k``: the rounds
a flush launched; on a full-grid launch that is what the flush costs).
The window's records are chosen by their wall-clock stamp, by
``mark_rate.py``'s ``window_records``: the warm-up's, the drain's and
the read-back's deep flushes are not the window's.  A record without
the field is left out; a program whose records carry no stamp, or a
window without such a record, gives nothing to read."""

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


def read(facts, field):
    vals = [r[field] for r in _window_records(facts) if field in r]
    if not vals:
        return None
    return sum(vals) / len(vals), len(vals)
