"""Programs compiled (or loaded from the compile cache: either way a
launch that met a shape for the first time) inside the window and its
drain: ``COMPILE_EVENTS`` newer than ``mark`` and not newer than the
window's end (both processes read the same wall clock; the read-back
after the window meets programs of its own).  Should be 0."""


def read(facts):
    events = [e for e in facts["dump"]["compile_events"]
              if e["t_unix"] <= facts["window_end_unix"]]
    return float(len(events)), len(events)
