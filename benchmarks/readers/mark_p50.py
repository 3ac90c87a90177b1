"""Median, in ms, of a per-flush mark of the service (``lat_records``:
host clock, one record per flush of the window), several marks summed
where a layer spans them.  A mark a record lacks counts 0 there; a
record that has none of them is left out."""


def read(facts, marks):
    vals = sorted(
        sum(r.get(m, 0.0) for m in marks) * 1e3
        for r in facts["dump"]["lat_records"]
        if any(m in r for m in marks))
    if not vals:
        return None
    mid = len(vals) // 2
    p50 = vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2
    return p50, len(vals)
