"""Of the seconds in the traced window's longest idle gaps of the
device (``trace_reduce.py``'s ``idle_gaps``: each named by the
innermost host event over its middle), the share, in %, whose name is
not ``unattributed``: how much of the device's idleness the trace can
put a name to.  Nothing to read without a device."""


def read(facts):
    red = (facts["dump"].get("trace") or {}).get("reduction")
    if not red or not red["idle_gaps"]:
        return None
    gaps = red["idle_gaps"]
    total = sum(s for _, s in gaps)
    if total <= 0:
        return None
    named = sum(s for name, s in gaps if name != "unattributed")
    return 100.0 * named / total, len(gaps)
