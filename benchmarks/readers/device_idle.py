"""Share of the traced window, in %, in which no operation ran on the
device (``trace_reduce.py``; averaged over the devices used)."""


def read(facts):
    red = (facts["dump"].get("trace") or {}).get("reduction")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"]), red["devices"]
