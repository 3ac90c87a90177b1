"""A percentile, in ms, of the window's client-side latencies (due
instant -> reply, the generator's clock) of one kind of request; a
request never answered has waited until the drain ended."""

import numpy as np


def read(facts, op, q):
    log = facts["log"]
    done = np.where(np.isnan(log.done), log.t_end, log.done)
    lat = ((done - log.due) * 1e3)[log.is_read == (op == "read")]
    if not lat.size:
        return None
    return float(np.percentile(lat, q)), int(lat.size)
