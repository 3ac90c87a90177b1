"""How late the generator itself ran: 95th percentile, in ms, of
(sent - due) over the window's requests, by the generator's clock."""

import numpy as np


def read(facts):
    log = facts["log"]
    late = (log.sent - log.due) * 1e3
    late = late[~np.isnan(late)]
    if not late.size:
        return None
    return float(np.percentile(late, 95)), int(late.size)
