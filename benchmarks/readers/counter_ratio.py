"""A ratio of the service's counters over the window (``dump``'s
``since_mark``: the counter at ``dump`` less the counter at ``mark``):
sum of ``num`` over sum of ``den``, times ``scale``."""


def read(facts, num, den, scale=1.0):
    since = facts["dump"]["since_mark"]
    bottom = sum(since[c] for c in den)
    if bottom <= 0:
        return None
    return scale * sum(since[c] for c in num) / bottom, bottom
