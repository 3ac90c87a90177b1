"""A ratio of the service's counters: sum of ``num`` over sum of
``den``, times ``scale``.  ``over`` says between which two readings of
the counters: ``since_mark`` (``mark`` to ``dump``: the window, its
drain and the read-back after it) or ``window_counters`` (``mark`` to
``fast_reads_off``, which is asked once the window has drained and
before the read-back begins: the window's own).  A child whose dump
has no such reading gives nothing to read."""


def read(facts, num, den, scale=1.0, over="since_mark"):
    since = facts["dump"].get(over)
    if since is None:
        return None
    bottom = sum(since[c] for c in den)
    if bottom <= 0:
        return None
    return scale * sum(since[c] for c in num) / bottom, bottom
