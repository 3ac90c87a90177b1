"""One number of the service's own ``stats()`` as the child's ``dump``
carries it, found by its path of keys (``["startup",
"state_init_s"]``: the seconds the constructor spent building the
device's planes and their first tree).  A program whose ``stats()``
has no such key gives nothing to read."""


def read(facts, path):
    at = facts["dump"].get("stats")
    for key in path:
        if not isinstance(at, dict) or key not in at:
            return None
        at = at[key]
    if isinstance(at, bool) or not isinstance(at, (int, float)):
        return None
    return float(at), 1
