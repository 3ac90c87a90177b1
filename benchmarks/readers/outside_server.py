"""What a request of one kind spends OUTSIDE the server, in ms: the
mean, over the window's answered requests of the kind, of the client's
``done - sent`` (the generator's log), less the mean ``residence_s`` of
the same kind's request rows (``req_rows.py``: front end's stamp of the
whole frame to the reply handed to the transport).  Means subtract
exactly where medians do not, and both sides are durations, so no
clock is shared.  What is left is the wire both ways, the client's own
loop, and the wait in the server's socket while a flush holds its loop.

``op`` is the generator's kind (``update`` / ``read``), ``verbs`` the
wire verbs it sends.  The rows are a sample (one loop cycle in eight),
the client's side is every request.  Nothing to read where the records
carry no rows of the kind."""

import importlib.util
import os

import numpy as np


def _rows(facts, verbs):
    spec = importlib.util.spec_from_file_location(
        "reader_req_rows",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "req_rows.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.rows(facts, verbs)


def read(facts, op, verbs):
    log = facts.get("log")
    if log is None:
        return None
    inside = [row[4] for row in _rows(facts, verbs)]
    rtt = (log.done - log.sent)[log.is_read == (op == "read")]
    rtt = rtt[~np.isnan(rtt)]
    if not inside or not rtt.size:
        return None
    return (float(rtt.mean()) - sum(inside) / len(inside)) * 1e3, \
        len(inside)
