"""What decides ``correct``: the replies of a run held against a plain
reference — a dict per key, ordered by the versions the server itself
acknowledged — and the guarantees the deployment states.

The reference takes nothing from the program but its replies.  Per key
the model entry is the acknowledged write with the highest version
``(epoch, seq)``.  A reply is wrong if

(a) a read returns bytes that no write to that key ever carried
    (``fabricated``);
(b) a read SENT after the acknowledgement of write W was received
    returns a value whose version is lower than W's (``stale_reads``:
    linearizability; a value whose write is still unacknowledged is
    concurrent and allowed);
(c) the read-back of a key differs from its model entry
    (``lost_writes``: an acknowledged write that is gone; a write that
    was sent and never answered is ambiguous and may stand);
(d) a request was answered ``failed``, with an error, or not at all
    where the model says ``ok`` (``failed``).

A key that was never loaded (an insert's) starts absent: a read of it
SENT after its insert's acknowledgement was received must not read
notfound (that is (b)); one sent before may, and is counted
(``reads_before_insert``); the read-back holds it to (c) as any
written key.  An insert that finds its ensemble full is answered
``failed`` and counts under (d).

Every comparison is exact, so every limit is 0.  ``correct`` also needs
the run to have been made under the stated guarantees: the WAL synced
by ``fsync``, every native half in use (a Python fallback is another
system), no corruption detected, the read-back answered by device
rounds, and a TPU.

The guarantee follows the deployment's settings (the configuration's
``riak_ensemble`` object).  Under ``trust_lease: false`` no read may be
answered from the lease mirror: a fifth comparison, ``leased_reads``
(the service's ``read_fastpath_hits`` from its start to the end of the
window) against 0, and the configuration's ``guarantees.reads`` must be
``READS_UNLEASED``, word for word.  Where ``trust_lease`` is true or
not given, neither is looked at.
"""

from __future__ import annotations

import bisect

import numpy as np

from loadgen import OK, WID_FABRICATED, WID_NOTFOUND

_ABSENT = (-1, -1)   # the "version" of a record that reads notfound
#: what ``guarantees.reads`` says where ``trust_lease`` is false
READS_UNLEASED = ("every read a quorum round on the device; "
                  "no lease trusted")


def model(recordcount: int, load_vsn: dict, logs: list):
    """(write id -> key, write id -> acknowledged version,
    key -> acknowledgements sorted by time with their running highest
    version)."""
    wkey: dict = {}
    wvsn: dict = {}
    acks: dict = {}
    for log in logs:
        for i in np.flatnonzero(~log.is_read & ~np.isnan(log.sent)).tolist():
            w, k = int(log.wid[i]), int(log.keynum[i])
            wkey[w] = k
            if log.status[i] == OK:
                v = (int(log.epoch[i]), int(log.seq[i]))
                wvsn[w] = v
                acks.setdefault(k, []).append((float(log.done[i]), v, w))
    history = {}
    for k, lst in acks.items():
        lst.sort()
        # (a key no load wrote, an insert's, starts absent)
        top, best = [], ((load_vsn[k], k) if k in load_vsn
                         else (_ABSENT, WID_NOTFOUND))
        for _, v, w in lst:
            best = max(best, (v, w))
            top.append(best)
        history[k] = ([t for t, _, _ in lst], top)
    return wkey, wvsn, history


def reads_before_insert(log, recordcount: int) -> int:
    """Reads of a never-loaded key that were answered notfound: sent
    before their key's insert was acknowledged, or ``stale_reads`` has
    them too."""
    return int((log.is_read & (log.status == OK)
                & (log.keynum >= recordcount)
                & (log.wid == WID_NOTFOUND)).sum())


def verdict(recordcount: int, load_vsn: dict, logs: list,
            read_back: dict, dump: dict, device: dict,
            mesh: bool = False, cfg: dict = None) -> dict:
    """The numbers compared, each beside its limit, and ``correct``.
    ``cfg``: the configuration as run (its ``riak_ensemble`` settings
    and the ``guarantees`` it states)."""
    wkey, wvsn, history = model(recordcount, load_vsn, logs)
    fabricated = stale = failed = 0
    examples: list = []

    def note(kind: str, **kw) -> None:
        if len(examples) < 8:
            examples.append(dict(kind=kind, **kw))

    for log in logs:
        failed += int((log.status != OK).sum())
        for i in np.flatnonzero(log.is_read & (log.status == OK)).tolist():
            k, w = int(log.keynum[i]), int(log.wid[i])
            if w == WID_NOTFOUND:
                v = _ABSENT
            elif w == WID_FABRICATED or (
                    w != k if w < recordcount else wkey.get(w) != k):
                fabricated += 1
                note("fabricated", key=k, wid=w)
                continue
            elif w < recordcount:
                v = load_vsn[k]
            else:
                v = wvsn.get(w)
                if v is None:
                    continue    # its write is unacknowledged: concurrent
            need = load_vsn.get(k, _ABSENT)
            if k in history:
                times, top = history[k]
                j = bisect.bisect_left(times, float(log.sent[i]))
                if j:
                    need = top[j - 1][0]
            if v < need:
                stale += 1
                note("stale_read", key=k, wid=w, version=v, needed=need)

    lost = 0
    for k, got in read_back.items():
        want = (history[k][1][-1][1] if k in history
                else k if k in load_vsn else WID_NOTFOUND)
        if got == want:
            continue
        if got >= recordcount and wkey.get(got) == k and got not in wvsn:
            continue            # sent, never answered: may have landed
        lost += 1
        note("lost_write", key=k, read_back=got, model=want)

    wal = (dump["stats"].get("wal") or {}).get("sync_mode")
    st = dump["stats"]
    guarantees = {
        "wal_fsync": wal == "fsync",
        "native_enqueue": st["native_enqueue"]["flushes"] > 0,
        "native_resolve": mesh or st["native_resolve"]["flushes"] > 0,
        "no_corruption": st["corruptions_detected"] == 0,
        "read_back_on_device": bool(dump.get("read_back_on_device")),
        "tpu": device.get("platform") == "tpu",
    }
    compared = [
        {"name": "fabricated_reads", "value": fabricated, "limit": 0},
        {"name": "stale_reads", "value": stale, "limit": 0},
        {"name": "lost_writes", "value": lost, "limit": 0},
        {"name": "failed_or_unanswered", "value": failed, "limit": 0},
    ]
    settings = (cfg or {}).get("riak_ensemble")
    if settings is not None and settings.get("trust_lease") is False:
        compared.append({"name": "leased_reads",
                         "value": dump["leased_reads"], "limit": 0})
        guarantees["reads_stated_unleased"] = (
            (cfg.get("guarantees") or {}).get("reads") == READS_UNLEASED)
    replies_correct = all(c["value"] <= c["limit"] for c in compared)
    return {
        "compared": compared,
        "replies_correct": replies_correct,
        "guarantees": guarantees,
        "examples": examples,
        "reads_before_insert": sum(
            reads_before_insert(lg, recordcount) for lg in logs),
        "reads_checked": int(sum(
            (lg.is_read & (lg.status == OK)).sum() for lg in logs)),
        "writes_acknowledged": len(wvsn),
        "keys_read_back": len(read_back),
        "correct": replies_correct and all(guarantees.values()),
    }
