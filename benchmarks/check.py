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

Every comparison is exact, so every limit is 0.  ``correct`` also needs
the run to have been made under the stated guarantees: the WAL synced
by ``fsync``, every native half in use (a Python fallback is another
system), no corruption detected, the read-back answered by device
rounds, and a TPU.
"""

from __future__ import annotations

import bisect

import numpy as np

from loadgen import OK, WID_FABRICATED, WID_NOTFOUND

_ABSENT = (-1, -1)   # the "version" of a record that reads notfound


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
        top, best = [], (load_vsn[k], k)
        for _, v, w in lst:
            best = max(best, (v, w))
            top.append(best)
        history[k] = ([t for t, _, _ in lst], top)
    return wkey, wvsn, history


def verdict(recordcount: int, load_vsn: dict, logs: list,
            read_back: dict, dump: dict, device: dict,
            mesh: bool = False) -> dict:
    """The numbers compared, each beside its limit, and ``correct``."""
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
            need = load_vsn[k]
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
        want = history[k][1][-1][1] if k in history else k
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
    replies_correct = all(c["value"] <= c["limit"] for c in compared)
    return {
        "compared": compared,
        "replies_correct": replies_correct,
        "guarantees": guarantees,
        "examples": examples,
        "reads_checked": int(sum(
            (lg.is_read & (lg.status == OK)).sum() for lg in logs)),
        "writes_acknowledged": len(wvsn),
        "keys_read_back": len(read_back),
        "correct": replies_correct and all(guarantees.values()),
    }
