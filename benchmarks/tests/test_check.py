"""``check.py`` fails what it must fail and passes a clean transcript
whose concurrent writes were acknowledged out of send order."""

import copy

import numpy as np
import pytest

import check
from loadgen import FAILED, OK, WID_FABRICATED, Log

RECORDS = 10
LOAD_VSN = {k: (1, k + 1) for k in range(RECORDS)}
DEVICE = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
DUMP = {
    "stats": {"wal": {"sync_mode": "fsync"},
              "native_enqueue": {"flushes": 5},
              "native_resolve": {"flushes": 5},
              "corruptions_detected": 0},
    "read_back_on_device": True,
}


def transcript(rows):
    """rows: (is_read, key, sent, done, wid-or-None, vsn-or-None, status)"""
    n = len(rows)
    log = Log(0.0, np.zeros(n), [r[0] for r in rows],
              [r[1] for r in rows], first_wid=100)
    for i, (is_read, _k, sent, done, wid, vsn, status) in enumerate(rows):
        log.sent[i], log.done[i], log.status[i] = sent, done, status
        if wid is not None:
            log.wid[i] = wid
        if vsn is not None:
            log.epoch[i], log.seq[i] = vsn
    return log


def clean_rows():
    # writes 100 and 101 to key 3 are concurrent: 101 is sent second,
    # acknowledged first, and given the LOWER version
    return [
        (False, 3, 1.0, 2.0, None, (1, 21), OK),    # wid 100
        (False, 3, 1.1, 1.5, None, (1, 20), OK),    # wid 101
        (True, 3, 1.2, 1.3, 3, None, OK),            # concurrent: loaded
        (True, 3, 1.6, 1.7, 101, None, OK),          # after 101's ack
        (True, 3, 1.6, 1.8, 100, None, OK),          # 100 unacked yet: ok
        (True, 3, 2.1, 2.2, 100, None, OK),          # after both
        (False, 5, 3.0, 3.5, None, (1, 30), OK),    # wid 106
        (True, 7, 3.0, 3.1, 7, None, OK),            # untouched key
    ]


def run(rows, read_back=None, dump=DUMP, device=DEVICE):
    log = transcript(rows)
    if read_back is None:
        read_back = {3: 100, 5: 106, 7: 7, 8: 8}
    return check.verdict(RECORDS, LOAD_VSN, [log], read_back, dump, device)


def value(v, name):
    return next(c["value"] for c in v["compared"] if c["name"] == name)


def test_clean_transcript_with_out_of_order_acks_passes():
    v = run(clean_rows())
    assert v["correct"], v
    assert all(c["value"] == 0 and c["limit"] == 0 for c in v["compared"])
    assert v["reads_checked"] == 5 and v["writes_acknowledged"] == 3


def test_stale_read_fails():
    rows = clean_rows()
    rows[5] = (True, 3, 2.1, 2.2, 101, None, OK)   # after 100's ack: stale
    v = run(rows)
    assert not v["correct"] and value(v, "stale_reads") == 1
    rows[5] = (True, 3, 2.1, 2.2, 3, None, OK)     # the loaded record
    assert value(run(rows), "stale_reads") == 1
    rows[5] = (True, 3, 2.1, 2.2, -2, None, OK)    # notfound
    assert value(run(rows), "stale_reads") == 1


def test_lost_acknowledged_write_fails():
    v = run(clean_rows(), read_back={3: 101, 5: 106, 7: 7})
    assert not v["correct"] and value(v, "lost_writes") == 1
    v = run(clean_rows(), read_back={3: 100, 5: 5, 7: 7})
    assert not v["correct"] and value(v, "lost_writes") == 1
    v = run(clean_rows(), read_back={3: 100, 5: 106, 7: -2})
    assert not v["correct"] and value(v, "lost_writes") == 1


def test_unanswered_write_may_or_may_not_have_landed():
    rows = clean_rows()
    rows.append((False, 5, 4.0, np.nan, None, None, 0))   # wid 108, no reply
    for got in (106, 108):
        v = run(rows, read_back={3: 100, 5: got, 7: 7})
        assert value(v, "lost_writes") == 0
        assert not v["correct"] and value(v, "failed_or_unanswered") == 1


def test_fabricated_bytes_fail():
    for wid in (WID_FABRICATED, 106, 4, 999):   # 106 and 4: other keys'
        rows = clean_rows()
        rows[3] = (True, 3, 1.6, 1.7, wid, None, OK)
        v = run(rows)
        assert not v["correct"] and value(v, "fabricated_reads") == 1, wid


def test_failed_reply_fails():
    rows = clean_rows()
    rows[7] = (True, 7, 3.0, 3.1, None, None, FAILED)
    v = run(rows)
    assert not v["correct"] and value(v, "failed_or_unanswered") == 1


@pytest.mark.parametrize("path,bad", [
    (("stats", "wal", "sync_mode"), "buffer"),
    (("stats", "native_enqueue", "flushes"), 0),
    (("stats", "native_resolve", "flushes"), 0),
    (("stats", "corruptions_detected"), 2),
    (("read_back_on_device",), False),
])
def test_a_run_under_a_weaker_guarantee_is_not_correct(path, bad):
    dump = copy.deepcopy(DUMP)
    d = dump
    for key in path[:-1]:
        d = d[key]
    d[path[-1]] = bad
    v = run(clean_rows(), dump=dump)
    assert not v["correct"]
    assert all(c["value"] == 0 for c in v["compared"])


def test_no_tpu_is_not_correct():
    assert not run(clean_rows(), device={"platform": "cpu"})["correct"]
