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


# -- inserts: a key that was never loaded starts absent --------------------

def insert_rows():
    # key 12 was never loaded (RECORDS is 10); write 100 inserts it
    return [
        (True, 12, 0.5, 0.6, -2, None, OK),          # before the insert
        (False, 12, 1.0, 2.0, None, (1, 40), OK),   # wid 101: the insert
        (True, 12, 1.5, 1.6, -2, None, OK),          # sent before its ack
        (True, 12, 1.5, 1.7, 101, None, OK),         # or sees it already
        (True, 12, 2.1, 2.2, 101, None, OK),         # after its ack
    ]


def test_reads_of_an_inserted_key_are_held_to_its_acknowledgement():
    v = run(insert_rows(), read_back={12: 101, 7: 7})
    assert v["correct"], v
    assert v["reads_before_insert"] == 2
    rows = insert_rows()
    rows[4] = (True, 12, 2.1, 2.2, -2, None, OK)    # notfound after the ack
    v = run(rows, read_back={12: 101, 7: 7})
    assert not v["correct"] and value(v, "stale_reads") == 1
    assert v["examples"][0]["needed"] == (1, 40)
    rows[4] = (True, 12, 2.1, 2.2, 7, None, OK)     # a loaded key's bytes
    assert value(run(rows, read_back={12: 101}), "fabricated_reads") == 1


def test_a_lost_insert_fails_and_an_unanswered_one_may_stand():
    v = run(insert_rows(), read_back={12: -2, 7: 7})  # acknowledged, gone
    assert not v["correct"] and value(v, "lost_writes") == 1
    rows = insert_rows()[:1] + [(False, 12, 1.0, np.nan, None, None, 0)]
    for got in (-2, 101):                 # never answered: either stands
        v = run(rows, read_back={12: got})
        assert value(v, "lost_writes") == 0
        assert value(v, "failed_or_unanswered") == 1
    # never sent at all (the connection was lost first): absent
    rows = [(True, 13, 0.5, 0.6, -2, None, OK)]
    assert run(rows, read_back={13: -2})["correct"]
    assert value(run(rows, read_back={13: 5}), "lost_writes") == 1


def test_an_insert_into_a_full_ensemble_is_a_failed_request():
    rows = insert_rows()
    rows[1] = (False, 12, 1.0, 2.0, None, None, FAILED)
    rows[3] = rows[4] = (True, 12, 2.1, 2.2, -2, None, OK)
    v = run(rows, read_back={12: -2})
    assert not v["correct"] and value(v, "failed_or_unanswered") == 1
    assert value(v, "stale_reads") == 0 and value(v, "lost_writes") == 0


# -- the guarantee follows the setting -------------------------------------

UNLEASED = {"riak_ensemble": {"trust_lease": False},
            "guarantees": {"reads": check.READS_UNLEASED}}


def under(cfg, leased_reads):
    log = transcript(clean_rows())
    return check.verdict(RECORDS, LOAD_VSN, [log],
                         {3: 100, 5: 106, 7: 7, 8: 8},
                         dict(DUMP, leased_reads=leased_reads), DEVICE,
                         cfg=cfg)


def test_no_lease_trusted_means_no_read_from_the_mirror():
    v = under(UNLEASED, 0)
    assert v["correct"]
    assert [c["name"] for c in v["compared"]][-1] == "leased_reads"
    v = under(UNLEASED, 3)                # three reads the mirror answered
    assert not v["correct"] and value(v, "leased_reads") == 3
    assert all(c["limit"] == 0 for c in v["compared"])


def test_the_configuration_has_to_state_what_it_sets():
    leased_words = {"riak_ensemble": {"trust_lease": False},
                    "guarantees": {"reads": "leased reads only inside the "
                                            "lease margin, else a device "
                                            "round"}}
    v = under(leased_words, 0)
    assert not v["correct"]
    assert v["guarantees"]["reads_stated_unleased"] is False


@pytest.mark.parametrize("cfg", [
    None, {}, {"guarantees": {"reads": "leased"}},
    {"riak_ensemble": {"trust_lease": True}},
    {"riak_ensemble": {"ensemble_tick": 0.25}},
], ids=["none", "empty", "no_settings", "trusted", "another_setting"])
def test_where_the_lease_is_trusted_nothing_changes(cfg):
    v = under(cfg, 12_345)
    assert v["correct"]
    assert [c["name"] for c in v["compared"]] == [
        "fabricated_reads", "stale_reads", "lost_writes",
        "failed_or_unanswered"]
    assert "reads_stated_unleased" not in v["guarantees"]
    # and a dump from before PR 49 (no ``leased_reads``) still checks
    log = transcript(clean_rows())
    assert check.verdict(RECORDS, LOAD_VSN, [log], {3: 100}, DUMP, DEVICE,
                         cfg=cfg)["correct"]
