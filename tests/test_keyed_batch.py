"""Vectorized keyed client path (kput_many/kget_many).

review r2 #5: the scalar keyed path is bounded by per-op Python
(futures, op objects, per-op resolve).  The batch API keeps keyed
semantics — arbitrary keys, per-key results in order, slot recycling,
WAL durability — while packing/resolving through array slices.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402


def make(n_ens=4, n_peers=3, n_slots=32, **kw):
    rt = Runtime(seed=61)
    svc = BatchedEnsembleService(rt, n_ens, n_peers, n_slots,
                                 tick=0.005, config=fast_test_config(),
                                 **kw)
    return rt, svc


def settle(rt, fut, timeout=10.0):
    return rt.await_future(fut, timeout)


def test_batch_roundtrip_ordered():
    rt, svc = make()
    keys = [f"k{i}" for i in range(20)]
    vals = [b"v%d" % i for i in range(20)]
    res = settle(rt, svc.kput_many(1, keys, vals))
    assert len(res) == 20
    assert all(r[0] == "ok" for r in res)
    # versions are per-key and monotone within the ensemble
    seqs = [r[1][1] for r in res]
    assert seqs == sorted(seqs)

    got = settle(rt, svc.kget_many(1, keys + ["nope"]))
    assert got[:20] == [("ok", b"v%d" % i) for i in range(20)]
    assert got[20] == ("ok", NOTFOUND)
    svc.stop()


def test_batch_larger_than_max_k_splits_across_flushes():
    rt, svc = make(n_slots=256)
    svc.max_k = 8
    keys = [f"k{i}" for i in range(50)]   # > 6 flushes at K=8
    res = settle(rt, svc.kput_many(0, keys, [b"x%d" % i
                                             for i in range(50)]))
    assert len(res) == 50 and all(r[0] == "ok" for r in res)
    got = settle(rt, svc.kget_many(0, keys))
    assert got == [("ok", b"x%d" % i) for i in range(50)]
    svc.stop()


def test_batch_capacity_fail_and_duplicates():
    rt, svc = make(n_ens=1, n_slots=2)
    # 3 distinct keys into 2 slots: the slotless key fails, the rest
    # ack; a duplicate key serializes (both ok, last write wins)
    res = settle(rt, svc.kput_many(
        0, ["a", "b", "c", "a"], [b"1", b"2", b"3", b"4"]))
    assert res[0][0] == "ok" and res[1][0] == "ok"
    assert res[2] == "failed"            # no slot
    assert res[3][0] == "ok"             # duplicate of a: same slot
    assert settle(rt, svc.kget_many(0, ["a", "b"])) == \
        [("ok", b"4"), ("ok", b"2")]
    svc.stop()


def test_batch_interleaves_with_scalar_ops():
    rt, svc = make()
    f1 = svc.kput(2, "s", b"scalar")
    fb = svc.kput_many(2, ["b1", "b2"], [b"x", b"y"])
    f2 = svc.kget(2, "s")
    assert settle(rt, f1)[0] == "ok"
    assert all(r[0] == "ok" for r in settle(rt, fb))
    assert settle(rt, f2) == ("ok", b"scalar")
    assert settle(rt, svc.kget_many(2, ["b1", "s", "b2"])) == \
        [("ok", b"x"), ("ok", b"scalar"), ("ok", b"y")]
    svc.stop()


def test_batch_acked_writes_survive_crash(tmp_path):
    rt, svc = make(data_dir=str(tmp_path / "d"))
    res = settle(rt, svc.kput_many(
        3, [f"k{i}" for i in range(10)],
        [b"w%d" % i for i in range(10)]))
    assert all(r[0] == "ok" for r in res)
    svc.stop()
    svc._wal.close()

    rt2 = Runtime(seed=62)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "d"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "d"))
    got = settle(rt2, svc2.kget_many(3, [f"k{i}" for i in range(10)]))
    assert got == [("ok", b"w%d" % i) for i in range(10)]
    svc2.stop()


def test_batch_delete_recycle_interop():
    """Slots freed by scalar deletes are reusable by later batches."""
    rt, svc = make(n_ens=1, n_slots=2)
    assert all(r[0] == "ok" for r in settle(
        rt, svc.kput_many(0, ["a", "b"], [b"1", b"2"])))
    assert settle(rt, svc.kdelete(0, "a"))[0] == "ok"
    res = settle(rt, svc.kput_many(0, ["c"], [b"3"]))
    assert res[0][0] == "ok"
    assert settle(rt, svc.kget_many(0, ["a", "b", "c"])) == \
        [("ok", NOTFOUND), ("ok", b"2"), ("ok", b"3")]
    svc.stop()


def test_missing_keys_consume_no_device_rounds():
    """Review finding: slotless/unknown keys must resolve immediately
    (no placeholder rounds, no flush dependency) — the docstring
    contract."""
    rt, svc = make(n_ens=1, n_slots=1)
    # all-unknown get resolves synchronously, queues stay empty
    fut = svc.kget_many(0, ["a", "b", "c"])
    assert fut.done
    assert fut.value == [("ok", NOTFOUND)] * 3
    assert svc._queue_rounds[0] == 0 and not svc.queues[0]

    # mixed: only the allocatable key queues a round
    fut = svc.kput_many(0, ["x", "y"], [b"1", b"2"])
    assert not fut.done
    assert svc._queue_rounds[0] == 1     # y had no slot: pre-failed
    res = settle(rt, fut)
    assert res[0][0] == "ok" and res[1] == "failed"
    svc.stop()


def test_kget_many_want_vsn():
    """Batch reads honor the kget_vsn contract."""
    rt, svc = make(n_ens=1)
    put = settle(rt, svc.kput_many(0, ["a", "b"], [b"1", b"2"]))
    got = settle(rt, svc.kget_many(0, ["a", "b", "nope"],
                                   want_vsn=True))
    assert got[0] == ("ok", b"1", tuple(put[0][1]))
    assert got[1] == ("ok", b"2", tuple(put[1][1]))
    assert got[2] == ("ok", NOTFOUND, (0, 0))
    svc.stop()


def test_stats_queued_ops_counts_batch_rounds():
    """Review finding: stats() must count ROUNDS, not queue entries —
    a 30-key batch is 30 queued ops, not 1."""
    rt, svc = make(n_ens=1, n_slots=64)
    svc.kput_many(0, [f"k{i}" for i in range(30)],
                  [b"v"] * 30)
    assert svc.stats()["queued_ops"] == 30
    while any(svc.queues):
        svc.flush()
    assert svc.stats()["queued_ops"] == 0
    svc.stop()


def test_kput_many_length_mismatch_rejected():
    """Network-exposed trust boundary: mismatched keys/values raise
    (never a silently-truncated batch whose future can't resolve)."""
    rt, svc = make(n_ens=1)
    with pytest.raises(ValueError):
        svc.kput_many(0, ["a", "b"], [b"1"])
    svc.stop()


def test_watcher_unwatches_itself_mid_callback():
    """A one-shot watcher deregistering inside its callback must not
    skip sibling watchers (snapshot iteration)."""
    rt, svc = make(n_ens=1)
    events = []

    def one_shot(e, old, new):
        if old == new:
            return  # skip the registration-time status notify
        svc.unwatch_leader(0, one_shot)
        events.append(("one", old, new))

    svc.watch_leader(0, one_shot)
    svc.watch_leader(0, lambda e, old, new: events.append(("two", old,
                                                           new)))
    n = len(events)
    assert settle(rt, svc.kput(0, "k", b"v"))[0] == "ok"
    fired = events[n:]
    assert ("one", -1, int(svc.leader_np[0])) in fired
    assert ("two", -1, int(svc.leader_np[0])) in fired
    # one_shot is gone; two remains
    assert svc._leader_watchers[0] != []
    assert one_shot not in svc._leader_watchers[0]
    svc.stop()


def test_kupdate_many_cas_semantics():
    """Batch CAS: per-key version compare, (0,0) = create-if-missing,
    stale versions fail cleanly, chains survive crash."""
    rt, svc = make(n_ens=1)
    put = settle(rt, svc.kput_many(0, ["a", "b"], [b"1", b"2"]))
    vsn_a, vsn_b = tuple(put[0][1]), tuple(put[1][1])

    res = settle(rt, svc.kupdate_many(
        0, ["a", "b", "c"],
        [vsn_a, (9, 9), (0, 0)],         # ok / stale / create
        [b"a2", b"b2", b"c1"]))
    assert res[0][0] == "ok"
    assert res[1] == "failed"            # stale vsn: definitive reject
    assert res[2][0] == "ok"             # create-if-missing
    assert settle(rt, svc.kget_many(0, ["a", "b", "c"])) == \
        [("ok", b"a2"), ("ok", b"2"), ("ok", b"c1")]
    # the stale CAS's payload must not leak
    assert len(svc.values) == 3
    svc.stop()


def test_kdelete_many_and_recycle():
    rt, svc = make(n_ens=1, n_slots=3)
    assert all(r[0] == "ok" for r in settle(
        rt, svc.kput_many(0, ["a", "b", "c"], [b"1", b"2", b"3"])))
    res = settle(rt, svc.kdelete_many(0, ["a", "c", "nope"]))
    assert res[0][0] == "ok" and res[1][0] == "ok"
    assert res[2] == ("ok", NOTFOUND)
    assert settle(rt, svc.kget_many(0, ["a", "b", "c"])) == \
        [("ok", NOTFOUND), ("ok", b"2"), ("ok", NOTFOUND)]
    # slots recycled: two fresh keys fit in the 3-slot ensemble
    res = settle(rt, svc.kput_many(0, ["x", "y"], [b"8", b"9"]))
    assert all(r[0] == "ok" for r in res)
    assert len(svc.values) == 3  # b, x, y — deleted payloads released
    svc.stop()


def test_batch_cas_and_delete_survive_crash(tmp_path):
    rt, svc = make(n_ens=1, data_dir=str(tmp_path / "d"))
    put = settle(rt, svc.kput_many(0, ["a", "b"], [b"1", b"2"]))
    assert all(r[0] == "ok" for r in put)
    up = settle(rt, svc.kupdate_many(0, ["a"], [tuple(put[0][1])],
                                     [b"a2"]))
    assert up[0][0] == "ok"
    dl = settle(rt, svc.kdelete_many(0, ["b"]))
    assert dl[0][0] == "ok"
    svc.stop()
    svc._wal.close()

    rt2 = Runtime(seed=63)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "d"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "d"))
    assert settle(rt2, svc2.kget_many(0, ["a", "b"])) == \
        [("ok", b"a2"), ("ok", NOTFOUND)]
    svc2.stop()


def test_batch_ops_on_dead_ensemble_fail():
    """All four batch ops reject a destroyed ensemble with 'failed' —
    never a fake ('ok', NOTFOUND) for an unserved delete."""
    rt = Runtime(seed=64)
    svc = BatchedEnsembleService(rt, 2, 3, 4, tick=0.005,
                                 config=fast_test_config(),
                                 dynamic=True)
    e = svc.create_ensemble("x")
    assert svc.destroy_ensemble("x")
    assert svc.kput_many(e, ["k"], [b"v"]).value == ["failed"]
    assert svc.kget_many(e, ["k"]).value == ["failed"]
    assert svc.kupdate_many(e, ["k"], [(0, 0)], [b"v"]).value == \
        ["failed"]
    assert svc.kdelete_many(e, ["k"]).value == ["failed"]
    svc.stop()
