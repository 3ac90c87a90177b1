"""Continuous durability of acked service writes (WAL).

The reference never acks a write that isn't on disk
(``riak_ensemble_basic_backend.erl:120-125`` synchronous save_data;
facts coalesce within 50 ms, ``riak_ensemble_storage.erl:86-103``).
These tests pin the same contract on the scale path: every write whose
future resolved 'ok' survives a crash — including a kill -9 with no
checkpoint ever taken — and replays into a serveable service.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("orbax.checkpoint")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService,
)
from riak_ensemble_tpu.parallel.wal import (  # noqa: E402
    PyLogStore, ServiceWAL,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_durable(tmp_path, n_ens=4, n_peers=3, n_slots=4, **kw):
    runtime = Runtime(seed=11)
    svc = BatchedEnsembleService(
        runtime, n_ens, n_peers, n_slots, tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"), **kw)
    return runtime, svc


def settle(runtime, fut, timeout=5.0):
    return runtime.await_future(fut, timeout)


def crash(svc):
    """Simulate a crash: release the WAL handle (so restore re-reads
    the on-disk bytes, not a shared in-memory map) WITHOUT any
    checkpoint/flush cleanup."""
    svc.stop()
    if svc._wal is not None:
        svc._wal.close()


# -- PyLogStore unit ---------------------------------------------------------


def test_pylogstore_roundtrip_and_latest_wins(tmp_path):
    p = str(tmp_path / "log")
    st = PyLogStore(p)
    st.store(("kv", 0, 1), ("a", 7))
    st.store(("kv", 0, 1), ("a", 8))   # latest record per key wins
    st.store(("kv", 1, 0), ("b", 9))
    st.delete(("kv", 1, 0))
    st.sync()
    st.close()

    st2 = PyLogStore(p)
    assert st2.count() == 1
    assert st2.fetch(("kv", 0, 1)) == ("a", 8)
    assert st2.fetch(("kv", 1, 0)) is None
    st2.close()


def test_pylogstore_torn_tail_dropped(tmp_path):
    p = str(tmp_path / "log")
    st = PyLogStore(p)
    st.store("k1", "v1")
    st.store("k2", "v2")
    st.sync()
    st.close()
    size = os.path.getsize(p)
    with open(p, "r+b") as f:       # tear the last record mid-frame
        f.truncate(size - 3)
        f.seek(0, 2)
        f.write(b"\x00garbage")     # and splat junk after the tear

    st2 = PyLogStore(p)
    assert st2.fetch("k1") == "v1"  # intact prefix survives
    assert st2.fetch("k2") is None  # torn record dropped, not mangled
    st2.close()


# -- service crash / restore -------------------------------------------------


def test_acked_writes_survive_crash_without_any_checkpoint(tmp_path):
    """kill before the FIRST save(): restore comes from META + WAL."""
    runtime, svc = make_durable(tmp_path)
    for e in range(4):
        assert settle(runtime, svc.kput(e, "k", b"v%d" % e))[0] == "ok"
    assert settle(runtime, svc.kput(0, "other", b"x"))[0] == "ok"
    assert settle(runtime, svc.kdelete(3, "k"))[0] == "ok"
    crash(svc)

    rt2 = Runtime(seed=12)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    for e in range(3):
        assert settle(rt2, svc2.kget(e, "k")) == ("ok", b"v%d" % e)
    assert settle(rt2, svc2.kget(0, "other")) == ("ok", b"x")
    assert settle(rt2, svc2.kget(3, "k")) == ("ok", NOTFOUND)
    # restored service keeps serving (and logging) writes
    assert settle(rt2, svc2.kput(1, "k", b"post"))[0] == "ok"
    assert settle(rt2, svc2.kget(1, "k")) == ("ok", b"post")


def test_acked_writes_survive_crash_after_checkpoint(tmp_path):
    """Checkpoint + later WAL records compose: post-checkpoint acks
    replay over the checkpoint image."""
    runtime, svc = make_durable(tmp_path)
    assert settle(runtime, svc.kput(0, "a", b"1"))[0] == "ok"
    assert settle(runtime, svc.kput(1, "z", b"z1"))[0] == "ok"
    svc.save()
    assert settle(runtime, svc.kput(0, "b", b"2"))[0] == "ok"
    assert settle(runtime, svc.kdelete(0, "a"))[0] == "ok"
    assert settle(runtime, svc.kput(1, "z", b"z2"))[0] == "ok"
    crash(svc)

    rt2 = Runtime(seed=13)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    assert settle(rt2, svc2.kget(0, "a")) == ("ok", NOTFOUND)
    assert settle(rt2, svc2.kget(0, "b")) == ("ok", b"2")
    assert settle(rt2, svc2.kget(1, "z")) == ("ok", b"z2")


def test_slot_recycled_to_new_key_across_crash(tmp_path):
    """A checkpoint-era key whose slot was recycled to ANOTHER key
    after the checkpoint must read notfound after replay (stale
    mapping sweep) while the new key serves."""
    runtime, svc = make_durable(tmp_path, n_ens=1, n_peers=3, n_slots=1)
    assert settle(runtime, svc.kput(0, "old", b"o"))[0] == "ok"
    svc.save()
    assert settle(runtime, svc.kdelete(0, "old"))[0] == "ok"
    # single slot: the delete's recycle must free it for the new key
    assert settle(runtime, svc.kput(0, "new", b"n"))[0] == "ok"
    crash(svc)

    rt2 = Runtime(seed=14)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    assert settle(rt2, svc2.kget(0, "new")) == ("ok", b"n")
    assert settle(rt2, svc2.kget(0, "old")) == ("ok", NOTFOUND)
    assert len(svc2.free_slots[0]) == 0


def test_membership_change_survives_crash(tmp_path):
    runtime, svc = make_durable(tmp_path, n_ens=2, n_peers=5)
    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"
    nv = np.ones((2, 5), bool)
    nv[:, 4] = False
    assert svc.update_members(np.ones(2, bool), nv).all()
    crash(svc)

    rt2 = Runtime(seed=15)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    assert (svc2.member_np == nv).all()
    # device view agrees: peer 4 down must not block quorum
    svc2.set_peer_up(0, 4, False)
    svc2.set_peer_up(1, 4, False)
    assert settle(rt2, svc2.kget(0, "k")) == ("ok", b"v")
    assert settle(rt2, svc2.kput(1, "m", b"w"))[0] == "ok"


def test_wal_rotates_on_save_and_old_generations_pruned(tmp_path):
    runtime, svc = make_durable(tmp_path)
    assert settle(runtime, svc.kput(0, "a", b"1"))[0] == "ok"
    assert svc._wal.count > 0
    svc.save()
    assert svc._wal.count == 0          # fresh generation
    names = os.listdir(tmp_path / "data")
    assert sum(n.startswith("wal.") for n in names) == 1
    assert f"wal.{svc._current_ckpt(str(tmp_path / 'data'))}" in names
    svc.stop()


def test_wal_auto_compacts_into_checkpoint(tmp_path):
    runtime, svc = make_durable(tmp_path, n_slots=8,
                                wal_compact_records=3)
    for i in range(6):
        assert settle(runtime,
                      svc.kput(0, f"k{i}", b"v%d" % i))[0] == "ok"
    # records crossed the bound -> a checkpoint happened, WAL rotated
    assert svc._current_ckpt(str(tmp_path / "data")) >= 1
    assert svc._wal.count < 3
    crash(svc)
    rt2 = Runtime(seed=16)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    for i in range(6):
        assert settle(rt2, svc2.kget(0, f"k{i}")) == ("ok", b"v%d" % i)


def test_bulk_execute_writes_survive_crash(tmp_path):
    """Host-array execute() commits are WAL'd (the result is the ack)
    and replay as inline payloads."""
    from riak_ensemble_tpu.ops import engine as eng

    runtime, svc = make_durable(tmp_path, n_ens=4, n_slots=4)
    kind = np.full((2, 4), eng.OP_PUT, np.int32)
    slot = np.tile(np.array([[0], [1]], np.int32), (1, 4))
    val = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    committed, _, _, _ = svc.execute(kind, slot, val)
    assert committed.all()
    crash(svc)

    rt2 = Runtime(seed=17)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=None,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    gk = np.full((2, 4), eng.OP_GET, np.int32)
    committed, get_ok, found, value = svc2.execute(
        gk, slot, np.zeros((2, 4), np.int32))
    assert get_ok.all() and found.all()
    np.testing.assert_array_equal(value, val)


def test_kill9_subprocess_acked_writes_survive(tmp_path):
    """The gold test: a separate OS process acks writes then dies via
    os._exit (no cleanup, no atexit, no checkpoint); the parent
    restores from disk and finds every acked write."""
    data = str(tmp_path / "data")
    child = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        from riak_ensemble_tpu.config import fast_test_config
        from riak_ensemble_tpu.parallel.batched_host import (
            BatchedEnsembleService)
        from riak_ensemble_tpu.runtime import Runtime
        rt = Runtime(seed=1)
        svc = BatchedEnsembleService(rt, 2, 3, 4, tick=0.005,
                                     config=fast_test_config(),
                                     data_dir={data!r})
        futs = [svc.kput(e, "k%d" % i, b"v%d%d" % (e, i))
                for e in range(2) for i in range(3)]
        for f in futs:
            assert rt.await_future(f, 5.0)[0] == "ok", f.value
        print("ACKED", flush=True)
        os._exit(1)   # kill -9 analog: nothing runs after the acks
    """)
    proc = subprocess.run([sys.executable, "-c", child],
                          capture_output=True, text=True, timeout=240)
    assert "ACKED" in proc.stdout, proc.stderr[-2000:]
    assert proc.returncode == 1

    rt2 = Runtime(seed=18)
    svc2 = BatchedEnsembleService.restore(
        rt2, data, tick=0.005, config=fast_test_config(), data_dir=data)
    for e in range(2):
        for i in range(3):
            assert settle(rt2, svc2.kget(e, "k%d" % i)) == \
                ("ok", b"v%d%d" % (e, i))


def test_pure_python_wal_fallback(tmp_path, monkeypatch):
    """With the native treestore unavailable the PyLogStore path gives
    the same durability."""
    from riak_ensemble_tpu.synctree import native_store

    monkeypatch.setattr(native_store, "available", lambda: False)
    runtime, svc = make_durable(tmp_path)
    assert isinstance(svc._wal._store, PyLogStore)
    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"
    crash(svc)

    rt2 = Runtime(seed=19)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=0.005,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    assert settle(rt2, svc2.kget(0, "k")) == ("ok", b"v")


def test_wal_generation_api(tmp_path):
    w = ServiceWAL.open_gen(str(tmp_path), 0)
    w.log([(("kv", 0, 0), ("k", 1, 1, 1, b"v", False))])
    assert w.count == 1
    w2 = ServiceWAL.rotate(str(tmp_path), 1, w)
    assert w2.count == 0
    assert os.path.isdir(ServiceWAL.gen_path(str(tmp_path), 1))
    assert not os.path.isdir(ServiceWAL.gen_path(str(tmp_path), 0))
    w2.close()


def test_pylogstore_double_crash_records_after_tear_survive(tmp_path):
    """Review finding: a torn tail must be TRUNCATED at reopen, or
    every record appended after it is unreachable at the next replay
    (acked writes silently lost on the second crash)."""
    p = str(tmp_path / "log")
    st = PyLogStore(p)
    st.store("k1", "v1")
    st.store("k2", "v2")
    st.sync()
    st.close()
    size = os.path.getsize(p)
    with open(p, "r+b") as f:
        f.truncate(size - 3)          # crash #1: torn k2 record

    st2 = PyLogStore(p)               # reopen truncates the tear
    assert st2.fetch("k1") == "v1" and st2.fetch("k2") is None
    st2.store("k3", "v3")             # acked after the first crash
    st2.sync()
    st2.close()                       # crash #2 (clean close is fine)

    st3 = PyLogStore(p)
    assert st3.fetch("k1") == "v1"
    assert st3.fetch("k3") == "v3", "record after torn tail lost"
    st3.close()


def test_pylogstore_foreign_prefix_starts_fresh(tmp_path):
    """Review finding: a non-MAGIC prefix must not be appended to —
    records after it would never replay.  The foreign bytes move
    aside and the log starts fresh."""
    p = str(tmp_path / "log")
    with open(p, "wb") as f:
        f.write(b"NOTAWALFILE")
    st = PyLogStore(p)
    assert st.quarantines == 1
    st.store("k", "v")
    st.sync()
    st.close()
    st2 = PyLogStore(p)
    assert st2.fetch("k") == "v"
    st2.close()
    assert os.path.exists(p + ".corrupt.0")


def test_pylogstore_second_quarantine_keeps_first_evidence(
        tmp_path, monkeypatch):
    """ISSUE 15 satellite: a second corruption must not clobber the
    first quarantined log — monotonic ``.corrupt.<n>`` suffixes, and
    the count rides stats() via ServiceWAL."""
    from riak_ensemble_tpu.synctree import native_store

    monkeypatch.setattr(native_store, "available", lambda: False)
    p = str(tmp_path / "w" / "wal")
    for i in (0, 1):
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "wb") as f:
            f.write(b"GARBAGE-%d" % i)
        w = ServiceWAL(str(tmp_path / "w"))
        assert w.stats()["quarantines"] == 1
        w.close()
    names = sorted(n for n in os.listdir(tmp_path / "w")
                   if ".corrupt." in n)
    assert names == ["wal.corrupt.0", "wal.corrupt.1"]
    with open(str(tmp_path / "w" / "wal.corrupt.0"), "rb") as f:
        assert f.read() == b"GARBAGE-0", "first evidence clobbered"


def test_buffer_mode_reaches_kernel_before_ack(tmp_path, monkeypatch):
    """Review finding: buffer mode promises process-crash safety, so
    log() must flush userspace buffers (another process / a fresh
    reader must see the records without any close)."""
    from riak_ensemble_tpu.synctree import native_store

    monkeypatch.setattr(native_store, "available", lambda: False)
    w = ServiceWAL(str(tmp_path / "w"), sync_mode="buffer")
    w.log([(("kv", 0, 0), ("k", 1, 1, 1, b"v", False))])
    # a fresh reader of the same file (no close on the writer!)
    rd = PyLogStore(os.path.join(str(tmp_path / "w"), "wal"))
    assert rd.fetch(("kv", 0, 0)) is not None, \
        "buffered record never reached the kernel"
    rd.close()
    w.close()


def test_recycled_row_inherits_no_pipeline_or_down_marks(tmp_path):
    """Review finding: a recycled row must not inherit the dead
    tenant's pending membership change or peer-down marks."""
    from riak_ensemble_tpu.runtime import Runtime

    rt = Runtime(seed=41)
    svc = BatchedEnsembleService(rt, 2, 3, 4, tick=0.005,
                                 config=fast_test_config(),
                                 dynamic=True)
    e = svc.create_ensemble("old")
    # leaderless desired change: no leader yet -> stays desired
    nv = np.zeros((2, 3), bool)
    nv[:, :2] = True
    sel = np.zeros(2, bool)
    sel[e] = True
    svc.update_members(sel, nv)
    assert svc._desired_mask[e]
    svc.set_peer_up(e, 2, False)      # old-tenant down mark
    assert svc.destroy_ensemble("old")

    e2 = svc.create_ensemble("new")
    assert e2 == e
    assert not svc._desired_mask[e2] and not svc._pending_mask[e2]
    assert svc.up[e2].all()
    # elect + serve with FULL membership; a later all-False-sel
    # update_members call must not re-propose the dead tenant's view
    f = svc.kput(e2, "k", b"v")
    assert rt.await_future(f, 5.0)[0] == "ok"
    svc.update_members(np.zeros(2, bool), nv)
    assert (svc.member_np[e2] == np.ones(3, bool)).all(), \
        "dead tenant's membership change applied to the new tenant"
    svc.stop()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_crash_point_fuzz_no_acked_write_lost(tmp_path, seed):
    """Randomized crash-point fuzz: a child process runs a random
    keyed workload (puts/deletes/batch puts, interleaved across
    ensembles), appends every ACKED op to its own fsync'd side log
    the instant the future resolves, and os._exit()s at a random op
    count.  The parent restores from the data dir and asserts the
    final acked state of every key is exactly what the restored
    service serves — the sc.erl 'Data loss!' check (test/sc.erl:
    835-880) applied to crash recovery."""
    data = str(tmp_path / "data")
    acklog = str(tmp_path / "acks")
    child = textwrap.dedent(f"""
        import os, pickle, sys
        sys.path.insert(0, {REPO!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        from riak_ensemble_tpu.config import fast_test_config
        from riak_ensemble_tpu.parallel.batched_host import (
            BatchedEnsembleService)
        from riak_ensemble_tpu.runtime import Runtime

        rng = np.random.default_rng({seed})
        rt = Runtime(seed={seed})
        svc = BatchedEnsembleService(rt, 3, 3, 8, tick=0.005,
                                     config=fast_test_config(),
                                     data_dir={data!r})
        ack_f = open({acklog!r}, "ab")
        def record(op, e, key, val):
            ack_f.write(pickle.dumps((op, e, key, val)))
            ack_f.flush(); os.fsync(ack_f.fileno())

        stop_at = int(rng.integers(5, 40))
        done = 0
        while done < stop_at:
            e = int(rng.integers(3))
            r = rng.random()
            if r < 0.5:
                key = f"k{{int(rng.integers(5))}}"
                val = b"v%d" % int(rng.integers(1000))
                if rt.await_future(svc.kput(e, key, val),
                                   10.0)[0] == "ok":
                    record("put", e, key, val)
            elif r < 0.7:
                keys = [f"b{{i}}" for i in range(3)]
                vals = [b"w%d" % int(rng.integers(1000))
                        for _ in range(3)]
                res = rt.await_future(
                    svc.kput_many(e, keys, vals), 10.0)
                for kk, vv, rr in zip(keys, vals, res):
                    if rr[0] == "ok":
                        record("put", e, kk, vv)
            else:
                key = f"k{{int(rng.integers(5))}}"
                rr = rt.await_future(svc.kdelete(e, key), 10.0)
                if isinstance(rr, tuple) and rr[0] == "ok":
                    record("del", e, key, None)
            done += 1
        print("CRASHED_AT", done, flush=True)
        os._exit(1)
    """)
    proc = subprocess.run([sys.executable, "-c", child],
                          capture_output=True, text=True, timeout=300)
    assert "CRASHED_AT" in proc.stdout, proc.stderr[-2000:]

    # final acked value per (ens, key), in ack order
    import pickle
    expect = {}
    with open(acklog, "rb") as f:
        while True:
            try:
                op, e, key, val = pickle.load(f)
            except EOFError:
                break
            if op == "put":
                expect[(e, key)] = val
            else:
                expect[(e, key)] = None

    rt2 = Runtime(seed=seed + 100)
    svc2 = BatchedEnsembleService.restore(
        rt2, data, tick=0.005, config=fast_test_config(),
        data_dir=data)
    for (e, key), val in expect.items():
        got = settle(rt2, svc2.kget(e, key))
        assert got[0] == "ok", (e, key, got)
        want = NOTFOUND if val is None else val
        assert got[1] == want, \
            f"acked write lost/stale at {(e, key)}: {got[1]!r} != {want!r}"
    svc2.stop()


def test_buffer_mode_delete_reaches_kernel_before_ack(tmp_path,
                                                      monkeypatch):
    """advice r3: delete() must honor buffer mode's process-crash
    floor exactly like log() — a destroy's kv deletions sitting in the
    userspace stdio buffer would die with the process and replay the
    destroyed tenant's records into a recycled row."""
    from riak_ensemble_tpu.synctree import native_store

    monkeypatch.setattr(native_store, "available", lambda: False)
    w = ServiceWAL(str(tmp_path / "w"), sync_mode="buffer")
    w.log([(("kv", 0, 0), ("k", 1, 1, 1, b"v", False))])
    w.delete([("kv", 0, 0)])
    # a fresh reader of the same file (no close on the writer!)
    rd = PyLogStore(os.path.join(str(tmp_path / "w"), "wal"))
    assert rd.fetch(("kv", 0, 0)) is None, \
        "buffered deletion never reached the kernel"
    rd.close()
    w.close()


def test_device_resident_execute_is_logged_and_replays(tmp_path):
    """``jax.Array`` planes handed to execute() are read back at the
    door and are from there an ordinary host call: the committed
    writes are WAL'd before the call returns (the result is the ack)
    and replay after a crash.  (They used to skip the WAL because of
    their argument's type.)"""
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng

    runtime, svc = make_durable(tmp_path)
    n = svc.n_ens
    kind = jnp.full((2, n), eng.OP_PUT, jnp.int32)
    slot = jnp.asarray(np.tile(np.array([[0], [1]], np.int32), (1, n)))
    val = jnp.arange(1, 2 * n + 1, dtype=jnp.int32).reshape(2, n)
    before = svc._wal.count
    committed, *_ = svc.execute(kind, slot, val)
    assert committed.all()
    assert svc._wal.count >= before + 2 * n, "writes were not logged"
    crash(svc)

    rt2 = Runtime(seed=17)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "data"), tick=None,
        config=fast_test_config(), data_dir=str(tmp_path / "data"))
    _, get_ok, found, value = svc2.execute(
        jnp.full((2, n), eng.OP_GET, jnp.int32), slot,
        jnp.zeros((2, n), jnp.int32))
    assert get_ok.all() and found.all()
    np.testing.assert_array_equal(value, np.asarray(val))
    svc2.stop()
