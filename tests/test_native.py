"""Native C++ components: monotonic clock (the clock-NIF role,
c_src/riak_ensemble_clock.c) and the treestore engine (the eleveldb
role, synctree_leveldb.erl) — plus ensemble_tests_pure.erl parity
(clock monotonicity).
"""

import pytest

from riak_ensemble_tpu.synctree import native_store
from riak_ensemble_tpu.utils import clock, native

needs_native = pytest.mark.skipif(native.load() is None,
                                  reason="native toolchain unavailable")


# -- clock (ensemble_tests_pure.erl monotonicity test) ----------------------


def test_clock_monotonic():
    readings = [clock.monotonic_time_ns() for _ in range(1000)]
    assert all(b >= a for a, b in zip(readings, readings[1:]))
    assert readings[-1] > 0


def test_clock_ms_coherent():
    ms = clock.monotonic_time_ms()
    ns = clock.monotonic_time_ns()
    assert 0 <= ns // 1_000_000 - ms < 10_000


@needs_native
def test_native_clock_loaded():
    lib = native.load()
    t1 = lib.retpu_monotonic_time_ns()
    t2 = lib.retpu_monotonic_time_ns()
    assert 0 < t1 <= t2


# -- treestore engine -------------------------------------------------------


@needs_native
def test_store_basic(tmp_path):
    be = native_store.NativeBackend(str(tmp_path / "t.db"))
    assert be.fetch(("x",)) is None
    be.store(("x",), {"a": b"1"})
    assert be.fetch(("x",)) == {"a": b"1"}
    assert be.exists(("x",))
    be.store(("x",), {"a": b"2"})
    assert be.fetch(("x",)) == {"a": b"2"}
    be.delete(("x",))
    assert not be.exists(("x",))
    be.close()


@needs_native
def test_store_key_scan_resumes_and_survives_mutation(tmp_path):
    """``keys()`` scans by index; the store resumes from its last
    position (a 100k-record WAL replay was quadratic without it), and
    a put or delete between two index reads must drop that position:
    index i is always the i-th key in order, as if walked from the
    start."""
    import ctypes

    import numpy as np

    be = native_store.NativeBackend(str(tmp_path / "scan.db"))
    for i in range(0, 40, 2):
        be.store_raw(b"k%02d" % i, b"v")

    def key_at(i):
        n = be._lib.retpu_store_key_at(be._handle, i, None, 0)
        if n < 0:
            return None
        buf = ctypes.create_string_buffer(n)
        assert be._lib.retpu_store_key_at(be._handle, i, buf, n) == n
        return buf.raw

    order = [b"k%02d" % i for i in range(0, 40, 2)]
    assert [key_at(i) for i in range(20)] == order
    assert key_at(20) is None
    assert key_at(7) == order[7]            # backwards re-seeks
    be.store_raw(b"k01", b"v")              # sorts before the cursor
    order.insert(1, b"k01")
    assert key_at(8) == order[8]
    be._lib.retpu_store_delete(be._handle, b"k00", 3)
    order.pop(0)
    assert [key_at(i) for i in (9, 10, 0)] == [order[9], order[10],
                                                order[0]]
    be.put_many_raw(np.frombuffer(b"k03v", np.uint8),
                    np.asarray([(0, 3, 3, 1)], np.int64))
    order = sorted(order + [b"k03"])
    assert [key_at(i) for i in range(len(order))] == order
    be.close()


@needs_native
def test_store_reload_and_compact(tmp_path):
    path = str(tmp_path / "t.db")
    be = native_store.NativeBackend(path)
    for i in range(500):
        be.store((1, i), i.to_bytes(4, "big"))
    for i in range(0, 500, 2):
        be.delete((1, i))
    be.compact()
    for i in range(500, 600):
        be.store((1, i), i.to_bytes(4, "big"))
    be.sync()
    assert be.count() == 250 + 100
    be.close()

    # reopen: snapshot + log replay reconstruct the same contents
    be2 = native_store.NativeBackend(path)
    assert be2.count() == 350
    assert be2.fetch((1, 1)) == (1).to_bytes(4, "big")
    assert be2.fetch((1, 0)) is None
    assert be2.fetch((1, 599)) == (599).to_bytes(4, "big")
    assert len(list(be2.keys())) == 350
    be2.close()


@needs_native
def test_store_shared_registry(tmp_path):
    """Two opens of one path share a single engine
    (synctree_leveldb.erl:52-83 shared-DB registry)."""
    path = str(tmp_path / "shared.db")
    a = native_store.NativeBackend(path)
    b = native_store.NativeBackend(path)
    a.store("k", b"v")
    assert b.fetch("k") == b"v"
    a.close()
    assert b.fetch("k") == b"v"  # refcounted: engine still open
    b.close()


@needs_native
def test_store_torn_tail_recovery(tmp_path):
    """A torn final log record is discarded; prior records survive
    (the WAL-framing guarantee the 4-copy CRC save format provides for
    facts — save.erl:49-56 spirit)."""
    path = str(tmp_path / "torn.db")
    be = native_store.NativeBackend(path)
    be.store("a", b"1")
    be.store("b", b"2")
    be.sync()
    be.close()

    with open(path + ".log", "ab") as f:
        f.write(b"\x00\x01\x02")  # garbage partial frame

    be2 = native_store.NativeBackend(path)
    assert be2.fetch("a") == b"1"
    assert be2.fetch("b") == b"2"
    assert be2.count() == 2
    be2.close()


# -- synctree over the native engine ---------------------------------------


@needs_native
def test_synctree_on_native_backend(tmp_path):
    from riak_ensemble_tpu.synctree.tree import SyncTree

    path = str(tmp_path / "tree.db")
    be = native_store.NativeBackend(path)
    t = SyncTree(tree_id=b"p1", segments=16**3, backend=be)
    for i in range(100, 0, -1):
        assert t.insert(i, (i * 10).to_bytes(8, "big")) is None
    assert t.get(42) == (420).to_bytes(8, "big")
    top = t.top_hash
    be.sync()
    be.close()

    be2 = native_store.NativeBackend(path)
    t2 = SyncTree(tree_id=b"p1", segments=16**3, backend=be2)
    assert t2.top_hash == top
    assert t2.get(42) == (420).to_bytes(8, "big")
    assert t2.verify()
    be2.close()


# -- resolve kernel (native/resolvekernel.cc) -------------------------------


def test_resolve_kernel_build_smoke():
    """The explicit $(RESOLVESO) make target builds and exports the
    full resolve-kernel ABI; a missing toolchain degrades to None
    (never an exception) — the graceful-degradation contract of
    utils/native.load_resolve."""
    lib = native.load_resolve()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    assert lib.retpu_resolve_version() >= 1
    for sym in ("retpu_resolve_unpack", "retpu_resolve_mirrors",
                "retpu_wal_encode", "retpu_delta_sections"):
        assert hasattr(lib, sym), sym


def test_enqueue_kernel_build_smoke():
    """The sibling enqueue kernel (native/enqueuekernel.cc) rides the
    SAME $(RESOLVESO) target and .so: when the resolve library builds,
    the enqueue symbols must be there too (a stale .so without them
    degrades through enqueue_native.get() -> None, never a crash)."""
    lib = native.load_resolve()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    assert hasattr(lib, "retpu_enqueue_pack")
    assert lib.retpu_enqueue_version() >= 1


@needs_native
def test_store_put_many_matches_per_record(tmp_path):
    """The arena batch append (the resolve kernel's WAL path) must
    leave byte-identical log files and store contents to per-record
    puts."""
    import numpy as np

    recs = [(b"k%d" % i, b"v%d" % (i * 7)) for i in range(20)]
    a = native_store.NativeBackend(str(tmp_path / "a.db"))
    for k, v in recs:
        a.store_raw(k, v)
    a.sync()
    a.close()
    arena = b"".join(k + v for k, v in recs)
    idx = []
    off = 0
    for k, v in recs:
        idx.append((off, len(k), off + len(k), len(v)))
        off += len(k) + len(v)
    # interleave a skipped (uncommitted) row: key_len 0 rows drop
    idx.insert(3, (0, 0, 0, 0))
    b = native_store.NativeBackend(str(tmp_path / "b.db"))
    b.put_many_raw(np.frombuffer(arena, np.uint8),
                   np.asarray(idx, np.int64))
    b.sync()
    b.close()
    la = open(str(tmp_path / "a.db") + ".log", "rb").read()
    lb = open(str(tmp_path / "b.db") + ".log", "rb").read()
    assert la == lb
    b2 = native_store.NativeBackend(str(tmp_path / "b.db"))
    assert b2.count() == len(recs)
    b2.close()


@needs_native
@pytest.mark.parametrize("seed", range(3))
def test_store_randomized_against_dict_model(tmp_path, seed):
    """Property sweep for the C++ store: random puts/overwrites/
    deletes interleaved with sync, compaction, and full close/reopen
    cycles must match a plain dict model exactly — keys, values, and
    counts (the synctree_eqc-style differential check for the
    eleveldb-role component)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    path = str(tmp_path / f"prop{seed}.db")
    be = native_store.NativeBackend(path)
    model = {}
    keyspace = [("k", int(i)) for i in range(40)]

    for step in range(600):
        r = rng.random()
        key = keyspace[int(rng.integers(len(keyspace)))]
        if r < 0.55:
            val = {"v": bytes(rng.integers(0, 256, int(rng.integers(0, 24)),
                                           dtype=np.uint8)),
                   "n": int(rng.integers(1 << 30))}
            be.store(key, val)
            model[key] = val
        elif r < 0.75:
            be.delete(key)
            model.pop(key, None)
        elif r < 0.85:
            be.sync()
        elif r < 0.93:
            be.compact()
        else:
            be.close()
            be = native_store.NativeBackend(path)  # reopen: WAL replay

        if step % 97 == 0:  # periodic full-state comparison
            assert be.count() == len(model)
            for k in keyspace:
                assert be.fetch(k) == model.get(k), (seed, step, k)

    be.close()
    be = native_store.NativeBackend(path)
    assert be.count() == len(model)
    assert sorted(map(repr, be.keys())) == sorted(map(repr, model))
    for k, v in model.items():
        assert be.fetch(k) == v
    be.close()
