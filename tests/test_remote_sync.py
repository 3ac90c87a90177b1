"""Streamed Merkle exchange across a REAL process boundary at 1M
segments (review r3 #7) — the ``test/synctree_remote.erl:24-38``
analog: two OS processes, each holding a 1M-segment device tree, a
level-by-level descent over the wire, and an asserted traffic ledger:
O(width · height · diffs), never O(keys)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from riak_ensemble_tpu.ops import hash as hashk  # noqa: E402
from riak_ensemble_tpu.synctree import remote_sync  # noqa: E402

SEGS = 16 ** 5  # 1M segments — the reference synctree's design scale
WIDTH = 16
N_DIFFS = 37
SEED = 424242


def _base_leaves():
    """Deterministic identical base tree on both sides."""
    idx = jnp.arange(SEGS, dtype=jnp.uint32)
    return hashk.leaf_hash(idx, idx * 7 + 1)


def _mutations():
    rng = np.random.default_rng(SEED)
    ids = rng.choice(SEGS, N_DIFFS, replace=False).astype(np.int32)
    new = jnp.asarray(
        rng.integers(0, 2 ** 32, (N_DIFFS, hashk.LANES)).astype(
            np.uint32))
    return jnp.asarray(ids), new


_CHILD = textwrap.dedent(f"""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    from riak_ensemble_tpu.ops import hash as hashk
    from riak_ensemble_tpu.synctree import remote_sync

    SEGS = {SEGS}; WIDTH = {WIDTH}; N_DIFFS = {N_DIFFS}; SEED = {SEED}
    idx = jnp.arange(SEGS, dtype=jnp.uint32)
    leaves = hashk.leaf_hash(idx, idx * 7 + 1)
    levels = hashk.build(leaves, width=WIDTH)
    rng = np.random.default_rng(SEED)
    ids = rng.choice(SEGS, N_DIFFS, replace=False).astype(np.int32)
    new = jnp.asarray(rng.integers(0, 2 ** 32,
                      (N_DIFFS, hashk.LANES)).astype(np.uint32))
    levels = hashk.update(levels, jnp.asarray(ids), new, width=WIDTH)
    jax.block_until_ready(levels)
    srv = remote_sync.TreeSyncServer(levels)
    print(f"port={{srv.port}}", flush=True)
    import time
    time.sleep(600)
""")


def test_streamed_exchange_1m_segments_across_processes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", _CHILD],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    try:
        line = proc.stdout.readline()
        assert line.startswith("port="), proc.stderr.read()[-3000:]
        port = int(line.split("=")[1])

        local = hashk.build(_base_leaves(), width=WIDTH)
        jax.block_until_ready(local)
        found, stats = remote_sync.sync_diff(local, "127.0.0.1", port,
                                             width=WIDTH)

        # -- correctness: exactly the mutated segments found ----------
        ids, _ = _mutations()
        assert sorted(found.tolist()) == sorted(
            np.asarray(ids).tolist())

        # -- the traffic bound (synctree.erl:372-417 premise) ---------
        height = len(local)          # root..leaves level count
        # one request per level + meta, regardless of key count
        assert stats["messages"] <= height + 1, stats
        # visited nodes match the DEVICE-side cost model exactly:
        # children of differing parents only
        remote_levels = hashk.update(local, *(_mutations()),
                                     width=WIDTH)
        expect_cost = np.asarray(
            hashk.exchange_cost(local, remote_levels, width=WIDTH))
        assert stats["visited"] == expect_cost.tolist(), stats
        # O(width·height·diffs) bytes — and monumentally smaller than
        # shipping the key space (the O(keys) failure mode)
        node_bytes = hashk.LANES * 4
        bound = (1 + N_DIFFS * WIDTH * height) * node_bytes * 2
        assert stats["bytes_rx"] <= bound, (stats, bound)
        tree_bytes = SEGS * node_bytes
        assert stats["bytes_rx"] < tree_bytes / 100, \
            f"exchange shipped {stats['bytes_rx']}B of a " \
            f"{tree_bytes}B key space"
    finally:
        proc.kill()


def test_exchange_identical_trees_costs_one_node():
    """Equal trees: the descent stops at the root — height messages
    never happen, only the root compare."""
    segs = 16 ** 3
    idx = jnp.arange(segs, dtype=jnp.uint32)
    levels = hashk.build(hashk.leaf_hash(idx, idx), width=WIDTH)
    srv = remote_sync.TreeSyncServer(levels)
    try:
        found, stats = remote_sync.sync_diff(levels, "127.0.0.1",
                                             srv.port, width=WIDTH)
        assert found.size == 0
        assert stats["visited"][0] == 1
        assert sum(stats["visited"]) == 1  # nothing below the root
    finally:
        srv.close()


def test_exchange_shape_mismatch_rejected():
    segs = 16 ** 2
    idx = jnp.arange(segs, dtype=jnp.uint32)
    levels = hashk.build(hashk.leaf_hash(idx, idx), width=WIDTH)
    srv = remote_sync.TreeSyncServer(levels)
    try:
        idx2 = jnp.arange(segs * WIDTH, dtype=jnp.uint32)
        bigger = hashk.build(hashk.leaf_hash(idx2, idx2), width=WIDTH)
        with pytest.raises(ValueError):
            remote_sync.sync_diff(bigger, "127.0.0.1", srv.port,
                                  width=WIDTH)
    finally:
        srv.close()
