"""synctree_jax kernel: build/update equivalence, diff exactness,
corruption detection, exchange cost bound (SURVEY §5 long-context
analog; BASELINE.md ladder #4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from riak_ensemble_tpu.ops import hash as hashk

W = 4          # small width for exhaustive tests
S = W ** 3     # 64 segments


def rand_leaves(rng, n=S):
    return jnp.asarray(
        rng.integers(0, 2**32, (n, hashk.LANES), dtype=np.uint32))


def test_build_shapes():
    rng = np.random.default_rng(0)
    levels = hashk.build(rand_leaves(rng), width=W)
    assert [lv.shape[0] for lv in levels] == [1, W, W * W, S]


def test_update_matches_rebuild():
    """Incremental update == full rebuild (the always-up-to-date
    property must not drift from the ground truth)."""
    rng = np.random.default_rng(1)
    leaves = rand_leaves(rng)
    levels = hashk.build(leaves, width=W)

    seg_ids = jnp.asarray([3, 17, 17, 63])  # includes a duplicate
    new = rand_leaves(rng, 4)
    updated = hashk.update(levels, seg_ids, new, width=W)

    ref_leaves = np.asarray(leaves).copy()
    for i, seg in enumerate(np.asarray(seg_ids)):
        ref_leaves[seg] = np.asarray(new)[i]
    rebuilt = hashk.build(jnp.asarray(ref_leaves), width=W)

    for lu, lr in zip(updated, rebuilt):
        np.testing.assert_array_equal(np.asarray(lu), np.asarray(lr))


def test_diff_exact():
    rng = np.random.default_rng(2)
    leaves = rand_leaves(rng)
    a = hashk.build(leaves, width=W)
    changed = [5, 40]
    new = rand_leaves(rng, len(changed))
    b = hashk.update(a, jnp.asarray(changed), new, width=W)

    masks = hashk.diff_levels(a, b)
    leaf_mask = np.asarray(masks[-1])
    assert sorted(np.nonzero(leaf_mask)[0].tolist()) == changed
    # root differs too
    assert bool(np.asarray(masks[0])[0])


def test_diff_identical_is_empty():
    rng = np.random.default_rng(3)
    a = hashk.build(rand_leaves(rng), width=W)
    masks = hashk.diff_levels(a, a)
    assert not any(bool(np.asarray(m).any()) for m in masks)


def test_exchange_cost_bound():
    """One differing segment: the streamed exchange visits at most
    width buckets per level (O(width * height * diffs)), far below the
    S-bucket full scan."""
    rng = np.random.default_rng(4)
    a = hashk.build(rand_leaves(rng), width=W)
    b = hashk.update(a, jnp.asarray([11]), rand_leaves(rng, 1), width=W)
    costs = np.asarray(hashk.exchange_cost(a, b, width=W))
    assert costs[0] == 1
    assert (costs[1:] <= W).all()
    assert costs.sum() < S


def test_verify_detects_corruption():
    rng = np.random.default_rng(5)
    levels = list(hashk.build(rand_leaves(rng), width=W))
    clean = hashk.verify(tuple(levels), width=W)
    assert not any(bool(np.asarray(m).any()) for m in clean)

    # corrupt one inner bucket at level 2
    lv2 = np.asarray(levels[2]).copy()
    lv2[7] ^= 0xDEAD
    levels[2] = jnp.asarray(lv2)
    masks = hashk.verify(tuple(levels), width=W)
    # level-1 recompute-from-children mismatches at bucket 7's parent?
    # No: verify flags the STORED parent vs recomputed-from-children —
    # corrupting level 2 makes (a) level-1's stored value stale at
    # bucket 7//W and (b) level-2 recomputed-from-level-3 mismatch at
    # bucket 7.
    assert bool(np.asarray(masks[1])[7 // W]) or \
        bool(np.asarray(masks[2])[7])


def test_leaf_hash_version_sensitivity():
    h1 = hashk.leaf_hash(jnp.asarray([1]), jnp.asarray([1]))
    h2 = hashk.leaf_hash(jnp.asarray([1]), jnp.asarray([2]))
    h3 = hashk.leaf_hash(jnp.asarray([2]), jnp.asarray([1]))
    assert not np.array_equal(np.asarray(h1), np.asarray(h2))
    assert not np.array_equal(np.asarray(h1), np.asarray(h3))
    assert not np.array_equal(np.asarray(h2), np.asarray(h3))


def test_million_segment_build_compiles():
    """The production shape (1M segments, width 16 — synctree.erl
    :88-89) builds and updates under jit."""
    rng = np.random.default_rng(6)
    segs = 16 ** 5
    leaves = jnp.zeros((segs, hashk.LANES), jnp.uint32)
    levels = hashk.build(leaves, width=16)
    assert levels[0].shape == (1, hashk.LANES)
    ids = jnp.asarray(rng.integers(0, segs, 256))
    new = jnp.asarray(
        rng.integers(0, 2**32, (256, hashk.LANES), dtype=np.uint32))
    updated = hashk.update(levels, ids, new, width=16)
    leaf_mask = np.asarray(
        hashk.diff_levels(levels, updated)[-1])
    assert set(np.nonzero(leaf_mask)[0]) == set(np.asarray(ids).tolist())


def test_update_duplicate_seg_ids_last_write_wins():
    """A batch with duplicate segment ids is a sequence of inserts:
    the final occurrence must win deterministically (JAX scatter order
    with duplicates is otherwise unspecified)."""
    segs = 16 ** 2
    leaves = jnp.zeros((segs, hashk.LANES), jnp.uint32)
    levels = hashk.build(leaves, width=16)
    ids = jnp.asarray([7, 3, 7, 7, 3])
    rng = np.random.default_rng(5)
    new = jnp.asarray(rng.integers(0, 2 ** 32, (5, hashk.LANES),
                                   dtype=np.uint32))
    got = hashk.update(levels, ids, new, width=16)
    # sequential oracle
    want = levels
    for i in range(5):
        want = hashk.update(want, ids[i:i + 1], new[i:i + 1], width=16)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- fold quality: the detection properties the parallel-mix form
# -- claims (uniformity + avalanche + order sensitivity) -------------


def test_fold_avalanche():
    """A single flipped bit in any child flips ~half the parent bits
    (the corruption-detection property the round-4 parallel-mix fold
    must preserve from the chained form)."""
    rng = np.random.default_rng(0)
    children = np.asarray(rng.integers(0, 2**32, (16, hashk.LANES)),
                          dtype=np.uint32)
    base = np.asarray(hashk.fold(jnp.asarray(children)))
    fracs = []
    for trial in range(64):
        i = rng.integers(0, 16)
        lane = rng.integers(0, hashk.LANES)
        bit = rng.integers(0, 32)
        mut = children.copy()
        mut[i, lane] ^= np.uint32(1) << np.uint32(bit)
        out = np.asarray(hashk.fold(jnp.asarray(mut)))
        assert (out != base).any(), "flip went undetected"
        diff = np.bitwise_xor(out, base)
        nbits = sum(int(x).bit_count() for x in diff.ravel())
        fracs.append(nbits / (32 * hashk.LANES))
    mean = float(np.mean(fracs))
    assert 0.40 < mean < 0.60, f"avalanche degraded: {mean:.3f}"


def test_fold_order_and_position_sensitivity():
    """Swapping two distinct children, or moving a value to a
    different position among zeros, changes the parent (the position
    salt)."""
    rng = np.random.default_rng(1)
    children = np.asarray(rng.integers(0, 2**32, (16, hashk.LANES)),
                          dtype=np.uint32)
    base = np.asarray(hashk.fold(jnp.asarray(children)))
    swapped = children.copy()
    swapped[[2, 9]] = swapped[[9, 2]]
    assert (np.asarray(hashk.fold(jnp.asarray(swapped))) != base).any()

    for pos in range(1, 16):
        a = np.zeros((16, hashk.LANES), np.uint32)
        b = np.zeros((16, hashk.LANES), np.uint32)
        a[0] = 12345
        b[pos] = 12345
        assert (np.asarray(hashk.fold(jnp.asarray(a)))
                != np.asarray(hashk.fold(jnp.asarray(b)))).any(), pos


def test_fold_collision_smoke():
    """10k random child blocks -> 10k distinct parents (128-bit lanes
    make true collisions astronomically unlikely; a structural flaw in
    the mix would show up immediately)."""
    rng = np.random.default_rng(2)
    blocks = np.asarray(
        rng.integers(0, 2**32, (10_000, 16, hashk.LANES)),
        dtype=np.uint32)
    outs = np.asarray(hashk.fold(jnp.asarray(blocks)))
    view = {tuple(int(v) for v in row) for row in outs}
    assert len(view) == 10_000


def test_fold_compensated_swap_no_collision():
    """Regression (round-5 advice): format 2's fold pre-mixed children
    LINEARLY (child*C1 + pos*C2 + lane), so replacing children (a, b)
    at positions (p, q) with (b+d, a-d), d = (q-p)*C2*C1^-1 mod 2^32,
    preserved the pre-mix multiset and collided deterministically.
    Format 3 xors an avalanched position salt and multiplies by a
    per-position odd constant, so neither additive nor xor shifts can
    compensate a swap."""
    # C1^-1 mod 2^32 (C1 is odd, hence invertible)
    c1, c2 = 0xCC9E2D51, 0x1B873593
    c1_inv = pow(c1, -1, 2**32)
    rng = np.random.default_rng(7)
    for trial in range(100):
        children = np.asarray(
            rng.integers(0, 2**32, (16, hashk.LANES)), dtype=np.uint32)
        base = np.asarray(hashk.fold(jnp.asarray(children)))
        p, q = sorted(rng.choice(16, size=2, replace=False))
        d = np.uint32((int(q - p) * c2 * c1_inv) % 2**32)
        # the exact format-2 attack: additive-compensated swap
        add = children.copy()
        add[p] = children[q] + d
        add[q] = children[p] - d
        assert (np.asarray(hashk.fold(jnp.asarray(add))) != base).any(), \
            f"additive compensated swap collided (trial {trial})"
        # the analogous xor-compensated swap (defeats a salt-only fix)
        for delta in (np.uint32(d), np.uint32(trial + 1)):
            xr = children.copy()
            xr[p] = children[q] ^ delta
            xr[q] = children[p] ^ delta
            assert (np.asarray(hashk.fold(jnp.asarray(xr)))
                    != base).any(), \
                f"xor compensated swap collided (trial {trial})"


def test_fold_plain_swap_with_shift_sweep():
    """Broader structured-collision sweep: swapping two children and
    shifting both by ANY small constant (add or xor, d in 1..64) never
    collides — simple arithmetic relationships between siblings must
    not cancel the position salts."""
    rng = np.random.default_rng(8)
    children = np.asarray(
        rng.integers(0, 2**32, (16, hashk.LANES)), dtype=np.uint32)
    base = np.asarray(hashk.fold(jnp.asarray(children)))
    for d in range(1, 65):
        du = np.uint32(d)
        add = children.copy()
        add[0], add[1] = children[1] + du, children[0] - du
        assert (np.asarray(hashk.fold(jnp.asarray(add))) != base).any()
        xr = children.copy()
        xr[0], xr[1] = children[1] ^ du, children[0] ^ du
        assert (np.asarray(hashk.fold(jnp.asarray(xr))) != base).any()
