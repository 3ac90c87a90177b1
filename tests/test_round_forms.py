"""The K/V round's stored forms against the plain gather / scatter.

Since ISSUE 42 the round reads and writes ``tree_node`` by masks over
the node axis and the object planes through their ``[M * E, S]`` row
view (``ops/engine.py`` "Merkle paths", ``_as_stored``), and since
ISSUE 44 the levels of 128 nodes and more are STORED as rows of 128
nodes (``tree_rows``) which the round gathers and scatters: forms
chosen for the layout the CHIP stores the planes in.  What they
compute must be what the plain ``take_along_axis`` / ``.at[].set``
forms compute over the flat ``[E, M, U, LANES]`` node plane, bit for
bit, on every shape: those forms are kept HERE as the reference and
swapped into the same round, through ``levels_to_rows`` /
``rows_to_levels``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from riak_ensemble_tpu.ops import engine as eng
from riak_ensemble_tpu.ops import hash as hashk

W16 = eng.TREE_WIDTH


# -- the reference: the round's accesses as plain gathers and scatters --

def _children_ref(arr, parent_idx, n):
    e, w = parent_idx.shape
    idx = parent_idx[..., None] * W16 + jnp.arange(W16, dtype=jnp.int32)
    idxc = jnp.clip(idx, 0, n - 1).reshape(e, 1, w * W16, 1)
    g = jnp.take_along_axis(arr, idxc, axis=2)
    g = g.reshape(e, arr.shape[1], w, W16, hashk.LANES)
    return jnp.where((idx < n)[:, None, :, :, None], g, jnp.uint32(0))


def _levels_ref(tree_node, s):
    offs, total = eng._tree_offsets(s)
    return total, [(off, n, jax.lax.slice_in_dim(tree_node, off, off + n,
                                                 axis=2))
                   for off, n in zip(offs, eng.tree_sizes(s))]


def _flat(tree_rows, tree_node, s):
    """The stored upper levels as the flat ``[E, M, U, LANES]`` plane."""
    return jnp.concatenate(eng.rows_to_levels(tree_rows, tree_node, s),
                           axis=-2)


def _stored(flat, s):
    """The flat plane as ``(tree_rows, tree_node)``."""
    return eng.levels_to_rows([lv for _, _, lv in _levels_ref(flat, s)[1]])


def _verify_path_ref(tree_leaf, tree_node, slot, tree_rows=None):
    s = tree_leaf.shape[-2]
    tree_node = _flat(tree_rows, tree_node, s)
    bad = jnp.zeros(tree_leaf.shape[:2] + (slot.shape[1],), bool)
    child_arr, child_n, idx = tree_leaf, s, slot
    for _, n, level in _levels_ref(tree_node, s)[1]:
        pidx = idx // W16
        expect = hashk.fold(_children_ref(child_arr, pidx, child_n))
        stored = jnp.take_along_axis(level, pidx[:, None, :, None], axis=2)
        bad = bad | (expect != stored).any(-1)
        child_arr, child_n, idx = level, n, pidx
    return bad


def _write_path_ref(tree_leaf, tree_node, slot, new_leaf, mask,
                    tree_rows=None, _path_rows=None):
    e, ml, w = mask.shape
    s = tree_leaf.shape[-2]
    tree_node = _flat(tree_rows, tree_node, s)
    eidx = jnp.arange(e, dtype=jnp.int32)[:, None, None]
    midx = jnp.arange(ml, dtype=jnp.int32)[None, :, None]
    tree_leaf = tree_leaf.at[eidx, midx, jnp.where(mask, slot[:, None], s)
                             ].set(jnp.broadcast_to(
                                 new_leaf[:, None], (e, ml, w, hashk.LANES)),
                                   mode="drop")
    total, levels = _levels_ref(tree_node, s)
    child_arr, child_n, idx, node = tree_leaf, s, slot, tree_node
    for off, n, _ in levels:
        pidx = idx // W16
        parent = hashk.fold(_children_ref(child_arr, pidx, child_n))
        tgt = jnp.where(mask, off + pidx[:, None, :], total)
        node = node.at[eidx, midx, tgt].set(parent, mode="drop")
        child_arr = jax.lax.slice_in_dim(node, off, off + n, axis=2)
        child_n, idx = n, pidx
    tree_rows, tree_node = _stored(node, s)
    return tree_leaf, tree_node, tree_rows


def _slot_read_ref(plane, slot):
    return jnp.take_along_axis(plane, slot[:, None, :], axis=2)


def _slot_write_ref(plane, slot, new):
    e, ml, _ = plane.shape
    eidx = jnp.arange(e, dtype=jnp.int32)[:, None, None]
    midx = jnp.arange(ml, dtype=jnp.int32)[None, :, None]
    return plane.at[eidx, midx, slot].set(
        jnp.broadcast_to(new[:, None, :], slot.shape), mode="drop")


REFERENCE = {"_verify_path": _verify_path_ref, "_write_path": _write_path_ref,
             "_slot_read": _slot_read_ref, "_slot_write": _slot_write_ref,
             # the reference reads its paths out of the whole plane
             "_gather_path_rows": lambda tree_rows, s, slot: tree_rows}


def _scan(state, kind, slot, val, lease, up, xe, xs):
    # the raw body: a jit of its own per arm, traced with whichever
    # forms the engine module names at that moment
    return eng.kv_step_scan.__wrapped__(state, kind, slot, val, lease, up,
                                        exp_epoch=xe, exp_seq=xs)


def _batches(rng, e, m, s, k=3, n=5):
    """Seeded launches of K rounds: every kind, slots on the short
    last block, out-of-range slots, a replica down in some."""
    for i in range(n):
        r = rng.random((k, e))
        kind = np.select([r < 0.45, r < 0.65, r < 0.8, r < 0.93],
                         [eng.OP_PUT, eng.OP_GET, eng.OP_CAS, eng.OP_RMW],
                         eng.OP_NOOP).astype(np.int32)
        slot = rng.integers(0, s, (k, e)).astype(np.int32)
        slot[rng.random((k, e)) < 0.3] = s - 1           # the last block
        slot[rng.random((k, e)) < 0.1] = rng.choice([-1, s, s + 5])
        val = rng.integers(0, 1 << 20, (k, e)).astype(np.int32)
        xe = np.where(kind == eng.OP_RMW,
                      rng.integers(1, 10, (k, e)), 0).astype(np.int32)
        xs = np.zeros((k, e), np.int32)
        # half of the CAS lanes expect the version a put left there
        xe = np.where((kind == eng.OP_CAS) & (rng.random((k, e)) < 0.5),
                      1, xe).astype(np.int32)
        xs = np.where((kind == eng.OP_CAS) & (xe == 1),
                      rng.integers(1, 4, (k, e)), xs).astype(np.int32)
        lease = rng.random((k, e)) < 0.5
        up = np.ones((e, m), bool)
        if i % 2:
            up[rng.integers(0, e), rng.integers(1, m)] = False
        yield kind, slot, val, lease, up, xe, xs


def _node_at(s, level, idx):
    """``(field, index after (e, m))`` of word 2 of node ``idx`` of
    upper level ``level``, where the state stores it."""
    lay = eng.tree_layout(s)
    if level < lay.row_levels:
        return "tree_rows", (lay.row_offs[level] + idx // eng.ROW_NODES,
                             2 * eng.ROW_NODES + idx % eng.ROW_NODES)
    return "tree_node", (lay.tail_offs[level - lay.row_levels] + idx, 2)


#: n_slots → (upper levels, row levels): none; 2,048: one row level of
#: one row; 4,096: 256 nodes, two rows; 3,000: a short last row;
#: 32,768: two row levels
SHAPES = {16: (1, 0), 128: (2, 0), 200: (2, 0), 2048: (3, 1),
          4096: (3, 1), 3000: (3, 1), 32768: (4, 2)}


@pytest.mark.parametrize("shape", [(4, 3, 16), (8, 5, 128), (3, 3, 4096),
                                   (2, 3, 200), (2, 3, 2048), (2, 3, 3000),
                                   (2, 3, 32768)],
                         ids=lambda sh: "x".join(map(str, sh)))
def test_masked_round_equals_gather_scatter_round(shape, monkeypatch):
    e, m, s = shape
    lay = eng.tree_layout(s)
    assert SHAPES[s] == (len(lay.sizes), lay.row_levels)
    rng = np.random.default_rng(42_000 + s)
    state, won = eng.elect_step(eng.init_state(e, m, s), jnp.ones(e, bool),
                                jnp.zeros(e, jnp.int32),
                                jnp.ones((e, m), bool))
    assert bool(won.all())
    engine_arm = jax.jit(_scan)
    with monkeypatch.context() as mp:
        for name, fn in REFERENCE.items():
            mp.setattr(eng, name, fn)
        reference_arm = jax.jit(lambda *a: _scan(*a))
        # trace it now, while the reference forms are the module's
        first = next(_batches(np.random.default_rng(0), e, m, s))
        reference_arm(state, *first)
    assert (state.tree_rows is None) == (lay.row_levels == 0)

    def corrupt(st, field, at):
        plane = getattr(st, field)
        return st._replace(**{field: plane.at[at].set(plane[at] ^ 1)})

    states = {"engine": state, "reference": state}
    for i, batch in enumerate(_batches(rng, e, m, s)):
        kind, slot, val, lease, up, xe, xs = batch
        kept = None
        if i == 2:
            # a leaf on a replica the round hears: the read flags it,
            # excludes the replica and repairs it
            sl = int(np.clip(slot[0, 0], 0, s - 1))
            kind[0, 0], slot[0, 0], lease[0, 0] = eng.OP_GET, sl, True
            for nm in states:
                states[nm] = corrupt(states[nm], "tree_leaf", (0, 1, sl, 0))
        if i == 3:
            # a node on the written slot's path, on a replica that is
            # DOWN: the others commit, its node must stay as corrupted
            sl = s - 1
            kind[:, 1], slot[:, 1] = eng.OP_PUT, sl
            up[1, :] = True
            up[1, 2] = False
            field, at = _node_at(s, 0, sl // W16)
            at = (1, 2) + at
            for nm in states:
                states[nm] = corrupt(states[nm], field, at)
            kept = (field, at, int(getattr(states["engine"], field)[at]))
        out = {}
        for nm, arm in (("engine", engine_arm), ("reference", reference_arm)):
            states[nm], out[nm] = arm(states[nm], kind, slot, val, lease,
                                      up, xe, xs)
        for f, a, b in zip(eng.EngineState._fields, states["engine"],
                           states["reference"]):
            assert (a is None) == (b is None), (i, f)
            assert a is None or np.array_equal(a, b), (i, f)
        for f, a, b in zip(eng.KvResult._fields, out["engine"],
                           out["reference"]):
            assert np.array_equal(a, b), (i, f)
        if i == 2:
            assert bool(out["engine"].tree_corrupt[0, 0, 1])
        if kept:
            field, at, value = kept
            assert bool(out["engine"].committed[:, 1].all())
            assert int(getattr(states["engine"], field)[at]) == value
            node_bad, _ = eng.verify_trees(states["engine"])
            assert bool(node_bad[1, 2])
    assert int(states["engine"].obj_seq_ctr.sum()) > e


@pytest.mark.parametrize("n", [1, 13, 16, 200, 256])
def test_fold_block_is_the_fold_of_the_padded_block(n):
    rng = np.random.default_rng(n)
    level = jnp.asarray(rng.integers(0, 1 << 32, (3, 2, n, hashk.LANES),
                                     dtype=np.uint32))
    nb = -(-n // W16)
    expect = eng._fold_blocks(level)                     # [3, 2, nb, L]
    block = jnp.asarray(rng.integers(0, nb, (3,)), jnp.int32)
    block = block.at[0].set(nb - 1)                      # the short block
    got = hashk.fold_block(level, block[:, None], W16)   # [3, 2, L]
    want = expect[jnp.arange(3), :, block]
    assert np.array_equal(got, want)


@pytest.mark.parametrize("s", sorted(SHAPES))
def test_stored_levels_round_trip_against_build_uppers(s):
    """``levels_to_rows`` / ``rows_to_levels`` alone: random leaves,
    the plain bottom-up build, there and back."""
    rng = np.random.default_rng(s)
    leaves = jnp.asarray(rng.integers(0, 1 << 32, (2, 2, s, hashk.LANES),
                                      dtype=np.uint32))
    flat = eng.build_uppers(leaves)
    lay = eng.tree_layout(s)
    assert flat.shape[2] == sum(lay.sizes) == eng._tree_offsets(s)[1]
    rows, tail = _stored(flat, s)
    assert tail.shape[2] == lay.tail_nodes <= 136
    assert all(n < eng.ROW_NODES for n in lay.sizes[lay.row_levels:])
    if lay.row_levels:
        assert rows.shape == (2, 2, lay.rows, eng.ROW_WORDS)
        assert lay.rows % 8 == 0
        # node i of a row level: row i // 128 of the level, lane-major
        n0 = lay.sizes[0]
        i = n0 - 1
        got = rows[:, :, i // 128].reshape(2, 2, hashk.LANES, 128)[..., i % 128]
        assert np.array_equal(got, flat[:, :, i])
        # what pads a short last row, and the plane to a multiple of 8
        used = sum(-(-n // 128) for n in lay.sizes[:lay.row_levels])
        assert not np.asarray(rows[:, :, used:]).any()
        if n0 % 128:
            last = rows[:, :, n0 // 128].reshape(2, 2, hashk.LANES, 128)
            assert not np.asarray(last[..., n0 % 128:]).any()
    else:
        assert rows is None and np.array_equal(tail, flat)
    assert np.array_equal(_flat(rows, tail, s), flat)
    built_rows, built_tail = eng._build_tree(leaves)
    assert np.array_equal(built_tail, tail)
    assert rows is None or np.array_equal(built_rows, rows)


@pytest.mark.parametrize("n", [128, 112])
def test_fold_block_with_the_nodes_on_the_minor_axis(n):
    rng = np.random.default_rng(n)
    level = jnp.asarray(rng.integers(0, 1 << 32, (3, 2, n, hashk.LANES),
                                     dtype=np.uint32))
    block = jnp.asarray([0, n // W16 - 1, 3], jnp.int32)
    want = hashk.fold_block(level, block[:, None], W16)
    got = hashk.fold_block(jnp.swapaxes(level, -1, -2), block[:, None],
                           W16, nodes_last=True)
    assert np.array_equal(got, want)
