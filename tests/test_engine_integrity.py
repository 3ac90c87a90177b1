"""Device-side integrity: the Merkle tree on the engine data path.

The reference's defining safety property is that the synctree gates
every K/V read and write (tree-is-truth, synctree.erl:44-73;
do_get_fsm/do_put_fsm tree reads, peer.erl:1370-1377; put_obj hash
updates, :1669-1698).  These tests drive the batched engine's form of
that property: corruption injected into a replica's object store or
tree is detected on device (``KvResult.tree_corrupt``), excluded from
read quorums, and healed by read repair / rebuild / exchange.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from riak_ensemble_tpu.ops import engine as eng
from riak_ensemble_tpu.ops import hash as hashk
from riak_ensemble_tpu.parallel.mesh import ShardedEngine, make_mesh

E, M, S = 4, 5, 16


def all_up():
    return jnp.ones((E, M), bool)


def elect_all(state, up=None):
    up = all_up() if up is None else up
    return eng.elect_step(
        state, jnp.ones((E,), bool), jnp.zeros((E,), jnp.int32), up)


def _put(st, slots, vals, up=None, lease=True):
    up = all_up() if up is None else up
    return eng.kv_step(
        st, jnp.full((E,), eng.OP_PUT, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(vals, jnp.int32),
        jnp.full((E,), lease, bool), up)


def _get(st, slots, up=None, lease=True):
    up = all_up() if up is None else up
    return eng.kv_step(
        st, jnp.full((E,), eng.OP_GET, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.zeros((E,), jnp.int32),
        jnp.full((E,), lease, bool), up)


def _seeded(slot=3, vals=(10, 20, 30, 40)):
    st, _ = elect_all(eng.init_state(E, M, S))
    st, res = _put(st, [slot] * E, list(vals))
    assert bool(res.committed.all())
    return st


def _corrupt_obj(st, peer, slot, val=999):
    """Flip a replica's stored object out-of-band (synctree_intercepts
    corrupt_segment analog): the tree leaf now disagrees."""
    return st._replace(obj_val=st.obj_val.at[:, peer, slot].set(val))


def _corrupt_node(st, peer, node=0):
    """Damage an upper tree node (corrupt_upper analog)."""
    return st._replace(
        tree_node=st.tree_node.at[:, peer, node, 0].set(jnp.uint32(0xDEAD)))


def test_write_maintains_tree():
    """Every committed put leaves leaf+path consistent (the
    always-up-to-date property, synctree.erl:44-73)."""
    st = _seeded()
    node_bad, leaf_bad = eng.verify_trees(st)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_corrupt_replica_detected_and_excluded():
    """A replica whose object diverges from its tree leaf fails the
    integrity gate; the read excludes it and serves the committed
    value (get_latest_obj hash extra-check, peer.erl:1646-1649)."""
    st = _corrupt_obj(_seeded(), peer=2, slot=3)
    _, leaf_bad = eng.verify_trees(st)
    assert bool(np.asarray(leaf_bad)[:, 2].all())
    st2, res = _get(st, [3] * E)
    assert bool(res.get_ok.all()) and bool(res.found.all())
    np.testing.assert_array_equal(res.value, [10, 20, 30, 40])
    # Detection surfaced to the host for exactly the corrupt replica.
    tc = np.asarray(res.tree_corrupt)
    assert tc[:, 2].all() and not tc[:, [0, 1, 3, 4]].any()


def test_read_repair_heals_corrupt_replica():
    """The same read that detects the corruption repairs it
    (maybe_repair, peer.erl:1518-1536): the replica re-adopts the
    winning version and its hash path is recomputed."""
    st = _corrupt_obj(_seeded(), peer=2, slot=3)
    st2, res = _get(st, [3] * E)
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, 2, 3],
                                  [10, 20, 30, 40])
    node_bad, leaf_bad = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())
    # Second read: clean, no corruption reported.
    _, res = _get(st2, [3] * E)
    assert not bool(np.asarray(res.tree_corrupt).any())


def test_corrupt_leader_replica_healed_from_followers():
    st = _corrupt_obj(_seeded(), peer=0, slot=3)  # leader is peer 0
    st2, res = _get(st, [3] * E)
    assert bool(res.get_ok.all())
    np.testing.assert_array_equal(res.value, [10, 20, 30, 40])
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, 0, 3],
                                  [10, 20, 30, 40])


def test_upper_node_corruption_detected_and_healed_on_access():
    """Damage to an inner tree node fails path verification on reads
    through it ({corrupted, Level, Bucket}, synctree.erl:322-340); the
    repair write recomputes the path, healing the node."""
    st = _corrupt_node(_seeded(), peer=1)
    node_bad, _ = eng.verify_trees(st)
    assert bool(np.asarray(node_bad)[:, 1].all())
    st2, res = _get(st, [3] * E)
    tc = np.asarray(res.tree_corrupt)
    assert tc[:, 1].all() and not tc[:, [0, 2, 3, 4]].any()
    assert bool(res.get_ok.all())
    np.testing.assert_array_equal(res.value, [10, 20, 30, 40])
    node_bad, leaf_bad = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_rebuild_trees_repairs_without_access():
    """Host-driven repair (peer_tree do_repair analog): rebuild flagged
    replicas' trees from their object stores."""
    st = _corrupt_node(_seeded(), peer=4)
    node_bad, _ = eng.verify_trees(st)
    st2 = eng.rebuild_trees(st, node_bad)
    node_bad2, leaf_bad2 = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad2).any())
    assert not bool(np.asarray(leaf_bad2).any())


def test_put_while_replica_corrupt_still_commits_and_heals_slot():
    """A put through a corrupt-slot replica overwrites the slot and its
    hash path — the write path never consults the stale object."""
    st = _corrupt_obj(_seeded(), peer=3, slot=3)
    st2, res = _put(st, [3] * E, [77] * E)
    assert bool(res.committed.all())
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, 3, 3], 77)
    node_bad, leaf_bad = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_read_repair_heals_lagging_replica():
    """drop_write analog: a replica that missed a committed write is
    healed by the next read (read_until, test/drop_write_test.erl)."""
    st = _seeded()
    # Age peer 1's replica (simulates a dropped backend write).
    st = st._replace(
        obj_seq=st.obj_seq.at[:, 1, 3].set(0),
        obj_val=st.obj_val.at[:, 1, 3].set(0),
        tree_leaf=st.tree_leaf.at[:, 1, 3].set(
            hashk.obj_leaf_hash(jnp.uint32(0), jnp.uint32(0),
                                jnp.uint32(0))))
    st = eng.rebuild_trees(st, jnp.asarray(np.eye(1, M, 1, dtype=bool)
                                           .repeat(E, 0)))
    st2, res = _get(st, [3] * E)
    assert bool(res.get_ok.all())
    np.testing.assert_array_equal(res.value, [10, 20, 30, 40])
    # The lagging replica adopted the winner (same version, no seq bump).
    np.testing.assert_array_equal(np.asarray(st2.obj_seq)[:, 1, 3], 1)
    assert not bool(res.committed.any())


def test_notfound_tombstone_when_member_unreachable():
    """all_or_quorum (msg.erl:282-317): a notfound read with every
    member responding serves without writing; with a member down it
    must commit a tombstone at the current epoch (peer.erl:1568-1584).
    """
    st, _ = elect_all(eng.init_state(E, M, S))
    st, res = _get(st, [5] * E)  # all members up: plain notfound
    assert bool(res.get_ok.all()) and not bool(res.found.any())
    assert not bool(res.committed.any())
    assert bool(np.asarray(st.obj_seq_ctr == 0).all())
    # Peer 4 down: tombstone commits (seq consumed).
    up = jnp.asarray(np.array([[1, 1, 1, 1, 0]] * E, dtype=bool))
    st2, res = _get(st, [5] * E, up=up)
    assert bool(res.get_ok.all()) and not bool(res.found.any())
    assert bool(res.committed.all())
    np.testing.assert_array_equal(np.asarray(st2.obj_seq_ctr), 1)
    # The tombstone replicated to reachable members with a hash update.
    np.testing.assert_array_equal(np.asarray(st2.obj_seq)[:, :4, 5], 1)
    node_bad, leaf_bad = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_exchange_converges_divergent_replicas():
    """Anti-entropy sweep (riak_ensemble_exchange analog): divergent
    and corrupt replicas adopt the newest hash-valid object per slot,
    trees rebuilt, divergence reported."""
    st = _seeded(slot=2, vals=(5, 6, 7, 8))
    st, _ = _put(st, [9] * E, [50] * E)
    # Peer 3 misses slot 9 entirely; peer 1 has a corrupt slot 2.
    st = st._replace(
        obj_seq=st.obj_seq.at[:, 3, 9].set(0),
        obj_epoch=st.obj_epoch.at[:, 3, 9].set(0),
        obj_val=st.obj_val.at[:, 3, 9].set(0))
    st = eng.rebuild_trees(
        st, jnp.asarray(np.eye(1, M, 3, dtype=bool).repeat(E, 0)))
    st = _corrupt_obj(st, peer=1, slot=2, val=666)
    st2, diverged, synced = eng.exchange_step(
        st, jnp.ones((E,), bool), all_up())
    assert bool(np.asarray(synced).all())
    dv = np.asarray(diverged)
    assert dv[:, 3].all() and dv[:, 1].all()
    assert not dv[:, [0, 2, 4]].any()
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, 3, 9], 50)
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, 1, 2],
                                  [5, 6, 7, 8])
    node_bad, leaf_bad = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_tombstone_reads_back_as_notfound():
    """The committed tombstone is a versioned object but stays
    client-invisible: later reads (all members back up) return
    notfound, not value 0."""
    st, _ = elect_all(eng.init_state(E, M, S))
    up = jnp.asarray(np.array([[1, 1, 1, 1, 0]] * E, dtype=bool))
    st, res = _get(st, [5] * E, up=up)
    assert bool(res.committed.all())          # tombstone committed
    st, res = _get(st, [5] * E)               # all up again
    assert bool(res.get_ok.all())
    assert not bool(res.found.any())
    assert not bool(res.committed.any())      # no second tombstone
    np.testing.assert_array_equal(res.value, 0)
    # A real put over the tombstone resurrects the key.
    st, res = _put(st, [5] * E, [11] * E)
    assert bool(res.committed.all())
    st, res = _get(st, [5] * E)
    assert bool(res.found.all())
    np.testing.assert_array_equal(res.value, 11)


def test_stale_tombstone_rewritten_at_current_epoch():
    """update_key applies to tombstones too: a tombstone from an old
    epoch is re-committed at the current one, still notfound."""
    st, _ = elect_all(eng.init_state(E, M, S))
    up = jnp.asarray(np.array([[1, 1, 1, 1, 0]] * E, dtype=bool))
    st, res = _get(st, [5] * E, up=up)        # epoch-1 tombstone
    st, _ = elect_all(st)                     # epoch 2
    st, res = _get(st, [5] * E)
    assert bool(res.committed.all())          # rewrite of the tombstone
    assert not bool(res.found.any())
    np.testing.assert_array_equal(np.asarray(st.obj_epoch)[:, :, 5], 2)


def test_exchange_preserves_data_when_no_valid_holder():
    """Exchange must never erase data it cannot replace: with every
    copy's tree upper levels corrupted (objects intact), the objects
    survive and the trees are rebuilt."""
    st = _seeded()
    for p in range(M):
        st = _corrupt_node(st, peer=p)
    st2, diverged, synced = eng.exchange_step(
        st, jnp.ones((E,), bool), all_up())
    assert bool(np.asarray(synced).all())
    # Objects intact, trees healed.
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, :, 3].T,
                                  np.tile([10, 20, 30, 40], (M, 1)))
    node_bad, leaf_bad = eng.verify_trees(st2)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_exchange_leaves_unreplaceable_slot_flagged():
    """A slot whose every copy is leaf-invalid has no valid winner:
    exchange leaves it (and its mismatched leaf) alone rather than
    blessing or erasing the data."""
    st = _seeded()
    for p in range(M):
        st = _corrupt_obj(st, peer=p, slot=3, val=600 + p)
    st2, diverged, synced = eng.exchange_step(
        st, jnp.ones((E,), bool), all_up())
    assert bool(np.asarray(synced).all())
    assert bool(np.asarray(diverged).all())
    # Data untouched, leaves still mismatched (replicas stay excluded).
    np.testing.assert_array_equal(
        np.asarray(st2.obj_val)[:, :, 3],
        np.tile(600 + np.arange(M), (E, 1)))
    _, leaf_bad = eng.verify_trees(st2)
    assert bool(np.asarray(leaf_bad).all())


def test_get_never_tombstones_over_integrity_excluded_data():
    """A GET whose integrity gate excluded the holders of a committed
    object must FAIL, not fabricate a quorum-committed notfound
    tombstone over the (recoverable) data."""
    st = _seeded()
    for p in range(M):
        st = _corrupt_obj(st, peer=p, slot=3, val=600 + p)
    st2, res = _get(st, [3] * E)
    assert not bool(res.get_ok.any())        # read errors, not notfound
    assert not bool(res.committed.any())     # and writes nothing
    np.testing.assert_array_equal(
        np.asarray(st2.obj_val)[:, :, 3],
        np.tile(600 + np.arange(M), (E, 1)))
    # Corruption surfaced for the host to run repair/exchange.
    assert bool(np.asarray(res.tree_corrupt).all())


def test_exchange_requires_majority():
    st = _seeded()
    up = jnp.asarray(np.array([[1, 1, 0, 0, 0]] * E, dtype=bool))
    st2, _, synced = eng.exchange_step(st, jnp.ones((E,), bool), up)
    assert not bool(np.asarray(synced).any())
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exchange_ignores_invalid_newer_object():
    """valid_obj_hash gate (exchange.erl:91-96): a hash-invalid object
    must not win the exchange even if its version looks newest."""
    st = _seeded()
    # Fabricate a "newer" object on peer 2 without a matching leaf.
    st = st._replace(
        obj_epoch=st.obj_epoch.at[:, 2, 3].set(9),
        obj_seq=st.obj_seq.at[:, 2, 3].set(9),
        obj_val=st.obj_val.at[:, 2, 3].set(123))
    st2, diverged, synced = eng.exchange_step(
        st, jnp.ones((E,), bool), all_up())
    assert bool(np.asarray(synced).all())
    # The forged object lost to the committed one and was overwritten.
    np.testing.assert_array_equal(np.asarray(st2.obj_val)[:, 2, 3],
                                  [10, 20, 30, 40])
    np.testing.assert_array_equal(np.asarray(st2.obj_epoch)[:, 2, 3], 1)


def test_tree_sizes_layout():
    assert eng.tree_sizes(16) == (1,)
    assert eng.tree_sizes(128) == (8, 1)
    assert eng.tree_sizes(256) == (16, 1)
    assert eng.tree_sizes(4096) == (256, 16, 1)
    assert eng.tree_sizes(1) == (1,)


@pytest.mark.parametrize("s", [8, 16, 60, 128])
def test_tree_consistency_across_shapes(s):
    """build/update/verify agree for non-power-of-16 slot counts."""
    st, _ = eng.elect_step(
        eng.init_state(2, 3, s), jnp.ones((2,), bool),
        jnp.zeros((2,), jnp.int32), jnp.ones((2, 3), bool))
    for slot in [0, s // 2, s - 1]:
        st, res = eng.kv_step(
            st, jnp.full((2,), eng.OP_PUT, jnp.int32),
            jnp.full((2,), slot, jnp.int32),
            jnp.full((2,), slot + 1, jnp.int32),
            jnp.ones((2,), bool), jnp.ones((2, 3), bool))
        assert bool(res.committed.all())
    node_bad, leaf_bad = eng.verify_trees(st)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_sharded_integrity_matches_single_device():
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    e, m, s = 8, 8, 16
    mesh = make_mesh(4, 2)
    se = ShardedEngine(mesh)
    views = [list(range(5))]

    def drive(elect_fn, kv_fn, exchange_fn, verify_fn, state):
        up = jnp.ones((e, m), bool)
        state, won = elect_fn(state, jnp.ones((e,), bool),
                              jnp.zeros((e,), jnp.int32), up)
        k = 2
        kind = jnp.full((k, e), eng.OP_PUT, jnp.int32)
        slot = jnp.broadcast_to(jnp.asarray([3, 7], jnp.int32)[:, None],
                                (k, e))
        val = jnp.asarray(np.arange(k * e).reshape(k, e) + 1, jnp.int32)
        lease = jnp.ones((k, e), bool)
        state, res = kv_fn(state, kind, slot, val, lease, up)
        # Diverge a replica, then exchange.
        state = state._replace(obj_val=state.obj_val.at[:, 1, 3].set(999))
        state, diverged, synced = exchange_fn(
            state, jnp.ones((e,), bool), up)
        nb, lb = verify_fn(state)
        return won, res, diverged, synced, nb, lb, state

    out_single = drive(eng.elect_step, eng.kv_step_scan, eng.exchange_step,
                       eng.verify_trees,
                       eng.init_state(e, m, s, views=views))
    out_sharded = drive(se.elect_step, se.kv_step_scan, se.exchange_step,
                        se.verify_trees,
                        se.init_state(e, m, s, views=views))
    for a, b in zip(jax.tree.leaves(out_single),
                    jax.tree.leaves(out_sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    won, res, diverged, synced, nb, lb, state = out_single
    assert bool(np.asarray(won).all())
    assert bool(np.asarray(res.committed).all())
    dv = np.asarray(diverged)
    assert dv[:, 1].all() and not dv[:, 0].any()
    assert not bool(np.asarray(nb).any()) and not bool(np.asarray(lb).any())


# -- the levels stored as rows of 128 nodes (ISSUE 44) -----------------------

#: 2 x 3 x 4,096: 256 + 16 + 1 upper nodes, the 256 two rows of
#: ``tree_rows``, the 17 flat in ``tree_node``
RE, RM, RS = 2, 3, 4096


def _row_state():
    """An elected 2 x 3 x 4,096 state with a put on slots in both rows
    of the row level and on the short end."""
    up = jnp.ones((RE, RM), bool)
    st, won = eng.elect_step(eng.init_state(RE, RM, RS),
                             jnp.ones((RE,), bool),
                             jnp.zeros((RE,), jnp.int32), up)
    assert bool(won.all())
    for slot in (5, 2047, 2048, RS - 1):
        st, res = _row_op(st, eng.OP_PUT, slot, slot + 7)
        assert bool(res.committed.all())
    return st


def _row_op(st, kind, slot, val=0, up=None):
    up = jnp.ones((RE, RM), bool) if up is None else up
    return eng.kv_step(st, jnp.full((RE,), kind, jnp.int32),
                       jnp.full((RE,), slot, jnp.int32),
                       jnp.full((RE,), val, jnp.int32),
                       jnp.ones((RE,), bool), up)


def _flip_row_node(st, peer, idx, lane=1):
    """Flip one bit of node ``idx`` of the row level on ``peer``."""
    at = (slice(None), peer, idx // eng.ROW_NODES,
          lane * eng.ROW_NODES + idx % eng.ROW_NODES)
    return st._replace(tree_rows=st.tree_rows.at[at].set(
        st.tree_rows[at] ^ jnp.uint32(1 << 9)))


def _assert_plain_trees(st):
    """Every replica's stored upper levels are the plain bottom-up
    build over its own leaves, and its leaves its objects' hashes."""
    flat = eng.build_uppers(st.tree_leaf)
    levels, off = [], 0
    for n in eng.tree_sizes(RS):
        levels.append(flat[:, :, off:off + n])
        off += n
    rows, tail = eng.levels_to_rows(levels)
    assert np.array_equal(st.tree_rows, rows)
    assert np.array_equal(st.tree_node, tail)
    assert np.array_equal(st.tree_leaf, hashk.obj_leaf_hash(
        st.obj_epoch, st.obj_seq, st.obj_val))


def test_row_levels_are_the_plain_build():
    st = _row_state()
    assert st.tree_rows.shape == (RE, RM, 8, eng.ROW_WORDS)
    assert st.tree_node.shape == (RE, RM, 17, hashk.LANES)
    _assert_plain_trees(st)
    node_bad, leaf_bad = eng.verify_trees(st)
    assert not np.asarray(node_bad).any() and not np.asarray(leaf_bad).any()


@pytest.mark.parametrize("slot", [5, 2047, 2048, RS - 1])
def test_flipped_row_node_is_caught_on_its_path_and_healed(slot):
    """A flipped word in a node of the row level: the read through it
    flags that replica alone and its repair heals it; a read under
    another parent does not see it; ``verify_trees`` does."""
    good = _row_state()
    st = _flip_row_node(good, peer=2, idx=slot // 16)
    node_bad, leaf_bad = eng.verify_trees(st)
    assert np.asarray(node_bad)[:, 2].all()
    assert np.asarray(node_bad).sum() == RE and not np.asarray(leaf_bad).any()
    beside = (slot + 1024) % RS          # another block of the level above
    st1, res = _row_op(st, eng.OP_GET, beside)
    assert bool(res.get_ok.all()) and not np.asarray(res.tree_corrupt).any()
    assert np.asarray(eng.verify_trees(st1)[0])[:, 2].all()
    st2, res = _row_op(st1, eng.OP_GET, slot)
    tc = np.asarray(res.tree_corrupt)
    assert tc[:, 2].all() and not tc[:, :2].any()
    assert bool(res.get_ok.all())
    np.testing.assert_array_equal(res.value, slot + 7)
    assert not np.asarray(eng.verify_trees(st2)[0]).any()
    for f, a, b in zip(st2._fields, st2, good):
        assert np.array_equal(a, b), f
    _assert_plain_trees(st2)


def test_flipped_row_node_on_a_down_replica_stays_as_it_is():
    """A put commits on the replicas that hear it; the one that is down
    keeps its rows, the flipped word included."""
    st = _flip_row_node(_row_state(), peer=1, idx=2048 // 16)
    up = jnp.ones((RE, RM), bool).at[:, 1].set(False)
    st2, res = _row_op(st, eng.OP_PUT, 2048, 99, up=up)
    assert bool(res.committed.all())
    assert np.array_equal(st2.tree_rows[:, 1], st.tree_rows[:, 1])
    assert not np.array_equal(st2.tree_rows[:, 0], st.tree_rows[:, 0])
    assert np.asarray(eng.verify_trees(st2)[0])[:, 1].all()


@pytest.mark.parametrize("heal", ["rebuild_trees", "exchange_step",
                                  "reset_rows"])
def test_flipped_row_node_healed_off_the_path(heal):
    st = _flip_row_node(_row_state(), peer=0, idx=255)
    node_bad, _ = eng.verify_trees(st)
    assert np.asarray(node_bad)[:, 0].all()
    if heal == "rebuild_trees":
        st2 = eng.rebuild_trees(st, node_bad)
    elif heal == "exchange_step":
        st2, diverged, synced = eng.exchange_step(
            st, jnp.ones((RE,), bool), jnp.ones((RE, RM), bool))
        assert bool(synced.all())
        dv = np.asarray(diverged)
        assert dv[:, 0].all() and not dv[:, 1:].any()
    else:
        st2 = eng.reset_rows(st, jnp.asarray([True, False]),
                             jnp.ones((RE, RM), bool))
        assert not np.asarray(st2.obj_seq[0]).any()
        bad = np.asarray(eng.verify_trees(st2)[0])
        assert not bad[0].any() and bad[1, 0]
        st2 = eng.rebuild_trees(st2, jnp.asarray(bad))
    assert not np.asarray(eng.verify_trees(st2)[0]).any()
    _assert_plain_trees(st2)


def test_sliced_launch_over_a_row_plane_equals_the_full_width_one():
    """E = 256, S = 2,048 (one row level of one row): the sliced step
    takes the active ensembles' rows out of ``tree_rows`` and puts them
    back, and leaves what the full-width step leaves."""
    e, m, s, k = 256, 3, 2048, 2
    assert eng.tree_layout(s).rows == 8
    rng = np.random.default_rng(44)
    active = np.array([3, 17, 100, 255], np.int32)
    elect = np.zeros(e, bool)
    elect[active] = True
    cand = np.zeros(e, np.int32)
    lease = np.zeros(e, bool)
    kind = np.zeros((k, e), np.int32)
    kind[:, active] = eng.OP_PUT
    slot = rng.integers(0, s, (k, e)).astype(np.int32)
    val = rng.integers(1, 1 << 20, (k, e)).astype(np.int32)
    planes = (kind, slot, val, None, None)
    up = jnp.ones((e, m), bool)
    state = eng.init_state(e, m, s)
    want = jax.jit(eng._full_step_slab_body)(
        state, jnp.asarray(eng.pack_op_slab(e, k, elect, cand, lease,
                                            planes)), up)
    a = 8
    aidx = np.full(a, e, np.int32)
    aidx[:len(active)] = active
    got = jax.jit(eng._full_step_sliced_slab_body)(
        state, jnp.asarray(eng.pack_op_slab(a, k, elect, cand, lease,
                                            planes, active, aidx)), up)
    assert np.asarray(want[2].committed)[:, active].all()
    assert got[0].tree_rows.shape == (e, m, 8, eng.ROW_WORDS)
    assert not np.array_equal(got[0].tree_rows, state.tree_rows)
    for f, g, w in zip(state._fields, got[0], want[0]):
        assert np.array_equal(g, w), f
    np.testing.assert_array_equal(np.asarray(got[1])[:len(active)],
                                  np.asarray(want[1])[active])
    assert not np.asarray(eng.verify_trees(got[0])[0]).any()
