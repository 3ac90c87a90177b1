"""Batched consensus engine — the vmapped ballot matrix.

The reference runs one Erlang gen_fsm process per peer per ensemble
(``src/riak_ensemble_peer.erl``); independent consensus groups are the
parallelism axis (SURVEY §2.7).  Here that axis is literal: the ballot
state of E ensembles x M peers lives in device arrays, and the protocol
transitions are jitted array kernels:

- :func:`elect_step` — batched leader election: phase-1 prepare
  (``prepare/2``, peer.erl:579-596; NextEpoch = epoch+1, :877-885) and
  phase-2 new_epoch (``prelead/2``, :609-620) fused into one kernel,
  with the quorum predicate of ``riak_ensemble_msg:quorum_met/5``
  (msg.erl:377-418) as a masked majority-reduce.
- :func:`kv_step` — batched steady-state K/V data path: the leased
  local read (``do_get_fsm`` fast path, peer.erl:1460-1462,1493-1516),
  the quorum epoch-check read (``check_epoch`` round, :1493-1516), the
  quorum replicated write (``put_obj``: local put + blocking_send_all
  {put,...} + wait_for_quorum, peer.erl:1669-1698), the quorum
  latest-object read (``get_latest_obj``, :1623-1662), the
  stale-epoch rewrite (``update_key``, :1564-1596), async read repair
  of lagging replicas (``maybe_repair``, :1518-1536) and the
  notfound tombstone-avoidance dance (``all_or_quorum`` +
  notfound_read_delay, msg.erl:282-317, peer.erl:1568-1584) — the
  "thundering herd" of first-touch rewrites after an election is
  batched across all ensembles in one kernel step (SURVEY §7).
- :func:`kv_step_scan` — K sequential ops per ensemble per launch via
  ``lax.scan`` (amortizes dispatch; per-key serialization analog of the
  key-hashed worker pool, peer.erl:1220-1225).

**Integrity is on the data path** (the synctree tree-is-truth design,
``src/synctree.erl:44-73``): every replica carries a Merkle trie over
its slot store — ``tree_leaf`` (per-slot object hashes) plus the upper
levels, stored by size ("Merkle paths" below): ``tree_rows`` (the
levels of 128 nodes and more, as rows of 128 nodes) and ``tree_node``
(the levels under 128, root last).  Every committed write
updates the leaf AND recomputes its root-ward path in the same kernel
(the always-up-to-date write-path property — ``put_obj`` →
``update_hash``/``send_update_hash``, peer.erl:1669-1715); every read
verifies the accessed slot's path root-ward (``get_path``/
``verify_hash``, synctree.erl:302-340) and checks the object against
its leaf (``valid_obj_hash``, peer.erl:1726), excluding failed
replicas from the read quorum (the hash extra-check of
``get_latest_obj``, :1646-1649) and surfacing them in
``KvResult.tree_corrupt`` for the host.  Read repair then heals
divergent or corrupted replicas in the same round.  Bulk kernels —
:func:`verify_trees`, :func:`rebuild_trees`, :func:`exchange_step` —
give the host the full repair/exchange surface
(``riak_ensemble_exchange``, ``riak_ensemble_peer_tree:do_repair``).

Peer-axis reductions go through :func:`quorum.reduce_peers` / :func:`_pmax`, which
lower to ``jax.lax.psum``/``pmax`` over a mesh axis when ``axis_name``
is given — under ``shard_map`` over a ``('ens', 'peer')`` mesh the vote
count literally rides the ICI all-reduce (see
:mod:`riak_ensemble_tpu.parallel.mesh`).  Host-side concerns — timers,
leases (monotonic clock), failure detection, membership gossip — stay
in the host runtime; the ``up`` and ``lease_ok`` masks are how the host
injects them into the kernels.

All integers are int32 (TPU-native; x64 stays disabled).  Object
payloads are int32 handles — real values live in the host/backend
object store keyed by (slot, epoch, seq); the device arrays carry the
version discipline, which is what consensus is about.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from riak_ensemble_tpu import funref
from riak_ensemble_tpu.ops import hash as hashk
from riak_ensemble_tpu.ops import quorum as quorum_lib
from riak_ensemble_tpu.ops.quorum import (
    quorum_met_batch, reduce_peers, views_to_mask,
)

#: opt-in: run the engine's quorum reduce as the Pallas kernel
#: (ops/pallas_quorum.quorum_met_epallas) instead of the jnp chain.
#: Single-shard launches only — the sharded (axis_name) path keeps the
#: psum collectives.
PALLAS_QUORUM = os.environ.get("RETPU_PALLAS_QUORUM", "") == "1"

# Op kinds for kv_step.
OP_NOOP = 0
OP_GET = 1
OP_PUT = 2
#: compare-and-swap: commit ``val`` iff the slot's current version
#: equals (exp_epoch, exp_seq); expecting (0, 0) on an absent slot is
#: create-if-missing — so OP_CAS carries both do_kupdate
#: (peer.erl:259-270) and do_kput_once (:278-284) semantics.
OP_CAS = 3
#: device read-modify-write — the batched analog of running kmodify's
#: mod-fun INSIDE the leader's FSM (do_kmodify, peer.erl:303-317): the
#: round reads the slot's latest hash-valid value, applies a
#: registered table fun (fun code in the ``exp_epoch`` plane —
#: funref.RMW_*; int32 operand in ``val``) and commits the result
#: under the SAME round's seq discipline.  The read and the write are
#: atomic within the round (no other lane touches the slot), so a
#: device RMW can never CAS-conflict — one round replaces the host's
#: read → fn → CAS retry cycle.  An absent key (or a tombstone) reads
#: as value 0 for the arithmetic funs; a fun result of 0 commits the
#: tombstone (the engine-wide 0-is-notfound payload encoding).
OP_RMW = 4

# The mod-fun table codes (canonical home: funref.py — the registry
# the service resolves kmodify funrefs against; re-exported here so
# kernel callers need only the engine module).
RMW_ADD = funref.RMW_ADD
RMW_SUB = funref.RMW_SUB
RMW_MAX = funref.RMW_MAX
RMW_MIN = funref.RMW_MIN
RMW_SET = funref.RMW_SET
RMW_BAND = funref.RMW_BAND
RMW_BOR = funref.RMW_BOR
RMW_BXOR = funref.RMW_BXOR
RMW_PIA = funref.RMW_PIA

# Merge-section cell codes (the commutative replication lane,
# docs/ARCHITECTURE.md §18): a merged cell names the FOLD to apply
# against the replica lane's own current value, not an op.
MERGE_ADD = funref.MERGE_ADD
MERGE_MAX = funref.MERGE_MAX
MERGE_MIN = funref.MERGE_MIN
MERGE_AND = funref.MERGE_AND
MERGE_OR = funref.MERGE_OR


def merge_vals(cur: jax.Array, mcls: jax.Array,
               operand: jax.Array) -> jax.Array:
    """The compiled half of the replica's merge-scatter: fold each
    merged cell's coalesced ``operand`` into the lane's own current
    value ``cur`` by merge class — the same int32 select ladder the
    kv round's RMW arm runs, restricted to the order-free funs (add
    covers sub via leader-side negation; semilattice max/min/and/or
    fold by themselves).  Elementwise over [n] cell vectors; callers
    gather ``cur`` from their own object plane and scatter the result
    back, so N leader-side ops on one hot slot land as ONE lattice
    merge with no per-entry sequencing."""
    return jnp.select(
        [mcls == MERGE_ADD, mcls == MERGE_MAX, mcls == MERGE_MIN,
         mcls == MERGE_AND],
        [cur + operand, jnp.maximum(cur, operand),
         jnp.minimum(cur, operand), cur & operand],
        default=cur | operand)


#: Merkle trie fan-out (the reference's width-16 trie, synctree.erl:88).
TREE_WIDTH = 16


#: ``jax.named_scope`` names on the step's phases (metadata only: they
#: reach the HLO's ``op_name`` and from there a profiler trace, where
#: ``tools/trace_scopes.py`` groups the device's time by them).
#: ``result_pack`` also wraps the packers of ``parallel.batched_host``.
SCOPES = ("elect", "quorum", "slot_gather", "merkle_verify", "apply",
          "merkle_write", "slot_scatter", "slice_columns",
          "scatter_columns", "idle_quorum", "result_pack")


class EngineState(NamedTuple):
    """Ballot + replicated-store + integrity state for E ensembles x M
    peers.

    Leading axes: E (ensemble) shardable over mesh axis 'ens', M (peer)
    shardable over mesh axis 'peer'.  With sharded M, each shard holds
    its local peer slice; ``leader``/``obj_seq_ctr`` are replicated
    along 'peer'.

    ``tree_leaf``/``tree_rows``/``tree_node`` are each replica's
    synctree: leaf k is the hash of the replica's object at slot k;
    the upper levels (sizes from :func:`tree_sizes`) are stored by
    size (:func:`tree_layout`): those of 128 nodes and more as rows of
    128 nodes in ``tree_rows``, those under 128, root last, flat in
    ``tree_node``.  A keyspace of 2,032 slots or fewer has no level of
    128 nodes and NO row plane: ``tree_rows`` is None, no leaf of the
    pytree.  Maintained synchronously by the K/V kernels.
    """

    epoch: jax.Array        # [E, M] int32  per-peer current epoch
    fact_seq: jax.Array     # [E, M] int32  per-peer fact seq
    leader: jax.Array       # [E]    int32  global leader peer idx, -1 none
    view_mask: jax.Array    # [E, V, M] bool  joint-consensus views,
    #                         newest first (slot 0 = head), all-zero
    #                         rows = unused capacity in the views list
    view_vsn: jax.Array     # [E] int32  bumps on every views change
    pend_vsn: jax.Array     # [E] int32  vsn of the adopted pending change
    commit_vsn: jax.Array   # [E] int32  pend_vsn as of the last collapse
    obj_seq_ctr: jax.Array  # [E]    int32  leader per-epoch obj counter
    obj_epoch: jax.Array    # [E, M, S] int32  replica store: obj epochs
    obj_seq: jax.Array      # [E, M, S] int32  replica store: obj seqs
    obj_val: jax.Array      # [E, M, S] int32  replica store: payloads
    tree_leaf: jax.Array    # [E, M, S, LANES] uint32  Merkle leaf hashes
    tree_node: jax.Array    # [E, M, T, LANES] uint32  levels < 128 nodes
    tree_rows: Optional[jax.Array] = None  # [E, M, R, 512] uint32  levels
    #                         of >= 128 nodes, a row = 128 nodes x LANES


class KvResult(NamedTuple):
    committed: jax.Array    # [E] bool  put/rewrite/tombstone reached quorum
    get_ok: jax.Array       # [E] bool  read served (lease or epoch quorum)
    found: jax.Array        # [E] bool  read found an object
    value: jax.Array        # [E] int32 read payload (0 if not found)
    obj_vsn: jax.Array      # [E, 2] int32 (epoch, seq) of the read/put obj
    quorum_ok: jax.Array    # [E] bool  leader up + epoch quorum this round
    tree_corrupt: jax.Array  # [E, M] bool replica failed the integrity gate


# ---------------------------------------------------------------------------
# The canonical sharded-pytree layout (ONE state layout, two placements)
#
# Every path — the single-shard service, the mesh service, checkpoints,
# warmup — shares the axis layout declared right here next to the
# NamedTuples it describes.  ``state_specs()`` gives the mesh placement
# (E over 'ens', M over 'peer'); ``state_specs(ens=None, peer=None)``
# gives the single-shard placement (everything replicated) — the SAME
# pytree of PartitionSpecs, so the two worlds can never drift apart.


def state_specs(ens: Optional[str] = "ens",
                peer: Optional[str] = "peer") -> "EngineState":
    """:class:`EngineState`-shaped pytree of ``PartitionSpec``\\ s.

    ``ens``/``peer`` name the mesh axes the E and M dims shard over
    (None = replicated along that axis).  Field ↔ spec table lives in
    docs/ARCHITECTURE.md §17.  A state without a row plane
    (``tree_rows`` None) takes these specs as they are: a spec tree is
    a PREFIX of what it places, and a leaf of it covers an empty
    subtree.
    """
    from jax.sharding import PartitionSpec as P
    return EngineState(
        epoch=P(ens, peer),
        fact_seq=P(ens, peer),
        leader=P(ens),
        view_mask=P(ens, None, peer),
        view_vsn=P(ens),
        pend_vsn=P(ens),
        commit_vsn=P(ens),
        obj_seq_ctr=P(ens),
        obj_epoch=P(ens, peer, None),
        obj_seq=P(ens, peer, None),
        obj_val=P(ens, peer, None),
        tree_leaf=P(ens, peer, None, None),
        tree_node=P(ens, peer, None, None),
        tree_rows=P(ens, peer, None, None),
    )


def scan_result_specs(ens: Optional[str] = "ens",
                      peer: Optional[str] = "peer") -> "KvResult":
    """:class:`KvResult` specs for :func:`kv_step_scan`'s stacked
    ``[K, E]`` planes (``obj_vsn`` ``[K, E, 2]``, ``tree_corrupt``
    ``[K, E, M]``)."""
    from jax.sharding import PartitionSpec as P
    return KvResult(
        committed=P(None, ens), get_ok=P(None, ens),
        found=P(None, ens), value=P(None, ens),
        obj_vsn=P(None, ens, None), quorum_ok=P(None, ens),
        tree_corrupt=P(None, ens, peer),
    )


def state_sharding(mesh) -> "EngineState":
    """:func:`state_specs` bound to a concrete mesh: an
    :class:`EngineState` of ``NamedSharding`` ready for
    ``jax.device_put`` / checkpoint-restore templates."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), state_specs(),
        is_leaf=lambda x: isinstance(x, PartitionSpec))


# ---------------------------------------------------------------------------
# Merkle trie layout + path kernels (the synctree on the data path)


@functools.lru_cache(maxsize=None)
def tree_sizes(n_slots: int) -> Tuple[int, ...]:
    """Upper-level sizes leafward→root for an ``n_slots``-leaf trie
    (width 16; short levels padded with zero hashes)."""
    sizes = []
    n = n_slots
    while n > 1:
        n = -(-n // TREE_WIDTH)
        sizes.append(n)
    if not sizes:
        sizes = [1]
    return tuple(sizes)


@functools.lru_cache(maxsize=None)
def _tree_offsets(n_slots: int) -> Tuple[Tuple[int, ...], int]:
    sizes = tree_sizes(n_slots)
    offs, total = [], 0
    for n in sizes:
        offs.append(total)
        total += n
    return tuple(offs), total


def _fold_blocks(x: jax.Array) -> jax.Array:
    """Fold ``[..., n, LANES]`` into ``[..., ceil(n/16), LANES]`` parent
    hashes, zero-padding the last (short) block."""
    n = x.shape[-2]
    nb = -(-n // TREE_WIDTH)
    pad = nb * TREE_WIDTH - n
    if pad:
        zeros = jnp.zeros(x.shape[:-2] + (pad, hashk.LANES), jnp.uint32)
        x = jnp.concatenate([x, zeros], axis=-2)
    return hashk.fold(x.reshape(x.shape[:-2] + (nb, TREE_WIDTH,
                                                hashk.LANES)))


def _build_levels(leaves: jax.Array) -> list:
    """Bottom-up rebuild of the upper levels from ``[..., S, LANES]``
    leaves, leafward → root (the ``rehash`` role,
    synctree.erl:489-535, one fused pass)."""
    outs = []
    cur = leaves
    for _ in tree_sizes(leaves.shape[-2]):
        cur = _fold_blocks(cur)
        outs.append(cur)
    return outs


def build_uppers(leaves: jax.Array) -> jax.Array:
    """:func:`_build_levels` as one flat ``[..., U, LANES]`` array: the
    plain form of a tree's upper levels, which the state stores as
    :func:`levels_to_rows` lays them out."""
    return jnp.concatenate(_build_levels(leaves), axis=-2)


#: nodes in a row of ``tree_rows``: the chip's lane width (the 128 of
#: every ``T(., 128)`` tile), not a knob
ROW_NODES = 128
ROW_WORDS = ROW_NODES * hashk.LANES

#: how a checkpoint's upper levels are stored, stamped beside
#: ``hash_format``: 2 = ``tree_rows`` + ``tree_node`` (:func:`tree_layout`);
#: an image without the stamp holds them flat in ``tree_node`` and is
#: restored by rebuilding every tree (docs/MIGRATION.md)
TREE_FORM = 2


class TreeLayout(NamedTuple):
    """Where an ``n_slots``-leaf trie's upper levels are stored."""

    sizes: Tuple[int, ...]      # every upper level, leafward → root
    row_levels: int             # the first L of them are stored as rows
    row_offs: Tuple[int, ...]   # [L] each row level's first row
    rows: int                   # R: rows a replica holds (0: no plane)
    tail_offs: Tuple[int, ...]  # the other levels' offsets in tree_node
    tail_nodes: int             # T


@functools.lru_cache(maxsize=None)
def tree_layout(n_slots: int) -> TreeLayout:
    """A level of n >= 128 nodes takes ``ceil(n / 128)`` rows of
    ``tree_rows``, starting on a row of its own, a short last row
    padded with zero hashes (what :func:`_fold_blocks` pads with).
    Width 16 divides 128, so the 16 children of any parent lie inside
    ONE row, and that row also holds the path's own node of the level.
    R is padded to a multiple of 8, so that ``[E, M, R, 512]`` viewed
    ``[E * M * R, 512]`` is a bitcast of the chip's (8, 128) tiles.
    The levels under 128 nodes (at most 127 + 8 + 1 nodes) stay flat
    in ``tree_node``, root last."""
    sizes = tree_sizes(n_slots)
    n_row = sum(n >= ROW_NODES for n in sizes)
    row_offs, rows = [], 0
    for n in sizes[:n_row]:
        row_offs.append(rows)
        rows += -(-n // ROW_NODES)
    tail_offs, tail = [], 0
    for n in sizes[n_row:]:
        tail_offs.append(tail)
        tail += n
    return TreeLayout(sizes, n_row, tuple(row_offs), -(-rows // 8) * 8,
                      tuple(tail_offs), tail)


def levels_to_rows(levels: Sequence[jax.Array]
                   ) -> Tuple[Optional[jax.Array], jax.Array]:
    """The upper levels (``[..., n, LANES]`` each, leafward → root) as
    the state stores them: ``(tree_rows [..., R, 512] or None,
    tree_node [..., T, LANES])``.  Inside a row the 128 nodes are the
    MINOR axis (word ``lane * 128 + node``): a gathered row is viewed
    ``[LANES, 128]`` with its nodes on the chip's lanes."""
    sizes = tuple(lv.shape[-2] for lv in levels)
    n_row = sum(n >= ROW_NODES for n in sizes)
    lead = levels[0].shape[:-2]
    rows = []
    for lv in levels[:n_row]:
        nr = -(-lv.shape[-2] // ROW_NODES)
        pad = nr * ROW_NODES - lv.shape[-2]
        if pad:
            lv = jnp.concatenate(
                [lv, jnp.zeros(lead + (pad, hashk.LANES), jnp.uint32)],
                axis=-2)
        lv = lv.reshape(lead + (nr, ROW_NODES, hashk.LANES))
        rows.append(jnp.swapaxes(lv, -1, -2).reshape(
            lead + (nr, ROW_WORDS)))
    tail = jnp.concatenate(list(levels[n_row:]), axis=-2)
    if not rows:
        return None, tail
    used = sum(r.shape[-2] for r in rows)
    pad = -(-used // 8) * 8 - used
    if pad:
        rows.append(jnp.zeros(lead + (pad, ROW_WORDS), jnp.uint32))
    return jnp.concatenate(rows, axis=-2), tail


def rows_to_levels(tree_rows: Optional[jax.Array], tree_node: jax.Array,
                   n_slots: int) -> list:
    """:func:`levels_to_rows` back: the stored form as the list of
    ``[..., n, LANES]`` levels, leafward → root."""
    lay = tree_layout(n_slots)
    lead = tree_node.shape[:-2]
    out = []
    for off, n in zip(lay.row_offs, lay.sizes):
        nr = -(-n // ROW_NODES)
        lv = jax.lax.slice_in_dim(tree_rows, off, off + nr, axis=-2)
        lv = jnp.swapaxes(
            lv.reshape(lead + (nr, hashk.LANES, ROW_NODES)), -1, -2)
        out.append(lv.reshape(lead + (nr * ROW_NODES, hashk.LANES))
                   [..., :n, :])
    for off, n in zip(lay.tail_offs, lay.sizes[lay.row_levels:]):
        out.append(jax.lax.slice_in_dim(tree_node, off, off + n, axis=-2))
    return out


def _build_tree(leaves: jax.Array
                ) -> Tuple[Optional[jax.Array], jax.Array]:
    """``(tree_rows, tree_node)`` rebuilt bottom-up from the leaves."""
    return levels_to_rows(_build_levels(leaves))


def _trees_differ(state: "EngineState", rows: Optional[jax.Array],
                  tail: jax.Array) -> jax.Array:
    """``[E, Ml]``: a replica's stored upper levels are not ``(rows,
    tail)``."""
    bad = (tail != state.tree_node).any((-1, -2))
    if rows is not None:
        bad = bad | (rows != state.tree_rows).any((-1, -2))
    return bad


def _select_trees(mask: jax.Array, rows: Optional[jax.Array],
                  tail: jax.Array, state: "EngineState"
                  ) -> Tuple[Optional[jax.Array], jax.Array]:
    """``(rows, tail)`` on the replicas in ``mask [E, Ml]``, the
    state's own upper levels elsewhere."""
    m4 = mask[:, :, None, None]
    return (None if rows is None
            else jnp.where(m4, rows, state.tree_rows),
            jnp.where(m4, tail, state.tree_node))


# Merkle paths.  What is stored where, and why 128.  The chip tiles
# every plane (8, 128) over its two minor-most axes and stores a
# lane-dense ``[E, M, U, LANES]`` U minor-most.  A gather or a scatter
# along U wants the 4-wide LANES minor-most instead, padded to the
# tile's 128 lanes: written as ``take_along_axis`` / ``.at[].set`` the
# compiler moved the whole plane to that layout and back in EVERY round
# of the scan (PERF.md section 6, PR 42).  Masks over U
# (:func:`_hit`) leave the plane where it lies, and a masked pass costs
# the plane's own bytes once: nothing at 136 nodes, 13-14 passes over
# 215 MB a round at 69,905 (3.55 ms, PR 43).  No formulation over the
# logical shape ``[E, M, U, LANES]`` does both (PERF.md section 6,
# PR 44's table), so the big levels are STORED otherwise: a level of
# 128 nodes or more as rows of 128 nodes (:func:`tree_layout`), one
# plane ``tree_rows [E, M, R, 512]`` whose 2-D view ``[E * M * R, 512]``
# is a bitcast, exactly the object planes' :func:`_peer_rows`.  A round
# GATHERS the one row per row level per replica that its path crosses
# (:func:`_gather_path_rows`: 576 rows of 2 KB at 64 x 3 x 1,048,576),
# folds, compares and substitutes inside the gathered block with its
# 128 nodes on the lanes (:func:`hash.fold_block`, ``nodes_last``), and
# scatters the rows back in place on the scan's carry.  The levels
# under 128 nodes keep the masks over ``tree_node``: a parent
# recomputed from a node level is :func:`hash.fold_block`, the path's
# stored hashes are compared with the recomputed ones in one pass
# (extracting them, a one-hot sum per level slice, brought a relayout
# back), and the path write is one select.  128 is the lane width and
# the only threshold: a shorter row would pad to it, a longer one has
# no tile to be a row of.  ``tree_leaf`` (3.2 GB there) is what must
# NEVER be passed over: the slot's leaf and the 16 leaves under its
# first parent are gathered, and its write is a scatter.


def _gather_children(arr: jax.Array, parent_idx: jax.Array,
                     n: int) -> jax.Array:
    """Gather the 16 children of ``parent_idx [E, W]`` from a
    per-replica level array ``arr [E, Ml, n, LANES]`` →
    ``[E, Ml, W, 16, LANES]`` (zero-padded beyond ``n``, matching
    :func:`_fold_blocks`).  The round calls it on ``tree_leaf`` only."""
    e, w = parent_idx.shape
    ml = arr.shape[1]
    idx = (parent_idx[..., None] * TREE_WIDTH
           + jnp.arange(TREE_WIDTH, dtype=jnp.int32))        # [E, W, 16]
    valid = idx < n
    idxc = jnp.clip(idx, 0, n - 1).reshape(e, 1, w * TREE_WIDTH, 1)
    g = jnp.take_along_axis(arr, idxc, axis=2)
    g = g.reshape(e, ml, w, TREE_WIDTH, hashk.LANES)
    return jnp.where(valid[:, None, :, :, None], g, jnp.uint32(0))


def _hit(n: int, idx: jax.Array) -> jax.Array:
    """One-hot of ``idx [E, W]`` over a level's n nodes, shaped to
    mask ``[E, Ml, W, n, LANES]``: ``[E, 1, W, n, 1]`` bool."""
    at = jnp.arange(n, dtype=jnp.int32)
    return (at == idx[..., None])[:, None, :, :, None]


def _node_levels(tree_node: jax.Array, s: int):
    """``(offset, level [E, Ml, n, LANES])`` of the levels that
    ``tree_node`` holds flat (those under 128 nodes), leafward → root."""
    lay = tree_layout(s)
    return [(off, jax.lax.slice_in_dim(tree_node, off, off + n, axis=2))
            for off, n in zip(lay.tail_offs, lay.sizes[lay.row_levels:])]


def _path_row_index(s: int, e: int, ml: int, slot: jax.Array
                    ) -> jax.Array:
    """``[E, Ml, L]``: the row of the 2-D view ``[E * Ml * R, 512]``
    that holds the path's node (and that node's 15 siblings) at each
    row level, ascending in that order."""
    lay = tree_layout(s)
    idx = slot[:, 0] // TREE_WIDTH                           # [E]
    at = []
    for off in lay.row_offs:
        at.append(off + idx // ROW_NODES)
        idx = idx // TREE_WIDTH
    replica = (jnp.arange(e, dtype=jnp.int32)[:, None] * ml
               + jnp.arange(ml, dtype=jnp.int32))            # [E, Ml]
    return (replica * lay.rows)[:, :, None] + jnp.stack(at, -1)[:, None]


def _gather_path_rows(tree_rows: Optional[jax.Array], s: int,
                      slot: jax.Array) -> Optional[jax.Array]:
    """The rows a round's paths cross, ``[E, Ml, L, LANES, 128]``: ONE
    gather on the 2-D view for all row levels, which the verify and the
    write of the round both read.  ``slot [E, 1]`` in range; None where
    the state has no row plane."""
    if tree_rows is None:
        return None
    e, ml, r, _ = tree_rows.shape
    if slot.shape[1] != 1:
        raise NotImplementedError(
            "the gathered path takes one lane (ROADMAP D12)")
    ridx = _path_row_index(s, e, ml, slot)
    got = tree_rows.reshape(e * ml * r, ROW_WORDS).at[
        ridx.reshape(-1)].get(unique_indices=True, indices_are_sorted=True,
                              mode="promise_in_bounds")
    return got.reshape(e, ml, ridx.shape[2], hashk.LANES, ROW_NODES)


def _row_hit(idx: jax.Array) -> jax.Array:
    """One-hot of node ``idx [E]`` inside its row, shaped to mask a
    gathered row ``[E, Ml, LANES, 128]``: ``[E, 1, 1, 128]`` bool."""
    at = jnp.arange(ROW_NODES, dtype=jnp.int32)
    return (at == (idx % ROW_NODES)[:, None])[:, None, None, :]


def _row_parent(row: jax.Array, pidx: jax.Array) -> jax.Array:
    """Parent ``pidx [E]`` folded from its 16 children inside the
    gathered row ``[E, Ml, LANES, 128]`` that holds them →
    ``[E, Ml, LANES]``."""
    per_row = ROW_NODES // TREE_WIDTH
    return hashk.fold_block(row, (pidx % per_row)[:, None], TREE_WIDTH,
                            nodes_last=True)


def _verify_path(tree_leaf: jax.Array, tree_node: jax.Array,
                 slot: jax.Array,
                 path_rows: Optional[jax.Array] = None) -> jax.Array:
    """Root-ward path verification for W slots per ensemble: recompute
    each stored parent on the paths from its stored children and
    compare (``get_path``/``verify_hash``, synctree.erl:302-340).
    ``slot [E, W]`` → ``[E, Ml, W]`` bool — replica's tree corrupted
    on lane w's path.  The first parent's children are 16 gathered
    leaves; a row level is checked inside its gathered row
    (``path_rows``, :func:`_gather_path_rows`), every level above by
    masks over ``tree_node`` ("Merkle paths")."""
    s = tree_leaf.shape[-2]
    t = tree_node.shape[2]
    node = tree_node[:, :, None]                             # [E,Ml,1,T,L]
    wrong = jnp.zeros((), bool)
    below, idx = None, slot
    row_wrong, carried = None, None
    if path_rows is not None:
        pidx = slot[:, 0] // TREE_WIDTH                      # [E]
        expect = hashk.fold(
            _gather_children(tree_leaf, pidx[:, None], s))[:, :, 0]
        row_wrong = jnp.zeros((), bool)
        for lv in range(path_rows.shape[2]):
            row = path_rows[:, :, lv]                        # [E,Ml,L,128]
            row_wrong = row_wrong | (_row_hit(pidx)
                                     & (row != expect[..., None]))
            idx, pidx = pidx[:, None], pidx // TREE_WIDTH
            expect = _row_parent(row, pidx)
        carried = expect[:, :, None]                         # [E,Ml,1,L]
    for off, level in _node_levels(tree_node, s):
        pidx = idx // TREE_WIDTH                             # [E, W]
        if below is not None:
            expect = hashk.fold_block(below[:, :, None], pidx[:, None, :],
                                      TREE_WIDTH)
        elif carried is not None:   # folded from the last row level's row
            expect = carried
        else:
            expect = hashk.fold(_gather_children(tree_leaf, pidx, s))
        wrong = wrong | (_hit(t, off + pidx)
                         & (node != expect[:, :, :, None]))
        below, idx = level, pidx
    wrong = wrong.any((3, 4))
    if row_wrong is not None:
        wrong = wrong | row_wrong.any((2, 3))[:, :, None]
    return wrong


def _write_path(tree_leaf: jax.Array, tree_node: jax.Array,
                slot: jax.Array, new_leaf: jax.Array, mask: jax.Array,
                tree_rows: Optional[jax.Array] = None,
                path_rows: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Set lane w's leaf to ``new_leaf [E, W, LANES]`` on replicas in
    ``mask [E, Ml, W]`` and recompute their root-ward paths — the
    synchronous write-path hash update (``update_hash`` +
    ``update_path``, peer.erl:1731-1738, synctree.erl:201-209).
    Non-writing replicas' nodes are untouched (a recompute would
    silently alter a corrupted-but-unwritten tree).  Returns
    ``(tree_leaf, tree_node, tree_rows)``.

    HBM discipline, plane by plane ("Merkle paths" above).
    ``tree_leaf`` is SCATTERED at the touched slot: only
    O(E·M·LANES) elements of the largest plane move (inside the kv
    scan the carried buffer aliases, so the scatter is an in-place
    update); a masked-off replica aims out of bounds and is DROPPED.
    ``tree_rows`` likewise: in each gathered row (``path_rows``, the
    round's one gather) this round's new child is substituted, the
    parent above folded from the row AS THIS ROUND WRITES IT, and the
    rows scattered back in one scatter, a masked-off replica's aimed at
    an out-of-range row of its own.  ``tree_node`` is written by ONE
    masked select over its T <= 136 nodes, each level's new parent
    folded from the level below with its own new child substituted in
    the mask, no intermediate plane.  One lane (W = 1, every caller's
    width): lanes that share a parent would each need the others' new
    children.
    """
    e, ml, w = mask.shape
    if w != 1:
        raise NotImplementedError(
            "the masked path write takes one lane (ROADMAP D12)")
    s = tree_leaf.shape[-2]
    eidx = jnp.arange(e, dtype=jnp.int32)[:, None, None]     # [E, 1, 1]
    midx = jnp.arange(ml, dtype=jnp.int32)[None, :, None]    # [1, Ml, 1]
    sl = jnp.where(mask, slot[:, None, :], s)                # [E, Ml, W]
    tree_leaf = tree_leaf.at[eidx, midx, sl].set(
        jnp.broadcast_to(new_leaf[:, None], (e, ml, w, hashk.LANES)),
        mode="drop")
    wr = mask[:, :, :, None, None]                           # [E,Ml,1,1,1]
    pidx = slot // TREE_WIDTH                                # [E, 1]
    parent = hashk.fold(_gather_children(tree_leaf, pidx, s))  # [E,Ml,1,L]
    if path_rows is not None:
        n_lv, r = path_rows.shape[2], tree_rows.shape[2]
        at, up = pidx[:, 0], parent[:, :, 0]                 # [E], [E,Ml,L]
        rows = []
        for lv in range(n_lv):
            rows.append(jnp.where(_row_hit(at), up[..., None],
                                  path_rows[:, :, lv]))
            at = at // TREE_WIDTH
            up = _row_parent(rows[-1], at)
        pidx, parent = at[:, None], up[:, :, None]
        # a masked-off replica's rows go past the end, each to a row
        # of its own (unique as the scatter is promised), and drop
        past = e * ml * r + jnp.arange(e * ml * n_lv, dtype=jnp.int32)
        ridx = jnp.where(mask, _path_row_index(s, e, ml, slot),
                         past.reshape(e, ml, n_lv))
        tree_rows = tree_rows.reshape(e * ml * r, ROW_WORDS).at[
            ridx.reshape(-1)].set(
                jnp.stack(rows, 2).reshape(e * ml * n_lv, ROW_WORDS),
                mode="drop", unique_indices=True
            ).reshape(e, ml, r, ROW_WORDS)
    levels = _node_levels(tree_node, s)
    writes = [(levels[0][0] + pidx, parent)]
    for (_, below), (off, _) in zip(levels, levels[1:]):
        # the level below with this round's write in place
        below = jnp.where(wr & _hit(below.shape[2], pidx),
                          parent[:, :, :, None], below[:, :, None])
        pidx = pidx // TREE_WIDTH
        parent = hashk.fold_block(below, pidx[:, None, :], TREE_WIDTH)
        writes.append((off + pidx, parent))
    node = tree_node[:, :, None]                             # [E,Ml,1,T,L]
    for tgt, parent in writes:
        node = jnp.where(wr & _hit(tree_node.shape[2], tgt),
                         parent[:, :, :, None], node)
    return tree_leaf, node[:, :, 0], tree_rows


def init_view_mask(n_peers: int, n_views: int = 2,
                   views: Optional[Sequence[Sequence[int]]] = None
                   ) -> np.ndarray:
    """The ``[V, M]`` view mask every ensemble of a fresh state starts
    with (:func:`init_state`'s ``views``): default one view of all
    peers."""
    if views is None:
        vm = np.zeros((n_views, n_peers), dtype=bool)
        vm[0, :] = True
        return vm
    assert len(views) <= n_views
    return views_to_mask(views, n_views, n_peers)


def init_state(n_ensembles: int, n_peers: int, n_slots: int,
               n_views: int = 2,
               views: Optional[Sequence[Sequence[int]]] = None) -> EngineState:
    """Fresh state: no leader, epoch 0, empty stores, trees built over
    the empty stores (every leaf = hash of the absent object).

    ``views`` is a list of views (each a list of global peer indices)
    applied to every ensemble; default one view of all peers.
    """
    e, m, s, v = n_ensembles, n_peers, n_slots, n_views
    vm = init_view_mask(m, v, views)
    zero = jnp.zeros((), jnp.int32)
    empty_leaf = hashk.obj_leaf_hash(zero, zero, zero)           # [LANES]
    leaves = jnp.broadcast_to(empty_leaf, (s, hashk.LANES))
    rows, tail = _build_tree(leaves)                # [R, 512], [T, LANES]
    return EngineState(
        epoch=jnp.zeros((e, m), jnp.int32),
        fact_seq=jnp.zeros((e, m), jnp.int32),
        leader=jnp.full((e,), -1, jnp.int32),
        view_mask=jnp.broadcast_to(jnp.asarray(vm), (e, v, m)),
        view_vsn=jnp.zeros((e,), jnp.int32),
        pend_vsn=jnp.zeros((e,), jnp.int32),
        commit_vsn=jnp.zeros((e,), jnp.int32),
        obj_seq_ctr=jnp.zeros((e,), jnp.int32),
        obj_epoch=jnp.zeros((e, m, s), jnp.int32),
        obj_seq=jnp.zeros((e, m, s), jnp.int32),
        obj_val=jnp.zeros((e, m, s), jnp.int32),
        tree_leaf=jnp.broadcast_to(leaves, (e, m, s, hashk.LANES)),
        tree_node=jnp.broadcast_to(tail, (e, m) + tail.shape),
        tree_rows=(None if rows is None
                   else jnp.broadcast_to(rows, (e, m) + rows.shape)),
    )


# ---------------------------------------------------------------------------
# Peer-axis reductions (ICI collectives under shard_map)


def _pmax(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    m = x.max(-1)
    if axis_name is not None:
        m = jax.lax.pmax(m, axis_name)
    return m


def _global_peer_idx(m_local: int, axis_name: Optional[str]) -> jax.Array:
    """Global peer indices of the local peer slice ([M_local] int32)."""
    idx = jnp.arange(m_local, dtype=jnp.int32)
    if axis_name is not None:
        idx = idx + jax.lax.axis_index(axis_name).astype(jnp.int32) * m_local
    return idx


def _quorum_met(ack: jax.Array, heard: jax.Array, view_mask: jax.Array,
                axis_name: Optional[str],
                met_only: bool = False) -> jax.Array:
    """Majority in EVERY active view (msg.erl:377-418), via the shared
    batched predicate :func:`quorum.quorum_met_batch`.

    ack [E, Ml] bool (epoch-matching up members — the caller's own vote
    is already included, so self_idx=-1); heard [E, Ml] bool (up
    members — heard-but-not-acking peers are nacks); view_mask
    [E, V, Ml] bool -> [E] bool.

    With ``RETPU_PALLAS_QUORUM=1`` (and no peer-axis sharding) the
    reduce runs as the Pallas kernel — differentially tested against
    this path.  ``met_only`` is ``quorum_met_batch``'s: the same
    answer (this function only ever asks ``== MET``) without the
    per-row gather, for a caller E rows wide.
    """
    if PALLAS_QUORUM and axis_name is None and ack.ndim == 2:
        from riak_ensemble_tpu.ops import pallas_quorum
        res = pallas_quorum.quorum_met_epallas(
            ack, heard & ~ack, view_mask,
            interpret=pallas_quorum.INTERPRET)
        return res == quorum_lib.MET
    if PALLAS_QUORUM and axis_name is None and ack.ndim == 3:
        # Wide-round shape [E, W, Ml] (every K/V round since the lane
        # refactor — W=1 included): flatten the lane axis into the
        # ensemble axis for the kernel, whose contract is [E', Ml].
        from riak_ensemble_tpu.ops import pallas_quorum
        e, w, ml = ack.shape
        # Broadcast BOTH a 3-dim [E, V, Ml] and an already-widened
        # 4-dim [E, W, V, Ml] view_mask to the full lane shape: a
        # 3-dim mask with W > 1 would otherwise reshape to the wrong
        # element count and crash any caller that didn't pre-widen.
        vm = jnp.broadcast_to(
            view_mask if view_mask.ndim == 4 else view_mask[:, None],
            (e, w) + view_mask.shape[-2:])
        res = pallas_quorum.quorum_met_epallas(
            ack.reshape(e * w, ml), (heard & ~ack).reshape(e * w, ml),
            vm.reshape(e * w, *vm.shape[-2:]),
            interpret=pallas_quorum.INTERPRET)
        return (res == quorum_lib.MET).reshape(e, w)
    res = quorum_met_batch(
        ack, heard & ~ack, view_mask,
        jnp.full(ack.shape[:-1], -1, jnp.int32),
        required="quorum", axis_name=axis_name, met_only=met_only)
    return res == quorum_lib.MET


def _latest_among(pe: jax.Array, ps: jax.Array, pv: jax.Array,
                  ok: jax.Array, axis_name: Optional[str]
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched ``get_latest_obj`` (peer.erl:1623-1662): the newest
    (epoch, seq) object among the replicas in ``ok`` (already filtered
    for reachability AND hash validity — the extra-check of
    :1646-1649), via a three-stage masked max-reduce over the trailing
    peer axis.  pe/ps/pv/ok are ``[..., Ml]``.

    Returns (epoch [...], seq [...], val [...], found [...]).
    """
    exists = ps > 0                                          # seq>=1 once written
    h = ok & exists
    neg = jnp.int32(-1)
    emax = _pmax(jnp.where(h, pe, neg), axis_name)           # [...]
    smax = _pmax(jnp.where(h & (pe == emax[..., None]), ps, neg), axis_name)
    on_max = h & (pe == emax[..., None]) & (ps == smax[..., None])
    vmax = _pmax(jnp.where(on_max, pv, jnp.iinfo(jnp.int32).min), axis_name)
    found = smax > 0
    return (jnp.maximum(emax, 0), jnp.maximum(smax, 0),
            jnp.where(found, vmax, 0), found)


# ---------------------------------------------------------------------------
# Election kernel


@functools.partial(jax.jit, static_argnames=("axis_name",))
def elect_step(state: EngineState, elect: jax.Array, cand: jax.Array,
               up: jax.Array, axis_name: Optional[str] = None
               ) -> Tuple[EngineState, jax.Array]:
    """Batched two-phase leader election for the ensembles in ``elect``.

    elect [E] bool — run an election in this ensemble this step.
    cand  [E] int32 — global peer index of the candidate (the reference
        picks whichever peer's randomized election timer fires first,
        peer.erl:493-505; the host supplies that choice).
    up    [E, Ml] bool — host availability mask (down/suspended peers
        never ack; the analog of synthesized nacks, msg.erl:134-138).

    Phase 1 (prepare, peer.erl:579-588): NextEpoch = max(epochs)+1;
    member peers with epoch < NextEpoch ack with their fact.  Phase 2
    (prelead new_epoch, :609-620): on quorum, members adopt NextEpoch,
    fact seq resets to 0, per-epoch obj counter resets (local_commit
    resets obj_seq, peer.erl:891-909).  Returns (state', elected [E]).
    """
    e, ml = state.epoch.shape
    gidx = _global_peer_idx(ml, axis_name)
    member = state.view_mask.any(1)                          # [E, Ml]
    heard = up & member
    next_epoch = _pmax(jnp.where(heard, state.epoch, -1), axis_name) + 1
    # Prepare acceptance is epoch < NextEpoch (peer.erl:506-519); with
    # NextEpoch = max(heard epochs)+1 computed from the same heard set,
    # every heard peer accepts by construction — refusal would need a
    # concurrent higher ballot, which sequential kernel launches over
    # consistent state rule out.
    ack = heard
    # The candidate must itself be an up member (it leads the round);
    # a host race handing in a dead/non-member candidate must not
    # produce a leader whose replica never adopted the new epoch.
    cand_heard = reduce_peers(
        ((gidx[None, :] == cand[:, None]) & heard).astype(jnp.int32),
        axis_name) > 0
    won = (_quorum_met(ack, heard, state.view_mask, axis_name)
           & elect & (cand >= 0) & cand_heard)

    adopt = won[:, None] & heard                             # [E, Ml]
    epoch = jnp.where(adopt, next_epoch[:, None], state.epoch)
    fact_seq = jnp.where(adopt, 0, state.fact_seq)
    leader = jnp.where(won, cand, state.leader)
    obj_seq_ctr = jnp.where(won, 0, state.obj_seq_ctr)
    return state._replace(epoch=epoch, fact_seq=fact_seq, leader=leader,
                          obj_seq_ctr=obj_seq_ctr), won


# ---------------------------------------------------------------------------
# K/V kernel


def _as_stored(x: jax.Array) -> jax.Array:
    """An ``[E, M, S]`` object plane named as the chip stores it,
    ``[M, E, S]`` (and back: the swap is its own inverse).  The chip
    keeps such a plane M OUTERMOST (layout ``{2,0,1}``, tiles over
    (E, S): the M of 3 or 5 is not padded to the tile's 8), so the
    view is a bitcast there, and a gather or scatter written against
    its rows (:func:`_peer_rows`) reads and writes the plane where it
    lies.  Written against ``[E, M, S]`` the compiler relayouts the
    whole plane E outermost and back: 50 MB each way per plane and
    ROUND at 64 x 3 x 65,536, 25.6 MB per launch at the sliced step's
    edges (PERF.md section 6, PRs 42 and 40).  Where the stored layout
    is another (small S, a CPU) the view costs what the compiler makes
    of it and the values are the same.  THE one name for "the plane
    as stored": the round's slot accesses and the sliced step's two
    edges both go through it."""
    return jnp.transpose(x, (1, 0, 2))


def _peer_rows(x: jax.Array) -> jax.Array:
    """``[E, M, S]`` viewed as ``[M * E, S]``: row ``m * E + e`` (a
    reshape of :func:`_as_stored`, so a bitcast where that is one and
    a shard's rows E are whole tiles of 8)."""
    e, m, s = x.shape
    return _as_stored(x).reshape(m * e, s)


def _slot_read(plane: jax.Array, slot: jax.Array) -> jax.Array:
    """``plane[e, m, slot[e, w]]`` of an ``[E, Ml, S]`` object plane →
    ``[E, Ml, W]`` (``slot [E, W]`` in range): each row of
    :func:`_peer_rows` gathers its W columns where it lies."""
    e, ml, _ = plane.shape
    with jax.named_scope("slot_gather"):
        got = jnp.take_along_axis(_peer_rows(plane),
                                  jnp.tile(slot, (ml, 1)), axis=1)
        return _as_stored(got.reshape(ml, e, slot.shape[1]))


def _slot_write(plane: jax.Array, slot: jax.Array,
                new: jax.Array) -> jax.Array:
    """``plane.at[e, m, slot[e, m, w]].set(new[e, w], mode="drop")``:
    ``slot [E, Ml, W]`` (S = out of range = no write), as one scatter
    into the rows of :func:`_peer_rows` (in place on the scan's carry)."""
    e, ml, s = plane.shape
    w = slot.shape[2]
    with jax.named_scope("slot_scatter"):
        row = jnp.arange(ml * e, dtype=jnp.int32)[:, None]
        out = _peer_rows(plane).at[
            row, _as_stored(slot).reshape(ml * e, w)].set(
                jnp.tile(new, (ml, 1)), mode="drop")
        return _as_stored(out.reshape(ml, e, s))


class _KvCtx(NamedTuple):
    """Loop-invariant K/V round context.

    Everything here depends only on ballot state (epoch/leader/views)
    and the ``up`` mask — none of which a K/V round mutates — so a
    scan of K rounds computes it (and its ~5 peer-axis collectives)
    exactly once (kv_step_scan).
    """

    heard: jax.Array        # [E, Ml] up members
    leader_up: jax.Array    # [E] the leader itself is up (it serves ops)
    lead_epoch: jax.Array   # [E] proposal epoch (leader's epoch)
    epoch_ok: jax.Array     # [E] epoch-check round reached quorum
    n_member: jax.Array     # [E] global member count (for all_or_quorum)


def _kv_context(state: EngineState, up: jax.Array,
                axis_name: Optional[str],
                met_only: bool = False) -> _KvCtx:
    e, ml = state.epoch.shape
    gidx = _global_peer_idx(ml, axis_name)                   # [Ml]
    is_leader = gidx[None, :] == state.leader[:, None]       # [E, Ml]
    has_leader = state.leader >= 0                           # [E]
    member = state.view_mask.any(1)
    heard = up & member
    # Leader's epoch, replicated to every shard (the proposal epoch).
    lead_epoch = reduce_peers(jnp.where(is_leader, state.epoch, 0),
                              axis_name)
    # Every op is served BY the leader (leased reads are the leader's
    # local read, puts include the leader's local put — peer.erl:1669-
    # 1698); a down leader serves nothing, whatever the quorum says.
    # This is also what makes commits durable under leased reads: a
    # committed write always includes the leader's own replica.
    leader_up = reduce_peers((is_leader & heard).astype(jnp.int32),
                             axis_name) > 0
    # Epoch-check acks: shared by put replication and non-leased reads.
    ack = heard & (state.epoch == lead_epoch[:, None])
    with jax.named_scope("quorum"):
        epoch_ok = (_quorum_met(ack, heard, state.view_mask, axis_name,
                                met_only)
                    & has_leader & leader_up)
    n_member = reduce_peers(member.astype(jnp.int32), axis_name)
    return _KvCtx(heard=heard, leader_up=leader_up & has_leader,
                  lead_epoch=lead_epoch, epoch_ok=epoch_ok,
                  n_member=n_member)


def _kv_round(state: EngineState, ctx: _KvCtx, kind: jax.Array,
              slot: jax.Array, val: jax.Array, lease_ok: jax.Array,
              axis_name: Optional[str],
              exp_epoch: Optional[jax.Array] = None,
              exp_seq: Optional[jax.Array] = None
              ) -> Tuple[EngineState, KvResult]:
    """One K/V protocol round given a precomputed context.

    kind/slot/val/lease_ok/exp_epoch/exp_seq are ``[E, W]``: a lane
    axis of W op lanes per ensemble whose valid slots must be DISTINCT
    within a row.  Every caller in the tree passes W = 1
    (``kind[:, None]``, squeezed after by :func:`_squeeze_lane`): the
    host scheduler that filled wider rows is gone, and the axis stays
    only because taking it out changes the served step's HLO (ROADMAP
    D12).  Lanes see the pre-round state and commit seqs in lane
    order; with W = 1 that is one op per ensemble per round.

    Corruption: a lane verifies against the PRE-round tree; a replica
    whose path fails is excluded from the round and flagged in
    ``tree_corrupt``, healed by the repair/scrub machinery one round
    later.
    """
    e = state.epoch.shape[0]
    s = state.obj_epoch.shape[-1]
    w = kind.shape[1]
    heard = ctx.heard                                        # [E, Ml]
    heard3 = heard[:, :, None]                               # [E, Ml, 1]
    leader_up = ctx.leader_up[:, None]                       # [E, 1]
    lead_epoch = ctx.lead_epoch[:, None]
    epoch_ok = ctx.epoch_ok[:, None]
    if exp_epoch is None:
        exp_epoch = jnp.zeros_like(kind)
    if exp_seq is None:
        exp_seq = jnp.zeros_like(kind)

    is_put = kind == OP_PUT
    is_get = kind == OP_GET
    is_cas = kind == OP_CAS
    is_rmw = kind == OP_RMW
    active = is_put | is_get | is_cas | is_rmw
    slot_valid = (slot >= 0) & (slot < s)                    # [E, W]
    slot_c = jnp.clip(slot, 0, s - 1)

    # Per-replica object at each lane's slot: ONE gather per plane
    # (invalid slots read the absent object).
    sv = slot_valid[:, None, :]
    pe = jnp.where(sv, _slot_read(state.obj_epoch, slot_c), 0)
    ps = jnp.where(sv, _slot_read(state.obj_seq, slot_c), 0)
    pv = jnp.where(sv, _slot_read(state.obj_val, slot_c), 0)

    # Integrity gate (tree-is-truth, synctree.erl:44-73): the object
    # must match its leaf, and the slot's root-ward path must verify.
    with jax.named_scope("merkle_verify"):
        leaf = jnp.take_along_axis(
            state.tree_leaf, slot_c[:, None, :, None], axis=2)  # [E,Ml,W,L]
        leaf_ok = (leaf == hashk.obj_leaf_hash(pe, ps, pv)).all(-1)
        path_rows = _gather_path_rows(state.tree_rows, s, slot_c)
        path_bad = _verify_path(state.tree_leaf, state.tree_node,
                                slot_c, path_rows)
    replica_ok = heard3 & leaf_ok & ~path_bad                # [E, Ml, W]
    tree_corrupt = ((path_bad | ~leaf_ok) & heard3
                    & (active & slot_valid)[:, None, :]).any(-1)

    # Peer-axis reductions run on the transposed [E, W, Ml] layout
    # (reduce_peers/quorum_met_batch contract: peers trailing).
    ok_t = replica_ok.transpose(0, 2, 1)                     # [E, W, Ml]

    # Read: newest object among valid replicas (hash extra-check).
    # ``obj_found`` is "some object exists" — possibly a tombstone
    # (val == 0, the device notfound-object); ``found`` is the
    # client-visible hit.  Tombstones carry full version discipline
    # (they win/lose by (epoch, seq) and replicate like any object)
    # but read back as notfound, exactly like the reference's notfound
    # obj (peer.erl:1568-1584).
    with jax.named_scope("quorum"):
        rd_epoch, rd_seq, rd_val, obj_found = _latest_among(
            pe.transpose(0, 2, 1), ps.transpose(0, 2, 1),
            pv.transpose(0, 2, 1), ok_t, axis_name)          # each [E, W]
        n_ok = reduce_peers(ok_t.astype(jnp.int32), axis_name)  # [E, W]
    found = obj_found & (rd_val != 0)
    all_ok = n_ok == ctx.n_member[:, None]

    get_gate = is_get & leader_up & (lease_ok | epoch_ok)
    stale = obj_found & (rd_epoch != lead_epoch)
    # Stale-epoch rewrite (update_key): needs the quorum either way.
    # A stale tombstone is rewritten at the current epoch too.
    rewrite = get_gate & stale & epoch_ok
    # Notfound with NO object anywhere: when every member replica
    # answered (valid) notfound, serve it without writing
    # (all_or_quorum full-response fast path, peer.erl:1568-1584);
    # otherwise a notfound tombstone must commit at the current epoch
    # so a stale straggler write cannot later win (update_key with
    # notfound, :1564-1596).  The tombstone additionally needs a
    # QUORUM of hash-valid notfound answers (non-valid heard replicas
    # count as nacks) — the reference's update_key read round fails on
    # the hash extra-check rather than erasing data the integrity gate
    # excluded; without this, corrupting the leaves of every holder
    # would let a single GET tombstone over a committed object.
    # Out-of-range slots never held data: plain notfound.
    nf = get_gate & ~obj_found
    with jax.named_scope("quorum"):
        nf_quorum = _quorum_met(
            ok_t, jnp.broadcast_to(heard[:, None, :], ok_t.shape),
            jnp.broadcast_to(state.view_mask[:, None],
                             (e, w) + state.view_mask.shape[1:]),
            axis_name)                                       # [E, W]
    # (scope "apply": the decision what commits, with which value
    # and seq, and which replicas a read repairs)
    with jax.named_scope("apply"):
        nf_write = nf & slot_valid & ~all_ok & epoch_ok & nf_quorum
        get_ok = ((get_gate & obj_found & (~stale | rewrite))
                  | (nf & (all_ok | ~slot_valid | nf_write)))

        # Commit path (shared by put, CAS, rewrite and notfound
        # tombstone).  CAS compares the expected version against the
        # slot's CURRENT stored version atomically within this round (the
        # do_kupdate (epoch, seq) equality, peer.erl:259-270 — atomic
        # because no other lane in the round touches this slot);
        # expecting (0, 0) on an absent slot is create-if-missing
        # (do_kput_once, :278-284).  A tombstone counts as an existing
        # version for the compare (ksafe_delete reads the tombstone's vsn)
        # but val 0 still reads back notfound.
        put_commit = is_put & epoch_ok & slot_valid
        exp_absent = (exp_epoch == 0) & (exp_seq == 0)
        # (0, 0) matches a tombstone as well as true absence — put-once
        # succeeds over a notfound-valued object (do_kput_once,
        # peer.erl:278-284) — and TRUE absence additionally needs a quorum
        # of hash-valid notfound answers (same nf_quorum guard as the GET
        # tombstone path): without it, corrupting every holder's leaves
        # would let a (0,0) CAS overwrite committed data the integrity
        # gate excluded.
        vsn_match = ((obj_found & (rd_epoch == exp_epoch)
                      & (rd_seq == exp_seq))
                     | (exp_absent & obj_found & (rd_val == 0))
                     | (exp_absent & ~obj_found & nf_quorum))
        cas_commit = is_cas & epoch_ok & slot_valid & vsn_match

        # Device RMW (OP_RMW): fn(cur, operand) committed in THIS round —
        # the fused kmodify.  ``cur`` is the round's own latest-object
        # read (tombstones and verified absence read as 0, the engine's
        # notfound value), so concurrent RMWs of one slot serialize
        # through round order with no conflict window.  Absence must be
        # VERIFIED (the same nf_quorum guard as the (0,0)-CAS create):
        # treating not-found-because-every-holder-is-corrupt as 0 would
        # overwrite committed data the integrity gate excluded.
        fn = exp_epoch                                           # [E, W]
        cur = jnp.where(obj_found, rd_val, 0)
        new_rmw = jnp.select(
            [fn == RMW_ADD, fn == RMW_SUB, fn == RMW_MAX, fn == RMW_MIN,
             fn == RMW_SET, fn == RMW_BAND, fn == RMW_BOR,
             fn == RMW_BXOR],
            [cur + val, cur - val, jnp.maximum(cur, val),
             jnp.minimum(cur, val), val, cur & val, cur | val, cur ^ val],
            default=val)                  # RMW_PIA commits the operand
        rmw_absent = ((obj_found & (rd_val == 0))
                      | (~obj_found & nf_quorum))
        rmw_known = obj_found | nf_quorum
        rmw_commit = (is_rmw & epoch_ok & slot_valid
                      & jnp.where(fn == RMW_PIA, rmw_absent, rmw_known))

        commit = (put_commit | cas_commit | rewrite | nf_write
                  | rmw_commit)                                  # [E, W]
        wval = jnp.where(is_put | is_cas, val,
                         jnp.where(is_rmw, new_rmw,
                                   jnp.where(rewrite, rd_val, 0)))

        # Commit seqs advance in lane order (obj_sequence, peer.erl:1776-
        # 1791): lane w's seq is ctr + (commits among lanes <= w), exactly
        # the values W sequential rounds would assign.
        ranks = jnp.cumsum(commit.astype(jnp.int32), axis=1)     # [E, W]
        new_seq = state.obj_seq_ctr[:, None] + ranks

        # Read repair (maybe_repair, peer.erl:1518-1536): a successful
        # current-epoch read heals reachable replicas that lag the winning
        # version or failed the integrity gate (re-writing the slot also
        # recomputes their hash path, healing tree corruption).
        plain_read = get_ok & obj_found & ~rewrite               # [E, W]
        divergent = heard3 & ((pe != rd_epoch[:, None, :])
                              | (ps != rd_seq[:, None, :])
                              | ~leaf_ok | path_bad)
        repair = plain_read[:, None, :] & divergent              # [E, Ml, W]

        w_epoch = jnp.where(commit, lead_epoch, rd_epoch)        # [E, W]
        w_seq = jnp.where(commit, new_seq, rd_seq)
        w_val = jnp.where(commit, wval, rd_val)
        do_write = (commit[:, None, :] & heard3) | repair        # [E, Ml, W]

    # Scatter, not full-plane where: per round only the touched slot
    # columns move through HBM (in place inside the kv scan's carry).
    # Non-writing lanes aim out of bounds and are dropped, so clipped
    # invalid slots can never collide with a real lane's write.
    sl2 = jnp.where(do_write, slot_c[:, None, :], s)         # [E, Ml, W]
    obj_epoch = _slot_write(state.obj_epoch, sl2, w_epoch)
    obj_seq = _slot_write(state.obj_seq, sl2, w_seq)
    obj_val = _slot_write(state.obj_val, sl2, w_val)
    obj_seq_ctr = state.obj_seq_ctr + ranks[:, -1]

    # Synchronous tree maintenance: leaves + root-ward paths, same
    # round.  Lanes sharing a path parent recompute it identically
    # from the post-scatter children, so duplicate targets agree.
    with jax.named_scope("merkle_write"):
        new_leaf = hashk.obj_leaf_hash(w_epoch, w_seq, w_val)  # [E, W, L]
        tree_leaf, tree_node, tree_rows = _write_path(
            state.tree_leaf, state.tree_node, slot_c, new_leaf,
            do_write, state.tree_rows, path_rows)

    # Version reported for any served object INCLUDING tombstones —
    # the reference's kget hands back the notfound obj with its vsn,
    # which is what ksafe_delete's CAS compares against
    # (client.erl:kget → peer.erl:1568-1584 tombstone objects).
    out_epoch = jnp.where(commit, lead_epoch,
                          jnp.where(get_ok & obj_found, rd_epoch, 0))
    out_seq = jnp.where(commit, new_seq,
                        jnp.where(get_ok & obj_found, rd_seq, 0))
    res = KvResult(
        committed=commit,
        get_ok=get_ok,
        found=found & get_ok,
        # reads report the winning value; a committed RMW reports the
        # value it COMPUTED (the host mirror/WAL needs it without a
        # follow-up read)
        value=jnp.where(rmw_commit, new_rmw,
                        jnp.where(get_ok & found, rd_val, 0)),
        obj_vsn=jnp.stack([out_epoch, out_seq], -1),
        quorum_ok=jnp.broadcast_to(ctx.epoch_ok[:, None], commit.shape),
        tree_corrupt=tree_corrupt,
    )
    new_state = state._replace(obj_epoch=obj_epoch, obj_seq=obj_seq,
                               obj_val=obj_val, obj_seq_ctr=obj_seq_ctr,
                               tree_leaf=tree_leaf, tree_node=tree_node,
                               tree_rows=tree_rows)
    return new_state, res


@functools.partial(jax.jit, static_argnames=("axis_name",))
def kv_step(state: EngineState, kind: jax.Array, slot: jax.Array,
            val: jax.Array, lease_ok: jax.Array, up: jax.Array,
            axis_name: Optional[str] = None,
            exp_epoch: Optional[jax.Array] = None,
            exp_seq: Optional[jax.Array] = None
            ) -> Tuple[EngineState, KvResult]:
    """One K/V protocol round per ensemble, batched over E.

    kind [E] int32 (OP_NOOP/OP_GET/OP_PUT/OP_CAS/OP_RMW); slot [E]
    int32; val [E] int32 (payload for puts/CAS; the int32 operand for
    RMW); exp_epoch/exp_seq [E] int32 (the CAS expected version — for
    OP_RMW rows exp_epoch instead carries the mod-fun table code
    (RMW_*); ignored for other kinds, default 0); lease_ok [E] bool
    (host lease check, check_lease peer.erl:1493-1516); up [E, Ml]
    bool.

    Semantics per ensemble:
    - PUT: one quorum round.  Proposal (lead_epoch, ctr+1); member
      replicas whose epoch matches ack (valid_request, peer.erl
      :869-871 — stale-epoch followers nack); on majority in every
      view, all heard member replicas apply the write (put_obj,
      :1669-1698), their tree leaf + hash path update in the same
      round (update_hash/send_update_hash, :1700-1715), and the
      counter advances (obj_sequence, :1776-1791).
    - GET: if lease_ok, leased local read; else the quorum epoch-check
      round gates it (:1460-1468).  Replicas failing the integrity
      gate (leaf/path hash mismatch) are excluded; the value returned
      is the newest version among the remaining replicas
      (get_latest_obj + hash extra-check, :1623-1662); a stale-epoch
      winner is rewritten at the current epoch through the quorum
      machinery (update_key, :1564-1596); a current-epoch read heals
      lagging/corrupt replicas (maybe_repair, :1518-1536); a notfound
      with unreachable members commits a tombstone (all_or_quorum,
      :1568-1584) — all batched across ensembles.
    - RMW: the fused kmodify (do_kmodify, peer.erl:303-317).  One
      quorum round reads the slot's latest hash-valid value, applies
      the registered table fun (exp_epoch = fun code, val = operand)
      and commits the result at (lead_epoch, next seq) — read and
      write atomic within the round, so device RMWs never
      CAS-conflict.  Arithmetic funs read absence/tombstones as 0;
      RMW_PIA (put-if-absent) commits only over verified absence or
      a tombstone; a fun result of 0 writes the tombstone.  The
      committed value is reported in ``KvResult.value``.
    """
    ctx = _kv_context(state, up, axis_name)
    state, res = _kv_round(
        state, ctx, kind[:, None], slot[:, None], val[:, None],
        lease_ok[:, None], axis_name,
        None if exp_epoch is None else exp_epoch[:, None],
        None if exp_seq is None else exp_seq[:, None])
    return _adopt_epochs(state, ctx), _squeeze_lane(res)


def _squeeze_lane(res: KvResult) -> KvResult:
    """Collapse a W=1 wide result back to the scalar [E] shapes
    (tree_corrupt is already lane-reduced to [E, Ml])."""
    return res._replace(
        committed=res.committed[:, 0], get_ok=res.get_ok[:, 0],
        found=res.found[:, 0], value=res.value[:, 0],
        obj_vsn=res.obj_vsn[:, 0], quorum_ok=res.quorum_ok[:, 0])


def _adopt_epochs(state: EngineState, ctx: _KvCtx) -> EngineState:
    """Follower epoch catch-up — the ``following({commit, Fact})``
    adoption (peer.erl:794-836): a heard member whose ballot epoch
    trails a live leader's adopts it at the END of the launch (it was
    a nack for THIS launch's quorums, exactly like a stale follower
    nacking until the commit round reaches it, and acks from the
    next).  Without this a peer returning from downtime would stay a
    permanent nack until the next election."""
    heal = (ctx.heard & ctx.leader_up[:, None]
            & (state.epoch < ctx.lead_epoch[:, None]))
    return state._replace(
        epoch=jnp.where(heal, ctx.lead_epoch[:, None], state.epoch))


@functools.partial(jax.jit, static_argnames=("axis_name",))
def kv_step_scan(state: EngineState, kind: jax.Array, slot: jax.Array,
                 val: jax.Array, lease_ok: jax.Array, up: jax.Array,
                 axis_name: Optional[str] = None,
                 exp_epoch: Optional[jax.Array] = None,
                 exp_seq: Optional[jax.Array] = None
                 ) -> Tuple[EngineState, KvResult]:
    """K sequential K/V rounds per ensemble in one launch.

    kind/slot/val (and exp_epoch/exp_seq when any op is OP_CAS):
    [K, E]; lease_ok: [K, E]; up: [E, Ml] (held fixed across the K
    rounds).  Sequentiality per ensemble preserves the per-key
    serialization the reference gets from key-hashed workers (async/3,
    peer.erl:1220-1225) — and makes each CAS's read-compare-write
    atomic.  Results are stacked [K, E].

    Ballot state (epoch/leader/views) is invariant across the rounds,
    so the round context — including its peer-axis collectives — is
    computed once outside the scan.
    """
    ctx = _kv_context(state, up, axis_name)
    if exp_epoch is None:
        exp_epoch = jnp.zeros_like(kind)
    if exp_seq is None:
        exp_seq = jnp.zeros_like(kind)

    def body(st, op):
        k, sl, v, lz, xe, xs = op
        st2, r = _kv_round(st, ctx, k[:, None], sl[:, None], v[:, None],
                           lz[:, None], axis_name, xe[:, None],
                           xs[:, None])
        return st2, _squeeze_lane(r)

    state, res = jax.lax.scan(
        body, state, (kind, slot, val, lease_ok, exp_epoch, exp_seq))
    return _adopt_epochs(state, ctx), res


# ---------------------------------------------------------------------------
# Result-plane compaction (active-column gather)


def gather_result_columns(res: KvResult,
                          active_idx: jax.Array) -> KvResult:
    """Active-column compaction of a packed-layout result: gather the
    per-round ensemble axis of the CLIENT result planes down to the
    active column set — ``[K, E] → [K, A]``.

    ``active_idx [A]`` holds the global column indices the flush
    actually scheduled ops into, A pow2-bucketed by the host for
    compile reuse (padding entries repeat index 0 and are ignored by
    the host unpack).  Only the planes a client op consumes move:
    ``quorum_ok`` (lease renewal reads EVERY column's epoch-check
    outcome) and ``tree_corrupt`` (corrupt-plane flags of *inactive*
    columns must still reach the scrub path; the ``E·M`` mask is
    bit-packed and cheap) deliberately stay full width.  Compaction is
    a pure re-indexing: the gathered planes are bit-identical to the
    full-width pack's active columns, and inactive columns carry only
    the all-false/zero NOOP results the host reconstructs at unpack.
    """
    def take(x):
        return jnp.take(x, active_idx, axis=1)
    with jax.named_scope("result_pack"):
        return res._replace(
            committed=take(res.committed), get_ok=take(res.get_ok),
            found=take(res.found), value=take(res.value),
            obj_vsn=take(res.obj_vsn))


# ---------------------------------------------------------------------------
# Integrity maintenance kernels (exchange / repair, §2.3)


@functools.partial(jax.jit, static_argnames=("axis_name",))
def verify_trees(state: EngineState, axis_name: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Full integrity sweep per replica (the BFS ``verify``,
    synctree.erl:549-571, one fused pass): recompute every upper level
    from the stored leaves and every leaf from the stored object.

    Returns ``(node_bad [E, Ml], leaf_bad [E, Ml])`` — upper-tree
    corruption vs object/leaf divergence.
    """
    del axis_name  # per-replica local; no collectives needed
    node_bad = _trees_differ(state, *_build_tree(state.tree_leaf))
    expect_leaf = hashk.obj_leaf_hash(state.obj_epoch, state.obj_seq,
                                      state.obj_val)
    leaf_bad = (expect_leaf != state.tree_leaf).any(-1).any(-1)
    return node_bad, leaf_bad


@jax.jit
def rebuild_trees(state: EngineState, mask: jax.Array) -> EngineState:
    """Rebuild replicas' trees from their object stores (the repair =
    segment delete + full rehash, riak_ensemble_peer_tree.erl:264-277).
    ``mask [E, Ml]`` selects replicas; others untouched."""
    leaves = hashk.obj_leaf_hash(state.obj_epoch, state.obj_seq,
                                 state.obj_val)
    tree_leaf = jnp.where(mask[:, :, None, None], leaves, state.tree_leaf)
    tree_rows, tree_node = _select_trees(mask, *_build_tree(tree_leaf),
                                         state)
    return state._replace(tree_leaf=tree_leaf, tree_node=tree_node,
                          tree_rows=tree_rows)


def _pmax2(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    """Max over the peer axis (axis 1) of [E, Ml, S] → [E, S]."""
    m = x.max(1)
    if axis_name is not None:
        m = jax.lax.pmax(m, axis_name)
    return m


@functools.partial(jax.jit, static_argnames=("axis_name",))
def exchange_step(state: EngineState, run: jax.Array, up: jax.Array,
                  axis_name: Optional[str] = None
                  ) -> Tuple[EngineState, jax.Array, jax.Array]:
    """Whole-store anti-entropy in one kernel — the tree exchange
    (riak_ensemble_exchange.erl:67-98) redesigned for the batch axis.

    The reference walks differing tree buckets level by level and
    adopts remote-newer objects per key.  On device the whole slot
    axis is one masked max-reduce: for every slot, the newest
    hash-valid object among reachable replicas wins
    (``valid_obj_hash(B, A)`` gate, exchange.erl:91-96), every
    reachable replica adopts it, and adopting replicas rebuild their
    trees.  Gated per ensemble on ``run`` AND a reachable majority
    (trust_majority, exchange.erl:109-126).

    Returns ``(state', diverged [E, Ml], synced [E])`` — which replicas
    held divergent/invalid data, and which ensembles completed.
    """
    member = state.view_mask.any(1)
    heard = up & member
    met = _quorum_met(heard, heard, state.view_mask, axis_name)
    adopt = run & met                                        # [E]

    # Source validity is the OBJECT hash (the leaf — valid_obj_hash
    # compares obj hashes, exchange.erl:91-96).  A replica whose upper
    # tree is corrupt still has trustworthy objects (its leaves vouch
    # for them); its tree gets rebuilt below, matching the reference's
    # repair-by-rehash-from-data (peer_tree.erl:264-277) rather than
    # data discard.
    expect_leaf = hashk.obj_leaf_hash(state.obj_epoch, state.obj_seq,
                                      state.obj_val)
    leaf_ok = (expect_leaf == state.tree_leaf).all(-1)       # [E, Ml, S]
    node_ok = ~_trees_differ(state, *_build_tree(state.tree_leaf))
    h = heard[:, :, None] & leaf_ok & (state.obj_seq > 0)

    neg = jnp.int32(-1)
    emax = _pmax2(jnp.where(h, state.obj_epoch, neg), axis_name)  # [E, S]
    smax = _pmax2(jnp.where(h & (state.obj_epoch == emax[:, None, :]),
                            state.obj_seq, neg), axis_name)
    on_max = (h & (state.obj_epoch == emax[:, None, :])
              & (state.obj_seq == smax[:, None, :]))
    vmax = _pmax2(jnp.where(on_max, state.obj_val,
                            jnp.iinfo(jnp.int32).min), axis_name)
    found = smax > 0                                         # [E, S]
    w_epoch = jnp.where(found, emax, 0)
    w_seq = jnp.where(found, smax, 0)
    w_val = jnp.where(found, vmax, 0)

    # Adopt ONLY where a hash-valid winner exists: a slot with no
    # valid holder (e.g. every copy's leaf is damaged) is left for
    # host-driven repair — exchange must never erase data it cannot
    # replace.
    tgt = (adopt[:, None, None] & heard[:, :, None]
           & found[:, None, :])                              # [E, Ml, S]
    mismatch = ((state.obj_epoch != w_epoch[:, None, :])
                | (state.obj_seq != w_seq[:, None, :])
                | (state.obj_val != w_val[:, None, :]))
    diverged = ((mismatch | ~leaf_ok)
                & adopt[:, None, None] & heard[:, :, None]).any(-1) | \
        (~node_ok & adopt[:, None] & heard)
    obj_epoch = jnp.where(tgt, w_epoch[:, None, :], state.obj_epoch)
    obj_seq = jnp.where(tgt, w_seq[:, None, :], state.obj_seq)
    obj_val = jnp.where(tgt, w_val[:, None, :], state.obj_val)

    # Refresh leaves for adopted slots only: a damaged leaf at a
    # no-winner slot must stay mismatched (rehashing it would bless
    # the corrupt object as valid).  Upper levels rebuild from the
    # resulting leaves, healing tree corruption (repair-by-rehash).
    leaves = hashk.obj_leaf_hash(obj_epoch, obj_seq, obj_val)
    rebuild = adopt[:, None] & heard                         # [E, Ml]
    fix_leaf = tgt | (leaf_ok & rebuild[:, :, None])
    tree_leaf = jnp.where(fix_leaf[..., None], leaves, state.tree_leaf)
    tree_rows, tree_node = _select_trees(rebuild, *_build_tree(tree_leaf),
                                         state)
    new_state = state._replace(obj_epoch=obj_epoch, obj_seq=obj_seq,
                               obj_val=obj_val, tree_leaf=tree_leaf,
                               tree_node=tree_node, tree_rows=tree_rows)
    return new_state, diverged, adopt


@jax.jit
def reset_rows(state: EngineState, mask: jax.Array,
               new_view: jax.Array) -> EngineState:
    """Recycle ensemble rows for fresh ensembles — the device half of
    dynamic ensemble creation (``riak_ensemble_manager:create_ensemble``,
    manager.erl:157-166, re-designed for fixed device arrays: a
    logical ensemble maps to a physical row; destroy frees the row,
    create resets and re-views it).

    mask [E] bool — rows being (re)created; new_view [E, M] bool —
    their initial single view.  Reset clears the object store, trees
    (rebuilt over the empty store), leader, seq counters and the
    views list; the ballot ``epoch`` is deliberately KEPT — epochs
    stay monotone per physical row, so any straggler op addressed to
    the destroyed tenant can never outrank the new tenant's ballots
    (the same reuse discipline the service applies to key slots).
    """
    zero = jnp.int32(0)
    head_view = jnp.concatenate(
        [new_view[:, None, :],
         jnp.zeros_like(state.view_mask[:, 1:, :])], axis=1)
    m3 = mask[:, None, None]
    st = state._replace(
        fact_seq=jnp.where(mask[:, None], zero, state.fact_seq),
        leader=jnp.where(mask, jnp.int32(-1), state.leader),
        view_mask=jnp.where(m3, head_view, state.view_mask),
        view_vsn=jnp.where(mask, state.view_vsn + 1, state.view_vsn),
        pend_vsn=jnp.where(mask, zero, state.pend_vsn),
        commit_vsn=jnp.where(mask, zero, state.commit_vsn),
        obj_seq_ctr=jnp.where(mask, zero, state.obj_seq_ctr),
        obj_epoch=jnp.where(m3, zero, state.obj_epoch),
        obj_seq=jnp.where(m3, zero, state.obj_seq),
        obj_val=jnp.where(m3, zero, state.obj_val),
    )
    return rebuild_trees(st, jnp.broadcast_to(
        mask[:, None], state.epoch.shape))


# ---------------------------------------------------------------------------
# Membership reconfiguration kernel (joint consensus, ladder #5)


def _reconfig_gate(state: EngineState, up: jax.Array,
                   axis_name: Optional[str]
                   ) -> Tuple[jax.Array, jax.Array]:
    """(heard [E, Ml], commit quorum in every CURRENT view [E]) — the
    try_commit gate (peer.erl:776-788) on epoch-matching acks."""
    member_now = state.view_mask.any(1)                      # [E, Ml]
    heard = up & member_now
    has_leader = state.leader >= 0
    gidx = _global_peer_idx(state.epoch.shape[1], axis_name)
    is_leader = gidx[None, :] == state.leader[:, None]
    lead_epoch = reduce_peers(jnp.where(is_leader, state.epoch, 0),
                              axis_name)
    ack = heard & (state.epoch == lead_epoch[:, None])
    commit_ok = (_quorum_met(ack, heard, state.view_mask, axis_name)
                 & has_leader)
    return heard, commit_ok


@functools.partial(jax.jit, static_argnames=("axis_name",))
def reconfig_propose(state: EngineState, propose: jax.Array,
                     new_view: jax.Array, vsn: jax.Array, up: jax.Array,
                     axis_name: Optional[str] = None
                     ) -> Tuple[EngineState, jax.Array]:
    """Batched ``update_members`` + ``maybe_change_views``
    (peer.erl:655-672, 1115-1135): CONS the proposed view onto the
    views list and adopt the manager's pending version.

    propose [E] bool; new_view [E, Ml] bool; vsn [E] int32 — the
    pending change's version from the manager/root (gossip side);
    up [E, Ml] bool.  Per ensemble, the install happens iff:

    - a commit quorum holds in EVERY current view (the try_commit
      gate — a joint ensemble may take FURTHER changes before
      transitioning, exactly like consing onto the views list);
    - ``vsn > pend_vsn`` (stale/duplicate pending changes are ignored,
      the maybe_change_views vsn guard, :1117-1121);
    - the proposed view is non-empty and the views list has a free
      slot (the device bounds list depth at V; a full list nacks and
      the host retries after a transition — backpressure the
      reference gets implicitly from transition frequency).

    Effect: views = [new | views], ``view_vsn`` bumps, ``pend_vsn``
    adopts ``vsn``, fact seq bumps on the replicas that heard it.
    Returns (state', installed [E]).
    """
    heard, commit_ok = _reconfig_gate(state, up, axis_name)
    new_nonempty = reduce_peers(new_view.astype(jnp.int32),
                                axis_name) > 0               # [E]
    # Free capacity: the last (oldest) slot must be unused.
    tail_used = reduce_peers(
        state.view_mask[:, -1, :].astype(jnp.int32), axis_name) > 0
    vsn_ok = vsn > state.pend_vsn
    install = propose & commit_ok & new_nonempty & ~tail_used & vsn_ok

    shifted = jnp.concatenate(
        [new_view[:, None, :], state.view_mask[:, :-1, :]], axis=1)
    view_mask = jnp.where(install[:, None, None], shifted,
                          state.view_mask)
    bump = install[:, None] & heard
    return state._replace(
        view_mask=view_mask,
        view_vsn=jnp.where(install, state.view_vsn + 1, state.view_vsn),
        pend_vsn=jnp.where(install, vsn, state.pend_vsn),
        fact_seq=jnp.where(bump, state.fact_seq + 1, state.fact_seq),
    ), install


@functools.partial(jax.jit, static_argnames=("axis_name",))
def reconfig_transition(state: EngineState, run: jax.Array,
                        up: jax.Array,
                        axis_name: Optional[str] = None
                        ) -> Tuple[EngineState, jax.Array]:
    """Batched ``maybe_transition``/``transition`` (peer.erl:751-774,
    1199-1214): once the joint configuration has a commit quorum in
    EVERY view, collapse the list to the head view alone and record
    ``commit_vsn = pend_vsn`` (the dance's final step,
    doc/Readme.md:106-153).  Returns (state', collapsed [E])."""
    heard, commit_ok = _reconfig_gate(state, up, axis_name)
    is_joint = reduce_peers(
        state.view_mask[:, 1:, :].any(1).astype(jnp.int32), axis_name) > 0
    collapse = run & is_joint & commit_ok

    head_only = jnp.concatenate(
        [state.view_mask[:, :1, :],
         jnp.zeros_like(state.view_mask[:, 1:, :])], axis=1)
    view_mask = jnp.where(collapse[:, None, None], head_only,
                          state.view_mask)
    bump = collapse[:, None] & heard
    return state._replace(
        view_mask=view_mask,
        view_vsn=jnp.where(collapse, state.view_vsn + 1, state.view_vsn),
        commit_vsn=jnp.where(collapse, state.pend_vsn, state.commit_vsn),
        fact_seq=jnp.where(bump, state.fact_seq + 1, state.fact_seq),
    ), collapse


@functools.partial(jax.jit, static_argnames=("axis_name",))
def reconfig_step(state: EngineState, propose: jax.Array,
                  new_view: jax.Array, up: jax.Array,
                  axis_name: Optional[str] = None
                  ) -> Tuple[EngineState, jax.Array, jax.Array]:
    """One reconfig phase per ensemble, batched over E — the fused
    convenience over :func:`reconfig_propose` /
    :func:`reconfig_transition`: ensembles with ``propose`` cons the
    new view (vsn auto-derived as pend_vsn+1, i.e. the manager's next
    pending version), the rest transition if joint and able.

    propose  [E] bool; new_view [E, Ml] bool; up [E, Ml] bool.
    Returns (state', installed [E], collapsed [E]).  Leaders whose
    commit gate fails keep their current views (the host steps them
    down / retries, as the reference does on failed try_commit).
    """
    state, installed = reconfig_propose(
        state, propose, new_view, state.pend_vsn + 1, up,
        axis_name=axis_name)
    state, collapsed = reconfig_transition(state, ~propose, up,
                                           axis_name=axis_name)
    return state, installed, collapsed


# ---------------------------------------------------------------------------
# Fused full step (election + K ops) — the "training step" analog


def _full_step_body(state: EngineState, elect: jax.Array, cand: jax.Array,
                    kind: jax.Array, slot: jax.Array, val: jax.Array,
                    lease_ok: jax.Array, up: jax.Array,
                    axis_name: Optional[str] = None,
                    exp_epoch: Optional[jax.Array] = None,
                    exp_seq: Optional[jax.Array] = None
                    ) -> Tuple[EngineState, jax.Array, KvResult]:
    """Election round (where needed) followed by K K/V rounds, fused.

    This is the flagship jitted step: the host decides *which*
    ensembles need elections (failure detection is host-side), the
    device does all the protocol math.
    """
    with jax.named_scope("elect"):
        state, won = elect_step(state, elect, cand, up,
                                axis_name=axis_name)
    state, res = kv_step_scan(state, kind, slot, val, lease_ok, up,
                              axis_name=axis_name, exp_epoch=exp_epoch,
                              exp_seq=exp_seq)
    return state, won, res


full_step = jax.jit(_full_step_body, static_argnames=("axis_name",))

#: ``full_step`` with the state argument DONATED (``donate_argnums``):
#: back-to-back launches alias the output state buffers onto the
#: input's instead of allocating + copying the E×M(×S) planes each
#: launch.  The caller's input ``EngineState`` is CONSUMED — any
#: retained reference (rollback snapshots included) is invalid after
#: the call on backends that honor donation; backends that don't
#: (older CPU runtimes) fall back to a copy with a one-time warning.
#: Used by the service's pipelined launch path (RETPU_DONATE).
full_step_donate = jax.jit(_full_step_body,
                           static_argnames=("axis_name",),
                           donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Active-column SLICED full step (the shrunk [K, A] launch grid)


# The sliced step's two edges address the A rows of a plane IN THE
# LAYOUT THE PLANE IS STORED IN, so a launch reads and writes [., A]
# and never [., E]: an object plane as A * M rows of
# :func:`_peer_rows` (see :func:`_as_stored`; ``jnp.take(x, idx,
# axis=0)`` / ``x.at[idx].set(...)`` relayouted the WHOLE plane both
# ways on every launch, three quarters of the device's busy time at
# 10,000 x 5 x 128, PERF.md section 6, PR 40).  The gather is a row
# gather and the scatter one in-place fusion on the donated buffer.

#: the state planes a sliced launch addresses as rows
_ROW_VIEWED = ("obj_epoch", "obj_seq", "obj_val")


def _take_peer_rows(x: jax.Array, idx_c: jax.Array) -> jax.Array:
    """``jnp.take(x, idx_c, axis=0)`` of an ``[E, M, S]`` plane, read
    as A * M rows of :func:`_peer_rows`."""
    e, m, _ = x.shape
    rows = jnp.arange(m, dtype=idx_c.dtype)[:, None] * e + idx_c[None, :]
    return _as_stored(jnp.take(_peer_rows(x), rows, axis=0))


def _set_peer_rows(x: jax.Array, sub: jax.Array,
                   active_idx: jax.Array) -> jax.Array:
    """``x.at[active_idx].set(sub, mode="drop")`` of an ``[E, M, S]``
    plane, written as A * M rows of :func:`_peer_rows`.  Real indices
    are distinct and every pad gets an out-of-range row of its own, so
    the rows are unique as the scatter is promised."""
    e, m, s = x.shape
    a = active_idx.shape[0]
    at = jnp.arange(m, dtype=active_idx.dtype)[:, None]
    rows = jnp.where((active_idx < e)[None, :],
                     at * e + active_idx[None, :],
                     m * e + at * a + jnp.arange(a, dtype=active_idx.dtype))
    out = _peer_rows(x).at[rows.reshape(m * a)].set(
        _as_stored(sub).reshape(m * a, s),
        mode="drop", unique_indices=True)
    return _as_stored(out.reshape(m, e, s))


def _slice_columns(state: EngineState, active_idx: jax.Array,
                   up: jax.Array) -> Tuple[EngineState, jax.Array]:
    """Gather the A active ensembles' rows out of every state plane
    (and the up mask): ``[E, ...] → [A, ...]``.  Padding entries
    (index E, out of range) clip to row E-1 — harmless, their op
    lanes are NOOP/elect-False so they never write, and the scatter
    drops them.  The three object planes are read as rows of their
    stored layout (above): no whole plane moves.  ``tree_leaf`` is
    stored E outermost and gathers where it lies, and so is
    ``tree_rows`` where the state has one (an ensemble's rows are one
    range of the plane); ``tree_node`` is stored E MINOR-most, its
    gather is a lane gather, and the chip still relayouts it (7.2 MB)
    on the way in and out, as it does the small ``[E]`` / ``[E, M]``
    planes."""
    e = state.epoch.shape[0]
    with jax.named_scope("slice_columns"):
        idx_c = jnp.clip(active_idx, 0, e - 1)
        sub = EngineState(*(
            None if x is None
            else _take_peer_rows(x, idx_c) if f in _ROW_VIEWED
            else jnp.take(x, idx_c, axis=0)
            for f, x in zip(state._fields, state)))
        return sub, jnp.take(up, idx_c, axis=0)


def _scatter_columns(state: EngineState, sub: EngineState,
                     active_idx: jax.Array) -> EngineState:
    """Scatter the stepped sub-state back into the full planes.
    Padding entries aim out of bounds (index E) and are DROPPED;
    real indices are distinct, so the scatter is conflict-free.
    With the full state donated, the object planes' and
    ``tree_leaf``'s scatters update the A touched rows of the
    parameter's own buffer (``tests/test_chip_compile.py`` reads that
    off the program compiled for the chip); ``tree_node`` and the
    small planes are scattered on a relayouted copy."""
    with jax.named_scope("scatter_columns"):
        return EngineState(*(
            None if full is None
            else _set_peer_rows(full, s, active_idx) if f in _ROW_VIEWED
            else full.at[active_idx].set(s, mode="drop")
            for f, full, s in zip(state._fields, state, sub)))


def _full_step_sliced_body(state: EngineState, active_idx: jax.Array,
                           elect: jax.Array, cand: jax.Array,
                           kind: jax.Array, slot: jax.Array,
                           val: jax.Array, lease_ok: jax.Array,
                           up: jax.Array,
                           axis_name: Optional[str] = None,
                           exp_epoch: Optional[jax.Array] = None,
                           exp_seq: Optional[jax.Array] = None
                           ) -> Tuple[EngineState, jax.Array, KvResult]:
    """:data:`full_step` on the ACTIVE COLUMNS ONLY — the shrunk
    launch grid.  One hot ensemble forces the [K, E] grid to its
    queue depth even when most columns idle; ensembles are fully
    independent in every K/V and election kernel (the batch-axis
    premise), so the step runs bit-identically on the gathered
    ``[A, ...]`` sub-state with ``[K, A]`` op planes — compute, HBM
    traffic and the result surface all scale with the live working
    set instead of E.

    ``active_idx [A]`` (A pow2-bucketed; padding = E, dropped at
    scatter) selects the columns; ``elect``/``cand`` are ``[A]``,
    the op planes ``[K, A]``, ``up`` stays full ``[E, M]`` (gathered
    on device — it is cached there between failure-detector
    changes).  The caller must include every electing column in the
    active set, and must treat the results as A-width: ``won`` and
    every plane of the result come back ``[A(...)]`` and the host
    scatters them.  All but ONE: ``res.quorum_ok`` comes back as one
    row at FULL width, ``[1, E]`` (:func:`_sliced_quorum`), so the
    host renews every ensemble's lease from a sliced launch as it
    does from a full-width one.

    Semantic note (vs the full-grid step): follower epoch catch-up
    (``_adopt_epochs``) runs only for active columns — an idle
    ensemble's stragglers heal on its NEXT active launch, which is
    exactly when the heal is first observable, and until then they
    count as nacks in its epoch check.  Under ``shard_map`` (the
    mesh engine's sliced programs) every shard runs this body on its
    own LOCAL rows with its own LOCAL indices (pad = the local row
    count): the gather, the scatter and the full-width epoch check
    stay on the chip that holds the rows, and nothing crosses the
    'ens' axis.
    """
    sub, up_a = _slice_columns(state, active_idx, up)
    sub, won, res = _full_step_body(
        sub, elect, cand, kind, slot, val, lease_ok, up_a,
        axis_name=axis_name, exp_epoch=exp_epoch, exp_seq=exp_seq)
    res = res._replace(quorum_ok=_sliced_quorum(
        state, up, active_idx, res.quorum_ok, axis_name))
    return _scatter_columns(state, sub, active_idx), won, res


def _sliced_quorum(state: EngineState, up: jax.Array,
                   active_idx: jax.Array, stepped: jax.Array,
                   axis_name: Optional[str]) -> jax.Array:
    """A sliced launch's quorum plane, one row at full width
    ``[1, E]``: an active column's entry is what the step reported
    for it (``stepped [K, A]``, any round), every other column's is
    the epoch check of the launch's own ``up`` over the state as the
    launch found it: :func:`_kv_context`, the function every round's
    ``quorum_ok`` comes from, so "the leader is up and holds a quorum
    of up members at its epoch in every view" has one definition.
    The launch does not touch a column outside its active set, so
    that is also what a round of a full-width launch at the same
    instant would have reported for it (the leader_tick renewal of an
    idle leader, peer.erl:1092-1095).  Pads of ``active_idx`` are out
    of range and dropped.  ``met_only``: the check is E rows wide, and
    the per-row gather ``quorum_met_batch`` spends on telling NACK from
    UNDECIDED, which no caller of ``epoch_ok`` reads, was 0.09 ms of
    every launch at 10,000 rows."""
    with jax.named_scope("idle_quorum"):
        idle = _kv_context(state, up, axis_name, met_only=True).epoch_ok
        return idle.at[active_idx].set(stepped.any(0), mode="drop")[None]


# ---------------------------------------------------------------------------
# The op slab: one scalar launch's host-built operands as ONE array
#
# THE layout (the only statement of it; the host packs with
# :func:`pack_op_slab`, the step programs take it apart with
# :func:`split_op_slab`).  int32 ``[R, W]``, W the launch's column
# width (the active bucket on a sliced launch, E otherwise):
#
#   row 0            elect       [W]   bool as 0/1
#   row 1            cand        [W]
#   row 2            lease_ok    [W]   bool as 0/1, ONE row: the program
#                                      broadcasts it to [K, W]
#   row 3            active_idx  [W]   launches that gather only: a
#                                      SLICED launch's active columns
#                                      (pad = E), or the first A columns
#                                      of a full-width PACK-GATHER
#                                      launch's (pad = 0; A < W is the
#                                      condition for gathering, so the
#                                      row always fits)
#   then K rows each kind, slot, val, exp_epoch, exp_seq   ([K, W] each)
#
# R = head + 5 K with head 3 (4 with an index row), so the shape alone
# carries K and W and a (K, A) bucket is one program.  Under ``shard_map`` the
# slab is sharded ``P(None, 'ens')`` like the op planes it replaces: a
# row of the local block is that shard's ``P('ens')`` vector.  A SLICED
# mesh slab is ``n_shards`` blocks of one width ``a_loc`` side by side
# (W = n_shards * a_loc), and each shard's local block is an ordinary
# sliced slab of its own: its columns gathered to the block's front,
# its index row LOCAL (pad = the rows a shard holds, E / n_shards).  A
# full-width mesh slab's index row is LOCAL too: the first A columns of
# each shard's block hold that shard's own pack-gather indices.

SLAB_ELECT, SLAB_CAND, SLAB_LEASE, SLAB_ACTIVE_IDX = 0, 1, 2, 3
#: the per-round planes, K rows each, in slab order
SLAB_PLANES = ("kind", "slot", "val", "exp_epoch", "exp_seq")


def _slab_head(indexed: bool) -> int:
    return SLAB_ACTIVE_IDX + 1 if indexed else SLAB_ACTIVE_IDX


def pack_op_slab(width: int, k: int, elect, cand, lease_ok, planes,
                 active=None, active_idx=None, at=None) -> np.ndarray:
    """Host half of the op slab: a FRESH ``[R, width]`` int32 array
    (an upload may still be reading the previous launch's).

    ``planes`` are the five ``[K, E]`` host planes in
    :data:`SLAB_PLANES` order (None = all zero, the absent CAS
    versions).  Full width (``active`` None): ``width`` is E and
    everything is copied whole; ``active_idx`` (``[width]``), where
    given, is the pack-gather's index row.  Sliced (``active`` names
    the real columns, ``active_idx`` is ``[width]`` with pad = E):
    the real columns are gathered to the slab's first ``len(active)``
    columns; padding columns stay NOOP/zero.  A mesh's sliced slab (the
    per-shard blocks above) names with ``at`` the slab column each of
    ``active`` goes to: the front of its shard's block."""
    sliced = active is not None
    head = _slab_head(active_idx is not None)
    slab = np.zeros((head + len(SLAB_PLANES) * k, width), np.int32)
    if at is None:
        at = slice(len(active) if sliced else width)

    def cols(x):
        x = np.asarray(x)
        return x[..., active] if sliced else x

    slab[SLAB_ELECT, at] = cols(elect)
    slab[SLAB_CAND, at] = cols(cand)
    slab[SLAB_LEASE, at] = cols(lease_ok)
    if active_idx is not None:
        slab[SLAB_ACTIVE_IDX] = active_idx
    if k:
        body = slab[head:].reshape(len(SLAB_PLANES), k, width)
        for i, p in enumerate(planes):
            if p is not None:
                body[i][:, at] = cols(p)
    return slab


def split_op_slab(slab: jax.Array, indexed: bool = False):
    """Program half: ``(elect, cand, lease_ok[K, W], kind, slot, val,
    exp_epoch, exp_seq)`` — the step bodies' operands in their call
    order — at static offsets of the slab (``indexed``: the slab has
    an index row, which the caller reads)."""
    head = _slab_head(indexed)
    n_p = len(SLAB_PLANES)
    w = slab.shape[1]
    k = (slab.shape[0] - head) // n_p
    body = slab[head:].reshape(n_p, k, w)
    lease = jnp.broadcast_to(slab[SLAB_LEASE] != 0, (k, w))
    return (slab[SLAB_ELECT] != 0, slab[SLAB_CAND], lease,
            *(body[i] for i in range(n_p)))


def _full_step_slab_body(state: EngineState, slab: jax.Array,
                         up: jax.Array,
                         axis_name: Optional[str] = None,
                         indexed: bool = False
                         ) -> Tuple[EngineState, jax.Array, KvResult]:
    """:func:`_full_step_body` fed by one op slab (layout above;
    ``indexed``: it carries a pack-gather's index row)."""
    elect, cand, lease, kind, slot, val, xe, xs = split_op_slab(
        slab, indexed)
    return _full_step_body(state, elect, cand, kind, slot, val, lease,
                           up, axis_name=axis_name, exp_epoch=xe,
                           exp_seq=xs)


def _full_step_sliced_slab_body(state: EngineState, slab: jax.Array,
                                up: jax.Array,
                                axis_name: Optional[str] = None
                                ) -> Tuple[EngineState, jax.Array,
                                           KvResult]:
    """:func:`_full_step_sliced_body` fed by one A-width op slab whose
    row :data:`SLAB_ACTIVE_IDX` is the active-column index vector."""
    elect, cand, lease, kind, slot, val, xe, xs = split_op_slab(
        slab, indexed=True)
    return _full_step_sliced_body(
        state, slab[SLAB_ACTIVE_IDX], elect, cand, kind, slot, val,
        lease, up, axis_name=axis_name, exp_epoch=xe, exp_seq=xs)


# ---------------------------------------------------------------------------
# The result pack: what a launch hands back to the host, ONE vector


def pack_results(won: jax.Array, res: KvResult, want_vsn: bool,
                 active_idx: Optional[jax.Array] = None) -> jax.Array:
    """Flatten a launch's results into ONE uint8 vector on device.

    The host needs ~7 result arrays per launch; fetching them
    separately costs a device round trip each.  And the d2h payload
    is paid on every launch, so the six boolean planes travel
    BIT-PACKED (32x smaller than int32) and only the genuinely
    integer planes ride at full
    width, bitcast into the same buffer: one fused pack, one
    transfer, ~3.6x less data than the all-int32 layout.

    ACTIVE-COLUMN COMPACTION: ``active_idx [A]`` (A pow2-bucketed,
    padding repeats index 0) gathers the per-round client planes down
    to the columns the flush actually scheduled ops into
    (:func:`gather_result_columns`), so the payload scales
    ``O(K·A)`` instead of ``O(K·E)`` — decoupled from the launch
    grid.  The election/lease/corruption planes stay full width: the
    host's lease renewal and scrub path see every column, active or
    not.  ``None`` keeps the full-width layout.

    Layout: packbits([won H | quorum_ok E | corrupt H*M |
    committed K*A | get_ok K*A | found K*A]) ++ bitcast_u8(
    [value K*A | (vsn_epoch K*A | vsn_seq K*A)])  (A = E when
    uncompacted; H = E, but A where the STEP was sliced: its ``won``
    and ``tree_corrupt`` arrive A wide and are packed as they are,
    and its ``quorum_ok`` is the one full-width row of
    :func:`_sliced_quorum`, so the quorum plane is E wide in every
    layout).  ``batched_host.packed_nbytes`` /
    ``unpack_results`` / ``unpack_results_sharded`` and
    ``native/resolvekernel.cc`` are the host's half of it.
    """
    with jax.named_scope("result_pack"):
        if active_idx is not None:
            res = gather_result_columns(res, active_idx)
        flags = jnp.concatenate([
            won.ravel(),
            res.quorum_ok.any(0).ravel(),
            res.tree_corrupt.any(0).ravel(),
            res.committed.ravel(),
            res.get_ok.ravel(),
            res.found.ravel(),
        ]).astype(bool)
        ints = [res.value.ravel()]
        if want_vsn:
            ints += [res.obj_vsn[..., 0].ravel(), res.obj_vsn[..., 1].ravel()]
        ints_u8 = jax.lax.bitcast_convert_type(
            jnp.concatenate(ints), jnp.uint8).ravel()
        return jnp.concatenate([jnp.packbits(flags), ints_u8])


def pack_gather_index(slab: jax.Array, gather: int
                      ) -> Optional[jax.Array]:
    """The pack-gather's index vector of a full-width slab (or of a
    shard's block of one): the first ``gather`` columns of row
    :data:`SLAB_ACTIVE_IDX`; ``gather`` 0 = the launch packs at full
    width and its slab has no index row."""
    return slab[SLAB_ACTIVE_IDX, :gather] if gather else None


# ---------------------------------------------------------------------------
# The served programs: ONE program a launch, (state, slab, up) -> (state, flat)


def _slab_step_body(state: EngineState, slab: jax.Array, up: jax.Array,
                    sliced: bool = False, gather: int = 0,
                    axis_name: Optional[str] = None
                    ) -> Tuple[EngineState, jax.Array, KvResult]:
    """The step body a launch runs over its slab: the sliced one, or
    the full-width one (its slab carries an index row where the pack
    gathers, ``gather`` > 0)."""
    if sliced:
        return _full_step_sliced_slab_body(state, slab, up,
                                           axis_name=axis_name)
    return _full_step_slab_body(state, slab, up, axis_name=axis_name,
                                indexed=bool(gather))


def _launch_body(state: EngineState, slab: jax.Array, up: jax.Array,
                 want_vsn: bool, gather: int = 0, sliced: bool = False,
                 axis_name: Optional[str] = None
                 ) -> Tuple[EngineState, jax.Array]:
    """What a launch asks of the device: :func:`_slab_step_body`, then
    :func:`pack_results` of what it returned: a sliced launch's
    A-width results (and its full-width quorum row) as they are, a
    full-width launch's gathered down to the ``gather`` columns the
    slab's index row names (0 = packed at full width)."""
    state, won, res = _slab_step_body(state, slab, up, sliced, gather,
                                      axis_name)
    return state, pack_results(won, res, want_vsn,
                               pack_gather_index(slab, gather))


#: the served programs, plain and donated (see :data:`full_step_donate`
#: for the aliasing contract; the sliced step's scatter back into the
#: donated object planes and ``tree_leaf`` is an in-place A-row update
#: on the chip, ``tree_node`` and the small planes pass through a
#: relayouted copy: "The sliced step's two edges" above).  ``want_vsn``
#: and the pack-gather's width are static, so a (K, A) bucket is ONE
#: program: the step and the pack of its results.
_FULL_STATIC = ("want_vsn", "gather", "axis_name")
full_step_slab = jax.jit(_launch_body, static_argnames=_FULL_STATIC)
full_step_slab_donate = jax.jit(_launch_body,
                                static_argnames=_FULL_STATIC,
                                donate_argnums=(0,))
_launch_sliced_body = functools.partial(_launch_body, sliced=True)
_SLICED_STATIC = ("want_vsn", "axis_name")
full_step_sliced_slab = jax.jit(_launch_sliced_body,
                                static_argnames=_SLICED_STATIC)
full_step_sliced_slab_donate = jax.jit(_launch_sliced_body,
                                       static_argnames=_SLICED_STATIC,
                                       donate_argnums=(0,))
