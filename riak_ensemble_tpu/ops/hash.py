"""synctree_jax: the Merkle hash trie as a batched TPU kernel.

The host :class:`~riak_ensemble_tpu.synctree.tree.SyncTree` mirrors the
reference's per-peer trie (md5 buckets, width 16, 1M segments —
synctree.erl:88-89,251-259) for protocol-faithful per-op updates.  This
module is the scale path (BASELINE.md ladder #4, "1M-key Merkle
exchange"): the whole trie as a structure-of-arrays program —

- ``levels[k]``: ``[width**k, LANES]`` uint32 hash lanes, level 0 the
  root (1 bucket), the last level the segment/leaf hashes,
- :func:`build` — one fused bottom-up rebuild (``rehash``'s role,
  synctree.erl:489-535) as per-level fold-reductions that XLA
  vectorizes across every bucket at once,
- :func:`update` — incremental batched insert: scatter new leaf hashes
  and recompute only the touched root-ward paths (the always-up-to-date
  write-path property, synctree.erl:44-73 — NOT a lazy full rebuild),
- :func:`diff_levels` / :func:`exchange_cost` — the level-by-level
  exchange descent (synctree.erl:372-417): per-level differing-bucket
  masks, giving the O(width · height · diffs) traffic bound that the
  streaming exchange ships over the network,
- :func:`verify` — full integrity check: recompute every parent from
  its children and flag mismatched buckets ({corrupted, Level, Bucket}
  detection, synctree.erl:322-340, as a bitmap).

Hash lanes are a murmur3-style mix — not md5: inside jit the hash only
needs uniformity + avalanche (corruption/diff detection), and a 4-lane
128-bit mix keeps the MXU-adjacent VPU busy instead of forcing a
byte-serial digest.  The host tree keeps cryptographic md5 where the
reference does.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: 4 x uint32 lanes = 128-bit hashes per bucket.
LANES = 4

#: Device-tree hash-format version.  Bump whenever :func:`fold` (or the
#: leaf-hash family) changes output values: checkpoints persist
#: ``tree_leaf``/``tree_node`` verbatim, and a restore across a format
#: change must rebuild every tree or `_verify_path` fails on every slot
#: (see docs/MIGRATION.md).  History: 1 = chained per-child accumulator
#: (rounds 1-3), 2 = linear-pre-mix parallel fold (round 4),
#: 3 = salted non-linear parallel fold (round 5).
HASH_FORMAT = 3

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_F1 = np.uint32(0x85EBCA6B)
_F2 = np.uint32(0xC2B2AE35)


def _rotl(x, r: int):
    return (x << r) | (x >> (32 - r))


def _fmix(h):
    """murmur3 finalizer: full avalanche per lane."""
    h = h ^ (h >> 16)
    h = h * _F1
    h = h ^ (h >> 13)
    h = h * _F2
    return h ^ (h >> 16)


def fold(children: jnp.ndarray) -> jnp.ndarray:
    """Combine ``[..., width, LANES]`` child hashes into ``[..., LANES]``
    parent hashes (the md5-over-concatenated-children role,
    synctree.erl hash/1:255-259).

    Parallel-mix form: each child is avalanched independently with a
    position salt (order sensitivity without order DEPENDENCE), the
    mixes sum mod 2^32, and one cross-lane stir + final avalanche seal
    the parent.  The original chained form (murmur-style sequential
    accumulator with a per-child lane roll) serialized the width axis
    and shuffled lanes 16x per fold — XLA could not vectorize it, and
    the fold dominated the whole K/V round (~3 ms per level at the
    512-ens CPU rung vs ~0.3 ms for this form).  Corruption/diff
    detection needs uniformity + avalanche, not a sequential
    construction — per-child ``_fmix`` provides both.

    The per-child pre-mix is deliberately NON-linear in (child, pos):
    the child is xor'd with an avalanched position salt and then
    multiplied by a per-position odd constant before the ``_fmix``.  A
    linear pre-mix (``child*C1 + pos*C2``) admits a deterministic
    compensated-swap collision — replacing children ``(a, b)`` at
    positions (0, 1) with ``(b+d, a-d)``, ``d = C2·C1⁻¹ mod 2³²``,
    preserves the pre-mix multiset and thus the sum (hash format 2's
    structured blind spot; regression: test_hash_kernel.py
    compensated-swap tests).  With distinct odd multipliers per
    position, neither additive nor xor shifts compensate a swap.
    Threat model matches the reference's: this is a public integrity
    hash for corruption/divergence *detection* (the reference's obj
    "hash" is the plaintext ``<<0,Epoch:64,Seq:64>>``,
    peer.erl:1717-1724) — adversarial forgery resistance is out of
    scope on the device path; the host tree keeps cryptographic md5.
    """
    width = children.shape[-2]
    salt, mul = _fold_consts(width)
    acc = _premix(children, salt, mul).sum(axis=-2, dtype=jnp.uint32)
    return _seal(acc, width)


def _fold_consts(width: int) -> Tuple[np.ndarray, np.ndarray]:
    """Trace-time numpy constants of :func:`fold`: ``[width, 1]``
    position salts and odd per-position multipliers."""
    pos = np.arange(width, dtype=np.uint32)
    salt = _fmix(pos * _C2 + np.uint32(0x9E3779B9))[:, None]
    mul = (_fmix(pos * _F1 + _C1) | np.uint32(1))[:, None]
    return salt, mul


def _premix(children, salt, mul, nodes_last: bool = False):
    """:func:`fold`'s per-child avalanche: ``[..., n, LANES]`` children
    with their ``[n, 1]`` salts and multipliers, mixed independently
    (``nodes_last``: ``[..., LANES, n]`` children, ``[1, n]`` salts)."""
    lane = jnp.arange(LANES, dtype=jnp.uint32)
    if nodes_last:
        lane = lane[:, None]
    return _fmix((children ^ salt) * mul + lane)


def _seal(acc, width: int):
    """:func:`fold`'s tail over the summed mixes ``[..., LANES]``."""
    # two cross-lane stirs: after roll(1)+fmix then roll(2), lane j
    # reads lanes {j, j-1, j-2, j-3} — a change in ANY input lane
    # avalanches every output lane (test_fold_avalanche pins ~50%)
    acc = _fmix(acc ^ jnp.roll(acc, 1, axis=-1))
    acc = acc ^ jnp.roll(acc, 2, axis=-1)
    return _fmix(acc ^ np.uint32(width))


def fold_block(level: jnp.ndarray, block: jnp.ndarray,
               width: int = 16, nodes_last: bool = False) -> jnp.ndarray:
    """:func:`fold` of ONE ``width``-block of a level, read where the
    level lies: ``level [..., n, LANES]``, ``block [...]`` (broadcast
    against the leading axes) → ``[..., LANES]``, bit-equal to
    ``fold(pad(level)[..., block * width:(block + 1) * width, :])``
    with the short last block zero-padded.  ``nodes_last``: the level
    comes ``[..., LANES, n]``, its nodes on the minor axis (a gathered
    row of ``ops/engine.py``'s row plane: 128 nodes on the chip's 128
    lanes).

    The fold is a position-salted mix SUMMED over the width, so the sum
    over the whole level of the mixes masked to the block IS the
    block's fold: one elementwise pass and a reduce along n, no gather.
    That is what lets a caller leave a lane-dense ``[..., n, LANES]``
    plane in the layout the chip stores it in (``ops/engine.py``,
    "Merkle paths").
    """
    n = level.shape[-1 if nodes_last else -2]
    pos = np.arange(n)
    salt, mul = _fold_consts(width)
    at_salt, at_mul = salt[pos % width], mul[pos % width]
    # (mixes first, mask second: the order the step programs of the
    # shapes without a row plane were traced in, kept to the letter)
    if nodes_last:
        at_salt, at_mul = at_salt.T, at_mul.T
    h = _premix(level, at_salt, at_mul, nodes_last)
    in_block = (jnp.asarray(pos // width, jnp.int32)[:, None]
                == block[..., None, None])
    if nodes_last:
        in_block = jnp.swapaxes(in_block, -1, -2)
    acc = jnp.where(in_block, h, np.uint32(0)).sum(
        axis=-1 if nodes_last else -2, dtype=jnp.uint32)
    short = n % width
    if short:
        # what the zero children that pad the last block mix to
        pad = _premix(jnp.zeros((width - short, LANES), jnp.uint32),
                      salt[short:], mul[short:]).sum(0, dtype=jnp.uint32)
        acc = acc + jnp.where((block == n // width)[..., None], pad,
                              np.uint32(0))
    return _seal(acc, width)


def leaf_hash(epoch: jnp.ndarray, seq: jnp.ndarray) -> jnp.ndarray:
    """Object-version leaf hashes: the reference's obj 'hash' IS the
    (epoch, seq) version (``get_obj_hash`` = ``<<0, Epoch:64, Seq:64>>``,
    peer.erl:1717-1724); mix them into the lane format.  Shapes
    broadcast; returns ``[..., LANES]``."""
    e = jnp.asarray(epoch, jnp.uint32)
    s = jnp.asarray(seq, jnp.uint32)
    base = jnp.stack([e, s, e ^ _rotl(s, 7), s ^ _rotl(e, 11)], axis=-1)
    return _fmix(base * _C1 + jnp.arange(LANES, dtype=jnp.uint32))


def obj_leaf_hash(epoch: jnp.ndarray, seq: jnp.ndarray,
                  val: jnp.ndarray) -> jnp.ndarray:
    """Object leaf hash covering version AND payload handle.

    The reference's obj hash is version-only (``<<0, Epoch:64, Seq:64>>``,
    peer.erl:1717-1724; payload corruption is the backend CRC's job).
    The device store holds the payload handle right next to the version,
    so covering it is free and strictly stronger: a replica whose
    ``obj_val`` lane was damaged out-of-band fails the tree check too.
    Shapes broadcast; returns ``[..., LANES]`` uint32.
    """
    e = jnp.asarray(epoch, jnp.uint32)
    s = jnp.asarray(seq, jnp.uint32)
    v = jnp.asarray(val, jnp.uint32)
    base = jnp.stack([e ^ _rotl(v, 5), s ^ _rotl(v, 9),
                      e ^ _rotl(s, 7), s ^ _rotl(e, 11)], axis=-1)
    return _fmix(base * _C1 + jnp.arange(LANES, dtype=jnp.uint32))


Levels = Tuple[jnp.ndarray, ...]


@functools.partial(jax.jit, static_argnames=("width",))
def build(leaves: jnp.ndarray, width: int = 16) -> Levels:
    """Bottom-up rebuild: ``leaves [S, LANES]`` → levels root-first
    (root ``[1, LANES]`` ... leaves ``[S, LANES]``)."""
    levels = [leaves]
    cur = leaves
    while cur.shape[0] > 1:
        cur = fold(cur.reshape(-1, width, LANES))
        levels.append(cur)
    return tuple(reversed(levels))


@functools.partial(jax.jit, static_argnames=("width",))
def update(levels: Levels, seg_ids: jnp.ndarray,
           new_leaves: jnp.ndarray, width: int = 16) -> Levels:
    """Incremental batched insert (the write-path hash update,
    peer.erl:1731-1738, batched across K keys).

    ``seg_ids [K]`` / ``new_leaves [K, LANES]``: scatter the leaf
    hashes, then per level recompute only the K touched parents by
    gathering their ``width`` children — O(K · width · height) work
    regardless of tree size.  Duplicate parents recompute identically,
    so the parent scatter is idempotent.

    Duplicate ``seg_ids`` in one batch are LAST-WRITE-WINS (the batch
    is a sequence of inserts): JAX leaves duplicate-index scatter order
    unspecified, so every duplicate is redirected to the value of its
    final occurrence before scattering.  A scatter-max over the segment
    axis finds that occurrence in O(K + S) (max is order-independent,
    so it is deterministic under duplicate indices, unlike set).
    """
    out = list(levels)
    depth = len(levels) - 1  # leaf level index
    k = seg_ids.shape[0]
    last_occ_by_seg = jnp.zeros(out[depth].shape[0], jnp.int32) \
        .at[seg_ids].max(jnp.arange(k, dtype=jnp.int32))
    out[depth] = out[depth].at[seg_ids].set(
        new_leaves[last_occ_by_seg[seg_ids]])
    ids = seg_ids
    for level in range(depth - 1, -1, -1):
        parent_ids = ids // width
        child_base = parent_ids * width
        # [K, width] child indices → gather [K, width, LANES]
        gather_ids = child_base[:, None] + jnp.arange(width)[None, :]
        children = out[level + 1][gather_ids]
        out[level] = out[level].at[parent_ids].set(fold(children))
        ids = parent_ids
    return tuple(out)


@jax.jit
def diff_levels(a: Levels, b: Levels) -> Tuple[jnp.ndarray, ...]:
    """Per-level differing-bucket masks between two trees — the
    device-side form of the exchange descent (synctree.erl:386-417).
    Mask k is True where bucket hashes differ at level k; the leaf
    mask marks exactly the segments whose keys need repair."""
    return tuple(jnp.any(x != y, axis=-1) for x, y in zip(a, b))


@functools.partial(jax.jit, static_argnames=("width",))
def exchange_cost(a: Levels, b: Levels, width: int = 16) -> jnp.ndarray:
    """Buckets that a streaming exchange would actually fetch: at each
    level only children of differing parents are visited
    (O(width·height·diffs), the remote-exchange traffic bound
    exercised by synctree_remote.erl).  Returns ``[height+1]`` visit
    counts root-ward → leaf-ward."""
    masks = diff_levels(a, b)
    counts = [jnp.asarray(1, jnp.int32)]  # root always compared
    visit = masks[0]  # [1]
    for level in range(1, len(masks)):
        # children of differing parents are visited...
        visited_children = jnp.repeat(visit, width)
        counts.append(jnp.sum(visited_children.astype(jnp.int32)))
        # ...and among those, the differing ones descend further
        visit = visited_children & masks[level]
    return jnp.stack(counts)


@functools.partial(jax.jit, static_argnames=("width",))
def verify(levels: Levels, width: int = 16) -> Tuple[jnp.ndarray, ...]:
    """Integrity sweep: recompute each parent level from its children
    and flag mismatches — per-level corruption bitmaps (the BFS verify,
    synctree.erl:549-571, as one fused pass)."""
    out = []
    for level in range(len(levels) - 1):
        expect = fold(levels[level + 1].reshape(-1, width, LANES))
        out.append(jnp.any(expect != levels[level], axis=-1))
    return tuple(out)


def segment_of(key_hash: jnp.ndarray, segments: int) -> jnp.ndarray:
    """Key → segment (the md5-mod mapping, synctree.erl:251-253) for
    uint32 key hashes computed host-side."""
    return jnp.asarray(key_hash, jnp.uint32) % np.uint32(segments)
