"""Quorum vote reduction — the kernel of the communication layer.

Reference semantics: ``riak_ensemble_msg:quorum_met/5``
(``src/riak_ensemble_msg.erl:377-418``):

- ``views`` is a list of member lists (joint consensus); quorum must be
  met in EVERY view, checked in order.
- Per view: ``thresh = len(members)//2 + 1`` (or ``len(members)`` for
  ``required='all'``); the caller counts as one implicit valid reply
  when it is a member, except in ``'other'`` mode (used by the
  untrusted-tree exchange, which must hear a majority *excluding*
  itself).
- A view with ``nacks >= thresh``, or where everyone was heard from yet
  quorum wasn't reached, fails the whole call with ``NACK``.  A view
  that might still succeed returns ``UNDECIDED`` (keep collecting) —
  and, exactly like the reference's recursion, later views are NOT
  examined for nacks in that case.

Two implementations with identical semantics:

- :func:`quorum_met` — host scalar version on Python sets, used by the
  per-peer FSM in the host runtime (and as the differential-test
  oracle).
- :func:`quorum_met_batch` — jit/vmap-able array version over an
  ``[E]`` ensemble batch with an ``[M]`` peer axis and ``[V, M]`` view
  membership masks.  This is the majority-reduce that rides ICI
  (``psum`` over the peer mesh axis) in the sharded engine.

The two agree exactly for ``extra=None`` (differentially tested).  The
``extra`` predicate (read-path hash-validity check) exists only on the
scalar path by design: the batched engine's read kernel expresses the
same check directly as array ops over its reply buffers
(an arbitrary Python callable can't cross into jit).
"""

from __future__ import annotations

import functools
from typing import Callable, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Result codes (shared by scalar and batched versions).
MET = 1
UNDECIDED = 0
NACK = -1

#: required() modes (msg.erl:43).
REQUIRED_MODES = ("quorum", "all", "all_or_quorum", "other")


def quorum_met(replies: Iterable[Tuple[object, object]],
               self_id: object,
               views: Sequence[Sequence[object]],
               required: str = "quorum",
               extra: "Optional[Callable[[list], bool]]" = None) -> int:
    """Scalar quorum predicate.

    ``replies`` is an iterable of ``(peer_id, reply)`` where a reply of
    the string ``'nack'`` is a negative vote.  Returns MET / UNDECIDED /
    NACK.  ``extra`` is an optional extra predicate on the replies,
    evaluated only once every view has met (the recursion base case,
    msg.erl:382-388) — used by the read path's hash-validity check.
    """
    assert required in REQUIRED_MODES, required
    replies = list(replies)
    for members in views:
        members = list(members)
        filtered = [(p, r) for (p, r) in replies if p in members]
        valid = [p for (p, r) in filtered if r != "nack"]
        nacks = [p for (p, r) in filtered if r == "nack"]
        if required == "all":
            thresh = len(members)
        else:
            thresh = len(members) // 2 + 1
        heard = len(valid)
        if required != "other" and self_id in members:
            heard += 1
        if heard >= thresh:
            continue
        if len(nacks) >= thresh:
            return NACK
        if heard + len(nacks) == len(members):
            return NACK
        return UNDECIDED
    if extra is not None and not extra(replies):
        return UNDECIDED
    return MET


def find_valid(replies):
    """Partition replies into (valid, nacks) (msg.erl:420-426)."""
    valid = [(p, r) for (p, r) in replies if r != "nack"]
    nacks = [(p, r) for (p, r) in replies if r == "nack"]
    return valid, nacks


# ---------------------------------------------------------------------------
# Batched array version


def reduce_peers(x: jax.Array, axis_name) -> jax.Array:
    """Sum over the trailing (local) peer axis, then over the mesh
    'peer' axis when sharded — the vote-count all-reduce.  Shared by
    every peer-axis reduction in the batched engine."""
    s = x.sum(-1)
    if axis_name is not None:
        s = jax.lax.psum(s, axis_name)
    return s


@functools.partial(jax.jit,
                   static_argnames=("required", "axis_name", "met_only"))
def quorum_met_batch(valid: jax.Array,
                     nack: jax.Array,
                     view_mask: jax.Array,
                     self_idx: jax.Array,
                     required: str = "quorum",
                     axis_name: Optional[str] = None,
                     met_only: bool = False) -> jax.Array:
    """Batched quorum predicate.

    Args:
      valid:      bool ``[..., M]`` — peer m replied positively.
      nack:       bool ``[..., M]`` — peer m replied nack.  (A peer is
                  at most one of valid/nack; unheard peers are neither.)
      view_mask:  bool ``[..., V, M]`` — membership of peer m in view v.
                  All-zero rows are ignored (views list shorter than V).
      self_idx:   int  ``[...]`` — caller's index on the peer axis, or
                  -1 when the caller is not on this peer axis.
      required:   one of REQUIRED_MODES (static).
      axis_name:  mesh axis name when the peer axis M is sharded under
                  ``shard_map`` — vote counts become ``psum`` ICI
                  all-reduces (this is how the sharded engine calls
                  it).  Sharded callers must pass ``self_idx=-1`` and
                  fold their own vote into ``valid`` (a global index
                  cannot be matched against a local peer slice).
      met_only:   the caller reads nothing but ``== MET`` (static): a
                  NACK is then reported as UNDECIDED, and the gather
                  that tells the two apart (one element a row: 0.09 ms
                  of a launch at 10,000 rows on a v5e, PERF.md
                  section 6, PR 47) is not traced.

    Returns int8 ``[...]`` of MET / UNDECIDED / NACK.
    """
    assert required in REQUIRED_MODES, required
    vm = view_mask.astype(jnp.int32)                      # [..., V, M]
    members = reduce_peers(vm, axis_name)                 # [..., V]
    active = members > 0                                  # [..., V]
    n_valid = reduce_peers(vm * valid[..., None, :].astype(jnp.int32),
                           axis_name)
    n_nack = reduce_peers(vm * nack[..., None, :].astype(jnp.int32),
                          axis_name)

    if required == "all":
        thresh = members
    else:
        thresh = members // 2 + 1

    if axis_name is not None:
        # Sharded contract enforced at the source: a global self_idx
        # cannot be matched against a local peer slice, so the self
        # term is hard-zeroed (callers fold self into `valid`); this
        # also saves an all-reduce on the hot ICI path.
        self_in_view = jnp.zeros_like(members)
    else:
        m = view_mask.shape[-1]
        self_oh = jax.nn.one_hot(self_idx, m, dtype=jnp.int32)  # [..., M]
        self_in_view = (vm * self_oh[..., None, :]).sum(-1)     # [..., V]
    if required != "other":
        heard = n_valid + self_in_view
    else:
        heard = n_valid

    met_v = heard >= thresh                               # [..., V]
    nack_v = (n_nack >= thresh) | ((heard + n_nack) == members)
    # Inactive (padding) views count as met and never nack.
    met_v = met_v | ~active
    nack_v = nack_v & active

    all_met = met_v.all(-1)
    if met_only:
        return jnp.where(all_met, MET, UNDECIDED).astype(jnp.int8)
    # First unmet view, in order — matches the reference's recursion,
    # which only reports NACK if every earlier view already met.
    first_unmet = jnp.argmin(met_v.astype(jnp.int32), axis=-1)  # [...]
    unmet_nacked = jnp.take_along_axis(
        nack_v.astype(jnp.int8), first_unmet[..., None], axis=-1
    )[..., 0]
    out = jnp.where(all_met, MET,
                    jnp.where(unmet_nacked > 0, NACK, UNDECIDED))
    return out.astype(jnp.int8)


def views_to_mask(views: Sequence[Sequence[int]], n_views: int,
                  n_peers: int) -> np.ndarray:
    """Encode a list of views (of peer indices) as a [V, M] bool mask."""
    mask = np.zeros((n_views, n_peers), dtype=bool)
    for i, view in enumerate(views):
        for p in view:
            mask[i, p] = True
    return mask
