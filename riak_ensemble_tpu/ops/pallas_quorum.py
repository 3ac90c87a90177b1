"""Pallas TPU kernel for the quorum vote reduction.

The batched predicate (:func:`riak_ensemble_tpu.ops.quorum.
quorum_met_batch`) is a chain of small reductions over the peer axis.
This kernel re-casts it MXU-first: per-view vote counting IS a matmul —

    heard[E, V] = votes[E, M] @ view_membership[M, V]

so the systolic array counts votes for every (ensemble, view) pair in
one pass, with the threshold/nack logic fused behind it on the VPU.
Axes are padded to the 128-lane tile (M and V are small — 3..8 — in
practice, so one [E_blk, 128] @ [128, 128] tile per grid step), the
ensemble axis is the grid.

Semantics match ``quorum_met_batch`` exactly (differentially tested in
``tests/test_pallas_quorum.py``): joint-view AND, in-order first-unmet
nack detection, inactive-view padding, 'all'/'quorum'/'other' modes,
and the implicit self vote (folded in as a +1 on the votes matrix
before the matmul, which is literally what ``heard = n_valid +
self_in_view`` computes).

The kernel compiles for the TPU (Mosaic) unless the caller asks for
the interpreter: direct calls pass ``interpret=True``, and the engine
gate (``ops/engine.py:_quorum_met``) passes the module-level
:data:`INTERPRET`, which only tests set.  Nothing here looks at the
backend, so a served path can never end up on the interpreter by
itself; the jnp reference implementation remains the portable path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from riak_ensemble_tpu.ops.quorum import MET, NACK, REQUIRED_MODES, UNDECIDED

LANE = 128

#: ``interpret=`` the engine gate hands both kernels.  False in every
#: deployment; tests/test_pallas_quorum.py flips it in a fixture so
#: the CPU suite can run the gated engine paths.
INTERPRET = False


def _resolve(heard, n_nack, members, thresh, is_active, out_ref):
    """Shared kernel tail: threshold + joint-view AND + in-order
    first-unmet nack — the subtle half of the quorum semantics
    (msg.erl:377-418's recursion), written once for both the shared-
    and per-ensemble-mask front ends."""
    met_v = (heard >= thresh) | ~is_active                  # [BE, Vp]
    nack_v = ((n_nack >= thresh) | (heard + n_nack == members)) \
        & is_active

    all_met = jnp.min(met_v.astype(jnp.int32), axis=1)      # [BE]
    # First unmet view in order (the reference recursion examines
    # views left to right): min index where met_v is False.
    vp = met_v.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, met_v.shape, 1)
    first_unmet = jnp.min(jnp.where(met_v, vp, iota), axis=1)
    unmet_nacked = jnp.max(
        jnp.where((iota == first_unmet[:, None]) & nack_v, 1, 0),
        axis=1)

    res = jnp.where(all_met > 0, MET,
                    jnp.where(unmet_nacked > 0, NACK, UNDECIDED))
    out_ref[:] = jnp.broadcast_to(res[:, None].astype(jnp.int32),
                                  out_ref.shape)


def _kernel(votes_ref, nacks_ref, vmt_ref, members_ref, thresh_ref,
            active_ref, out_ref):
    votes = votes_ref[:]          # [BE, Mp] f32 (valid + self term)
    nacks = nacks_ref[:]          # [BE, Mp] f32
    vmt = vmt_ref[:]              # [Mp, Vp] f32 view membership
    members = members_ref[:]      # [1, Vp]
    thresh = thresh_ref[:]        # [1, Vp]
    active = active_ref[:]        # [1, Vp] (1.0 = real view)

    # MXU: per-view vote counts for the whole ensemble block at once.
    heard = jnp.dot(votes, vmt, preferred_element_type=jnp.float32)
    n_nack = jnp.dot(nacks, vmt, preferred_element_type=jnp.float32)
    _resolve(heard, n_nack, members, thresh, active > 0.0, out_ref)


@functools.partial(jax.jit,
                   static_argnames=("required", "block_e", "interpret"))
def quorum_met_pallas(valid: jax.Array, nack: jax.Array,
                      view_mask: jax.Array, self_idx: jax.Array,
                      required: str = "quorum", block_e: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Drop-in for ``quorum_met_batch(..., axis_name=None)`` on a 2-D
    ``[E, M]`` batch with shared or per-ensemble ``view_mask``
    (``[V, M]`` or ``[E, V, M]`` — the latter reduces to the shared
    case only if identical, so per-ensemble masks take the jnp path;
    the engine's steady state is one shared mask).

    Returns int8 ``[E]`` of MET / UNDECIDED / NACK.
    """
    assert required in REQUIRED_MODES, required
    e, m = valid.shape
    assert view_mask.ndim == 2, "pallas path takes a shared [V, M] mask"
    v = view_mask.shape[0]
    assert m <= LANE and v <= LANE, "peer/view axes exceed one tile"

    vm = view_mask.astype(jnp.float32)                    # [V, M]
    members = vm.sum(-1)                                  # [V]
    active = (members > 0).astype(jnp.float32)
    if required == "all":
        thresh = members
    else:
        thresh = jnp.floor(members / 2) + 1

    votes = valid.astype(jnp.float32)
    if required != "other":
        self_oh = jax.nn.one_hot(self_idx, m, dtype=jnp.float32)
        votes = votes + jnp.broadcast_to(self_oh, votes.shape)

    # Pad to tiles.
    ep = -(-e // block_e) * block_e
    votes = jnp.pad(votes, ((0, ep - e), (0, LANE - m)))
    nacks = jnp.pad(nack.astype(jnp.float32),
                    ((0, ep - e), (0, LANE - m)))
    vmt = jnp.pad(vm.T, ((0, LANE - m), (0, LANE - v)))   # [Mp, Vp]
    # Padded (inactive) views: members=0 → active=0 → always met.
    members_p = jnp.pad(members, (0, LANE - v))[None, :]
    thresh_p = jnp.pad(thresh, (0, LANE - v))[None, :]
    active_p = jnp.pad(active, (0, LANE - v))[None, :]

    grid = (ep // block_e,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_e, LANE), lambda i: (i, 0)),
            pl.BlockSpec((LANE, LANE), lambda i: (0, 0)),
            pl.BlockSpec((1, LANE), lambda i: (0, 0)),
            pl.BlockSpec((1, LANE), lambda i: (0, 0)),
            pl.BlockSpec((1, LANE), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_e, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ep, LANE), jnp.int32),
        interpret=interpret,
    )(votes, nacks, vmt, members_p, thresh_p, active_p)
    return out[:e, 0].astype(jnp.int8)


# ---------------------------------------------------------------------------
# Per-ensemble view masks (the engine's state layout)


_SUB = 8  # f32 sublane tile: pad the view axis to it


def _ekernel(votes_ref, nacks_ref, mask_ref, out_ref):
    """Per-ensemble variant: each ensemble carries its own ``[V, M]``
    membership (reconfigs diverge them), so vote counting is a fused
    broadcast-multiply-reduce over the peer lanes instead of one shared
    MXU matmul, with the threshold derived in-kernel; the resolve tail
    is shared with :func:`_kernel`."""
    votes = votes_ref[:]          # [BE, Mp] f32
    nacks = nacks_ref[:]          # [BE, Mp] f32
    mask = mask_ref[:]            # [BE, Vp, Mp] f32

    heard = jnp.sum(mask * votes[:, None, :], axis=2)       # [BE, Vp]
    n_nack = jnp.sum(mask * nacks[:, None, :], axis=2)
    members = jnp.sum(mask, axis=2)
    thresh = jnp.floor(members * 0.5) + 1.0
    _resolve(heard, n_nack, members, thresh, members > 0.0, out_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_e", "interpret"))
def quorum_met_epallas(valid: jax.Array, nack: jax.Array,
                       view_mask: jax.Array, block_e: int = 512,
                       interpret: bool = False) -> jax.Array:
    """Pallas form of the ENGINE's quorum predicate: ``required=
    "quorum"``, no self term (the leader's vote is already folded into
    ``valid``), per-ensemble ``view_mask [E, V, M]``.  Drop-in for
    ``quorum_met_batch(valid, nack, view_mask, self_idx=-1,
    required="quorum", axis_name=None)``; returns int8 ``[E]``.
    """
    e, m = valid.shape
    assert view_mask.ndim == 3 and view_mask.shape[0] == e \
        and view_mask.shape[2] == m, view_mask.shape
    v = view_mask.shape[1]
    assert m <= LANE and v <= _SUB, "peer/view axes exceed one tile"

    ep = -(-e // block_e) * block_e
    votes = jnp.pad(valid.astype(jnp.float32),
                    ((0, ep - e), (0, LANE - m)))
    nacks = jnp.pad(nack.astype(jnp.float32),
                    ((0, ep - e), (0, LANE - m)))
    # Padded views have zero members → inactive → always met.
    mask = jnp.pad(view_mask.astype(jnp.float32),
                   ((0, ep - e), (0, _SUB - v), (0, LANE - m)))

    grid = (ep // block_e,)
    out = pl.pallas_call(
        _ekernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_e, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_e, _SUB, LANE), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_e, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ep, LANE), jnp.int32),
        interpret=interpret,
    )(votes, nacks, mask)
    return out[:e, 0].astype(jnp.int8)
