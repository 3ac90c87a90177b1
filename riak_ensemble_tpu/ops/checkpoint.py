"""Checkpoint/restore for the batched engine state.

The reference checkpoints per-peer facts through the coalescing storage
manager (maybe_save_fact, peer.erl:2201-2228; SURVEY §5) and recovers
by reloading + probing.  The device engine's equivalent: snapshot the
whole ``EngineState`` — E ensembles' ballots and replicated stores in
one pytree — via orbax (the TPU-native checkpointer), and restore it
into a fresh process.  A restored state is immediately serveable: the
ballot arrays ARE the facts, so there is no probe phase (the batched
analog of reload_fact + local_commit).

Orbax handles sharded arrays transparently, so the same two calls
checkpoint a mesh-sharded state from a multi-host job.
"""

from __future__ import annotations

import os
from typing import Optional

from riak_ensemble_tpu.ops.engine import EngineState


def save(path: str, state: EngineState) -> None:
    """Write a checkpoint (atomic directory swap, orbax semantics)."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.PyTreeCheckpointer()
    ckptr.save(path, _planes(state), force=True)


def _planes(state: EngineState) -> dict:
    """The state's arrays by field: a state without a row plane
    (``tree_rows`` None) writes and reads no such entry."""
    return {k: v for k, v in state._asdict().items() if v is not None}


def load(path: str, template: Optional[EngineState] = None,
         flat_trees: bool = False) -> EngineState:
    """Restore a checkpoint.  ``template`` (an ``init_state`` of the
    same shapes) restores each array DIRECTLY onto the template
    leaf's sharding — so a checkpoint taken under one device
    placement restores onto another (mesh-sharded save → single-shard
    serve and back) without inheriting the save-time placement from
    the file.  Without a template, arrays come back with saved
    metadata.

    ``flat_trees``: the image was written before the tree's storage
    stamp (``engine.TREE_FORM``) and holds every upper level flat in
    ``tree_node``, in a shape the template may not have.  Its upper
    levels are left on disk and the TEMPLATE's are returned in their
    place: the caller rebuilds every tree from the restored object
    planes (docs/MIGRATION.md)."""
    import jax
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.PyTreeCheckpointer()
    if template is not None:
        tpl = _planes(template)
        if flat_trees:
            return template._replace(**_load_but_the_trees(ckptr, path, tpl))
        restore_args = jax.tree.map(
            lambda x: ocp.ArrayRestoreArgs(sharding=x.sharding)
            if isinstance(x, jax.Array) else ocp.RestoreArgs(), tpl)
        restored = ckptr.restore(path, item=tpl,
                                 restore_args=restore_args)
    else:
        restored = ckptr.restore(path)
    return EngineState(**restored)


def _load_but_the_trees(ckptr, path: str, tpl: dict) -> dict:
    """Every plane of the image but its upper levels, onto the
    template's shardings."""
    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    kept = {k: v for k, v in tpl.items()
            if k not in ("tree_node", "tree_rows")}
    # orbax reads an image whole: the flat node plane goes to the host
    # as numpy and is dropped
    item = dict(kept, tree_node=0)
    restore_args = dict(
        jax.tree.map(lambda x: ocp.ArrayRestoreArgs(sharding=x.sharding),
                     kept),
        tree_node=ocp.RestoreArgs(restore_type=np.ndarray))
    restored = ckptr.restore(path, item=item, restore_args=restore_args)
    return {k: restored[k] for k in kept}
