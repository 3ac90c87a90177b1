"""Differential test: the Pallas MXU quorum kernel must agree with the
jnp reference (quorum_met_batch) — which itself is differentially
tested against the scalar msg.erl-semantics oracle — on randomized
vote matrices, joint views, and every required mode.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from riak_ensemble_tpu.ops import pallas_quorum  # noqa: E402
from riak_ensemble_tpu.ops.pallas_quorum import quorum_met_pallas  # noqa: E402
from riak_ensemble_tpu.ops.quorum import (  # noqa: E402
    REQUIRED_MODES, quorum_met_batch, views_to_mask,
)


@pytest.fixture
def interpreted_engine_gate(monkeypatch):
    """The engine gate compiles the kernel for the TPU unless told
    otherwise; these CPU tests run it in the Pallas interpreter."""
    monkeypatch.setattr(pallas_quorum, "INTERPRET", True)


@pytest.mark.parametrize("required", REQUIRED_MODES)
@pytest.mark.parametrize("seed", [0, 1])
def test_pallas_matches_reference(required, seed):
    rng = np.random.default_rng(seed)
    e, m, v = 100, 7, 3
    # random joint views (first always full membership)
    views = [list(range(m))]
    for _ in range(v - 1):
        if rng.random() < 0.5:
            views.append(sorted(rng.choice(m, size=rng.integers(1, m + 1),
                                           replace=False).tolist()))
    mask = jnp.asarray(views_to_mask(views, v, m))

    valid = jnp.asarray(rng.random((e, m)) < 0.45)
    nack = jnp.asarray((rng.random((e, m)) < 0.3)) & ~valid
    self_idx = jnp.asarray(rng.integers(-1, m, (e,)), jnp.int32)

    ref = np.asarray(quorum_met_batch(valid, nack, mask, self_idx,
                                      required=required))
    got = np.asarray(quorum_met_pallas(valid, nack, mask, self_idx,
                                       required=required,
                                       interpret=True))
    np.testing.assert_array_equal(got, ref)


def test_pallas_singleton_and_edge_cases():
    # Singleton view: self vote alone meets quorum.
    mask = jnp.asarray(views_to_mask([[0]], 1, 1))
    valid = jnp.zeros((4, 1), bool)
    nack = jnp.zeros((4, 1), bool)
    self_idx = jnp.asarray([0, 0, -1, -1], jnp.int32)
    ref = np.asarray(quorum_met_batch(valid, nack, mask, self_idx))
    got = np.asarray(quorum_met_pallas(valid, nack, mask, self_idx,
                                       interpret=True))
    np.testing.assert_array_equal(got, ref)


def test_pallas_block_padding():
    """E not a multiple of the block size exercises the pad/slice."""
    rng = np.random.default_rng(7)
    e, m = 300, 5
    mask = jnp.asarray(views_to_mask([list(range(m))], 1, m))
    valid = jnp.asarray(rng.random((e, m)) < 0.5)
    nack = jnp.asarray((rng.random((e, m)) < 0.2)) & ~valid
    self_idx = jnp.zeros((e,), jnp.int32)
    ref = np.asarray(quorum_met_batch(valid, nack, mask, self_idx))
    got = np.asarray(quorum_met_pallas(valid, nack, mask, self_idx,
                                       block_e=256, interpret=True))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Per-ensemble-mask kernel (the engine's quorum path under
# RETPU_PALLAS_QUORUM=1)


@pytest.mark.parametrize("seed", range(4))
def test_epallas_matches_reference(seed):
    from riak_ensemble_tpu.ops.pallas_quorum import quorum_met_epallas

    rng = np.random.default_rng(seed)
    e, v, m = 37, 3, 7
    valid = jnp.asarray(rng.random((e, m)) < 0.55)
    nack = jnp.asarray((rng.random((e, m)) < 0.3)) & ~valid
    mask = rng.random((e, v, m)) < 0.6
    mask[:, 0, :] |= ~mask[:, 0, :].any(-1, keepdims=True)  # view 0 active
    if seed == 2:
        mask[:, 2, :] = False  # padded (inactive) trailing view
    mask = jnp.asarray(mask)

    ref = quorum_met_batch(valid, nack, mask,
                           jnp.full((e,), -1, jnp.int32),
                           required="quorum")
    got = quorum_met_epallas(valid, nack, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_engine_flag_gated_pallas_equivalence(interpreted_engine_gate):
    """RETPU_PALLAS_QUORUM=1 must not change any engine result: run a
    full protocol slice (elect, puts/gets with a down peer, reconfig)
    with the flag off and on and compare everything."""
    import jax as _jax

    from riak_ensemble_tpu.ops import engine as eng

    e, m, s, k = 16, 5, 8, 3

    def scenario():
        state = eng.init_state(e, m, s, views=[list(range(m))])
        up = jnp.ones((e, m), bool)
        yes = jnp.ones((e,), bool)
        state, won = eng.elect_step(state, yes,
                                    jnp.zeros((e,), jnp.int32), up)
        kind = jnp.asarray(np.stack([np.full(e, eng.OP_PUT),
                                     np.full(e, eng.OP_PUT),
                                     np.full(e, eng.OP_GET)]), jnp.int32)
        slot = jnp.asarray(np.arange(k * e).reshape(k, e) % s, jnp.int32)
        val = jnp.asarray(1 + np.arange(k * e).reshape(k, e), jnp.int32)
        lease = jnp.ones((k, e), bool)
        up2 = up.at[:, 0].set(False)
        state, res = eng.kv_step_scan(state, kind, slot, val, lease, up2)
        nv = jnp.asarray(np.tile(np.arange(m) < m - 1, (e, 1)))
        state, inst, _ = eng.reconfig_step(state, yes, nv, up2)
        return won, res, inst, state

    try:
        eng.PALLAS_QUORUM = False
        _jax.clear_caches()
        base = scenario()
        eng.PALLAS_QUORUM = True
        _jax.clear_caches()
        flagged = scenario()
    finally:
        eng.PALLAS_QUORUM = False
        _jax.clear_caches()

    for a, b in zip(_jax.tree.leaves(base), _jax.tree.leaves(flagged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quorum_met_wide_pallas_3dim_view_mask(interpreted_engine_gate):
    """Regression (round-5 advice): the wide Pallas branch of
    engine._quorum_met must accept a 3-dim [E, V, Ml] view_mask with
    W > 1 — broadcasting it per lane — not just a caller-pre-widened
    4-dim mask."""
    import jax as _jax

    from riak_ensemble_tpu.ops import engine as eng

    rng = np.random.default_rng(5)
    e, w, m, v = 9, 3, 5, 2
    ack = jnp.asarray(rng.random((e, w, m)) < 0.6)
    heard = jnp.asarray(np.ones((e, w, m), bool))
    mask = rng.random((e, v, m)) < 0.7
    mask[:, 0, :] |= ~mask[:, 0, :].any(-1, keepdims=True)
    mask3 = jnp.asarray(mask)
    mask4 = jnp.broadcast_to(mask3[:, None], (e, w, v, m))

    try:
        eng.PALLAS_QUORUM = True
        _jax.clear_caches()
        got3 = np.asarray(eng._quorum_met(ack, heard, mask3, None))
        got4 = np.asarray(eng._quorum_met(ack, heard, mask4, None))
        eng.PALLAS_QUORUM = False
        _jax.clear_caches()
        ref = np.asarray(eng._quorum_met(ack, heard, mask4, None))
    finally:
        eng.PALLAS_QUORUM = False
        _jax.clear_caches()
    np.testing.assert_array_equal(got3, ref)
    np.testing.assert_array_equal(got4, ref)
