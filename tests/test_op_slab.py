"""The op slab: one upload and one program call per launch (ISSUE 31,
32, 46).

Everything a launch reads from the host travels as ONE int32
array (``engine.pack_op_slab`` has the row layout) in one transfer,
placed where the step wants it, and is taken apart inside the compiled
program, which returns the state and ONE packed result vector (the
step and ``engine.pack_results`` of what it returned, one program).
Pinned here:

- the served programs are BIT-identical, state and packed vector, to
  the per-plane programs on the same operands followed by the pack
  called apart (full width, sliced, the 'ens'-sharded mesh step; with
  and without elections; K 1 and 4), and to the step body over the
  slab followed by the pack, each jitted apart
  (``testing.launch_apart``), on one chip and on a four-shard mesh,
  full width with the pack-gather and sliced;
- a served flush records its transfers (``uploads``), the device
  programs it called (``calls``) and whether its step sliced
  (``sliced``): 1 upload and 1 call on every launch that carries
  operations, sliced or pack-gather, one chip's or a mesh's (2
  uploads only where the failure detector changed ``up``), and no
  device op of ``jnp`` inside the ``h2d`` span;
- every way into the step (keyed ops of each kind, an election-only
  launch, ``execute()`` from host or ``jax.Array`` planes, a replica's
  apply) is one such launch;
- the mesh slab is committed to the step's own ``P(None, 'ens')``;
- a wrapped engine's step (``testing.wrap_engine_steps``) is what
  runs, sliced and at full width;
- ``warmup`` covers the slab programs: a flush of a warmed bucket
  compiles nothing.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    SLICE_MIN_E, BatchedEnsembleService, WallRuntime, _LocalEngine,
    unpack_results, unpack_results_sharded)
from riak_ensemble_tpu.parallel.mesh import (  # noqa: E402
    mesh_engine, shard_active_columns)
from riak_ensemble_tpu.testing import launch_apart  # noqa: E402

E, M, S, A = 64, 3, 8, 8


def _operands(k, elections, seed):
    """Seeded host operands of one launch over E columns: a mixed
    [K, E] op stream, leases on most columns, elections on a few (or
    on none)."""
    rng = np.random.default_rng(seed)
    kind = rng.choice([eng.OP_NOOP, eng.OP_GET, eng.OP_PUT, eng.OP_CAS,
                       eng.OP_RMW], (k, E)).astype(np.int32)
    slot = rng.integers(0, S, (k, E)).astype(np.int32)
    val = rng.integers(1, 1 << 20, (k, E)).astype(np.int32)
    exp_e = rng.integers(0, 3, (k, E)).astype(np.int32)
    exp_s = rng.integers(0, 3, (k, E)).astype(np.int32)
    lease = rng.random(E) < 0.7
    elect = (rng.random(E) < 0.3) if elections else np.zeros(E, bool)
    cand = rng.integers(0, M, E).astype(np.int32)
    return elect, cand, lease, (kind, slot, val, exp_e, exp_s)


def _per_plane(engine):
    """The per-plane REFERENCE program the slab forms are compared
    with: the mesh engine's own, the module's jit otherwise."""
    return getattr(engine, "full_step", eng.full_step)


def _led_state(engine):
    """A state with history: every ensemble has elected a leader and
    holds a few committed writes."""
    st = engine.init_state(E, M, S)
    up = jnp.ones((E, M), bool)
    kind = jnp.full((2, E), eng.OP_PUT, jnp.int32)
    slot = jnp.stack([jnp.arange(E, dtype=jnp.int32) % S,
                      (jnp.arange(E, dtype=jnp.int32) + 3) % S])
    st, won, _ = _per_plane(engine)(
        st, jnp.ones((E,), bool), jnp.zeros((E,), jnp.int32), kind,
        slot, slot + 7, jnp.zeros((2, E), bool), up)
    assert np.asarray(won).all()
    return st


def _pack_apart(engine, won, res, want_vsn=True):
    """``engine.pack_results`` of a step's results as a program of its
    own (per shard on the mesh, as its served program packs)."""
    def pack(won, res):
        return eng.pack_results(won, res, want_vsn)
    mesh = getattr(engine, "mesh", None)
    if mesh is not None:
        pack = jax.shard_map(
            pack, mesh=mesh, in_specs=(P("ens"), eng.scan_result_specs()),
            out_specs=P("ens"), check_vma=False)
    return jax.jit(pack)(won, res)


def _assert_same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        assert x.dtype == y.dtype and x.shape == y.shape, (i, x, y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"leaf {i}")


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("elections", [False, True],
                         ids=["no-elect", "elect"])
@pytest.mark.parametrize("form", ["full", "sliced", "mesh4"])
def test_slab_program_matches_per_plane_program(form, elections, k):
    engine = mesh_engine(4) if form == "mesh4" else _LocalEngine()
    st = _led_state(engine)
    elect, cand, lease, planes = _operands(k, elections, seed=31 + k)
    up_np = np.ones((E, M), bool)
    up_np[::5, 1] = False
    up = jnp.asarray(up_np)
    if form == "sliced":
        active = np.array([2, 5, 11, 40, 63], np.int32)
        elect[[e for e in range(E) if e not in active]] = False
        aidx = np.full((A,), E, np.int32)
        aidx[:active.size] = active

        def cut(x):  # what the per-plane launch uploads
            out = np.zeros(x.shape[:-1] + (A,), x.dtype)
            out[..., :active.size] = x[..., active]
            return out

        kind, slot, val, xe, xs = (jnp.asarray(cut(p)) for p in planes)
        lease_j = jnp.broadcast_to(jnp.asarray(cut(lease)), (k, A))
        want = jax.jit(eng._full_step_sliced_body)(
            st, jnp.asarray(aidx), jnp.asarray(cut(elect)),
            jnp.asarray(cut(cand)), kind, slot, val, lease_j, up,
            exp_epoch=xe, exp_seq=xs)
        slab = eng.pack_op_slab(A, k, elect, cand, lease, planes,
                                active, aidx)
        assert slab.shape == (4 + 5 * k, A) and slab.dtype == np.int32
        got = engine.full_step_sliced_slab(st, jnp.asarray(slab), up,
                                           want_vsn=True)
    else:
        kind, slot, val, xe, xs = (jnp.asarray(p) for p in planes)
        lease_j = jnp.broadcast_to(jnp.asarray(lease), (k, E))
        want = _per_plane(engine)(
            st, jnp.asarray(elect), jnp.asarray(cand), kind, slot, val,
            lease_j, up, exp_epoch=xe, exp_seq=xs)
        slab = eng.pack_op_slab(E, k, elect, cand, lease, planes)
        assert slab.shape == (3 + 5 * k, E) and slab.dtype == np.int32
        slab_j = (jax.device_put(slab, engine.slab_sharding)
                  if form == "mesh4" else jnp.asarray(slab))
        got = engine.full_step_slab(st, slab_j, up, want_vsn=True,
                                    gather=0)
    assert np.asarray(want[2].committed).any(), "nothing committed"
    if elections:
        assert np.asarray(want[1]).any(), "no election won"
    _assert_same(got, (want[0], _pack_apart(engine, *want[1:])))


def _shard_blocks(active, n_sh):
    """A sliced slab's index rows for ``active`` over ``n_sh`` shards
    of E columns: ``(rows [n_sh, a_loc]`` of LOCAL indices, pad =
    the rows a shard holds; ``at``, the slab column of each active
    column; ``a_loc)``."""
    from riak_ensemble_tpu.parallel.mesh import shard_active_columns
    e_loc = E // n_sh
    per_shard, a_loc = shard_active_columns(active, E, n_sh, 2)
    rows = np.full((n_sh, a_loc), e_loc, np.int32)
    for sh, p in enumerate(per_shard):
        rows[sh, :p.size] = p
    return rows, np.flatnonzero(rows.ravel() < e_loc), a_loc


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("elections", [False, True],
                         ids=["no-elect", "elect"])
def test_mesh_sliced_slab_program_matches_one_chips(elections, k):
    """The mesh's sliced program on per-shard blocks (each shard its
    own LOCAL index row, one shard's block all pad) against one chip's
    sliced program on the same columns: the same state, and the same
    ``won`` and result columns, found at each column's place in its
    shard's block."""
    n_sh, e_loc = 4, E // 4
    engine = mesh_engine(n_sh)
    elect, cand, lease, planes = _operands(k, elections, seed=33 + k)
    active = np.array([2, 5, 11, 40, 63], np.int32)   # shard 1 empty
    elect[[e for e in range(E) if e not in active]] = False
    if elections:       # two that are won, whatever the seed drew
        elect[[2, 63]], cand[[2, 63]] = True, 0
    up_np = np.ones((E, M), bool)
    up_np[::5, 1] = False
    up = jnp.asarray(up_np)

    aidx = np.full((A,), E, np.int32)
    aidx[:active.size] = active
    want = jax.jit(eng._full_step_sliced_slab_body)(
        _led_state(_LocalEngine()),
        jnp.asarray(eng.pack_op_slab(A, k, elect, cand, lease, planes,
                                     active, aidx)), up)

    rows, at, a_loc = _shard_blocks(active, n_sh)
    assert a_loc == 4 and [r[r < e_loc].tolist() for r in rows] == [
        [2, 5, 11], [], [8], [15]]
    slab = eng.pack_op_slab(n_sh * a_loc, k, elect, cand, lease, planes,
                            active, rows.ravel(), at)
    assert slab.shape == (4 + 5 * k, n_sh * a_loc)
    np.testing.assert_array_equal(
        slab[eng.SLAB_ACTIVE_IDX].reshape(n_sh, a_loc), rows)
    got = launch_apart(
        engine, _led_state(engine),
        jax.device_put(slab, engine.slab_sharding),
        jax.device_put(up_np, engine.up_sharding), True, sliced=True)

    assert np.asarray(want[2].committed).any(), "nothing committed"
    if elections:
        assert np.asarray(want[1]).any(), "no election won"
    _assert_same(got[0], want[0])
    n = active.size
    pad = np.setdiff1d(np.arange(n_sh * a_loc), at)
    np.testing.assert_array_equal(np.asarray(got[1])[at],
                                  np.asarray(want[1])[:n])
    for name, g, w in zip(want[2]._fields, got[2], want[2]):
        g, w = np.asarray(g), np.asarray(w)
        if name == "quorum_ok":
            # ONE row at full width on both: each shard's epoch check
            # of all its own rows, side by side, is one chip's
            assert g.shape == w.shape == (1, E)
            assert w[0, active].all() and w.sum() > n
            np.testing.assert_array_equal(g, w, err_msg=name)
            continue
        assert g.shape[1] == n_sh * a_loc, (name, g.shape)
        np.testing.assert_array_equal(g[:, at], w[:, :n], err_msg=name)
        if name in ("committed", "get_ok", "found"):
            assert not g[:, pad].any(), name


def _random_state(e, m, s, seed):
    """Every plane of an ``[e, m, s]`` state filled with seeded noise
    (no protocol meaning: the edges only move rows)."""
    rng = np.random.default_rng(seed)

    def noise(x):
        if x.dtype == jnp.bool_:
            return jnp.asarray(rng.random(x.shape) < 0.5)
        return jnp.asarray(rng.integers(0, 1 << 30, x.shape)
                           .astype(x.dtype))

    return jax.tree.map(noise, jax.eval_shape(
        lambda: eng.init_state(e, m, s)))


@pytest.mark.parametrize("a", [8, 64])
@pytest.mark.parametrize("shape", [(300, 5, 128), (64, 3, 16),
                                   (512, 5, 256), (260, 3, 2048)], ids=str)
def test_sliced_edges_match_take_and_set(shape, a):
    """The sliced step's gather and scatter address the object planes
    as rows of their ``[M * E, S]`` view (ISSUE 40): bit-equal to
    ``jnp.take(x, idx, 0)`` / ``x.at[idx].set(s, mode="drop")`` on
    every plane (the row plane of a state of 2,048 slots among them:
    ISSUE 44), padding indices (= E) clipped by the one and dropped
    by the other."""
    e, m, s = shape
    assert (eng.init_state(1, 1, s).tree_rows is None) == (s < 2048)
    rng = np.random.default_rng(40 + a)
    real = rng.choice(e, a - 5, replace=False).astype(np.int32)
    aidx = np.full((a,), e, np.int32)       # 5 pads, not all at the end
    aidx[np.sort(rng.choice(a, a - 5, replace=False))] = real
    aidx_j = jnp.asarray(aidx)
    state = _random_state(e, m, s, seed=1)
    up = jnp.asarray(rng.random((e, m)) < 0.8)

    sub, up_a = jax.jit(eng._slice_columns)(state, aidx_j, up)
    idx_c = jnp.clip(aidx_j, 0, e - 1)
    _assert_same(sub, jax.tree.map(lambda x: jnp.take(x, idx_c, axis=0),
                                   state))
    np.testing.assert_array_equal(np.asarray(up_a),
                                  np.asarray(up)[np.asarray(idx_c)])

    stepped = _random_state(a, m, s, seed=2)
    got = jax.jit(eng._scatter_columns)(state, stepped, aidx_j)
    _assert_same(got, jax.tree.map(
        lambda full, x: full.at[aidx_j].set(x, mode="drop"),
        state, stepped))
    # the pads wrote nothing, the real rows hold the stepped values
    idle = np.setdiff1d(np.arange(e), real)
    for g, x, new in zip(*map(jax.tree.leaves, (got, state, stepped))):
        np.testing.assert_array_equal(np.asarray(g)[idle],
                                      np.asarray(x)[idle])
        np.testing.assert_array_equal(np.asarray(g)[aidx[aidx < e]],
                                      np.asarray(new)[aidx < e])


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("peers", ["all-up", "some-down"])
@pytest.mark.parametrize("form", ["one-chip", "mesh4"])
def test_sliced_step_matches_full_width_step(form, peers, k):
    """The same operations through the sliced program and through the
    full-width program: the same state, and the same ``won`` and
    result columns at each active column's place (an idle column's
    full-width NOOP round changes nothing).  The QUORUM plane agrees
    over all E columns: the sliced program's one full-width row is
    what the full-width program's rounds report, for the columns it
    stepped and for those it did not: also where an idle ensemble's
    leader is down or its up members are short of a quorum."""
    n_sh = 4 if form == "mesh4" else 1
    engine = mesh_engine(n_sh) if form == "mesh4" else _LocalEngine()
    elect, cand, lease, planes = _operands(k, True, seed=40 + k)
    active = np.array([2, 5, 11, 40, 63], np.int32)
    idle = np.setdiff1d(np.arange(E), active)
    elect[idle], lease[idle] = False, False
    elect[[2, 63]], cand[[2, 63]] = True, 0
    planes[0][:, idle] = eng.OP_NOOP
    up_np = np.ones((E, M), bool)
    no_quorum = []
    if peers == "some-down":
        up_np[::5, 1] = False       # one follower: a quorum stands
        up_np[[7, 33], 0] = False   # idle, the leader itself down
        up_np[[9, 50], 1:] = False  # idle, the leader alone
        up_np[11, 1:] = False       # active, the leader alone
        no_quorum = [7, 9, 11, 33, 50]
    up = jnp.asarray(up_np)

    def placed(slab):
        return (jax.device_put(slab, engine.slab_sharding)
                if form == "mesh4" else jnp.asarray(slab))

    want = launch_apart(
        engine, _led_state(engine),
        placed(eng.pack_op_slab(E, k, elect, cand, lease, planes)), up,
        True)

    rows, at, a_loc = _shard_blocks(active, n_sh)
    got = launch_apart(
        engine, _led_state(engine),
        placed(eng.pack_op_slab(n_sh * a_loc, k, elect, cand, lease,
                                planes, active, rows.ravel(), at)), up,
        True, sliced=True)

    assert np.asarray(want[2].committed).any(), "nothing committed"
    assert np.asarray(want[1]).any(), "no election won"
    _assert_same(got[0], want[0])
    np.testing.assert_array_equal(np.asarray(got[1])[at],
                                  np.asarray(want[1])[active])
    for name, g, w in zip(want[2]._fields, got[2], want[2]):
        g, w = np.asarray(g), np.asarray(w)
        if name == "quorum_ok":
            assert g.shape == (1, E)
            np.testing.assert_array_equal(g[0], w.any(0), err_msg=name)
            ok = np.ones(E, bool)
            ok[no_quorum] = False
            np.testing.assert_array_equal(g[0], ok, err_msg=name)
            continue
        np.testing.assert_array_equal(g[:, at], w[:, active],
                                      err_msg=name)


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("form", ["full", "pack-gather", "sliced",
                                  "mesh4-pack-gather", "mesh4-sliced"])
def test_one_program_launch_matches_step_then_pack_apart(form, k):
    """The served program (step and pack, ONE program) against the
    step body over the same slab followed by ``engine.pack_results``,
    each jitted apart (``testing.launch_apart``: what a launch called
    until ISSUE 46): the packed vector and the final state bit-equal,
    on one chip and on a four-shard mesh, full width (packed whole,
    and gathered by the slab's index row) and sliced, donated and not;
    and the host's unpack of that vector returns the step's own
    planes at the launch's columns."""
    mesh = form.startswith("mesh4")
    n_sh = 4 if mesh else 1
    e_loc = E // n_sh
    engine = mesh_engine(4) if mesh else _LocalEngine()
    elect, cand, lease, planes = _operands(k, True, seed=46 + k)
    active = np.array([2, 5, 11, 40, 63], np.int32)
    idle = np.setdiff1d(np.arange(E), active)
    elect[idle] = False
    elect[[2, 63]], cand[[2, 63]] = True, 0
    planes[0][:, idle] = eng.OP_NOOP
    up_np = np.ones((E, M), bool)
    up_np[::5, 1] = False
    per_shard, a_loc = shard_active_columns(active, E, n_sh, 2)
    sliced = form.endswith("sliced")
    gather = a_loc if form.endswith("pack-gather") else 0
    if sliced:
        rows, at, _ = _shard_blocks(active, n_sh)
        slab = eng.pack_op_slab(n_sh * a_loc, k, elect, cand, lease,
                                planes, active, rows.ravel(), at)
    else:
        row = None
        if gather:      # each shard's LOCAL indices, pad 0
            row = np.zeros((n_sh, e_loc), np.int32)
            for sh, p in enumerate(per_shard):
                row[sh, :p.size] = p
            row = row.ravel()
        slab = eng.pack_op_slab(E, k, elect, cand, lease, planes,
                                None, row)

    def placed():
        if not mesh:
            return jnp.asarray(slab), jnp.asarray(up_np)
        return (jax.device_put(slab, engine.slab_sharding),
                jax.device_put(up_np, engine.up_sharding))

    static = {"want_vsn": True} if sliced else {"want_vsn": True,
                                                "gather": gather}
    name = "full_step_sliced_slab" if sliced else "full_step_slab"
    st_ref, won, res, flat_ref = launch_apart(
        engine, _led_state(engine), *placed(), True, gather, sliced)
    assert np.asarray(res.committed).any(), "nothing committed"
    assert np.asarray(won).any(), "no election won"
    for twin in ("", "_donate"):
        st, flat = getattr(engine, name + twin)(
            _led_state(engine), *placed(), **static)
        _assert_same(st, st_ref)
        assert flat.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(flat),
                                      np.asarray(flat_ref))

    shaped = sliced or gather
    if mesh:
        out = unpack_results_sharded(
            np.asarray(flat), E, M, k, True, n_sh,
            per_shard if shaped else None, a_loc if shaped else 0,
            sliced)
    else:
        out = unpack_results(np.asarray(flat), E, M, k, True,
                             active if shaped else None,
                             a_loc if shaped else 0, sliced)
    u_won, u_quorum, _, committed, get_ok, found, value, vsn = out
    won, res = np.asarray(won), jax.tree.map(np.asarray, res)
    # where each launch column's results sit in the step's planes
    src = at if sliced else active
    np.testing.assert_array_equal(u_won[active], won[src])
    # the quorum plane is E wide whatever the launch's shape
    assert u_quorum.shape == (E,)
    assert u_quorum.sum() > active.size
    np.testing.assert_array_equal(u_quorum, res.quorum_ok.any(0))
    for name, got, want in (("committed", committed, res.committed),
                            ("get_ok", get_ok, res.get_ok),
                            ("found", found, res.found),
                            ("value", value, res.value),
                            ("vsn", vsn, res.obj_vsn)):
        np.testing.assert_array_equal(got[:, active], want[:, src],
                                      err_msg=name)


@pytest.mark.parametrize("form", ["full", "sliced", "pack-gather"])
def test_split_returns_what_pack_was_given(form):
    """Layout round trip, absent CAS planes included (None = zeros);
    a full-width slab carries a pack-gather's index row as a sliced
    one carries its own, and is otherwise the slab without it."""
    k = 3
    sliced = form == "sliced"
    elect, cand, lease, planes = _operands(k, True, seed=7)
    planes = planes[:3] + (None, None)
    if sliced:
        active = np.array([1, 9, 33], np.int32)
        aidx = np.full((A,), E, np.int32)
        aidx[:3] = active
        slab = eng.pack_op_slab(A, k, elect, cand, lease, planes,
                                active, aidx)
        np.testing.assert_array_equal(slab[eng.SLAB_ACTIVE_IDX], aidx)
        w, take = A, lambda x: np.pad(
            x[..., active], [(0, 0)] * (x.ndim - 1) + [(0, A - 3)])
    else:
        slab = eng.pack_op_slab(E, k, elect, cand, lease, planes)
        w, take = E, lambda x: x
        if form == "pack-gather":
            row = np.zeros((E,), np.int32)
            row[:A] = [1, 9, 33, 0, 0, 0, 0, 0]
            plain, slab = slab, eng.pack_op_slab(
                E, k, elect, cand, lease, planes, None, row)
            assert slab.shape == (4 + 5 * k, E)
            np.testing.assert_array_equal(
                np.delete(slab, eng.SLAB_ACTIVE_IDX, axis=0), plain)
            np.testing.assert_array_equal(
                np.asarray(eng.pack_gather_index(jnp.asarray(slab), A)),
                row[:A])
            assert eng.pack_gather_index(jnp.asarray(plain), 0) is None
    indexed = form != "full"
    el, ca, lz, kind, slot, val, xe, xs = jax.jit(
        eng.split_op_slab, static_argnames="indexed")(
            jnp.asarray(slab), indexed=indexed)
    assert el.dtype == bool and lz.dtype == bool and lz.shape == (k, w)
    np.testing.assert_array_equal(np.asarray(el), take(elect))
    np.testing.assert_array_equal(np.asarray(ca), take(cand))
    np.testing.assert_array_equal(
        np.asarray(lz), np.broadcast_to(take(lease), (k, w)))
    for got, p in zip((kind, slot, val), planes):
        np.testing.assert_array_equal(np.asarray(got), take(p))
    assert not np.asarray(xe).any() and not np.asarray(xs).any()


# -- the served launch -------------------------------------------------------


class _SpyJnp:
    """``svc._jnp`` stand-in that stamps every attribute the launch
    path takes from ``jnp``."""

    def __init__(self):
        self.taken = []

    def __getattr__(self, name):
        self.taken.append((name, time.perf_counter()))
        return getattr(jnp, name)


def _settle(svc, futs):
    while not all(f.done for f in futs):
        svc.flush()
    return [f.value for f in futs]


def _service(shape, **kw):
    if shape == "sliced":       # E >= SLICE_MIN_E, 3 columns: A = 8
        return BatchedEnsembleService(WallRuntime(), 512, M, S,
                                      tick=None, **kw)
    if shape == "pack_gather":  # under SLICE_MIN_E
        return BatchedEnsembleService(WallRuntime(), E, M, S,
                                      tick=None, **kw)
    # a mesh slices where a SHARD holds SLICE_MIN_E rows; at E = 64 a
    # shard holds 16, and the full grid and the pack-gather stay
    n_ens = 4 * SLICE_MIN_E if shape == "mesh_sliced" else E
    return BatchedEnsembleService(WallRuntime(), n_ens, M, S, tick=None,
                                  engine=mesh_engine(4), **kw)


COLS = (1, 7, 40)


def _ok(vals):
    assert all(v[0] == "ok" for v in vals), vals
    return vals


def _drive_kput(svc):
    for i in range(3):
        _ok(_settle(svc, [svc.kput(c, f"k{i}", b"v%d" % i)
                          for c in COLS]))


def _drive_kupdate(svc):     # CAS planes present
    vals = _ok(_settle(svc, [svc.kput(c, "k", b"v") for c in COLS]))
    _ok(_settle(svc, [svc.kupdate(c, "k", v[1], b"v2")
                      for c, v in zip(COLS, vals)]))


def _drive_kmodify(svc):     # the RMW arm
    from riak_ensemble_tpu import funref
    for _ in range(2):
        _ok(_settle(svc, [svc.kmodify(c, "ctr", funref.ref("rmw:add", 5),
                                      0) for c in COLS]))


def _drive_kdelete(svc):
    _ok(_settle(svc, [svc.kput(c, "k", b"v") for c in COLS]))
    _ok(_settle(svc, [svc.kdelete(c, "k") for c in COLS]))


def _drive_kget_no_lease(svc):
    _ok(_settle(svc, [svc.kput(c, "k", b"v") for c in COLS]))
    svc.lease_until[:] = 0.0
    futs = [svc.kget(c, "k") for c in COLS]
    assert not any(f.done for f in futs), "the lease served the read"
    assert _settle(svc, futs) == [("ok", b"v")] * len(COLS)


def _drive_election_only(svc):
    """K = 0: a leader goes down with nothing queued.  The launch
    uploads the changed ``up`` mask beside its slab."""
    svc.set_peer_up(7, int(svc.leader_np[7]), False)
    flushes = svc.flushes
    svc.flush()
    assert svc.flushes == flushes + 1 and svc.leader_np[7] >= 0
    rec = svc.lat_records[-1]
    assert rec["k"] == 0 and rec["uploads"] == 2, rec


def _dense_planes(xp):
    kind = xp.full((2, E), eng.OP_PUT, xp.int32)
    slot = xp.zeros((2, E), xp.int32)
    return kind, slot, slot + 5


def _drive_execute_host(svc):
    committed, *_ = svc.execute(*_dense_planes(np))
    assert committed.all()


def _drive_execute_jax(svc):
    """``jax.Array`` planes are read back at the door: the same one
    launch, the same answer."""
    kind, slot, val = _dense_planes(jnp)
    committed, *_ = svc.execute(kind, slot, val)
    assert isinstance(committed, np.ndarray) and committed.all()
    _, get_ok, found, value = svc.execute(
        jnp.full_like(kind, eng.OP_GET), slot, val)
    assert get_ok.all() and found.all() and (value == 5).all()


#: (service shape, drive, most uploads on a launch: 1 on every launch
#: that carries operations, the slab; 2 only on the k == 0 launch that
#: first uploads a changed ``up`` mask)
CASES = {
    "sliced": ("sliced", _drive_kput, 1),
    "pack_gather": ("pack_gather", _drive_kput, 1),
    "mesh": ("mesh", _drive_kput, 1),
    "mesh_sliced": ("mesh_sliced", _drive_kput, 1),
    "mesh_sliced_kupdate": ("mesh_sliced", _drive_kupdate, 1),
    "kupdate": ("sliced", _drive_kupdate, 1),
    "kmodify": ("sliced", _drive_kmodify, 1),
    "kdelete": ("pack_gather", _drive_kdelete, 1),
    "kget_no_lease": ("sliced", _drive_kget_no_lease, 1),
    "election_only": ("sliced", _drive_election_only, 2),
    "execute_host": ("pack_gather", _drive_execute_host, 1),
    "execute_jax": ("mesh", _drive_execute_jax, 1),
    # the launch's shape in the record (SHAPES below)
    "shape_sliced": ("sliced", _drive_kput, 1),
    "shape_pack_gather": ("pack_gather", _drive_kput, 1),
    "shape_election_full_grid": ("sliced", _drive_election_only, 2),
    "shape_mesh_sliced": ("mesh_sliced", _drive_kput, 1),
    "shape_mesh_pack_gather": ("mesh", _drive_kput, 1),
}

#: (a, cols, cols_max, shards) of every launch the drive makes: the
#: pow2 width it packed at (0: the full grid, nothing gathered), its
#: real columns, the busiest shard's, the shards.  COLS are three
#: columns of shard 0; at E = 64 over four shards column 40 is shard 2's
SHAPES = {
    "shape_sliced": (8, 3, 3, 1),
    "shape_pack_gather": (8, 3, 3, 1),
    "shape_election_full_grid": (0, 0, 0, 1),
    "shape_mesh_sliced": (8, 3, 3, 4),
    "shape_mesh_pack_gather": (8, 3, 2, 4),
}

#: the only step programs a launch may compile
STEP_PROGRAMS = {"step", "step_sliced"}


def _assert_slab_launches(svc, recs, shape, most):
    assert recs
    slices = shape in ("sliced", "mesh_sliced")
    for r in recs:
        assert 1 <= r["uploads"] <= most, r
        assert r["calls"] == 1, r
        assert r["sliced"] == int(slices and r["k"] > 0), r
        if r["sliced"]:
            assert r["a"] > 0, r
        assert r["shards"] == (svc._mesh_shards or 1), r
        assert r["cols_max"] <= r["cols"] <= svc.n_ens, r
        assert r["a"] == 0 or r["cols_max"] <= r["a"], r
    assert svc.stats()["launches_sliced"] >= sum(
        r["sliced"] for r in recs)
    launch = svc.stats()["launch"]
    assert launch["calls"] == launch["launches"] >= len(recs), launch
    assert launch["uploads"] >= launch["launches"], launch
    assert {e["fn"] for e in svc._compile_log
            if e["fn"].startswith("step")} <= STEP_PROGRAMS, \
        list(svc._compile_log)


@pytest.mark.parametrize("case", list(CASES))
def test_served_flush_counts_its_uploads(case):
    shape, drive, most = CASES[case]
    svc = _service(shape)
    try:
        _settle(svc, [svc.kput(0, "warm", b"w")])  # elects every column
        spy = _SpyJnp()
        svc._jnp = spy
        n0 = len(svc.lat_records)
        drive(svc)
        recs = [r for r in list(svc.lat_records)[n0:] if "uploads" in r]
        _assert_slab_launches(svc, recs, shape, most)
        if case in SHAPES:
            assert {(r["a"], r["cols"], r["cols_max"], r["shards"])
                    for r in recs} == {SHAPES[case]}
        for r in recs:
            # no device op of jnp inside the h2d span, eager or an
            # upload: the slab's device_put is all of it
            t0 = r["starts"]["h2d"]
            inside = {n for n, t in spy.taken if t0 <= t <= t0 + r["h2d"]}
            assert not inside, inside
    finally:
        svc.stop()


def test_a_replicas_apply_is_a_slab_launch(tmp_path):
    """Three hosts: the first settled round elects every column, so it
    ships full-plane and each replica RE-EXECUTES it through the same
    door (the slab and ``up``'s first upload)."""
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.parallel import repgroup

    srvs = [repgroup.ReplicaServer(
        E, 3, S, data_dir=str(tmp_path / f"r{i}"),
        config=fast_test_config()) for i in (1, 2)]
    svc = repgroup.ReplicatedService(
        WallRuntime(), E, 1, S, group_size=3,
        peers=[("127.0.0.1", s.repl_port) for s in srvs],
        ack_timeout=15.0, config=fast_test_config(),
        data_dir=str(tmp_path / "leader"))
    try:
        assert svc.takeover()
        _ok(_settle(svc, [svc.kput(c, "k", b"v") for c in COLS]))
        svc.heartbeat()
        svc._drain_pending(block_all=True)
        for s in srvs + [svc]:
            lane = getattr(s, "svc", s)
            recs = [r for r in lane.lat_records if "uploads" in r]
            _assert_slab_launches(lane, recs, "pack_gather", 2)
            assert any(r["k"] for r in recs), "no op-carrying apply"
    finally:
        svc.stop()
        for s in srvs:
            s.stop()


def test_mesh_slab_is_committed_to_the_steps_sharding():
    engine = mesh_engine(4)
    seen = []

    def spy(name):
        inner = getattr(engine, name)

        def step(state, slab, up, **static):
            seen.append((slab, up))
            return inner(state, slab, up, **static)
        setattr(engine, name, step)

    spy("full_step_slab")
    spy("full_step_slab_donate")
    svc = BatchedEnsembleService(WallRuntime(), E, M, S, tick=None,
                                 engine=engine)
    try:
        _settle(svc, [svc.kput(c, "k", b"v") for c in (0, 17, 63)])
        assert seen
        want = NamedSharding(engine.mesh, P(None, "ens"))
        want_up = NamedSharding(engine.mesh, P("ens", "peer"))
        for slab, up in seen:
            assert slab.committed and up.committed
            assert slab.sharding.is_equivalent_to(want, slab.ndim)
            assert up.sharding.is_equivalent_to(want_up, up.ndim)
            # each chip holds its own quarter of the columns, once
            assert {s.data.shape for s in slab.addressable_shards} == {
                (slab.shape[0], E // 4)}
    finally:
        svc.stop()


@pytest.mark.parametrize("n_ens,program", [
    (512, "full_step_sliced_slab"), (E, "full_step_slab")],
    ids=["sliced", "full-width"])
def test_a_wrapped_engines_step_is_what_runs(n_ens, program):
    """``testing.wrap_engine_steps`` is how a test stands in the way
    of the step: whichever program the flush dispatches, the wrapper
    is what runs, once per launch."""
    from riak_ensemble_tpu.testing import wrap_engine_steps

    calls = []

    def count(inner, state, slab, up, sliced):
        calls.append((inner, sliced))
        return inner(state, slab, up)

    base = _LocalEngine()
    svc = BatchedEnsembleService(WallRuntime(), n_ens, M, S, tick=None,
                                 engine=wrap_engine_steps(base, count))
    try:
        _settle(svc, [svc.kput(0, "warm", b"w")])
        n0, l0 = len(calls), len(svc.lat_records)
        _ok(_settle(svc, [svc.kput(c, "k", b"v") for c in (3, 9)]))
        launches = sum(1 for r in list(svc.lat_records)[l0:]
                       if "uploads" in r)
        assert len(calls) - n0 == launches > 0
        twin = program + ("_donate" if svc._donate else "")
        # ``inner`` is the engine's program with the launch's static
        # arguments bound
        assert all(inner.func is getattr(base, twin)
                   and inner.keywords["want_vsn"] is True
                   and ("gather" in inner.keywords) != sliced
                   and sliced == ("sliced" in program)
                   for inner, sliced in calls[n0:]), calls[n0:]
    finally:
        svc.stop()


@pytest.mark.parametrize("n_ens,cols", [(328, (2, 77, 300)),
                                        (40, (2, 17, 39))],
                         ids=["sliced", "pack-gather"])
def test_warmup_covers_the_slab_programs(n_ens, cols):
    """A flush of a warmed (K, A) bucket compiles nothing.  The ring
    sizes are this test's own (jit caches are process-wide)."""
    svc = BatchedEnsembleService(WallRuntime(), n_ens, M, S, tick=None,
                                 max_ops_per_tick=2)
    try:
        svc.warmup(buckets=[(1, 8), (1, None), (2, 8), (2, None)])
        warm = [e["fn"] for e in svc._compile_log]
        # "step" / "step_sliced" name the served (slab) programs
        assert "step" in warm, warm
        assert ("step_sliced" in warm) == (n_ens >= 256), warm
        serve0 = svc._c_compile.labels("serve").value
        _settle(svc, [svc.kput(0, "warm", b"w")])      # election flush
        for i in range(2):
            _settle(svc, [svc.kput(c, f"k{i}", b"v") for c in cols])
        leaked = [e for e in svc._compile_log if e["phase"] == "serve"]
        assert svc._c_compile.labels("serve").value == serve0, leaked
    finally:
        svc.stop()
