"""The op slab: one upload per scalar launch (ISSUE 31).

Everything a scalar launch reads from the host travels as ONE int32
array (``engine.pack_op_slab`` has the row layout) in one transfer,
placed where the step wants it, and is taken apart inside the compiled
program.  Pinned here:

- the slab programs are BIT-identical to the per-plane programs on the
  same operands (full width, sliced, the 'ens'-sharded mesh step; with
  and without elections; K 1 and 4);
- a served flush records its transfers (``uploads``): 1 on a sliced
  launch, at most 2 on a pack-gather or mesh launch, and runs no eager
  device op inside the ``h2d`` span;
- the mesh slab is committed to the step's own ``P(None, 'ens')``;
- device-resident planes and engines that override the plain step keep
  the per-plane call, and the override is what runs;
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
    BatchedEnsembleService, WallRuntime, _LocalEngine)
from riak_ensemble_tpu.parallel.mesh import mesh_engine  # noqa: E402

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


def _led_state(engine):
    """A state with history: every ensemble has elected a leader and
    holds a few committed writes."""
    st = engine.init_state(E, M, S)
    up = jnp.ones((E, M), bool)
    kind = jnp.full((2, E), eng.OP_PUT, jnp.int32)
    slot = jnp.stack([jnp.arange(E, dtype=jnp.int32) % S,
                      (jnp.arange(E, dtype=jnp.int32) + 3) % S])
    st, won, _ = engine.full_step(
        st, jnp.ones((E,), bool), jnp.zeros((E,), jnp.int32), kind,
        slot, slot + 7, jnp.zeros((2, E), bool), up)
    assert np.asarray(won).all()
    return st


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
        got = engine.full_step_sliced_slab(st, jnp.asarray(slab), up)
    else:
        kind, slot, val, xe, xs = (jnp.asarray(p) for p in planes)
        lease_j = jnp.broadcast_to(jnp.asarray(lease), (k, E))
        want = engine.full_step(
            st, jnp.asarray(elect), jnp.asarray(cand), kind, slot, val,
            lease_j, up, exp_epoch=xe, exp_seq=xs)
        slab = eng.pack_op_slab(E, k, elect, cand, lease, planes)
        assert slab.shape == (3 + 5 * k, E) and slab.dtype == np.int32
        slab_j = (jax.device_put(slab, engine.slab_sharding)
                  if form == "mesh4" else jnp.asarray(slab))
        got = engine.full_step_slab(st, slab_j, up)
    assert np.asarray(want[2].committed).any(), "nothing committed"
    if elections:
        assert np.asarray(want[1]).any(), "no election won"
    _assert_same(got, want)


@pytest.mark.parametrize("sliced", [False, True], ids=["full", "sliced"])
def test_split_returns_what_pack_was_given(sliced):
    """Layout round trip, absent CAS planes included (None = zeros)."""
    k = 3
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
    el, ca, lz, kind, slot, val, xe, xs = jax.jit(
        eng.split_op_slab, static_argnames="sliced")(
            jnp.asarray(slab), sliced=sliced)
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


def _served_records(kind):
    """Drive one service of the given launch kind through an election
    flush and three steady flushes; returns (svc, steady records,
    spy)."""
    if kind == "sliced":       # E >= SLICE_MIN_E, 3 columns: A = 8
        svc = BatchedEnsembleService(WallRuntime(), 512, M, S,
                                     tick=None)
    elif kind == "pack_gather":  # under SLICE_MIN_E
        svc = BatchedEnsembleService(WallRuntime(), E, M, S, tick=None)
    else:
        svc = BatchedEnsembleService(WallRuntime(), E, M, S, tick=None,
                                     engine=mesh_engine(4))
    _settle(svc, [svc.kput(0, "warm", b"w")])  # elects every column
    spy = _SpyJnp()
    svc._jnp = spy
    n0 = len(svc.lat_records)
    for i in range(3):
        vals = _settle(svc, [svc.kput(c, f"k{i}", b"v%d" % i)
                             for c in (1, 7, 40)])
        assert all(v[0] == "ok" for v in vals), vals
    recs = [r for r in list(svc.lat_records)[n0:] if r.get("k")]
    assert len(recs) >= 3
    return svc, recs, spy


@pytest.mark.parametrize("kind,most", [("sliced", 1), ("pack_gather", 2),
                                       ("mesh", 2)])
def test_served_flush_counts_its_uploads(kind, most):
    svc, recs, spy = _served_records(kind)
    try:
        for r in recs:
            assert 1 <= r["uploads"] <= most, r
            if kind == "sliced":
                assert r["uploads"] == 1, r
            # no eager device op inside the h2d span: all the launch
            # takes from jnp there is the index vector's upload
            t0 = r["starts"]["h2d"]
            inside = {n for n, t in spy.taken if t0 <= t <= t0 + r["h2d"]}
            assert inside <= {"asarray"}, inside
            if kind != "pack_gather":
                assert not inside, inside
        st = svc.stats()
        assert st["slab_launches"] >= len(recs) + 1
        assert st["plane_launches"] == 0
    finally:
        svc.stop()


def test_mesh_slab_is_committed_to_the_steps_sharding():
    engine = mesh_engine(4)
    seen = []

    def spy(name):
        inner = getattr(engine, name)

        def step(state, slab, up):
            seen.append((slab, up))
            return inner(state, slab, up)
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


def test_device_resident_planes_keep_the_per_plane_call():
    svc = BatchedEnsembleService(WallRuntime(), E, M, S, tick=None)
    try:
        _settle(svc, [svc.kput(0, "warm", b"w")])
        slabs = svc.slab_launches
        kind = jnp.full((2, E), eng.OP_PUT, jnp.int32)
        slot = jnp.zeros((2, E), jnp.int32)
        committed, *_ = svc.execute(kind, slot, slot + 5)
        assert np.asarray(committed).all()
        assert svc.slab_launches == slabs and svc.plane_launches == 1
        # elect, cand and the lease row: the planes never moved
        assert svc.lat_records[-1]["uploads"] == 3
        # the same planes from the host ride the slab
        svc.execute(np.asarray(kind), np.asarray(slot),
                    np.asarray(slot) + 5)
        assert svc.slab_launches == slabs + 1
        assert svc.lat_records[-1]["uploads"] == 1
    finally:
        svc.stop()


class _CountingEngine(_LocalEngine):
    """A fault injector's shape: overrides the PLAIN step only."""
    calls = 0

    @classmethod
    def full_step(cls, *a, **kw):
        cls.calls += 1
        return _LocalEngine.full_step(*a, **kw)


def _instance_override():
    engine = _LocalEngine()
    engine.calls = 0

    def full_step(*a, **kw):
        engine.calls += 1
        return _LocalEngine.full_step(*a, **kw)
    engine.full_step = full_step
    return engine


@pytest.mark.parametrize("make", [_CountingEngine, _instance_override],
                         ids=["subclass", "instance"])
def test_an_overridden_plain_step_is_what_runs(make):
    engine = make()
    svc = BatchedEnsembleService(WallRuntime(), 512, M, S, tick=None,
                                 engine=engine)
    try:
        fns = svc._step_fns()
        assert fns.slab is None and fns.sliced_slab is None
        vals = _settle(svc, [svc.kput(c, "k", b"v") for c in (3, 9)])
        assert all(v[0] == "ok" for v in vals)
        launches = sum(1 for r in svc.lat_records if "uploads" in r)
        assert engine.calls == launches > 0
        assert svc.slab_launches == 0
        assert svc.plane_launches == launches
        assert all(r["uploads"] >= 7 for r in svc.lat_records
                   if r.get("k"))
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
        assert svc.slab_launches >= 3 and svc.plane_launches == 0
    finally:
        svc.stop()
