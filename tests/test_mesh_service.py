"""Single-shard ↔ mesh serving equivalence (the shard-wise pack path).

The mesh engine (8 virtual CPU devices, 'ens'-sharded, peer axis
unsharded) must be BIT-IDENTICAL to the single-shard oracle over mixed
put/CAS/RMW/tombstone streams — results, device state, host mirror
slabs, and WAL bytes — including compacted (per-shard active-column
bucketing) flushes.  Plus the mesh serving-path
contracts: warmup covers the mesh step/pack variants (CompileWatch
asserts zero serve-phase compiles), and checkpoints round-trip across
shard counts (8→1 and 1→8) bit-equal.

The SLICED mesh launch (ISSUE 33: four virtual devices, a shard of
``SLICE_MIN_E`` rows) is held against the full-grid mesh launch and
against one chip's sliced launch on the same ops.

Marked ``mesh`` so the suite can run as its own session
(``pytest -m mesh``); the forced 8-device CPU mesh comes from
conftest.py's XLA_FLAGS bootstrap (process-wide by design — the flag
must precede the jax import).
"""

import os
import shutil
import tempfile

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import funref  # noqa: E402
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    SLICE_MIN_E, BatchedEnsembleService, WallRuntime, mesh_ens_shards,
    packed_nbytes,
)
from riak_ensemble_tpu.parallel.mesh import mesh_engine  # noqa: E402

pytestmark = pytest.mark.mesh

if jax.device_count() < 8:  # pragma: no cover - driver contract
    pytest.skip("needs the 8-device virtual CPU mesh",
                allow_module_level=True)


def _mk(n_ens, n_slots=8, n_peers=3, mesh=False, **kw):
    engine = mesh_engine(8) if mesh else None
    return BatchedEnsembleService(WallRuntime(), n_ens, n_peers,
                                  n_slots, tick=None, engine=engine,
                                  **kw)


def _drive(svc, futs):
    while not all(f.done for f in futs):
        svc.flush()
    return [f.value for f in futs]


def _mixed_stream(svc, phase, rows):
    """One phase of the mixed workload on the given ensemble rows:
    puts, CAS (hit + miss), RMW, deletes (tombstones), gets."""
    futs = []
    for e in rows:
        futs.append(svc.kput(e, "a", b"A%d" % (phase + e)))
        futs.append(svc.kput(e, "b", b"B"))
        futs.append(svc.kput_once(e, "once", b"first"))
    _drive(svc, futs)
    vsns = _drive(svc, [svc.kget_vsn(e, "b") for e in rows])
    futs = [svc.kupdate(e, "b", vsn[2], b"B%d" % phase)
            for e, vsn in zip(rows, vsns)]          # CAS hit
    futs += [svc.kupdate(e, "b", (1, 1 << 30), b"never")
             for e in rows]                          # CAS miss
    futs += [svc.kmodify(e, "ctr", funref.RMW_ADD, 3 + phase)
             for e in rows]
    _drive(svc, futs)
    futs = [svc.kdelete(e, "a") for e in rows]
    futs += [svc.kget(e, "b") for e in rows]
    futs += [svc.kget(e, "a") for e in rows]
    return _drive(svc, futs)


def _assert_device_state_equal(a, b):
    for name, xa, xb in zip(a.state._fields, a.state, b.state):
        if xa is None or xb is None:    # no row plane at this shape
            assert xa is None and xb is None, name
            continue
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=f"state.{name}")
    np.testing.assert_array_equal(a.leader_np, b.leader_np)


def _assert_state_equal(a, b):
    """Device state plus the host read-path mirrors — for arms that
    served identical op streams from birth (the mirrors are lazy
    caches, so this is only meaningful for lockstep services)."""
    _assert_device_state_equal(a, b)
    np.testing.assert_array_equal(a._slot_vsn_np, b._slot_vsn_np)
    np.testing.assert_array_equal(a._inline_value_np,
                                  b._inline_value_np)
    np.testing.assert_array_equal(a._inline_value_ok,
                                  b._inline_value_ok)


def _wal_bytes(data_dir):
    out = {}
    for root, _dirs, files in os.walk(data_dir):
        for f in files:
            if f.startswith("wal"):
                with open(os.path.join(root, f), "rb") as fh:
                    out[f] = fh.read()
    return out


def test_shardwise_pack_selected():
    """An 'ens'-sharded mesh with the 'peer' axis whole packs per
    shard, inside its step program: the packed vector is one block a
    shard, sharded along 'ens' as the state is."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    svc = _mk(16, mesh=True)
    try:
        engine = svc.engine
        assert engine.pack_shards == mesh_ens_shards(engine) == 8
        assert svc._mesh_shards == 8
        assert mesh_engine(8, n_peer=2).pack_shards == 0
        k, z = 1, np.zeros((16,), np.int32)
        _st, flat = engine.full_step_slab(
            engine.init_state(16, 3, 8),
            svc._put(eng.pack_op_slab(16, k, z, z, z, (None,) * 5),
                     "slab"),
            svc._put(np.ones((16, 3), bool), "up"),
            want_vsn=True, gather=0)
        assert flat.shape == (8 * packed_nbytes(2, 3, k, True),)
        assert flat.sharding.is_equivalent_to(
            NamedSharding(engine.mesh, P("ens")), 1)
    finally:
        svc.stop()


def test_mesh_equals_oracle_mixed_stream():
    """Bit-identical results + state + mirrors + WAL bytes over a
    mixed put/CAS/RMW/tombstone stream (uncompacted full-width
    flushes: every row active)."""
    da = tempfile.mkdtemp(prefix="mesh_eq_a_")
    db = tempfile.mkdtemp(prefix="mesh_eq_b_")
    oracle = _mk(16, mesh=False, data_dir=da)
    meshed = _mk(16, mesh=True, data_dir=db)
    try:
        rows = range(16)
        for phase in range(2):
            ra = _mixed_stream(oracle, phase, rows)
            rb = _mixed_stream(meshed, phase, rows)
            assert ra == rb, f"phase {phase} results diverge"
        _assert_state_equal(oracle, meshed)
        wa, wb = _wal_bytes(da), _wal_bytes(db)
        assert wa and wa == wb, "WAL bytes diverge"
    finally:
        oracle.stop()
        meshed.stop()
        shutil.rmtree(da, ignore_errors=True)
        shutil.rmtree(db, ignore_errors=True)


def test_mesh_equals_oracle_compacted_flush():
    """Per-shard active-column compaction (E=128, a few hot rows →
    A_loc strictly below E/8) must stay bit-identical to the oracle,
    and must actually compact (payload below full width)."""
    oracle = _mk(128, mesh=False)
    meshed = _mk(128, mesh=True)
    try:
        rows = [0, 3, 17, 63, 64, 127]  # spans shards incl. empties
        ra = _mixed_stream(oracle, 0, rows)
        rb = _mixed_stream(meshed, 0, rows)
        assert ra == rb
        _assert_state_equal(oracle, meshed)
        assert meshed.payload_bytes < meshed.payload_bytes_full_width
        # the shard-wise path really took the per-shard branch
        assert meshed._occ_launches > 0
        assert meshed._occ_sum < meshed._occ_launches
    finally:
        oracle.stop()
        meshed.stop()


def test_mesh_warmup_zero_serve_compiles():
    """Satellite 1: warmup compiles the mesh step AND the shard-wise
    pack variants (per-shard (K, A) buckets included) so serving a
    mixed stream afterwards records ZERO serve-phase compile events
    (CompileWatch-asserted)."""
    svc = _mk(128, mesh=True)
    try:
        svc.warmup()
        assert svc._c_compile.labels("warmup").value > 0
        serve0 = svc._c_compile.labels("serve").value
        _mixed_stream(svc, 0, [0, 3, 17, 63, 127])  # compacted
        _mixed_stream(svc, 1, range(128))           # full width
        served = svc._c_compile.labels("serve").value - serve0
        events = [e for e in svc._compile_log
                  if e["phase"] == "serve"]
        assert served == 0, f"serve-phase compiles leaked: {events}"
    finally:
        svc.stop()


@pytest.mark.parametrize("direction", ["8to1", "1to8"])
def test_checkpoint_across_shard_counts(direction):
    """Satellite 2: a checkpoint taken under one device placement
    restores bit-equal under the other (mesh 8-shard ↔ single-shard),
    including the host mirrors and a post-restore serving round."""
    src_mesh = direction == "8to1"
    d = tempfile.mkdtemp(prefix="mesh_ckpt_")
    src = _mk(16, mesh=src_mesh, data_dir=d)
    dst = None
    try:
        _mixed_stream(src, 0, range(16))
        src.save()
        dst = BatchedEnsembleService.restore(
            WallRuntime(), d, tick=None,
            engine=mesh_engine(8) if not src_mesh else None)
        _assert_device_state_equal(src, dst)
        # The restored placement actually serves: reads return the
        # checkpointed data and writes commit.  (No cross-arm version
        # equality here — restore is lease-less by design, so the
        # restored side re-elects into a higher epoch than the
        # still-running source.)
        got = _drive(dst, [dst.kget(e, "b") for e in range(16)])
        assert got == [("ok", b"B0")] * 16
        put = _drive(dst, [dst.kput(e, "p1", b"post") for e in
                           range(16)])
        assert all(r[0] == "ok" for r in put)
    finally:
        src.stop()
        if dst is not None:
            dst.stop()
        shutil.rmtree(d, ignore_errors=True)


# -- the sliced mesh launch (ISSUE 33) ---------------------------------------

N_SH = 4
E_LOC = SLICE_MIN_E          # rows a shard holds: the least that slices
E_SL = N_SH * E_LOC

#: active columns of one flush, by what they do to the per-shard blocks
SLICED_CASES = {
    # every shard busy, unevenly: a_loc 8 is the busiest shard's bucket
    "spread": [1, 7, 40, E_LOC + 3, 2 * E_LOC, 2 * E_LOC + 9,
               3 * E_LOC + 200, E_SL - 1],
    # every column on ONE shard: three blocks all pad
    "one_shard": [2 * E_LOC + c for c in (0, 5, 6, 77, 255)],
    # shard 2 EMPTY (its block all pad), its neighbours not
    "empty_shard": [0, 3, E_LOC + 1, E_LOC + 2, 3 * E_LOC + 8],
    # 65 columns on shard 1: a_loc 128, and 128 * 4 > E_LOC rows, so
    # the mesh steps the full grid and only the pack gathers
    "too_wide": [5] + [E_LOC + 2 * c for c in range(65)],
}


def _sliced_arms():
    """(the mesh as it serves, the same mesh held to the full grid,
    one chip) at E_SL ensembles: three services fed the same ops."""
    def mk(engine):
        return BatchedEnsembleService(WallRuntime(), E_SL, 3, 8,
                                      tick=None, engine=engine)
    mesh, grid, one = (mk(mesh_engine(N_SH)), mk(mesh_engine(N_SH)),
                       mk(None))
    assert mesh._fns.sliced_slab is not None
    grid._fns = grid._fns._replace(sliced_slab=None)
    return mesh, grid, one


def _state_rows(svc, rows=slice(None)):
    """Every plane the state holds (no row plane at 128 slots: the
    field is None and no leaf)."""
    return [np.asarray(x)[rows] for x in jax.tree.leaves(svc.state)]


def _launches(svc, n0):
    return [(r["k"], r["uploads"], r["sliced"])
            for r in list(svc.lat_records)[n0:] if "uploads" in r]


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("case", list(SLICED_CASES))
def test_sliced_mesh_launch_matches_full_grid_and_one_chip(case, k):
    cols = SLICED_CASES[case]
    slices = case != "too_wide"
    arms = _sliced_arms()
    try:
        replies, before = [], None
        for svc in arms:
            _drive(svc, [svc.kput(0, "warm", b"w")])  # elects every row
            if svc is arms[0]:
                before = _state_rows(svc)
            n0 = len(svc.lat_records)
            # K ops deep on every column, ONE flush: writes, then a
            # read of the first through the same launch at K 4
            futs = [svc.kput(c, f"k{j}", b"v%d.%d" % (c, j))
                    for c in cols for j in range(max(k - 1, 1))]
            if k > 1:
                futs += [svc.kget(c, "k0") for c in cols]
            replies.append(_drive(svc, futs))
            got = _launches(svc, n0)
            # one upload whichever way: a pack-gather's index rows
            # ride in the slab as a sliced launch's do
            if svc is arms[0]:    # the mesh as it serves
                assert got == [(k, 1, int(slices))], got
            elif svc is arms[1]:  # held to the full grid: pack-gather
                assert got == [(k, 1, 0)], got
            else:                 # one chip slices all four cases
                assert got == [(k, 1, 1)], got
        assert replies[0] == replies[1] == replies[2]
        assert all(r[0] == "ok" for r in replies[0]), replies[0]
        mesh, grid, one = arms
        assert mesh.launches_sliced == int(slices)
        # every touched ensemble's rows: the three arms agree
        for name, a, b, c in zip(mesh.state._fields, _state_rows(mesh, cols),
                                 _state_rows(grid, cols),
                                 _state_rows(one, cols)):
            np.testing.assert_array_equal(a, b, err_msg=f"state.{name}")
            np.testing.assert_array_equal(a, c, err_msg=f"state.{name}")
        # ...and no other row of the sliced arm moved
        idle = np.setdiff1d(np.arange(E_SL), cols)
        for name, was, now in zip(mesh.state._fields, before,
                                  _state_rows(mesh)):
            np.testing.assert_array_equal(was[idle], now[idle],
                                          err_msg=f"state.{name}")
        for svc in (grid, one):
            np.testing.assert_array_equal(mesh.leader_np, svc.leader_np)
            np.testing.assert_array_equal(mesh._slot_vsn_np,
                                          svc._slot_vsn_np)
    finally:
        for svc in arms:
            svc.stop()


# -- a sliced launch renews every shard's leases (ISSUE 47) -------------------


def _leased_arms():
    """(the mesh as it serves, one chip) at E_SL ensembles on a
    VIRTUAL clock, so a lease lapses when the test says so."""
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.runtime import Runtime

    def mk(engine):
        rt = Runtime(seed=47)
        return rt, BatchedEnsembleService(rt, E_SL, 3, 8, tick=None,
                                          config=fast_test_config(),
                                          engine=engine)
    return mk(mesh_engine(N_SH)), mk(None)


@pytest.mark.parametrize("down", [None, "followers", "everyone",
                                  "leader-and-follower"])
def test_sliced_mesh_launch_renews_every_shards_leases(down):
    """One operation on ONE shard (the other three blocks all pad):
    each shard's block of the packed vector carries the epoch check of
    all of that shard's own rows, so the launch renews the lease of
    every ensemble on every shard and a read of an idle ensemble of
    ANOTHER shard is answered from the mirror.  Not where the device's
    check fails: an idle ensemble whose leader is down, or whose up
    members are short of a quorum, keeps its lapsed lease and its read
    takes the device round.  The mesh and one chip agree on every
    ensemble's lease."""
    busy, idle = 5, 2 * E_LOC + 7           # shards 0 and 2
    seen = []
    for rt, svc in _leased_arms():
        try:
            _drive(svc, [svc.kput(idle, "b", b"v")])
            lead = int(svc.leader_np[idle])
            others = [p for p in range(3) if p != lead]
            gone = {None: [], "followers": others,
                    "everyone": [lead] + others,
                    "leader-and-follower": [lead, others[0]]}[down]
            for p in gone:
                svc.set_peer_up(idle, p, False)
            rt.run_for(svc.config.lease() * 3)
            assert svc.stats()["lease_valid_fraction"] == 0.0
            idle0, n0 = svc.lease_renewals_idle, len(svc.lat_records)
            assert _drive(svc, [svc.kput(busy, "a", b"x")])[0][0] == "ok"
            # one sliced launch; the failed election of the third case
            # rides in it as an active column of its own shard
            assert [x[2] for x in _launches(svc, n0)] == [1]
            active = 1 + (down == "leader-and-follower")
            kept = down is not None
            assert svc.lease_renewals_idle - idle0 == E_SL - active - (
                kept and active == 1)
            assert svc.stats()["lease_valid_fraction"] == (
                E_SL - kept) / E_SL
            flushes = svc.flushes
            g = svc.kget(idle, "b")
            if kept:
                assert not g.done and svc.lease_until[idle] <= rt.now
                assert _drive(svc, [g]) != [("ok", b"v")]
                assert svc.flushes > flushes
            else:
                assert g.done and g.value == ("ok", b"v")
                assert svc.flushes == flushes
            seen.append((svc.lease_until > rt.now + svc._read_margin,
                         svc.lease_renewals_idle))
        finally:
            svc.stop()
    np.testing.assert_array_equal(seen[0][0], seen[1][0])
    assert seen[0][1] == seen[1][1]


def test_election_only_launch_on_a_mesh_that_slices():
    """K = 0 carries no op planes to compact: the mesh steps the full
    grid, as one chip does, and the re-elected row then serves through
    a sliced launch."""
    col = 2 * E_LOC + 5
    arms = _sliced_arms()
    try:
        out = []
        for svc in arms:
            _drive(svc, [svc.kput(col, "k", b"v")])
            svc.set_peer_up(col, int(svc.leader_np[col]), False)
            n0 = len(svc.lat_records)
            svc.flush()
            assert _launches(svc, n0) == [(0, 2, 0)]  # slab + ``up``
            assert svc.leader_np[col] >= 0
            n0 = len(svc.lat_records)
            out.append(_drive(svc, [svc.kput(col, "k", b"v2"),
                                    svc.kget_vsn(col, "k")]))
            assert [x[2] for x in _launches(svc, n0)] == [
                int(svc is not arms[1])] * len(_launches(svc, n0))
        assert out[0] == out[1] == out[2]
        for a, b, c in zip(*(_state_rows(svc, [col]) for svc in arms)):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
    finally:
        for svc in arms:
            svc.stop()


def test_warmup_covers_the_sliced_mesh_programs():
    """``warmup`` tests the launch's own rule per shard: a sliced flush
    of a warmed (K, a_loc) bucket compiles nothing."""
    svc = BatchedEnsembleService(WallRuntime(), E_SL + N_SH * 8, 3, 8,
                                 tick=None, max_ops_per_tick=2,
                                 engine=mesh_engine(N_SH))
    try:
        svc.warmup(buckets=[(1, 8), (1, None), (2, 8), (2, None)])
        assert "step_sliced" in [e["fn"] for e in svc._compile_log]
        serve0 = svc._c_compile.labels("serve").value
        _drive(svc, [svc.kput(0, "warm", b"w")])
        for i in range(2):
            _drive(svc, [svc.kput(c, f"k{i}", b"v")
                         for c in (2, 300, 301, E_SL)])
        assert svc.launches_sliced >= 2
        leaked = [e for e in svc._compile_log if e["phase"] == "serve"]
        assert svc._c_compile.labels("serve").value == serve0, leaked
    finally:
        svc.stop()


# -- a mesh builds its state where it lives (ISSUE 45) ------------------------


@pytest.mark.parametrize("devices,n_peer,shape,kw", [
    (8, 1, (16, 3, 128), {}),            # no row plane
    (4, 1, (8, 3, 4096), {}),            # one row level
    (4, 1, (4, 3, 131072), {}),          # two, five levels in all
    (4, 1, (8, 4, 64), {"views": [[0, 1, 2], [1, 2, 3]]}),
    (8, 2, (8, 4, 4096), {"n_views": 3,  # a sharded 'peer' axis
                          "views": [[0, 1], [2, 3], [0, 3]]}),
], ids=["flat", "rows1", "rows2", "views", "peer2-views"])
def test_mesh_init_state_is_the_placed_host_built_state(devices, n_peer,
                                                        shape, kw):
    """``ShardedEngine.init_state`` builds every shard's block on its
    own device (one program, no operand): plane by plane and sharding
    by sharding what ``shard_state`` makes of the state one device
    builds."""
    from riak_ensemble_tpu.ops import engine as eng

    engine = mesh_engine(devices, n_peer=n_peer)
    built = engine.init_state(*shape, **kw)
    placed = engine.shard_state(eng.init_state(*shape, **kw))
    rows = eng.tree_layout(shape[2]).row_levels
    assert (built.tree_rows is None) == (rows == 0)
    for name, a, b in zip(built._fields, built, placed):
        if b is None:
            assert a is None, name
            continue
        assert (a.shape, a.dtype) == (b.shape, b.dtype), name
        assert a.sharding == b.sharding, (name, a.sharding, b.sharding)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
        # a device holds its block of the ensembles and no more
        block = a.sharding.shard_shape(a.shape)
        assert block[0] == a.shape[0] * n_peer // devices, name
        assert {s.data.shape for s in a.addressable_shards} == {block}
    # one program a shape, met again on the next call
    assert engine.init_state(*shape, **kw).epoch.sharding == \
        built.epoch.sharding
    assert len(engine._init_programs) == 1


def test_mesh_service_says_what_each_device_holds():
    """``stats()["startup"]`` / ``["mesh"]`` of a mesh service: the
    state's bytes device by device (equal blocks along 'ens'), the
    ensembles a shard holds; no allocator peak on a CPU."""
    svc = BatchedEnsembleService(WallRuntime(), 8, 3, 4096, tick=None,
                                 engine=mesh_engine(4))
    try:
        st = svc.stats()
        held = st["startup"]["state_bytes_per_device"]
        total = sum(x.nbytes for x in jax.tree.leaves(svc.state))
        assert held == [total // 4] * 4
        assert st["startup"]["state_init_s"] > 0.0
        assert "device_peak_bytes" not in st["startup"]
        assert st["mesh"]["e_loc"] == 2
        assert st["mesh"]["state_bytes_per_shard"] == total // 4
    finally:
        svc.stop()


H5_ENS, H5_SLOTS, H5_KEYS = 4, 131072, 20


def _h5_stream(svc):
    """A seeded stream over five-level trees through the normal queue
    and flush (``tests/test_h5_ring.py``'s, every ensemble at once so
    that a flush spans the shards): loads, versioned reads from device
    rounds, overwrites, deletes, leased reads.  Returns every reply,
    the plain model (a dict ordered by the versions the service
    acknowledged), the keys and the slots they were given."""
    from test_h5_ring import distant_slots, settle
    from riak_ensemble_tpu.parallel.batched_host import _FreeSlots
    from riak_ensemble_tpu.types import NOTFOUND

    rng = np.random.default_rng([45, H5_SLOTS])
    ens = range(H5_ENS)
    slots = [distant_slots(rng, H5_SLOTS, H5_KEYS) for _ in ens]
    for e in ens:
        svc.free_slots[e] = _FreeSlots(0, slots[e][::-1])
    keys = [[f"user{e}.{i}" for i in range(H5_KEYS)] for e in ens]
    model, replies = {}, []

    def put(ks, tag):
        vals = [[f"{tag}.{e}.{k}".encode() * 4 for k in ks[e]]
                for e in ens]
        got = settle(svc, [svc.kput_many(e, ks[e], vals[e]) for e in ens])
        replies.append(got)
        for e in ens:
            for k, v, r in zip(ks[e], vals[e], got[e]):
                assert r[0] == "ok", (e, k, r)
                old = model.get((e, k))
                assert old is None or tuple(r[1]) > old[1]
                model[(e, k)] = (v, tuple(r[1]))

    def read(ks):
        got = settle(svc, [svc.kget_many(e, ks[e], want_vsn=True)
                           for e in ens])
        replies.append(got)
        for e in ens:
            for k, r in zip(ks[e], got[e]):
                want = model.get((e, k))
                if want is None or want[0] is NOTFOUND:
                    assert r[:2] == ("ok", NOTFOUND), (e, k, r)
                else:
                    assert r == ("ok", want[0], want[1]), (e, k, r)

    put(keys, "load")
    assert all([svc.key_slot[e][k] for k in keys[e]] == slots[e]
               for e in ens)
    svc.set_fast_reads(False)       # every read a device round
    read(keys)
    order = [[keys[e][i] for i in rng.permutation(H5_KEYS)] for e in ens]
    third = H5_KEYS // 3
    put([o[:third] for o in order], "again")
    gone = [o[third:2 * third] for o in order]
    got = settle(svc, [svc.kdelete_many(e, gone[e]) for e in ens])
    replies.append(got)
    for e in ens:
        for k, r in zip(gone[e], got[e]):
            assert r[0] == "ok", (e, k, r)
            model[(e, k)] = (NOTFOUND, None)
    read([ks + ["never.written"] for ks in keys])
    svc.set_fast_reads(True)
    live = [[k for k in keys[e] if model[(e, k)][0] is not NOTFOUND][:2]
            for e in ens]
    put(live, "leased")
    hits = svc.read_fastpath_hits
    got = settle(svc, [svc.kget(e, k) for e in ens for k in live[e]])
    replies.append(got)
    assert got == [("ok", model[(e, k)][0]) for e in ens for k in live[e]]
    assert svc.read_fastpath_hits == hits + 2 * H5_ENS
    assert svc.stats()["corruptions_detected"] == 0
    return replies, model, keys, slots


@pytest.fixture(scope="module")
def h5_arms():
    """The same seeded stream served by ``mesh_engine(4)`` (an
    ensemble a shard, every flush the full-grid mesh step over row
    planes under ``shard_map``) and by one device, at 4 x 3 x 131,072:
    ``ring256-n3-h5-mesh4``'s height and shard count."""
    from test_h5_ring import WALL_CONFIG

    arms = {}
    for name, engine in (("mesh", mesh_engine(4)), ("one", None)):
        svc = BatchedEnsembleService(WallRuntime(), H5_ENS, 3, H5_SLOTS,
                                     tick=None, config=WALL_CONFIG,
                                     engine=engine)
        arms[name] = (svc, *_h5_stream(svc))
    yield arms
    for svc, *_ in arms.values():
        svc.stop()


def test_mesh_served_path_over_five_levels_is_the_plain_models(h5_arms):
    """Every reply was the dict's (asserted as the stream ran), every
    launch was a full-grid mesh launch (a shard of one ensemble never
    slices), and the device holds what the model holds."""
    from riak_ensemble_tpu.ops import engine as eng
    from riak_ensemble_tpu.types import NOTFOUND

    svc, replies, model, keys, slots = h5_arms["mesh"]
    assert len(eng.tree_sizes(H5_SLOTS)) == 5
    m = svc.stats()["mesh"]
    assert (m["shards"], m["e_loc"]) == (4, 1)
    assert m["launches_sliced"] == 0
    assert m["launches_pack_gathered"] + m["launches_full_grid"] > 0
    assert svc.state.tree_rows.sharding == \
        svc.engine.init_state(H5_ENS, 3, H5_SLOTS).tree_rows.sharding
    seq = np.asarray(svc.state.obj_seq)
    for e in range(H5_ENS):
        for k, s in zip(keys[e], slots[e]):
            value, vsn = model[(e, k)]
            if value is NOTFOUND:
                assert k not in svc.key_slot[e]
            else:       # the acknowledged version, on every replica
                assert (seq[e, :, s] == vsn[1]).all(), (e, k)


def test_mesh_trees_over_five_levels_are_the_plain_build(h5_arms):
    """Every replica's leaves are the hashes of its own objects and
    its interior nodes, rows and tail, the plain bottom-up build over
    them, on every shard."""
    import jax.numpy as jnp
    from test_h5_ring import plain_uppers
    from riak_ensemble_tpu.ops import engine as eng
    from riak_ensemble_tpu.ops import hash as hashk

    svc = h5_arms["mesh"][0]
    st = svc.state
    leaf = np.asarray(st.tree_leaf)
    assert np.array_equal(leaf, np.asarray(hashk.obj_leaf_hash(
        st.obj_epoch, st.obj_seq, st.obj_val)))
    node = np.asarray(jnp.concatenate(
        eng.rows_to_levels(st.tree_rows, st.tree_node, H5_SLOTS),
        axis=-2))
    for e in range(H5_ENS):
        for m in range(3):
            assert np.array_equal(node[e, m], plain_uppers(leaf[e, m]))
    node_bad, leaf_bad = svc.engine.verify_trees(st)
    assert not np.asarray(node_bad).any()
    assert not np.asarray(leaf_bad).any()


def test_mesh_and_one_device_agree_over_five_levels(h5_arms):
    """The same replies and the same state, planes and host mirrors,
    for the same seeded stream."""
    (mesh, r_mesh, *_), (one, r_one, *_) = h5_arms["mesh"], h5_arms["one"]
    assert r_mesh == r_one
    _assert_state_equal(one, mesh)
