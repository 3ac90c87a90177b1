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
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    SLICE_MIN_E, BatchedEnsembleService, WallRuntime, mesh_ens_shards,
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
    svc = _mk(16, mesh=True)
    try:
        assert mesh_ens_shards(svc.engine) == 8
        assert svc._mesh_shards == 8
        assert getattr(svc._pack, "fn", svc._pack)
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
            if svc is arms[0]:    # the mesh as it serves
                assert got == [(k, 1, 1) if slices else (k, 2, 0)], got
            elif svc is arms[1]:  # held to the full grid: pack-gather
                assert got == [(k, 2, 0)], got
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
