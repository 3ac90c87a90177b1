"""Single-shard ↔ mesh serving equivalence (the shard-wise pack path).

The mesh engine (8 virtual CPU devices, 'ens'-sharded, peer axis
unsharded) must be BIT-IDENTICAL to the single-shard oracle over mixed
put/CAS/RMW/tombstone streams — results, device state, host mirror
slabs, and WAL bytes — including compacted (per-shard active-column
bucketing) flushes.  Plus the mesh serving-path
contracts: warmup covers the mesh step/pack variants (CompileWatch
asserts zero serve-phase compiles), and checkpoints round-trip across
shard counts (8→1 and 1→8) bit-equal.

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
    BatchedEnsembleService, WallRuntime, mesh_ens_shards,
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
