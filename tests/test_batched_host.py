"""Host↔engine bridge: engine-backed ensembles behind the service API,
with host-side failure detection driving batched elections.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402


def make_service(n_ens=64, n_peers=5, n_slots=16):
    runtime = Runtime(seed=50)
    svc = BatchedEnsembleService(runtime, n_ens, n_peers, n_slots,
                                 tick=0.005, config=fast_test_config())
    return runtime, svc


def settle(runtime, fut, timeout=5.0):
    return runtime.await_future(fut, timeout)


def test_put_get_roundtrip_across_ensembles():
    runtime, svc = make_service()
    futs = [(e, svc.kput(e, "k", f"v{e}".encode()))
            for e in range(svc.n_ens)]
    for e, fut in futs:
        r = settle(runtime, fut)
        assert r[0] == "ok", (e, r)
    for e in range(svc.n_ens):
        r = settle(runtime, svc.kget(e, "k"))
        assert r == ("ok", f"v{e}".encode())
    # unknown key
    assert settle(runtime, svc.kget(0, "nope")) == ("ok", NOTFOUND)
    assert svc.flushes >= 1


def test_delete_recycles_slot():
    runtime, svc = make_service(n_ens=1, n_slots=2)
    assert settle(runtime, svc.kput(0, "a", b"1"))[0] == "ok"
    assert settle(runtime, svc.kput(0, "b", b"2"))[0] == "ok"
    # full: next new key fails
    assert settle(runtime, svc.kput(0, "c", b"3")) == "failed"
    assert settle(runtime, svc.kdelete(0, "a"))[0] == "ok"
    assert settle(runtime, svc.kput(0, "c", b"3"))[0] == "ok"
    assert settle(runtime, svc.kget(0, "a")) == ("ok", NOTFOUND)
    assert settle(runtime, svc.kget(0, "c")) == ("ok", b"3")


def test_leader_failure_reelection():
    runtime, svc = make_service(n_ens=8)
    for e in range(8):
        assert settle(runtime, svc.kput(e, "k", b"v"))[0] == "ok"
    leaders = np.asarray(svc.state.leader).copy()
    assert (leaders >= 0).all()

    # kill every leader replica (host failure detector)
    for e in range(8):
        svc.set_peer_up(e, int(leaders[e]), False)
    # expire leases so reads can't ride the old lease
    svc.lease_until[:] = 0.0
    runtime.run_for(0.1)  # a few ticks: elections fold into flushes

    new_leaders = np.asarray(svc.state.leader)
    assert (new_leaders != leaders).all(), "no re-election"
    for e in range(8):
        r = settle(runtime, svc.kget(e, "k"))
        assert r == ("ok", b"v"), (e, r)
    # writes work under the new leaders too
    for e in range(8):
        assert settle(runtime, svc.kput(e, "k", b"v2"))[0] == "ok"


def test_no_quorum_no_service():
    runtime, svc = make_service(n_ens=4, n_peers=5)
    for e in range(4):
        assert settle(runtime, svc.kput(e, "k", b"v"))[0] == "ok"
    # majority down
    for e in range(4):
        for p in (0, 1, 2):
            svc.set_peer_up(e, p, False)
    svc.lease_until[:] = 0.0
    for e in range(4):
        assert settle(runtime, svc.kput(e, "k", b"x")) == "failed"
        assert settle(runtime, svc.kget(e, "k")) == "failed"
    # heal: service resumes (election re-folds in)
    for e in range(4):
        for p in (0, 1, 2):
            svc.set_peer_up(e, p, True)
    runtime.run_for(0.1)
    for e in range(4):
        r = settle(runtime, svc.kget(e, "k"))
        assert r == ("ok", b"v"), (e, r)


def test_batching_amortizes_flushes():
    runtime, svc = make_service(n_ens=32)
    futs = []
    for e in range(32):
        for i in range(8):
            futs.append(svc.kput(e, f"k{i}", b"x"))
    runtime.run_for(0.2)
    assert all(f.done and f.value[0] == "ok" for f in futs)
    # 8 ops per ensemble served in ~= 8/k flush rounds, not 256 calls
    assert svc.flushes < 50
    assert svc.ops_served == 256


def test_read_only_load_keeps_lease_renewed():
    """A leader serving only reads renews its lease via the epoch-check
    quorum (leader_tick renewal, peer.erl:1092-1095) — read-only load
    must not fall off the lease fast path."""
    runtime, svc = make_service(n_ens=4)
    for e in range(4):
        assert settle(runtime, svc.kput(e, "k", b"v"))[0] == "ok"
    lease0 = svc.lease_until.copy()
    # Read-only traffic past the original lease horizon.
    deadline = float(lease0.max()) + 3 * svc.config.lease()
    while runtime.now < deadline:
        for e in range(4):
            assert settle(runtime, svc.kget(e, "k")) == ("ok", b"v")
        runtime.run_for(svc.config.lease() / 4)
    assert (svc.lease_until > lease0).all(), "reads did not renew leases"


def test_service_heals_device_corruption():
    """Corruption injected into a replica's store is detected by the
    engine's integrity gate, served around, and healed by the service's
    exchange flow (tree_corrupted -> repair -> exchange)."""
    runtime, svc = make_service(n_ens=4)
    futs = {}
    for e in range(4):
        assert settle(runtime, svc.kput(e, "k", b"v"))[0] == "ok"
        futs[e] = settle(runtime, svc.kput(e, "j", b"w"))
    # Damage peer 2's object for "k" on every ensemble, out-of-band.
    slot_k = [svc.key_slot[e]["k"] for e in range(4)]
    ov = svc.state.obj_val
    for e in range(4):
        ov = ov.at[e, 2, slot_k[e]].set(424242)
    svc.state = svc.state._replace(obj_val=ov)
    # Out-of-band device damage is only visible to a DEVICE round (a
    # leased fast read serves the host committed mirror — like cold
    # slots, damage waits for the next device access or scrub): expire
    # the leases before each read so every one takes the round and
    # trips the gate (a flush's quorum round re-leases every column).
    # Reads still serve the committed value; repair kicks in.
    for e in range(4):
        svc.lease_until[:] = 0.0
        assert settle(runtime, svc.kget(e, "k")) == ("ok", b"v")
    assert svc.corruptions > 0   # detected on device, surfaced to host
    from riak_ensemble_tpu.ops import engine as eng
    node_bad, leaf_bad = eng.verify_trees(svc.state)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())


def test_service_composes_with_sharded_engine():
    """The same service runs over a ShardedEngine on the virtual
    8-device mesh (the scale-out path, review round-1 item 3)."""
    from riak_ensemble_tpu.parallel.mesh import ShardedEngine, make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    from riak_ensemble_tpu.runtime import Runtime
    runtime = Runtime(seed=51)
    se = ShardedEngine(make_mesh(4, 2))
    svc = BatchedEnsembleService(runtime, n_ens=8, n_peers=4, n_slots=16,
                                 tick=0.005, config=fast_test_config(),
                                 engine=se)
    for e in range(8):
        assert settle(runtime, svc.kput(e, "k", f"v{e}".encode()))[0] == "ok"
    for e in range(8):
        assert settle(runtime, svc.kget(e, "k")) == ("ok", f"v{e}".encode())
    # Failover on the mesh: kill the leaders, service re-elects.
    leaders = np.asarray(svc.state.leader).copy()
    for e in range(8):
        svc.set_peer_up(e, int(leaders[e]), False)
    svc.lease_until[:] = 0.0
    runtime.run_for(0.1)
    assert (np.asarray(svc.state.leader) != leaders).all()
    for e in range(8):
        assert settle(runtime, svc.kget(e, "k")) == ("ok", f"v{e}".encode())


def test_delete_then_put_same_flush_keeps_put():
    """A delete and a later put for the same key riding one flush:
    the delete's deferred slot recycle must NOT free the slot the put
    re-wrote, or the committed put becomes unreachable (found by the
    service linearizability sweep, seed 702)."""
    runtime, svc = make_service(n_ens=1, n_slots=4)
    assert settle(runtime, svc.kput(0, "k", b"v1"))[0] == "ok"
    fd = svc.kdelete(0, "k")
    fp = svc.kput(0, "k", b"v2")
    assert settle(runtime, fd)[0] == "ok"
    assert settle(runtime, fp)[0] == "ok"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v2")
    # and a lone delete still recycles its slot
    assert settle(runtime, svc.kdelete(0, "k"))[0] == "ok"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", NOTFOUND)
    assert len(svc.free_slots[0]) == 4


def test_committed_overwrites_release_payloads():
    """The host payload store must not grow per committed overwrite or
    delete — superseded handles are released when the new write
    commits."""
    runtime, svc = make_service(n_ens=1, n_slots=4)
    for i in range(20):
        assert settle(runtime, svc.kput(0, "k", b"v%d" % i))[0] == "ok"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v19")
    assert len(svc.values) <= 2, len(svc.values)
    assert settle(runtime, svc.kdelete(0, "k"))[0] == "ok"
    for i in range(10):
        assert settle(runtime, svc.kput(0, "x", b"x%d" % i))[0] == "ok"
    assert len(svc.values) <= 2, len(svc.values)


def test_handles_recycled_not_monotonic():
    """Released payload handles return to a pool (device handles are
    int32 and 0 is the tombstone; a wrapping counter would eventually
    alias live handles)."""
    runtime, svc = make_service(n_ens=1, n_slots=2)
    for i in range(30):
        assert settle(runtime, svc.kput(0, "k", b"v%d" % i))[0] == "ok"
    # 30 committed overwrites but only ~1 live payload: the handle
    # counter must not have advanced 30 times.
    assert svc._next_handle <= 4, svc._next_handle
    assert len(svc.values) <= 2


def test_service_update_members_end_to_end():
    """Membership change through the serving path: shrink 5 -> 3
    members (dropping the current leader), data survives, the next
    flush elects a new leader from the surviving membership, and ops
    keep flowing; then grow back to 5."""
    runtime, svc = make_service(n_ens=8, n_peers=5, n_slots=8)
    for e in range(8):
        assert settle(runtime, svc.kput(e, "k", b"v-%d" % e))[0] == "ok"

    leader0 = svc.leader_np.copy()
    assert (leader0 >= 0).all()
    # Drop the leader's peer from every ensemble's membership.
    new_view = np.ones((8, 5), bool)
    new_view[np.arange(8), leader0] = False
    changed = svc.update_members(np.ones(8, bool), new_view)
    assert changed.all(), changed
    assert (svc.member_np == new_view).all()
    # Old leaders were transitioned out -> elections pending.
    assert (svc.leader_np == -1).all()

    for e in range(8):
        r = settle(runtime, svc.kget(e, "k"))
        assert r == ("ok", b"v-%d" % e), (e, r)
    assert (svc.leader_np >= 0).all()
    assert np.take_along_axis(new_view, svc.leader_np[:, None],
                              1).all(), "leader outside new membership"

    # Grow back to the full membership and write through it.
    changed = svc.update_members(np.ones(8, bool), np.ones((8, 5), bool))
    assert changed.all()
    for e in range(8):
        assert settle(runtime, svc.kput(e, "k", b"w-%d" % e))[0] == "ok"
        assert settle(runtime, svc.kget(e, "k")) == ("ok", b"w-%d" % e)


def test_service_update_members_sharded_engine():
    """The same membership change composes with ShardedEngine on the
    virtual mesh."""
    import jax
    from riak_ensemble_tpu.parallel.mesh import ShardedEngine, make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    runtime = Runtime(seed=61)
    se = ShardedEngine(make_mesh(4, 2))
    svc = BatchedEnsembleService(runtime, 8, 8, n_slots=8, tick=0.005,
                                 config=fast_test_config(), engine=se)
    for e in range(8):
        assert settle(runtime, svc.kput(e, "k", b"x%d" % e))[0] == "ok"
    new_view = np.ones((8, 8), bool)
    new_view[:, 7] = False
    changed = svc.update_members(np.ones(8, bool), new_view)
    assert changed.all()
    for e in range(8):
        assert settle(runtime, svc.kget(e, "k")) == ("ok", b"x%d" % e)


def test_service_skewed_queues():
    """Heavily skewed load: one ensemble with a deep queue, the rest
    idle or light — padding rounds must not corrupt idle ensembles'
    state and every queued op resolves correctly."""
    runtime, svc = make_service(n_ens=16, n_peers=3, n_slots=8)
    futs = []
    for i in range(40):  # deep queue on ensemble 0 (> max_ops_per_tick)
        futs.append((b"d%d" % i, svc.kput(0, "hot", b"d%d" % i)))
    light = [(e, svc.kput(e, "cold", b"c%d" % e)) for e in (3, 9)]
    for _v, f in futs:
        assert settle(runtime, f)[0] == "ok"
    for e, f in light:
        assert settle(runtime, f)[0] == "ok"
    assert settle(runtime, svc.kget(0, "hot")) == ("ok", b"d39")
    for e in (3, 9):
        assert settle(runtime, svc.kget(e, "cold")) == ("ok", b"c%d" % e)
    for e in (1, 2, 15):
        assert settle(runtime, svc.kget(e, "hot")) == ("ok", NOTFOUND)


def test_service_flush_depth_buckets_to_pow2():
    """Distinct [K, E] shapes each cost an XLA compile; flush must
    bucket the batch depth to powers of two so skewed/varying queue
    lengths don't trigger compile churn (one program per depth)."""
    from riak_ensemble_tpu.ops.engine import split_op_slab
    from riak_ensemble_tpu.parallel.batched_host import _LocalEngine
    from riak_ensemble_tpu.testing import wrap_engine_steps

    seen = []

    def record(inner, state, slab, up, sliced):
        indexed = sliced or bool(inner.keywords.get("gather"))
        seen.append(int(split_op_slab(slab, indexed)[3].shape[0]))
        return inner(state, slab, up)

    runtime = Runtime(seed=50)
    svc = BatchedEnsembleService(
        runtime, 8, 3, 16, tick=None, config=fast_test_config(),
        engine=wrap_engine_steps(_LocalEngine(), record))
    for depth in (1, 2, 3, 5, 7, 11, 13):
        futs = [svc.kput(0, f"k{i}", b"v") for i in range(depth)]
        while any(svc.queues):
            svc.flush()
        for f in futs:
            assert f.done and f.value[0] == "ok"
    assert seen, "no launches recorded"
    assert all(k & (k - 1) == 0 for k in seen), seen  # powers of two
    # 7 distinct raw depths collapse into at most 5 compiled shapes
    assert len(set(seen)) <= 5, seen


def test_service_update_members_blocked_collapse_lands_later():
    """Install commits under the old view while the NEW view lacks
    quorum, so the collapse blocks; after healing, a later call (pure
    retry, all-False sel) must land the leftover collapse and promote
    the host membership mirror (the joint view is collapsed by the
    FIRST launch's transition half — its outcome must not be lost)."""
    runtime, svc = make_service(n_ens=1, n_peers=5, n_slots=4)
    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"
    leader = int(svc.leader_np[0])
    assert leader == 0  # lowest-index candidate wins

    svc.set_peer_up(0, 1, False)
    svc.set_peer_up(0, 2, False)
    nv = np.zeros((1, 5), bool)
    nv[0, :3] = True  # {0,1,2}: only 1/3 up -> collapse must block
    changed = svc.update_members(np.ones(1, bool), nv)
    assert not changed.any()
    assert svc._pending_mask[0]
    assert svc.member_np[0].all()  # mirror keeps the old view

    svc.set_peer_up(0, 1, True)
    svc.set_peer_up(0, 2, True)
    changed = svc.update_members(np.zeros(1, bool), nv)
    assert changed.all(), changed
    assert (svc.member_np[0] == nv[0]).all()
    assert not svc._pending_mask[0]
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v")


def test_service_update_members_blocked_install_retries():
    """A request made while no commit quorum exists (leader down, no
    election yet) cannot install; it stays desired and a later pure
    retry lands it after the next flush re-elects."""
    runtime, svc = make_service(n_ens=1, n_peers=5, n_slots=4)
    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"
    svc.set_peer_up(0, int(svc.leader_np[0]), False)

    nv = np.zeros((1, 5), bool)
    nv[0, 1:4] = True
    changed = svc.update_members(np.ones(1, bool), nv)
    assert not changed.any()
    assert svc._desired_mask[0] and not svc._pending_mask[0]

    # A flush folds in the re-election; the retry then completes.
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v")
    changed = svc.update_members(np.zeros(1, bool), nv)
    assert changed.all(), changed
    assert (svc.member_np[0] == nv[0]).all()


def test_service_save_restore_roundtrip(tmp_path):
    """Full service checkpoint: device state via orbax + host mirrors
    via the CRC blob; a restored service serves the same data, holds
    no pre-crash lease, and keeps its membership pipeline."""
    runtime, svc = make_service(n_ens=4, n_peers=5, n_slots=4)
    for e in range(4):
        assert settle(runtime, svc.kput(e, "k", b"v%d" % e))[0] == "ok"
    assert settle(runtime, svc.kdelete(3, "k"))[0] == "ok"
    nv = np.ones((4, 5), bool)
    nv[:, 4] = False
    assert svc.update_members(np.ones(4, bool), nv).all()
    svc.save(str(tmp_path / "ckpt"))
    svc.stop()

    rt2 = Runtime(seed=99)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "ckpt"), tick=0.005,
        config=fast_test_config())
    assert (svc2.lease_until == 0).all()  # never trust pre-crash leases
    assert (svc2.member_np == nv).all()
    for e in range(3):
        assert settle(rt2, svc2.kget(e, "k")) == ("ok", b"v%d" % e)
    assert settle(rt2, svc2.kget(3, "k")) == ("ok", NOTFOUND)
    # and the restored service keeps serving writes
    assert settle(rt2, svc2.kput(0, "k", b"post"))[0] == "ok"
    assert settle(rt2, svc2.kget(0, "k")) == ("ok", b"post")


def test_service_update_members_queued_request_not_dropped():
    """A request targeting an ensemble whose earlier change is still
    joint is queued (not silently dropped): once the first change
    collapses, a retry proposes and lands the queued view."""
    runtime, svc = make_service(n_ens=1, n_peers=5, n_slots=4)
    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"

    svc.set_peer_up(0, 1, False)
    svc.set_peer_up(0, 2, False)
    view_a = np.zeros((1, 5), bool)
    view_a[0, :3] = True          # collapse blocks: 1/3 up
    assert not svc.update_members(np.ones(1, bool), view_a).any()
    assert svc._pending_mask[0]

    view_b = np.zeros((1, 5), bool)
    view_b[0, [0, 3, 4]] = True   # new request while A is joint
    changed = svc.update_members(np.ones(1, bool), view_b)
    # A still cannot collapse (quorum still missing) and B must wait.
    assert not changed.any()
    assert svc._queued_mask[0]

    svc.set_peer_up(0, 1, True)
    svc.set_peer_up(0, 2, True)
    # Retry 1: A collapses, B advances to desired.
    changed = svc.update_members(np.zeros(1, bool), view_a)
    assert changed.all()
    assert (svc.member_np[0] == view_a[0]).all()
    # Retry 2: B proposes + lands.
    changed = svc.update_members(np.zeros(1, bool), view_a)
    assert changed.all()
    assert (svc.member_np[0] == view_b[0]).all()
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v")


def test_service_save_versioned_and_queued_ops_flushed(tmp_path):
    """Repeated saves keep a restorable checkpoint at every point
    (CURRENT pointer flips only after the new pair is complete), and
    queued-but-unflushed ops are resolved before snapshotting so no
    slot/handle side effects leak into the image."""
    runtime, svc = make_service(n_ens=1, n_peers=3, n_slots=2)
    assert settle(runtime, svc.kput(0, "a", b"1"))[0] == "ok"
    svc.save(str(tmp_path / "c"))
    # enqueue WITHOUT settling: save must flush it, not leak it
    fut = svc.kput(0, "b", b"2")
    svc.save(str(tmp_path / "c"))
    assert fut.done and fut.value[0] == "ok"
    svc.stop()

    import os
    names = sorted(os.listdir(tmp_path / "c"))
    assert "CURRENT" in names
    assert sum(n.startswith("ckpt.") for n in names) == 1  # old pruned

    rt2 = Runtime(seed=7)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "c"), tick=0.005, config=fast_test_config())
    assert settle(rt2, svc2.kget(0, "a")) == ("ok", b"1")
    assert settle(rt2, svc2.kget(0, "b")) == ("ok", b"2")
    # no leaked slots/handles: both keys live, store consistent
    assert len(svc2.values) == 2
    assert len(svc2.free_slots[0]) == 0
    assert settle(rt2, svc2.kdelete(0, "a"))[0] == "ok"
    assert settle(rt2, svc2.kput(0, "c", b"3"))[0] == "ok"


def test_service_cas_chain():
    """kupdate/ksafe_delete through the serving path: CAS on the vsn
    from kput/kget_vsn; stale CAS fails without touching data;
    tombstone vsn rides kget_vsn so delete-then-guard chains work."""
    runtime, svc = make_service(n_ens=2, n_peers=5, n_slots=4)
    r = settle(runtime, svc.kput(0, "k", b"v1"))
    assert r[0] == "ok"
    vsn1 = r[1]

    r = settle(runtime, svc.kupdate(0, "k", vsn1, b"v2"))
    assert r[0] == "ok"
    vsn2 = r[1]
    assert vsn2 != vsn1
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v2")

    # stale CAS: fails, value untouched, payload store clean
    assert settle(runtime, svc.kupdate(0, "k", vsn1, b"v3")) == "failed"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v2")

    # kget_vsn returns the same vsn a CAS needs
    r = settle(runtime, svc.kget_vsn(0, "k"))
    assert r == ("ok", b"v2", vsn2), r

    # version-guarded delete, then stale-guard delete fails
    assert settle(runtime, svc.ksafe_delete(0, "k", vsn1)) == "failed"
    r = settle(runtime, svc.ksafe_delete(0, "k", vsn2))
    assert r[0] == "ok"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", NOTFOUND)

    # create-if-missing: CAS against (0, 0) is kput_once
    r = settle(runtime, svc.kupdate(1, "fresh", (0, 0), b"first"))
    assert r[0] == "ok"
    assert settle(runtime, svc.kupdate(1, "fresh", (0, 0),
                                       b"second")) == "failed"
    assert settle(runtime, svc.kget(1, "fresh")) == ("ok", b"first")


def test_service_cas_failed_releases_payload():
    runtime, svc = make_service(n_ens=1, n_peers=3, n_slots=2)
    r = settle(runtime, svc.kput(0, "k", b"a"))
    vsn = r[1]
    assert settle(runtime, svc.kupdate(0, "k", (9, 9), b"b")) == "failed"
    assert settle(runtime, svc.kupdate(0, "k", vsn, b"c"))[0] == "ok"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"c")
    assert len(svc.values) == 1  # failed/superseded payloads released


def test_service_create_if_missing_on_recycled_slot():
    """A recycled slot keeps the previous key's tombstone on device;
    create-if-missing for a NEW key mapped onto it must still succeed
    (the engine's (0,0) matches tombstones, like do_kput_once)."""
    runtime, svc = make_service(n_ens=1, n_peers=3, n_slots=1)
    assert settle(runtime, svc.kput(0, "old", b"x"))[0] == "ok"
    assert settle(runtime, svc.kdelete(0, "old"))[0] == "ok"
    assert len(svc.free_slots[0]) == 1  # slot recycled, tombstone stays
    r = settle(runtime, svc.kupdate(0, "new", (0, 0), b"y"))
    assert r[0] == "ok", r
    assert settle(runtime, svc.kget(0, "new")) == ("ok", b"y")


def test_service_stats_and_trace():
    from riak_ensemble_tpu.utils.trace import Tracer

    runtime, svc = make_service(n_ens=2, n_peers=3, n_slots=4)
    tracer = Tracer(runtime).install()
    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"
    assert settle(runtime, svc.kget(1, "k")) == ("ok", NOTFOUND)
    st = svc.stats()
    assert st["flushes"] >= 1 and st["ops_served"] >= 1
    assert st["ensembles_with_leader"] == 2
    assert st["live_payloads"] == 1
    assert tracer.counters.get("svc_launch", 0) >= 1
    tracer.uninstall()


def test_service_execute_with_cas_planes():
    """Bulk array API: CAS planes flow through execute()."""
    runtime, svc = make_service(n_ens=4, n_peers=3, n_slots=8)
    from riak_ensemble_tpu.ops import engine as eng2

    kind = np.full((1, 4), eng2.OP_PUT, np.int32)
    slot = np.zeros((1, 4), np.int32)
    val = np.full((1, 4), 10, np.int32)
    committed, *_ = svc.execute(kind, slot, val)
    assert committed.all()
    # CAS expecting (epoch=1, seq=1) after the first commit
    kind[:] = eng2.OP_CAS
    val[:] = 20
    xe = np.ones((1, 4), np.int32)
    xs = np.ones((1, 4), np.int32)
    committed, *_ = svc.execute(kind, slot, val, exp_epoch=xe, exp_seq=xs)
    assert committed.all()
    # stale now
    committed, *_ = svc.execute(kind, slot, val, exp_epoch=xe, exp_seq=xs)
    assert not committed.any()
    kind[:] = eng2.OP_GET
    _, get_ok, found, value = svc.execute(kind, slot, np.zeros_like(val))
    assert get_ok.all() and found.all() and (value == 20).all()


def test_service_on_netruntime_asyncio():
    """The engine-backed service runs on the real-time asyncio runtime
    (NetRuntime) with wall-clock flush ticks — the single-host
    production composition of the DCN/host half and the device
    engine."""
    import asyncio

    from riak_ensemble_tpu.netruntime import NetRuntime

    async def scenario():
        runtime = NetRuntime("node0", {"node0": ("127.0.0.1", 0)})
        runtime.loop = asyncio.get_running_loop()
        svc = BatchedEnsembleService(runtime, 4, 3, n_slots=4,
                                     tick=0.01,
                                     config=fast_test_config())
        r = await runtime.await_future(svc.kput(0, "k", b"v"), 10.0)
        assert r[0] == "ok"
        vsn = r[1]
        r = await runtime.await_future(svc.kget(0, "k"), 10.0)
        assert r == ("ok", b"v")
        r = await runtime.await_future(
            svc.kupdate(0, "k", vsn, b"v2"), 10.0)
        assert r[0] == "ok"
        r = await runtime.await_future(svc.kget(0, "k"), 10.0)
        assert r == ("ok", b"v2")
        svc.stop()

    asyncio.run(scenario())


def test_launch_failure_fails_ops_instead_of_orphaning():
    """A device launch that raises (XLA error, dead backend) must fail
    every op taken for that flush — clients would otherwise block on
    their futures forever — and the service must keep working once
    the device recovers (request_failed analog, peer.erl:1274-1275)."""
    from riak_ensemble_tpu.parallel.batched_host import _LocalEngine
    from riak_ensemble_tpu.testing import wrap_engine_steps

    fail_next = []

    def flaky(inner, state, slab, up, sliced):
        if fail_next:
            fail_next.clear()
            raise RuntimeError("injected device failure")
        return inner(state, slab, up)

    runtime = Runtime(seed=50)
    svc = BatchedEnsembleService(
        runtime, 4, 3, 8, tick=None, config=fast_test_config(),
        engine=wrap_engine_steps(_LocalEngine(), flaky))
    ok = svc.kput(0, "a", b"1")
    svc.flush()
    assert ok.done and ok.value[0] == "ok"

    fail_next.append(True)
    f1 = svc.kput(0, "b", b"2")
    # a leased read of an untouched key serves from the committed
    # mirror BEFORE the failing launch — the failure can't reach it
    f2 = svc.kget(0, "a")
    assert f2.done and f2.value == ("ok", b"1")
    # while one of the write-pended key rides the (failing) round
    f2b = svc.kget(0, "b")
    with pytest.raises(RuntimeError, match="injected"):
        svc.flush()
    assert f1.done and f1.value == "failed"
    assert f2b.done and f2b.value == "failed"
    # payload of the failed put released, slot queued for recycle
    assert len(svc.values) == 1  # only "a"'s committed payload

    # The device "recovers": the service keeps serving.
    f3 = svc.kput(0, "b", b"3")
    while any(svc.queues):
        svc.flush()
    assert f3.done and f3.value[0] == "ok"
    # the committed write is immediately visible to a leased read
    # (mirror-before-ack), no second round needed
    assert svc.kget(0, "b").value == ("ok", b"3")


def test_async_launch_failure_rolls_back_state():
    """Under async dispatch a real device failure surfaces at the d2h
    fetch, AFTER self.state was replaced with the failed launch's
    poisoned arrays; the launch path must roll back to the pre-launch
    state or every subsequent flush consumes the poison and fails
    forever."""
    from riak_ensemble_tpu.parallel.batched_host import _LocalEngine
    from riak_ensemble_tpu.testing import wrap_engine_steps

    poison_next = []

    def poison(inner, state, slab, up, sliced):
        state, flat = inner(state, slab, up)
        if poison_next:
            poison_next.clear()
            # The returned state LOOKS fine (it replaces svc.state),
            # but the result fetch blows up — the async-dispatch
            # failure shape.
            flat = "poisoned-not-an-array"
        return state, flat

    runtime = Runtime(seed=50)
    svc = BatchedEnsembleService(
        runtime, 4, 3, 8, tick=None, config=fast_test_config(),
        engine=wrap_engine_steps(_LocalEngine(), poison))
    assert_ok = svc.kput(0, "a", b"1")
    svc.flush()
    assert assert_ok.done and assert_ok.value[0] == "ok"
    good_state = svc.state

    poison_next.append(True)
    f1 = svc.kput(0, "b", b"2")
    with pytest.raises(Exception):
        svc.flush()
    assert f1.done and f1.value == "failed"
    assert svc.state is good_state, "poisoned state was not rolled back"

    # Clean state: the service serves again immediately.
    f2 = svc.kput(0, "b", b"3")
    while any(svc.queues):
        svc.flush()
    assert f2.done and f2.value[0] == "ok"
    r = svc.kget(0, "a")
    while any(svc.queues):
        svc.flush()
    assert r.done and r.value == ("ok", b"1")


def test_raising_client_waiter_does_not_orphan_batch():
    """Future.resolve runs waiters synchronously; a client callback
    that raises must not abort the resolve loop (orphaning later ops)
    nor mask a device error on the failure path."""
    runtime = Runtime(seed=50)
    svc = BatchedEnsembleService(runtime, 2, 3, 8, tick=None,
                                 config=fast_test_config())
    bad = svc.kput(0, "a", b"1")
    bad.add_waiter(lambda _r: (_ for _ in ()).throw(ValueError("client bug")))
    good = svc.kput(1, "b", b"2")
    while any(svc.queues):
        svc.flush()   # must not raise: client bug is traced, not fatal
    assert bad.done and bad.value[0] == "ok"
    assert good.done and good.value[0] == "ok"


def test_all_waiters_run_despite_raising_waiter():
    """Future.resolve must run every waiter even when an earlier one
    raises — the waiter list is swapped out before iterating, so a
    skipped waiter could never fire again."""
    from riak_ensemble_tpu.runtime import Future

    ran = []
    f = Future()
    f.add_waiter(lambda _r: ran.append("a"))
    f.add_waiter(lambda _r: (_ for _ in ()).throw(ValueError("bug")))
    f.add_waiter(lambda _r: ran.append("b"))
    with pytest.raises(ValueError, match="bug"):
        f.resolve("x")
    assert ran == ["a", "b"]
    assert f.done and f.value == "x"


def test_burst_flush_does_not_wait_for_tick():
    """A queue reaching a full launch's depth flushes on the next
    runtime turn instead of waiting out the tick — batching must
    amortize, not add latency."""
    runtime = Runtime(seed=50)
    svc = BatchedEnsembleService(runtime, 2, 3, 16, tick=10.0,
                                 max_ops_per_tick=4,
                                 config=fast_test_config())
    futs = [svc.kput(0, f"k{i}", b"v") for i in range(4)]  # = max_k
    runtime.run_for(0.01)  # far less than the 10s tick
    assert all(f.done and f.value[0] == "ok" for f in futs), \
        "burst did not trigger an early flush"
    # a burst DEEPER than max_k drains fully too (a look re-armed
    # after each flush that left work queued)
    deep = [svc.kput(0, f"d{i}", b"v") for i in range(11)]
    runtime.run_for(0.01)
    assert all(f.done and f.value[0] == "ok" for f in deep), \
        "multi-launch burst left a residue waiting for the tick"
    # below the threshold an op does not wait out the (huge) tick
    # either: its arrival starts the flush (tests/test_flush_trigger.py)
    f = svc.kput(1, "x", b"v")
    assert not f.done, "flushed inside the enqueue"
    runtime.run_for(0.01)
    assert f.done and f.value[0] == "ok"
    assert svc.lat_records[-1]["arrival"] == 1


def test_service_leader_watchers():
    """watch_leader (the scale-path watch_leader_status,
    peer.erl:212-218): fires on election-driven changes and
    membership-driven depositions; watcher exceptions are contained."""
    runtime, svc = make_service(n_ens=2, n_peers=3)
    events = []
    svc.watch_leader(0, lambda e, old, new: events.append((e, old, new)))
    svc.watch_leader(0, lambda e, old, new: 1 / 0)  # hostile watcher
    # registration notifies the CURRENT status immediately
    assert events == [(0, -1, -1)]

    assert settle(runtime, svc.kput(0, "k", b"v"))[0] == "ok"
    assert events[1] == (0, -1, int(svc.leader_np[0]))

    # leader dies -> next flush elects a new one -> watcher fires
    old_leader = int(svc.leader_np[0])
    svc.set_peer_up(0, old_leader, False)
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"v")
    assert events[-1][1] == old_leader
    assert events[-1][2] == int(svc.leader_np[0]) != old_leader

    # membership change that drops the leader deposes it (-1) before
    # the re-election flush.  The returned peer needs one commit round
    # to adopt the current epoch (the following({commit, Fact})
    # catch-up) before it counts toward the collapse quorum.
    svc.set_peer_up(0, old_leader, True)
    assert settle(runtime, svc.kput(0, "k", b"v2"))[0] == "ok"
    n = len(events)
    nv = np.ones((2, 3), bool)
    nv[0, int(svc.leader_np[0])] = False
    sel = np.zeros(2, bool)
    sel[0] = True
    assert svc.update_members(sel, nv)[0]
    assert any(ev[2] == -1 for ev in events[n:])
    # other-ensemble watchers never fired (no watcher on ens 1)
    assert all(ev[0] == 0 for ev in events)

    # unwatch: no further events after deregistration
    fn = svc._leader_watchers[0][0]
    assert svc.unwatch_leader(0, fn)
    assert not svc.unwatch_leader(0, fn)   # idempotent: already gone
    n2 = len(events)
    assert settle(runtime, svc.kget(0, "k"))[0] == "ok"  # re-elects
    assert len(events) == n2
    svc.stop()


def test_service_kput_once():
    """do_kput_once (peer.erl:278-284): create-if-missing through the
    (0,0) CAS — commits on absence or tombstone, rejects existing."""
    runtime, svc = make_service(n_ens=1, n_peers=3, n_slots=4)
    r = settle(runtime, svc.kput_once(0, "k", b"first"))
    assert r[0] == "ok"
    assert settle(runtime, svc.kput_once(0, "k", b"second")) == "failed"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"first")
    # over a tombstone it succeeds (the notfound-obj case)
    assert settle(runtime, svc.kdelete(0, "k"))[0] == "ok"
    r = settle(runtime, svc.kput_once(0, "k", b"third"))
    assert r[0] == "ok"
    assert settle(runtime, svc.kget(0, "k")) == ("ok", b"third")
    svc.stop()


def test_service_scrub_heals_cold_slot_damage():
    """scrub(): damage on a slot NO read ever touches is found by the
    full verify sweep and healed by the exchange — the AAE-cadence
    maintenance surface."""
    runtime, svc = make_service(n_ens=4, n_peers=3)
    for e in range(4):
        assert settle(runtime, svc.kput(e, "cold", b"c%d" % e))[0] == "ok"
        assert settle(runtime, svc.kput(e, "hot", b"h%d" % e))[0] == "ok"
    # damage the COLD slot's object on a minority replica + an upper
    # tree node on another — nothing reads them again before scrub
    s_cold = svc.key_slot[2]["cold"]
    svc.state = svc.state._replace(
        obj_val=svc.state.obj_val.at[2, 1, s_cold].set(123456))
    import jax.numpy as jnp
    svc.state = svc.state._replace(
        tree_node=svc.state.tree_node.at[3, 2, 0, :].set(
            jnp.uint32(0xBAD)))

    rep = svc.scrub()
    assert rep["replicas_damaged"] >= 2
    assert rep["replicas_healed"] == rep["replicas_damaged"]
    assert rep["ensembles_swept"] >= 2
    # clean now: a second scrub finds nothing, data intact
    assert svc.scrub() == {"replicas_damaged": 0,
                           "replicas_healed": 0, "ensembles_swept": 0}
    for e in range(4):
        assert settle(runtime, svc.kget(e, "cold")) == ("ok", b"c%d" % e)
        assert settle(runtime, svc.kget(e, "hot")) == ("ok", b"h%d" % e)
    svc.stop()


def test_periodic_scrub_cadence():
    """scrub_every_flushes: cold-slot damage heals without any
    explicit scrub call — the tick-driven AAE analog."""
    from riak_ensemble_tpu.config import fast_test_config
    from riak_ensemble_tpu.runtime import Runtime
    runtime = Runtime(seed=52)
    svc = BatchedEnsembleService(runtime, 2, 3, 8, tick=0.005,
                                 config=fast_test_config(),
                                 scrub_every_flushes=3)
    assert settle(runtime, svc.kput(0, "cold", b"c"))[0] == "ok"
    s = svc.key_slot[0]["cold"]
    svc.state = svc.state._replace(
        obj_val=svc.state.obj_val.at[0, 1, s].set(777777))
    # traffic on the OTHER ensemble drives flushes past the cadence
    for i in range(8):
        assert settle(runtime, svc.kput(1, f"k{i}", b"v"))[0] == "ok"
    from riak_ensemble_tpu.ops import engine as eng
    node_bad, leaf_bad = eng.verify_trees(svc.state)
    assert not bool(np.asarray(node_bad).any())
    assert not bool(np.asarray(leaf_bad).any())
    assert svc.repairs >= 1 or svc.corruptions >= 1
    assert settle(runtime, svc.kget(0, "cold")) == ("ok", b"c")
    svc.stop()


@pytest.mark.parametrize("n_slots", [4, 4096])
def test_restore_of_an_image_without_the_tree_stamp_rebuilds(tmp_path,
                                                             n_slots):
    """The tree's storage stamp (ISSUE 44): a checkpoint from before it
    holds every upper level flat in ``tree_node`` (``[E, M, 273, 4]`` at
    4,096 slots, where this state holds 2 rows of ``tree_rows`` and 17
    nodes) and no ``tree_form``.  Restore reads its object planes and
    leaves, rebuilds every tree from them, and serves verified reads
    (docs/MIGRATION.md)."""
    import pickle

    import jax.numpy as jnp

    from riak_ensemble_tpu import save as savelib
    from riak_ensemble_tpu.ops import checkpoint as ckpt
    from riak_ensemble_tpu.ops import engine as eng

    runtime, svc = make_service(n_ens=2, n_peers=3, n_slots=n_slots)
    for e in range(2):
        assert settle(runtime, svc.kput(e, "k", b"v%d" % e))[0] == "ok"
    svc.save(str(tmp_path / "c"))
    state = svc.state
    assert (state.tree_rows is None) == (n_slots == 4)
    svc.stop()

    d = tmp_path / "c"
    n = int(savelib.read(str(d / "CURRENT")).decode())
    host_path = str(d / f"ckpt.{n}" / "host")
    host = pickle.loads(savelib.read(host_path))
    assert host.pop("tree_form") == eng.TREE_FORM
    savelib.write(host_path, pickle.dumps(host, protocol=4))
    # the image as the parent wrote it: the flat node plane and no row
    # plane; scrambled, so that reading it would poison every path
    flat = jnp.concatenate(eng.rows_to_levels(
        state.tree_rows, state.tree_node, n_slots), axis=-2)
    assert flat.shape[2] == eng._tree_offsets(n_slots)[1]
    old = {k: v for k, v in state._asdict().items() if k != "tree_rows"}
    old["tree_node"] = flat ^ jnp.uint32(0x0BADF00D)
    import orbax.checkpoint as ocp
    ocp.PyTreeCheckpointer().save(str(d / f"ckpt.{n}" / "engine"), old,
                                  force=True)

    rt2 = Runtime(seed=12)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(d), tick=0.005, config=fast_test_config())
    assert svc2.state.tree_node.shape == state.tree_node.shape
    for f in ("obj_epoch", "obj_seq", "obj_val", "tree_leaf", "epoch"):
        np.testing.assert_array_equal(getattr(svc2.state, f),
                                      getattr(state, f), err_msg=f)
    node_bad, leaf_bad = eng.verify_trees(svc2.state)
    assert not np.asarray(node_bad).any() and not np.asarray(leaf_bad).any()
    np.testing.assert_array_equal(svc2.state.tree_node, state.tree_node)
    if n_slots == 4096:
        np.testing.assert_array_equal(svc2.state.tree_rows, state.tree_rows)
    svc2.set_fast_reads(False)          # every read a verified round
    for e in range(2):
        assert settle(rt2, svc2.kget(e, "k")) == ("ok", b"v%d" % e)
    assert settle(rt2, svc2.kput(0, "k", b"post"))[0] == "ok"
    assert settle(rt2, svc2.kget(0, "k")) == ("ok", b"post")
    assert svc2.stats()["corruptions_detected"] == 0
    # ...and writes the stamp with its next image
    svc2.save(str(tmp_path / "c2"))
    n2 = int(savelib.read(str(tmp_path / "c2" / "CURRENT")).decode())
    host2 = pickle.loads(savelib.read(
        str(tmp_path / "c2" / f"ckpt.{n2}" / "host")))
    assert host2["tree_form"] == eng.TREE_FORM
    again = ckpt.load(str(tmp_path / "c2" / f"ckpt.{n2}" / "engine"),
                      template=svc2.state)
    assert (again.tree_rows is None) == (n_slots == 4)
    svc2.stop()


def test_restore_rebuilds_trees_on_hash_format_change(tmp_path):
    """Hash-format migration (round-5 advice): a checkpoint written
    under a different device fold persists tree_leaf/tree_node that
    mismatch the running code's hashes.  Restore must detect the
    stamped format and rebuild every replica tree from the object
    store — otherwise _verify_path fails on every slot and reads of
    committed data fail cluster-wide (docs/MIGRATION.md)."""
    import pickle

    import jax.numpy as jnp

    from riak_ensemble_tpu import save as savelib
    from riak_ensemble_tpu.ops import hash as hashk

    runtime, svc = make_service(n_ens=2, n_peers=3, n_slots=4)
    for e in range(2):
        assert settle(runtime, svc.kput(e, "k", b"v%d" % e))[0] == "ok"
    # Simulate an image written under a different fold: scramble the
    # device trees in place, then checkpoint them verbatim.
    svc.state = svc.state._replace(
        tree_leaf=svc.state.tree_leaf ^ jnp.uint32(0xDEADBEEF),
        tree_node=svc.state.tree_node ^ jnp.uint32(0x0BADF00D))
    svc.save(str(tmp_path / "c"))
    svc.stop()

    d = tmp_path / "c"
    n = int(savelib.read(str(d / "CURRENT")).decode())
    host_path = str(d / f"ckpt.{n}" / "host")
    host = pickle.loads(savelib.read(host_path))
    assert host["hash_format"] == hashk.HASH_FORMAT

    # Control: format matches -> trees restored verbatim (scrambled),
    # committed reads do NOT come back ok (the read either fails or
    # retries past the budget — both prove the stale trees poison it).
    rt_bad = Runtime(seed=11)
    svc_bad = BatchedEnsembleService.restore(
        rt_bad, str(d), tick=0.005, config=fast_test_config())
    try:
        r = settle(rt_bad, svc_bad.kget(0, "k"), timeout=1.0)
        assert r != ("ok", b"v0"), r
    except TimeoutError:
        pass
    svc_bad.stop()

    # Stamp the old format: restore must rebuild and serve.
    host["hash_format"] = 2
    savelib.write(host_path, pickle.dumps(host, protocol=4))
    rt2 = Runtime(seed=12)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(d), tick=0.005, config=fast_test_config())
    for e in range(2):
        assert settle(rt2, svc2.kget(e, "k")) == ("ok", b"v%d" % e)
    assert settle(rt2, svc2.kput(0, "k", b"post"))[0] == "ok"
    assert settle(rt2, svc2.kget(0, "k")) == ("ok", b"post")
