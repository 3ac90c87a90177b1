"""Lease-protected read fast path (batched_host, ARCHITECTURE §9).

Unit coverage for the read router: a kget/kget_vsn/kget_many of a
keyed slot serves from the leader's committed host mirror — no OP_GET
row, no flush — iff the lease is margin-valid, the slot has no
queued/in-flight write, the row has a live leader and is not
corruption-flagged.  Every miss reason is pinned, visibility
(mirror-update-before-ack ⇒ read-your-acked-writes) is exercised
across pipeline depth 2 and RMW inline slots, and the replication
group's leader-only / host-lease / depose-invalidation gates are
covered without sockets.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import funref  # noqa: E402
from riak_ensemble_tpu.config import Config, fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402


def make(n_ens=4, n_peers=3, seed=7, **kw):
    runtime = Runtime(seed=seed)
    svc = BatchedEnsembleService(runtime, n_ens, n_peers, n_slots=8,
                                 tick=None, max_ops_per_tick=8,
                                 config=fast_test_config(), **kw)
    return runtime, svc


def settle(runtime, svc, fut):
    for _ in range(30):
        if fut.done:
            return fut.value
        svc.flush()
        runtime.run_for(0.001)
    raise AssertionError("future never resolved")


def test_hit_after_committed_write():
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v1"))[0] == "ok"
    g = svc.kget(0, "a")
    assert g.done and g.value == ("ok", b"v1")
    assert svc.read_fastpath_hits == 1
    assert svc.read_fastpath_misses == 0
    # kget_vsn hits too, with the committed version a CAS accepts
    gv = svc.kget_vsn(0, "a")
    assert gv.done and gv.value[:2] == ("ok", b"v1")
    vsn = gv.value[2]
    assert settle(runtime, svc,
                  svc.kupdate(0, "a", vsn, b"v2"))[0] == "ok"
    assert svc.kget(0, "a").value == ("ok", b"v2")


def test_pending_write_gate_and_read_your_acked_writes():
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v1"))[0] == "ok"
    p = svc.kput(0, "a", b"v2")
    # a read racing a queued write must NOT serve the mirror — it
    # falls back to the device round and orders after the write
    g = svc.kget(0, "a")
    assert not g.done
    assert svc.read_fastpath_miss_reasons["pending_write"] == 1
    settle(runtime, svc, g)
    assert p.value[0] == "ok" and g.value == ("ok", b"v2")
    # after the ack the mirror already carries the write: fast hit
    g2 = svc.kget(0, "a")
    assert g2.done and g2.value == ("ok", b"v2")


@pytest.mark.parametrize("depth", [1, 2])
def test_ack_waiter_sees_write_immediately(depth):
    """The mirror updates BEFORE the write future resolves, so a read
    issued from inside the ack waiter observes the write — including
    across the depth-2 launch pipeline's late resolve."""
    runtime, svc = make(pipeline_depth=depth)
    assert settle(runtime, svc, svc.kput(0, "a", b"v0"))[0] == "ok"
    seen = []

    def on_ack(_r):
        f = svc.kget(0, "a")
        seen.append((f.done, f.value if f.done else None))
    p = svc.kput(0, "a", b"v1")
    p.add_waiter(on_ack)
    settle(runtime, svc, p)
    assert p.value[0] == "ok"
    (done, value), = seen
    # fast hit (no pending write left, mirror fresh) with the value
    assert done and value == ("ok", b"v1")


def test_lease_expiry_and_margin_misses():
    runtime, svc = make()
    cfg = svc.config
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    assert svc.kget(0, "a").done
    # jump INSIDE the safety margin: lease not lapsed, but a correct
    # margin check refuses (the clock-skew guard)
    horizon = float(svc.lease_until[0]) - runtime.now
    runtime.run_for(horizon - cfg.read_margin() * 0.5)
    g = svc.kget(0, "a")
    assert not g.done
    assert svc.read_fastpath_miss_reasons["no_lease"] == 1
    settle(runtime, svc, g)  # the device round renews the lease
    assert g.value == ("ok", b"v")
    assert svc.kget(0, "a").done  # leased again
    # and a full lapse misses as well
    runtime.run_for(cfg.lease() * 3)
    assert not svc.kget(0, "a").done
    assert svc.read_fastpath_miss_reasons["no_lease"] == 2


#: a ring wide enough for a SLICED launch (``SLICE_MIN_E`` rows, the
#: smallest bucket at most a quarter of them), the ensemble the
#: launches below carry operations for, and one they carry nothing for
WIDE, BUSY, IDLE = 256, 5, 200


def make_wide(**kw):
    return make(n_ens=WIDE, **kw)


def test_lease_lapses_on_a_service_that_never_launches():
    """Nothing but a launch renews a lease: with no flush at all the
    lease runs out on the clock and stays out, whatever the width of
    the ring (a ring wide enough to slice: nothing renews it behind
    the service's back)."""
    runtime, svc = make_wide()
    cfg = svc.config
    assert settle(runtime, svc, svc.kput(IDLE, "b", b"v"))[0] == "ok"
    assert svc.kget(IDLE, "b").done
    flushes, idle = svc.flushes, svc.lease_renewals_idle
    runtime.run_for(float(svc.lease_until[IDLE]) - runtime.now
                    - cfg.read_margin() * 0.5)
    assert not svc._try_fast(IDLE, svc.key_slot[IDLE]["b"], False)[0]
    runtime.run_for(cfg.lease() * 3)
    assert not svc._try_fast(IDLE, svc.key_slot[IDLE]["b"], False)[0]
    assert svc.read_fastpath_miss_reasons["no_lease"] == 2
    assert svc.stats()["lease_valid_fraction"] == 0.0
    assert (svc.flushes, svc.lease_renewals_idle) == (flushes, idle)


def _lapse_all(runtime, svc):
    runtime.run_for(svc.config.lease() * 3)
    assert svc.stats()["lease_valid_fraction"] == 0.0


def _sliced_put(runtime, svc, electing=0):
    """One settled kput on ``BUSY`` alone; the launch that carried it
    sliced (its record says so), its active set that one column and
    the ``electing`` ones."""
    before = svc.launches_sliced
    assert settle(runtime, svc, svc.kput(BUSY, "a", b"x"))[0] == "ok"
    assert svc.launches_sliced == before + 1
    rec = [r for r in svc.lat_records if r.get("k")][-1]
    assert (rec["sliced"], rec["cols"], rec["a"]) == (1, 1 + electing, 8)


def test_sliced_launch_renews_an_idle_ensembles_lease():
    """ISSUE 47: a sliced launch carries the epoch check of EVERY
    ensemble, so the lease of one it had no operation for is renewed
    and the next read of that ensemble is answered from the mirror:
    no flush, no device round."""
    runtime, svc = make_wide()
    assert settle(runtime, svc, svc.kput(IDLE, "b", b"v"))[0] == "ok"
    _lapse_all(runtime, svc)
    assert svc._fast_read_ok(IDLE, runtime.now) == "no_lease"
    idle0 = svc.stats()["lease_renewals_idle"]

    stamp = runtime.now
    _sliced_put(runtime, svc)
    # every ensemble but the busy one, renewed outside the active set,
    # and none beyond the launch's own pre-upload stamp + lease
    st = svc.stats()
    assert st["lease_renewals_idle"] - idle0 == WIDE - 1
    assert st["lease_valid_fraction"] == 1.0
    assert np.all(svc.lease_until <= runtime.now + svc.config.lease())
    assert np.all(svc.lease_until >= stamp + svc.config.lease())
    flushes, hits = svc.flushes, svc.read_fastpath_hits
    g = svc.kget(IDLE, "b")
    assert g.done and g.value == ("ok", b"v")
    assert (svc.flushes, svc.read_fastpath_hits) == (flushes, hits + 1)
    assert svc.obs_registry.snapshot()[
        "retpu_lease_renewals_idle_total"] == st["lease_renewals_idle"]
    assert "retpu_lease_renewals_idle_total %d" % (
        st["lease_renewals_idle"]) in svc.obs_registry.render_prometheus()


@pytest.mark.parametrize("down", [
    "followers",            # the leader alone: short of a quorum
    "everyone",             # leader down, nobody to elect
    "leader-and-follower",  # leader down, the election cannot win
])
def test_sliced_launch_renews_no_lease_without_the_epoch_check(down):
    """...and ONLY from the device's own check: an idle ensemble whose
    leader is down, or whose up members are short of a quorum, is not
    renewed by another ensemble's launch; its read misses and takes
    the device round (which cannot succeed either).  Peers back up,
    the next launch renews it."""
    runtime, svc = make_wide()
    assert settle(runtime, svc, svc.kput(IDLE, "b", b"v"))[0] == "ok"
    lead = int(svc.leader_np[IDLE])
    others = [p for p in range(3) if p != lead]
    gone = {"followers": others, "everyone": [lead] + others,
            "leader-and-follower": [lead, others[0]]}[down]
    for p in gone:
        svc.set_peer_up(IDLE, p, False)
    _lapse_all(runtime, svc)
    idle0 = svc.lease_renewals_idle

    # (the third case puts IDLE in the active set: its election runs,
    # and loses, inside the same launch)
    _sliced_put(runtime, svc, electing=down == "leader-and-follower")
    assert svc.lease_until[IDLE] <= runtime.now
    assert svc.lease_renewals_idle - idle0 == WIDE - 2
    assert svc.stats()["lease_valid_fraction"] == (WIDE - 1) / WIDE
    flushes = svc.flushes
    g = svc.kget(IDLE, "b")
    assert not g.done
    reason = "no_lease" if down == "followers" else "no_leader"
    assert svc.read_fastpath_miss_reasons[reason] == 1
    assert settle(runtime, svc, g) != ("ok", b"v")
    assert svc.flushes > flushes
    assert svc.lease_until[IDLE] <= runtime.now

    for p in gone:
        svc.set_peer_up(IDLE, p, True)
    _sliced_put(runtime, svc)   # the old leader is back: no election
    assert svc.stats()["lease_valid_fraction"] == 1.0
    g = svc.kget(IDLE, "b")
    assert g.done and g.value == ("ok", b"v")


def test_leader_down_then_reelection_revalidates_vsn_mirror():
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    lead = int(svc.leader_np[0])
    svc.set_peer_up(0, lead, False)
    g = svc.kget(0, "a")
    assert not g.done  # electing rows never serve
    assert svc.read_fastpath_miss_reasons["no_leader"] == 1
    settle(runtime, svc, g)  # election folds into this flush; the
    assert g.value == ("ok", b"v")  # same-launch read re-mirrors "a"
    assert int(svc.leader_np[0]) != lead
    # force ANOTHER election with no covering read of "a": the won
    # election must invalidate the row's vsn mirror (the epoch bump
    # re-versions objects lazily — a mirrored token would go stale)
    svc.set_peer_up(0, lead, True)
    svc.set_peer_up(0, int(svc.leader_np[0]), False)
    settle(runtime, svc, svc.kput(0, "other", b"x"))
    gv = svc.kget_vsn(0, "a")
    assert not gv.done
    assert svc.read_fastpath_miss_reasons["vsn_unmirrored"] == 1
    settle(runtime, svc, gv)  # device read re-mirrors the REWRITTEN
    gv2 = svc.kget_vsn(0, "a")  # version...
    assert gv2.done and gv2.value == gv.value
    # ...and the re-mirrored vsn is a live CAS token
    assert settle(runtime, svc, svc.kupdate(
        0, "a", gv2.value[2], b"v2"))[0] == "ok"
    # plain value reads stay fast throughout (the epoch rewrite
    # never changes values)
    assert svc.kget(0, "a").done


def test_inline_rmw_slots_serve_fast():
    runtime, svc = make()
    f = svc.kmodify(1, "ctr", funref.ref("rmw:add", 5), 0)
    settle(runtime, svc, f)
    assert f.value[0] == "ok"
    g = svc.kget(1, "ctr")
    assert g.done and g.value == ("ok", 5)
    gv = svc.kget_vsn(1, "ctr")
    assert gv.done and gv.value[1] == 5
    # fast answer == forced device answer
    svc.set_fast_reads(False)
    gd = svc.kget_vsn(1, "ctr")
    settle(runtime, svc, gd)
    assert gd.value == gv.value
    svc.set_fast_reads(True)
    # a put flips the slot back to handle storage; reads follow
    assert settle(runtime, svc, svc.kput(1, "ctr", b"blob"))[0] == "ok"
    assert svc.kget(1, "ctr").value == ("ok", b"blob")


def test_tombstone_reads_fast_with_real_vsn():
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    d = svc.kdelete(0, "a")
    settle(runtime, svc, d)
    assert d.value[0] == "ok"
    g = svc.kget(0, "a")
    # slot may already be recycled (then the key is unknown —
    # immediate NOTFOUND) or still mapped (fast tombstone read);
    # either way: NOTFOUND, no device round needed
    assert g.done and g.value == ("ok", NOTFOUND)


def test_corrupt_row_bypasses_fast_path():
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    assert svc.kget(0, "a").done
    svc._corrupt_rows[0] = True
    g = svc.kget(0, "a")
    assert not g.done
    assert svc.read_fastpath_miss_reasons["corrupt"] == 1
    settle(runtime, svc, g)
    assert g.value == ("ok", b"v")
    # other rows are unaffected
    assert settle(runtime, svc, svc.kput(1, "b", b"w"))[0] == "ok"
    assert svc.kget(1, "b").done


def test_corruption_detection_flags_and_exchange_clears():
    """Real in-round detection: damage a minority copy, force a
    device read; detection flags the row, the in-resolve exchange
    heals it and re-admits fast reads."""
    import jax.numpy as jnp

    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "k", b"v"))[0] == "ok"
    slot = svc.key_slot[0]["k"]
    svc.state = svc.state._replace(
        obj_val=svc.state.obj_val.at[0, 2, slot].set(424242))
    svc.lease_until[:] = 0.0  # force the device round
    g = svc.kget(0, "k")
    settle(runtime, svc, g)
    assert g.value == ("ok", b"v")
    assert svc.corruptions > 0
    # the exchange ran inside the same resolve and synced the row:
    # fast reads are re-admitted (lease renewed by that same flush)
    g2 = svc.kget(0, "k")
    assert g2.done and g2.value == ("ok", b"v")
    assert not svc._corrupt_rows.any()
    node_bad, leaf_bad = svc.engine.verify_trees(svc.state)
    assert not bool(jnp.asarray(node_bad).any())


def test_opt_outs():
    # programmatic
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    svc.set_fast_reads(False)
    g = svc.kget(0, "a")
    assert not g.done
    assert svc.read_fastpath_miss_reasons["disabled"] == 1
    settle(runtime, svc, g)
    svc.set_fast_reads(True)
    assert svc.kget(0, "a").done

    # config.trust_lease=False pins the path off even when enabled
    runtime2 = Runtime(seed=8)
    cfg = fast_test_config()
    cfg.trust_lease = False
    svc2 = BatchedEnsembleService(runtime2, 2, 3, n_slots=4,
                                  tick=None, config=cfg)
    assert settle(runtime2, svc2, svc2.kput(0, "a", b"v"))[0] == "ok"
    svc2.set_fast_reads(True)  # trust_lease overrides
    assert not svc2.kget(0, "a").done
    settle(runtime2, svc2, svc2.kget(0, "a"))


def test_env_opt_out(monkeypatch):
    monkeypatch.setenv("RETPU_FAST_READS", "0")
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    assert not svc.kget(0, "a").done
    assert svc.read_fastpath_miss_reasons["disabled"] == 1
    settle(runtime, svc, svc.kget(0, "a"))


def test_kget_many_mixed_fast_and_fallback():
    runtime, svc = make()
    r = settle(runtime, svc, svc.kput_many(
        0, ["a", "b"], [b"1", b"2"]))
    assert all(x[0] == "ok" for x in r)
    p = svc.kput(0, "b", b"2x")  # pending write parks only "b"
    m = svc.kget_many(0, ["a", "b", "zz"], want_vsn=True)
    assert not m.done  # "b" rides the round
    h0 = svc.read_fastpath_hits
    settle(runtime, svc, m)
    assert p.value[0] == "ok"
    assert m.value[0][:2] == ("ok", b"1")      # fast
    assert m.value[1][:2] == ("ok", b"2x")     # device, post-write
    assert m.value[2] == ("ok", NOTFOUND, (0, 0))  # unknown key
    assert svc.read_fastpath_hits == h0  # "a" counted at submit
    # order-preserving all-fast batch resolves synchronously
    m2 = svc.kget_many(0, ["b", "a"])
    assert m2.done and m2.value == [("ok", b"2x"), ("ok", b"1")]


@pytest.mark.parametrize("ring", ["narrow", "sliced"])
def test_equivalence_random_ops_fast_vs_device(ring):
    """After a random keyed workload, every key's fast answer equals
    its forced device-round answer (value AND version): on a ring of
    three ensembles, and on three ensembles of a ring wide enough that
    every launch slices and the others' leases ride along."""
    rng = np.random.default_rng(42)
    ens = [0, 1, 2] if ring == "narrow" else [BUSY, 100, IDLE]
    runtime, svc = make(n_ens=3 if ring == "narrow" else WIDE)
    keys = [f"k{i}" for i in range(4)]
    for _ in range(30):
        e = ens[int(rng.integers(3))]
        key = keys[int(rng.integers(4))]
        r = rng.random()
        if r < 0.5:
            fut = svc.kput(e, key, b"v%d" % int(rng.integers(1e6)))
        elif r < 0.7:
            fut = svc.kmodify(e, f"c{key}",
                              funref.ref("rmw:add", 3), 0)
        elif r < 0.85:
            fut = svc.kdelete(e, key)
        else:
            fut = svc.kget(e, key)
        if rng.random() < 0.4:
            settle(runtime, svc, fut)
    while any(svc.queues):
        svc.flush()
    svc.flush()
    if ring == "sliced":
        assert svc.launches_sliced > 5 and svc.lease_renewals_idle > WIDE
    for e in ens:
        for key in keys + [f"c{k}" for k in keys]:
            fast = svc.kget_vsn(e, key)
            assert fast.done  # hit or immediate NOTFOUND
            svc.set_fast_reads(False)
            dev = svc.kget_vsn(e, key)
            settle(runtime, svc, dev)
            svc.set_fast_reads(True)
            assert fast.value == dev.value, (e, key)


def test_disjoint_read_and_write_keys_hit_over_nine_in_ten():
    """A 90/10 mix whose reads and writes never share a key: the
    mirror answers over 0.9 of the reads while every round's writes
    launch, and the only miss reason on record is the one this test
    provokes, a read racing a queued write of its own key."""
    runtime, svc = make()
    rkeys, wkeys = ["r0", "r1", "r2"], ["w0", "w1", "w2"]
    for e in range(4):
        settle(runtime, svc, svc.kput_many(e, rkeys + wkeys, [b"0"] * 6))
    svc.read_fastpath_hits = svc.read_fastpath_misses = 0
    svc.read_fastpath_miss_reasons.clear()
    for rnd in range(10):
        reads = [svc.kget_many(e, rkeys * 3) for e in range(4)]
        write = svc.kput(rnd % 4, wkeys[rnd % 3], b"v%d" % rnd)
        assert all(f.done and f.value == [("ok", b"0")] * 9
                   for f in reads)
        assert settle(runtime, svc, write)[0] == "ok"
    assert svc.read_fastpath_misses == 0
    assert svc.read_fastpath_miss_reasons == {}
    p = svc.kput(0, "r0", b"1")
    g = svc.kget(0, "r0")
    settle(runtime, svc, g)
    assert p.value[0] == "ok" and g.value == ("ok", b"1")
    st = svc.stats()
    assert st["read_fastpath_miss_reasons"] == {"pending_write": 1}
    hits, misses = st["read_fastpath_hits"], st["read_fastpath_misses"]
    assert hits == 360 and hits / (hits + misses) > 0.9


def test_stats_surface():
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    svc.kget(0, "a")
    st = svc.stats()
    assert st["read_fastpath_hits"] == 1
    assert st["read_fastpath_misses"] == 0
    assert st["read_fastpath_miss_reasons"] == {}
    assert 0.0 <= st["lease_valid_fraction"] <= 1.0


def test_restore_starts_leaseless_then_recovers(tmp_path):
    runtime, svc = make()
    assert settle(runtime, svc, svc.kput(0, "a", b"v"))[0] == "ok"
    f = svc.kmodify(0, "ctr", funref.ref("rmw:add", 9), 0)
    settle(runtime, svc, f)
    svc.save(str(tmp_path / "ckpt"))
    rt2 = Runtime(seed=9)
    svc2 = BatchedEnsembleService.restore(
        rt2, str(tmp_path / "ckpt"), tick=None,
        config=fast_test_config())
    # restarts stay lease-less: no pre-crash lease is ever trusted
    g = svc2.kget(0, "a")
    assert not g.done
    assert svc2.read_fastpath_miss_reasons.get("no_lease", 0) >= 1
    settle(rt2, svc2, g)
    assert g.value == ("ok", b"v")
    # warmed again: values AND the inline slot serve fast (the
    # device read re-mirrored what the checkpoint couldn't)
    gi = svc2.kget(0, "ctr")
    if not gi.done:  # inline mirror re-warms via one device round
        settle(rt2, svc2, gi)
        gi = svc2.kget(0, "ctr")
    assert gi.done and gi.value == ("ok", 9)


# -- replication-group gates (no sockets: quorum monkeypatched) -------------


def _group_leader(trust=True):
    from riak_ensemble_tpu.parallel import repgroup

    runtime = Runtime(seed=11)
    svc = repgroup.ReplicatedService(
        runtime, 2, 1, 8, group_size=3, config=fast_test_config(),
        trust_host_lease=trust)
    svc._is_leader = True
    svc._ge = 1
    svc._quorum_from = lambda acked: True  # pretend replicas ack
    return runtime, svc


def test_repgroup_replica_never_serves_fast():
    from riak_ensemble_tpu.parallel import repgroup

    runtime = Runtime(seed=12)
    svc = repgroup.ReplicatedService(
        runtime, 2, 1, 8, group_size=3, config=fast_test_config(),
        trust_host_lease=True)
    svc.key_slot[0]["k"] = 3  # a mapped key on an unpromoted lane
    g = svc.kget(0, "k")
    assert not g.done
    assert svc.read_fastpath_miss_reasons["not_leader"] == 1


def test_repgroup_leader_host_lease_and_depose_invalidation():
    runtime, svc = _group_leader(trust=True)
    p = svc.kput(0, "k", b"v")
    settle(runtime, svc, p)
    assert p.value[0] == "ok"
    p2 = svc.kput(0, "k2", b"w")  # a second settled round: host
    settle(runtime, svc, p2)      # lease granted at warm cadence
    g = svc.kget(0, "k")
    assert g.done and g.value == ("ok", b"v")
    assert svc.stats()["group"]["host_lease_valid"] is True
    # a deposed leader invalidates BEFORE its next ack
    svc._note_depose(99)
    g2 = svc.kget(0, "k")
    assert not g2.done
    assert svc.read_fastpath_miss_reasons["not_leader"] == 1


def test_repgroup_host_lease_opt_in_default_off():
    runtime, svc = _group_leader(trust=False)
    p = svc.kput(0, "k", b"v")
    settle(runtime, svc, p)
    p2 = svc.kput(0, "k2", b"w")
    settle(runtime, svc, p2)
    # without trust_host_lease the strict reads-need-the-host-quorum
    # barrier stands: no fast serves on a group
    g = svc.kget(0, "k")
    assert not g.done
    assert svc.read_fastpath_miss_reasons[
        "no_host_lease_trust"] == 1


def test_repgroup_quorum_loss_revokes_host_lease():
    runtime, svc = _group_leader(trust=True)
    settle(runtime, svc, svc.kput(0, "k", b"v"))
    p2 = svc.kput(0, "k2", b"w")
    settle(runtime, svc, p2)
    assert svc.kget(0, "k").done
    svc._quorum_from = lambda acked: False  # replicas vanish
    p3 = svc.kput(0, "k3", b"x")
    settle(runtime, svc, p3)
    assert p3.value == "failed"  # no false acks
    g = svc.kget(0, "k")
    assert not g.done  # host lease revoked at the failed settle
    assert svc.read_fastpath_miss_reasons["no_lease"] >= 1
