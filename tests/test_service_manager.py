"""Consensus-managed scale-plane membership (review r3 #3): tenants
flow through the root ensemble + gossip, placement derives from the
svcnode directory, and reconciliation loops converge every node's
batched service — joining a new svcnode rebalances tenants via gossip
alone (manager.erl:610-641 / check_peers:697-715 for the scale
plane)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import service_directory as sd  # noqa: E402
from riak_ensemble_tpu import service_manager as sm  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService)
from riak_ensemble_tpu.testing import ManagedCluster  # noqa: E402

N_ENS, N_PEERS, N_SLOTS = 16, 3, 8
TENANTS = [f"tenant{i}" for i in range(10)]


def _bring_up(mc, node, name, registry):
    svc = BatchedEnsembleService(
        mc.runtime, N_ENS, N_PEERS, N_SLOTS, tick=0.05,
        config=fast_test_config(), dynamic=True)
    rec = sm.ServiceReconciler(mc.runtime, mc.mgr(node), svc, name,
                               registry.get, poll=0.2)
    registry[name] = rec
    r = sd.register_service(mc.mgr(node), mc.runtime, name,
                            "127.0.0.1", 7000 + len(registry),
                            (N_ENS, N_PEERS, N_SLOTS))
    assert r == "ok", r
    return svc, rec


def _settle_fut(mc, fut, t=60.0):
    ok = mc.runtime.run_until(lambda: fut.done, t)
    assert ok, "future never resolved"
    return fut.value


def test_join_rebalances_tenants_via_gossip_alone():
    mc = ManagedCluster(seed=5, nodes=("node0", "node1"))
    mc.enable("node0")
    mc.join("node1", "node0")
    registry = {}
    svc0, rec0 = _bring_up(mc, "node0", "svc@node0", registry)

    # tenants enter the cluster through the ROOT ensemble
    for t in TENANTS:
        assert sm.create_tenant(mc.mgr("node0"), mc.runtime, t) == "ok"

    # the single svcnode adopts everything (reconciliation, not a
    # direct create_ensemble call)
    ok = mc.runtime.run_until(
        lambda: all(svc0.resolve_ensemble(t) is not None
                    for t in TENANTS), 60.0)
    assert ok, "tenants never reconciled onto the only svcnode"

    # real data in every tenant
    written = {}
    futs = []
    for i, t in enumerate(TENANTS):
        ens = svc0.resolve_ensemble(t)
        val = b"payload-%d" % i
        futs.append(svc0.kput(ens, "k", val))
        written[t] = val
    for f in futs:
        assert _settle_fut(mc, f)[0] == "ok"

    # -- join a second svcnode: ONE registration through the root;
    #    everything after rides gossip + local reconciliation --------
    svc1, rec1 = _bring_up(mc, "node1", "svc@node1", registry)

    both = ["svc@node0", "svc@node1"]
    moved = [t for t in TENANTS if sm.place(t, both) == "svc@node1"]
    stayed = [t for t in TENANTS if t not in moved]
    assert moved and stayed, "rendezvous should split the tenants"

    def converged():
        return (all(svc1.resolve_ensemble(t) is not None
                    and svc0.resolve_ensemble(t) is None
                    for t in moved)
                and all(svc0.resolve_ensemble(t) is not None
                        and svc1.resolve_ensemble(t) is None
                        for t in stayed)
                and not rec1._importing)
    assert mc.runtime.run_until(converged, 120.0), (
        "rebalance never converged: "
        f"moved={[(t, svc0.resolve_ensemble(t), svc1.resolve_ensemble(t)) for t in moved]}")

    # handoff carried the data: moved tenants read back on the NEW
    # owner; stayed tenants untouched on the old one
    for t, svc in [(t, svc1) for t in moved] + \
                  [(t, svc0) for t in stayed]:
        f = svc.kget(svc.resolve_ensemble(t), "k")
        assert _settle_fut(mc, f) == ("ok", written[t]), t

    # -- consensus-managed per-tenant view change ---------------------
    target = stayed[0]
    r = sm.set_tenant_view(mc.mgr("node0"), mc.runtime, target,
                           [True, True, False])
    assert r == "ok", r
    ens = svc0.resolve_ensemble(target)
    ok = mc.runtime.run_until(
        lambda: (svc0.member_np[ens] == [True, True, False]).all(),
        60.0)
    assert ok, "registry view change never reconciled into the device"
    # data survives the joint-consensus transition
    f = svc0.kget(ens, "k")
    assert _settle_fut(mc, f) == ("ok", written[target])

    # -- retire through the root: every copy converges away ----------
    r = sm.retire_tenant(mc.mgr("node0"), mc.runtime, moved[0])
    assert r == "ok", r
    ok = mc.runtime.run_until(
        lambda: (svc1.resolve_ensemble(moved[0]) is None
                 and svc0.resolve_ensemble(moved[0]) is None), 60.0)
    assert ok, "retired tenant still running somewhere"

    rec0.stop()
    rec1.stop()
    svc0.stop()
    svc1.stop()


def test_tenant_placement_is_stable_and_minimal():
    """Rendezvous properties the rebalance story depends on: same
    inputs → same owner everywhere; adding a node only ever moves
    tenants TO the new node."""
    one = ["a"]
    two = ["a", "b"]
    owners_one = {t: sm.place(t, one) for t in TENANTS}
    owners_two = {t: sm.place(t, two) for t in TENANTS}
    assert all(o == "a" for o in owners_one.values())
    for t in TENANTS:
        assert owners_two[t] in ("a", "b")
        if owners_two[t] != owners_one[t]:
            assert owners_two[t] == "b"
    # and the registered-directory order can't change the answer
    assert {t: sm.place(t, ["b", "a"]) for t in TENANTS} == owners_two


def test_handoff_survives_capacity_pressure_and_late_offers():
    """Review r4: (a) a capacity-failed adoption must keep the
    handoff payload for the retry tick (not drop it with the popped
    inbox entry); (b) a handoff arriving AFTER an empty adoption
    merges create-if-missing — local writes made since stay newest."""
    mc = ManagedCluster(seed=6, nodes=("node0",))
    mc.enable("node0")
    registry = {}
    # a 2-row service: capacity pressure is real
    svc = BatchedEnsembleService(
        mc.runtime, 2, N_PEERS, N_SLOTS, tick=0.05,
        config=fast_test_config(), dynamic=True)
    rec = sm.ServiceReconciler(mc.runtime, mc.mgr("node0"), svc,
                               "svc@node0", registry.get, poll=0.2)
    registry["svc@node0"] = rec
    r = sd.register_service(mc.mgr("node0"), mc.runtime, "svc@node0",
                            "127.0.0.1", 7100, (2, N_PEERS, N_SLOTS))
    assert r == "ok", r

    # fill both rows with registry tenants (the reconciler keeps
    # registered tenants and destroys strays, so blockers must be
    # real), then hand a third tenant off: its adoption must fail on
    # capacity WITHOUT losing the payload
    for b in ("blocker0", "blocker1"):
        assert sm.create_tenant(mc.mgr("node0"), mc.runtime, b) == "ok"
    ok = mc.runtime.run_until(
        lambda: all(svc.resolve_ensemble(b) is not None
                    for b in ("blocker0", "blocker1")), 60.0)
    assert ok
    assert sm.create_tenant(mc.mgr("node0"), mc.runtime, "t-cap") \
        == "ok"
    rec.offer_handoff("t-cap", [("k", b"precious")])
    mc.runtime.run_for(5.0)
    assert svc.resolve_ensemble("t-cap") is None  # no capacity yet
    assert rec._inbox.get("t-cap"), "payload dropped under capacity"

    # free a row through the registry: adoption completes WITH data
    assert sm.retire_tenant(mc.mgr("node0"), mc.runtime, "blocker0") \
        == "ok"
    ok = mc.runtime.run_until(
        lambda: (svc.resolve_ensemble("t-cap") is not None
                 and not rec._importing), 60.0)
    assert ok
    f = svc.kget(svc.resolve_ensemble("t-cap"), "k")
    assert _settle_fut(mc, f) == ("ok", b"precious")

    # late handoff into a LIVE tenant: local data wins per key,
    # absent keys fill in
    ens = svc.resolve_ensemble("t-cap")
    f = svc.kput(ens, "local", b"newer")
    assert _settle_fut(mc, f)[0] == "ok"
    rec.offer_handoff("t-cap", [("local", b"stale"),
                                ("extra", b"carried")])
    ok = mc.runtime.run_until(
        lambda: "t-cap" not in rec._inbox and not rec._importing,
        60.0)
    assert ok
    f1 = svc.kget(ens, "local")
    f2 = svc.kget(ens, "extra")
    assert _settle_fut(mc, f1) == ("ok", b"newer")
    assert _settle_fut(mc, f2) == ("ok", b"carried")

    rec.stop()
    svc.stop()


def test_all_false_views_rejected_and_contained():
    """Review r4: an all-False view is rejected at the registry entry
    points, and a malformed record that sneaks in anyway must not
    crash the reconciliation loop."""
    mc = ManagedCluster(seed=7, nodes=("node0",))
    mc.enable("node0")
    with pytest.raises(ValueError):
        sm.create_tenant(mc.mgr("node0"), mc.runtime, "bad",
                         view=[False, False, False])

    registry = {}
    svc, rec = _bring_up(mc, "node0", "svc@node0", registry)
    # sneak a malformed record straight into the registry (bypassing
    # the validating entry point)
    fut = mc.mgr("node0").create_ensemble(
        sm.tenant_id("sneaky"), None, [], sm.TENANT_MOD,
        ([False, False, False],), 30.0)
    assert mc.runtime.await_future(fut, 35.0) == "ok"
    assert sm.create_tenant(mc.mgr("node0"), mc.runtime, "good") \
        == "ok"
    # the loop survives the bad record and still reconciles others
    ok = mc.runtime.run_until(
        lambda: svc.resolve_ensemble("good") is not None, 60.0)
    assert ok, "reconciler died on a malformed view"
    assert svc.resolve_ensemble("sneaky") is None
    rec.stop()
    svc.stop()


def test_versions_survive_tenant_handoff():
    """review r4 missing #2 / directive #4: a placement move carries
    {epoch, seq} with the values (replace_members_test.erl:26-30
    semantics — consensus moves, objects keep their versions).  A CAS
    token read BEFORE a reconciler-driven move must work AFTER it,
    and post-move writes must version-dominate the installed
    objects."""
    mc = ManagedCluster(seed=11, nodes=("node0", "node1"))
    mc.enable("node0")
    mc.join("node1", "node0")
    registry = {}
    svc0, rec0 = _bring_up(mc, "node0", "svc@node0", registry)
    for t in TENANTS:
        assert sm.create_tenant(mc.mgr("node0"), mc.runtime, t) == "ok"
    ok = mc.runtime.run_until(
        lambda: all(svc0.resolve_ensemble(t) is not None
                    for t in TENANTS), 60.0)
    assert ok

    # write, then capture each tenant's CAS token pre-move
    tokens = {}
    for i, t in enumerate(TENANTS):
        ens = svc0.resolve_ensemble(t)
        assert _settle_fut(mc, svc0.kput(ens, "k",
                                         b"v%d" % i))[0] == "ok"
        r = _settle_fut(mc, svc0.kget_vsn(ens, "k"))
        assert r[0] == "ok" and r[1] == b"v%d" % i, r
        tokens[t] = r[2]
        assert tokens[t] != (0, 0)

    # join node1: rendezvous moves a subset; handoff must preserve vsn
    svc1, rec1 = _bring_up(mc, "node1", "svc@node1", registry)
    both = ["svc@node0", "svc@node1"]
    moved = [t for t in TENANTS if sm.place(t, both) == "svc@node1"]
    assert moved
    ok = mc.runtime.run_until(
        lambda: all(svc1.resolve_ensemble(t) is not None
                    and svc0.resolve_ensemble(t) is None
                    for t in moved) and not rec1._importing, 120.0)
    assert ok, "rebalance never converged"

    for t in moved:
        i = TENANTS.index(t)
        ens = svc1.resolve_ensemble(t)
        # the version travelled with the value
        r = _settle_fut(mc, svc1.kget_vsn(ens, "k"))
        assert r == ("ok", b"v%d" % i, tokens[t]), (t, r, tokens[t])
        # THE criterion: the pre-move CAS token still works
        r = _settle_fut(mc, svc1.kupdate(ens, "k", tokens[t],
                                         b"updated-%d" % i))
        assert r[0] == "ok", (t, r)
        new_vsn = r[1]
        # post-move versions strictly dominate the installed ones
        assert tuple(new_vsn) > tuple(tokens[t]), (new_vsn, tokens[t])
        # and the stale token is now correctly refused
        r = _settle_fut(mc, svc1.kupdate(ens, "k", tokens[t],
                                         b"stale"))
        assert r == "failed", r
        r = _settle_fut(mc, svc1.kget(ens, "k"))
        assert r == ("ok", b"updated-%d" % i), r

    rec0.stop()
    rec1.stop()
    svc0.stop()
    svc1.stop()


def test_leaderless_export_pairs_payload_with_committed_version():
    """advice r5 regression: _export on a LEADERLESS row must not read
    versions from lane 0 — lane 0 can lag a quorum-committed write
    (it was down when the write committed), and pairing the newest
    payload with its stale (epoch, seq) voids every CAS token minted
    from the true version after the install.  The export must carry
    the per-slot max (epoch, seq) across up lanes."""
    from riak_ensemble_tpu.runtime import Runtime

    runtime = Runtime(seed=42)
    svc = BatchedEnsembleService(runtime, 4, N_PEERS, N_SLOTS,
                                 tick=0.005,
                                 config=fast_test_config(),
                                 dynamic=True)
    ens = svc.create_ensemble("t0")
    assert ens is not None

    def settle(fut, t=30.0):
        return runtime.await_future(fut, t)

    # v1 commits on every lane
    assert settle(svc.kput(ens, "k", b"v1"))[0] == "ok"
    # lane 0 goes down; v2 commits on the surviving quorum only —
    # lane 0's device copy now holds v1's stale (epoch, seq)
    svc.set_peer_up(ens, 0, False)
    r = settle(svc.kput(ens, "k", b"v2"))
    assert r[0] == "ok", r
    token = settle(svc.kget_vsn(ens, "k"))
    assert token[0] == "ok" and token[1] == b"v2"
    vsn = token[2]

    # the export-time window: no leader (e.g. mid-failover)
    svc.leader_np[ens] = -1
    rec = sm.ServiceReconciler(runtime, None, svc, "svc@x",
                               lambda _n: None, poll=None)
    data = rec._export(ens)
    by_key = {e[0]: e for e in data}
    assert by_key["k"][1] == b"v2"
    # THE regression: the exported version is the committed one, not
    # lane 0's stale copy
    assert tuple(by_key["k"][2]) == tuple(vsn), (by_key["k"], vsn)

    # and the CAS token survives the export → install move
    svc2 = BatchedEnsembleService(runtime, 4, N_PEERS, N_SLOTS,
                                  tick=0.005,
                                  config=fast_test_config(),
                                  dynamic=True)
    row = svc2.create_ensemble("t0")
    res = svc2.install_objs(row, [(key, ver, payload)
                                  for key, payload, ver in data])
    assert all(r[0] == "ok" for r in res)
    r = settle(svc2.kupdate(row, "k", vsn, b"v3"))
    assert r[0] == "ok", r
    assert settle(svc2.kget(row, "k")) == ("ok", b"v3")
    svc.stop()
    svc2.stop()
