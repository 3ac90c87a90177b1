"""sc.erl-analog linearizability check for the BATCHED SERVICE path.

The scalar actor stack has its own workload checker
(test_linearizability.py); this one drives the same plausible-value
model (test/sc.erl get_post:112-148, prop_sc:835-880 postconditions)
against :class:`BatchedEnsembleService` — the engine-backed scale path
— under an up-mask nemesis: the leader is killed between enqueue and
flush (so the election folds into the same launch that carries the
ops), peers flap, and virtual time jumps past the lease so reads race
lease expiry.  Every seed is a reproducible schedule.
"""

import itertools

import numpy as np
import pytest

import conftest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.linearizability import KeyModel  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402

N_ENS = 6
N_PEERS = 5
N_KEYS = 3
ROUNDS = 35
#: the same six ensembles as columns of a ring wide enough that every
#: launch SLICES (``SLICE_MIN_E`` rows): the other 250 carry nothing
WIDE_ENS = 256
WIDE_COLS = (3, 40, 77, 130, 201, 255)


def _drain(svc, runtime, pending, max_flushes=10, tolerate=None,
           on_tolerated=None):
    """Flush until every submitted future resolves (queued ops past
    max_ops_per_tick ride later launches).  ``tolerate`` is a
    substring of flush errors to survive (the launch-failure nemesis);
    ``on_tolerated`` is called for each one."""
    for _ in range(max_flushes):
        if all(fut.done for _, _, _, fut, _ in pending):
            return
        try:
            svc.flush()
        except RuntimeError as exc:
            if tolerate is None or tolerate not in str(exc):
                raise
            if on_tolerated is not None:
                on_tolerated()
        runtime.run_for(0.001)
    raise AssertionError("ops never resolved")


def _apply_outcomes(pending):
    """Feed resolutions to the models in resolution (= device round)
    order.  Put/delete acks are linearization points; 'failed' is a
    DEFINITIVE no-op — the engine gates every replica write on the
    round's quorum commit (_kv_round put_commit), so a failed op can
    never partially land later.  fail_write keeps the checker strong:
    a timed-out value would stay plausible forever and mask exactly
    the stale-read/data-loss signals this sweep exists to catch."""
    for kind, model, op_id, fut, _payload in pending:
        r = fut.value
        if kind in ("put", "del"):
            if isinstance(r, tuple) and r[0] == "ok":
                model.ack_write(op_id)
            else:
                model.fail_write(op_id)
        else:  # get
            if isinstance(r, tuple) and r[0] == "ok":
                model.ack_read(r[1])
            # 'failed' read returned nothing: no model event


def _submit_batch(rng, svc, models, vals, vsns, seed,
                  cols=range(N_ENS)):
    """One round of the concurrent workload, shared by every sweep:
    puts, CAS updates on the last acked vsn (sometimes stale — then
    they must fail cleanly), reads, and deletes (on the service's
    ensembles ``cols``)."""
    pending = []
    for _ in range(int(rng.integers(2, 8))):
        e = cols[int(rng.integers(N_ENS))]
        k = int(rng.integers(N_KEYS))
        m = models[(e, k)]
        key = f"key{k}"
        op = rng.random()
        if op < 0.55:
            payload = f"{seed}-{next(vals)}".encode()
            op_id = m.invoke_write(payload)
            if op < 0.4:
                fut = svc.kput(e, key, payload)
            else:
                # all-or-nothing CAS against the engine's vsn check
                fut = svc.kupdate(e, key, vsns.get((e, k), (0, 0)),
                                  payload)
            if fut.done and fut.value == "failed":
                # pre-flush rejection (no slot): definitely a no-op
                m.fail_write(op_id)
            else:
                pending.append(("put", m, op_id, fut, payload))

            def _track(res, ek=(e, k)):
                if isinstance(res, tuple) and res[0] == "ok":
                    vsns[ek] = res[1]
            fut.add_waiter(_track)
        elif op < 0.85:
            pending.append(("get", m, None, svc.kget(e, key), None))
        else:
            op_id = m.invoke_write(NOTFOUND)
            fut = svc.kdelete(e, key)
            if fut.done:
                # no slot -> nothing to delete: an immediate ack of
                # the NOTFOUND state
                m.ack_write(op_id)
            else:
                pending.append(("del", m, op_id, fut, None))
    return pending


@pytest.mark.parametrize("seed", conftest.soak_seeds([701, 702, 703, 704, 705, 706]))
def test_service_linearizable_under_nemesis(seed):
    _nemesis_sweep(seed, pipeline_depth=1)


@pytest.mark.parametrize("seed", conftest.soak_seeds([711, 712, 713]))
def test_service_linearizable_under_nemesis_pipelined(seed):
    """The SAME nemesis sweep through the depth-2 launch pipeline
    (max_ops_per_tick=4 so rounds split across overlapped flushes):
    the async path must stay linearizable — results in submission
    order, WAL-free acks still quorum-gated, elections folded
    correctly after the pre-elect drain."""
    _nemesis_sweep(seed, pipeline_depth=2, max_k=4)


@pytest.mark.parametrize("seed,depth", [(721, 1), (722, 2)])
def test_service_linearizable_under_nemesis_on_a_ring_that_slices(
        seed, depth):
    """The SAME sweep on six ensembles of a 256-wide ring: every op
    launch is a sliced one, whose quorum plane renews the lease of
    EVERY ensemble whose leader holds an epoch quorum of up members
    (ISSUE 47), so most reads of the sweep are leased replies that
    race the leader kills, the membership churn and the jumps past the
    lease, and the model still finds no stale or lost value."""
    svc = _nemesis_sweep(seed, pipeline_depth=depth,
                         max_k=8 if depth == 1 else 4,
                         n_ens=WIDE_ENS, cols=WIDE_COLS)
    assert svc.launches_sliced >= ROUNDS // 2
    assert svc.lease_renewals_idle > WIDE_ENS
    assert svc.read_fastpath_hits > 0


def _nemesis_sweep(seed, pipeline_depth, max_k=8, n_ens=N_ENS,
                   cols=range(N_ENS)):
    rng = np.random.default_rng(seed)
    runtime = Runtime(seed=seed)
    config = fast_test_config()
    svc = BatchedEnsembleService(runtime, n_ens, N_PEERS, n_slots=8,
                                 tick=None, max_ops_per_tick=max_k,
                                 config=config,
                                 pipeline_depth=pipeline_depth)
    models = {(e, k): KeyModel(f"{e}/key{k}")
              for e in cols for k in range(N_KEYS)}
    vals = itertools.count(1)
    down = {}  # ens -> peer index currently down
    #: last vsn seen in a write ack per (ens, key) — CAS ops use it
    #: (sometimes deliberately stale)
    vsns = {}

    for _round in range(ROUNDS):
        # -- nemesis: up-mask + membership churn -------------------------
        r = rng.random()
        if r < 0.25 and down:
            # heal a random downed peer
            e = list(down)[int(rng.integers(len(down)))]
            svc.set_peer_up(e, down.pop(e), True)
        elif r < 0.55:
            # kill the CURRENT LEADER of a random ensemble right
            # before the flush that carries this round's ops — the
            # election folds into the same launch (mid-flush kill)
            e = cols[int(rng.integers(N_ENS))]
            if e not in down and svc.leader_np[e] >= 0:
                p = int(svc.leader_np[e])
                svc.set_peer_up(e, p, False)
                down[e] = p
        elif r < 0.7:
            # membership churn concurrent with the workload: shrink a
            # random up-and-running ensemble by one member (or restore
            # the full view), keys must survive the joint-consensus
            # transition
            e = cols[int(rng.integers(N_ENS))]
            sel = np.zeros((n_ens,), bool)
            sel[e] = True
            nv = svc.member_np.copy()
            if nv[e].sum() == N_PEERS:
                victim = int(rng.integers(N_PEERS))
                if victim != svc.leader_np[e]:
                    nv[e, victim] = False
            else:
                nv[e] = True
            svc.update_members(sel, nv)

        pending = _submit_batch(rng, svc, models, vals, vsns, seed,
                                cols)

        # -- lease expiry race: sometimes jump virtual time past the
        #    lease before flushing, so leased reads race renewal ------
        if rng.random() < 0.3:
            runtime.run_for(config.lease() * 2.5)
        _drain(svc, runtime, pending)
        _apply_outcomes(pending)

    # -- quiesce + no-data-loss read-back (prop_sc:835-880) -------------
    for e, p in list(down.items()):
        svc.set_peer_up(e, p, True)
    svc.flush()  # fold in any pending elections
    pending = []
    for (e, k), m in models.items():
        pending.append(("get", m, None, svc.kget(e, f"key{k}"), None))
    _drain(svc, runtime, pending)
    _apply_outcomes(pending)  # raises Violation on stale/lost reads

    served = sum(1 for m in models.values()
                 for ev in m.history if ev[0] == "read")
    assert served >= len(models), "quiesced read-back did not complete"
    # Sanity floor, not equality: a round whose ops all resolve
    # pre-flush (absent-key gets/deletes) never launches.
    assert svc.flushes >= ROUNDS // 2
    return svc


@pytest.mark.parametrize("seed", conftest.soak_seeds([801, 802, 803, 804]))
def test_service_linearizable_across_launch_failures(seed):
    """Device-launch failures (XLA error / dead backend shapes) join
    the nemesis: a seeded ~15% of full_step launches raise, the
    service fails that flush's ops and rolls the engine state + host
    mirrors back, and the surviving history must STILL be
    linearizable — a rollback that resurrected or dropped a committed
    write would surface as a Violation on read-back."""
    from riak_ensemble_tpu.parallel.batched_host import _LocalEngine
    from riak_ensemble_tpu.testing import wrap_engine_steps

    inject_rng = np.random.default_rng(seed + 50_000)
    # The nemesis SCHEDULE guarantees >=1 firing per seed (one launch
    # in the first handful fails deterministically; the rest draw the
    # usual ~15%), so the firing gate below measures the system's
    # rollback behavior, never the dice — a purely random schedule can
    # legitimately draw zero injections on a quiet seed and abort a
    # soak (review r3 weak #5 / directive #8).
    forced_launch = 1 + int(inject_rng.integers(6))
    launch_no = 0

    def failing(inner, state, slab, up, sliced):
        nonlocal launch_no
        launch_no += 1
        if launch_no == forced_launch or inject_rng.random() < 0.15:
            raise RuntimeError("injected-launch-failure")
        return inner(state, slab, up)

    rng = np.random.default_rng(seed)
    runtime = Runtime(seed=seed)
    config = fast_test_config()
    svc = BatchedEnsembleService(runtime, N_ENS, N_PEERS, n_slots=8,
                                 tick=None, max_ops_per_tick=8,
                                 config=config,
                                 engine=wrap_engine_steps(
                                     _LocalEngine(), failing))
    models = {(e, k): KeyModel(f"{e}/key{k}")
              for e in range(N_ENS) for k in range(N_KEYS)}
    vals = itertools.count(1)
    vsns = {}
    down = {}
    failures = 0

    def bump():
        nonlocal failures
        failures += 1

    def drain(pending):
        _drain(svc, runtime, pending, max_flushes=25,
               tolerate="injected-launch-failure", on_tolerated=bump)

    for _round in range(ROUNDS):
        r = rng.random()
        if r < 0.3 and down:
            e = list(down)[int(rng.integers(len(down)))]
            svc.set_peer_up(e, down.pop(e), True)
        elif r < 0.6:
            e = int(rng.integers(N_ENS))
            if e not in down and svc.leader_np[e] >= 0:
                p = int(svc.leader_np[e])
                svc.set_peer_up(e, p, False)
                down[e] = p

        pending = _submit_batch(rng, svc, models, vals, vsns, seed)

        if rng.random() < 0.3:
            runtime.run_for(config.lease() * 2.5)
        drain(pending)
        _apply_outcomes(pending)

    # quiesce: heal everything, then read back every key — the
    # model raises Violation on any stale/lost/resurrected value.
    for e, p in list(down.items()):
        svc.set_peer_up(e, p, True)
    for _ in range(10):
        try:
            svc.flush()
            break
        except RuntimeError as exc:
            # only the nemesis is survivable; a genuine service bug
            # raising here must fail the test, not count as a firing
            assert "injected-launch-failure" in str(exc)
            failures += 1
    pending = [("get", m, None, svc.kget(e, f"key{k}"), None)
               for (e, k), m in models.items()]
    drain(pending)
    _apply_outcomes(pending)
    # The schedule forces >=1 injection, so zero observed firings now
    # means a firing was swallowed somewhere (a real harness bug), not
    # an unlucky seed.
    assert failures > 0, "scheduled nemesis firing was not observed"


@pytest.mark.parametrize("seed", conftest.soak_seeds([901, 902, 903, 904]))
def test_service_linearizable_under_corruption_nemesis(seed):
    """Device-state corruption joins the nemesis (review r3 #9): the
    sweep flips object/tree-leaf/tree-node lanes on a minority of
    replicas MID-RUN — concurrent with client load, leader kills and
    lease races — and the history must stay linearizable: the
    integrity gate excludes damaged replicas from read quorums
    (get_latest_obj's hash extra-check), reads heal accessed slots,
    detection triggers the exchange, and no corrupted copy is ever
    served.  Matches test/sc.erl postconditions (:835-880) under the
    corrupt_* scenario family.
    """
    import jax.numpy as jnp

    from riak_ensemble_tpu.ops import engine as eng

    rng = np.random.default_rng(seed)
    runtime = Runtime(seed=seed)
    config = fast_test_config()
    svc = BatchedEnsembleService(runtime, N_ENS, N_PEERS, n_slots=8,
                                 tick=None, max_ops_per_tick=8,
                                 config=config)
    models = {(e, k): KeyModel(f"{e}/key{k}")
              for e in range(N_ENS) for k in range(N_KEYS)}
    vals = itertools.count(1)
    vsns = {}
    down = {}
    corruptions_injected = 0

    def corrupt_lane():
        """Flip one replica lane.  Only peers {2, 3} are targets — at
        most 2 of 5 copies, always a minority, so a hash-valid holder
        of every committed object survives by construction (the
        engine refuses to bless slots with no valid copy; an
        all-copies nemesis would be unrecoverable by design)."""
        nonlocal corruptions_injected
        e = int(rng.integers(N_ENS))
        p = int(rng.integers(2, 4))
        s = int(rng.integers(svc.n_slots))
        mode = int(rng.integers(3))
        st = svc.state
        if mode == 0:    # object plane: value diverges from its leaf
            st = st._replace(obj_val=st.obj_val.at[e, p, s].set(
                int(rng.integers(1 << 20, 1 << 21))))
        elif mode == 1:  # leaf lane: hash no longer vouches for obj
            st = st._replace(tree_leaf=st.tree_leaf.at[e, p, s, :].set(
                jnp.uint32(0xDEADBEEF)))
        else:            # upper tree node: path verification fails
            u = int(rng.integers(st.tree_node.shape[2]))
            st = st._replace(tree_node=st.tree_node.at[e, p, u, :].set(
                jnp.uint32(0xBADBAD)))
        svc.state = st
        corruptions_injected += 1

    for _round in range(ROUNDS):
        r = rng.random()
        if r < 0.2 and down:
            e = list(down)[int(rng.integers(len(down)))]
            svc.set_peer_up(e, down.pop(e), True)
        elif r < 0.45:
            e = int(rng.integers(N_ENS))
            if e not in down and svc.leader_np[e] >= 0:
                p = int(svc.leader_np[e])
                if p not in (2, 3):   # keep corruption targets up:
                    svc.set_peer_up(e, p, False)   # down+corrupt on
                    down[e] = p       # the same copy would stack the
                                      # two nemeses past a minority
        elif r < 0.8:
            corrupt_lane()

        pending = _submit_batch(rng, svc, models, vals, vsns, seed)
        if rng.random() < 0.3:
            runtime.run_for(config.lease() * 2.5)
        _drain(svc, runtime, pending)
        _apply_outcomes(pending)

    assert corruptions_injected > 0, "corruption arm never fired"
    assert svc.corruptions > 0, \
        "no injected corruption was ever DETECTED in-round"

    # quiesce + scrub: heal peers, run the anti-entropy sweep over
    # every ensemble (the host-driven scrub the exchange flow serves),
    # then the read-back must see every acked value and the trees must
    # verify clean — healed, not blessed.
    for e, p in list(down.items()):
        svc.set_peer_up(e, p, True)
    svc.flush()
    svc.state, diverged, synced = svc.engine.exchange_step(
        svc.state, jnp.ones((N_ENS,), bool), jnp.asarray(svc.up))
    assert bool(np.asarray(synced).all())
    pending = [("get", m, None, svc.kget(e, f"key{k}"), None)
               for (e, k), m in models.items()]
    _drain(svc, runtime, pending)
    _apply_outcomes(pending)   # Violation on stale/lost reads

    node_bad, leaf_bad = eng.verify_trees(svc.state)
    # Damaged lanes on SLOTS THAT NEVER HELD DATA can survive the
    # scrub (no valid winner exists to adopt; the engine refuses to
    # bless them) — but every lane carrying committed data must have
    # healed.  Re-verify only slots with objects: leaf corruption on
    # empty slots is the one acceptable residue.
    obj_exists = np.asarray(svc.state.obj_seq) > 0      # [E, M, S]
    leaf_ok = np.asarray(
        eng.hashk.obj_leaf_hash(svc.state.obj_epoch, svc.state.obj_seq,
                                svc.state.obj_val)
        == svc.state.tree_leaf).all(-1)
    assert (leaf_ok | ~obj_exists).all(), "committed data not healed"
