"""Quorum predicate: hand cases + scalar-vs-batched differential test.

The scalar version encodes riak_ensemble_msg:quorum_met/5 semantics
(msg.erl:377-418); the batched kernel must agree on every input.
"""

import random

import numpy as np
import pytest

from riak_ensemble_tpu.ops.quorum import (
    MET, UNDECIDED, NACK, quorum_met, quorum_met_batch, views_to_mask,
)


def P(i):
    return ("p%d" % i, "node%d" % i)


class TestScalar:
    def test_empty_views_met(self):
        assert quorum_met([], P(0), []) == MET

    def test_self_counts(self):
        # 3 members, self is one: one more valid reply reaches 2/3 quorum.
        views = [[P(0), P(1), P(2)]]
        assert quorum_met([], P(0), views) == UNDECIDED
        assert quorum_met([(P(1), "ok")], P(0), views) == MET

    def test_self_not_member(self):
        views = [[P(1), P(2), P(3)]]
        assert quorum_met([(P(1), "ok")], P(0), views) == UNDECIDED
        assert quorum_met([(P(1), "ok"), (P(2), "ok")], P(0), views) == MET

    def test_other_mode_excludes_self(self):
        # 'other': majority excluding self (exchange uses this when its
        # own tree is untrusted).
        views = [[P(0), P(1), P(2)]]
        assert quorum_met([(P(1), "ok")], P(0), views, "other") == UNDECIDED
        assert quorum_met([(P(1), "ok"), (P(2), "ok")], P(0), views,
                          "other") == MET

    def test_all_mode(self):
        views = [[P(0), P(1), P(2)]]
        r = [(P(1), "ok")]
        assert quorum_met(r, P(0), views, "all") == UNDECIDED
        r = [(P(1), "ok"), (P(2), "ok")]
        assert quorum_met(r, P(0), views, "all") == MET

    def test_nack_majority(self):
        views = [[P(0), P(1), P(2)]]
        r = [(P(1), "nack"), (P(2), "nack")]
        assert quorum_met(r, P(0), views) == NACK

    def test_all_heard_no_quorum_nacks(self):
        # 5 members, self + 1 valid + 3 nacks = everyone heard, quorum
        # (3) not met -> NACK via the heard+nacks==members branch.
        views = [[P(0), P(1), P(2), P(3), P(4)]]
        r = [(P(1), "ok"), (P(2), "nack"), (P(3), "nack"), (P(4), "nack")]
        assert quorum_met(r, P(0), views) == NACK

    def test_joint_views_all_must_meet(self):
        v1 = [P(0), P(1), P(2)]
        v2 = [P(3), P(4), P(5)]
        r = [(P(1), "ok")]
        assert quorum_met(r, P(0), [v1, v2]) == UNDECIDED
        r = [(P(1), "ok"), (P(3), "ok"), (P(4), "ok")]
        assert quorum_met(r, P(0), [v1, v2]) == MET

    def test_joint_later_view_nack_hidden_by_earlier_undecided(self):
        # Reference recursion: if view 1 is undecided it never looks at
        # view 2, so a nack-failing later view still reports UNDECIDED.
        v1 = [P(0), P(1), P(2)]
        v2 = [P(3), P(4), P(5)]
        r = [(P(3), "nack"), (P(4), "nack")]
        assert quorum_met(r, P(0), [v1, v2]) == UNDECIDED
        # Once view 1 met, view 2's nacks surface.
        r += [(P(1), "ok")]
        assert quorum_met(r, P(0), [v1, v2]) == NACK


class TestBatchedDifferential:
    @pytest.mark.parametrize("required", ["quorum", "all", "all_or_quorum",
                                          "other"])
    def test_random_agreement(self, required):
        from riak_ensemble_tpu.ops.quorum import REQUIRED_MODES
        rng = random.Random(1000 + REQUIRED_MODES.index(required))
        M, V = 7, 3
        peers = [P(i) for i in range(M)]
        for trial in range(200):
            n_views = rng.randint(1, V)
            views_idx = []
            for _ in range(n_views):
                size = rng.randint(1, M)
                views_idx.append(sorted(rng.sample(range(M), size)))
            self_i = rng.randrange(-1, M)
            self_id = peers[self_i] if self_i >= 0 else ("nobody", "x")
            # Random reply pattern: each peer unheard / valid / nack.
            valid = np.zeros(M, bool)
            nack = np.zeros(M, bool)
            replies = []
            for i in range(M):
                roll = rng.random()
                if peers[i] == self_id:
                    continue  # self never replies to itself via transport
                if roll < 0.4:
                    valid[i] = True
                    replies.append((peers[i], "ok"))
                elif roll < 0.6:
                    nack[i] = True
                    replies.append((peers[i], "nack"))
            views = [[peers[i] for i in vi] for vi in views_idx]
            expect = quorum_met(replies, self_id, views, required)
            mask = views_to_mask(views_idx, V, M)
            got = int(quorum_met_batch(valid, nack, mask,
                                       np.int32(self_i), required))
            assert got == expect, (
                f"trial={trial} views={views_idx} self={self_i} "
                f"valid={valid} nack={nack} expect={expect} got={got}")
            # met_only: the same MET, and a NACK told as UNDECIDED
            met = int(quorum_met_batch(valid, nack, mask,
                                       np.int32(self_i), required,
                                       met_only=True))
            assert met == (MET if expect == MET else UNDECIDED), (
                trial, expect, met)

    def test_vmapped_batch_shape(self):
        E, V, M = 32, 2, 5
        rng = np.random.RandomState(0)
        valid = rng.rand(E, M) < 0.5
        nack = (~valid) & (rng.rand(E, M) < 0.3)
        mask = np.zeros((E, V, M), bool)
        mask[:, 0, :] = True
        self_idx = np.zeros(E, np.int32)
        out = quorum_met_batch(valid, nack, mask, self_idx)
        assert out.shape == (E,)
        met = quorum_met_batch(valid, nack, mask, self_idx, met_only=True)
        np.testing.assert_array_equal(np.asarray(met) == MET,
                                      np.asarray(out) == MET)
        assert NACK in np.asarray(out) and NACK not in np.asarray(met)
        for e in range(E):
            peers = [P(i) for i in range(M)]
            replies = [(peers[i], "ok") for i in range(M) if valid[e, i]]
            replies += [(peers[i], "nack") for i in range(M) if nack[e, i]]
            assert int(out[e]) == quorum_met(replies, peers[0],
                                             [peers], "quorum")


class TestExtraCheck:
    def test_extra_gates_met(self):
        # extra evaluated only once all views met (msg.erl:382-388);
        # False maps to UNDECIDED (keep collecting), never NACK.
        views = [[P(0), P(1), P(2)]]
        replies = [(P(1), "obj")]
        assert quorum_met(replies, P(0), views, "quorum",
                          extra=lambda rs: False) == UNDECIDED
        assert quorum_met(replies, P(0), views, "quorum",
                          extra=lambda rs: True) == MET

    def test_extra_not_consulted_before_views_met(self):
        views = [[P(0), P(1), P(2)]]
        calls = []

        def extra(rs):
            calls.append(rs)
            return True

        assert quorum_met([], P(0), views, "quorum", extra=extra) == UNDECIDED
        assert calls == []

    def test_extra_receives_all_replies_unfiltered(self):
        # The reference passes the full reply list (incl. non-members
        # and nacks) to Extra (msg.erl:382-388).
        views = [[P(0), P(1)]]
        replies = [(P(1), "obj"), (P(9), "stranger"), (P(1), "nack")]
        seen = []
        quorum_met(replies, P(0), views, "quorum",
                   extra=lambda rs: seen.append(list(rs)) or True)
        assert seen and seen[0] == replies


def test_lazy_collector_releases_owner_monitor():
    """Every lazy_send_all (ping_quorum) must drop its owner-death
    monitor when the collector finishes, or a long-lived leader
    accumulates one dead closure per call forever."""
    from riak_ensemble_tpu.peer import peer_name
    from riak_ensemble_tpu.testing import Cluster, make_peers

    c = Cluster(seed=23)
    peers = make_peers(3)
    c.create_ensemble("ens", peers)
    leader = c.wait_stable("ens")
    lname = peer_name("ens", leader)

    def n_monitors():
        return len(c.runtime._monitors.get(lname, []))

    from riak_ensemble_tpu.peer import sync_send_event

    base = n_monitors()
    for _ in range(10):
        r = sync_send_event(c.runtime, lname, ("ping_quorum",),
                            timeout=10.0)
        assert len(r) >= 2, r
    c.runtime.run_for(1.0)
    assert n_monitors() <= base + 1, (base, n_monitors())
