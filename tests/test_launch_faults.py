"""A launch that fails, on every arm of the one door into the step.

{one chip sliced, one chip full width, 'ens'-sharded mesh on four
virtual devices at the full grid, the same mesh sliced per shard} x
{the device fails inside the step, the packed result's fetch fails} x
{state not donated, donated}.  The fault stands
in the way of whichever program the flush dispatches
(``testing.wrap_engine_steps``), so these fail, roll back and poison
on the path that serves.

What holds on every arm: the flush raises the error and its futures
resolve 'failed' (no client blocks on a dead launch); the leader and
lease mirrors are what they were before the launch.

Not donated: the device state rolls back to the pre-launch snapshot
too, and the next flush commits and reads back.

Donated (ROADMAP D3 decides whether this is right; this file is the
record of what happens today): the snapshot's buffers were consumed,
so there is nothing to roll back to and ONE ``svc_state_poisoned``
event says so.
- A failure inside the step leaves ``svc.state`` naming the consumed
  buffers: every later launch raises and fails its ops until a restart
  or ``restore()``.  (On the mesh the test stops at the event: a
  launch that hands one shard a deleted buffer wedges the CPU client
  for the whole process.)
- A failure at the fetch comes after the step ran: ``svc.state`` is the
  stepped state, so the service keeps serving, and the failed flush's
  writes are on the device although their clients were told 'failed'
  (an ambiguous outcome, as a timeout is): the next write of the key
  commits one seq further on.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    SLICE_MIN_E, BatchedEnsembleService, WallRuntime, _LocalEngine)
from riak_ensemble_tpu.parallel.mesh import mesh_engine  # noqa: E402
from riak_ensemble_tpu.testing import wrap_engine_steps  # noqa: E402

COLS = (1, 7, 40)
#: n_ens per arm: the sliced arms' three columns bucket to A = 8, at
#: most a quarter of what one chip (or one of the mesh's four) holds
N_ENS = {"sliced": SLICE_MIN_E, "full": 64, "mesh4": 64,
         "mesh4-sliced": 4 * SLICE_MIN_E}


class _Fault:
    """Arms one failure: ``step`` raises after the program ran (a
    donated input is gone by then, as when the device fails inside it);
    ``fetch`` lets the launch through and fails its packed result's
    fetch in the resolve half."""

    def __init__(self, where: str) -> None:
        self.where = where
        self.armed = False
        self.fail_fetch = False
        self.sliced = []     # of the launches it failed

    def around(self, inner, state, slab, up, sliced):
        out = inner(state, slab, up)
        if self.armed:
            self.armed = False
            self.sliced.append(sliced)
            if self.where == "step":
                raise RuntimeError("injected step failure")
            self.fail_fetch = True
        return out

    def patch_fetch(self, svc) -> None:
        fetch = svc._fetch_packed

        def fetch_or_fail(fl):
            if self.fail_fetch:
                self.fail_fetch = False
                raise RuntimeError("injected fetch failure")
            return fetch(fl)
        svc._fetch_packed = fetch_or_fail


def _settle(svc, futs):
    for _ in range(10):
        if all(f.done for f in futs):
            break
        svc.flush()
    return [f.value for f in futs]


@pytest.mark.parametrize("donate", [False, True],
                         ids=["undonated", "donated"])
@pytest.mark.parametrize("where", ["step", "fetch"])
@pytest.mark.parametrize("shape", list(N_ENS))
def test_failed_launch(shape, where, donate, monkeypatch):
    monkeypatch.setenv("RETPU_DONATE", "1" if donate else "0")
    fault = _Fault(where)
    runtime = WallRuntime()
    events = []
    runtime.trace = lambda kind, payload: events.append(kind)
    mesh = shape.startswith("mesh4")
    base = mesh_engine(4) if mesh else _LocalEngine()
    svc = BatchedEnsembleService(
        runtime, N_ENS[shape], 3, 8, tick=None,
        engine=wrap_engine_steps(base, fault.around))
    fault.patch_fetch(svc)
    try:
        assert svc._donate is donate
        ok = _settle(svc, [svc.kput(c, "a", b"1") for c in COLS])
        assert [v[0] for v in ok] == ["ok"] * 3, ok
        state0 = svc.state
        leader0 = svc.leader_np.copy()
        lease0 = svc.lease_until.copy()

        fault.armed = True
        futs = [svc.kput(c, "b", b"2") for c in COLS]
        with pytest.raises(RuntimeError, match=f"injected {where}"):
            svc.flush()
        assert [f.done and f.value for f in futs] == ["failed"] * 3
        assert fault.sliced == [shape.endswith("sliced")]
        np.testing.assert_array_equal(svc.leader_np, leader0)
        np.testing.assert_array_equal(svc.lease_until, lease0)
        poisoned = events.count("svc_state_poisoned")

        if not donate:
            assert poisoned == 0
            assert svc.state is state0, "state was not rolled back"
            seq = 2      # the failed flush's seq was never spent
        else:
            assert poisoned == 1
            assert state0.epoch.is_deleted(), "donation consumed nothing"
            if where == "step":
                assert svc.state is state0   # the consumed buffers
                if mesh:
                    return   # see the module docstring
                f = svc.kput(COLS[0], "b", b"3")
                with pytest.raises(Exception, match="buffer|deleted"):
                    svc.flush()
                assert f.done and f.value == "failed"
                return
            assert svc.state is not state0   # the stepped state
            seq = 3      # ...in which the 'failed' writes committed

        vals = _settle(svc, [svc.kput(c, "b", b"3") for c in COLS])
        assert vals == [("ok", (1, seq))] * 3, vals
        assert _settle(svc, [svc.kget(c, "b") for c in COLS]) == [
            ("ok", b"3")] * 3
        assert events.count("svc_state_poisoned") == poisoned
    finally:
        svc.stop()
