"""What starts a flush on a timer-driven service: work that has
boarded, not the clock (``BatchedEnsembleService._note_arrival``).

Every case runs on both runtimes a ticked service is built on: the
virtual-time ``Runtime`` (a turn is one event of the heap) and the
asyncio ``NetRuntime`` that ``svcnode.serve`` uses (a turn is one pass
of the loop).  A turn that takes time (a front end parsing frames) is
``busy``: the virtual clock is moved by hand, the real loop sleeps.
"""

import asyncio
import time

import pytest

from riak_ensemble_tpu.config import fast_test_config
from riak_ensemble_tpu.netruntime import NetRuntime
from riak_ensemble_tpu.parallel.batched_host import BatchedEnsembleService
from riak_ensemble_tpu.runtime import Future, Runtime

#: far longer than any case runs: a flush inside it was not the timer's
NEVER = 30.0
TICK = 0.02
N_ENS = 8


class _Sim:
    """The virtual-time runtime."""

    def __init__(self) -> None:
        self.rt = Runtime(seed=37)

    def busy(self, dt: float) -> None:
        self.rt.now += dt

    def run_until(self, pred, timeout: float) -> bool:
        return self.rt.run_until(pred, max_time=timeout, poll=TICK / 40)

    def pump(self) -> None:
        self.rt.run_until_time(self.rt.now)

    def close(self) -> None:
        pass


class _Net:
    """The asyncio runtime, on a loop of the test's own."""

    def __init__(self) -> None:
        self.loop = asyncio.new_event_loop()
        self.rt = NetRuntime("n0", {"n0": ("127.0.0.1", 0)})
        self.rt.loop = self.loop

    def busy(self, dt: float) -> None:
        time.sleep(dt)

    def run_until(self, pred, timeout: float) -> bool:
        async def wait() -> bool:
            end = time.monotonic() + timeout
            while not pred():
                if time.monotonic() > end:
                    return False
                await asyncio.sleep(0.0005)
            return True
        return self.loop.run_until_complete(wait())

    def pump(self) -> None:
        # as orbax's checkpoint writer does from inside a callback
        nest_asyncio = pytest.importorskip("nest_asyncio")
        nest_asyncio.apply(self.loop)
        self.loop.run_until_complete(asyncio.sleep(0.002))

    def close(self) -> None:
        self.loop.close()


@pytest.fixture(params=[_Sim, _Net], ids=["virtual", "asyncio"])
def host(request):
    h = request.param()
    h.services = []

    def service(tick, **kw):
        svc = BatchedEnsembleService(h.rt, N_ENS, 3, 32, tick=tick,
                                     config=fast_test_config(), **kw)
        h.services.append(svc)
        return svc
    h.service = service
    yield h
    for svc in h.services:
        svc.stop()
    h.close()


def _ok(futs) -> bool:
    return all(f.done and f.value[0] == "ok" for f in futs)


def _arrivals(svc):
    return [r["arrival"] for r in svc.lat_records]


def test_lone_op_flushes_without_the_tick_elapsing(host):
    svc = host.service(NEVER)
    t0 = host.rt.now
    fut = svc.kput(0, "k", b"v")
    assert not fut.done and svc.flushes == 0   # never inside the enqueue
    assert host.run_until(lambda: fut.done, NEVER / 2)
    assert fut.value[0] == "ok"
    assert host.rt.now - t0 < NEVER / 2
    assert svc.flush_triggers == {"arrival": 1, "tick": 0, "idle": 0}
    assert _arrivals(svc) == [1]


def test_ops_enqueued_in_one_turn_board_one_flush(host):
    svc = host.service(NEVER)
    futs = []

    def turn() -> None:
        futs.extend(svc.kput(e, f"k{e}", b"v") for e in range(N_ENS))
    host.rt.defer(turn)
    assert host.run_until(lambda: futs and _ok(futs), NEVER / 2)
    assert svc.flushes == 1 and svc.ops_served == N_ENS
    assert svc.flush_triggers["arrival"] == 1


def test_what_arrives_during_a_flush_boards_the_next_at_once(host):
    svc = host.service(NEVER)
    second = []
    first = svc.kput(0, "a", b"1")
    # resolved inside flush 1: the loop is still in that flush
    first.add_waiter(lambda _r: second.append(svc.kput(1, "b", b"2")))
    assert host.run_until(lambda: second and second[0].done, NEVER / 2)
    assert _ok([first] + second)
    assert _arrivals(svc) == [1, 1]


def test_loop_turned_from_inside_a_flush_starts_no_flush_there(host):
    """A WAL compaction checkpoints from inside a loop flush, and the
    checkpoint's writer runs an event loop of its own that turns this
    one: requests are parsed and looks fire INSIDE the flush.  A flush
    started there would step the state being written."""
    svc = host.service(NEVER)
    seen = []
    maintenance = svc._flush_maintenance

    def pumping() -> None:
        if not seen:
            seen.append(svc.kput(1, "b", b"2"))
            calls = svc._flush_calls
            host.pump()
            seen.append(svc._flush_calls - calls)
        maintenance()
    svc._flush_maintenance = pumping
    first = svc.kput(0, "a", b"1")
    assert host.run_until(lambda: len(seen) == 2 and seen[0].done,
                          NEVER / 2)
    assert seen[1] == 0, "a flush ran inside a flush"
    assert _ok([first, seen[0]])
    assert _arrivals(svc) == [1, 1]     # it boarded the next, at once


def _stream(host, svc, links: int, dt: float, seen: list, futs: list,
            until=lambda: False):
    """Enqueue on every turn, each turn ``dt`` long: a front end that
    never goes quiet.  ``seen`` gets (time, flush() calls so far) a
    link."""
    def link(i: int = 0) -> None:
        seen.append((host.rt.now, svc._flush_calls))
        if i >= links or until():
            return
        futs.append(svc.kput(i % N_ENS, f"s{i}", b"v"))
        host.busy(dt)
        host.rt.defer(lambda: link(i + 1))
    link()


def test_stream_that_never_goes_quiet_flushes_at_the_tick(host):
    svc = host.service(TICK)
    assert host.run_until(lambda: svc.flushes == 1, NEVER)  # elections
    assert svc.flush_triggers == {"arrival": 0, "tick": 0, "idle": 1}
    calls0, due = svc._flush_calls, svc._timer.fire_at
    dt = TICK / 20
    seen, futs = [], []
    _stream(host, svc, 200, dt, seen, futs,
            until=lambda: svc._flush_calls > calls0)
    assert host.run_until(lambda: futs and _ok(futs), NEVER)
    # no flush while the stream ran short of the tick ...
    before = [t for t, calls in seen if calls == calls0]
    assert len(before) >= 10 and before[-1] >= due - 2 * dt
    # ... then the timer's, which took everything queued so far
    assert svc.flush_triggers["tick"] == 1
    assert _arrivals(svc)[:2] == [0, 0]
    assert svc.lat_records[1]["k"] >= 2


def test_full_queue_flushes_at_once_whatever_else_arrives(host):
    svc = host.service(NEVER, max_ops_per_tick=4)
    seen, futs = [], []
    deep = [svc.kput(0, f"d{i}", b"v") for i in range(11)]   # > 2 * max_k
    _stream(host, svc, 50, 0.0, seen, futs)
    assert host.run_until(lambda: _ok(deep) and _ok(futs), NEVER / 2)
    # the stream was still enqueuing when the full queue flushed, and
    # again for the rest of the burst: still a full launch deep, the
    # same work, not a trickle to wait out the stream
    assert len(seen) == 51
    assert seen[2][1] >= 1 and seen[4][1] >= 2
    assert [(r["arrival"], r["k"]) for r in svc.lat_records][:2] \
        == [(0, 4), (0, 4)]
    assert svc.flush_triggers["tick"] >= 2


def test_with_nothing_queued_the_timer_still_elects_and_drains(host):
    svc = host.service(TICK)
    assert host.run_until(lambda: (svc.leader_np >= 0).all(), NEVER)
    assert svc.flush_triggers == {"arrival": 0, "tick": 0, "idle": 1}
    # a leader goes down with no request in sight: the heartbeat's
    # idle flush elects again
    svc.set_peer_up(0, int(svc.leader_np[0]), False)
    old = int(svc.leader_np[0])
    assert host.run_until(lambda: svc.leader_np[0] not in (-1, old), NEVER)
    assert svc.flush_triggers["idle"] == 2
    # a parked retry has no arrival to wake it: the idle flush runs
    # it, and what it enqueues is served like any other work
    fut = []
    svc._retry_at.append((svc._flush_calls + 2, 1, Future(),
                          lambda: fut.append(svc.kput(1, "r", b"v"))))
    assert host.run_until(lambda: fut and fut[0].done, NEVER)
    assert fut[0].value[0] == "ok"


def test_tick_none_never_flushes_by_itself(host):
    svc = host.service(None)
    fut = svc.kput(0, "k", b"v")
    assert not host.run_until(lambda: fut.done, 5 * TICK)
    assert svc.flushes == 0 and not svc._look_armed
    svc.flush()
    assert fut.done and fut.value[0] == "ok"
    assert svc.flush_triggers == {"arrival": 0, "tick": 1, "idle": 0}


def test_stop_disarms_a_pending_look(host):
    svc = host.service(TICK)
    fut = svc.kput(0, "k", b"v")
    svc.stop()
    assert not host.run_until(lambda: fut.done, 5 * TICK)
    assert svc.flushes == 0 and svc._timer is None


def test_records_carry_arrival_and_triggers_add_up_to_flushes(host):
    svc = host.service(TICK)
    assert host.run_until(lambda: svc.flushes == 1, NEVER)      # idle
    lone = svc.kput(0, "k", b"v")                               # arrival
    assert host.run_until(lambda: lone.done, NEVER)
    seen, futs = [], []
    calls0 = svc._flush_calls
    _stream(host, svc, 200, TICK / 20, seen, futs,              # tick
            until=lambda: svc._flush_calls > calls0)
    assert host.run_until(lambda: futs and _ok(futs), NEVER)
    trig = svc.stats()["flush_triggers"]
    assert all(trig[t] >= 1 for t in ("arrival", "tick", "idle"))
    assert sum(trig.values()) == svc.flushes == svc.stats()["flushes"]
    assert sum(_arrivals(svc)) == trig["arrival"]
    assert set(_arrivals(svc)) == {0, 1}
