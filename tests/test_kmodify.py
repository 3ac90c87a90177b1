"""kmodify on the batched service (review r3 #6): server-side
read→fn→CAS retry with the actor plane's funref/MFA discipline
(riak_ensemble_peer.erl:303-317, do_modify_fsm :1404-1416;
riak_ensemble_root.erl:74-90 runs all cluster ops through it)."""

import asyncio

import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import funref, svcnode  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.testing import Cluster, make_peers  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402


@funref.register("test:incr")
def _incr(vsn, cur):
    return (int.from_bytes(cur, "big") + 1).to_bytes(4, "big")


@funref.register("test:fail-if-set")
def _fail_if_set(vsn, cur):
    return "failed" if cur != b"\0\0\0\0" else b"\0\0\0\1"


def _svc(tick=None, **kw):
    runtime = Runtime(seed=7)
    svc = BatchedEnsembleService(runtime, 2, 3, n_slots=8, tick=tick,
                                 config=fast_test_config(), **kw)
    return runtime, svc


def _drive(runtime, svc, futs, flushes=40):
    for _ in range(flushes):
        if all(f.done for f in futs):
            break
        svc.flush()
    assert all(f.done for f in futs)
    return [f.value for f in futs]


def test_kmodify_basic_and_default():
    runtime, svc = _svc()
    # absent key: fn sees the default
    f = svc.kmodify(0, "ctr", ("fn", "test:incr", ()),
                    (0).to_bytes(4, "big"))
    _drive(runtime, svc, [f])
    assert f.value[0] == "ok"
    g = svc.kget(0, "ctr")
    _drive(runtime, svc, [g])
    assert g.value == ("ok", (1).to_bytes(4, "big"))
    # present key: fn sees the committed value
    f = svc.kmodify(0, "ctr", ("fn", "test:incr", ()),
                    (0).to_bytes(4, "big"))
    _drive(runtime, svc, [f])
    assert f.value[0] == "ok"
    g = svc.kget(0, "ctr")
    _drive(runtime, svc, [g])
    assert g.value == ("ok", (2).to_bytes(4, "big"))


def test_kmodify_concurrent_increments_serialize():
    """N concurrent kmodifys of one key: all read the same version in
    the first flush, one CAS per device round wins, the losers retry
    — the final value must be exactly +N (the seq discipline the
    reference gets from running the fun inside the leader FSM)."""
    runtime, svc = _svc()
    zero = (0).to_bytes(4, "big")
    futs = [svc.kmodify(0, "ctr", ("fn", "test:incr", ()), zero)
            for _ in range(5)]
    _drive(runtime, svc, futs)
    assert all(f.value[0] == "ok" for f in futs), [f.value for f in futs]
    # all five acked versions are distinct (each saw a unique commit)
    assert len({tuple(f.value[1]) for f in futs}) == 5
    g = svc.kget(0, "ctr")
    _drive(runtime, svc, [g])
    assert g.value == ("ok", (5).to_bytes(4, "big"))


def test_kmodify_fn_abort_and_errors_write_nothing():
    runtime, svc = _svc()
    zero = (0).to_bytes(4, "big")
    f = svc.kmodify(0, "k", ("fn", "test:fail-if-set", ()), b"\0\0\0\7")
    _drive(runtime, svc, [f])
    assert f.value == "failed"
    g = svc.kget(0, "k")
    _drive(runtime, svc, [g])
    assert g.value == ("ok", NOTFOUND)  # aborted modify wrote nothing
    # unregistered funref name: immediate clean failure
    f = svc.kmodify(0, "k", ("fn", "no:such", ()), zero)
    assert f.done and f.value == "failed"

    # a raising mod_fun is contained (traced), resolves 'failed'
    def boom(vsn, cur):
        raise RuntimeError("user bug")
    f = svc.kmodify(0, "k", boom, zero)
    _drive(runtime, svc, [f])
    assert f.value == "failed"
    g = svc.kget(0, "k")
    _drive(runtime, svc, [g])
    assert g.value == ("ok", NOTFOUND)


def test_kmodify_over_the_wire():
    """svcnode ships the funref as plain data; the SERVER's registry
    resolves it (root.erl:82,104 MFA discipline — no code on the
    wire)."""
    async def scenario():
        server = await svcnode.serve(2, 3, 8, port=0,
                                     config=fast_test_config())
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        zero = (0).to_bytes(4, "big")
        r = await c.kmodify(0, "ctr", funref.ref("test:incr"), zero)
        assert r[0] == "ok", r
        r = await c.kmodify(0, "ctr", funref.ref("test:incr"), zero)
        assert r[0] == "ok", r
        assert await c.kget(0, "ctr") == ("ok", (2).to_bytes(4, "big"))
        # unregistered name fails cleanly, connection survives
        r = await c.call("kmodify", 0, "ctr", ("fn", "no:such", ()),
                         zero)
        assert r == "failed"
        assert await c.kget(0, "ctr") == ("ok", (2).to_bytes(4, "big"))
        await c.close()
        await server.stop()

    asyncio.run(scenario())


def test_kmodify_parity_with_actor_plane():
    """Same observable semantics as the actor stack's kmodify: an
    increment chain over an absent key converges identically."""
    c = Cluster(seed=3)
    peers = make_peers(3)
    c.create_ensemble("e", peers)
    c.wait_stable("e")

    for expect in (1, 2, 3):
        r = c.kmodify("e", "ctr", lambda vsn, v: v + 1, 0)
        assert isinstance(r, tuple) and r[0] == "ok", r
        assert r[1].value == expect
        assert c.kget_value("e", "ctr") == expect

    runtime, svc = _svc()
    zero = NOTFOUND

    def incr_svc(vsn, cur):
        base = 0 if cur is NOTFOUND else int.from_bytes(cur, "big")
        return (base + 1).to_bytes(4, "big")

    for expect in (1, 2, 3):
        f = svc.kmodify(0, "ctr", incr_svc, NOTFOUND)
        _drive(runtime, svc, [f])
        assert f.value[0] == "ok"
        g = svc.kget(0, "ctr")
        _drive(runtime, svc, [g])
        assert g.value == ("ok", expect.to_bytes(4, "big"))
