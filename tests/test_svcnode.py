"""Network front-end for the batched service (svcnode): remote
clients reach the engine-backed K/V plane over TCP with the
restricted wire codec — the scale-path analog of netnode."""

import asyncio
import struct

import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import svcnode  # noqa: E402
from riak_ensemble_tpu.config import fast_test_config  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402


def test_svcnode_end_to_end():
    async def scenario():
        server = await svcnode.serve(4, 3, 8, port=0,
                                     config=fast_test_config())
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()

        r = await c.kput(0, "k", b"v1")
        assert r[0] == "ok"
        vsn = tuple(r[1])
        assert await c.kget(0, "k") == ("ok", b"v1")
        r = await c.kupdate(0, "k", vsn, b"v2")
        assert r[0] == "ok"
        assert await c.kget(0, "k") == ("ok", b"v2")
        r = await c.kget_vsn(0, "k")
        assert r[0] == "ok" and r[1] == b"v2"
        r = await c.ksafe_delete(0, "k", tuple(r[2]))
        assert r[0] == "ok"  # CAS-to-tombstone acks with the new vsn
        assert await c.kget(0, "k") == ("ok", NOTFOUND)
        assert await c.kdelete(0, "nope") == ("ok", NOTFOUND)

        # pipelining: many in-flight ops, out-of-order-safe by req id
        puts = [c.kput(e, f"p{i}", b"x%d" % i)
                for e in range(4) for i in range(5)]
        results = await asyncio.gather(*puts)
        assert all(r[0] == "ok" for r in results)
        gets = [c.kget(e, f"p{i}") for e in range(4) for i in range(5)]
        results = await asyncio.gather(*gets)
        assert [r[1] for r in results] == \
            [b"x%d" % i for _e in range(4) for i in range(5)]

        st = await c.stats()
        assert st["ops_served"] > 0 and st["ensembles_with_leader"] >= 1

        # the runtime-controller audit verb (ARCHITECTURE §14): the
        # health section + the decision journal, wire-encodable; a
        # stock boot is observe-only with an empty journal
        ctl = await c.controller()
        assert ctl["controller"]["enabled"] is False
        assert ctl["controller"]["pipeline_depth"] >= 1
        assert ctl["decisions"] == []
        h = await c.health()
        assert h["controller"] == ctl["controller"]

        # unknown op answers, connection stays usable
        assert await c.call("bogus-op") == ("error", "unknown-op")
        assert await c.kget(1, "p0") == ("ok", b"x0")

        # ensemble index is untrusted input: negative (would alias
        # via Python indexing) and out-of-range reject cleanly, as
        # does wrong arity — and the connection survives all three
        assert await c.call("kput", -1, "k", b"v") == \
            ("error", "bad-request")
        assert await c.call("kput", 99, "k", b"v") == \
            ("error", "bad-request")
        assert await c.call("kput", 0) == ("error", "bad-request")
        assert await c.kget(1, "p0") == ("ok", b"x0")

        await c.close()
        await server.stop()

    asyncio.run(scenario())


def test_svcnode_hostile_frames_drop_connection_only():
    async def scenario():
        server = await svcnode.serve(2, 3, 4, port=0,
                                     config=fast_test_config())
        # hostile: garbage payload -> server drops THIS connection
        reader, writer = await asyncio.open_connection(
            server.host, server.port)
        junk = b"\x93\x01\x02pickle-ish\xff"
        writer.write(struct.pack(">I", len(junk)) + junk)
        await writer.drain()
        assert await reader.read(1) == b""  # server closed it
        writer.close()

        # hostile: absurd length prefix -> dropped without allocation
        reader, writer = await asyncio.open_connection(
            server.host, server.port)
        writer.write(struct.pack(">I", (1 << 31) - 1))
        await writer.drain()
        assert await reader.read(1) == b""
        writer.close()

        # a well-behaved client is unaffected
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        assert (await c.kput(0, "k", b"v"))[0] == "ok"
        assert await c.kget(0, "k") == ("ok", b"v")
        await c.close()
        await server.stop()

    asyncio.run(scenario())


def _frame(msg):
    from riak_ensemble_tpu import wire

    payload = wire.encode(msg)
    return struct.pack(">I", len(payload)) + payload


def test_svcnode_inflight_backpressure_bounds_queued_ops(monkeypatch):
    """A client pipelining thousands of ops can never hold more than
    _MAX_INFLIGHT unresolved at the server (the read loop blocks on
    the semaphore; TCP flow control pushes back) — and the pipeline
    still completes exactly."""
    monkeypatch.setattr(svcnode, "_MAX_INFLIGHT", 8)

    async def scenario():
        server = await svcnode.serve(2, 3, 64, port=0,
                                     config=fast_test_config())
        svc = server.svc
        orig_flush = svc.flush
        seen = []

        def spy_flush():
            seen.append(sum(len(q) for q in svc.queues))
            return orig_flush()
        svc.flush = spy_flush

        reader, writer = await asyncio.open_connection(
            server.host, server.port)
        n = 400
        for i in range(n):
            writer.write(_frame((i, "kput", i % 2, f"k{i % 16}",
                                 b"v%d" % i)))
        await writer.drain()
        # read every response (order may interleave; correlate by id)
        got = set()
        while len(got) < n:
            head = await asyncio.wait_for(
                reader.readexactly(4), timeout=30)
            (length,) = struct.unpack(">I", head)
            frame = await asyncio.wait_for(
                reader.readexactly(length), timeout=30)
            from riak_ensemble_tpu import wire
            req_id, result = wire.decode(frame)
            assert result[0] == "ok", (req_id, result)
            got.add(req_id)
        assert got == set(range(n))
        # the cap held at every flush
        assert seen and max(seen) <= 8, max(seen)
        writer.close()
        await server.stop()

    asyncio.run(scenario())


def test_svcnode_nonreading_client_dropped_not_buffered(monkeypatch):
    """A client that pipelines reads but never drains its socket is
    disconnected once the server-side write buffer passes the cap —
    bounded memory — while a well-behaved client stays served."""
    monkeypatch.setattr(svcnode, "_MAX_WRITE_BUF", 4096)

    async def scenario():
        server = await svcnode.serve(1, 3, 4, port=0,
                                     config=fast_test_config())
        good = svcnode.ServiceClient(server.host, server.port)
        await good.connect()
        big = b"x" * 8192
        assert (await good.kput(0, "k", big))[0] == "ok"

        reader, writer = await asyncio.open_connection(
            server.host, server.port)
        # hostile: request far more response bytes than the cap and
        # never read them
        for i in range(2000):
            writer.write(_frame((i, "kget", 0, "k")))
        try:
            await writer.drain()
        except ConnectionError:
            pass  # already dropped mid-send: that's the point
        # Don't read while the responses pile up: give the server time
        # to exceed the cap and abort (RST discards the kernel receive
        # queue; only the small already-pulled StreamReader buffer can
        # still hand out bytes), THEN drain until the reset/EOF
        # surfaces.
        await asyncio.sleep(10)
        dropped = False
        for _ in range(60):
            try:
                b = await asyncio.wait_for(reader.read(1 << 20),
                                           timeout=2.0)
            except asyncio.TimeoutError:
                continue
            except ConnectionError:
                dropped = True
                break
            if b == b"":
                dropped = True
                break
        assert dropped, "non-reading client was never disconnected"
        writer.close()

        # the good client is unaffected
        assert await good.kget(0, "k") == ("ok", big)
        await good.close()
        await server.stop()

    asyncio.run(scenario())


def test_svcnode_batch_ops_over_the_wire():
    """kput_many/kget_many ride the TCP protocol: one frame, one
    response carrying the per-key result list in order."""
    async def scenario():
        server = await svcnode.serve(2, 3, 32, port=0,
                                     config=fast_test_config())
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        keys = [f"k{i}" for i in range(10)]
        res = await c.kput_many(1, keys, [b"v%d" % i for i in range(10)])
        assert len(res) == 10 and all(r[0] == "ok" for r in res)
        got = await c.kget_many(1, keys + ["nope"])
        assert [r[1] for r in got[:10]] == [b"v%d" % i for i in range(10)]
        assert got[10] == ("ok", NOTFOUND)
        # CAS + delete batches over the wire
        up = await c.kupdate_many(1, [keys[0]], [tuple(res[0][1])],
                                  [b"up0"])
        assert up[0][0] == "ok"
        assert await c.kget(1, keys[0]) == ("ok", b"up0")
        dl = await c.kdelete_many(1, [keys[1], "nope"])
        assert dl[0][0] == "ok" and dl[1] == ("ok", NOTFOUND)
        assert await c.kget(1, keys[1]) == ("ok", NOTFOUND)
        # versioned batch reads over the wire
        gv = await c.kget_many(1, [keys[0], "nope"], want_vsn=True)
        assert gv[0][:2] == ("ok", b"up0") and len(gv[0]) == 3
        assert gv[1] == ("ok", NOTFOUND, (0, 0))
        # bad ensemble index still rejected cleanly
        assert (await c.kput_many(-1, ["k"], [b"v"]))[0] == "error"
        await c.close()
        await server.stop()

    asyncio.run(scenario())


def test_svcnode_slab_verbs_and_fallback():
    """The zero-copy slab lane (kput_slab/kget_slab): all-str-ascii /
    all-bytes batches ride it transparently through the client's
    kput_many/kget_many; exotic batches (non-ascii keys, non-bytes
    payloads) fall back to the legacy list verbs with identical
    results; malformed slab tables answer bad-request without
    dropping the connection."""
    import numpy as np

    from riak_ensemble_tpu import wire

    async def scenario():
        server = await svcnode.serve(2, 3, 32, port=0,
                                     config=fast_test_config())
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        # slab route (asserted: the client really built a slab frame)
        assert c._key_slab(["a", "bb"]) is not None
        res = await c.kput_many(0, ["a", "bb"], [b"1", b"22"])
        assert [r[0] for r in res] == ["ok", "ok"]
        got = await c.kget_many(0, ["a", "bb", "zz"], want_vsn=True)
        assert got[0][:2] == ("ok", b"1") and len(got[0]) == 3
        assert got[2] == ("ok", NOTFOUND, (0, 0))
        # exotic batches bypass the slab subset, same results
        assert c._key_slab(["κλειδί"]) is None
        res = await c.kput_many(0, ["κλειδί", "plain"],
                                [b"nb", b"pv"])
        assert [r[0] for r in res] == ["ok", "ok"]
        assert await c.kget_many(0, ["κλειδί"]) == [("ok", b"nb")]
        res = await c.kput_many(0, ["obj"], ["not-bytes"])
        assert res[0][0] == "ok"
        assert await c.kget_many(0, ["obj"]) == [("ok", "not-bytes")]
        # hostile slab: length table exceeding its arena answers
        # bad-request (trust boundary), connection stays usable
        bad = await c.call_parts(
            "kput_slab", 0,
            wire.Raw(np.asarray([5], np.int32)), wire.Raw(b"ab"),
            wire.Raw(np.asarray([1], np.int32)), wire.Raw(b"x"))
        assert bad == ("error", "bad-request")
        bad = await c.call_parts(
            "kget_slab", 0,
            wire.Raw(np.asarray([-1], np.int32)), wire.Raw(b""))
        assert bad == ("error", "bad-request")
        assert await c.kget(0, "a") == ("ok", b"1")
        await c.close()
        await server.stop()

    asyncio.run(scenario())


def test_svcnode_restart_adopts_persisted_dynamic_mode(tmp_path):
    """advice r3 (medium): restarting a --dynamic-persisted data_dir
    WITHOUT re-passing --dynamic must adopt the persisted mode (the
    restore docstring's 'persisted lifecycle mode WINS'), not crash at
    startup; an explicitly contradictory flag still fails loudly."""
    data = str(tmp_path / "d")

    async def first_boot():
        server = await svcnode.serve(4, 3, 8, port=0,
                                     config=fast_test_config(),
                                     dynamic=True, data_dir=data)
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        assert (await c.create_ensemble("tenant"))[0] == "ok"
        r = await c.resolve_ensemble("tenant")
        assert r[0] == "ok"
        ens = r[1]
        assert (await c.kput(ens, "k", b"v"))[0] == "ok"
        await c.close()
        await server.stop()

    async def restart_without_flag():
        # the operator restart path: no dynamic flag at all
        server = await svcnode.serve(4, 3, 8, port=0,
                                     config=fast_test_config(),
                                     data_dir=data)
        assert server.svc.dynamic is True  # persisted mode adopted
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        r = await c.resolve_ensemble("tenant")
        assert r[0] == "ok"
        assert await c.kget(r[1], "k") == ("ok", b"v")
        await c.close()
        await server.stop()

    asyncio.run(first_boot())
    asyncio.run(restart_without_flag())

    # a static-persisted dir restarted with an EXPLICIT --dynamic
    # still errors loudly (the mismatch is a genuine operator bug)
    static_dir = str(tmp_path / "s")

    async def static_boot():
        server = await svcnode.serve(2, 3, 4, port=0,
                                     config=fast_test_config(),
                                     data_dir=static_dir)
        await server.stop()

    async def conflicting_restart():
        with pytest.raises(ValueError):
            await svcnode.serve(2, 3, 4, port=0,
                                config=fast_test_config(),
                                dynamic=True, data_dir=static_dir)

    # ...and the False direction: an embedder explicitly asserting
    # static over a dynamic-persisted dir must ALSO error, not
    # silently come up dynamic (the tri-state contract)
    async def conflicting_static_assertion():
        with pytest.raises(ValueError):
            await svcnode.serve(4, 3, 8, port=0,
                                config=fast_test_config(),
                                dynamic=False, data_dir=data)

    asyncio.run(static_boot())
    asyncio.run(conflicting_restart())
    asyncio.run(conflicting_static_assertion())
