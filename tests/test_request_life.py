"""A request's life in the flush record (docs/ARCHITECTURE.md §11; obs.spans
"A request's life"): the front end's stamp of the whole frame
(``t_rx``), how long the loop had not looked at its sockets by then
(``rx_hold``, from the stamped polls of the selector loop), the rows a
sampled cycle's requests leave in the record of the flush that answers
them, the per-op plane starting at ``t_rx``, and what ``RETPU_OBS=0``
leaves of it: nothing.

Nothing here sleeps on a margin under 5x: the one timed case holds the
loop for 0.25 s and asks for 0.05 s of it.
"""

import asyncio
import socket
import struct
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import obs, svcnode, wire  # noqa: E402
from riak_ensemble_tpu.config import Config, fast_test_config  # noqa: E402
from riak_ensemble_tpu.obs.spans import PollWatch, SpanRecorder  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime)

_HDR = struct.Struct(">I")
HOLD_S = 0.25
#: a lease that outlives a first compile on this box (the verify
#: skill's wall-clock gotcha)
LEASED = dict(ensemble_tick=1.0, lease_duration=60.0,
              follower_timeout=300.0)


def frame(req_id, op, *args):
    payload = wire.encode((req_id, op) + args)
    return _HDR.pack(len(payload)) + payload


async def read_reply(loop, sock):
    head = b""
    while len(head) < 4:
        head += await loop.sock_recv(sock, 4 - len(head))
    (n,) = _HDR.unpack(head)
    body = b""
    while len(body) < n:
        body += await loop.sock_recv(sock, n - len(body))
    return wire.decode(body)


def rows_of(svc):
    return [row for r in svc.lat_records for row in r.get("reqs", ())]


async def served(fn, *, every=1, config=None, **kw):
    """``fn(server, svc, client)`` against one served service whose
    recorder samples one cycle in ``every``."""
    server = await svcnode.serve(
        4, 3, 8, port=0, tick=0.002,
        config=config or fast_test_config(), **kw)
    svc = server.svc
    svc.spans.DETAIL_EVERY = every
    client = svcnode.ServiceClient(server.host, server.port)
    await client.connect()
    try:
        # the first flushes compile: out of the way of every stamp
        for i in range(4):
            assert (await client.kput(i, "warm", b"w"))[0] == "ok"
        return await fn(server, svc, client)
    finally:
        await client.close()
        await server.stop()


def test_frame_read_behind_a_held_loop_says_how_long_it_was_held():
    """The frame is in the server's socket when a callback starts to
    hold the loop: its row's ``rx_hold_s`` has the hold, its ``t_rx``
    lies after the hold's end, so its ``residence_s`` cannot contain
    it."""
    async def drive(server, svc, _client):
        loop = asyncio.get_running_loop()
        sock = socket.create_connection((server.host, server.port))
        sock.setblocking(False)
        await asyncio.sleep(0.05)   # accepted, its reader waiting
        ends = []

        def hold():
            sock.send(frame(1, "kput", 0, "held", b"v"))
            time.sleep(HOLD_S)
            ends.append(time.perf_counter())

        n0 = len(rows_of(svc))
        loop.call_soon(hold)
        rid, result = await read_reply(loop, sock)
        assert rid == 1 and result[0] == "ok"
        sock.close()
        await asyncio.sleep(0.05)   # the record closes after its reply
        (row,) = [r for r in rows_of(svc)[n0:] if r[0] == "kput"]
        op, direct, t_rx, rx_hold_s, residence_s = row
        assert direct == 0
        assert rx_hold_s >= HOLD_S / 5
        assert t_rx >= ends[0]
        assert residence_s < HOLD_S
        hold_ms = svc.stats()["frontend"]["rx_hold_ms"]
        assert hold_ms["max"] >= 1e3 * HOLD_S / 5
        assert hold_ms["p50"] <= hold_ms["p95"] <= hold_ms["max"]

    asyncio.run(served(drive))


def test_frame_read_on_an_idle_loop_was_not_held():
    async def drive(_server, svc, client):
        await asyncio.sleep(0.1)    # idle: the loop sits in its poll
        n0 = len(rows_of(svc))
        for i in range(5):
            assert (await client.kput(1, f"k{i}", b"v"))[0] == "ok"
            await asyncio.sleep(0.02)
        await asyncio.sleep(0.05)
        rows = rows_of(svc)[n0:]
        assert len(rows) == 5
        # (a poll the loop sat in is looking, not holding)
        assert sorted(r[3] for r in rows)[2] < 0.02, rows

    asyncio.run(served(drive))


def test_rows_are_left_in_one_cycle_in_eight():
    async def drive(_server, svc, client):
        assert svc.spans.DETAIL_EVERY == 8
        n0 = len(svc.lat_records)
        for i in range(48):     # one flush, one cycle, each
            assert (await client.kput(i % 4, f"k{i % 24}", b"v"))[0] \
                == "ok"
        await asyncio.sleep(0.05)
        recs = [r for r in list(svc.lat_records)[n0:] if r.get("k")]
        with_rows = [r for r in recs if "reqs" in r]
        assert len(recs) >= 48
        assert 0 < len(with_rows) <= len(recs) // 4
        assert 0 < sum(len(r["reqs"]) for r in with_rows) <= 48 // 4
        for r in with_rows:
            # the cycle's flag decides rows and front-end spans alike
            assert "fe_decode" in r

    asyncio.run(served(drive, every=8))


def test_rows_are_left_in_every_cycle_of_a_profiler_session(tmp_path):
    async def drive(_server, svc, client):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            n0 = len(rows_of(svc))
            for i in range(12):
                assert (await client.kput(i % 4, f"s{i}", b"v"))[0] \
                    == "ok"
            await asyncio.sleep(0.05)
            assert len([r for r in rows_of(svc)[n0:]
                        if r[0] == "kput"]) >= 11
        finally:
            jax.profiler.stop_trace()

    asyncio.run(served(drive, every=8))


def test_leased_read_leaves_a_direct_row_in_the_next_flushs_record():
    async def drive(_server, svc, client):
        assert (await client.kput(2, "k", b"v"))[0] == "ok"
        hits = svc.read_fastpath_hits
        n0 = len(rows_of(svc))
        assert await client.kget(2, "k") == ("ok", b"v")
        assert svc.read_fastpath_hits == hits + 1
        # written at once: the row waits in the loop's own record ...
        assert [r[:2] for r in svc.spans.loop.get("reqs", ())] \
            == [["kget", 1]]
        assert rows_of(svc)[n0:] == []
        # ... and the flush that settles next takes it
        assert (await client.kput(2, "k2", b"w"))[0] == "ok"
        await asyncio.sleep(0.05)
        got = [r[:2] for r in rows_of(svc)[n0:]]
        assert ["kget", 1] in got and ["kput", 0] in got
        rec = [r for r in svc.lat_records if "reqs" in r][-1]
        assert all(r[4] > 0.0 and r[2] > 0.0 for r in rec["reqs"])
        # an error is a reply written at once, too
        assert await client.call("kput", 99, "k", b"v") \
            == ("error", "bad-request")
        assert svc.spans.loop["reqs"][-1][:2] == ["kput", 1]

    asyncio.run(served(drive, config=Config(**LEASED)))


def test_obs_off_takes_no_stamp_and_leaves_no_row(monkeypatch):
    monkeypatch.setenv("RETPU_OBS", "0")

    async def drive(server, svc, client):
        seen = []
        push = svc._push
        svc._push = lambda ens, op: (seen.append(svc.t_rx),
                                     push(ens, op))[1]
        for i in range(10):
            assert (await client.kput(i % 4, f"k{i}", b"v"))[0] == "ok"
        assert seen == [0.0] * 10
        assert server._poll is None and svc.rx_holds is None
        assert "rx_hold_ms" not in svc.stats()["frontend"]
        assert svc.stats()["frontend"]["frames_in"] == 14
        assert not any("reqs" in r for r in svc.lat_records)
        assert "reqs" not in svc.spans.loop

    asyncio.run(served(drive))


def test_per_op_plane_starts_at_the_socket_for_a_wire_op():
    """A wire op's ring row carries ``t_rx``, its stage ``rx`` and a
    latency counted from ``t_rx``; an in-process op on the same
    service has no ``t_rx`` and counts from its submit."""
    async def drive(_server, svc, client):
        ring = svc._slo
        n0 = ring._next
        put = svc._h_op.labels("put")
        sum0, count0 = put.sum, put.count
        assert (await client.kput(0, "wire", b"v"))[0] == "ok"
        await asyncio.sleep(0.02)
        (r,) = range(n0, ring._next)
        assert ring.t_rx[r] > 0.0
        assert ring.t_rx[r] <= ring.t_submit[r] <= ring.t_enq[r]
        view = ring.row_view(r)
        assert view["stages_ms"]["rx"] >= 0.0
        assert set(view["stages_ms"]) == set(obs.opslo.STAGES)
        from_rx = (ring.t_ack[r] - ring.t_rx[r]) * 1e3
        assert view["ms"] == pytest.approx(from_rx, abs=2e-3)
        assert put.count == count0 + 1
        assert put.sum - sum0 == pytest.approx(from_rx, rel=1e-6)
        assert svc.t_rx == 0.0      # only while a frame is dispatched

        n1 = ring._next
        fut = svc.kput(0, "inproc", b"v")
        while not fut.done:
            await asyncio.sleep(0.005)
        (r,) = range(n1, ring._next)
        assert ring.t_rx[r] == 0.0
        view = ring.row_view(r)
        assert view["stages_ms"]["rx"] == 0.0
        assert view["ms"] == pytest.approx(
            (ring.t_ack[r] - ring.t_submit[r]) * 1e3, abs=2e-3)
        assert ring.rows_of(view["flush_id"])[0]["t_rx"] == 0.0

    asyncio.run(served(drive))


def test_a_split_batch_keeps_its_t_rx():
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None,
                                 max_ops_per_tick=2)
    try:
        svc.t_rx = 123.0
        fut = svc.kput_many(0, ["a", "b", "c"], [b"1", b"2", b"3"])
        svc.t_rx = 0.0
        while not fut.done:
            svc.flush()
        ring = svc._slo
        rows = [r for r in range(ring._next) if ring.kind[r]]
        assert len(rows) == 2 and all(ring.t_rx[r] == 123.0
                                      for r in rows)
        assert len({int(ring.fid[r]) for r in rows}) == 2
    finally:
        svc.stop()


def test_close_adds_seconds_and_extends_rows():
    sp = SpanRecorder()
    sp.loop["fe_decode"] = 0.25
    sp.loop["reqs"] = [["kget", 1, 1.0, 0.0, 0.1]]
    rec = sp.begin()
    rec["fe_decode"] = 0.5
    rec["reqs"] = [["kput", 0, 2.0, 0.0, 0.2]]
    sp.close(rec)
    assert rec["fe_decode"] == 0.75
    assert [r[0] for r in rec["reqs"]] == ["kput", "kget"]
    assert sp.loop == {"starts": {}}
    # a record without rows takes the loop's list whole
    sp.loop["reqs"] = [["kget", 1, 3.0, 0.0, 0.1]]
    rec2 = sp.begin()
    sp.close(rec2)
    assert rec2["reqs"] == [["kget", 1, 3.0, 0.0, 0.1]]
    sp.loop.setdefault("reqs", []).append(["x", 1, 0.0, 0.0, 0.0])
    assert len(rec2["reqs"]) == 1   # ... and the loop starts a new one


def test_poll_watch_is_one_per_loop_and_goes_with_its_last_user():
    async def run():
        loop = asyncio.get_running_loop()
        sel = loop._selector
        plain = sel.select
        a, b = PollWatch.of(loop), PollWatch.of(loop)
        assert a is b and sel.select.__self__ is a
        base0 = a.base
        await asyncio.sleep(0.03)   # a few polls, most of it looking
        t = time.perf_counter()
        assert a.base > base0
        # time the loop sat in its poll is not a hold
        assert 0.0 <= t - a.base < 0.02
        a.release()
        assert sel.select.__self__ is a
        b.release()
        assert sel.select == plain and "select" not in vars(sel)
        assert PollWatch.of(object()) is None

    asyncio.run(run())


def test_nested_marks_leave_total_what_it_was():
    svc = BatchedEnsembleService(WallRuntime(), 4, 3, 8, tick=None)
    try:
        fut = svc.kput(0, "k", b"v")
        while not fut.done:
            svc.flush()
        rec = [r for r in svc.lat_records if r.get("k")][-1]
        # the slab's put and the launch's one program call; the call
        # of a pack program went with that program (ISSUE 46)
        nested = ("h2d_put", "dispatch_step")
        assert all(rec[m] > 0.0 and m in rec["starts"] for m in nested)
        assert "dispatch_pack" not in rec
        assert "dispatch_pack" not in rec["starts"]
        assert rec["h2d_put"] <= rec["h2d"]
        assert rec["dispatch_step"] <= rec["dispatch"]
        assert rec["uploads"] >= 1 and rec["calls"] == 1
        # the sum over the marks the parent summed, on the same flush
        parents = {"h2d", "dispatch", "device_d2h", "unpack", "wal",
                   "resolve", "queue_wait", "exchange"}
        assert rec["total"] == pytest.approx(
            sum(rec.get(m, 0.0) for m in parents))
        for m in nested + ("a", "cols", "cols_max", "shards", "reqs",
                           "uploads", "calls"):
            assert m in obs.flightrec.META_FIELDS
        # the flight ring keeps the dump's name for the width
        ring = svc.flight.records[-1]
        assert ring["a_width"] == rec["a"] and "a" not in ring
    finally:
        svc.stop()


@pytest.mark.parametrize("mesh", [False, True])
def test_stats_mesh_is_there_on_a_sharded_engine_only(mesh):
    from riak_ensemble_tpu.parallel.mesh import mesh_engine
    kw = {"engine": mesh_engine(4)} if mesh else {}
    svc = BatchedEnsembleService(WallRuntime(), 64, 3, 8, tick=None,
                                 **kw)
    try:
        for cols in ((0,), (0, 1, 2, 17)):  # elections, then four
            futs = [svc.kput(c, "k", b"v") for c in cols]
            while not all(f.done for f in futs):
                svc.flush()
        stats = svc.stats()
        assert ("mesh" in stats) == mesh
        if not mesh:
            return
        m = stats["mesh"]
        assert m["shards"] == 4
        launches = (m["launches_sliced"] + m["launches_pack_gathered"]
                    + m["launches_full_grid"])
        assert launches == stats["launches_sliced"] \
            + stats["launches_unsliced"] >= 2
        # the election flush ran the full grid; the next gathered
        # three columns of shard 0 and one of shard 1 into 4 x 8
        assert m["launches_full_grid"] >= 1
        assert m["launches_pack_gathered"] >= 1
        recs = [r for r in svc.lat_records if r.get("a")]
        want_busy = np.mean([r["cols_max"] / r["cols"] for r in recs])
        want_pad = np.mean([1 - r["cols"] / (r["shards"] * r["a"])
                            for r in recs])
        assert m["busiest_shard_share"] == pytest.approx(want_busy)
        assert m["pad_share"] == pytest.approx(want_pad)
        assert 0.25 <= m["busiest_shard_share"] <= 1.0
        assert 0.0 <= m["pad_share"] < 1.0
    finally:
        svc.stop()
