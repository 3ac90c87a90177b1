"""Proxy/ingress tier (docs/ARCHITECTURE.md §16): svcnode-protocol
forwarding through a stateless hop.

Covers the slab-verb edge cases the forwarding hop must not disturb —
empty batches, a client frame at EXACTLY the max-frame boundary
(and one byte over), non-ascii key batches falling back to the
legacy list verbs — plus the leader-discovery story: a proxy racing
a leader step-down re-resolves on the not-leader rejection and
retries transparently, and the reconnect satellite on
:class:`ServiceClient` survives a dropped socket.
"""

import asyncio
import json
import os
import socket
import struct
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

from riak_ensemble_tpu import svcnode, wire  # noqa: E402
from riak_ensemble_tpu import proxy as proxy_mod  # noqa: E402
from riak_ensemble_tpu.config import Config, fast_test_config  # noqa: E402
from riak_ensemble_tpu.parallel import repgroup  # noqa: E402
from riak_ensemble_tpu.svcnode import _HDR, _MAX_FRAME  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402


def test_proxy_forwards_all_verbs_and_slab_edges():
    """One svcnode + one proxy: the whole keyed surface forwards,
    the slab lane survives the hop (including empty batches and the
    non-ascii fallback to list verbs), notfound stays authoritative,
    and proxy_stats counts the traffic."""
    async def scenario():
        server = await svcnode.serve(4, 3, 8, port=0,
                                     config=fast_test_config())
        px = await proxy_mod.serve_proxy([(server.host, server.port)])
        c = svcnode.ServiceClient(px.host, px.port)
        await c.connect()

        r = await c.kput(0, "k", b"v1")
        assert r[0] == "ok", r
        assert await c.kget(0, "k") == ("ok", b"v1")
        r = await c.kget_vsn(0, "k")
        assert r[0] == "ok" and r[1] == b"v1"

        # the slab lane end to end: ascii keys / bytes values ride
        # kput_slab/kget_slab through the proxy's Raw re-wrap
        keys = [f"s{i}" for i in range(6)]
        vals = [b"x%d" % i for i in range(6)]
        rs = await c.kput_many(1, keys, vals)
        assert all(r[0] == "ok" for r in rs), rs
        rs = await c.kget_many(1, keys)
        assert [r[1] for r in rs] == vals
        rs = await c.kget_many(1, keys, want_vsn=True)
        assert all(r[0] == "ok" and len(r[2]) == 2 for r in rs)

        # empty batches: the degenerate slab shape answers [] and
        # leaves the connection healthy
        assert await c.kget_many(1, []) == []
        assert await c.kput_many(1, [], []) == []
        r = await c.call_parts(
            "kget_slab", 1, wire.Raw(b""), wire.Raw(b""))
        assert r == []

        # non-ascii keys leave the slab subset: the client falls back
        # to the legacy list verbs, results unchanged through the hop
        rs = await c.kput_many(1, ["ключ"], [b"v"])
        assert rs[0][0] == "ok", rs
        rs = await c.kget_many(1, ["ключ", "s0"])
        assert rs == [("ok", b"v"), ("ok", b"x0")], rs

        assert await c.kget(0, "absent") == ("ok", NOTFOUND)
        # the proxy's own verb is answered locally, never forwarded
        ps = await c.call("proxy_stats")
        assert ps["clients"] == 1
        assert ps["forwarded"] > 0
        assert ps["upstream"] == f"{server.host}:{server.port}"
        assert ps["backpressure"] == {"inflight_stalls": 0,
                                      "write_buf_drops": 0}
        # forwarded stats carry the engine's backpressure row (the
        # svcnode satellite)
        st = await c.stats()
        assert st["svc_backpressure"] == {"inflight_stalls": 0,
                                          "write_buf_drops": 0}
        await c.close()
        await px.stop()
        await server.stop()

    asyncio.run(scenario())


def test_proxy_max_frame_boundary_arena():
    """A client slab frame at EXACTLY _MAX_FRAME forwards and
    commits (the proxy re-frames only the tiny header, so the
    upstream frame cannot outgrow the client's when the client's
    req id is the larger encoding); one byte over drops the
    connection without disturbing the next client."""
    async def scenario():
        server = await svcnode.serve(2, 3, 8, port=0,
                                     config=fast_test_config())
        px = await proxy_mod.serve_proxy([(server.host, server.port)])
        rid = 2 ** 40  # encodes no smaller than any proxy-side rid

        def build(vlen):
            key = "bigk"
            parts = wire.encode_parts(
                (rid, "kput_slab", 0,
                 wire.Raw(np.asarray([len(key)], "<i4")),
                 wire.Raw(key.encode("ascii")),
                 wire.Raw(np.asarray([vlen], "<i4")),
                 wire.Raw(bytes(vlen))))
            return parts, sum(memoryview(p).nbytes for p in parts)

        vlen = _MAX_FRAME - 4096
        for _ in range(8):  # converge on the exact boundary (varint
            parts, length = build(vlen)  # header widths shift a bit)
            if length == _MAX_FRAME:
                break
            vlen += _MAX_FRAME - length
        assert length == _MAX_FRAME, (length, _MAX_FRAME)

        reader, writer = await asyncio.open_connection(px.host,
                                                       px.port)
        writer.write(_HDR.pack(length))
        for p in parts:
            writer.write(p)
        await writer.drain()
        head = await reader.readexactly(_HDR.size)
        (n,) = _HDR.unpack(head)
        resp = wire.decode(await reader.readexactly(n))
        assert resp[0] == rid
        assert resp[1][0][0] == "ok", resp
        writer.close()

        # one byte past the cap: hostile length, connection dropped
        reader, writer = await asyncio.open_connection(px.host,
                                                       px.port)
        writer.write(_HDR.pack(_MAX_FRAME + 1))
        await writer.drain()
        assert await reader.read(1) == b""
        writer.close()

        # the serving plane stayed healthy through both: normal ops
        # keep flowing on a fresh connection.  (Reading the boundary
        # VALUE back in one frame would trip the engine's slow-reader
        # write-buffer guard — responses are capped at _MAX_WRITE_BUF,
        # a deliberate pre-existing bound; the boundary case under
        # test is the REQUEST frame through the hop.)
        c = svcnode.ServiceClient(px.host, px.port)
        await c.connect()
        assert (await c.kput(0, "small", b"s"))[0] == "ok"
        assert await c.kget(0, "small") == ("ok", b"s")
        await c.close()
        await px.stop()
        await server.stop()

    asyncio.run(scenario())


def test_service_client_reconnects_with_backoff():
    """The reconnect satellite: a previously-connected client whose
    socket drops redials before the next op (safe for every verb —
    nothing was dispatched), counts the reconnect, and an explicitly
    closed client stays DISCONNECTED."""
    async def scenario():
        server = await svcnode.serve(2, 3, 8, port=0,
                                     config=fast_test_config())
        c = svcnode.ServiceClient(server.host, server.port)
        await c.connect()
        assert (await c.kput(0, "k", b"v"))[0] == "ok"

        c._writer.close()  # the drop: kernel-level, client unaware
        await asyncio.sleep(0.05)
        assert await c.kget(0, "k") == ("ok", b"v")
        assert c.reconnects >= 1

        # never-connected and closed clients keep the documented
        # DISCONNECTED contract (no redial loops)
        fresh = svcnode.ServiceClient(server.host, server.port)
        assert await fresh.kget(0, "k") == fresh.DISCONNECTED
        await c.close()
        assert await c.kget(0, "k") == c.DISCONNECTED
        await server.stop()

    asyncio.run(scenario())


# -- leader step-down race ---------------------------------------------------

_CFG = Config(ensemble_tick=0.05, lease_duration=1.5,
              probe_delay=0.1, storage_delay=0.005,
              storage_tick=0.5, gossip_tick=0.2)


def _control(port, frame, timeout=120.0):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        repgroup.send_frame(s, frame)
        return repgroup.recv_frame(s)


def test_proxy_rides_out_leader_step_down(tmp_path):
    """The DeposedError re-resolve story: a proxy fronting a 3-host
    group keeps serving the SAME client connection across an
    in-place leader handoff — the deposed host's not-leader
    rejections (never dispatched) retry transparently against the
    freshly discovered leader."""
    srvs = [repgroup.ReplicaServer(
        2, 3, 8, data_dir=str(tmp_path / f"r{i}"), config=_CFG)
        for i in range(3)]
    ports = [s.repl_port for s in srvs]
    try:
        resp = _control(ports[0], ("promote",
                                   [("127.0.0.1", ports[1]),
                                    ("127.0.0.1", ports[2])]))
        assert resp[0] == "ok", resp

        async def scenario():
            px = await proxy_mod.serve_proxy(
                [("127.0.0.1", s.client_port) for s in srvs],
                discover_timeout=60.0)
            c = svcnode.ServiceClient(px.host, px.port)
            await c.connect()
            r = await c.kput(0, "pre", b"1", timeout=120.0)
            assert r[0] == "ok", r
            first = px.link.leader_addr
            assert first == ("127.0.0.1", srvs[0].client_port)

            # in-place handoff while the proxy's connection is live
            resp2 = await asyncio.get_running_loop().run_in_executor(
                None, lambda: _control(
                    ports[1], ("promote",
                               [("127.0.0.1", ports[0]),
                                ("127.0.0.1", ports[2])])))
            assert resp2[0] == "ok", resp2

            # the same client connection keeps working: the proxy
            # eats the not-leader rejection, re-resolves, retries
            deadline = time.monotonic() + 60.0
            while True:
                r = await c.kput(0, "post", b"2", timeout=120.0)
                if isinstance(r, tuple) and r[0] == "ok":
                    break
                # a 'failed' can leak out while the fresh leader
                # re-syncs its host quorum; never a stuck not-leader
                assert r != ("error", "not-leader"), r
                assert time.monotonic() < deadline, r
                await asyncio.sleep(0.5)
            assert px.link.leader_addr == \
                ("127.0.0.1", srvs[1].client_port)
            assert px.link.rediscoveries >= 1
            # acked data readable through the new leader via the hop
            assert await c.kget(0, "pre", timeout=120.0) == \
                ("ok", b"1")
            ps = await c.call("proxy_stats")
            assert ps["not_leader_retries"] >= 1
            await c.close()
            await px.stop()

        asyncio.run(scenario())
    finally:
        for s in srvs:
            s.stop()


# -- the serving plane as processes ------------------------------------------

#: the client's process: `wire` and sockets only.  Writes and reads a
#: slab batch a round through the proxy, then reads every key straight
#: off each follower, and prints one tally
_CLIENT = r'''
import json, socket, struct, sys, time
from riak_ensemble_tpu import wire
cfg, HDR = json.loads(sys.argv[1]), struct.Struct(">I")
ROUNDS, N_ENS = 3, 2
keys = ["k%d" % j for j in range(4)]
lens = struct.pack("<4i", *map(len, keys))
arena = "".join(keys).encode()
tally = dict(writes=0, reads=0, follower_reads=0, errors=0)
def ask(sock, *frame):
    payload = wire.encode(frame)
    sock.sendall(HDR.pack(len(payload)) + payload)
    buf = b""
    while len(buf) < 4 or len(buf) < 4 + HDR.unpack(buf[:4])[0]:
        got = sock.recv(1 << 16)
        assert got, "closed"
        buf += got
    return wire.decode(buf[4:])[1]
def oks(res, vals=None):
    good = isinstance(res, list) and all(r[0] == "ok" for r in res) \
        and (vals is None or [r[1] for r in res] == vals)
    tally["errors"] += not good
    return len(res) if good else 0
px = socket.create_connection(tuple(cfg["proxy"]), timeout=120)
for rnd in range(ROUNDS):
    vals = [b"v%d.%d" % (rnd, j) for j in range(4)]
    for ens in range(N_ENS):
        tally["writes"] += oks(ask(
            px, rnd, "kput_slab", ens, lens, arena,
            struct.pack("<4i", *map(len, vals)), b"".join(vals)))
        tally["reads"] += oks(ask(px, rnd, "kget_slab", ens, lens, arena),
                              vals)
for addr in cfg["followers"]:
    s = socket.create_connection(tuple(addr), timeout=120)
    end = time.monotonic() + 60
    while ask(s, 0, "kget", 0, "k0") == ("error", "not-leader"):
        assert time.monotonic() < end, "follower lease never arrived"
        time.sleep(0.1)
    for ens in range(N_ENS):
        tally["follower_reads"] += oks(
            ask(s, 1, "kget_slab", ens, lens, arena), vals)
print(json.dumps(tally), flush=True)
'''


def test_promoted_group_behind_a_proxy_process(tmp_path):
    """A promoted three-host group with follower reads, a proxy in a
    process of its own and a client in another: no write or read
    fails through the hop, the followers answer from their mirrors,
    and ONE ``("fleet", "metrics")`` pull off the leader carries the
    replicas' own count of the reads they served."""
    from test_follower_reads import _ask

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    srvs = [repgroup.ReplicaServer(
        2, 3, 8, data_dir=str(tmp_path / f"r{i}"), config=_CFG,
        follower_reads=True) for i in range(3)]
    hosts = [("127.0.0.1", s.client_port) for s in srvs]
    px = None
    try:
        resp = _control(srvs[0].repl_port, (
            "promote", [("127.0.0.1", s.repl_port) for s in srvs[1:]]))
        assert resp[0] == "ok", resp
        px = subprocess.Popen(
            [sys.executable, "-m", "riak_ensemble_tpu.proxy",
             "--port", "0", "--discover-timeout", "120", "--upstream",
             ",".join(f"{h}:{p}" for h, p in hosts)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        line = px.stdout.readline()
        assert line.startswith("proxy serving on "), line
        host, _, port = line.split()[3].rpartition(":")
        # the serving gate: the first write rides out the fresh
        # leader's host-quorum heal
        end = time.monotonic() + 120.0
        while _ask(int(port), 0, "kput", 0, "gate", b"g",
                   timeout=120.0)[0] != "ok":
            assert time.monotonic() < end, "group never served"
            time.sleep(0.25)
        out = subprocess.run(
            [sys.executable, "-c", _CLIENT, json.dumps(dict(
                proxy=[host, int(port)], followers=hosts[1:]))],
            capture_output=True, text=True, env=env, timeout=240)
        assert out.returncode == 0, out.stderr[-800:]
        tally = json.loads(out.stdout.strip().splitlines()[-1])
        assert tally == dict(writes=24, reads=24, follower_reads=16,
                             errors=0), tally
        fm = _ask(srvs[0].client_port, 1, "fleet", "metrics",
                  timeout=120.0)
        assert len(fm["hosts"]) == 3, sorted(fm["hosts"])
        served = [snap.get("retpu_group_follower_reads_served", 0)
                  for snap in fm["hosts"].values()]
        assert sum(served) >= 16, served
    finally:
        if px is not None:
            px.kill()
            px.wait(timeout=10)
        for s in srvs:
            s.stop()
