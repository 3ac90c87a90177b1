"""The client side of a run: a lean pipelined TCP client for the
svcnode protocol, an OPEN-LOOP driver that times every request from
the instant it was due, and the batched load and read-back.

One thread, one asyncio loop, in a process that never imports JAX and
shares nothing with the server but the socket: a flush blocks the
server's loop, and a generator inside that loop would starve with it
and be charged to it.  From the program this takes the wire codec
(``riak_ensemble_tpu.wire``: the protocol is the system's) and nothing
else — ``ServiceClient`` imports the service, and with it JAX.
"""

from __future__ import annotations

import asyncio
import gc
import struct
import time

import numpy as np

_HDR = struct.Struct(">I")

#: reply status in a :class:`Log`
PENDING, OK, FAILED, ERROR = 0, 1, 2, 3
#: ``Log.wid`` of a read that found no record / returned bytes no write
#: ever carried
WID_NOTFOUND, WID_FABRICATED = -2, -1


class _Link(asyncio.Protocol):
    """One connection.  Replies go to ``client.pending[req_id]`` as
    ``handler(token, t_received, result)``; every frame of one
    ``data_received`` shares its timestamp (they arrived together)."""

    def __init__(self, client: "Client") -> None:
        self.client = client
        self.buf = bytearray()
        self.transport = None
        self.lost = asyncio.get_running_loop().create_future()

    def connection_made(self, transport) -> None:
        self.transport = transport

    def data_received(self, data: bytes) -> None:
        t = time.perf_counter()
        buf = self.buf
        buf += data
        pos, end = 0, len(buf)
        decode, pending = self.client.decode, self.client.pending
        while end - pos >= 4:
            (length,) = _HDR.unpack_from(buf, pos)
            if end - pos - 4 < length:
                break
            req_id, result = decode(bytes(buf[pos + 4:pos + 4 + length]))
            pos += 4 + length
            handler, token = pending.pop(req_id)
            handler(token, t, result)
        del buf[:pos]

    def connection_lost(self, exc) -> None:
        if not self.lost.done():
            self.lost.set_result(exc)


class Client:
    """``connections`` pipelined links to one svcnode."""

    def __init__(self, host: str, port: int, connections: int) -> None:
        from riak_ensemble_tpu import wire
        self.wire = wire
        self.decode = wire.decode
        self.host, self.port, self.n = host, port, connections
        self.links: list = []
        self.pending: dict = {}
        self._next_id = 1

    async def connect(self) -> None:
        loop = asyncio.get_running_loop()
        for _ in range(self.n):
            _, link = await loop.create_connection(
                lambda: _Link(self), self.host, self.port)
            self.links.append(link)

    def close(self) -> None:
        for link in self.links:
            link.transport.close()

    def lost(self) -> bool:
        return any(link.lost.done() for link in self.links)

    def send(self, lane: int, op: str, args: tuple, handler, token) -> None:
        """One request on link ``lane``; never waits (open loop)."""
        rid = self._next_id
        self._next_id = rid + 1
        self.pending[rid] = (handler, token)
        payload = self.wire.encode((rid, op) + args)
        self.links[lane % self.n].transport.write(
            _HDR.pack(len(payload)) + payload)

    def send_parts(self, lane: int, op: str, args: tuple, handler,
                   token) -> None:
        """A ``wire.Raw``-carrying request (the ``*_slab`` verbs)."""
        rid = self._next_id
        self._next_id = rid + 1
        self.pending[rid] = (handler, token)
        parts = self.wire.encode_parts((rid, op) + args)
        length = sum(memoryview(p).nbytes for p in parts)
        tr = self.links[lane % self.n].transport
        tr.write(_HDR.pack(length))
        for p in parts:
            tr.write(p)


class Log:
    """What the generator saw of one phase, one row per request, all
    times on this process's ``perf_counter``."""

    def __init__(self, t0: float, due, is_read, keynum, first_wid) -> None:
        n = len(due)
        self.t0 = t0
        self.due = t0 + np.asarray(due, np.float64)
        self.is_read = np.asarray(is_read, bool)
        self.keynum = np.asarray(keynum, np.int64)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.status = np.zeros(n, np.int8)
        #: an update's own write id; a read's: the id its value names
        self.wid = np.where(self.is_read, WID_NOTFOUND,
                            first_wid + np.arange(n)).astype(np.int64)
        #: the version an acknowledged update was given
        self.epoch = np.zeros(n, np.int64)
        self.seq = np.zeros(n, np.int64)


async def open_loop(client: Client, records, due, is_read, keynum,
                    first_wid: int, drain_seconds: float) -> Log:
    """Send every request at its due instant whatever the replies do,
    then wait up to ``drain_seconds`` for what is outstanding."""
    n = len(due)
    log = Log(time.perf_counter(), due, is_read, keynum, first_wid)
    left = [n]
    all_done = asyncio.Event()
    done, status, wid = log.done, log.status, log.wid
    epoch, seq = log.epoch, log.seq
    decode = records.decode

    def finish(i: int, t: float, code: int) -> None:
        done[i] = t
        status[i] = code
        left[0] -= 1
        if not left[0]:
            all_done.set()

    def on_put(i: int, t: float, result) -> None:
        if type(result) is tuple and len(result) == 2 \
                and result[0] == "ok":
            epoch[i], seq[i] = result[1]
            finish(i, t, OK)
        else:
            finish(i, t, FAILED if result == "failed" else ERROR)

    def on_get(i: int, t: float, result) -> None:
        if type(result) is tuple and len(result) == 2 \
                and result[0] == "ok":
            value = result[1]
            wid[i] = (decode(value) if type(value) is bytes
                      else WID_NOTFOUND)
            finish(i, t, OK)
        else:
            finish(i, t, FAILED if result == "failed" else ERROR)

    keys, ens, value = records.keys, records.ens.tolist(), records.value
    due_abs, sent = log.due.tolist(), log.sent
    kn, rd, wids = log.keynum.tolist(), log.is_read.tolist(), wid.tolist()
    clock = time.perf_counter
    # a collection over this process's millions of long-lived objects
    # (keys, versions, logs) pauses the generator for a second: none
    # while requests are being timed
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        i = 0
        while i < n:
            now = clock()
            while i < n and due_abs[i] <= now:
                k = kn[i]
                sent[i] = clock()
                if rd[i]:
                    client.send(i, "kget", (ens[k], keys[k]), on_get, i)
                else:
                    client.send(i, "kput",
                                (ens[k], keys[k], value(wids[i])), on_put, i)
                i += 1
            if i < n:
                wait = due_abs[i] - clock()
                # the loop's timers are good to about a millisecond:
                # spin through the loop (replies are read there) for less
                await asyncio.sleep(wait if wait > 0.002 else 0)
            if client.lost():
                break
        log.t_last_due = log.t0 + (float(due[-1]) if n else 0.0)
        if left[0]:
            try:
                await asyncio.wait_for(all_done.wait(), drain_seconds)
            except asyncio.TimeoutError:
                pass
    finally:
        gc.enable()
    log.t_end = clock()
    return log


async def batched(client: Client, calls: list, outstanding: int,
                  timeout: float) -> list:
    """Issue ``calls`` (``(op, args)`` with ``wire.Raw`` parts), at most
    ``outstanding`` unanswered at a time; replies in call order."""
    replies: list = [None] * len(calls)
    state = {"open": 0, "left": len(calls)}
    wake = asyncio.Event()

    def on_reply(i: int, _t: float, result) -> None:
        replies[i] = result
        state["open"] -= 1
        state["left"] -= 1
        wake.set()

    deadline = time.perf_counter() + timeout
    i = 0
    while state["left"]:
        while i < len(calls) and state["open"] < outstanding:
            op, args = calls[i]
            client.send_parts(i, op, args, on_reply, i)
            state["open"] += 1
            i += 1
        wake.clear()
        try:
            await asyncio.wait_for(wake.wait(), 1.0)
        except asyncio.TimeoutError:
            pass
        if client.lost() or time.perf_counter() > deadline:
            raise RuntimeError(
                f"batched phase: {state['left']} of {len(calls)} calls "
                f"unanswered (connection lost: {client.lost()})")
    return replies


def _key_slab(client: Client, keys: list):
    raw = client.wire.Raw
    lens = np.fromiter(map(len, keys), np.int32, len(keys))
    return raw(lens), raw("".join(keys).encode("ascii"))


async def load(client: Client, records, outstanding: int,
               timeout: float) -> dict:
    """Write every record, one ``kput_slab`` per ensemble; returns
    record number -> the version its write was given."""
    raw = client.wire.Raw
    groups = records.by_ensemble(np.arange(records.recordcount))
    calls = []
    for e, kns in groups.items():
        vals = [records.value(kn) for kn in kns]
        vlens = np.fromiter(map(len, vals), np.int32, len(vals))
        calls.append(("kput_slab", (
            e, *_key_slab(client, [records.keys[kn] for kn in kns]),
            raw(vlens), raw(b"".join(vals)))))
    replies = await batched(client, calls, outstanding, timeout)
    vsn = {}
    for kns, rep in zip(groups.values(), replies):
        if not (isinstance(rep, list) and len(rep) == len(kns)
                and all(type(r) is tuple and r[0] == "ok" for r in rep)):
            raise RuntimeError(f"load: kput_slab answered {rep!r:.200}")
        for kn, r in zip(kns, rep):
            vsn[kn] = (int(r[1][0]), int(r[1][1]))
    return vsn


async def depth_burst(client: Client, records, depth: int, keynums,
                      first_wid: int, timeout: float) -> Log:
    """``depth`` updates of each record of ``keynums`` (records of
    different ensembles), all sent at once: one ``kput_slab`` per
    ensemble naming its key ``depth`` times, which the service runs as
    ``depth`` rounds of that column.  A flush that takes the whole
    burst is ``depth`` deep and ``len(keynums)`` columns wide.  The
    rows come back as a :class:`Log` (every write is checked like any
    other)."""
    raw = client.wire.Raw
    width = len(keynums)
    rows = np.repeat(np.asarray(keynums, np.int64), depth)
    log = Log(time.perf_counter(), np.zeros(rows.size), np.zeros(rows.size,
              bool), rows, first_wid)
    calls = []
    for j, kn in enumerate(np.asarray(keynums).tolist()):
        last = first_wid + (j + 1) * depth - 1
        vals = [records.value(w, stub=w != last)
                for w in range(first_wid + j * depth, last + 1)]
        vlens = np.fromiter(map(len, vals), np.int32, depth)
        calls.append(("kput_slab", (
            int(records.ens[kn]),
            *_key_slab(client, [records.keys[kn]] * depth),
            raw(vlens), raw(b"".join(vals)))))
    log.sent[:] = time.perf_counter()
    replies = await batched(client, calls, width, timeout)
    log.done[:] = time.perf_counter()
    for j, rep in enumerate(replies):
        for d in range(depth):
            i = j * depth + d
            r = rep[d] if isinstance(rep, list) and len(rep) == depth \
                else None
            if type(r) is tuple and len(r) == 2 and r[0] == "ok":
                log.epoch[i], log.seq[i] = r[1]
                log.status[i] = OK
            else:
                log.status[i] = FAILED if r == "failed" else ERROR
    log.t_last_due = log.t0
    log.t_end = time.perf_counter()
    return log


async def read_back(client: Client, records, keynums, outstanding: int,
                    timeout: float) -> dict:
    """Read ``keynums`` back, one ``kget_slab`` per ensemble; returns
    record number -> the write id its value names (or the negative
    codes of :class:`Log`; -3 for a reply that is not ``ok``)."""
    groups = records.by_ensemble(keynums)
    calls = [("kget_slab", (
        e, *_key_slab(client, [records.keys[kn] for kn in kns]), False))
        for e, kns in groups.items()]
    replies = await batched(client, calls, outstanding, timeout)
    got = {}
    for kns, rep in zip(groups.values(), replies):
        if not (isinstance(rep, list) and len(rep) == len(kns)):
            rep = [None] * len(kns)
        for kn, r in zip(kns, rep):
            if type(r) is tuple and len(r) == 2 and r[0] == "ok":
                got[kn] = (records.decode(r[1]) if type(r[1]) is bytes
                           else WID_NOTFOUND)
            else:
                got[kn] = -3
    return got
