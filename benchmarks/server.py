"""The process that holds the chip: one svcnode, started through its
normal entry point with the program's defaults, and a one-word command
protocol on stdin (one JSON line on stdout per event or answer).

Events, in order: ``device`` (what JAX found; anything but a TPU ends
the process with code 2 unless ``--rehearse``), ``native`` (the four
native halves, built from source when stale), ``serving`` (host, port,
compile cache, the settings).  Commands: ``mark``, ``trace_start``,
``trace_stop``, ``fast_reads_off``, ``dump``, ``quit`` — see
``README.md``.

``--riak-ensemble <json>`` is the deployment's ``riak_ensemble``
object: the reference's application settings by their own names
(``config.Config``'s fields).  The service is then started with
``config=Config(**settings)`` and nothing else changes; a name
``Config`` does not have ends the process with an ``error`` line and
code 2 before JAX is imported.

This process cannot outlive the ``run.py`` that started it: before it
imports JAX it asks the kernel for SIGKILL at its parent's death
(:func:`die_with_parent`), and the ``make`` of ``native/`` runs under
``make_native.py``, in a process group that the same request ends
whole.

``--control <name>`` (never passed by the driver) serves with one
stated guarantee broken, to show that the check fails such a run:
``stale_read``, ``lost_write``, ``wal_buffer``, ``python_resolve``,
``leased_read`` (the lease mirror answers reads again under a
deployment that set ``trust_lease`` false).
"""

from __future__ import annotations

import argparse
import asyncio
import ctypes
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NATIVE_TARGETS = ("libretpu_native.so", "_retpu_resolve.so",
                  "_retpu_wire.so")
COUNTERS = ("flushes", "ops_served", "read_fastpath_hits",
            "read_fastpath_misses")


PR_SET_PDEATHSIG = 1    # <linux/prctl.h>


def die_with_parent(sig: int, parent_pid: int = 0) -> None:
    """Ask the kernel to send this process ``sig`` when the thread that
    started it ends, however it ends: no ``finally`` of the parent has
    to run.  The request is made after the fork, so a parent that died
    in between is missed: where the starter's pid is given and it is
    no longer the parent, leave at once.  Linux only (where the chip
    is); elsewhere nothing is asked."""
    if not sys.platform.startswith("linux"):
        return
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(PR_SET_PDEATHSIG, int(sig), 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_PDEATHSIG)")
    if parent_pid and os.getppid() != parent_pid:
        os._exit(1)


def say(event: str, **fields) -> None:
    print(json.dumps(dict(event=event, **fields), default=_plain),
          flush=True)


def _plain(x):
    """numpy scalars and arrays inside ``stats()``"""
    if hasattr(x, "tolist"):
        return x.tolist()
    return str(x)


def build_native(work: str) -> dict:
    """Build ``native/`` from source where a library is absent or older
    than a source, each library moved into place atomically, and load
    every half (as ``chip_smoke.build_native`` does)."""
    src = os.path.join(ROOT, "native")
    sources = [n for n in os.listdir(src)
               if n.endswith((".cc", ".h")) or n == "Makefile"]
    newest = max(os.path.getmtime(os.path.join(src, n)) for n in sources)
    stale = [so for so in NATIVE_TARGETS
             if not os.path.exists(os.path.join(src, so))
             or os.path.getmtime(os.path.join(src, so)) < newest]
    if stale:
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        for name in sources:
            shutil.copy2(os.path.join(src, name), work)
        # (make_native.py: make in a group that dies with this process)
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "make_native.py"), work,
             str(os.getpid())], capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build failed:\n{proc.stdout}\n{proc.stderr}")
        for so in NATIVE_TARGETS:
            os.replace(os.path.join(work, so), os.path.join(src, so))
        shutil.rmtree(work, ignore_errors=True)

    from riak_ensemble_tpu import wire
    from riak_ensemble_tpu.utils import native
    base, resolve = native.load(), native.load_resolve()
    return {
        "clock+treestore": base is not None,
        "resolve": resolve is not None,
        "enqueue": resolve is not None
        and hasattr(resolve, "retpu_enqueue_pack")
        and hasattr(resolve, "retpu_enqueue_gather"),
        "wire": wire._native_codec() is not None,
        "built": bool(stale),
    }


def break_guarantee(control: str, server) -> None:
    """Wrap the server's dispatch so that it breaks one guarantee."""
    svc = server.svc
    inner = server._dispatch
    cur: dict = {}       # key -> value of the latest kput dispatched
    settled: dict = {}   # key -> (value before it, when it was acked)
    count = [0]

    class Answered:
        def __init__(self, value) -> None:
            self.value = value

        def add_waiter(self, fn) -> None:
            fn(self.value)

    def dispatch(op: str, args: tuple):
        if op == "kput":
            ens, key, value = args
            before = cur.get(key)
            cur[key] = value
            fut = inner(op, args)
            count[0] += 1
            nth = count[0]

            def acked(result) -> None:
                if result == "failed":
                    return
                if control == "lost_write" and nth % 50 == 0:
                    # the acknowledged write is quietly undone (the
                    # first this wrapper saw of its key, an insert
                    # among them: the key is gone with it)
                    if before is None:
                        svc.kdelete(ens, key)
                    else:
                        svc.kput(ens, key, before)
                if before is not None:
                    settled[key] = (before, time.monotonic())
            fut.add_waiter(acked)
            return fut
        if op == "kget" and control == "stale_read":
            was = settled.get(args[1])
            if was is not None and time.monotonic() - was[1] > 0.5:
                return Answered(("ok", was[0]))
        return inner(op, args)

    server._dispatch = dispatch


async def serve(args, device: dict):
    from riak_ensemble_tpu import svcnode
    from riak_ensemble_tpu.utils.jaxcache import setup_compile_cache

    cache_dir = setup_compile_cache()
    kw = {}
    if args.engine == "mesh":
        from riak_ensemble_tpu.parallel.mesh import mesh_engine
        kw["engine"] = mesh_engine(args.chips)
    if args.control == "wal_buffer":
        import functools
        svcnode.BatchedEnsembleService = functools.partial(
            svcnode.BatchedEnsembleService, wal_sync="buffer")
    said = {}
    if args.settings is not None:
        from riak_ensemble_tpu.config import Config
        kw["config"] = Config(**args.settings)
        kw["config"].validate()
        said["riak_ensemble"] = args.settings
    data_dir = os.path.join(args.out, "data")
    shutil.rmtree(data_dir, ignore_errors=True)
    server = await svcnode.serve(args.n_ens, args.n_peers, args.n_slots,
                                 data_dir=data_dir, **kw)
    if args.control in ("stale_read", "lost_write"):
        break_guarantee(args.control, server)
    if args.control == "leased_read":
        # what ``set_fast_reads(True)`` refuses under trust_lease false
        server.svc._assert_read_margin()
        server.svc._fast_reads = True
    say("serving", host=server.host, port=server.port,
        compile_cache=cache_dir, control=args.control, **said, **device)
    return server


class Recorder:
    """Copies the service's per-flush marks out of its bounded deque
    (the last 1,024) as they appear, so a window keeps all of its own."""

    def __init__(self, svc) -> None:
        self.svc = svc
        self.records: list = []
        self._last = None

    def poll(self) -> None:
        recs = list(self.svc.lat_records)
        start = 0
        if self._last is not None:
            for j in range(len(recs) - 1, -1, -1):
                if recs[j] is self._last:
                    start = j + 1
                    break
        self.records += recs[start:]
        if recs:
            self._last = recs[-1]

    async def run(self) -> None:
        while True:
            self.poll()
            await asyncio.sleep(0.5)

    def take(self) -> list:
        self.poll()
        out, self.records = self.records, []
        return out


async def commands(args, server, device: dict) -> None:
    import jax
    from riak_ensemble_tpu.obs.compilewatch import COMPILE_EVENTS

    svc = server.svc
    loop = asyncio.get_running_loop()
    recorder = Recorder(svc)
    poller = loop.create_task(recorder.run())
    trace_dir = os.path.join(args.out, "trace")
    state = {"mark_t": time.time(), "mark": {}, "trace": None,
             "read_back": None}

    def counters() -> dict:
        return {c: getattr(svc, c) for c in COUNTERS}

    try:
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            word = line.strip()
            if word == "mark":
                recorder.take()
                state["mark_t"] = time.time()
                state["mark"] = counters()
                say("mark", **state["mark"], **device)
            elif word == "trace_start":
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                state["trace"] = {"t0": time.perf_counter(),
                                  "at_start": counters()}
                say("trace_start", **device)
            elif word == "trace_stop":
                tr = state["trace"]
                tr["window_s"] = time.perf_counter() - tr["t0"]
                tr["at_stop"] = counters()
                # writing the trace out takes seconds: off the loop
                await loop.run_in_executor(None, jax.profiler.stop_trace)
                say("trace_stop", window_s=tr["window_s"], **device)
            elif word == "fast_reads_off":
                svc.set_fast_reads(False)
                state["read_back"] = counters()
                say("fast_reads_off", **device)
            elif word == "dump":
                now = counters()
                rb = state["read_back"]
                # (the window's end: where the read-back began, else now)
                end = rb or now
                out = {
                    "stats": svc.stats(),
                    "since_mark": {c: now[c] - state["mark"].get(c, 0)
                                   for c in COUNTERS},
                    "window_counters": {
                        c: end[c] - state["mark"].get(c, 0)
                        for c in COUNTERS},
                    "leased_reads": end["read_fastpath_hits"],
                    "lat_records": recorder.take(),
                    "compile_events": [
                        e for e in list(COMPILE_EVENTS)
                        if e["t_unix"] > state["mark_t"]],
                    "memory_peak_bytes": max(
                        ((d.memory_stats() or {}).get(
                            "peak_bytes_in_use", 0)
                         for d in jax.local_devices()), default=0),
                    "read_back_on_device": rb is not None
                    and now["read_fastpath_hits"] == rb["read_fastpath_hits"]
                    and now["flushes"] > rb["flushes"],
                }
                tr = state["trace"]
                if tr is not None and "at_stop" in tr:
                    import trace_reduce
                    out["trace"] = {
                        "window_s": tr["window_s"],
                        "counters": {c: tr["at_stop"][c] - tr["at_start"][c]
                                     for c in COUNTERS},
                        "reduction": trace_reduce.reduce(
                            trace_reduce.load(trace_dir),
                            tr["window_s"]),
                    }
                say("dump", **out, **device)
            elif word in ("quit", ""):
                break
            else:
                say("error", what=f"unknown command {word!r}", **device)
    finally:
        poller.cancel()
        await server.stop()
        say("stopped", **device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-ens", type=int, required=True)
    ap.add_argument("--n-peers", type=int, required=True)
    ap.add_argument("--n-slots", type=int, required=True)
    ap.add_argument("--engine", choices=("single", "mesh"),
                    default="single")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parent-pid", type=int, default=0,
                    help="run.py's pid: leave at once if it is no "
                         "longer the parent")
    ap.add_argument("--control", default=None,
                    choices=("stale_read", "lost_write", "wal_buffer",
                             "python_resolve", "leased_read"))
    ap.add_argument("--riak-ensemble", default=None, metavar="JSON",
                    help="the deployment's riak_ensemble settings")
    args = ap.parse_args(argv)
    die_with_parent(signal.SIGKILL, args.parent_pid)    # before JAX
    sys.path.insert(0, ROOT)
    args.settings = None
    if args.riak_ensemble is not None:
        from riak_ensemble_tpu.config import Config     # imports no JAX
        args.settings = json.loads(args.riak_ensemble)
        unknown = sorted(set(args.settings)
                         - {f.name for f in dataclasses.fields(Config)})
        if unknown:
            say("error", what="riak_ensemble: config.Config has no "
                              f"setting {', '.join(unknown)}")
            return 2
    if args.control == "python_resolve":
        os.environ["RETPU_NATIVE_RESOLVE"] = "0"

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform,
              "device_kind": devs[0].device_kind, "count": len(devs)}
    if args.rehearse:
        device["rehearsal"] = True
    say("device", **device)
    if device["platform"] != "tpu" and not args.rehearse:
        say("error", what="no TPU: nothing run", **device)
        return 2
    if len(devs) < args.chips:
        say("error", what=f"{args.chips} chips asked, {len(devs)} found",
            **device)
        return 2
    os.makedirs(args.out, exist_ok=True)
    halves = build_native(os.path.join(args.out, "native_build"))
    say("native", **halves, **device)
    if not all(v for k, v in halves.items() if k != "built"):
        say("error", what="a native half fell back to Python", **device)
        return 1

    async def run() -> None:
        server = await serve(args, device)
        await commands(args, server, device)

    asyncio.run(run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
