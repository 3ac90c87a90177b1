"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX.  It starts ``server.py`` (which holds
the chip), loads the records, warms up with the cell's own traffic,
offers the seeded open-loop schedule for ``--seconds``, drains, reads
every written key back through device rounds, checks every reply
against the plain reference (``check.py``) and prints the result as the
last line of stdout.  ``--sweep`` (one set-up, a ladder of rates) and
``--rehearse`` (a CPU, cut shapes, exit 3, no result line) are for
builders; the driver passes neither.

A deployment is data: ``configs/<name>.json`` may carry a
``riak_ensemble`` object (the reference's own application settings,
``config.Config``'s fields), which goes to ``server.py`` as one
argument where the key is present and not at all where it is not; a
traffic file may carry ``insertproportion`` and ``requestdistribution:
"latest"`` (``ycsb.schedule``).  A ``--benchmark`` file may name a
directory of its own (``"data"``, relative to the file) whose
``configs/`` and ``traffic/`` are looked through before this one's.

No process this run starts outlives it.  ``server.py`` asks the kernel
for SIGKILL at this process's death (so SIGKILL here, which runs no
handler, leaves nothing either); SIGTERM, SIGINT and SIGHUP print one
``"what": "cut"`` line that names the phase, kill the child, wait for
it and exit 128 + the signal; and a run that ends by itself looks for
what it started (``processes_left``) before it says ``checked``.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import importlib.util
import json
import os
import shutil
import signal
import sys
import time

T_START = time.perf_counter()

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import check  # noqa: E402
import loadgen  # noqa: E402
import ycsb  # noqa: E402

LOAD_OUTSTANDING = 1000     # under the server's 1,024 per connection
READ_BACK_OUTSTANDING = 256  # a width the warm-up's grid has met
PHASE_TIMEOUT_S = 1100.0    # a first run compiles inside its load
TRACE_S = 5.0
UNTOUCHED_SAMPLE = 10_000
SWEEP_STEP_S = 10.0
WARM_ATTEMPTS = 3
#: every process of a run carries this in its environment, so that what
#: the run started can be found when the processes between are gone
RUN_TAG = "RETPU_BENCH_RUN"
CUT_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


def tagged(tag: str) -> list:
    """The pids alive now, but for this one, of the processes whose
    environment carries a run's tag that begins with ``tag`` (a whole
    tag finds that run's; a zombie has no environment)."""
    want, out = f"{RUN_TAG}={tag}".encode(), []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if any(v.startswith(want) for v in f.read().split(b"\0")):
                    out.append(int(pid))
        except OSError:
            continue        # gone meanwhile, or another user's
    return out


class Run:
    """The cell, its files and what every printed line carries."""

    def __init__(self, args) -> None:
        path = args.benchmark or os.path.join(ROOT, "BENCHMARK.json")
        with open(path) as f:
            self.bench = json.load(f)
        #: where a cell's files are looked for, by name, in order
        self.dirs = [HERE]
        if "data" in self.bench:
            self.dirs.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(path)), self.bench["data"]))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
        self.cell = cells[args.workload]
        self.cfg = self._json("configs", self.cell["config"])
        self.traffic = self._json("traffic", self.cell["traffic"])
        self.args = args
        if args.rehearse:
            self.cfg.update(self.cfg.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))
        if args.rate:
            self.traffic["rate"] = args.rate
        for item in args.set or ():
            key, _, val = item.partition("=")
            where = self.traffic if key in self.traffic else self.cfg
            where[key] = json.loads(val)
        self.recordcount = self.cfg["records_per_ens"] * self.cfg["n_ens"]
        #: inserts scheduled so far: the next takes key number
        #: ``recordcount + inserted``
        self.inserted = 0
        #: the settings as given, on every line that states the run
        self.settings = ({"riak_ensemble": self.cfg["riak_ensemble"]}
                         if "riak_ensemble" in self.cfg else {})
        self.out = os.path.join(ROOT, ".bench_out", self.cell["name"])
        self.device: dict = {}
        self.tag = f"{os.getpid()}.{time.time_ns()}"
        self.phase = "starting"    # the last ``what`` said

    def _json(self, kind: str, name: str) -> dict:
        paths = [os.path.join(base, kind, name + ".json")
                 for base in self.dirs]
        with open(next(filter(os.path.exists, paths), paths[-1])) as f:
            return json.load(f)

    def say(self, what: str, **fields) -> None:
        self.phase = " ".join(
            str(x) for x in (what, fields.get("phase")) if x is not None)
        print(json.dumps(dict(what=what, cell=self.cell["name"],
                              **fields, **self.device)), flush=True)

    def started(self) -> list:
        """The pids alive now of the processes this run started."""
        return tagged(self.tag)

    def end_started(self) -> int:
        """Kill what :meth:`started` finds until it finds nothing; how
        many it found at first."""
        found = pids = self.started()
        deadline = time.monotonic() + 10.0
        while pids and time.monotonic() < deadline:
            for pid in pids:
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
            time.sleep(0.05)
            pids = self.started()
        return len(found)

    def cut(self, signum: int, _frame) -> None:
        """SIGTERM, SIGINT or SIGHUP: say where the run stood, as its
        last line, leave nothing behind, exit 128 + the signal.  (Not
        through the loop: it may be in a long step of this process's
        own, and the child holds the chip meanwhile.)"""
        line = json.dumps(dict(
            what="cut", cell=self.cell["name"],
            signal=signal.Signals(signum).name, signum=signum,
            phase=self.phase,
            seconds_since_start=time.perf_counter() - T_START,
            **self.device))
        try:
            sys.stdout.flush()
        except Exception:
            pass                # cut inside a print: its buffer is lost
        os.write(1, (line + "\n").encode())
        self.end_started()
        try:
            while True:
                os.waitpid(-1, 0)
        except ChildProcessError:
            pass                # every child of this process is reaped
        shutil.rmtree(self.out, ignore_errors=True)
        os._exit(128 + signum)


class Child:
    """``server.py``: its events and the answers to commands."""

    def __init__(self, run: Run) -> None:
        self.run = run
        self.proc = None

    def command(self) -> list:
        """``server.py``'s command line: the ring's shape, the engine
        and, only where the configuration has the key, its
        ``riak_ensemble`` settings as one argument."""
        r, cfg = self.run, self.run.cfg
        cmd = [sys.executable, os.path.join(HERE, "server.py"),
               "--n-ens", str(cfg["n_ens"]), "--n-peers",
               str(cfg["n_peers"]), "--n-slots", str(cfg["n_slots"]),
               "--engine", cfg.get("engine", "single"),
               "--chips", str(r.cell["chips"]), "--out", r.out]
        if r.args.rehearse:
            cmd.append("--rehearse")
        if r.args.control:
            cmd += ["--control", r.args.control]
        if "riak_ensemble" in cfg:
            cmd += ["--riak-ensemble", json.dumps(cfg["riak_ensemble"])]
        return cmd

    async def start(self) -> None:
        # (from this thread, the main one: the kernel ties the child's
        # request to the thread that forked it)
        cmd = self.command() + ["--parent-pid", str(os.getpid())]
        self.proc = await asyncio.create_subprocess_exec(
            *cmd, stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, limit=256 << 20, cwd=ROOT,
            env=dict(os.environ, **{RUN_TAG: self.run.tag}))

    async def event(self, want: str) -> dict:
        """The next line of the child, which must be ``want``."""
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                code = await self.proc.wait()
                raise SystemExit(code or 1)
            try:
                ev = json.loads(line)
            except ValueError:
                continue        # a library's own chatter
            if not isinstance(ev, dict) or "event" not in ev:
                continue
            if ev["event"] == "error":
                self.run.say("server_error", error=ev.get("what"))
                continue
            if ev["event"] != want:
                raise RuntimeError(f"server said {ev['event']!r}, "
                                   f"expected {want!r}")
            return ev

    async def ask(self, word: str) -> dict:
        self.proc.stdin.write(word.encode() + b"\n")
        await self.proc.stdin.drain()
        return await self.event(word)

    async def stop(self) -> None:
        if self.proc is None or self.proc.returncode is not None:
            return
        try:
            self.proc.stdin.write(b"quit\n")
            await self.proc.stdin.drain()
            await asyncio.wait_for(self.proc.wait(), 60)
        except (asyncio.TimeoutError, ConnectionError, OSError):
            self.proc.kill()
            await self.proc.wait()


def percentile(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if values.size else float("nan")


def latencies_ms(log, end: float) -> tuple:
    """Due time -> reply, every request of the phase; one that was never
    answered has waited until ``end`` (it misses any limit)."""
    done = np.where(np.isnan(log.done), end, log.done)
    lat = (done - log.due) * 1e3
    return lat[~log.is_read], lat[log.is_read]


def end_to_end(log, seconds: float, setup_s: float) -> dict:
    upd, rd = latencies_ms(log, log.t_end)
    in_window = ((log.status == loadgen.OK)
                 & (log.done <= log.t0 + seconds))
    return {
        "update_p50_ms": (percentile(upd, 50), "ms", upd.size),
        "read_p50_ms": (percentile(rd, 50), "ms", rd.size),
        "committed_ops_per_s": (float(in_window.sum()) / seconds, "ops/s",
                                int(in_window.sum())),
        "setup_s": (setup_s, "s", 1),
    }


def per_layer(run: Run, facts: dict) -> dict:
    """Every per-layer metric of this cell whose reader finds something
    to read: ``layers/<metric>.json`` names the reader and its
    arguments, ``readers/<reader>.py`` is the reader."""
    out, readers = {}, {}
    for m in run.bench["per_layer"]:
        if "workloads" in m and run.cell["name"] not in m["workloads"]:
            continue
        spec = run._json("layers", m["name"])
        name = spec["reader"]
        if name not in readers:
            mod_spec = importlib.util.spec_from_file_location(
                "reader_" + name,
                os.path.join(HERE, "readers", name + ".py"))
            readers[name] = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(readers[name])
        got = readers[name].read(facts, **spec.get("args", {}))
        if got is not None:
            value, samples = got
            out[m["name"]] = (float(value), m["unit"], samples)
    return out


async def phase(run: Run, client, records, stream: int, seconds: float,
                first_wid: int, child: Child = None, trace: bool = False,
                drain: float = None, pileup: int = 0):
    """One open-loop phase of the cell's traffic; with ``trace`` a
    profiler trace of its middle ``TRACE_S`` seconds.  ``pileup``: that
    many requests of the mix, all due at once."""
    t = run.traffic
    due, is_read, keynum = ycsb.schedule(
        run.args.seed, stream, pileup or t["rate"], seconds,
        run.recordcount,
        t["readproportion"], t["requestdistribution"],
        t.get("insertproportion", 0.0), run.inserted)
    if pileup:
        due = np.zeros_like(due)
    # the phase's inserts: the write keys past those there were
    run.inserted += int((~is_read & (
        keynum >= run.recordcount + run.inserted)).sum())
    records.grow(run.recordcount + run.inserted)

    async def tracer() -> None:
        span = min(TRACE_S, seconds / 2.0)
        await asyncio.sleep((seconds - span) / 2.0)
        await child.ask("trace_start")
        await asyncio.sleep(span)
        await child.ask("trace_stop")

    task = asyncio.ensure_future(tracer()) if trace else None
    log = await loadgen.open_loop(client, records, due, is_read, keynum,
                                  first_wid,
                                  t["drain_seconds"] if drain is None
                                  else drain)
    if task is not None:
        await task
    return log


async def warm_up(run: Run, child: Child, client, records, next_wid: int,
                  logs: list, t_loaded: float) -> int:
    """Meet the programs the cell's traffic can meet, before the
    window.  The service compiles one program per (depth, width) bucket
    of a flush (powers of two: the deepest column's queue, the number
    of active columns), and a launch that meets a bucket for the first
    time stalls every request behind it, which deepens the next flush.
    So, first, ``warm_grid``: for every depth and width of the traffic
    file whose product is at most ``max_ops``, and then for every
    ``[depth, width]`` pair of its ``bursts`` (a band of the grid where
    the whole product would cost set-up for programs no backlog of the
    mix can meet), one burst that is that deep and that wide
    (``loadgen.depth_burst``), each drained before the next, ``rounds``
    times over.  Second, ``warm_pileups``: that
    many requests of the mix itself, all due at once, as they pile up
    behind a stalled flush (the widest flushes come only from many
    small frames parsed in one turn of the server's loop).  Then the
    mix at its own rate for ``warm_seconds``, again (at most
    ``WARM_ATTEMPTS`` times) while such a phase still meets a program
    for the first time."""
    t = run.traffic

    async def quiet(what: str, **fields) -> bool:
        events = (await child.ask("dump"))["compile_events"]
        by_fn: dict = {}
        for e in events:
            n, s = by_fn.get(e["fn"], (0, 0.0))
            by_fn[e["fn"]] = (n + 1, s + e["compile_ms"] / 1e3)
        run.say("warmed", phase=what, programs_first_met=len(events),
                first_met_seconds=sum(e["compile_ms"] for e in events) / 1e3,
                first_met_by_fn=by_fn,
                seconds_since_loaded=time.perf_counter() - t_loaded,
                **fields)
        if run.args.keep:       # which program, at which shapes
            os.makedirs(run.args.keep, exist_ok=True)
            with open(os.path.join(
                    run.args.keep, f"{run.cell['name']}.{run.args.seed}"
                    ".programs.jsonl"), "a") as f:
                for e in events:
                    f.write(json.dumps(dict(phase=what, **e)) + "\n")
        return not events

    grid = t.get("warm_grid")
    rng = np.random.default_rng([int(run.args.seed), 0x47524944])
    # one record of every ensemble, to aim a column at
    first_of = np.full(run.cfg["n_ens"], -1, np.int64)
    first_of[records.ens[:run.recordcount][::-1]] = np.arange(
        run.recordcount)[::-1]
    aimable = first_of[first_of >= 0]
    await quiet("load")     # what the load itself met, since the start
    bursts = []
    if grid:
        bursts = [(d, w) for d in grid.get("depths", ())
                  for w in grid.get("widths", ())
                  if d * w <= grid["max_ops"]]
        bursts += [(d, w) for d, w in grid.get("bursts", ())]
    for rnd in range(grid["rounds"] if grid else 0):
        await child.ask("mark")
        for depth, width in bursts:
            if width > aimable.size:
                continue
            log = await loadgen.depth_burst(
                client, records, depth,
                rng.choice(aimable, width, replace=False), next_wid,
                PHASE_TIMEOUT_S)
            next_wid += log.due.size
            logs.append(log)
        await quiet(f"grid {rnd}")
    # (which bucket a pile-up lands in is chance: in a fresh checkout the
    # ladder is walked again, other draws, while it still meets programs,
    # so that the first run compiles them and not the second or third)
    for attempt in range(WARM_ATTEMPTS if t.get("warm_pileups") else 0):
        await child.ask("mark")
        for j, n in enumerate(t["warm_pileups"]):
            log = await phase(run, client, records, 50 + 10 * attempt + j,
                              1.0, next_wid, drain=PHASE_TIMEOUT_S,
                              pileup=n)
            next_wid += log.due.size
            logs.append(log)
        if await quiet(f"pileups {attempt}"):
            break
    for attempt in range(WARM_ATTEMPTS):
        await child.ask("mark")
        log = await phase(run, client, records, 100 + attempt,
                          t["warm_seconds"], next_wid,
                          drain=PHASE_TIMEOUT_S)
        next_wid += log.due.size
        logs.append(log)
        if await quiet(f"steady {attempt}",
                       **step_summary(log, t["warm_seconds"])):
            break
    return next_wid


def step_summary(log, seconds: float) -> dict:
    """One rung of the sweep: answered share, latencies, and whether the
    backlog grew (second half's update p95 against the first's)."""
    upd, rd = latencies_ms(log, log.t_end)
    half = (log.due - log.t0)[~log.is_read] < seconds / 2.0
    p95a, p95b = percentile(upd[half], 95), percentile(upd[~half], 95)
    p50a, p50b = percentile(upd[half], 50), percentile(upd[~half], 50)
    inside = (log.status == loadgen.OK) & (log.done <= log.t0 + seconds)
    late = (log.sent - log.due) * 1e3
    return {
        "due": int(log.due.size),
        "answered_inside_share": float(inside.sum()) / max(log.due.size, 1),
        "failed": int((log.status != loadgen.OK).sum()),
        "update_p50_ms": percentile(upd, 50),
        "update_p95_ms": percentile(upd, 95),
        "read_p50_ms": percentile(rd, 50),
        "read_p95_ms": percentile(rd, 95),
        "update_p95_first_half_ms": p95a,
        "update_p95_second_half_ms": p95b,
        "update_p50_first_half_ms": p50a,
        "update_p50_second_half_ms": p50b,
        "gen_late_p95_ms": percentile(late[~np.isnan(late)], 95),
        "sustained": bool(inside.sum() >= 0.99 * log.due.size
                          and p95b <= 1.5 * p95a),
    }


async def main_async(run: Run) -> int:
    args, t = run.args, run.traffic
    shutil.rmtree(run.out, ignore_errors=True)
    os.makedirs(run.out)
    for sig in CUT_SIGNALS:
        signal.signal(sig, run.cut)
    child = Child(run)
    await child.start()
    client = None
    processes_left = 0
    try:
        dev = await child.event("device")
        run.device = {"platform": dev["platform"],
                      "device_kind": dev["device_kind"],
                      "count": dev["count"]}
        if args.rehearse:
            run.device["rehearsal"] = True
        native = await child.event("native")
        run.say("native", **{k: v for k, v in native.items()
                             if k not in ("event", *run.device)})
        serving = await child.event("serving")
        t_serving = time.perf_counter()
        run.say("serving", seconds_since_start=t_serving - T_START,
                compile_cache=serving["compile_cache"],
                control=serving["control"],
                **{k: serving[k] for k in run.settings})

        records = ycsb.Records(args.seed, run.recordcount,
                               run.cfg["n_ens"])
        client = loadgen.Client(serving["host"], serving["port"],
                                t["connections"])
        await client.connect()
        load_vsn = await loadgen.load(client, records, LOAD_OUTSTANDING,
                                      PHASE_TIMEOUT_S)
        t_loaded = time.perf_counter()
        run.say("loaded", records=run.recordcount,
                seconds=t_loaded - t_serving)
        next_wid, logs = run.recordcount, []
        next_wid = await warm_up(run, child, client, records, next_wid,
                                 logs, t_loaded)

        if args.sweep:
            await sweep(run, child, client, records, next_wid)
            return 3 if args.rehearse else 0

        await child.ask("mark")
        setup_s = time.perf_counter() - T_START
        run.say("measuring", seconds=args.seconds, setup_s=setup_s)
        log = await phase(run, client, records, 2, args.seconds, next_wid,
                          child, trace=bool(args.trace))
        logs.append(log)
        window_end_unix = time.time()
        run.say("window", seconds=args.seconds,
                drained_seconds=log.t_end - log.t_last_due,
                **step_summary(log, args.seconds),
                reads=int(log.is_read.sum()),
                keys=int(np.unique(log.keynum).size),
                inserts=int((~log.is_read
                             & (log.keynum >= run.recordcount)).sum()),
                reads_before_insert=check.reads_before_insert(
                    log, run.recordcount))

        # read-back: every key written since the load, and a seeded
        # sample of untouched ones, from device rounds
        await child.ask("fast_reads_off")
        written = np.unique(np.concatenate(
            [lg.keynum[~lg.is_read] for lg in logs]))
        rng = np.random.default_rng([int(args.seed), 0x5245])
        untouched = np.setdiff1d(
            rng.choice(run.recordcount, min(UNTOUCHED_SAMPLE,
                                            run.recordcount),
                       replace=False), written)
        t0 = time.perf_counter()
        got = await loadgen.read_back(
            client, records, np.concatenate([written, untouched]),
            READ_BACK_OUTSTANDING, PHASE_TIMEOUT_S)
        run.say("read_back", keys_written=int(written.size),
                keys_untouched=int(untouched.size),
                seconds=time.perf_counter() - t0)
        dump = await child.ask("dump")
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            for path in glob.glob(os.path.join(
                    run.out, "trace", "plugins", "profile", "*", "*.pb")):
                shutil.copy(path, args.keep)
            tag = f"{run.cell['name']}.{args.seed}"
            np.savez(os.path.join(args.keep, tag + ".npz"),
                     **{k: getattr(log, k) for k in (
                         "due", "sent", "done", "status", "is_read",
                         "keynum")})
            with open(os.path.join(args.keep, tag + ".json"), "w") as f:
                json.dump(dump, f)
    finally:
        if client is not None:
            client.close()
        await child.stop()
        # the driver's own check, made here first: nothing this run
        # started is alive once the child has been stopped
        processes_left = run.end_started()
        shutil.rmtree(run.out, ignore_errors=True)

    v = check.verdict(run.recordcount, load_vsn, logs, got, dump,
                      run.device, mesh=run.cfg.get("engine") == "mesh",
                      cfg=run.cfg)
    run.say("checked", **run.settings, **v,
            processes_left=processes_left)
    if processes_left:
        return 1                # killed by now, but it was there
    facts = {"dump": dump, "log": log, "cfg": run.cfg,
             "seconds": args.seconds, "here": HERE,
             "window_end_unix": window_end_unix}
    layer = per_layer(run, facts)
    e2e = end_to_end(log, args.seconds, setup_s)
    names = {m["name"] for m in run.bench["end_to_end"]
             if "workloads" not in m
             or run.cell["name"] in m["workloads"]}
    e2e = {k: val for k, val in e2e.items() if k in names}
    for group, metrics in (("end_to_end", e2e), ("per_layer", layer)):
        run.say(group, **{k: {"value": val, "unit": unit, "samples": n}
                          for k, (val, unit, n) in metrics.items()})
    run.say("compile_events_since_mark", window_end_unix=window_end_unix,
            events=dump["compile_events"])
    if args.rehearse:
        run.say("rehearsed",
                correct_but_for_the_device=v["replies_correct"])
        return 3

    reported = layer if args.trace else e2e
    device = {"platform": run.device["platform"],
              "kind": run.device["device_kind"],
              "count": run.device["count"],
              "memory_peak_bytes": dump["memory_peak_bytes"]}
    result = {
        "correct": v["correct"],
        "attempted": int(log.due.size),
        "failed": int((log.status != loadgen.OK).sum()),
        "metrics": {k: {"value": val, "unit": unit}
                    for k, (val, unit, _) in reported.items()},
        "device": device,
    }
    red = (dump.get("trace") or {}).get("reduction")
    if args.trace:
        if red is None:
            raise SystemExit("traced run: no operation ran on a device")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result.update(run.settings)
    # each number compared beside its limit (and each guarantee as the
    # run was found to keep it): last in the line, and the last lines
    # of stderr
    result["guarantees"] = v["guarantees"]
    result["compared"] = {c["name"]: [c["value"], c["limit"]]
                          for c in v["compared"]}
    print(json.dumps(result), flush=True)
    for name, kept in v["guarantees"].items():
        print(f"guarantee {name}: {'kept' if kept else 'BROKEN'}",
              file=sys.stderr)
    for name, (value, limit) in result["compared"].items():
        print(f"{name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return 0


async def sweep(run: Run, child: Child, client, records,
                next_wid: int) -> None:
    """One set-up, a ladder of rates (x ``--sweep-factor``) from
    ``--sweep``'s start; the knee is the highest sustained rung."""
    rate, knee, rows = float(run.args.sweep), None, []
    for step in range(run.args.sweep_steps):
        run.traffic["rate"] = rate
        await child.ask("mark")
        log = await phase(run, client, records, 10 + step, SWEEP_STEP_S,
                          next_wid)
        next_wid += log.due.size
        dump = await child.ask("dump")
        recs = [r for r in dump["lat_records"] if "k" in r]
        marks = {m: float(np.median([r.get(m, 0.0) for r in recs]) * 1e3)
                 for m in ("queue_wait", "h2d", "dispatch", "device_d2h",
                           "wal", "resolve", "total")} if recs else {}
        since = dump["since_mark"]
        row = dict(rate=rate, **step_summary(log, SWEEP_STEP_S),
                   flushes=since["flushes"],
                   ops_served=since["ops_served"],
                   fast_hits=since["read_fastpath_hits"],
                   # the rung's own readings of per-layer metrics
                   # (``mean_k`` is its ``rounds_per_flush``)
                   ops_per_flush=since["ops_served"]
                   / max(since["flushes"], 1),
                   step_wait_p50_ms=float(np.median(
                       [r.get("device_d2h", 0.0) + r.get("inflight_wait", 0.0)
                        for r in recs]) * 1e3) if recs else None,
                   mean_k=float(np.mean([r["k"] for r in recs]))
                   if recs else None,
                   max_k=max((r["k"] for r in recs), default=None),
                   flush_marks_p50_ms=marks,
                   compiles=len(dump["compile_events"]))
        rows.append(row)
        run.say("sweep_step", **row)
        if row["sustained"]:
            knee = rate
        elif row["answered_inside_share"] < 0.9:
            break               # collapsed: a higher rung says nothing
        rate *= run.args.sweep_factor
    run.say("sweep", knee=knee, steps=len(rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", type=float, default=0.0,
                    help="builders: first rate of a x1.5 ladder")
    ap.add_argument("--sweep-steps", type=int, default=8)
    ap.add_argument("--sweep-factor", type=float, default=1.5)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="builders: offer this rate, not the cell's")
    ap.add_argument("--rehearse", action="store_true",
                    help="builders: a CPU, cut shapes, exit 3")
    ap.add_argument("--benchmark", default=None, metavar="FILE",
                    help="builders: read this in place of "
                         "BENCHMARK.json")
    ap.add_argument("--set", action="append", metavar="KEY=JSON",
                    help="builders: override a key of the traffic or "
                         "the configuration file")
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="builders: leave the window's log, the child's "
                         "dump and the trace file there")
    ap.add_argument("--control", default=None,
                    help="builders: serve with one guarantee broken")
    args = ap.parse_args(argv)
    return asyncio.run(main_async(Run(args)))


if __name__ == "__main__":
    sys.exit(main())
