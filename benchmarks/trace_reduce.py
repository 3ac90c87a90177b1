"""From a profiler trace (``.xplane.pb``) to the device's busy time,
its operations by time and its longest idle gaps.

A device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation that ran, nested where an operation (a
``while``) contains others.  Busy time is the UNION of those events'
intervals, so nesting and overlap count once; an operation's time is
its SELF time (its events minus what runs nested inside them).  An idle
gap is named by the innermost host event (any thread of the
``/host:CPU`` plane) that covers its middle; the program carries no
``TraceAnnotation`` of its own yet, so a gap no host event covers is
``unattributed``.

Only :func:`load` touches JAX (``jax.profiler.ProfileData``); the
arithmetic below it takes plain tuples and is what the tests check.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> str:
    """The trace prints an operation as its whole HLO line; keep its
    name, result type and opcode: ``%fusion.8 f32[512,512] fusion``."""
    name, eq, rest = text.partition(" = ")
    code = _OPCODE.search(" " + rest)
    if not eq or code is None:
        return text[:96]
    kind = "tuple" if rest.startswith("(") else re.split(r"[{ ]", rest)[0]
    return f"{name} {kind} {code.group(1)}"


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns), ...]},
    "host": [...]}`` of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events if e.duration_ns > 0]
    return {"devices": devices, "host": host}


def busy_union(events: list) -> list:
    """Merged ``[start, end]`` intervals of the events, in order."""
    out: list = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


def self_times(events: list) -> dict:
    """name -> nanoseconds in events of that name, not counting what
    ran nested inside them."""
    total: dict = {}
    stack: list = []     # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def _host_name(host: list, at: float) -> str:
    best = None
    for name, start, dur in host:
        if start <= at <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "unattributed"


def reduce(trace: dict, window_s: float) -> dict | None:
    """The reduction the per-layer readers and ``breakdown`` use, or
    None where no operation ran on a device (a rehearsal on a CPU).
    ``window_s`` is the traced window's length by the tracing process's
    own clock; busy seconds are averaged over the devices."""
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        return None
    busy_ns = 0.0
    ops: dict = {}
    gaps: list = []
    for events in devices.values():
        union = busy_union(events)
        busy_ns += sum(end - start for start, end in union)
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + ns
        gaps += [(b[0] - a[1], (a[1] + b[0]) / 2.0)
                 for a, b in zip(union, union[1:])]
    n = len(devices)
    gaps.sort(reverse=True)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "busy_s": busy_ns / n / 1e9,
        "window_s": window_s,
        "device_ops": [[name, ns / n / 1e9] for name, ns in top_ops],
        "idle_gaps": [[_host_name(trace["host"], mid), ns / 1e9]
                      for ns, mid in gaps[:TOP]],
    }
