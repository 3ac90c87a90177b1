"""Device time of a kept profiler trace, grouped by named scope.

``ops/engine.py`` puts ``jax.named_scope`` names on the step's phases
(``engine.SCOPES``); they reach each HLO instruction's ``op_name`` and,
in a trace taken on a TPU, the ``tf_op`` stat of the operation's event
metadata (``jit(step)/while/body/slot_scatter/scatter``), beside the
``source`` line that emitted it.  The benchmark's reducer keeps only
``name type opcode`` of an operation (``%copy.702 s32[10000,5,128]
copy``), so which phase owns a copy is read here, from a trace kept by
``benchmarks/run.py --keep DIR``::

    python tools/trace_scopes.py DIR/<host>.xplane.pb [--top 12]

Prints one JSON object: ``scopes`` (scope -> self seconds and share of
the device's busy self time; an operation inside no scope of
``engine.SCOPES`` counts under ``(none)``), and ``ops`` (the ``--top``
operations by self time with their scope, ``tf_op`` and source line).
Self time is ``benchmarks/trace_reduce.self_times`` (an operation's
events minus what ran nested inside them), summed over the devices.

``jax.profiler.ProfileData`` shows an event's own stats but not its
metadata's, so the file is read as what it is: a protobuf (``XSpace``,
tsl/profiler/protobuf/xplane.proto), by field number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, Iterator, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SCOPE = "(none)"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return val, i


def fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, bytes for anything length-delimited or fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        yield num, val


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    kv = dict(fields(buf))
    return kv[1], kv[2]


def device_ops(path: str) -> Dict[str, Dict[str, Any]]:
    """plane name -> {"events": [(metadata id, start ns, dur ns)],
    "meta": {id: {"name", "tf_op", "source"}}} of every TPU plane's
    ``XLA Ops`` line."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, Any]] = {}
    for num, plane in fields(space):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for pnum, val in fields(plane):
            if pnum == 2:
                name = val.decode()
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:
                key, md = _map_entry(val)
                emeta[key] = md
            elif pnum == 5:
                key, md = _map_entry(val)
                smeta[key] = dict(fields(md)).get(2, b"").decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        events: List[Tuple[int, float, float]] = []
        for line in lines:
            lf = list(fields(line))
            if next((v for n, v in lf if n == 2),
                    b"").decode() != OPS_LINE:
                continue
            t0_ns = next((v for n, v in lf if n == 3), 0)
            for n, ev in lf:
                if n != 4:
                    continue
                e = dict(fields(ev))
                events.append((e.get(1, 0),
                               t0_ns + e.get(2, 0) / 1e3,
                               e.get(3, 0) / 1e3))
        meta: Dict[int, Dict[str, str]] = {}
        for key in {e[0] for e in events}:
            md: Dict[str, str] = {"name": "", "tf_op": "", "source": ""}
            for mnum, val in fields(emeta.get(key, b"")):
                if mnum == 2:
                    md["name"] = val.decode(errors="replace")
                elif mnum == 5:
                    st = dict(fields(val))
                    stat = smeta.get(st.get(1), "")
                    if stat in ("tf_op", "source") and 5 in st:
                        md[stat] = st[5].decode(errors="replace")
            meta[key] = md
        if events:
            out[name] = {"events": events, "meta": meta}
    return out


def scope_of(tf_op: str, scopes: Tuple[str, ...]) -> str:
    """The innermost component of an ``op_name`` path that is one of
    ``scopes``."""
    for part in reversed(tf_op.split("/")):
        if part in scopes:
            return part
    return NO_SCOPE


def grouped(path: str, top: int) -> Dict[str, Any]:
    import trace_reduce
    from riak_ensemble_tpu.ops.engine import SCOPES

    by_scope: Dict[str, float] = {}
    by_op: Dict[Tuple[str, str, str, str], float] = {}
    for plane in device_ops(path).values():
        meta = plane["meta"]
        for key, ns in trace_reduce.self_times(plane["events"]).items():
            md = meta[key]
            scope = scope_of(md["tf_op"], SCOPES)
            by_scope[scope] = by_scope.get(scope, 0.0) + ns
            op = (trace_reduce.op_name(md["name"]), scope, md["tf_op"],
                  md["source"])
            by_op[op] = by_op.get(op, 0.0) + ns
    busy = sum(by_scope.values()) or 1.0
    return {
        "self_s": busy / 1e9,
        "scopes": {s: {"self_s": ns / 1e9, "share": ns / busy}
                   for s, ns in sorted(by_scope.items(),
                                       key=lambda kv: -kv[1])},
        "ops": [{"op": op, "scope": scope, "tf_op": tf_op,
                 "source": source, "self_s": ns / 1e9,
                 "share": ns / busy}
                for (op, scope, tf_op, source), ns in sorted(
                    by_op.items(), key=lambda kv: -kv[1])[:top]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a .xplane.pb kept by run.py --keep")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    print(json.dumps(grouped(args.trace, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
