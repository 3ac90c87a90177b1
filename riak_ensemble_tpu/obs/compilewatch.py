"""Compile-event telemetry for jitted hot-path programs.

Every jitted step/pack/scatter variant the launch path dispatches is
wrapped in a :class:`CompileWatch`: before and after each call the
wrapper reads the jitted callable's executable-cache size, and a
growth means THIS call paid an XLA compile.  The event records which
program, the argument shape signature (the (K, A) bucket, in
practice) and the wall time the call took — so a ``warmup()``
coverage gap or a first-use compile at a fresh bucket becomes a
visible ``retpu_compile_events_total{phase="serve"}`` increment and
a named log entry instead of an unexplained dispatch-p99 spike.

The detection is exact, not a latency heuristic: ``jax.jit``
callables expose ``_cache_size()`` (the per-function executable
count).  Callables without it (plain Python closures, the mesh
pack wrapper) pass through unwatched.  The cache is per PROCESS and
per jitted function object — services sharing module-level step
programs share their compiles, which is precisely what the warmup
story needs to observe.

Cost: one C-level ``_cache_size()`` call before and after each
launch dispatch; the shape signature is only computed on a miss.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, Optional

__all__ = ["CompileWatch", "COMPILE_EVENTS", "signature"]

#: process-global bounded log of compile events (newest last) — the
#: flight recorder's compile-event section reads the service-local
#: log, this one serves debugging across services in one process
COMPILE_EVENTS: "deque[Dict[str, Any]]" = deque(maxlen=256)


def signature(args: tuple, kwargs: dict) -> str:
    """Compact signature of a call, one part an argument: an array's
    ``dtype[shape]``, a pytree's first leaf and its leaf count
    (``u32[64,3]..x14``: an engine state), a static scalar as it is
    (``want_vsn=True``), e.g.
    ``"u32[64,3]..x14;int32[13,64];bool[64,3];want_vsn=True;gather=8"``
    — enough to name the (K, A) bucket that compiled.  Computed only
    on a cache miss."""
    def one(x: Any) -> str:
        if isinstance(x, (bool, int, float, str, type(None))):
            return repr(x)
        leaves = [leaf for leaf in jax.tree_util.tree_leaves(x)
                  if getattr(leaf, "shape", None) is not None]
        if not leaves:
            return "?"
        dt = getattr(leaves[0], "dtype", None)
        dt = getattr(dt, "name", str(dt)) if dt is not None else "?"
        part = f"{dt}[{','.join(map(str, leaves[0].shape))}]"
        return part if len(leaves) == 1 else f"{part}..x{len(leaves)}"

    try:
        import jax
        return ";".join([one(a) for a in args]
                        + [f"{k}={one(v)}" for k, v in kwargs.items()])
    except Exception:
        return "?"  # telemetry must never fail the launch


class CompileWatch:
    """Callable wrapper that reports executable-cache misses.

    ``on_miss`` (if given) receives the event dict after it is
    appended to :data:`COMPILE_EVENTS`; attribute access (``lower``,
    ``_cache_size``, ...) passes through to the wrapped callable so
    AOT helpers keep working on the watched object.
    """

    __slots__ = ("fn", "name", "on_miss")

    def __init__(self, fn: Callable, name: str,
                 on_miss: Optional[Callable[[Dict[str, Any]], None]]
                 = None) -> None:
        self.fn = fn
        self.name = name
        self.on_miss = on_miss

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        fn = self.fn
        cs = getattr(fn, "_cache_size", None)
        if cs is None:
            return fn(*args, **kwargs)
        try:
            before = cs()
        except Exception:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        try:
            missed = cs() > before
        except Exception:
            missed = False
        if missed:
            ev = {
                "fn": self.name,
                "shapes": signature(args, kwargs),
                "compile_ms": round(dt * 1e3, 3),
                "t_unix": time.time(),
            }
            COMPILE_EVENTS.append(ev)
            if self.on_miss is not None:
                try:
                    self.on_miss(ev)
                except Exception:
                    pass  # telemetry must never fail the launch
        return out

    def __getattr__(self, item: str) -> Any:
        return getattr(self.fn, item)
