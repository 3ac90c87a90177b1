"""ctypes bridge to the native enqueue-pack kernel
(``native/enqueuekernel.cc`` — the sibling of ``resolvekernel.cc``,
compiled into the same ``_retpu_resolve.so``).

PR 7 moved the per-flush RESOLVE half to C++ and the latency breakdown
promptly showed the remaining host cost on the ENQUEUE half
(``queue_wait`` + per-op future fan-out — ROADMAP item 4).  The
service now carries each flush's pending ops as flat int32 LANES and
this module exposes the C++ pass that scatters all five ``[K, E]`` op
planes from those lanes in one traversal.

Knob discipline mirrors :mod:`.resolve_native` exactly:

- ``RETPU_NATIVE_ENQUEUE=0`` opts the service out of the whole
  slab-resident enqueue path — per-entry plane pack and per-op future
  fan-out run as before (the oracle arm of
  ``tests/test_native_enqueue.py``).
- Knob on but no toolchain / stale .so: the slab path still runs, with
  the plane pack through numpy fancy indexing (the ``enqueue_fallback``
  arm) — graceful degradation, never a crash.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

from riak_ensemble_tpu.utils import native

__all__ = ["enabled", "get", "NativeEnqueue"]

_instance: Optional["NativeEnqueue"] = None
_instance_tried = False


def enabled() -> bool:
    """The ``RETPU_NATIVE_ENQUEUE`` knob (default on): ``0`` pins the
    historical per-entry pack + per-op future fan-out — the oracle arm
    of the equivalence tests."""
    return os.environ.get("RETPU_NATIVE_ENQUEUE", "1") != "0"


def get() -> Optional["NativeEnqueue"]:
    """The loaded kernel wrapper, or None when the knob is off, the
    toolchain can't build the .so, or the .so predates the enqueue
    symbols (callers then use the numpy lane pack).  Re-reads the knob
    per call (a service constructed under ``RETPU_NATIVE_ENQUEUE=0``
    never picks the kernel up); the library handle builds once."""
    global _instance, _instance_tried
    if not enabled():
        return None
    if not _instance_tried:
        _instance_tried = True
        lib = native.load_resolve()
        if lib is not None and hasattr(lib, "retpu_enqueue_pack") \
                and hasattr(lib, "retpu_enqueue_gather") \
                and lib.retpu_enqueue_version() >= 2:
            _instance = NativeEnqueue(lib)
    return _instance


def _pt(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


class NativeEnqueue:
    """Thin wrapper over the C ABI; outputs are written in place and
    are bit-identical to the numpy fallback's.  Both passes walk the
    pending slab's RUN DESCRIPTORS — per taken entry its ensemble
    column, first plane row, run length and uniform op kind — so the
    Python→C conversion cost scales with entries, not ops."""

    def __init__(self, lib: ctypes.CDLL) -> None:
        self._lib = lib

    def pack(self, k: int, e: int, ent_col: np.ndarray,
             ent_row0: np.ndarray, ent_len: np.ndarray,
             ent_kind: np.ndarray, slot: np.ndarray, val: np.ndarray,
             expe: np.ndarray, exps: np.ndarray,
             kind_p: np.ndarray, slot_p: np.ndarray,
             val_p: np.ndarray, expe_p: np.ndarray,
             exps_p: np.ndarray) -> bool:
        """Scatter the pending slab into the five zero-initialized
        ``[K, E]`` int32 planes in one C traversal.  False on an
        out-of-grid run (the caller re-packs through the numpy path,
        which raises the honest IndexError)."""
        rc = self._lib.retpu_enqueue_pack(
            len(ent_col), k, e, _pt(ent_col), _pt(ent_row0),
            _pt(ent_len), _pt(ent_kind), _pt(slot), _pt(val),
            _pt(expe), _pt(exps), _pt(kind_p), _pt(slot_p),
            _pt(val_p), _pt(expe_p), _pt(exps_p))
        return rc == 0

    def gather(self, k: int, e: int, ent_col: np.ndarray,
               ent_row0: np.ndarray, ent_len: np.ndarray,
               committed: np.ndarray, get_ok: np.ndarray,
               found: np.ndarray, value: np.ndarray,
               vsn: np.ndarray, n_rows: int):
        """Result planes → completion slab: ``[R]`` records in taken
        order (ok, get_ok, found, value, vsn[R, 2]), one C traversal.
        None on a layout surprise (the caller falls back to the numpy
        gather)."""
        out_ok = np.empty((n_rows,), np.uint8)
        out_gok = np.empty((n_rows,), np.uint8)
        out_fnd = np.empty((n_rows,), np.uint8)
        out_val = np.empty((n_rows,), np.int32)
        out_vsn = np.empty((n_rows, 2), np.int32)
        rc = self._lib.retpu_enqueue_gather(
            len(ent_col), k, e, _pt(ent_col), _pt(ent_row0),
            _pt(ent_len), _pt(committed), _pt(get_ok), _pt(found),
            _pt(value), _pt(vsn), _pt(out_ok), _pt(out_gok),
            _pt(out_fnd), _pt(out_val), _pt(out_vsn))
        if rc != 0:
            return None
        return (out_ok.view(bool), out_gok.view(bool),
                out_fnd.view(bool), out_val, out_vsn)
