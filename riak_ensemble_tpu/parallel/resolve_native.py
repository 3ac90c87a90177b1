"""ctypes bridge to the native single-pass resolve kernel
(``native/resolvekernel.cc``).

The batched service's per-flush resolve half — packed-result unpack,
``_slot_vsn``/``_inline_value`` mirror scatter, WAL record encode and
the changed-slot delta-frame build — is pure Python per flush and
binds the keyed host ceiling before the device does (ROADMAP item 5).
This module exposes the C++ pass that replaces those four traversals
with one, loaded through :mod:`riak_ensemble_tpu.utils.native`'s
builder with the same degradation discipline as the wire codec and
treestore: no toolchain (or ``RETPU_NATIVE_RESOLVE=0``) means the
pure-Python implementations keep running — they remain the oracle,
and every native output is byte-identical to theirs
(tests/test_native_resolve.py fuzzes the equivalence).
"""

from __future__ import annotations

import ctypes
import os
from typing import Any, Optional, Tuple

import numpy as np

from riak_ensemble_tpu.utils import native

__all__ = ["enabled", "get", "NativeResolve"]

_instance: Optional["NativeResolve"] = None
_instance_tried = False


def enabled() -> bool:
    """The ``RETPU_NATIVE_RESOLVE`` knob (default on): ``0`` pins the
    pure-Python resolve path — the oracle of the equivalence
    tests."""
    return os.environ.get("RETPU_NATIVE_RESOLVE", "1") != "0"


def get() -> Optional["NativeResolve"]:
    """The loaded kernel wrapper, or None when the knob is off or the
    toolchain can't build it (callers use the Python fallback).  The
    knob is re-read per call so a service constructed under
    ``RETPU_NATIVE_RESOLVE=0`` (the tests' fallback arm) never picks
    the kernel up; the library handle itself is built once."""
    global _instance, _instance_tried
    if not enabled():
        return None
    if not _instance_tried:
        _instance_tried = True
        lib = native.load_resolve()
        if lib is not None:
            _instance = NativeResolve(lib)
    return _instance


def _pt(a: Optional[np.ndarray]):
    return None if a is None else a.ctypes.data_as(ctypes.c_void_p)


_MERGE_LUT: Optional[Tuple[np.ndarray, np.ndarray]] = None


def _merge_lut() -> Tuple[np.ndarray, np.ndarray]:
    """(merge_of[16], negate[16]) LUTs for the native comm fold —
    built from :mod:`riak_ensemble_tpu.funref`'s classification so C
    never hard-codes the RMW fun table (merge-CLASS codes it does pin:
    they are the wire's cell-fun bytes)."""
    global _MERGE_LUT
    if _MERGE_LUT is None:
        from riak_ensemble_tpu import funref
        merge_of = np.full((16,), -1, np.int32)
        for code, mcls in funref.MERGE_OF.items():
            merge_of[code] = mcls
        negate = np.zeros((16,), np.uint8)
        negate[funref.RMW_SUB] = 1
        _MERGE_LUT = (merge_of, negate)
    return _MERGE_LUT


class NativeResolve:
    """Thin, allocation-explicit wrapper over the C ABI.  Every method
    returns numpy arrays shaped exactly like its Python-fallback
    counterpart's output."""

    def __init__(self, lib: ctypes.CDLL) -> None:
        self._lib = lib

    # -- 1) packed-result unpack ----------------------------------------

    def unpack(self, flat: np.ndarray, e: int, m: int, k: int,
               want_vsn: bool, active: Optional[np.ndarray],
               a_width: int, sliced: bool):
        """Single-pass :func:`batched_host.unpack_results` replacement
        (full-width scatter included).  Returns the same 8-tuple, or
        None when the payload doesn't match the expected layout (the
        caller falls back to the Python unpack, which raises the
        honest error)."""
        flat = np.ascontiguousarray(flat, np.uint8)
        if active is not None:
            active = np.ascontiguousarray(active, np.int32)
        won = np.zeros((e,), bool)
        quorum = np.zeros((e,), bool)
        corrupt = np.zeros((e, m), bool)
        if k:
            committed = np.zeros((k, e), bool)
            get_ok = np.zeros((k, e), bool)
            found = np.zeros((k, e), bool)
            value = np.zeros((k, e), np.int32)
            vsn = np.zeros((k, e, 2), np.int32) if want_vsn else None
        else:
            # election-only launches carry no client planes; the
            # kernel still unpacks the control planes
            committed = get_ok = found = value = vsn = None
        rc = self._lib.retpu_resolve_unpack(
            _pt(flat), flat.nbytes, e, m, k, int(want_vsn),
            _pt(active), 0 if active is None else len(active),
            a_width, int(bool(sliced)),
            _pt(won), _pt(quorum), _pt(corrupt),
            _pt(committed), _pt(get_ok), _pt(found),
            _pt(value), _pt(vsn))
        if rc != 0:
            return None
        return (won, quorum, corrupt, committed, get_ok, found,
                value, vsn)

    # -- 2) mirror-slab scatter -----------------------------------------

    def scatter_mirrors(self, e_total: int, s_dim: int,
                        kind: np.ndarray, slot: np.ndarray,
                        committed: np.ndarray, get_ok: np.ndarray,
                        found: np.ndarray, value: np.ndarray,
                        vsn: Optional[np.ndarray],
                        cols: np.ndarray, kcounts: np.ndarray,
                        ack_reads: bool,
                        op_codes: Tuple[int, int, int, int],
                        vsn_np: np.ndarray, vsn_ok: np.ndarray,
                        inl_np: np.ndarray, inl_ok: np.ndarray,
                        inline_cls: np.ndarray) -> bool:
        """Scatter a flush's committed mirror updates straight into
        the service's slabs — the per-op dict-write half of the
        resolve loop, in identical per-column round order."""
        op_put, op_cas, op_get, op_rmw = op_codes
        rc = self._lib.retpu_resolve_mirrors(
            e_total, s_dim,
            _pt(np.ascontiguousarray(kind, np.int32)),
            _pt(np.ascontiguousarray(slot, np.int32)),
            _pt(np.ascontiguousarray(committed, np.uint8)),
            _pt(np.ascontiguousarray(get_ok, np.uint8)),
            _pt(np.ascontiguousarray(found, np.uint8)),
            _pt(np.ascontiguousarray(value, np.int32)),
            _pt(None if vsn is None
                else np.ascontiguousarray(vsn, np.int32)),
            _pt(np.ascontiguousarray(cols, np.int32)),
            _pt(np.ascontiguousarray(kcounts, np.int32)),
            len(cols), int(bool(ack_reads)),
            op_put, op_cas, op_get, op_rmw,
            _pt(vsn_np), _pt(vsn_ok), _pt(inl_np), _pt(inl_ok),
            _pt(inline_cls))
        return rc == 0

    # -- 3) WAL arena encode --------------------------------------------

    def wal_encode(self, e_total: int, lane_j: np.ndarray,
                   lane_e: np.ndarray, lane_slot: np.ndarray,
                   lane_f2: np.ndarray, lane_inline: np.ndarray,
                   key_is_bytes: np.ndarray, key_off: np.ndarray,
                   key_len: np.ndarray, key_arena: bytes,
                   pay_off: np.ndarray, pay_len: np.ndarray,
                   pay_arena: bytes, committed: np.ndarray,
                   value: np.ndarray, vsn: np.ndarray
                   ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Pickle the flush's committed keyed WAL records into one
        preallocated byte arena.  Returns ``(arena_view, index)`` —
        ``index`` rows are (key_off, key_len, val_off, val_len) per
        lane, zero-length for uncommitted lanes — or None on a sizing
        bug (caller falls back)."""
        n = len(lane_j)
        karr = np.frombuffer(key_arena, np.uint8)
        parr = np.frombuffer(pay_arena, np.uint8)
        # exact worst case per record pair: two PROTO+FRAME headers
        # (22), key pickle ("kv" + two ints <= 18), value pickle
        # (MARK/ints/bool/tuple overhead <= 30) + key and payload
        # bytes with their own opcode headers (<= 6 each)
        cap = int(76 * n + int(key_len.sum())
                  + int(np.maximum(pay_len, 0).sum()))
        arena = np.empty((max(cap, 1),), np.uint8)
        idx = np.zeros((n, 4), np.int64)
        used = self._lib.retpu_wal_encode(
            n, e_total, _pt(lane_j), _pt(lane_e), _pt(lane_slot),
            _pt(lane_f2), _pt(lane_inline), _pt(key_is_bytes),
            _pt(key_off), _pt(key_len), _pt(karr),
            _pt(pay_off), _pt(pay_len), _pt(parr),
            _pt(np.ascontiguousarray(committed, np.uint8)),
            _pt(np.ascontiguousarray(value, np.int32)),
            _pt(np.ascontiguousarray(vsn, np.int32)),
            _pt(arena), arena.nbytes, _pt(idx))
        if used < 0:
            return None
        return arena[:used], idx

    # -- 4) delta-frame sections ----------------------------------------

    def delta_sections(self, k: int, e_dim: int,
                       committed: np.ndarray, value: np.ndarray,
                       kind: np.ndarray, slot: np.ndarray,
                       opval: np.ndarray, quorum: np.ndarray,
                       op_codes: Tuple[int, int, int], j_dt, s_dt
                       ) -> Optional[Tuple]:
        """The committed-cell sections + section CRC of
        :func:`repgroup.build_delta_entry`, one column-major pass.
        Returns ``(cols, counts, jj, slots, vals, rmw_b, q_b, crc)``
        with the exact dtypes/bytes of the numpy path."""
        op_put, op_cas, op_rmw = op_codes
        ncap = k * e_dim
        cols = np.empty((e_dim,), np.uint16)
        counts = np.empty((e_dim,), np.uint16)
        jj = np.empty((max(ncap, 1),), j_dt)
        slots = np.empty((max(ncap, 1),), s_dt)
        vals = np.empty((max(ncap, 1),), np.int32)
        rmw_b = np.empty(((ncap + 7) // 8 or 1,), np.uint8)
        q_b = np.empty(((e_dim + 7) // 8,), np.uint8)
        meta = np.zeros((2,), np.int64)
        crc = ctypes.c_uint32(0)
        rc = self._lib.retpu_delta_sections(
            k, e_dim,
            _pt(np.ascontiguousarray(committed, np.uint8)),
            _pt(np.ascontiguousarray(value, np.int32)),
            _pt(np.ascontiguousarray(kind, np.int32)),
            _pt(np.ascontiguousarray(slot, np.int32)),
            _pt(np.ascontiguousarray(opval, np.int32)),
            _pt(np.ascontiguousarray(quorum, np.uint8)),
            op_put, op_cas, op_rmw,
            int(np.dtype(j_dt).itemsize), int(np.dtype(s_dt).itemsize),
            _pt(cols), _pt(counts), _pt(jj), _pt(slots), _pt(vals),
            _pt(rmw_b), _pt(q_b), _pt(meta), ctypes.byref(crc))
        if rc != 0:
            return None
        ncells, ncols = int(meta[0]), int(meta[1])
        return (cols[:ncols].copy(), counts[:ncols].copy(),
                jj[:ncells].copy(), slots[:ncells].copy(),
                vals[:ncells].copy(), rmw_b[:(ncells + 7) // 8].copy(),
                q_b.copy(), int(crc.value))

    # -- 5) commutative-lane fold ---------------------------------------

    def comm_fold(self, committed: np.ndarray, exp_e: np.ndarray,
                  slot: np.ndarray, val: np.ndarray,
                  cand: np.ndarray) -> Optional[dict]:
        """The per-candidate-column coalescing fold of
        :func:`repgroup.build_comm_entry` (ARCHITECTURE §18), one C
        pass.  Returns ``{col: (cells, n_ops)}`` where cells =
        ``[(slot, merge_class, folded_operand, last_rank, last_j),
        ...]`` in first-seen slot order and candidate columns
        disqualified by a mixed-class slot are ABSENT — or None when
        the loaded library predates the symbol (the caller runs the
        Python fold, which is also the equivalence oracle)."""
        fn = getattr(self._lib, "retpu_comm_fold", None)
        if fn is None:
            return None
        k, e_dim = committed.shape
        committed_u8 = np.ascontiguousarray(committed, np.uint8)
        ncap = max(int(committed_u8.sum()), 1)
        merge_of, negate = _merge_lut()
        out_cols = np.empty((e_dim,), np.int32)
        out_counts = np.empty((e_dim,), np.int32)
        out_nops = np.empty((e_dim,), np.int32)
        out_slots = np.empty((ncap,), np.int32)
        out_funs = np.empty((ncap,), np.uint8)
        out_ops = np.empty((ncap,), np.int32)
        out_rl = np.empty((ncap,), np.int32)
        out_jl = np.empty((ncap,), np.int32)
        meta = np.zeros((2,), np.int64)
        rc = fn(
            int(k), int(e_dim), _pt(committed_u8),
            _pt(np.ascontiguousarray(exp_e, np.int32)),
            _pt(np.ascontiguousarray(slot, np.int32)),
            _pt(np.ascontiguousarray(val, np.int32)),
            _pt(np.ascontiguousarray(cand, np.uint8)),
            _pt(merge_of), _pt(negate),
            _pt(out_cols), _pt(out_counts), _pt(out_nops),
            _pt(out_slots), _pt(out_funs), _pt(out_ops),
            _pt(out_rl), _pt(out_jl), _pt(meta))
        if rc != 0:
            return None
        out = {}
        pos = 0
        for i in range(int(meta[0])):
            cnt = int(out_counts[i])
            out[int(out_cols[i])] = (
                [(int(out_slots[x]), int(out_funs[x]),
                  int(out_ops[x]), int(out_rl[x]), int(out_jl[x]))
                 for x in range(pos, pos + cnt)],
                int(out_nops[i]))
            pos += cnt
        return out
