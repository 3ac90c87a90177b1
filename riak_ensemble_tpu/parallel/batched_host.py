"""Host↔engine bridge: many ensembles served through the batched
device engine.

The scalar actor stack (:mod:`riak_ensemble_tpu.peer`) is the protocol
oracle and the fully-general path (dynamic membership, synctree,
gossip).  This module is the scale path the north star describes: a
host *service* that multiplexes thousands of engine-backed ensembles —

- client ops (kget/kput/kdelete) queue per ensemble and flush as one
  fused-step launch per tick: ``[K, E]`` op matrices, one device
  dispatch for every queued op of every ensemble (the batched analog
  of E leader processes × worker pools).  There is ONE way into the
  step: every launch (a flush, an election-only round, ``execute()``,
  a replica's apply) uploads one op slab and calls one program,
  ``(state, op slab, up) -> (state, packed results)``, sliced where a
  device can slice and full width otherwise (``_launch_enqueue``);
- the host side keeps what consensus doesn't need on-device: the
  key→slot assignment per ensemble, the payload store (device arrays
  carry int32 handles; real bytes live host-side keyed by handle —
  engine.py's object-store contract), per-ensemble leases (monotonic
  clock), and the failure detector (an ``up`` mask per ensemble);
- leaderless or leader-down ensembles get an election folded into the
  SAME launch (the slab's elect rows) — the thundering-herd
  re-election after failures is one kernel call, not E timers.

Results come back to client futures after each flush (one d2h per
flush, amortized over every op in the batch).

Launches are TWO-PHASE (pipelined async service execution): an
enqueue half dispatches the fused step + packed-result transfer
without any host read, and a resolve half — up to ``pipeline_depth``
launches later — unpacks, applies the host mirrors, WAL-logs and
fans out the futures, so a round's d2h transfer and host bookkeeping
overlap the next round's device step.  Ordering, the WAL-before-ack
barrier, and corruption→exchange semantics are preserved; see
docs/ARCHITECTURE.md §7 "Two-phase launch pipeline".

Launches are ACTIVE-COLUMN COMPACTED: one hot ensemble forces the
`[K, E]` grid to its queue depth, but the flush gathers down to the
columns that actually hold ops (`[K, A]`, A pow2-bucketed like the K
ladder).  At low occupancy of what one device holds the fused step
itself runs on the gathered grid (``full_step_sliced_slab``, one
chip's or, per shard inside ``shard_map``, the mesh engine's —
compute, h2d and the packed d2h all scale with the live working
set); small rings and mid-occupancy launches keep the full-grid
step and gather only the packed result.  The host unpack scatters
everything back to full width — pure re-indexing, results
bit-identical to the full-width pack (``RETPU_COMPACT=0`` opts
out); in pack-gather mode the corrupt mask stays full width so
inactive columns' integrity flags still reach the scrub path.  See
docs/ARCHITECTURE.md §7 "Active-column compaction and the (K, A)
bucket grid".

Read-modify-writes have a DEVICE FAST PATH: a ``kmodify`` whose
mod-fun resolves against the funref device table (rmw:add & co) runs
as one fused ``OP_RMW`` engine round — read, fun and commit under the
round's seq discipline, conflict-free by construction — instead of
the host's read→fn→CAS retry cycle; such keys hold device-native
int32 values (``_inline_slots``).  Arbitrary mod-funs keep the host
path, with the CAS half chained into the flush that resolved its read
and jittered backoff between conflicted retries.  See
docs/ARCHITECTURE.md §3 "Device-side RMW and the mod-fun table".

Reads have a LEASE-PROTECTED FAST PATH (``RETPU_FAST_READS=0`` or
``Config.trust_lease=False`` opt out): a ``kget``/``kget_vsn``/
``kget_many`` of a keyed slot is answered directly from the leader's
host-resident committed mirror — no ``OP_GET`` row, no flush —
whenever the ensemble's lease is valid on the monotonic clock with a
safety margin (``Config.read_margin``, with lease + margin strictly
inside the follower timeout), the slot has no queued or in-flight
write (the per-slot ``_pending_writes`` index; otherwise the read
falls back to the device round), the row has a live leader and is not
corruption-flagged (flagged rows always take the device round so the
synctree integrity gate still vets the read).  The resolve half
updates every mirror BEFORE completing write futures, so a read
issued after a write's ack always observes it — across pipeline
depth, RMW inline slots and tenant install.  See
docs/ARCHITECTURE.md §9 "Lease-protected reads".
"""

from __future__ import annotations

import errno
import operator
import os
import random
import sys
import time
import weakref
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from riak_ensemble_tpu import faults, obs
from riak_ensemble_tpu.config import Config
from riak_ensemble_tpu.ops import engine as eng
from riak_ensemble_tpu.parallel import enqueue_native, resolve_native
from riak_ensemble_tpu.parallel.mesh import shard_active_columns
from riak_ensemble_tpu.runtime import Future, Runtime, Timer
from riak_ensemble_tpu.types import NOTFOUND

#: latency-record fields excluded from every ``total`` sum so the
#: breakdown stays additive: the metadata fields plus the canonical
#: derived-mark list (obs.flightrec.DERIVED_MARKS — 'enqueue' and the
#: resolve_native/resolve_fallback arm attribution, which subdivide
#: the resolve half by which arm ran without double-counting it).
#: Single-sourced from flightrec so the flight recorder's
#: dominant-mark argmax and these sums can never drift apart.
#: ``starts`` and ``clock`` are the span primitive's stamps
#: (obs.spans), not seconds; ``a``, ``cols``, ``cols_max`` and
#: ``shards`` are the launch's shape and ``reqs`` the rows of the
#: requests the flush answered.
DERIVED_MARKS = frozenset(obs.flightrec.SHAPE_FIELDS
                          + ("total", "starts", "clock", "reqs")
                          + obs.flightrec.DERIVED_MARKS)

#: per-entry field extractor for the per-op SLO fold (C-level
#: attrgetter: one call per taken entry beats a Python loop body)
_OP_SLO_FIELDS = operator.attrgetter("kind", "n", "t_rx", "t_sub",
                                     "t_enq")



def _backend_mem_bytes() -> float:
    """Live device-memory gauge read (export time only): bytes in
    use on the default jax device; NaN when the backend keeps no
    allocator stats (CPU) — the registry maps NaN to None/``NaN``
    rather than forging a 0."""
    import jax
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return float("nan")
    return float(stats.get("bytes_in_use", float("nan")))


def _backend_mem_bytes_per_device(field: str = "bytes_in_use"
                                  ) -> Dict[str, float]:
    """Per-device form of :func:`_backend_mem_bytes` for mesh-sharded
    engines: bytes in use on EVERY local device, keyed by device id.
    An 'ens'-shard imbalance (one shard's slabs growing past its
    siblings) is invisible in the default-device gauge.  ``field``
    names another of the allocator's numbers (``peak_bytes_in_use``:
    the most a device has held since the process began)."""
    import jax
    out: Dict[str, float] = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        out[str(d.id)] = (float(stats.get(field, float("nan")))
                          if stats else float("nan"))
    return out


def _state_bytes_per_device(state) -> List[int]:
    """Bytes of the engine state each device holds, by device id:
    summed over the addressable shards of every plane."""
    held: Dict[int, int] = {}
    for plane in jax.tree.leaves(state):
        for shard in plane.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    return [held[d] for d in sorted(held)]


def mesh_ens_shards(engine) -> int:
    """Number of 'ens'-axis shards the engine's SHARD-WISE result pack
    runs over (``ShardedEngine.pack_shards``: >1 only for a mesh whose
    'peer' axis is unsharded): the packed payload is then per-shard
    blocks and the launch buckets its columns per shard.  0 = not
    shard-wise (single-device engines included)."""
    return int(getattr(engine, "pack_shards", 0))


#: smallest active-column bucket the pack compiles: below 8 columns
#: the payload is mostly headers anyway, and every extra (K, A)
#: bucket is one more XLA program — the floor keeps the warm grid
#: (and test suites full of tiny services) from compiling compaction
#: variants that can't pay for themselves.
A_BUCKET_MIN = 8

#: smallest grid width the SLICED launch engages at: the slice adds
#: fixed per-launch cost (host column slicing, the index upload, the
#: gather/scatter dispatches) that only amortizes when the full-grid
#: step it replaces is itself substantial — measured at the skewed
#: CPU rung: ~3.9x ops/sec at E=512, but a net LOSS at E=64 where
#: the full step is already sub-millisecond.  Below this, compaction
#: still runs in pack-gather mode (the d2h payload cut is ~free).
SLICE_MIN_E = 256


def packed_nbytes(e: int, m: int, k: int, want_vsn: bool,
                  a_width: Optional[int] = None,
                  sliced: bool = False) -> int:
    """Size in bytes of one ``engine.pack_results`` payload — the
    per-flush d2h transfer.  ``a_width`` is the compacted column
    count (None = full width E), ``sliced`` a launch whose step ran
    on the gathered grid (its won/corrupt planes ``a_width`` wide,
    its quorum plane E wide all the same); used for the
    ``payload_bytes`` accounting and the tests'
    full-width-vs-compacted comparison."""
    aw = e if a_width is None else a_width
    hw = aw if sliced else e
    nbits = hw + e + hw * m + 3 * k * aw
    return (nbits + 7) // 8 + 4 * k * aw * (3 if want_vsn else 1)


def unpack_results(flat: np.ndarray, e: int, m: int, k: int,
                   want_vsn: bool, active: Optional[np.ndarray] = None,
                   a_width: int = 0, sliced: bool = False):
    """Invert ``engine.pack_results``: one packed uint8 vector →
    ``(won, quorum_ok, corrupt, committed, get_ok, found, value,
    vsn)`` host arrays (the k == 0 planes are None).  Module-level so
    the replica side of the replication group
    (:mod:`riak_ensemble_tpu.parallel.repgroup`) unpacks the SAME
    layout its leader packs.

    With ``active`` (the launch's active column index list, packed at
    ``a_width`` pow2-padded columns), the per-round planes arrive
    compacted ``[K, A]`` and are scattered back through the index
    list into full-width ``[K, E]`` arrays — inactive columns get the
    all-false/zero NOOP results a full-width pack would have carried
    for them, so every downstream consumer (resolve loops, WAL,
    replica CRC) is layout-blind.  ``sliced`` marks a
    launch whose step itself ran on the gathered grid: then the
    won/corrupt planes are A-width too and scatter the same way
    (inactive columns won nothing and flagged nothing — exactly what
    the full grid reports for columns no round touched).  The
    ``quorum_ok`` plane is E wide in EVERY layout and is read where
    it lies: a sliced launch reports the epoch check of the columns
    it did not step too (``engine._sliced_quorum``), so the caller's
    lease renewal sees every ensemble, as on a full-width launch."""
    aw = e if active is None else a_width
    hw = aw if sliced else e  # election/corrupt plane width
    nbits = hw + e + hw * m + 3 * k * aw
    bits = np.unpackbits(flat[:(nbits + 7) // 8],
                         count=nbits).astype(bool)
    ints = flat[(nbits + 7) // 8:].copy().view(np.int32)
    boff = ioff = 0

    def take_bits(n, shape=None):
        nonlocal boff
        out = bits[boff:boff + n]
        boff += n
        return out.reshape(shape) if shape is not None else out

    def take_ints(n, shape=None):
        nonlocal ioff
        out = ints[ioff:ioff + n]
        ioff += n
        return out.reshape(shape) if shape is not None else out

    won = take_bits(hw)
    quorum_ok = take_bits(e)
    corrupt = take_bits(hw * m, (hw, m))
    if sliced and active is not None:
        a = len(active)

        def scat_cols(c, shape):
            out = np.zeros(shape, bool)
            out[active] = c[:a]
            return out
        won = scat_cols(won, (e,))
        corrupt = scat_cols(corrupt, (e, m))
    if k:
        committed = take_bits(k * aw, (k, aw))
        get_ok = take_bits(k * aw, (k, aw))
        found = take_bits(k * aw, (k, aw))
        value = take_ints(k * aw, (k, aw))
        vsn = None
        if want_vsn:
            vsn = np.stack([take_ints(k * aw, (k, aw)),
                            take_ints(k * aw, (k, aw))], axis=-1)
        if active is not None:
            a = len(active)

            def scatter(c, dtype):
                out = np.zeros((k, e) + c.shape[2:], dtype)
                out[:, active] = c[:, :a]
                return out
            committed = scatter(committed, bool)
            get_ok = scatter(get_ok, bool)
            found = scatter(found, bool)
            value = scatter(value, np.int32)
            if vsn is not None:
                vsn = scatter(vsn, np.int32)
    else:
        committed = get_ok = found = value = vsn = None
    return won, quorum_ok, corrupt, committed, get_ok, found, value, vsn


def unpack_results_sharded(flat: np.ndarray, e: int, m: int, k: int,
                           want_vsn: bool, n_shards: int,
                           shard_active: Optional[List[np.ndarray]]
                           = None, a_width: int = 0,
                           sliced: bool = False):
    """Invert a shard-wise mesh engine's pack (``mesh.ShardedEngine``):
    the payload is ``n_shards`` ``engine.pack_results`` blocks in shard
    order, each covering a contiguous ``e_loc = E/n_shards`` column
    slice, compacted per shard through its LOCAL active index list
    (``shard_active[s]``, ≤ ``a_width`` entries; None = every shard
    at full width).  Each block unpacks through the single-shard
    oracle and the full-width planes concatenate back along E — so
    every downstream consumer (mirror scatter, WAL, replica CRC)
    stays layout-blind, exactly as with the gathered
    pack.  ``sliced``: every shard's STEP ran on its gathered
    ``[K, a_width]`` grid, so each block is a sliced launch's
    (its won/corrupt planes ``a_width`` wide too, its quorum plane
    the shard's whole ``e_loc``)."""
    e_loc = e // n_shards
    nb = packed_nbytes(e_loc, m, k, want_vsn,
                       a_width if shard_active is not None else None,
                       sliced)
    parts = []
    for s in range(n_shards):
        act = None if shard_active is None else shard_active[s]
        parts.append(unpack_results(
            flat[s * nb:(s + 1) * nb], e_loc, m, k, want_vsn,
            active=act,
            a_width=0 if shard_active is None else a_width,
            sliced=sliced))

    def cat(i, axis):
        if parts[0][i] is None:
            return None
        return np.concatenate([p[i] for p in parts], axis=axis)

    return (cat(0, 0), cat(1, 0), cat(2, 0), cat(3, 1), cat(4, 1),
            cat(5, 1), cat(6, 1), cat(7, 1))


def _lane_indices(ent_col: np.ndarray, ent_row0: np.ndarray,
                  ent_len: np.ndarray):
    """Flat (rows, cols) plane indices expanded from the pending
    slab's run descriptors — the numpy fallback's form of the walk
    the C pack/gather passes do natively (one np.repeat-based
    expansion, no Python loop)."""
    ent_of = np.repeat(np.arange(len(ent_len)), ent_len)
    ends = np.cumsum(ent_len)
    starts = ends - ent_len
    within = np.arange(int(ends[-1]) if len(ends) else 0) \
        - starts[ent_of]
    return ent_row0[ent_of] + within, ent_col[ent_of]


def _u8view(x: np.ndarray) -> np.ndarray:
    """Zero-copy uint8 view of a contiguous bool plane (the native
    gather's input form); copies only on a layout surprise."""
    if x.dtype == np.bool_ and x.flags.c_contiguous:
        return x.view(np.uint8)
    return np.ascontiguousarray(x, np.uint8)


def warmup_kernels(svc: "BatchedEnsembleService") -> None:
    """Back-compat wrapper for
    :meth:`BatchedEnsembleService.warmup` (the (K, A)-grid
    pre-compile every entry point shares)."""
    svc.warmup()


class _LocalEngine:
    """Default engine adapter: the module kernels, single-process jit
    (data-parallel over whatever devices XLA picks).  A
    :class:`~riak_ensemble_tpu.parallel.mesh.ShardedEngine` instance
    slots in here to run the same service over a ('ens', 'peer') mesh.
    """

    init_state = staticmethod(eng.init_state)
    # the launch's programs: (state, op slab, up)
    full_step_slab = staticmethod(eng.full_step_slab)
    full_step_slab_donate = staticmethod(eng.full_step_slab_donate)
    full_step_sliced_slab = staticmethod(eng.full_step_sliced_slab)
    full_step_sliced_slab_donate = staticmethod(
        eng.full_step_sliced_slab_donate)
    rebuild_trees = staticmethod(eng.rebuild_trees)
    exchange_step = staticmethod(eng.exchange_step)
    reconfig_step = staticmethod(eng.reconfig_step)
    reset_rows = staticmethod(eng.reset_rows)
    verify_trees = staticmethod(eng.verify_trees)


class WallRuntime:
    """Minimal real-time runtime for driving the service outside the
    simulator (bench / production): ``now`` is the monotonic clock.
    It has no event loop, so it only supports caller-driven services —
    construct the service with ``tick=None`` and call ``flush()``."""

    @property
    def now(self) -> float:
        return time.monotonic()

    def schedule(self, delay: float, fn) -> Timer:
        raise RuntimeError(
            "WallRuntime has no event loop; use tick=None and drive "
            "flush() from the caller")


class _FreeSlots:
    """One ensemble's allocatable slots, sized by what has been handed
    out and not by the keyspace: slots ``[0, fresh)`` were never
    allocated (the mark walks down from ``n_slots``) and ``recycled``
    holds the ones handed back, which go out again first, last in
    first out.  That is the order a list of every slot, popped from
    its end, gave; ``len``, truth, ``pop`` and ``append`` are that
    list's."""

    __slots__ = ("fresh", "recycled")

    def __init__(self, fresh: int, recycled: Optional[List[int]] = None
                 ) -> None:
        self.fresh = fresh
        self.recycled: List[int] = [] if recycled is None else recycled

    @classmethod
    def unused(cls, n_slots: int, used) -> "_FreeSlots":
        """Every slot of ``range(n_slots)`` that ``used`` (a set) does
        not hold: the mark under its lowest slot, the gaps above it
        ascending (so the highest goes out first)."""
        fresh = min(used, default=n_slots)
        return cls(fresh, [s for s in range(fresh + 1, n_slots)
                           if s not in used])

    @classmethod
    def from_list(cls, slots: List[int]) -> "_FreeSlots":
        """A checkpoint from before PR 43 lists every free slot, in
        pop order from its end: the leading ``0, 1, 2, ...`` run is
        the mark, the rest goes out first as it would have."""
        fresh = 0
        for s in slots:
            if s != fresh:
                break
            fresh += 1
        return cls(fresh, list(slots[fresh:]))

    def __len__(self) -> int:
        return self.fresh + len(self.recycled)

    def pop(self) -> int:
        if self.recycled:
            return self.recycled.pop()
        if not self.fresh:
            raise IndexError("pop from an ensemble with no free slot")
        self.fresh -= 1
        return self.fresh

    def append(self, slot: int) -> None:
        self.recycled.append(slot)


def _note(counts: Dict[int, int], slot: int) -> None:
    """One more queued write on ``slot`` (a per-ensemble ``{slot:
    count}`` that holds only the slots with something queued)."""
    counts[slot] = counts.get(slot, 0) + 1


def _unnote(counts: Dict[int, int], slot: int) -> None:
    """One fewer; a slot at zero leaves the dict.  An unpaired
    un-note is a bug, but it must park reads on the safe device
    round, never underflow into "every later write is invisible":
    a slot that is not there stays not there."""
    c = counts.get(slot, 0)
    if c > 1:
        counts[slot] = c - 1
    elif c:
        del counts[slot]


@dataclass(slots=True)
class _PendingOp:
    kind: int
    slot: int
    handle: int
    fut: Future
    key: Any = None
    #: slot write generation at enqueue (puts only) — lets the failed
    #: path tell whether it was the slot's last queued write
    gen: int = 0
    #: CAS expected version (OP_CAS); for OP_RMW, (fun code, 0) — the
    #: exp_epoch plane carries the mod-fun table code and ``handle``
    #: the int32 operand
    exp: Tuple[int, int] = (0, 0)
    #: resolve gets as ("ok", value, vsn) instead of ("ok", value)
    want_vsn: bool = False
    #: enqueue timestamp (perf_counter) — queue-wait latency component
    t_enq: float = 0.0
    #: rounds this entry occupies in the [K, E] op matrix
    n: int = 1
    #: per-op SLO ring (obs.opslo): API-entry submit timestamp
    #: (0 = use t_enq; the settle-time record_flush reads both)
    t_sub: float = 0.0
    #: where the front end had the op's frame whole (``svc.t_rx`` at
    #: the push; 0 = an in-process caller, which has no such stamp)
    t_rx: float = 0.0


@dataclass(slots=True)
class _PendingBatch:
    """A struct-of-arrays batch of keyed ops for ONE ensemble sharing
    one Future — the vectorized keyed path (kput_many/kget_many).

    Arrays are COMPACT: keys with no slot never queue a device round —
    their results are pre-filled into the accumulator at submit time —
    and ``pos`` maps each compact row back to its position in the
    caller's key order.  Packing into the flush's [K, E] planes is an
    array slice (no per-op Python), and resolution is positional
    assembly into the shared accumulator.  ``kind`` is uniform per
    batch (all-put, all-get, all-CAS or all-RMW; an RMW batch's
    ``handle`` column carries int32 operands and ``exp_e`` the fun
    code).
    """

    kind: int
    slot: Any          # List[int] [n] compact (plain lists end to
    #                    end: numpy slice assignment packs them into
    #                    the flush planes, and list zips resolve them
    #                    — no per-entry asarray/tolist round trips)
    handle: Any        # List[int] [n] (puts; zeros for gets)
    fut: Future
    pos: Any = None    # List[int] [n] position in the caller's order
    keys: Any = None   # list of key objects (puts: for WAL/recycle)
    gen: Any = None    # List[int] [n] slot generations (puts)
    #: CAS expected versions (OP_CAS batches; None otherwise)
    exp_e: Any = None  # List[int] [n]
    exp_s: Any = None  # List[int] [n]
    accum: Any = None  # shared _BatchAccum across splits
    want_vsn: bool = False
    t_enq: float = 0.0
    n: int = 0
    #: per-op SLO ring (obs.opslo): API-entry submit timestamp
    #: (0 = use t_enq; the settle-time record_flush reads both)
    t_sub: float = 0.0
    #: where the front end had the op's frame whole (``svc.t_rx`` at
    #: the push; 0 = an in-process caller, which has no such stamp)
    t_rx: float = 0.0

    def split(self, head_n: int) -> Tuple["_PendingBatch", "_PendingBatch"]:
        """Split into (head, tail) when a flush's K cap lands inside
        the batch; both halves share the Future and accumulator — it
        resolves once the whole batch's results accumulated.  Both
        halves keep the submit/enqueue stamps: each settles as its
        own per-op SLO entry under its OWN flush's id, weights
        conserved."""
        def cut(x, a, b):
            return None if x is None else x[a:b]
        h = _PendingBatch(self.kind, self.slot[:head_n],
                          self.handle[:head_n], self.fut,
                          self.pos[:head_n], cut(self.keys, 0, head_n),
                          cut(self.gen, 0, head_n),
                          cut(self.exp_e, 0, head_n),
                          cut(self.exp_s, 0, head_n), self.accum,
                          self.want_vsn, self.t_enq, head_n,
                          self.t_sub, self.t_rx)
        t = _PendingBatch(self.kind, self.slot[head_n:],
                          self.handle[head_n:], self.fut,
                          self.pos[head_n:], cut(self.keys, head_n, None),
                          cut(self.gen, head_n, None),
                          cut(self.exp_e, head_n, None),
                          cut(self.exp_s, head_n, None), self.accum,
                          self.want_vsn, self.t_enq, self.n - head_n,
                          self.t_sub, self.t_rx)
        return h, t


class _BatchAccum:
    """Positional result assembly for a (possibly split) batch: each
    chunk fills its rows by original position; the shared Future
    resolves once every position is filled."""

    __slots__ = ("remaining", "results")

    def __init__(self, total: int) -> None:
        self.remaining = total
        self.results: List[Any] = [None] * total

    def fill(self, fut: Future, positions: List[int],
             chunk: List[Any], resolver) -> None:
        res = self.results
        for i, r in zip(positions, chunk):
            res[i] = r
        self.remaining -= len(chunk)
        if self.remaining <= 0 and not fut.done:
            resolver(fut, res)


class _StepFns(NamedTuple):
    """The two programs a launch can call, both ``(state, op slab, up)
    -> (state, packed results)`` (``engine.pack_op_slab`` has the
    slab's layout, ``engine.pack_results`` the vector's), donated when
    the service donates: ``slab`` at full width (static ``want_vsn``
    and ``gather``, the pack-gather's width), ``sliced_slab`` on the
    gathered active columns (static ``want_vsn``; None = the launch
    cannot slice on this engine).  ONE of them is all a launch asks of
    the device: the step and the pack of its results."""

    slab: Any
    sliced_slab: Any


@dataclass(slots=True)
class _InFlightLaunch:
    """One dispatched-but-unresolved device launch in the service's
    bounded launch pipeline: the enqueue half's outputs (the packed
    result array whose d2h transfer is already running, the latency
    marks so far, the rollback snapshots) plus whatever the resolve
    half needs to finish the round (election vector for the leader
    mirror, the flush's taken queue entries or an ``execute_async``
    future)."""

    flat: Any               # device uint8 packed result (in flight)
    rec: Dict[str, float]   # latency marks (enqueue half)
    k: int                  # round count
    want_vsn: bool
    kind_np: Any            # host kind plane (the native mirror
    #                         scatter reads it)
    elect: Any              # [E] bool — this launch's election vector
    cand: Any               # [E] int32 — its candidates
    now: float              # runtime.now at enqueue (lease renewal)
    state_snapshot: Any     # pre-launch EngineState (rollback)
    leader_snapshot: Any
    lease_snapshot: Any
    #: active-column compaction: the launch's active ensemble index
    #: list (None = full-width pack) and the pow2-bucketed packed
    #: column count — the resolve half scatters the compact [K, A]
    #: planes back through these.  ``sliced`` marks a launch whose
    #: STEP ran on the gathered [K, A] grid (then the won/corrupt
    #: planes are A-width too, not just the client planes; the
    #: quorum plane is E wide on every launch).
    active: Any = None
    a_width: int = 0
    sliced: bool = False
    #: shard-wise mesh pack (mesh_ens_shards > 0): the packed payload
    #: is n_shards per-shard blocks; ``shard_active`` holds each
    #: shard's LOCAL active index list when the flush compacted
    #: (None = per-shard full width).  Set on EVERY launch of a
    #: shard-wise service — election-only launches included.
    n_shards: int = 0
    shard_active: Any = None
    #: host slot plane in op order (the native mirror scatter's
    #: companion to ``kind_np``)
    op_slot_np: Any = None
    #: flush path: the (ensemble, taken ops) pairs this launch serves
    taken: Any = None
    #: slab enqueue path: the flush's pending-slab record
    #: (ent_col, ent_row0, ent_len run descriptors, taken round
    #: count, per-entry offsets, SLO stamp columns) — the
    #: completion-slab resolve gathers every result plane through
    #: the runs in one pass (ARCHITECTURE §12b)
    lanes: Any = None
    #: execute_async path: the client future + WAL planes + op count
    exec_fut: Any = None
    exec_wal: Any = None
    exec_ops: int = 0
    t_enq: float = 0.0
    #: replication-group extension (repgroup.ReplicatedService): the
    #: launch's group seq, its captured ship inputs (op planes +
    #: elect/lease vectors), its put-lane metadata, and the
    #: corruption-counter snapshot that gates delta eligibility.
    #: ``grp_ship`` is the discriminator: None = not a replicated
    #: leader launch.
    grp_seq: int = 0
    grp_ship: Any = None
    grp_meta: Any = None
    grp_corr0: int = 0
    #: the round's quorum confirmations [E], stashed by the resolve
    #: half for subclass hooks (delta frames ship them)
    quorum_np: Any = None
    #: observability plane: the launch's process-monotonic flush id
    #: (stamped at enqueue, joins leader and replica spans — rides
    #: the replication wire as each entry's trailing field) and the
    #: packed d2h byte count the resolve half measured
    flush_id: int = 0
    payload_nbytes: int = 0
    #: per-op SLO plane: when this launch's enqueue half started —
    #: the flush-JOIN stamp shared by every taken entry (queue_wait
    #: ends here; the 'flush' stage runs from here to settle, so a
    #: serve-time compile inside the dispatch lands in 'flush')
    t_join: float = 0.0


class BatchedEnsembleService:
    """N engine-backed ensembles behind a put/get API.

    ``n_slots`` bounds live keys per ensemble (slots are recycled when
    keys are deleted).  Work that has boarded starts a flush: an
    enqueue arms a look at the queue for the runtime's next turn, and
    the first turns that bring nothing new flush what arrived
    (:meth:`_note_arrival`).  ``tick`` is the CEILING on that wait (a
    stream that never goes quiet flushes this long after the last
    flush ended) and the idle heartbeat (due retries, elections and
    the pipeline's tail with nothing queued); ``tick=None`` arms
    neither — the caller drives ``flush()`` (bench / WallRuntime mode).
    """

    #: consecutive runtime turns without an enqueue that mean the
    #: front end has gone quiet.  Two, not one: on an asyncio loop a
    #: frame takes two turns from its socket to its enqueue (the
    #: transport's read wakes the connection's task, which parses on
    #: the turn after), so after ONE empty turn the rest of a burst
    #: may be received and not yet parsed.
    QUIET_TURNS = 2

    def __init__(self, runtime: Runtime, n_ens: int, n_peers: int,
                 n_slots: int = 128, tick: Optional[float] = 0.005,
                 max_ops_per_tick: int = 64,
                 config: Optional[Config] = None,
                 engine: Optional[Any] = None,
                 data_dir: Optional[str] = None,
                 wal_sync: str = "fsync",
                 wal_compact_records: int = 1 << 18,
                 dynamic: bool = False,
                 scrub_every_flushes: Optional[int] = None,
                 pipeline_depth: int = 1) -> None:
        import jax.numpy as jnp

        self.runtime = runtime
        self.config = config if config is not None else Config()
        self.n_ens, self.n_peers, self.n_slots = n_ens, n_peers, n_slots
        self.tick = tick
        self.max_k = max_ops_per_tick
        self.engine = engine if engine is not None else _LocalEngine()
        #: >0 = the shard-wise mesh pack path: packed payloads are
        #: per-ens-shard blocks and active-column compaction computes
        #: its |A| bucket PER SHARD (compaction-aware sharding)
        self._mesh_shards = mesh_ens_shards(self.engine)
        #: unified observability plane (riak_ensemble_tpu.obs): a
        #: per-service metrics registry + flight recorder, plus
        #: per-tenant accounting vectorized over ensemble rows.
        #: ``RETPU_OBS=0`` short-circuits every hot-path record; the
        #: answer is cached here so the gate is one attribute test.
        self._obs = obs.enabled()
        #: the span primitive (obs.spans): every mark of a flush's
        #: record is stamped through it, and with RETPU_OBS on each
        #: is a profiler annotation ``svc.<mark>`` as well
        self.spans = obs.spans.SpanRecorder(annotate=self._obs)
        # the device's planes and their first tree, built where they
        # will live (a mesh engine's: every device its own block);
        # waited for on every device, so that the span is the build's
        # seconds (the first launch would have waited for them)
        with self.spans.span("state_init", {}) as state_init:
            self.state = jax.block_until_ready(
                self.engine.init_state(n_ens, n_peers, n_slots))
        t_state = time.perf_counter()
        #: ``stats()["tree"]``: where the shape puts the Merkle upper
        #: levels (``engine.tree_layout``) and the rows of the row
        #: plane a K/V round gathers and scatters; static, the round's
        #: form follows the shape alone
        lay = eng.tree_layout(n_slots)
        self._tree_stats = {
            "row_levels": lay.row_levels, "rows": lay.rows,
            "tail_nodes": lay.tail_nodes,
            "rows_per_round": n_ens * n_peers * lay.row_levels}
        #: host failure detector input (set_peer_up)
        self.up = np.ones((n_ens, n_peers), dtype=bool)
        self._up_dev = None  # cached device copy (see _up_device)
        #: host mirrors of device ballot state (leader changes only via
        #: elections THIS host requested, membership only via reconfigs
        #: it issued) — election planning costs zero device round trips
        self.leader_np = np.full((n_ens,), -1, dtype=np.int32)
        self.member_np = np.ones((n_ens, n_peers), dtype=bool)
        #: membership-change pipeline, host side: a requested change is
        #: QUEUED while an earlier change is still joint on device,
        #: DESIRED until its joint view installs, PENDING until the
        #: joint view collapses, then live in member_np.  Every
        #: update_members call advances whatever is in flight.
        self._queued_view_np = np.ones((n_ens, n_peers), dtype=bool)
        self._queued_mask = np.zeros((n_ens,), dtype=bool)
        self._desired_view_np = np.ones((n_ens, n_peers), dtype=bool)
        self._desired_mask = np.zeros((n_ens,), dtype=bool)
        self._pending_view_np = np.ones((n_ens, n_peers), dtype=bool)
        self._pending_mask = np.zeros((n_ens,), dtype=bool)
        #: per-ensemble key→slot and free slots
        self.key_slot: List[Dict[Any, int]] = [dict() for _ in range(n_ens)]
        self.free_slots: List[_FreeSlots] = [
            _FreeSlots(n_slots) for _ in range(n_ens)]
        #: per-ensemble slot write generation: bumped on every queued
        #: put, so a delete's deferred recycle can tell whether a later
        #: write re-used the slot (then recycling would orphan it)
        self.slot_gen: List[Dict[int, int]] = [dict() for _ in range(n_ens)]
        #: per-ensemble slot -> handle of the last COMMITTED payload;
        #: lets a committed overwrite/delete release the superseded
        #: handle from ``values`` (otherwise the store grows forever)
        self.slot_handle: List[Dict[int, int]] = [
            dict() for _ in range(n_ens)]
        #: deferred slot recycles: (key, slot, gen) waiting until no
        #: queued op still references the slot (an overflowed get must
        #: never read a recycled slot another key re-used)
        self._recycle_pending: List[List[Tuple[Any, int, int]]] = [
            [] for _ in range(n_ens)]
        #: per-ensemble slots holding DEVICE-NATIVE int32 values (the
        #: kmodify device fast path — OP_RMW commits) rather than
        #: payload-store handles: reads of these slots return the raw
        #: int32, and a committed RMW records the sentinel handle -1
        #: in ``slot_handle`` (blocks recycling like a live handle;
        #: released as a no-op).  A committed put/CAS flips the slot
        #: back to handle storage.
        self._inline_slots: List[set] = [set() for _ in range(n_ens)]
        #: slots with QUEUED (not yet resolved) host-payload writes:
        #: per ensemble a ``{slot: count}`` that holds only the slots
        #: with something queued (``_note`` / ``_unnote``; a slot at
        #: zero leaves it, so membership is the gate).  The RMW
        #: fast-path eligibility must see these — slot_handle only
        #: reflects COMMITTED writes, and a device RMW racing a
        #: same-flush kput would do int32 arithmetic on the put's
        #: payload HANDLE (silent corruption).  Sized by the queue,
        #: not by the keyspace: a count row per slot was 8 B of
        #: pointer for every slot of every ensemble, all of them
        #: walked by each full collector pass (PR 43; the accesses are
        #: per-slot scalar bumps, 70 ns more an op on a dict than on
        #: a list row and 380 ns less than on a numpy cell,
        #: docs/ARCHITECTURE.md §12).  Advisory queue state (reset
        #: with the queues, never persisted); drift only parks a slot
        #: on the safe host path.
        self._queued_handle_writes: List[Dict[int, int]] = [
            dict() for _ in range(n_ens)]
        #: payload store: handle -> value (device carries handles).
        #: Handles are int32 on device and 0 is the tombstone sentinel,
        #: so released handles are recycled — a monotonically growing
        #: counter would wrap into live (or tombstone) handles after
        #: 2^31 puts.
        self.values: Dict[int, Any] = {}
        self._free_handles: List[int] = []
        self._next_handle = 1
        self.queues: List[List[Any]] = [[] for _ in range(n_ens)]
        #: queued device ROUNDS per ensemble (a batch entry occupies
        #: entry.n rounds) — drives flush depth and the burst trigger
        self._queue_rounds: List[int] = [0] * n_ens
        #: ensembles with queued ops / pending recycles: flush and the
        #: recycle drain iterate THESE, not range(n_ens) — at 10k
        #: ensembles with sparse traffic the O(E) Python sweep per
        #: flush would dwarf the work itself
        self._active: set = set()
        self._recycle_dirty: set = set()
        #: leader leases, host-side: ensemble -> expiry (runtime.now)
        self.lease_until = np.zeros((n_ens,), dtype=float)
        #: lease-protected read fast path (RETPU_FAST_READS=0 or
        #: config.trust_lease=False opt out): reads of keyed slots
        #: serve from the host committed mirror while the lease holds
        self._fast_reads = (os.environ.get("RETPU_FAST_READS", "1")
                            != "0") and self.config.trust_lease
        self._read_margin = self.config.read_margin()
        # the lease-read safety inequality (see Config.validate):
        # every read the leader may still serve expires strictly
        # before any follower's election patience runs out.  Only
        # enforced while the fast path is ON — a config that opted
        # out (trust_lease=False / RETPU_FAST_READS=0) never serves
        # around the round and must keep constructing as before.
        if self._fast_reads:
            self._assert_read_margin()
        #: committed (epoch, seq) per slot — the version a fast
        #: kget_vsn serves.  Invalidated per-row on won elections
        #: (the epoch bump re-versions objects lazily on next device
        #: access); repopulated by every committed write's resolve and
        #: refreshed by device reads.  SLAB layout ([E, S, 2] int32 +
        #: an [E, S] validity plane) rather than per-row dicts: the
        #: native resolve kernel scatters a whole flush's committed
        #: versions into it in one C pass, and the Python fallback
        #: writes the same cells per-op (byte-identical slabs either
        #: way — the tests' equivalence contract).
        self._slot_vsn_np = np.zeros((n_ens, n_slots, 2), np.int32)
        self._slot_vsn_ok = np.zeros((n_ens, n_slots), bool)
        #: committed device-native int32 per inline (RMW) slot — the
        #: value a fast read of a device-native key serves (the engine
        #: arrays hold it; slot_handle only carries the -1 sentinel).
        #: Invalid entries (fresh restore) miss to the device round,
        #: which refreshes the mirror.  Same slab layout as the vsn
        #: mirror, same native/fallback write discipline.
        self._inline_value_np = np.zeros((n_ens, n_slots), np.int32)
        self._inline_value_ok = np.zeros((n_ens, n_slots), bool)
        #: [E, S] storage-class slab kept in lockstep with
        #: ``_inline_slots`` (every add/discard writes both): the
        #: native kernel reads it to route leased-GET refreshes to the
        #: right mirror without touching Python sets mid-pass.
        self._inline_np = np.zeros((n_ens, n_slots), bool)
        #: per-slot count of QUEUED + IN-FLIGHT writes (put/CAS/RMW/
        #: tombstone): a fast read of a slot with any pending write
        #: falls back to the device round — the round orders it after
        #: the writes, and the mirror-before-ack discipline alone only
        #: covers writes whose resolve already ran.  Per ensemble a
        #: ``{slot: count}`` like ``_queued_handle_writes``: the
        #: enqueue half notes a whole batch's slots at ``_push`` time
        #: and the completion-slab resolve un-notes every write lane
        #: at settle — the PR 4 fast-read gate sees slab-enqueued
        #: writes the moment they queue.
        self._pending_writes: List[Dict[int, int]] = [
            dict() for _ in range(n_ens)]
        #: rows whose last resolve flagged synctree corruption: fast
        #: reads bypass to the device round (its integrity gate vets
        #: the read) until the exchange/scrub reports the row synced
        self._corrupt_rows = np.zeros((n_ens,), dtype=bool)
        #: per-row won-election count — the ensemble-health verb's
        #: election-churn signal (a row re-electing every few flushes
        #: is losing its leader; host-mirror-sourced, zero device
        #: rounds).  Reset with the row on lifecycle recycle.
        self.elections_np = np.zeros((n_ens,), dtype=np.int64)
        #: read fast-path observability
        self.read_fastpath_hits = 0
        self.read_fastpath_misses = 0
        #: network front-end backpressure events, incremented by
        #: whatever server fronts this service (svcnode.ServiceServer,
        #: the proxy tier): a client stalled at the per-connection
        #: inflight cap, or dropped because its reply buffer passed
        #: the write cap — the evidence row behind
        #: retpu_svc_backpressure_total
        self.svc_backpressure: Dict[str, int] = {
            "inflight_stalls": 0, "write_buf_drops": 0}
        self.read_fastpath_miss_reasons: Dict[str, int] = {}
        self.flushes = 0
        self.ops_served = 0
        #: integrity-gate detections (replica flagged corrupt in a round)
        self.corruptions = 0
        #: replicas the post-detection exchange actually healed (in-round
        #: read repair usually heals the accessed slot first, so this
        #: counts only residual divergence the sweep fixed)
        self.repairs = 0
        #: dynamic ensemble lifecycle (create_ensemble,
        #: manager.erl:157-166, over fixed device arrays): a logical
        #: ensemble maps to a physical row; ``dynamic=True`` starts
        #: with every row FREE (no members, no elections) and
        #: create/destroy manage the rows.  ``dynamic=False`` keeps
        #: the historical all-rows-live service.
        self.dynamic = dynamic
        self._live = np.full((n_ens,), not dynamic, dtype=bool)
        self._free_rows: List[int] = (
            list(range(n_ens - 1, -1, -1)) if dynamic else [])
        self._ens_names: Dict[Any, int] = {}
        self._row_name: Dict[int, Any] = {}
        if dynamic:
            self.member_np[:] = False
            self.state = self.engine.reset_rows(
                self.state, jnp.ones((n_ens,), bool),
                jnp.zeros((n_ens, n_peers), bool))
        #: leader-status watchers per ensemble (watch_leader)
        self._leader_watchers: Dict[int, List[Any]] = {}
        #: periodic anti-entropy cadence: run :meth:`scrub` every N
        #: flushes (None = on demand only) — the AAE-timer analog.
        #: Watermark, not modulo: a pipelined drain can settle two
        #: launches in one flush (flushes += 2), which would jump a
        #: modulo test past its multiple and silently skip the sweep.
        self.scrub_every_flushes = scrub_every_flushes
        self._scrubbed_at_flush = 0
        self._timer: Optional[Timer] = None
        #: what starts a flush (see _note_arrival): whether a look at
        #: the queue is deferred, whether anything was enqueued since
        #: the last look, quiet turns seen in a row, whether a queue
        #: has reached a full launch's depth, whether the flush under
        #: way was started by arrivals (its launches' records say so),
        #: and whether a loop flush is under way at all
        self._look_armed = False
        self._arrived = False
        self._quiet_turns = 0
        self._queue_full = False
        self._by_arrival = False
        self._in_loop_flush = False
        #: settled launches by what started them: the arrival trigger;
        #: ``tick`` for every other launch that carried rounds (the
        #: timer, a full queue, a caller's own flush() or execute());
        #: ``idle`` for a launch of no rounds (an election alone).
        #: Adds up to ``flushes``.
        self.flush_triggers = {"arrival": 0, "tick": 0, "idle": 0}
        self._jnp = jnp
        #: active-column compaction (RETPU_COMPACT=0 opts out): a
        #: flush's packed d2h payload gathers down to the columns that
        #: actually hold ops — O(K·A) instead of O(K·E) — with |A|
        #: pow2-bucketed for compile reuse, mirroring the K ladder.
        #: Pure re-indexing (results bit-identical to the full-width
        #: pack); the corrupt mask stays full width so inactive
        #: columns' integrity flags still reach the scrub path.
        self._compact = os.environ.get("RETPU_COMPACT", "1") != "0"
        #: payload observability: actual packed d2h bytes fetched, the
        #: bytes the full-width [K, E] layout would have moved, and
        #: the mean packed-grid occupancy (a_width / E; 1.0 for
        #: full-width launches) — the compaction win, measurable
        self.payload_bytes = 0
        self.payload_bytes_full_width = 0
        self._occ_sum = 0.0
        self._occ_launches = 0
        #: launches whose STEP ran on the gathered [K, a_loc] grid,
        #: and those that stepped the full grid (each flush record
        #: carries its own ``sliced`` 0/1 beside ``uploads``)
        self.launches_sliced = 0
        self.launches_unsliced = 0
        #: what the launches handed the device and asked of it: host
        #: arrays uploaded and device programs called, summed over
        #: every launch (``stats()["launch"]``; one of each a launch,
        #: one upload more where the failure detector changed ``up``)
        self.launch_uploads = 0
        self.launch_calls = 0
        #: lease renewals launches made OUTSIDE their active set: the
        #: columns a launch carried no operation and no election for
        #: and renewed all the same, from the epoch check every launch
        #: reports at full width (a sliced launch's too:
        #: ``engine._sliced_quorum``); a launch over the whole grid
        #: has no such columns (``stats()["lease_renewals_idle"]``)
        self.lease_renewals_idle = 0
        #: the unsliced launches that pack-gathered (the rest stepped
        #: and packed the full grid), and over every launch that
        #: packed at a width (``a`` > 0) the sums of the busiest
        #: shard's share of its columns and of the share of its
        #: blocks that was padding (``stats()["mesh"]``)
        self.launches_gathered = 0
        self._busiest_sum = 0.0
        self._pad_sum = 0.0
        #: RMW observability: host-path kmodify CAS attempts that
        #: failed and were retried (write races, plus transient
        #: quorum failures — indistinguishable client-side), and ops
        #: the device mod-fun table served in one round
        self.rmw_conflicts = 0
        self.rmw_device_fastpath = 0
        #: commutative replication lane (docs/ARCHITECTURE.md §18):
        #: gates the leader's merge-section build, kmodify_many's
        #: enqueue-side coalescing and the replicas' early acks as
        #: one knob — =0 is the bit-identical ordered oracle arm
        self._comm_repl = os.environ.get(
            "RETPU_COMM_REPL", "1") != "0"
        #: §18 enqueue-side coalescing: duplicate-key commutative ops
        #: within one kmodify_many absorbed into an already-queued
        #: device row (ops saved, not rows pushed)
        self.rmw_enqueue_coalesced = 0
        #: svc_kmodify_error rate limit (a hot mod-fun bug at flush
        #: rate would otherwise emit a traceback per op per retry)
        self._kmodify_err_at = -1e9
        self._kmodify_err_dropped = 0
        #: backed-off kmodify retries: (due_flush_call, ensemble,
        #: client future, thunk), run at the top of the flush whose
        #: ordinal reaches them — backoff is measured in FLUSH CALLS
        #: (the service's round clock; a wall-clock sleep would stall
        #: caller-driven flush loops).  Tagged with ensemble + future
        #: so destroy_ensemble can fail them: a thunk surviving a
        #: row recycle would commit the dead tenant's kmodify value
        #: into the new tenant.
        self._retry_at: List[Tuple[int, int, Future, Any]] = []
        self._flush_calls = 0
        self._rng = random.Random(0x524D57)
        #: same-flush chaining: set when a resolve enqueues follow-up
        #: ops (a kmodify read's CAS half), consumed by flush() to run
        #: one bounded extra launch cycle inside the same flush call
        self._chain_kick = False
        self._chain_depth = 0
        #: per-flush latency breakdown records (bounded); see
        #: :meth:`latency_breakdown`.  Collection is always on — the
        #: clock reads are nanoseconds against millisecond launches.
        from collections import deque
        self.lat_records = deque(maxlen=1024)
        #: bounded launch pipeline (the two-phase async service
        #: execution): up to ``pipeline_depth`` launches may be
        #: dispatched-but-unresolved, so batch N's packed d2h transfer
        #: and host resolve overlap batch N+1's device step.  Depth 1
        #: keeps the historical fully-synchronous flush.
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._inflight_launches: "deque[_InFlightLaunch]" = deque()
        #: jit buffer donation for the fused step's state argument:
        #: back-to-back launches then reuse the E×M(×S) plane buffers
        #: instead of copying them each launch.  RETPU_DONATE=1/0
        #: forces it; default ON off-CPU (CPU keeps the copy so the
        #: launch-failure rollback snapshots stay valid — a donated
        #: launch that fails poisons the state, see _rollback_launch).
        _don = os.environ.get("RETPU_DONATE", "")
        self._donate = (_don == "1" if _don
                        else jax.default_backend() != "cpu")
        #: continuous durability (task: never ack a write that isn't on
        #: disk — basic_backend.erl:120-125): when ``data_dir`` is set,
        #: committed client writes append to a WAL generation paired
        #: with the checkpoint generation, forced down per ``wal_sync``
        #: BEFORE their futures resolve, and the WAL auto-compacts into
        #: a full checkpoint after ``wal_compact_records`` records.
        self.data_dir = data_dir
        self.wal_sync = wal_sync
        self.wal_compact_records = wal_compact_records
        #: WAL-compaction observability: save() is a full checkpoint
        #: and used to run SYNCHRONOUSLY inside flush() the moment the
        #: record bound tripped — a multi-hundred-ms pause billed to
        #: whatever client op was in flight (the mixed p99 spike).
        #: Compaction now waits for an idle flush (queues empty,
        #: pipeline drained) and only runs in-line past a hard 2x
        #: record bound; every run emits an ``svc_compaction`` latency
        #: mark + trace event so the pause is attributable.
        self.wal_compactions = 0
        self.wal_compaction_ms_last = 0.0
        self.wal_compaction_ms_total = 0.0
        self._wal = None
        self._in_save = False
        #: graceful storage degradation (docs/ARCHITECTURE.md §15):
        #: an EIO/ENOSPC surfacing from the WAL's durability barrier
        #: flips the service READ-ONLY (writes fail fast at enqueue,
        #: reads keep serving) instead of crashing the serving loop —
        #: the decision record lands here, in health()["storage"],
        #: in a trace event and in the retpu_recovery_* gauges.  A
        #: replicated leader additionally steps down through the
        #: group's existing depose machinery (repgroup override).
        self._storage_degraded: Optional[Dict[str, Any]] = None
        #: WAL OSErrors observed on the ack path (monotonic evidence)
        self.wal_storage_errors = 0
        if data_dir is not None:
            from riak_ensemble_tpu import save as savelib
            from riak_ensemble_tpu.parallel.wal import ServiceWAL

            os.makedirs(data_dir, exist_ok=True)
            meta = os.path.join(data_dir, "META")
            if savelib.read(meta) is None:
                import pickle
                from riak_ensemble_tpu.ops import hash as hashk
                savelib.write(meta, pickle.dumps(
                    {"shape": (n_ens, n_peers, n_slots),
                     "dynamic": dynamic,
                     # informational: META-only restores replay the
                     # WAL through the CURRENT fold, so no rebuild is
                     # needed — trees are born in the current format
                     "hash_format": hashk.HASH_FORMAT}, protocol=4))
            self._wal = ServiceWAL.open_gen(
                data_dir, self._current_ckpt(data_dir), wal_sync)
        #: the record flush() opened for the launch it is packing
        self._enq_rec: Optional[Dict[str, Any]] = None
        if self._wal is not None:
            self._wal.spans = self.spans
        #: the collector's pauses (``gc.callbacks``): installed with
        #: the flush timer, removed by stop()
        self._gc_watch = obs.spans.GcWatch(self.spans)
        #: what the front end (svcnode.ServiceServer) moved over the
        #: wire, counted where it encodes and decodes
        self.frontend = {"frames_in": 0, "bytes_in": 0,
                         "frames_out": 0, "bytes_out": 0}
        #: a request's life (obs.spans, "A request's life"): the stamp
        #: of the frame the front end is dispatching right now (0.0
        #: outside that call: an in-process caller has none), which
        #: :meth:`_push` copies onto the entry, and, once a server on
        #: a selector loop fronts this service, how long the loop had
        #: not looked at its sockets when each of the last
        #: frames was read (seconds; the server's array, a ring
        #: indexed by ``frontend["frames_in"]``)
        self.t_rx = 0.0
        self.rx_holds: Optional[np.ndarray] = None
        #: native single-pass resolve kernel (RETPU_NATIVE_RESOLVE=0
        #: or a missing toolchain pins the pure-Python fallback — the
        #: oracle arm; docs/ARCHITECTURE.md §12).  Resolved at
        #: construction like RETPU_OBS so an A/B test can hold one
        #: arm per live service.
        self._native_resolve = resolve_native.get()
        self.native_resolve_flushes = 0
        self.fallback_resolve_flushes = 0
        #: slab-resident ENQUEUE half (RETPU_NATIVE_ENQUEUE, default
        #: on; docs/ARCHITECTURE.md §12): pending ops pack into the
        #: [K, E] op planes from flat int32 lanes (one C++ traversal
        #: when the kernel loads, one numpy fancy-index pack
        #: otherwise) and each flush resolves through a per-flush
        #: COMPLETION SLAB — one gathered record per taken round, one
        #: wake per flush — instead of the per-op future fan-out.
        #: ``=0`` pins the historical per-entry pack + per-op resolve
        #: (the oracle arm).  Resolved at construction like the
        #: resolve knob so a bench A/B holds one arm per live service.
        self._enq_slab = enqueue_native.enabled()
        self._native_enqueue = enqueue_native.get()
        self.native_enqueue_flushes = 0
        self.fallback_enqueue_flushes = 0
        #: completion-slab observability: wakes (exactly one per
        #: settled op-carrying flush on the slab path) and the rounds
        #: those wakes fanned in — the "one wake per flush" claim,
        #: measurable (stats()["completion_slab"])
        self.completion_wakes = 0
        self.completion_rows = 0
        #: sharded resolve/enqueue workers (RETPU_RESOLVE_SHARDS,
        #: default 1 = the single-threaded path, the bit-identical
        #: oracle arm; docs/ARCHITECTURE.md §16).  >1 partitions the
        #: per-flush host bookkeeping — pending-slab build,
        #: completion-slab gather, mirror scatter — by contiguous
        #: run-descriptor/column range across a small thread pool.
        #: Every chunk writes/reads disjoint plane cells or mirror
        #: rows, so the sharded result is state-identical to the
        #: serial walk; the native kernels release the GIL, which is
        #: where the parallelism comes from.  The pool is lazy
        #: (created on the first sharded flush) and torn down in
        #: stop().
        self._resolve_shards = max(1, int(
            os.environ.get("RETPU_RESOLVE_SHARDS", "1") or "1"))
        self._resolve_pool: Optional[ThreadPoolExecutor] = None
        self.sharded_flushes = 0
        self.obs_registry = obs.MetricsRegistry()
        self.flight = obs.FlightRecorder(name="svc")
        self._h_flush = self.obs_registry.histogram(
            "retpu_flush_total_ms",
            "settled launch wall time (all marks summed)")
        #: per-op SLO plane (obs.opslo, docs/ARCHITECTURE.md §11):
        #: bounded stamp ring + the latency histogram it feeds
        #: (labeled by op kind; a wire op from its arrival at the
        #: server's loop, ``t_rx``, an in-process one from its API
        #: call) — the surface that answers "what p99 does a kput
        #: spend in this server, and was that tail queue wait, a
        #: compile, or the device".  The wait before ``t_rx`` is not
        #: in it: ``stats()["frontend"]["rx_hold_ms"]`` bounds that.
        #: RETPU_SLO_RING=0 disables the ring ALONE (per-op + tenant
        #: latency histograms freeze; counters and the rest of the
        #: obs plane stay live) — the op-trace A/B's off arm.
        self._slo = (obs.OpSloRing()
                     if self._obs and obs.opslo.ring_capacity()
                     else None)
        self._h_op = self.obs_registry.histogram(
            "retpu_op_latency_ms",
            "op latency, arrival at the server's loop to ack (a wire "
            "op from the front end's stamp of its frame, an "
            "in-process op from its API call; mirror-served leased "
            "reads included; the wait in the socket before the loop "
            "reads the frame is not in it)",
            label_name="kind")
        #: compile/device telemetry: every jitted step/pack variant
        #: the launch path dispatches is wrapped in a CompileWatch
        #: (executable-cache-size deltas) — a first-use compile at an
        #: un-warmed (K, A) bucket increments the serve-phase counter
        #: and logs the bucket shape instead of hiding in dispatch p99
        self._compile_watch: Dict[str, Any] = {}
        self._compile_log: "deque[Dict[str, Any]]" = deque(maxlen=64)
        self._in_warmup = False
        self._c_compile = self.obs_registry.counter(
            "retpu_compile_events_total",
            "XLA executable-cache misses in watched launch programs",
            label_name="phase")
        self._c_compile_ms = self.obs_registry.counter(
            "retpu_compile_ms_total",
            "wall ms spent inside watched calls that compiled",
            label_name="phase")
        #: the launch's programs, bound once (see _bind_step_fns)
        self._fns = self._bind_step_fns()
        #: per-tenant attribution planes [E] (a tenant is an ensemble
        #: row; named tenants via _row_name / set_tenant_label):
        #: keyed+fast-read ops, committed rounds, put payload bytes,
        #: device launches the row was active in, and a fixed-bucket
        #: latency histogram [E, B] (enqueue → resolve, ms).  All
        #: updates are numpy fancy-index adds over the flush's active
        #: rows — never per-op Python dicts.
        self.tenant_ops = np.zeros((n_ens,), np.int64)
        self.tenant_commits = np.zeros((n_ens,), np.int64)
        self.tenant_bytes = np.zeros((n_ens,), np.int64)
        self.tenant_rounds = np.zeros((n_ens,), np.int64)
        self._tenant_lat = np.zeros(
            (n_ens, len(obs.MS_BUCKETS) + 1), np.int64)
        self._lat_edges = np.asarray(obs.MS_BUCKETS)
        self._tenant_labels: Dict[int, Any] = {}
        self._launches_total = 0
        #: tenant-guard flush admission (docs/ARCHITECTURE.md §14):
        #: None = no caps installed (the bit-identical default path —
        #: flush() takes one falsy test); else {row: rounds-per-flush
        #: cap} with a per-row token bucket (refill = cap per flush,
        #: burst 2x) so a capped tenant keeps steady throughput while
        #: its queue stops forcing every flush to its own batch depth
        self._admission_caps: Optional[Dict[int, int]] = None
        self._admission_tokens: Dict[int, float] = {}
        #: the obs-actuated runtime controller (obs/controller.py):
        #: ALWAYS constructed so its retpu_autotune_* gauge family
        #: registers (zeros while off); it only acts when
        #: RETPU_AUTOTUNE=1 — cached here so the off arm pays one
        #: attribute test per settled flush
        self.controller = obs.RuntimeController(self)
        self._autotune = self.controller.enabled
        self._register_obs_metrics()
        #: the start, stamped once (``stats()["startup"]``): seconds
        #: building the device's planes and their first tree, seconds
        #: building everything this constructor holds on the host,
        #: the state's bytes on each device and, where the platform
        #: keeps allocator stats, the most any device has held by now
        #: (one copy of the state: a start that builds on one device
        #: and then places reads twice that)
        self.startup = {
            "state_init_s": state_init.seconds,
            "host_init_s": time.perf_counter() - t_state,
            "state_bytes_per_device": _state_bytes_per_device(self.state)}
        peak = max(_backend_mem_bytes_per_device(
            "peak_bytes_in_use").values())
        if not np.isnan(peak):      # no allocator stats (a CPU)
            self.startup["device_peak_bytes"] = int(peak)
        self._schedule()

    # -- dynamic ensemble lifecycle ----------------------------------------

    def create_ensemble(self, name: Any,
                        view: Optional[np.ndarray] = None
                        ) -> Optional[int]:
        """Create a named ensemble at runtime
        (``riak_ensemble_manager:create_ensemble``, manager.erl:157-166,
        over fixed device arrays): allocate a free physical row, reset
        it on device (objects/trees/leader cleared; the row's ballot
        epoch stays monotone so straggler ops of a destroyed tenant
        can never outrank the new one), install the initial view, and
        register the name.  Returns the ensemble id, or None when the
        name is taken or no row is free (capacity backpressure — the
        caller retries after a destroy, the analog of the reference's
        peer-sup limits).  ``view`` defaults to all peers.
        """
        assert self.dynamic, "construct with dynamic=True"
        # lifecycle mutates device rows + host mirrors: settle any
        # in-flight launches first (no-op in steady state)
        self._drain_launches()
        if name in self._ens_names or not self._free_rows:
            return None
        row = self._free_rows.pop()
        view = (np.ones((self.n_peers,), bool) if view is None
                else np.asarray(view, bool))
        assert view.any(), "an ensemble needs at least one member"
        mask = np.zeros((self.n_ens,), bool)
        mask[row] = True
        view_e = np.zeros((self.n_ens, self.n_peers), bool)
        view_e[row] = view
        jnp = self._jnp
        self.state = self.engine.reset_rows(
            self.state, jnp.asarray(mask), jnp.asarray(view_e))
        self.member_np[row] = view
        self._live[row] = True
        self.leader_np[row] = -1
        self.lease_until[row] = 0.0
        self._ens_names[name] = row
        self._row_name[row] = name
        self._reset_row_host(row)
        if self._wal is not None:
            self._wal.log([(("mem", row), (name, view.tolist()))])
        self._emit("svc_create_ensemble", {"name": name, "row": row})
        return row

    def destroy_ensemble(self, name: Any) -> bool:
        """Tear down a named ensemble and recycle its row: queued ops
        fail (request_failed semantics), payload handles release, the
        device row is wiped eagerly, and the row returns to the free
        pool.  Returns False for unknown names."""
        assert self.dynamic, "construct with dynamic=True"
        # settle first: an in-flight launch's WAL/settle reads this
        # row's payload handles, which the reset below releases
        self._drain_launches()
        row = self._ens_names.pop(name, None)
        if row is None:
            return False
        # the name label dies with the tenant (the row-reset below
        # only sees the post-delete fallback label) — unless a
        # sibling row still serves under it
        self._drop_tenant_series(row)
        del self._row_name[row]
        for op in self.queues[row]:
            self._fail_entry(row, op)
        self.queues[row] = []
        self._queue_rounds[row] = 0
        self._active.discard(row)
        self._purge_retries(row)
        mask = np.zeros((self.n_ens,), bool)
        mask[row] = True
        jnp = self._jnp
        self.state = self.engine.reset_rows(
            self.state, jnp.asarray(mask),
            jnp.zeros((self.n_ens, self.n_peers), bool))
        self.member_np[row] = False
        self._live[row] = False
        self.leader_np[row] = -1
        self.lease_until[row] = 0.0
        self._reset_row_host(row)
        self._free_rows.append(row)
        if self._wal is not None:
            # The destroyed tenant's kv records must not replay into
            # the recycled row; its membership row is now empty.
            self._wal.log([(("mem", row), (None, [False] * self.n_peers))])
            self._wal.delete([("kv", row, s)
                              for s in range(self.n_slots)])
        self._emit("svc_destroy_ensemble", {"name": name, "row": row})
        return True

    def resolve_ensemble(self, name: Any) -> Optional[int]:
        """Name → ensemble id (the manager's directory read)."""
        return self._ens_names.get(name)

    def _reset_row_host(self, row: int) -> None:
        """Clear a row's keyed-store host mirrors, releasing payloads,
        and wipe per-row control state a recycled tenant must not
        inherit: the membership-change pipeline (a dead tenant's
        desired/queued view would otherwise re-propose over the new
        tenant) and the failure-detector marks (an old peer-down flag
        would block the new tenant's elections)."""
        for h in self.slot_handle[row].values():
            self._release_handle(h)
        self.key_slot[row] = {}
        self.free_slots[row] = _FreeSlots(self.n_slots)
        self.slot_gen[row] = {}
        self.slot_handle[row] = {}
        self._inline_slots[row] = set()
        self._inline_np[row] = False
        self._queued_handle_writes[row] = {}
        self._recycle_pending[row] = []
        self._slot_vsn_ok[row] = False
        self._inline_value_ok[row] = False
        self._pending_writes[row] = {}
        self._corrupt_rows[row] = False
        self.elections_np[row] = 0
        # a recycled row starts with no watchers (the reference cleans
        # up watchers with their watched peer)
        self._leader_watchers.pop(row, None)
        self._desired_mask[row] = False
        self._queued_mask[row] = False
        self._pending_mask[row] = False
        self.up[row] = True
        self._up_dev = None
        # per-tenant attribution must not leak across row recycles —
        # the new tenant starts with a clean ledger, and any LABELED
        # registry series recorded under the old tenant's label go
        # with it (labeled children otherwise persist forever — a
        # successor tenant reusing the label would inherit a dead
        # tenant's samples; registry.remove_labeled is the hook).
        # Read the label BEFORE the ledger row is zeroed; a label a
        # sibling row still serves under survives the recycle.
        self._drop_tenant_series(row)
        self.tenant_ops[row] = 0
        self.tenant_commits[row] = 0
        self.tenant_bytes[row] = 0
        self.tenant_rounds[row] = 0
        self._tenant_lat[row] = 0
        self._tenant_labels.pop(row, None)

    # -- client API --------------------------------------------------------

    def _dead(self, ens: int) -> bool:
        """Ops addressed to a free/destroyed row fail fast (the
        unknown-ensemble rejection of the reference client)."""
        return self.dynamic and not self._live[ens]

    def kput(self, ens: int, key: Any, value: Any) -> Future:
        """Quorum-replicated write; resolves ('ok', handle_vsn) or
        'failed' (no slot / no quorum this flush)."""
        fut = Future()
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        slot = self._slot_for(ens, key, allocate=True)
        if slot is None:
            fut.resolve("failed")
            return fut
        handle = self._alloc_handle()
        self.values[handle] = value
        gen = self.slot_gen[ens].get(slot, 0) + 1
        self.slot_gen[ens][slot] = gen
        self._note_handle_write(ens, slot)
        self._push(ens, _PendingOp(eng.OP_PUT, slot, handle, fut,
                                   key, gen))
        return fut

    def kput_many(self, ens: int, keys: List[Any],
                  values: List[Any]) -> Future:
        """Vectorized keyed writes: N puts for one ensemble behind ONE
        future, resolving to a list of per-key results (('ok', vsn) |
        'failed') in key order.  The queue entry is struct-of-arrays —
        flush packs it into the [K, E] planes as array slices and
        resolves it with sliced result columns, so the per-op Python
        cost of the scalar kput path (Future + op object + per-op
        resolve) is amortized over the batch.  Duplicate keys
        serialize in order (sequential device rounds); keys that can't
        get a slot resolve 'failed' immediately and consume no device
        round."""
        fut = Future()
        t_sub = time.perf_counter()  # per-op SLO: submit stamp (the
        n = len(keys)                # slot/handle assignment below is
        #                              the op's 'assign' stage)
        if n != len(values):
            # trust-boundary check (this surface is network-exposed
            # via svcnode): zip truncation would leave accumulator
            # positions unfillable and hang the batch future forever
            raise ValueError(
                f"kput_many: {n} keys vs {len(values)} values")
        if self._dead(ens) or n == 0:
            fut.resolve(["failed"] * n)
            return fut
        accum = _BatchAccum(n)
        # hot path (the keyed ceiling is per-key host Python —
        # review r3 weak #3), vectorized per ARCHITECTURE §12b rung
        # 1: key→slot is ONE dict pass whose loop body is dict work
        # alone; handle allocation is one slab operation
        # (_alloc_handles), the payload store one bulk update, the
        # queued-handle-write notes a by-position bump pass.  Only
        # the generation bump stays order-sensitive — duplicate keys
        # in one batch must observe each other's bump.
        slot_l: List[int] = []
        pos_l: List[int] = []
        live_keys: List[Any] = []
        miss_pos: List[int] = []
        ks = self.key_slot[ens]
        fs = self.free_slots[ens]
        for i, key in enumerate(keys):
            s = ks.get(key)
            if s is None:
                if not fs:
                    miss_pos.append(i)   # capacity-fail: no round
                    continue
                s = fs.pop()
                ks[key] = s
            slot_l.append(s)
            pos_l.append(i)
            live_keys.append(key)
        m = len(slot_l)
        handle_l = self._alloc_handles(m)
        self.values.update(zip(handle_l,
                               (values[i] for i in pos_l)))
        sg = self.slot_gen[ens]
        gen_l: List[int] = []
        g_append = gen_l.append
        for s in slot_l:
            g = sg.get(s, 0) + 1
            sg[s] = g
            g_append(g)
        qh = self._queued_handle_writes[ens]
        for s in slot_l:
            qh[s] = qh.get(s, 0) + 1
        if miss_pos:
            accum.fill(fut, miss_pos, ["failed"] * len(miss_pos),
                       self._safe_resolve)
        if live_keys:
            # fields stay PLAIN LISTS end to end: the flush's lane
            # build extends them straight into its flat lanes, the
            # WAL encoder walks them, and the oracle arm zips them
            self._push(ens, _PendingBatch(
                eng.OP_PUT, slot_l, handle_l, fut, pos_l, live_keys,
                gen_l, accum=accum, n=m, t_sub=t_sub))
        return fut

    def kupdate_many(self, ens: int, keys: List[Any],
                     expected_vsns: List[Tuple[int, int]],
                     values: List[Any]) -> Future:
        """Vectorized CAS batch (the kupdate/kput_once semantics per
        key): commit values[i] iff keys[i]'s current version equals
        expected_vsns[i] ((0, 0) = create-if-missing).  One future,
        per-key ('ok', new_vsn) | 'failed' in order."""
        fut = Future()
        t_sub = time.perf_counter()
        n = len(keys)
        if n != len(values) or n != len(expected_vsns):
            raise ValueError(
                f"kupdate_many: {n} keys vs {len(expected_vsns)} vsns "
                f"vs {len(values)} values")
        if self._dead(ens) or n == 0:
            fut.resolve(["failed"] * n)
            return fut
        accum = _BatchAccum(n)
        # same vectorized shape as kput_many: one dict pass for
        # key→slot (CAS expectations ride it — per-key ints, no
        # allocation), one handle slab op, one store update, one
        # generation pass, one queued-handle note
        slot: List[int] = []
        pos: List[int] = []
        exp_e: List[int] = []
        exp_s: List[int] = []
        live_keys: List[Any] = []
        miss_pos: List[int] = []
        ks = self.key_slot[ens]
        fs = self.free_slots[ens]
        for i, (key, vsn) in enumerate(zip(keys, expected_vsns)):
            s = ks.get(key)
            if s is None:
                if not fs:
                    miss_pos.append(i)
                    continue
                s = fs.pop()
                ks[key] = s
            slot.append(s)
            pos.append(i)
            exp_e.append(int(vsn[0]))
            exp_s.append(int(vsn[1]))
            live_keys.append(key)
        m = len(slot)
        handle = self._alloc_handles(m)
        self.values.update(zip(handle, (values[i] for i in pos)))
        sg = self.slot_gen[ens]
        gen: List[int] = []
        g_append = gen.append
        for s in slot:
            g = sg.get(s, 0) + 1
            sg[s] = g
            g_append(g)
        qh = self._queued_handle_writes[ens]
        for s in slot:
            qh[s] = qh.get(s, 0) + 1
        if miss_pos:
            accum.fill(fut, miss_pos, ["failed"] * len(miss_pos),
                       self._safe_resolve)
        if live_keys:
            self._push(ens, _PendingBatch(
                eng.OP_CAS, slot, handle, fut, pos, live_keys, gen,
                exp_e, exp_s, accum, n=m, t_sub=t_sub))
        return fut

    def kdelete_many(self, ens: int, keys: List[Any]) -> Future:
        """Vectorized tombstone writes: one future, per-key
        ('ok', vsn) | ('ok', NOTFOUND) (no such key) | 'failed' in
        order.  Committed slots recycle like scalar kdelete."""
        fut = Future()
        t_sub = time.perf_counter()
        n = len(keys)
        if self._dead(ens) or n == 0:
            # dead-ensemble rejection, same as scalar kdelete and the
            # other batch ops — never a fake 'ok' for an unserved op
            fut.resolve(["failed"] * n)
            return fut
        accum = _BatchAccum(n)
        slot: List[int] = []
        gen: List[int] = []
        pos: List[int] = []
        live_keys: List[Any] = []
        miss_pos: List[int] = []
        sg = self.slot_gen[ens]
        ks = self.key_slot[ens]  # one dict pass (no allocation)
        for i, key in enumerate(keys):
            s = ks.get(key)
            if s is None:
                miss_pos.append(i)
                continue
            slot.append(s)
            pos.append(i)
            gen.append(sg.get(s, 0))
            live_keys.append(key)
        if miss_pos:
            accum.fill(fut, miss_pos, [("ok", NOTFOUND)] * len(miss_pos),
                       self._safe_resolve)
        if live_keys:
            m = len(live_keys)
            batch = _PendingBatch(
                eng.OP_PUT, slot, [0] * m, fut, pos, live_keys, gen,
                accum=accum, n=m, t_sub=t_sub)
            self._push(ens, batch)
            # deferred recycle per committed tombstone, keyed off the
            # batch result list (the _recycle_on_ok discipline)
            keyslots = list(zip(live_keys, slot, gen, pos))

            def recycle(results):
                if not isinstance(results, list):
                    return
                for key, s, g, p in keyslots:
                    r = results[p]
                    if isinstance(r, tuple) and r[0] == "ok":
                        self._queue_recycle(ens, (key, s, g))
            fut.add_waiter(recycle)
        return fut

    def kget_many(self, ens: int, keys: List[Any],
                  want_vsn: bool = False) -> Future:
        """Vectorized keyed reads: one future resolving to a list of
        (('ok', value|NOTFOUND) | 'failed') in key order (with
        ``want_vsn`` each hit is ('ok', value, (epoch, seq)) — the
        kget_vsn contract).  Unknown keys resolve ('ok', NOTFOUND)
        immediately and consume no device round."""
        fut = Future()
        t_sub = time.perf_counter()
        n = len(keys)
        if self._dead(ens) or n == 0:
            fut.resolve(["failed"] * n)
            return fut
        accum = _BatchAccum(n)
        slot_l: List[int] = []
        pos_l: List[int] = []
        miss_pos: List[int] = []
        fast_pos: List[int] = []
        fast_res: List[Any] = []
        ks = self.key_slot[ens]
        # ensemble-level fast-path gate checked ONCE for the batch;
        # per-key conditions (pending write, mirror coverage) below
        ens_reason = self._fast_read_ok(ens, self.runtime.now)
        for i, key in enumerate(keys):
            s = ks.get(key)
            if s is None:
                miss_pos.append(i)
                continue
            if ens_reason is None:
                reason, res = self._fast_read_result(ens, s, want_vsn)
            else:
                reason, res = ens_reason, None
            if self._count_fast(ens, reason):
                fast_pos.append(i)
                fast_res.append(res)
            else:
                slot_l.append(s)
                pos_l.append(i)
        if miss_pos:
            nf = (("ok", NOTFOUND, (0, 0)) if want_vsn
                  else ("ok", NOTFOUND))
            accum.fill(fut, miss_pos, [nf] * len(miss_pos),
                       self._safe_resolve)
        if fast_pos:
            accum.fill(fut, fast_pos, fast_res, self._safe_resolve)
        if slot_l:
            m = len(slot_l)
            self._push(ens, _PendingBatch(
                eng.OP_GET, slot_l, [0] * m, fut, pos_l, accum=accum,
                want_vsn=want_vsn, n=m, t_sub=t_sub))
        return fut

    def kget(self, ens: int, key: Any) -> Future:
        """Linearizable read; resolves ('ok', value|NOTFOUND) or
        'failed'.  Served from the leader's committed host mirror —
        no device round — while the lease-protected fast path's
        conditions hold (see the module docstring); otherwise the read
        rides an ``OP_GET`` round like always."""
        fut = Future()
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        slot = self._slot_for(ens, key, allocate=False)
        if slot is None:
            fut.resolve(("ok", NOTFOUND))
            return fut
        hit, res = self._try_fast(ens, slot, False)
        if hit:
            self._safe_resolve(fut, res)
            return fut
        self._push(ens, _PendingOp(eng.OP_GET, slot, 0, fut))
        return fut

    def kget_vsn(self, ens: int, key: Any) -> Future:
        """Read returning the version too: ('ok', value|NOTFOUND,
        (epoch, seq)) — the handle a subsequent :meth:`kupdate` /
        :meth:`ksafe_delete` CAS needs.  An absent key reads as
        ('ok', NOTFOUND, (0, 0)); CAS'ing against (0, 0) is
        create-if-missing (the kput_once semantics)."""
        fut = Future()
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        slot = self._slot_for(ens, key, allocate=False)
        if slot is None:
            fut.resolve(("ok", NOTFOUND, (0, 0)))
            return fut
        hit, res = self._try_fast(ens, slot, True)
        if hit:
            self._safe_resolve(fut, res)
            return fut
        self._push(ens, _PendingOp(eng.OP_GET, slot, 0, fut,
                                   want_vsn=True))
        return fut

    def kupdate(self, ens: int, key: Any, expected_vsn: Tuple[int, int],
                value: Any) -> Future:
        """Compare-and-swap (do_kupdate, peer.erl:259-270): commit
        `value` iff the key's current version equals `expected_vsn`
        (from a :meth:`kput`/:meth:`kupdate` result or
        :meth:`kget_vsn`); (0, 0) on an absent key is
        create-if-missing (kput_once).  Resolves ('ok', new_vsn) or
        'failed' (version mismatch / no quorum)."""
        fut = Future()
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        slot = self._slot_for(ens, key, allocate=True)
        if slot is None:
            fut.resolve("failed")
            return fut
        handle = self._alloc_handle()
        self.values[handle] = value
        gen = self.slot_gen[ens].get(slot, 0) + 1
        self.slot_gen[ens][slot] = gen
        self._note_handle_write(ens, slot)
        self._push(ens, _PendingOp(
            eng.OP_CAS, slot, handle, fut, key, gen,
            exp=(int(expected_vsn[0]), int(expected_vsn[1]))))
        return fut

    def kput_once(self, ens: int, key: Any, value: Any) -> Future:
        """Create-if-missing (do_kput_once, peer.erl:278-284): the
        (0, 0)-expected CAS — commits only when the key holds nothing
        (true absence or a tombstone).  Resolves ('ok', vsn) |
        'failed' (exists / no quorum)."""
        return self.kupdate(ens, key, (0, 0), value)

    def ksafe_delete(self, ens: int, key: Any,
                     expected_vsn: Tuple[int, int]) -> Future:
        """Version-guarded delete (ksafe_delete): CAS to a tombstone."""
        fut = Future()
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        slot = self._slot_for(ens, key, allocate=False)
        if slot is None:
            fut.resolve("failed")  # nothing at this key to guard
            return fut
        op = _PendingOp(eng.OP_CAS, slot, 0, fut, key,
                        self.slot_gen[ens].get(slot, 0),
                        exp=(int(expected_vsn[0]), int(expected_vsn[1])))
        self._push(ens, op)
        self._recycle_on_ok(fut, ens, key, slot)
        return fut

    def kdelete(self, ens: int, key: Any) -> Future:
        """Tombstone write (slot recycled once committed)."""
        fut = Future()
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        slot = self._slot_for(ens, key, allocate=False)
        if slot is None:
            fut.resolve(("ok", NOTFOUND))
            return fut
        handle = 0  # 0 = tombstone handle
        # key rides along for the WAL record (replay must drop the
        # checkpoint-era key→slot mapping this delete invalidated);
        # gen matches the slot so a failed delete can't queue a bogus
        # recycle through _fail_op.
        op = _PendingOp(eng.OP_PUT, slot, handle, fut, key,
                        self.slot_gen[ens].get(slot, 0))
        self._push(ens, op)
        self._recycle_on_ok(fut, ens, key, slot)
        return fut

    # -- version-preserving bulk install (tenant handoff) ------------------

    def install_objs(self, ens: int,
                     items: List[Tuple[Any, Tuple[int, int], Any]]
                     ) -> List[Any]:
        """Install keyed objects WITH their versions — the
        version-continuity half of a placement move (the reference's
        membership changes move consensus while objects keep their
        {epoch, seq}: a client's CAS token survives member replacement,
        replace_members_test.erl:26-30, doc/Readme.md:156-167).  A
        re-ingest through kput would mint fresh versions and void
        every outstanding CAS token.

        ``items``: ``[(key, (epoch, seq), payload), ...]``.  Applied
        synchronously: slots/handles allocate host-side, the objects
        scatter into EVERY replica lane of the row (they are committed
        data from the previous owner), the row's trees rebuild, and
        the row's ballot epoch rises to the max installed epoch with
        the leader cleared — the next election runs at a strictly
        higher epoch, so post-move writes always version-dominate the
        installed objects.  Committed to the WAL with the real
        versions.  Returns per-item ``("ok", (epoch, seq))`` |
        ``"failed"`` (no slot).
        """
        self._drain_launches()  # installs splice device state directly
        results, applied = self._allocate_install(ens, items)
        if applied:
            self._apply_installed(ens, applied,
                                  self._install_lead(ens))
        return results

    def _install_lead(self, ens: int) -> int:
        """The install's leadership decision, made ONCE (on a
        replication-group leader it ships with the frame — deciding
        per lane from local host mirrors would diverge the lanes):
        a leaderless row gets the first view member declared at the
        installed epoch (no election bump → no stale-read re-version
        → CAS tokens survive); a live row keeps its leader (-1) —
        the late-merge path must not stomp a serving leader."""
        return (int(np.argmax(self.member_np[ens]))
                if int(self.leader_np[ens]) < 0 else -1)

    def _allocate_install(self, ens: int, items):
        """Host-side half: slots + handles for the installable items
        (separated so a replication-group leader can ship the exact
        allocation to its replicas — independent allocation could
        diverge free-list orders across lanes)."""
        results: List[Any] = []
        applied: List[Tuple] = []
        for key, (ve, vs), payload in items:
            slot = self._slot_for(ens, key, allocate=True)
            if slot is None:
                results.append("failed")
                continue
            handle = self._alloc_handle()
            self.values[handle] = payload
            applied.append((key, int(slot), int(handle), int(ve),
                            int(vs), payload))
            results.append(("ok", (int(ve), int(vs))))
        return results, applied

    def _apply_installed(self, ens: int, applied: List[Tuple],
                         lead: int = -1,
                         extra_records: Optional[List[Tuple]] = None
                         ) -> None:
        """Device + mirror + WAL application of an allocation from
        :meth:`_allocate_install` (verbatim on replicas).  ``lead``
        is the leader's :meth:`_install_lead` decision (-1 = keep);
        ``extra_records`` overrides :meth:`_wal_extra_records` in the
        install's durability barrier — a replication-group replica
        passes its REAL (promised, ge, seq, cfg) (its inherited
        leader-side fields would write regressed group meta)."""
        jnp = self._jnp
        ens = int(ens)
        slots = np.asarray([a[1] for a in applied], np.int32)
        eps = np.asarray([a[3] for a in applied], np.int32)
        sqs = np.asarray([a[4] for a in applied], np.int32)
        hds = np.asarray([a[2] for a in applied], np.int32)
        st = self.state
        s_j = jnp.asarray(slots)
        # index (int, :, array) puts the advanced axes FIRST — the
        # update target is [n_items, M], so the per-item vectors need
        # an explicit lane axis (a bare [n_items] only broadcast for
        # the single-item installs the early tests happened to do)
        st = st._replace(
            obj_epoch=st.obj_epoch.at[ens, :, s_j].set(
                jnp.asarray(eps)[:, None]),
            obj_seq=st.obj_seq.at[ens, :, s_j].set(
                jnp.asarray(sqs)[:, None]),
            obj_val=st.obj_val.at[ens, :, s_j].set(
                jnp.asarray(hds)[:, None]))
        # Version continuity requires NO epoch change on first touch:
        # a read at a ballot epoch above the objects' epochs triggers
        # the stale-epoch rewrite (update_key), re-versioning every
        # object and voiding the tokens this install exists to
        # preserve.  So: raise the row's ballot epoch to the max
        # installed epoch and DECLARE leadership at that epoch (the
        # row is brand-new to this service — no straggler writer at
        # that epoch can exist here, the old owner's row was destroyed
        # before the offer), and raise the per-row seq counter past
        # the installed seqs so same-epoch writes version-dominate.
        # If the recycled row's epoch already EXCEEDS the installed
        # max (its previous tenant's straggler fence), the fence wins:
        # first reads re-version, exactly like the reference after an
        # election (update_key, peer.erl:1564-1596).
        row_max = jnp.asarray(int(eps.max()) if len(eps) else 0,
                              jnp.int32)
        st = st._replace(
            epoch=st.epoch.at[ens, :].set(
                jnp.maximum(st.epoch[ens], row_max)),
            obj_seq_ctr=st.obj_seq_ctr.at[ens].set(
                jnp.maximum(st.obj_seq_ctr[ens],
                            jnp.asarray(int(sqs.max())
                                        if len(sqs) else 0,
                                        jnp.int32))))
        if lead >= 0:
            st = st._replace(leader=st.leader.at[ens].set(lead))
        mask = np.zeros((self.n_ens, self.n_peers), bool)
        mask[ens] = True
        self.state = self.engine.rebuild_trees(st, jnp.asarray(mask))
        for key, slot, handle, ve, vs, payload in applied:
            self._inline_slots[ens].discard(slot)
            self._inline_np[ens, slot] = False
            self._inline_value_ok[ens, slot] = False
            # installs carry their committed versions: the fast
            # path's vsn mirror adopts them (CAS-token continuity
            # extends to leased reads)
            self._slot_vsn_np[ens, slot] = (ve, vs)
            self._slot_vsn_ok[ens, slot] = True
            old = self.slot_handle[ens].pop(slot, 0)
            if old and old != handle:
                # values-only drop, NEVER the handle pool: the handle
                # numbers are the allocating leader's; pooling them on
                # a replica would let a later promotion re-allocate a
                # number the leader still has live (cross-key payload
                # corruption).  The handle number leaks; numbers are
                # 31-bit and installs are rare.
                self.values.pop(old, None)
            self.values[handle] = payload
            self.slot_handle[ens][slot] = handle
            if handle >= self._next_handle:
                self._next_handle = handle + 1
            self.key_slot[ens][key] = slot
        if lead >= 0:
            self.leader_np[ens] = lead
            self.lease_until[ens] = 0.0
        self._up_dev = None
        if self._wal is not None:
            recs = [(("kv", ens, slot),
                     (key, handle, ve, vs, payload, False))
                    for key, slot, handle, ve, vs, payload in applied]
            self._wal.log(recs + (extra_records
                                  if extra_records is not None
                                  else self._wal_extra_records()))

    def kmodify(self, ens: int, key: Any, mod_fun: Any, default: Any,
                retries: int = 8) -> Future:
        """Server-side modify — the batched analog of the put FSM's
        kmodify (do_kmodify, peer.erl:303-317; modify FSM
        :1404-1416): read the key, apply ``mod_fun`` to the current
        value (``default`` when absent), and commit the result under
        the read version's CAS guard, retrying the whole
        read→fn→CAS cycle on conflict (another writer's commit landed
        between our read and our write — the seq discipline the
        reference gets from running the fun inside the leader's FSM).

        ``mod_fun`` is a callable or a wire-safe funref
        (:mod:`riak_ensemble_tpu.funref`), called as
        ``mod_fun(vsn, current_value) -> new_value | "failed"`` —
        the actor plane's signature, except ``vsn`` is the version
        the value was READ at, not the prospective commit version
        (the batched engine assigns versions on device at commit
        time, so they are unknowable host-side; root-style vsn-pinned
        merges use the CAS guard itself for that).  Returning
        "failed" (or raising) aborts without writing.  Resolves
        ('ok', new_vsn) | 'failed'.

        The DEVICE FAST PATH: a ``mod_fun`` funref that resolves to a
        mod-fun table entry (:func:`funref.device_entry` — rmw:add,
        rmw:max, ..., one bound int32 operand) on a key holding a
        device-native value (fresh, or previously written by the fast
        path) runs as ONE ``OP_RMW`` engine round instead: the read,
        the fun and the commit fuse under the round's seq discipline,
        so the op costs one flush and can never CAS-conflict.
        Requires ``default == 0`` (the engine reads absence as 0);
        anything else keeps the host path below.

        The host path's chain rides the flush cadence: each attempt's
        read and CAS are ordinary queued ops, so concurrent kmodifys
        of one key serialize through device-round order and the
        losers retry — N concurrent increments converge to exactly
        +N.  The CAS half of an attempt is CHAINED into the flush
        that resolved its read (flush() runs a bounded extra launch
        cycle when a resolve enqueued follow-ups), and conflicted
        retries back off by a jittered number of flushes.
        """
        from riak_ensemble_tpu import funref

        fut = Future()
        try:
            fn = funref.resolve(mod_fun)
        except ValueError:
            fut.resolve("failed")
            return fut
        if self._dead(ens):
            fut.resolve("failed")
            return fut
        dev = funref.device_entry(mod_fun)
        if dev is not None and funref.is_int32(default) \
                and int(default) == 0:
            slot = self._slot_for(ens, key, allocate=True)
            if slot is None:
                fut.resolve("failed")
                return fut
            if self._rmw_eligible(ens, slot):
                # A device RMW cannot CAS-conflict, so a failed round
                # is a transient (quorum blip / unverifiable absence)
                # — honor ``retries`` for those, exactly like the
                # host path's on_cas does.  Each attempt re-resolves
                # the slot: a racing put may have flipped the key to
                # host storage (then 'failed' is the honest outcome —
                # retrying device arithmetic there would corrupt it).
                def dev_attempt(tries_left: int) -> None:
                    s = self._slot_for(ens, key, allocate=True)
                    if s is None or not self._rmw_eligible(ens, s):
                        self._safe_resolve(fut, "failed")
                        return
                    inner = Future()
                    self._push_rmw(ens, key, s, dev, inner)

                    def on_res(r: Any) -> None:
                        if fut.done:
                            return
                        if (isinstance(r, tuple) and r[0] == "ok") \
                                or tries_left <= 1 \
                                or self._dead(ens):
                            self._safe_resolve(fut, r)
                            return
                        if (dev[0] == funref.RMW_PIA
                                and self.slot_handle[ens].get(s, 0)
                                == -1):
                            # deterministic refuse: the slot provably
                            # holds a live device value — retrying a
                            # put-if-absent can't change the outcome
                            self._safe_resolve(fut, r)
                            return
                        self._retry_later(
                            ens, fut, 0,
                            lambda: dev_attempt(tries_left - 1))
                    inner.add_waiter(on_res)

                dev_attempt(max(1, retries))
                return fut
            # key holds a host payload: the host retry path below
            # (its read returns the stored value, not an int32 lane)
        if funref.device_code(mod_fun) == funref.RMW_PIA \
                and len(mod_fun[2]) == 1:
            # put-if-absent over a host-payload key cannot go through
            # the fn (a live payload of int 0 would read as 'absent'
            # and be clobbered); the (0,0)-CAS IS the exact
            # do_kput_once semantics — a live payload of ANY value
            # refuses, absence/tombstone commits.  Routed by NAME,
            # not device_entry: a non-int32 operand (put-if-absent of
            # an arbitrary payload) must take this path too.
            self.kput_once(ens, key, mod_fun[2][0]).add_waiter(
                lambda r: self._safe_resolve(fut, r))
            return fut

        def attempt(tries_left: int, conflicts: int) -> None:
            g = self.kget_vsn(ens, key)

            def on_read(res: Any) -> None:
                if fut.done:
                    return
                if not (isinstance(res, tuple) and res[0] == "ok"):
                    self._safe_resolve(fut, "failed")
                    return
                cur, vsn = res[1], tuple(res[2])
                try:
                    new = fn(vsn, default if cur is NOTFOUND else cur)
                except Exception:
                    self._emit_kmodify_error()
                    self._safe_resolve(fut, "failed")
                    return
                if isinstance(new, str) and new == "failed":
                    self._safe_resolve(fut, "failed")
                    return
                if (dev is not None and funref.is_int32(new)
                        and int(new) == 0
                        and self._slot_for(ens, key, allocate=False)
                        is not None):
                    # a TABLE fun computing 0 means the tombstone on
                    # the device path — mirror it here whenever the
                    # key HAS a slot (vsn (0,0) included: an
                    # absent-read over an existing slot still CASes
                    # the tombstone); a kupdate would store a live
                    # int-0 payload and the key would read back found
                    # where the device path reads notfound.  A truly
                    # slotless key (only reachable with a non-zero
                    # default, outside the device-equivalence domain)
                    # keeps the generic path.
                    c = self.ksafe_delete(ens, key, vsn)
                else:
                    c = self.kupdate(ens, key, vsn, new)
                # the CAS was enqueued by a resolve: let the flush
                # that is settling this read serve it too
                self._chain_kick = True

                def on_cas(r: Any) -> None:
                    if fut.done:
                        return
                    if isinstance(r, tuple) and r[0] == "ok":
                        self._safe_resolve(fut, r)
                    elif tries_left > 1 and not self._dead(ens):
                        # counts retried CAS losses: true write races
                        # plus transient quorum failures (the client
                        # can't tell them apart from 'failed'; a
                        # destroyed row stops retrying entirely)
                        self.rmw_conflicts += 1
                        self._retry_later(
                            ens, fut, conflicts,
                            lambda: attempt(tries_left - 1,
                                            conflicts + 1))
                    else:
                        self._safe_resolve(fut, "failed")
                c.add_waiter(on_cas)
            g.add_waiter(on_read)

        attempt(max(1, retries), 0)
        return fut

    def kmodify_many(self, ens: int, keys: List[Any], mod_fun: Any,
                     default: Any = 0, retries: int = 8) -> Future:
        """Vectorized server-side modify: apply ONE ``mod_fun`` to N
        keys behind one future, resolving to per-key ('ok', new_vsn) |
        'failed' in key order.  A device-table funref takes one
        ``OP_RMW`` round per key — the whole batch is a single
        struct-of-arrays queue entry costing one flush, conflict-free
        by construction.  Non-table funs (or keys holding host
        payloads) fall back to per-key :meth:`kmodify` chains sharing
        the batch accumulator.

        Enqueue-side coalescing (docs/ARCHITECTURE.md §18): when the
        comm lane is on and the fun is commutative/semilattice,
        duplicate keys in one call fold into a SINGLE device row —
        operands merged with the same int32-exact fold the replication
        merge section uses (sub normalizes to add of the negated
        operand), so the slot's final value and version are bit-equal
        to the sequenced chain's.  All members of a coalesced group
        share the row's ('ok', vsn): the group commits or fails as
        one op, and the version is the slot's post-group version, the
        only one a subsequent CAS could use anyway.  Ordered funs
        (set/bxor/put_if_absent) never coalesce."""
        from riak_ensemble_tpu import funref

        fut = Future()
        n = len(keys)
        if self._dead(ens) or n == 0:
            fut.resolve(["failed"] * n)
            return fut
        accum = _BatchAccum(n)
        dev = funref.device_entry(mod_fun)
        device_ok = (dev is not None and funref.is_int32(default)
                     and int(default) == 0)

        def host_one(i: int, key: Any) -> None:
            f = self.kmodify(ens, key, mod_fun, default, retries)
            f.add_waiter(lambda r, i=i: accum.fill(
                fut, [i], [r], self._safe_resolve))

        if not device_ok:
            for i, key in enumerate(keys):
                host_one(i, key)
            return fut
        code, operand = dev
        coalesce = (self._comm_repl
                    and funref.merge_class(code) is not None)
        sg = self.slot_gen[ens]
        inline = self._inline_slots[ens]
        ks = self.key_slot[ens]
        fs = self.free_slots[ens]
        slot_l: List[int] = []
        ops_l: List[int] = []
        gen_l: List[int] = []
        live_keys: List[Any] = []
        members: List[List[int]] = []   # result positions per row
        row_of: Dict[int, int] = {}
        miss_pos: List[int] = []
        # one dict pass for key→slot + eligibility; the storage-class
        # set/slab adopt the whole batch in bulk below
        for i, key in enumerate(keys):
            s = ks.get(key)
            if s is None:
                if not fs:
                    miss_pos.append(i)
                    continue
                s = fs.pop()
                ks[key] = s
            if not self._rmw_eligible(ens, s):
                host_one(i, key)  # host-payload key: per-key fallback
                continue
            if coalesce:
                r = row_of.get(s)
                if r is not None:
                    ops_l[r] = funref.fold_operand(
                        code, ops_l[r], operand)
                    members[r].append(i)
                    self.rmw_enqueue_coalesced += 1
                    continue
                row_of[s] = len(slot_l)
            g = sg.get(s, 0) + 1
            sg[s] = g
            slot_l.append(s)
            ops_l.append(funref.fold_seed(code, operand) if coalesce
                         else operand)
            gen_l.append(g)
            live_keys.append(key)
            members.append([i])
        if slot_l:
            inline.update(slot_l)
            self._inline_np[ens, np.asarray(slot_l, np.int32)] = True
        if miss_pos:
            accum.fill(fut, miss_pos, ["failed"] * len(miss_pos),
                       self._safe_resolve)
        if live_keys:
            m = len(slot_l)
            self.rmw_device_fastpath += sum(
                len(mb) for mb in members)
            # sub ships as add of the (folded) negated operand when
            # coalescing — fold_seed/fold_operand live in the
            # MERGE_ADD-normalized domain, so the row's fun code must
            # match it (bit-equal value either way: cur-a-b == cur+
            # (-(a+b)) under int32 wraparound)
            ship_code = (funref.RMW_ADD
                         if coalesce and code == funref.RMW_SUB
                         else code)
            # the batch rides an INNER future so transiently-failed
            # rows (quorum blips — a device RMW cannot CAS-conflict)
            # get their remaining ``retries`` through the scalar
            # path, same contract as kmodify; a failed coalesced
            # group applied NOTHING (all-or-nothing row), so each
            # member retrying its own single op is exact
            inner = Future()
            self._push(ens, _PendingBatch(
                eng.OP_RMW, slot_l, ops_l, inner,
                list(range(m)), live_keys, gen_l, [ship_code] * m,
                [0] * m, _BatchAccum(m), want_vsn=True, n=m))

            def on_batch(results: Any) -> None:
                if not isinstance(results, list):
                    allp = [p for mb in members for p in mb]
                    accum.fill(fut, allp, ["failed"] * len(allp),
                               self._safe_resolve)
                    return
                for mb, key, r in zip(members, live_keys, results):
                    if (isinstance(r, tuple) and r[0] == "ok") \
                            or retries <= 1 or self._dead(ens):
                        accum.fill(fut, mb, [r] * len(mb),
                                   self._safe_resolve)
                    else:
                        for pos in mb:
                            f = self.kmodify(ens, key, mod_fun,
                                             default, retries - 1)
                            f.add_waiter(
                                lambda r2, pos=pos: accum.fill(
                                    fut, [pos], [r2],
                                    self._safe_resolve))
            inner.add_waiter(on_batch)
        return fut

    # -- runtime-controller actuation points (ARCHITECTURE §14) -------------

    def set_pipeline_depth(self, depth: int) -> int:
        """Retune the launch pipeline depth at runtime (the ack-RTT
        actuator's knob; also callable by an operator).  Settles
        every in-flight launch first so no launch ever observes a
        depth change mid-stream — submission order, the WAL barrier
        and rollback snapshots are exactly as at construction.
        Returns the previous depth."""
        depth = max(1, int(depth))
        old = self.pipeline_depth
        if depth != old:
            self._drain_launches()
            self.pipeline_depth = depth
            self._emit("svc_autotune",
                       {"knob": "pipeline_depth", "old": old,
                        "new": depth})
        return old

    def set_admission_caps(self,
                           caps: Optional[Dict[int, int]]) -> None:
        """Install (or clear, with None) per-row flush-admission
        round caps — the tenant guard's knob.  Each capped row gets
        a token bucket: refill = cap per flush, burst 2x cap, so a
        capped tenant's flush share is bounded without starving it.
        ``None``/empty restores the exact uncapped take path."""
        caps = {int(e): max(1, int(c))
                for e, c in caps.items()} if caps else None
        self._admission_caps = caps
        # fresh buckets start full: the first capped flush admits a
        # full cap rather than zero (no spurious stall on install)
        self._admission_tokens = (
            {e: float(c) for e, c in caps.items()} if caps else {})
        self._emit("svc_autotune",
                   {"knob": "admission_caps",
                    "new": dict(caps) if caps else None})

    def set_autotune(self, enabled: bool) -> None:
        """Arm/disarm the runtime controller for THIS service (the
        programmatic form of ``RETPU_AUTOTUNE``; svcnode's
        ``--autotune``).  Disarming also clears any installed
        admission caps so the service returns to the exact
        pre-controller take path."""
        enabled = bool(enabled)
        if enabled == self._autotune:
            return
        self._autotune = enabled
        self.controller.enabled = enabled
        if enabled:
            # re-anchor the heal target at ARM time: the operator may
            # have moved the knobs since construction, and the tuner
            # must never walk below the configuration it was armed on
            self._autotune_base_depth = int(self.pipeline_depth)
            self._autotune_base_window = int(
                getattr(self, "repl_window", 1))
        if not enabled and self._admission_caps:
            self.controller.guard.throttled.clear()
            self.set_admission_caps(None)
        self._emit("svc_autotune",
                   {"knob": "autotune", "new": enabled})

    # -- lease-protected read fast path -------------------------------------

    def set_fast_reads(self, enabled: bool) -> None:
        """Runtime opt-in/out for the lease-protected read fast path
        (the programmatic form of ``RETPU_FAST_READS``); disabling
        routes every read through the device round again."""
        enabled = bool(enabled) and self.config.trust_lease
        if enabled:
            # the safety inequality is a precondition of SERVING, so
            # it is (re-)checked at every enable, not just the one in
            # __init__ — a config whose margin doesn't fit the
            # lease/follower gap may run, but never fast-serve
            self._assert_read_margin()
        self._fast_reads = enabled

    def _assert_read_margin(self) -> None:
        assert (0.0 <= self._read_margin
                and self.config.lease() + self._read_margin
                < self.config.follower()), \
            "need 0 <= read_margin and lease + read_margin " \
            "< follower_timeout to enable lease-protected reads"

    def _fast_read_ok(self, ens: int, now: float) -> Optional[str]:
        """None when ensemble ``ens`` may serve lease-protected reads
        right now; otherwise the miss reason.  Subclasses layer their
        own gates (a replication-group leader adds the host-quorum
        lease and the leader-only rule)."""
        if not self._fast_reads:
            return "disabled"
        lead = self.leader_np[ens]
        if lead < 0 or not self.up[ens, lead]:
            # leaderless / leader-down rows are electing (the next
            # flush folds the election in) — never serve around that
            return "no_leader"
        if self._corrupt_rows[ens]:
            # flagged rows take the device round so the synctree
            # integrity gate still vets the read (reference read-path
            # validation); cleared once the exchange syncs the row
            return "corrupt"
        if self.lease_until[ens] <= now + self._read_margin:
            return "no_lease"
        return None

    def _try_fast(self, ens: int, slot: int, want_vsn: bool
                  ) -> Tuple[bool, Any]:
        """The whole fast-path gate for one scalar read: (hit,
        result).  Accounts the attempt either way; ``result`` is only
        valid on a hit.  (``kget_many`` inlines the same sequence so
        it can check the ensemble-level gate once per batch.)"""
        reason = self._fast_read_ok(ens, self.runtime.now)
        if reason is None:
            reason, res = self._fast_read_result(ens, slot, want_vsn)
        else:
            res = None
        return self._count_fast(ens, reason), res

    def _fast_read_result(self, ens: int, slot: int, want_vsn: bool
                          ) -> Tuple[Optional[str], Any]:
        """(miss_reason, result) for one slot read off the committed
        host mirror; ``result`` is only valid when the reason is None.
        The caller has already passed :meth:`_fast_read_ok`."""
        if slot in self._pending_writes[ens]:
            return "pending_write", None
        vsn: Any = None
        if want_vsn:
            if not self._slot_vsn_ok[ens, slot]:
                # unmirrored version (fresh restore / post-election
                # invalidation): the device round re-versions and its
                # resolve refreshes the mirror
                return "vsn_unmirrored", None
            ve, vs = self._slot_vsn_np[ens, slot]
            vsn = (int(ve), int(vs))
        h = self.slot_handle[ens].get(slot, 0)
        if h == -1:
            if not self._inline_value_ok[ens, slot]:
                return "inline_unmirrored", None
            out: Any = int(self._inline_value_np[ens, slot])
        elif h:
            out = self.values.get(h, NOTFOUND)
        else:
            # nothing committed (tombstone or never-written slot): the
            # device reads it notfound too; a tombstone's real vsn
            # rides along so CAS chains still work
            out = NOTFOUND
        return None, (("ok", out, vsn) if want_vsn else ("ok", out))

    def _count_fast(self, ens: int, reason: Optional[str]) -> bool:
        """Account one fast-path attempt; True = hit (serve now)."""
        if reason is None:
            self.read_fastpath_hits += 1
            # a mirror-served read is a served op: keep the
            # throughput counter honest when 90% of traffic never
            # reaches a resolve path
            self.ops_served += 1
            if self._obs:
                # mirror-served reads are tenant ops too — without
                # them a read-heavy tenant would look idle — and they
                # contribute a lowest-bucket latency sample (a mirror
                # hit is microseconds, far under the ladder's 50 µs
                # floor), so a read-heavy tenant's p50/p99 reflects
                # its real service time instead of reporting 0
                self.tenant_ops[ens] += 1
                if self._slo is not None:
                    # per-op + per-tenant latency samples follow the
                    # ring knob (RETPU_SLO_RING=0 freezes BOTH —
                    # leaving only the lowest-bucket fast-read
                    # samples live would skew p50/p99, worse than
                    # frozen): one lowest-bucket 'get_fast' sample —
                    # mirror hits ARE the client's experienced
                    # latency for these reads
                    self._tenant_lat[ens, 0] += 1
                    child = self._h_op.labels(obs.opslo.KIND_NAMES[
                        obs.opslo.KIND_FAST_READ])
                    child.counts[0] += 1
                    child.count += 1
            return True
        self.read_fastpath_misses += 1
        r = self.read_fastpath_miss_reasons
        r[reason] = r.get(reason, 0) + 1
        return False

    def _note_write(self, ens: int, slot: int) -> None:
        _note(self._pending_writes[ens], slot)

    def _unnote_write(self, ens: int, slot: int) -> None:
        _unnote(self._pending_writes[ens], slot)

    def _rmw_eligible(self, ens: int, slot: int) -> bool:
        """A slot the device fast path may RMW: no QUEUED host-payload
        write racing it, and device-native already or holding no
        committed host payload (fresh/tombstoned) — running int32
        arithmetic over a payload HANDLE (committed or about to
        commit earlier in the same flush) would corrupt the data
        while acking 'ok'."""
        if slot in self._queued_handle_writes[ens]:
            return False
        return (slot in self._inline_slots[ens]
                or self.slot_handle[ens].get(slot, 0) == 0)

    def _note_handle_write(self, ens: int, slot: int) -> None:
        _note(self._queued_handle_writes[ens], slot)

    def _unnote_handle_write(self, ens: int, slot: int) -> None:
        _unnote(self._queued_handle_writes[ens], slot)

    def _push_rmw(self, ens: int, key: Any, slot: int,
                  dev: Tuple[int, int], fut: Future) -> None:
        code, operand = dev
        gen = self.slot_gen[ens].get(slot, 0) + 1
        self.slot_gen[ens][slot] = gen
        # optimistic inline marking: a second kmodify racing this
        # one's commit must still see the slot as device-native
        self._inline_slots[ens].add(slot)
        self._inline_np[ens, slot] = True
        self.rmw_device_fastpath += 1
        self._push(ens, _PendingOp(eng.OP_RMW, slot, operand, fut,
                                   key, gen, exp=(code, 0),
                                   want_vsn=True))

    def _retry_later(self, ens: int, fut: Future, conflict_idx: int,
                     thunk) -> None:
        """Jittered backoff between CAS-conflict retries, in flush
        calls: retry 0 is immediate (the common two-writer race wins
        on the second round), later ones pick a uniformly random
        delay from a doubling window so N stampeding writers spread
        over ~N flushes instead of re-colliding every round."""
        delay = self._rng.randrange(1 << min(conflict_idx, 4))
        if delay == 0:
            thunk()
            # an immediate retry enqueued during a resolve is a chain
            # follow-up like the CAS half
            self._chain_kick = True
        else:
            self._retry_at.append((self._flush_calls + delay, ens,
                                   fut, thunk))

    def _run_due_retries(self) -> None:
        if not self._retry_at:
            return
        now = self._flush_calls
        due = [t for at, _e, fut, t in self._retry_at
               if at <= now and not fut.done]
        self._retry_at = [r for r in self._retry_at
                          if r[0] > now and not r[2].done]
        for thunk in due:
            thunk()

    def _purge_retries(self, ens: int) -> None:
        """Fail and drop parked retries addressed to a destroyed row
        — a thunk firing after the row recycles would run the dead
        tenant's mod-fun against the NEW tenant's ensemble (its
        create-if-missing CAS would even commit)."""
        keep: List[Tuple[int, int, Future, Any]] = []
        for at, e, fut, thunk in self._retry_at:
            if e == ens:
                self._safe_resolve(fut, "failed")
            else:
                keep.append((at, e, fut, thunk))
        self._retry_at = keep

    def _emit_kmodify_error(self) -> None:
        """Trace a mod-fun exception, rate-limited to one traceback
        per second (a hot fun bug at flush rate would otherwise emit
        thousands); suppressed counts ride the next emission."""
        now = time.monotonic()
        if now - self._kmodify_err_at >= 1.0:
            import traceback
            self._kmodify_err_at = now
            self._emit("svc_kmodify_error",
                       {"error": traceback.format_exc(limit=8),
                        "suppressed": self._kmodify_err_dropped})
            self._kmodify_err_dropped = 0
        else:
            self._kmodify_err_dropped += 1

    def _recycle_on_ok(self, fut: Future, ens: int, key: Any,
                       slot: int) -> None:
        """Once a delete commits, queue the slot for deferred
        recycling (validated and applied by _drain_recycles)."""
        gen = self.slot_gen[ens].get(slot, 0)

        def recycle(result):
            if isinstance(result, tuple) and result[0] == "ok":
                self._queue_recycle(ens, (key, slot, gen))
        fut.add_waiter(recycle)

    def watch_leader(self, ens: int, fn) -> None:
        """Leader-status watcher for one ensemble — the scale-path
        ``watch_leader_status`` (peer.erl:212-218, 2070-2075):
        ``fn(ens, old_leader, new_leader)`` fires immediately with the
        CURRENT status at registration (old == new, the reference's
        initial notify) and then after any flush or membership change
        that moved the leader (-1 = none).  Watcher exceptions are
        contained and traced like client waiters; remove with
        :meth:`unwatch_leader` (the stop_watching counterpart)."""
        self._leader_watchers.setdefault(ens, []).append(fn)
        cur = int(self.leader_np[ens])
        self._safe_notify(fn, ens, cur, cur)

    def unwatch_leader(self, ens: int, fn) -> bool:
        """Deregister a leader watcher (stop_watching,
        peer.erl:220-226); True iff it was registered."""
        fns = self._leader_watchers.get(ens)
        if fns is None or fn not in fns:
            return False
        fns.remove(fn)
        if not fns:
            del self._leader_watchers[ens]
        return True

    def _safe_notify(self, fn, *args) -> None:
        """Run a watcher callback, containing and tracing exceptions
        (the _safe_resolve contract for watchers)."""
        try:
            fn(*args)
        except Exception:
            import traceback
            self._emit("svc_watcher_error",
                       {"error": traceback.format_exc(limit=8)})

    def _notify_leader_changes(self, old: np.ndarray) -> None:
        if not self._leader_watchers:
            return
        changed = np.nonzero(old != self.leader_np)[0]
        for e in changed.tolist():
            # snapshot: a watcher may watch/unwatch from its callback
            for fn in list(self._leader_watchers.get(e, ())):
                self._safe_notify(fn, e, int(old[e]),
                                  int(self.leader_np[e]))

    def set_peer_up(self, ens: int, peer: int, up: bool) -> None:
        """Failure-detector input (the host's nodedown/suspend signal)."""
        self.up[ens, peer] = up
        self._up_dev = None

    def _up_device(self):
        """Device copy of the up mask, re-uploaded only after a
        failure-detector change (steady state: zero h2d bytes)."""
        if self._up_dev is None:
            self._up_dev = self._put(self.up, "up")
        return self._up_dev

    def update_members(self, sel: np.ndarray,
                       new_view: np.ndarray) -> np.ndarray:
        """Batched joint-consensus membership change for the selected
        ensembles — the update_members → transition dance
        (peer.erl:655-672, 751-774) as two device launches: install
        the joint view (old AND new quorums gate commits while it
        holds), then collapse to the new view once the joint quorum
        confirms.

        sel [E] bool — change these ensembles; new_view [E, M] bool —
        their new membership (rows of unselected ensembles ignored).
        Returns ``changed [E]``: ensembles whose membership finished
        changing during this call (including changes left in flight by
        an earlier call that completed now).  A change whose install
        or collapse could not commit yet (no leader, quorum missing)
        stays in flight and EVERY later call advances it — an
        all-False ``sel`` makes this a pure retry.  A new request for
        an ensemble whose previous change is still joint on device is
        QUEUED (latest request wins the queue slot) and becomes the
        next proposal once that change collapses — so ``changed[e]``
        means e's membership reached its in-flight view this call, not
        necessarily this call's ``new_view`` row; compare
        ``member_np`` when that distinction matters.  Ensembles whose
        leader left the membership (or was down) get an election
        folded into the next flush via the host mirrors, exactly like
        a reference leader shutting down after transitioning itself
        out (peer.erl:763-771).
        """
        jnp = self._jnp
        # reconfig reads the leader/membership mirrors and fetches
        # device results synchronously: settle in-flight launches
        # first (no-op in steady state)
        self._drain_launches()
        sel = np.asarray(sel, bool)
        if self.dynamic:
            sel = sel & self._live  # free rows have no membership
        new_view = np.asarray(new_view, bool)

        # Record the request.  An ensemble already joint on device
        # keeps its in-flight view until that collapses; the new
        # request waits in the queued tier.
        accept = sel & ~self._pending_mask
        defer = sel & self._pending_mask
        self._desired_view_np = np.where(accept[:, None], new_view,
                                         self._desired_view_np)
        self._desired_mask = self._desired_mask | accept
        self._queued_view_np = np.where(defer[:, None], new_view,
                                        self._queued_view_np)
        self._queued_mask = self._queued_mask | defer

        up_j = self._up_device()
        # Proposing is leader work (leading({update_members,_}),
        # peer.erl:655): only ensembles with a live leader install —
        # leaderless ones keep the change desired until a flush's
        # election gives them one.
        idx = np.arange(self.n_ens)
        leader = self.leader_np
        leader_ok = np.zeros((self.n_ens,), bool)
        has = leader >= 0
        leader_ok[has] = self.up[idx[has], leader[has]]
        propose = self._desired_mask & ~self._pending_mask & leader_ok
        dv_j = jnp.asarray(self._desired_view_np)
        # Same rollback discipline as _launch: an async device failure
        # surfaces at the np.asarray fetches below, after self.state
        # was replaced — restore the pre-launch state so the request
        # stays queued/desired and a later call retries cleanly.
        state_snapshot = self.state
        try:
            state, installed, collapsed1 = self.engine.reconfig_step(
                self.state, jnp.asarray(propose), dv_j, up_j)
            # Launch 2 only exists to collapse views launch 1 freshly
            # installed (launch 1's transition half already attempted
            # every leftover); skip the device round trip if nothing
            # could have installed.
            if propose.any():
                state, _, collapsed2 = self.engine.reconfig_step(
                    state, jnp.zeros((self.n_ens,), bool), dv_j, up_j)
                collapsed2 = np.asarray(collapsed2)
            else:
                collapsed2 = np.zeros((self.n_ens,), bool)
            self.state = state
            installed_now = propose & np.asarray(installed)
            collapsed1 = np.asarray(collapsed1)
        except BaseException:
            self.state = state_snapshot
            raise
        # Collapses land in EITHER launch: joint views left over from
        # earlier calls transition during launch 1 (its ~propose
        # half), fresh installs during launch 2.
        collapsed = collapsed1 | collapsed2

        # Host mirrors.  Installs move desired -> pending; a collapse
        # promotes its pending view to the live membership and lets a
        # queued next request advance to desired.
        self._pending_view_np = np.where(installed_now[:, None],
                                         self._desired_view_np,
                                         self._pending_view_np)
        self._pending_mask = self._pending_mask | installed_now
        self._desired_mask = self._desired_mask & ~installed_now
        changed = self._pending_mask & collapsed
        self.member_np = np.where(changed[:, None],
                                  self._pending_view_np, self.member_np)
        self._pending_mask = self._pending_mask & ~changed
        promote = self._queued_mask & changed
        self._desired_view_np = np.where(promote[:, None],
                                         self._queued_view_np,
                                         self._desired_view_np)
        self._desired_mask = self._desired_mask | promote
        self._queued_mask = self._queued_mask & ~promote

        # A leader no longer in (or not up in) its membership forces
        # an election on the next flush.
        still_ok = np.zeros((self.n_ens,), bool)
        still_ok[has] = self.member_np[idx[has], leader[has]] & \
            self.up[idx[has], leader[has]]
        dropped = changed & has & ~still_ok
        self.leader_np = np.where(dropped, -1, leader)
        self.lease_until[dropped] = 0.0
        self._notify_leader_changes(leader)
        # Durability: committed membership rows persist before the
        # caller observes `changed` (the fact-save-on-meaningful-change
        # discipline, peer.erl:2201-2228).
        if self._wal is not None and changed.any():
            self._wal.log([(("mem", int(e)),
                            (self._row_name.get(int(e)),
                             self.member_np[e].tolist()))
                           for e in np.nonzero(changed)[0]])
        return changed

    def stop(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        self.spans.between_end()
        self._gc_watch.remove()
        if self._resolve_pool is not None:
            self._resolve_pool.shutdown(wait=True)
            self._resolve_pool = None

    # -- sharded resolve/enqueue workers (ARCHITECTURE §16) ---------------

    def _shard_bounds(self, n: int):
        """Contiguous [lo, hi) chunk bounds partitioning ``n`` run
        descriptors (or taken columns) across the worker pool, or
        ``None`` when sharding is off or pointless — the caller then
        takes the untouched single-threaded path.  Chunks are
        descriptor-granular: a descriptor's lane run never splits, so
        each chunk touches a disjoint set of plane cells."""
        s = self._resolve_shards
        if s <= 1 or n <= 1:
            return None
        s = min(s, n)
        step = -(-n // s)
        return [(lo, min(lo + step, n)) for lo in range(0, n, step)]

    def _shard_map(self, fn, bounds) -> list:
        """Run ``fn(lo, hi)`` over every chunk, tail chunks on the
        pool and the first on the calling thread, and return results
        in chunk order (the order invariant every sharded site's
        concatenation relies on)."""
        if self._resolve_pool is None:
            self._resolve_pool = ThreadPoolExecutor(
                max_workers=self._resolve_shards,
                thread_name_prefix="retpu-resolve")
        futs = [self._resolve_pool.submit(fn, lo, hi)
                for lo, hi in bounds[1:]]
        out = [fn(*bounds[0])]
        out.extend(f.result() for f in futs)
        return out

    # -- checkpoint / resume -----------------------------------------------

    def save(self, path: Optional[str] = None) -> None:
        """Checkpoint the whole service: the device ``EngineState``
        via orbax plus the host mirrors (key→slot maps, payload store,
        membership pipeline) as one 4-copy CRC blob (save.erl's
        paranoid format; pickle is fine here — local disk is the same
        trust boundary as the reference's term_to_binary files).

        Crash atomicity: each save writes a fresh ``ckpt.<n>``
        directory and only then flips the CRC-protected ``CURRENT``
        pointer — a crash at any point leaves the previous checkpoint
        (engine+host pair, always from the same save) restorable.

        Queued ops are flushed (resolved) first so the persisted host
        mirrors carry no half-applied side effects (a saved key→slot
        allocation whose put never ran would leak the slot forever).
        Leases are not persisted — a restarted service must never
        trust a pre-crash lease (the reference restarts into probe; we
        restart lease-less and re-establish via the next quorum
        round).
        """
        import pickle

        from riak_ensemble_tpu import save as savelib
        from riak_ensemble_tpu.ops import checkpoint as ckpt

        if path is None:
            path = self.data_dir
        assert path is not None, "save() needs a path or data_dir"
        self._in_save = True
        try:
            while self._active:
                self.flush()
            # settle the launch pipeline too: an unresolved launch's
            # host bookkeeping (slot_handle, recycles) must land
            # before the mirrors persist
            self._drain_launches()
        finally:
            self._in_save = False
        os.makedirs(path, exist_ok=True)
        n = self._current_ckpt(path) + 1
        d = os.path.join(path, f"ckpt.{n}")
        ckpt.save(os.path.join(d, "engine"), self.state)
        from riak_ensemble_tpu.ops import hash as hashk
        host = {
            "shape": (self.n_ens, self.n_peers, self.n_slots),
            # Device-tree hash-format version: tree_leaf/tree_node are
            # persisted verbatim, so a restore under a different fold
            # must rebuild every tree (docs/MIGRATION.md).
            "hash_format": hashk.HASH_FORMAT,
            # ... and in which form: the levels of 128 nodes and more
            # as rows of `tree_rows` (engine.TREE_FORM).  An image
            # without the stamp holds every level flat in `tree_node`.
            "tree_form": eng.TREE_FORM,
            "key_slot": self.key_slot,
            # (mark, recycled) per ensemble: PR 43's form; before it
            # "free_slots" listed every free slot, and restore() still
            # reads that
            "free_marks": [(f.fresh, f.recycled)
                           for f in self.free_slots],
            "slot_gen": self.slot_gen,
            "slot_handle": self.slot_handle,
            "inline_slots": [sorted(s) for s in self._inline_slots],
            "recycle_pending": self._recycle_pending,
            "values": self.values,
            "free_handles": self._free_handles,
            "next_handle": self._next_handle,
            "leader": self.leader_np,
            "member": self.member_np,
            "desired_view": self._desired_view_np,
            "desired_mask": self._desired_mask,
            "queued_view": self._queued_view_np,
            "queued_mask": self._queued_mask,
            "pending_view": self._pending_view_np,
            "pending_mask": self._pending_mask,
            "up": self.up,
            "dynamic": self.dynamic,
            "live": self._live,
            "free_rows": self._free_rows,
            "ens_names": self._ens_names,
        }
        savelib.write(os.path.join(d, "host"),
                      pickle.dumps(host, protocol=4),
                      crash_class="ckpt")
        savelib.write(os.path.join(path, "CURRENT"), str(n).encode(),
                      crash_class="ckpt")
        # Old checkpoints are garbage once CURRENT moved (best effort).
        import shutil
        for name in os.listdir(path):
            if name.startswith("ckpt.") and name != f"ckpt.{n}":
                shutil.rmtree(os.path.join(path, name),
                              ignore_errors=True)
        # Checkpoint n subsumes every WAL record: start generation n
        # fresh and drop the old ones.  Restore replays ONLY the WAL
        # generation matching CURRENT, so a crash between the CURRENT
        # flip and this rotation leaves stale wal.<n-1> dirs that are
        # simply ignored (and cleaned by the next rotation).
        if self._wal is not None and path == self.data_dir:
            from riak_ensemble_tpu.parallel.wal import ServiceWAL
            self._wal = ServiceWAL.rotate(self.data_dir, n, self._wal,
                                          self.wal_sync)
            self._wal.spans = self.spans

    @staticmethod
    def _current_ckpt(path: str) -> int:
        from riak_ensemble_tpu import save as savelib

        raw = savelib.read(os.path.join(path, "CURRENT"))
        try:
            return int(raw.decode()) if raw else 0
        except ValueError:
            return 0

    @classmethod
    def restore(cls, runtime: Runtime, path: str, **kw
                ) -> "BatchedEnsembleService":
        """Bring a service back from :meth:`save`; ``kw`` forwards
        construction options (tick, config, engine, ...).

        When the directory is a ``data_dir`` (META present / WAL
        generations on disk), every write acked after the latest
        checkpoint is replayed from the WAL — including the case where
        the service crashed before its FIRST checkpoint (restore from
        META shape + WAL alone).  Callers restoring a durable service
        should pass ``data_dir=path`` in ``kw`` so logging continues.
        """
        import pickle

        from riak_ensemble_tpu import save as savelib
        from riak_ensemble_tpu.ops import checkpoint as ckpt
        from riak_ensemble_tpu.parallel.wal import ServiceWAL

        n = cls._current_ckpt(path)
        d = os.path.join(path, f"ckpt.{n}")
        raw = savelib.read(os.path.join(d, "host"))
        if raw is None:
            # No checkpoint: a durable service that crashed before its
            # first save() restores from META shape + WAL generation 0.
            meta_raw = savelib.read(os.path.join(path, "META"))
            if meta_raw is None:
                raise FileNotFoundError(
                    f"no service checkpoint at {path}")
            meta = pickle.loads(meta_raw)
            kw = cls._merge_dynamic(kw, bool(meta.get("dynamic",
                                                      False)))
            svc = cls(runtime, *meta["shape"], **kw)
            svc._replay_wal_from(path, 0, ServiceWAL)
            return svc
        host = pickle.loads(raw)
        n_ens, n_peers, n_slots = host["shape"]
        kw = cls._merge_dynamic(kw, bool(host.get("dynamic", False)))
        svc = cls(runtime, n_ens, n_peers, n_slots, **kw)
        # An image from before the tree's storage stamp holds the upper
        # levels flat in `tree_node`: its object planes and leaves are
        # restored, its upper levels are not read into this state.
        flat_trees = host.get("tree_form") != eng.TREE_FORM
        svc.state = ckpt.load(os.path.join(d, "engine"),
                              template=svc.state, flat_trees=flat_trees)
        # Hash-format migration: checkpoints persist tree_leaf/
        # tree_node/tree_rows verbatim, so an image written under a
        # different fold would fail _verify_path on EVERY slot (reads
        # of committed data returning failures cluster-wide), and one
        # written in another storage form has no upper levels this
        # state can take.  Rebuild every replica tree from the restored
        # object store before any WAL replay touches a subset of
        # slots.  Format history: riak_ensemble_tpu/ops/hash.py
        # HASH_FORMAT, ops/engine.py TREE_FORM; docs/MIGRATION.md.
        from riak_ensemble_tpu.ops import hash as hashk
        if flat_trees or host.get("hash_format", 2) != hashk.HASH_FORMAT:
            svc.state = svc.engine.rebuild_trees(
                svc.state,
                jnp.ones((svc.n_ens, svc.n_peers), bool))
        svc.key_slot = host["key_slot"]
        if "free_marks" in host:
            svc.free_slots = [_FreeSlots(fresh, recycled)
                              for fresh, recycled in host["free_marks"]]
        else:
            svc.free_slots = [_FreeSlots.from_list(slots)
                              for slots in host["free_slots"]]
        svc.slot_gen = host["slot_gen"]
        svc.slot_handle = host["slot_handle"]
        svc._inline_slots = [set(s) for s in host.get(
            "inline_slots", [[] for _ in range(n_ens)])]
        for row, slots_ in enumerate(svc._inline_slots):
            if slots_:  # keep the kernel's storage-class slab in step
                svc._inline_np[row, list(slots_)] = True
        svc._recycle_pending = host["recycle_pending"]
        # restored pending recycles must re-enter the dirty set or
        # the sparse drain would never revisit them (leaked slots)
        svc._recycle_dirty = {e for e, p in
                              enumerate(svc._recycle_pending) if p}
        svc.values = host["values"]
        svc._free_handles = host["free_handles"]
        svc._next_handle = host["next_handle"]
        svc.leader_np = np.asarray(host["leader"])
        svc.member_np = np.asarray(host["member"])
        svc._desired_view_np = np.asarray(host["desired_view"])
        svc._desired_mask = np.asarray(host["desired_mask"])
        svc._queued_view_np = np.asarray(host["queued_view"])
        svc._queued_mask = np.asarray(host["queued_mask"])
        svc._pending_view_np = np.asarray(host["pending_view"])
        svc._pending_mask = np.asarray(host["pending_mask"])
        svc.up = np.asarray(host["up"])
        if host.get("dynamic"):
            svc.dynamic = True
            svc._live = np.asarray(host["live"])
            svc._free_rows = list(host["free_rows"])
            svc._ens_names = dict(host["ens_names"])
            svc._row_name = {r: n_ for n_, r in svc._ens_names.items()}
        # lease_until stays zero: no pre-crash lease is ever trusted.
        svc._replay_wal_from(path, n, ServiceWAL)
        return svc

    @staticmethod
    def _merge_dynamic(kw: Dict[str, Any], persisted: bool
                       ) -> Dict[str, Any]:
        """The persisted lifecycle mode WINS at restore: a static
        image restored as dynamic would mark every row free (the
        first create would wipe restored data on device); a dynamic
        image restored as static would drop the name directory.  An
        explicitly mismatched caller flag fails loudly."""
        if "dynamic" in kw and bool(kw["dynamic"]) != persisted:
            raise ValueError(
                f"restore: service was persisted with "
                f"dynamic={persisted}; cannot restore with "
                f"dynamic={kw['dynamic']}")
        kw = dict(kw)
        kw["dynamic"] = persisted
        return kw

    def _replay_wal_from(self, path: str, gen: int, wal_cls) -> None:
        """Replay WAL generation ``gen`` under ``path`` if it exists
        (re-using the already-open handle when this service logs to
        the same generation)."""
        if not os.path.isdir(wal_cls.gen_path(path, gen)):
            return
        wal = (self._wal if self._wal is not None
               and self._wal.dir_path == wal_cls.gen_path(path, gen)
               else wal_cls.open_gen(path, gen))
        try:
            self._replay_wal(wal)
        finally:
            if wal is not self._wal:
                wal.close()

    def _replay_wal(self, wal) -> None:
        """Install every WAL record — the writes acked after the
        checkpoint this service was restored from — into the device
        state and host mirrors.

        Objects land on every replica at their committed (epoch, seq)
        (a fully-repaired configuration, what exchange would converge
        to); ballot epochs are raised to at least the newest installed
        object epoch so the restart's elections propose higher — any
        epoch skew left over is healed by the read path's stale-epoch
        rewrite (update_key, peer.erl:1564-1596), exactly like the
        reference restarting into probe with persisted facts.
        """
        jnp = self._jnp
        recs = wal.records()
        if not recs:
            return
        e_, m_, s_ = self.n_ens, self.n_peers, self.n_slots
        obj_epoch = np.asarray(self.state.obj_epoch).copy()
        obj_seq = np.asarray(self.state.obj_seq).copy()
        obj_val = np.asarray(self.state.obj_val).copy()
        epoch = np.asarray(self.state.epoch).copy()
        view_mask = np.asarray(self.state.view_mask).copy()
        #: (ens -> slot -> replayed owner key or None): replayed slots
        #: whose checkpoint-era key mapping must not survive if it
        #: disagrees (the slot was recycled to another key — or
        #: tombstoned — after the checkpoint)
        owners: Dict[int, Dict[int, Any]] = {}
        touched = False
        for key, value in recs:
            if key[0] == "mem":
                ens = key[1]
                name, row_l = value
                row = np.asarray(row_l, bool)
                self.member_np[ens] = row
                view_mask[ens] = False
                view_mask[ens, 0] = row
                # The replayed row is the newest COMMITTED membership;
                # any checkpoint-era in-flight pipeline state for this
                # ensemble predates it.
                self._pending_mask[ens] = False
                self._desired_mask[ens] = False
                self._queued_mask[ens] = False
                if self.dynamic:
                    # Lifecycle replay: a non-empty row is a live
                    # (possibly renamed) tenant; an empty row was
                    # destroyed.  Directory rebuilt below.
                    old_name = self._row_name.pop(ens, None)
                    if old_name is not None:
                        self._ens_names.pop(old_name, None)
                    if row.any() and name is not None:
                        self._ens_names[name] = ens
                        self._row_name[ens] = name
                    self._live[ens] = bool(row.any())
                    if not row.any():
                        self._reset_row_host(ens)
                touched = True
            elif key[0] == "kv":
                _, ens, slot = key
                key_obj, handle, oe, os_, payload, inline = value
                obj_epoch[ens, :, slot] = oe
                obj_seq[ens, :, slot] = os_
                obj_val[ens, :, slot] = handle
                touched = True
                if inline:
                    # Inline write: the int32 value IS the payload (no
                    # handle indirection).  With a key AND a live
                    # value it is a keyed device-native slot (a
                    # committed RMW) — restore the mapping and the
                    # inline marking.  A keyed inline TOMBSTONE (RMW
                    # computed 0) replays like a delete: the live
                    # leader recycled the slot, so retaining the
                    # mapping would leak the slot and shadow its next
                    # tenant.  Keyless records are bulk-array writes.
                    if key_obj is not None and handle:
                        self._inline_slots[ens].add(slot)
                        self._inline_np[ens, slot] = True
                        self._inline_value_np[ens, slot] = handle
                        self._inline_value_ok[ens, slot] = True
                        self._slot_vsn_np[ens, slot] = (oe, os_)
                        self._slot_vsn_ok[ens, slot] = True
                        self.slot_handle[ens][slot] = -1
                        self.key_slot[ens][key_obj] = slot
                        owners.setdefault(ens, {})[slot] = key_obj
                    else:
                        if key_obj is not None:
                            self._inline_slots[ens].discard(slot)
                            self._inline_np[ens, slot] = False
                            self._inline_value_ok[ens, slot] = False
                            self.slot_handle[ens].pop(slot, None)
                        owners.setdefault(ens, {})[slot] = None
                    continue
                self._inline_slots[ens].discard(slot)
                self._inline_np[ens, slot] = False
                self._inline_value_ok[ens, slot] = False
                self._slot_vsn_np[ens, slot] = (oe, os_)
                self._slot_vsn_ok[ens, slot] = True
                if handle:
                    self.values[handle] = payload
                    self._next_handle = max(self._next_handle,
                                            handle + 1)
                    self.slot_handle[ens][slot] = handle
                    if key_obj is not None:
                        self.key_slot[ens][key_obj] = slot
                    owners.setdefault(ens, {})[slot] = key_obj
                else:
                    # Tombstone: the slot holds nothing and stays
                    # reusable; any mapping is stale.
                    self.slot_handle[ens].pop(slot, None)
                    owners.setdefault(ens, {})[slot] = None
        if not touched:
            return
        # Checkpoint-era key mappings that disagree with a replayed
        # slot's owner are stale (the slot was recycled/tombstoned
        # after the checkpoint) — without this sweep two keys could
        # share a slot and reads of the dead key would serve the live
        # key's value.
        for ens, owner in owners.items():
            ks = self.key_slot[ens]
            for k in [k for k, s in ks.items()
                      if s in owner and owner[s] != k]:
                del ks[k]
        # Rebuild the free lists from the surviving mappings (mapped
        # slots are live; everything else — including tombstoned
        # slots — is allocatable).
        for ens in range(e_):
            self.free_slots[ens] = _FreeSlots.unused(
                s_, set(self.key_slot[ens].values()))
        # Ballot epochs >= newest installed object epoch per ensemble.
        epoch = np.maximum(epoch, obj_epoch.max(-1))
        state = self.state._replace(
            epoch=jnp.asarray(epoch),
            view_mask=jnp.asarray(view_mask),
            obj_epoch=jnp.asarray(obj_epoch),
            obj_seq=jnp.asarray(obj_seq),
            obj_val=jnp.asarray(obj_val))
        # Every replica's tree rebuilds over its replayed store (the
        # repair-by-rehash-from-data discipline).
        self.state = self.engine.rebuild_trees(
            state, jnp.ones((e_, m_), bool))
        # Replayed membership/leader state invalidates cached planes
        # and any pre-crash leader claim.
        self._up_dev = None
        self.leader_np = np.full((e_,), -1, dtype=np.int32)
        self.lease_until[:] = 0.0
        if self.dynamic:
            # Free pool = rows with no live tenant after replay.
            self._free_rows = [r for r in range(e_ - 1, -1, -1)
                               if not self._live[r]]

    # -- internals ---------------------------------------------------------

    def _alloc_handle(self) -> int:
        if self._free_handles:
            return self._free_handles.pop()
        h = self._next_handle
        assert h <= 0x7FFFFFFF, "2^31 live payloads cannot fit int32 handles"
        self._next_handle += 1
        return h

    def _alloc_handles(self, m: int) -> List[int]:
        """``m`` payload handles in ONE slab operation — the pooled
        tail (in the exact order ``m`` sequential pops would have
        yielded) then a fresh contiguous range — replacing ``m``
        per-key :meth:`_alloc_handle` calls on the vectorized keyed
        enqueue paths (docs/ARCHITECTURE.md §12, rung 1)."""
        free = self._free_handles
        t = min(m, len(free))
        out = free[len(free) - t:][::-1]
        if t:
            del free[len(free) - t:]
        if t < m:
            h0 = self._next_handle
            self._next_handle = h0 + (m - t)
            assert self._next_handle - 1 <= 0x7FFFFFFF, \
                "2^31 live payloads cannot fit int32 handles"
            out.extend(range(h0, self._next_handle))
        return out

    def _release_handle(self, handle: int) -> None:
        """Drop a payload and make its handle reusable (double release
        is a no-op — the handle returns to the pool once)."""
        if handle and self.values.pop(handle, None) is not None:
            self._free_handles.append(handle)

    def _slot_for(self, ens: int, key: Any, allocate: bool) -> Optional[int]:
        slot = self.key_slot[ens].get(key)
        if slot is not None or not allocate:
            return slot
        if not self.free_slots[ens]:
            return None
        slot = self.free_slots[ens].pop()
        self.key_slot[ens][key] = slot
        return slot

    def _drain_recycles(self) -> None:
        """Free slots whose recycle was deferred, once nothing queued
        references them and the conditions still hold: no later put
        bumped the generation, nothing live is committed, and the key
        still owns the slot."""
        if not self._recycle_dirty:
            return
        dirty, self._recycle_dirty = self._recycle_dirty, set()
        for e in dirty:
            pend = self._recycle_pending[e]
            if not pend:
                continue
            busy = set()
            for op in self.queues[e]:
                if isinstance(op, _PendingBatch):
                    busy.update(op.slot)
                else:
                    busy.add(op.slot)
            keep = []
            for key, slot, gen in pend:
                if slot in busy:
                    keep.append((key, slot, gen))
                elif self.slot_gen[e].get(slot, 0) == gen \
                        and self.slot_handle[e].get(slot, 0) == 0 \
                        and self.key_slot[e].get(key) == slot:
                    # (a committed device-native value holds the -1
                    # sentinel in slot_handle, so inline slots with
                    # live values never reach this branch)
                    del self.key_slot[e][key]
                    self._inline_slots[e].discard(slot)
                    self._inline_np[e, slot] = False
                    self.free_slots[e].append(slot)
                # else: the slot was re-used meanwhile — drop the stale
                # recycle request
            self._recycle_pending[e] = keep
            if keep:  # still blocked: revisit on a later drain
                self._recycle_dirty.add(e)

    def _push(self, ens: int, op) -> None:
        """Enqueue one pending entry (timestamped for the queue-wait
        latency component) and arm the flush trigger.  Write entries
        register in the per-slot pending-write index here — the ONE
        choke point every keyed write passes — and deregister when
        their entry resolves or fails; a slot with a nonzero count
        never serves a lease-protected fast read."""
        if op.kind != eng.OP_GET:
            if isinstance(op, _PendingBatch):
                # whole-batch note on the ensemble's counts
                pw = self._pending_writes[ens]
                for s in op.slot:
                    pw[s] = pw.get(s, 0) + 1
            else:
                self._note_write(ens, op.slot)
            if self._storage_degraded is not None:
                # read-only degradation (ARCHITECTURE §15): the WAL
                # cannot take the durability barrier, so no write may
                # queue toward an ack.  The entry fails through the
                # normal path (notes just taken are un-noted, handles
                # released, slots recycled); reads flow on.
                self._fail_entry(ens, op)
                return
            if self._obs and op.kind in (eng.OP_PUT, eng.OP_CAS):
                self._obs_note_put_bytes(
                    ens, op.handle if isinstance(op, _PendingBatch)
                    else (op.handle,))
        op.t_rx = self.t_rx
        op.t_enq = time.perf_counter()
        self.queues[ens].append(op)
        self._queue_rounds[ens] += op.n
        self._active.add(ens)
        self._note_arrival(ens)

    def _queue_recycle(self, ens: int, item: Tuple[Any, int, int]
                       ) -> None:
        self._recycle_pending[ens].append(item)
        self._recycle_dirty.add(ens)

    def _note_arrival(self, ens: int) -> None:
        """What starts a flush on a timer-driven service: work that
        has boarded, not the clock.  An enqueue with no look pending
        defers ONE look at the queue to the runtime's next turn (never
        reentrant inside an enqueue).  A look that finds new entries
        since the last one looks again next turn; after
        :attr:`QUIET_TURNS` turns in a row that brought nothing the
        front end has gone quiet and everything parsed meanwhile
        boards one flush (a lone op flushes at once, a burst whole,
        and what arrived while the previous flush held the loop rides
        the next one).  A queue at a full launch's depth (``max_k``)
        flushes at the next look whatever else arrives: batching is
        for amortization, not added latency.  The timer stays as the
        ceiling (:meth:`_on_tick`).  Caller-driven services
        (``tick=None``) control their own flush points."""
        if self.tick is None:
            return
        if self._queue_rounds[ens] >= self.max_k:
            self._queue_full = True
        if self._look_armed:
            self._arrived = True
        else:
            self._arm_look()

    def _arm_look(self) -> None:
        self._look_armed = True
        self._arrived = False
        self._quiet_turns = 0
        self.runtime.defer(self._look)

    def _look(self) -> None:
        if not self._active:    # a flush took everything meanwhile
            self._look_armed = self._queue_full = False
            return
        if not self._queue_full:
            if self._arrived:
                self._arrived = False
                self._quiet_turns = 0
            else:
                self._quiet_turns += 1
            if self._quiet_turns < self.QUIET_TURNS:
                self.runtime.defer(self._look)
                return
        self._look_armed = False
        self._loop_flush(arrival=not self._queue_full)

    def _schedule(self) -> None:
        if self.tick is None:
            return
        if self._obs and not self._gc_watch.installed:
            self._gc_watch.install()
            # a service dropped without stop() takes its hook along
            weakref.finalize(self, self._gc_watch.remove)
        self._timer = self.runtime.schedule(self.tick, self._on_tick)

    def _on_tick(self) -> None:
        """The timer: ``tick`` after the last loop flush ended.  The
        ceiling on what :meth:`_look` waits for (a stream that never
        goes quiet flushes here) and, with nothing queued, the idle
        flush: due retries, the pipeline's tail, elections, chained
        CAS halves."""
        self._loop_flush()

    def _loop_flush(self, arrival: bool = False) -> None:
        """A flush driven by the service's own loop (a look, the
        timer), after which the timer runs from this flush's end:
        what the loop thread does from there to the next one's start
        is the span ``between_flushes``, so every instant of that
        thread lies inside a named span."""
        if self._timer is None or self._in_loop_flush:
            # stopped; or re-entered from inside a loop flush: the
            # checkpoint writer of a WAL compaction (orbax) runs an
            # event loop of its own that turns this loop's callbacks,
            # and a flush started there would step, and donate, the
            # state being written.  What is queued boards when the
            # outer flush ends: it looks again.
            return
        self._timer.cancel()
        self._by_arrival = arrival
        self._in_loop_flush = True
        if self._obs:
            self.spans.between_end()
        try:
            self.flush()
        finally:
            self._by_arrival = self._in_loop_flush = False
            if self._timer is not None:  # not stopped meanwhile
                self._schedule()
                if self._obs:
                    self.spans.between_begin()
                # deeper than one launch takes: the rest is the same
                # work, not a trickle to wait out the tick
                self._queue_full = any(
                    self._queue_rounds[e] >= self.max_k
                    for e in self._active)
                if self._active and not self._look_armed:
                    self._arm_look()

    def _election_inputs(self) -> Tuple[np.ndarray, np.ndarray]:
        """Elect wherever there is no leader or the leader is down;
        candidate = lowest-index up member (the randomized-timeout
        winner in the reference; the host picks deterministically).
        Runs entirely on the host mirrors — no device round trip."""
        leader = self.leader_np
        leader_up = np.zeros((self.n_ens,), dtype=bool)
        has = leader >= 0
        leader_up[has] = self.up[np.nonzero(has)[0], leader[has]]
        cand_ok = self.up & self.member_np
        any_up = cand_ok.any(1)
        cand = np.where(any_up, cand_ok.argmax(1), -1).astype(np.int32)
        elect = (~has | ~leader_up) & any_up
        return elect, cand

    def _launch(self, kind: np.ndarray, slot: np.ndarray,
                val: np.ndarray, k: int, want_vsn: bool,
                exp_e: Optional[np.ndarray] = None,
                exp_s: Optional[np.ndarray] = None,
                entries: Optional[List[Tuple[int, List[Any]]]] = None,
                elect: Optional[np.ndarray] = None,
                cand: Optional[np.ndarray] = None,
                lease_ok: Optional[np.ndarray] = None):
        """One SYNCHRONOUS ``full_step`` launch + host bookkeeping —
        the two pipeline halves (:meth:`_launch_enqueue` /
        :meth:`_launch_resolve`) composed back to back, shared by
        :meth:`execute` (bulk) and the replica apply path.  Returns np
        result arrays (vsn None unless asked — it is the largest
        transfer and bulk callers rarely need it).

        ``entries`` is the flush's taken queue entries as
        (ensemble, ops) pairs over the ACTIVE ensembles (None for
        bulk execute); the base launch doesn't need them, but the
        replicated subclass (:mod:`..parallel.repgroup`) ships their
        key/payload metadata to its peer hosts.  ``elect``/``cand``/
        ``lease_ok`` may be passed precomputed so a wrapper that must
        OBSERVE the exact launch inputs (to replicate them) sees the
        same vectors this launch consumes — recomputing lease_ok from
        a later ``runtime.now`` could differ.
        """
        fl = self._launch_enqueue(kind, slot, val, k, want_vsn, exp_e,
                                  exp_s, entries, elect, cand, lease_ok)
        out = self._launch_resolve(fl)
        if self._obs:
            # synchronous launches (bulk execute, replica applies,
            # heartbeats) settle here — their obs record must not
            # depend on the pipelined settle path running
            with self.spans.span("obs", fl.rec):
                self._obs_flush_settled(fl)
        return out

    def _bind_step_fns(self) -> _StepFns:
        """The engine's two launch programs (step and result pack,
        one program each), resolved ONCE (the constructor calls this
        when the engine and ``_donate`` are set): the donated twins
        when the service donates.  The service trusts the engine it
        was given; a test that injects a fault wraps EVERY program of
        its engine (``testing.wrap_engine_steps``).  With obs on each
        reports its executable-cache misses (ARCHITECTURE §11) as
        ``step`` / ``step_sliced``: what a served flush launches, as
        the names always meant (the benchmark's warm-up lines group by
        them)."""
        e = self.engine
        twin = "_donate" if self._donate else ""
        sliced = getattr(e, "full_step_sliced_slab" + twin, None)
        if getattr(e, "n_ens_shards", 1) != (self._mesh_shards or 1):
            # 'ens' shards under a sharded 'peer' axis: the gathered
            # pack knows no per-shard blocks, so the full grid stays
            sliced = None
        fns = _StepFns(getattr(e, "full_step_slab" + twin), sliced)
        if not self._obs:
            return fns
        return _StepFns(*map(self._watched, ("step", "step_sliced"),
                             fns))

    def _watched(self, name: str, fn):
        """Memoized CompileWatch wrapper around a launch program."""
        if fn is None:
            return None
        w = self._compile_watch.get(name)
        if w is None or w.fn is not fn:
            w = self._compile_watch[name] = obs.CompileWatch(
                fn, name, on_miss=self._on_compile_event)
        return w

    def _on_compile_event(self, ev: Dict[str, Any]) -> None:
        """One watched program compiled: count it by phase (warmup
        coverage vs a serve-time first-use leak — the latter is the
        dispatch-p99 bug the counter exists to catch) and keep the
        event in the service-local log the flight dumps carry."""
        phase = "warmup" if self._in_warmup else "serve"
        self._c_compile.labels(phase).inc()
        self._c_compile_ms.labels(phase).inc(ev.get("compile_ms", 0.0))
        self._compile_log.append({**ev, "phase": phase})

    def _launch_enqueue(self, kind: np.ndarray, slot: np.ndarray,
                        val: np.ndarray, k: int, want_vsn: bool,
                        exp_e: Optional[np.ndarray] = None,
                        exp_s: Optional[np.ndarray] = None,
                        entries: Optional[List[Tuple[int,
                                                     List[Any]]]] = None,
                        elect: Optional[np.ndarray] = None,
                        cand: Optional[np.ndarray] = None,
                        lease_ok: Optional[np.ndarray] = None
                        ) -> _InFlightLaunch:
        """ENQUEUE half of a launch: ONE upload (everything the step
        reads from the host, the pack-gather's index rows included, as
        one op slab) and ONE program call (the fused step and the pack
        of its results, ``(state, slab, up) -> (state, flat)``), then
        the packed vector's d2h transfer is started — all asynchronous
        — and the in-flight record returned.  The flush record says so:
        ``uploads`` (1; 2 on the launch after the failure detector
        changed ``up``) and ``calls`` (device programs called: 1).
        No host read of device data happens here, so while batch N's
        packed vector is in flight the host is free to enqueue batch
        N+1 against the new (not yet materialized) ``EngineState`` —
        the overlap :meth:`flush` exploits at ``pipeline_depth`` > 1.
        """
        del entries  # base launch doesn't need them (subclass hook)
        if elect is None:
            elect, cand = self._election_inputs()
        now = self.runtime.now
        if lease_ok is None:
            lease_ok = self.lease_until > now

        # the flush's record: flush() opened it for its pack stage;
        # a bare launch (execute, a replica's apply) opens its own
        rec, self._enq_rec = self._enq_rec, None
        if rec is None:
            rec = self.spans.begin()
        h2d = self.spans.span("h2d", rec).begin()
        fns = self._fns
        # Active-column compaction, two strengths (the payload and
        # the grid both decouple from E), ONE rule read per ens-shard
        # (one chip is the case n_sh = 1; a shard-wise mesh buckets at
        # the busiest shard's pow2 width a_loc, so the bucketing, the
        # gathers and the packed d2h payload all stay shard-local: no
        # replicated index constraint, no all-gather):
        # - SLICED launch (``_slices``: the engine has a sliced
        #   program, a shard holds e_loc >= SLICE_MIN_E rows, a_loc at
        #   or under e_loc/4): the fused step itself runs on the gathered
        #   [K, a_loc] grid of each shard: compute, HBM traffic,
        #   op-plane h2d and the packed result all scale with the
        #   live working set.  The active set must include every
        #   electing column (their rounds run inside the same
        #   launch).  On a mesh each shard's columns fill its own
        #   block of the slab (engine "The op slab") and its index
        #   row is LOCAL: the gather runs inside shard_map.
        # - PACK-GATHER (small/mid grids, or a_loc above e_loc/4):
        #   the step keeps the full grid; only the packed result
        #   gathers down to [K, a_loc] (the d2h cut alone), in the
        #   same program, by the index row of each shard's own block
        #   of the slab.
        # Buckets ride the pow2 A ladder (mirroring the K ladder's
        # compile-reuse discipline).
        active = idx_rows = shard_active = at = None
        a_width = cols_max = 0
        sliced = False
        n_sh = self._mesh_shards or 1
        e_loc = self.n_ens // n_sh
        cols = np.flatnonzero(
            (np.asarray(kind) != eng.OP_NOOP).any(axis=0)
            | np.asarray(elect, bool)) if self._compact and k else ()
        if len(cols):
            per_shard, a_loc = shard_active_columns(
                cols, self.n_ens, n_sh, A_BUCKET_MIN)
            cols_max = max(p.size for p in per_shard)
            if a_loc < e_loc:
                active = cols.astype(np.int32)
                a_width = a_loc
                sliced = self._slices(a_loc)
                # the slab's index row, a block a shard, each shard's
                # LOCAL columns at its block's front.  Sliced: blocks
                # of a_loc, pads aim OUT OF RANGE (the local row
                # count) so the state scatter drops them; the pack
                # gather reads the first a_loc columns of full-width
                # blocks and pads with column 0 (ignored by the host
                # unpack)
                idx_rows = (np.full((n_sh, a_loc), e_loc, np.int32)
                            if sliced
                            else np.zeros((n_sh, e_loc), np.int32))
                for si, p in enumerate(per_shard):
                    idx_rows[si, :p.size] = p
                if self._mesh_shards:
                    shard_active = per_shard
                    if sliced:  # each column's place in its block
                        at = np.flatnonzero(idx_rows.ravel() < e_loc)
        # EVERY input upload belongs to the h2d mark — an upload
        # inlined into the step call would bill its (synchronous)
        # transfer to 'dispatch' and make the async-enqueue number
        # read milliseconds of jitter it doesn't have (review r3 #4).
        # The up mask uploads only when the failure detector actually
        # changed it (sliced launches gather it on device).
        uploads = int(self._up_dev is None)
        up_j = self._up_device()
        # ONE upload: everything the program reads from the host as
        # one op slab (engine.pack_op_slab has the row layout; the
        # program takes it apart), put where the step wants it; the
        # index rows are a slab row.
        slab_np = eng.pack_op_slab(
            n_sh * a_width if sliced else self.n_ens, k, elect, cand,
            lease_ok, (kind, slot, val, exp_e, exp_s),
            active if sliced else None,
            None if idx_rows is None else idx_rows.ravel(), at)
        with self.spans.span("h2d_put", rec):   # inside h2d
            slab_j = self._put(slab_np, "slab")
        uploads += 1
        rec["uploads"] = uploads
        rec["calls"] = 1
        rec["sliced"] = int(sliced)
        rec["arrival"] = int(self._by_arrival)
        # the launch's shape: the pow2 width it packed at (0: the
        # full grid ran and nothing was gathered), its real columns,
        # the busiest shard's, and the shards (n_sh blocks of ``a``)
        n_cols = len(cols)
        rec["a"] = a_width
        rec["cols"] = n_cols
        rec["cols_max"] = cols_max
        rec["shards"] = n_sh
        self.launches_sliced += sliced
        self.launches_unsliced += not sliced
        self.launch_uploads += uploads
        self.launch_calls += rec["calls"]
        if a_width:
            self.launches_gathered += not sliced
            self._busiest_sum += cols_max / n_cols
            self._pad_sum += 1.0 - n_cols / (n_sh * a_width)
        h2d.end()

        # Rollback snapshots: under async dispatch a device failure
        # surfaces at the d2h fetch in the RESOLVE half, after
        # self.state was replaced with the failed computation's
        # poisoned arrays; without rolling back, every later launch
        # would consume the poison and fail forever.  (JAX arrays are
        # immutable, so the state snapshot stays valid — unless the
        # step DONATED them; lease_until is mutated in place, so it
        # needs a copy.)
        state_snapshot = self.state
        leader_snapshot = self.leader_np
        lease_snapshot = self.lease_until.copy()
        dispatch = self.spans.span("dispatch", rec).begin()
        try:
            # the ONE program call (inside dispatch; what is left of
            # it is the d2h copy's start).  A sliced launch's result
            # planes are ALREADY A-width (its quorum plane E wide, as
            # on every launch); a pack-gather's width is the
            # full-width program's static ``gather``
            with self.spans.span("dispatch_step", rec):
                if sliced:
                    self.state, flat = fns.sliced_slab(
                        self.state, slab_j, up_j, want_vsn=want_vsn)
                else:
                    self.state, flat = fns.slab(
                        self.state, slab_j, up_j, want_vsn=want_vsn,
                        gather=a_width)
            # Kick the packed vector's d2h transfer off NOW — the
            # resolve half (possibly a full flush later) only blocks
            # on its completion, so the transfer rides under the next
            # batch's device step instead of serializing after it.
            start = getattr(flat, "copy_to_host_async", None)
            if start is not None:
                start()
        except BaseException:
            self._rollback_launch(state_snapshot, leader_snapshot,
                                  lease_snapshot)
            raise
        finally:
            dispatch.end()
        return _InFlightLaunch(
            flat=flat, rec=rec, k=k, want_vsn=want_vsn,
            kind_np=np.asarray(kind),
            elect=elect, cand=cand, now=now,
            state_snapshot=state_snapshot,
            leader_snapshot=leader_snapshot,
            lease_snapshot=lease_snapshot,
            active=active, a_width=a_width, sliced=sliced,
            n_shards=self._mesh_shards, shard_active=shard_active,
            op_slot_np=np.asarray(slot),
            flush_id=obs.next_flush_id() if self._obs else 0,
            t_join=rec["starts"]["h2d"])

    def _slices(self, a_loc: int) -> bool:
        """THE rule for a sliced launch, read per ens-shard (one chip
        is one shard): the engine has a sliced program, a shard holds
        at least ``SLICE_MIN_E`` rows, and the launch's columns,
        bucketed at the busiest shard's pow2 width ``a_loc``, are at
        most a quarter of them.  The launch and ``warmup`` both ask
        here."""
        e_loc = self.n_ens // (self._mesh_shards or 1)
        return (self._fns.sliced_slab is not None
                and e_loc >= SLICE_MIN_E and a_loc * 4 <= e_loc)

    def _put(self, x: np.ndarray, what: str):
        """ONE host→device transfer of a step operand, committed where
        the step reads it: a mesh engine names the sharding
        (``slab_sharding``, ``up_sharding``: its step's own in_specs,
        so each chip receives its columns once and the dispatch places
        nothing); a single-device engine takes the default device."""
        return jax.device_put(
            x, getattr(self.engine, what + "_sharding", None))

    def _fetch_packed(self, fl: _InFlightLaunch) -> np.ndarray:
        """Block until the launch's packed result is on the host (the
        ONE device→host transfer per launch).  Isolated as a seam so
        tests can inject d2h latency deterministically."""
        return np.asarray(fl.flat)

    def _rollback_launch(self, state_snapshot, leader_snapshot,
                         lease_snapshot) -> None:
        """Restore the pre-launch device state + host mirrors after a
        failed launch.  The host mirrors roll back with the state: a
        mirror claiming a leader the restored device state doesn't
        have would suppress re-election forever.  A DONATED launch has
        no rollback — the snapshot's buffers were consumed by the
        failed program — so the state stays poisoned (restart or
        restore() recovers); surfaced as a trace event."""
        arr = state_snapshot.epoch
        deleted = getattr(arr, "is_deleted", None)
        if self._donate and deleted is not None and deleted():
            self._emit("svc_state_poisoned",
                       {"reason": "donated launch failed; no rollback "
                                  "snapshot survives buffer donation"})
            return
        self.state = state_snapshot
        self.leader_np = leader_snapshot
        self.lease_until = lease_snapshot

    def _launch_resolve(self, fl: _InFlightLaunch,
                        wait_key: str = "device_d2h"):
        """RESOLVE half of a launch: block on the packed transfer,
        unpack, apply the host mirrors (leader/lease), run the
        corruption→exchange sweep, and finish the launch's latency
        record.  Under the pipelined flush this runs one round LATE —
        after batch N+1's enqueue — so the block recorded under
        ``wait_key`` (``inflight_wait`` there) is only the part of the
        device round + transfer the host failed to overlap.

        Corruption deferral rides the same structure: the ``corrupt``
        planes are the only host inspection of a round's integrity
        gate, and they are read HERE — one round late in the pipelined
        flush — with the exchange dispatched onto the CURRENT device
        state chain (which already includes batch N+1's step).  The
        exchange therefore lands before batch N+1's results are
        resolved: a flagged ensemble is repaired before its next
        result is acked, the semantics the in-round sweep provided.
        """
        rec = fl.rec
        span = self.spans.span
        try:
            with span(wait_key, rec):
                flat = self._fetch_packed(fl)
            unpack = span("unpack", rec).begin()
            e, m = self.n_ens, self.n_peers
            # Native single-pass unpack (docs/ARCHITECTURE.md §12):
            # one C traversal scatters the packed payload straight
            # into full-width planes; election-only launches (k == 0)
            # and layout surprises fall back to the Python oracle.
            # per-flush attribution of the resolve half's arm: the
            # derived resolve_native/resolve_fallback marks accumulate
            # every native-eligible stage (unpack here; the mirror
            # scatter and WAL encode add theirs), excluded from the
            # additive total like 'enqueue'.  The span is named for
            # the arm that ran once that is known.
            planes8 = None
            with span("resolve_fallback", rec,
                      label="svc.unpack_kernel") as arm:
                if fl.n_shards:
                    # shard-wise mesh payload: per-shard blocks,
                    # Python unpack per block (the native kernel walks
                    # the single-block layout; this path trades it
                    # for zero cross-device gathers on the pack side)
                    planes8 = unpack_results_sharded(
                        flat, e, m, fl.k, fl.want_vsn,
                        fl.n_shards, shard_active=fl.shard_active,
                        a_width=fl.a_width, sliced=fl.sliced)
                    native_arm = False
                else:
                    if self._native_resolve is not None and fl.k:
                        planes8 = self._native_resolve.unpack(
                            flat, e, m, fl.k, fl.want_vsn,
                            fl.active, fl.a_width, fl.sliced)
                    native_arm = planes8 is not None
                if planes8 is None:
                    planes8 = unpack_results(
                        flat, e, m, fl.k, fl.want_vsn,
                        active=fl.active, a_width=fl.a_width,
                        sliced=fl.sliced)
                if native_arm:
                    arm.name = "resolve_native"
            (won_np, quorum_ok, corrupt_np, committed, get_ok, found,
             value, vsn) = planes8
            if native_arm:
                self.native_resolve_flushes += 1
            else:
                self.fallback_resolve_flushes += 1
            # Compaction observability: the actual d2h bytes vs the
            # full-width [K, E] layout's, and the packed-grid
            # occupancy (skewed/partial load drives this toward 0).
            self.payload_bytes += int(flat.nbytes)
            self.payload_bytes_full_width += packed_nbytes(
                e, m, fl.k, fl.want_vsn)
            # shard-wise launches pack a_width columns PER SHARD, so
            # the effective packed width is a_width * n_shards
            self._occ_sum += (fl.a_width * max(fl.n_shards, 1) / e
                              if fl.active is not None else 1.0)
            self._occ_launches += 1
            if self._obs:
                fl.payload_nbytes = int(flat.nbytes)
                self._launches_total += 1
                # device-round share: the rows this launch actually
                # carried (the compacted active set, or every live
                # row for a full-width launch)
                if fl.active is not None:
                    self.tenant_rounds[fl.active] += 1
                else:
                    self.tenant_rounds[self._live] += 1
            corrupt = corrupt_np if fl.k else None

            # Host mirror: a won election installed our candidate.
            self.leader_np = np.where(won_np, fl.cand, self.leader_np)
            #: the round's quorum confirmations, kept on the launch
            #: record for subclass resolve hooks (the replication
            #: group's delta frames ship them so replica lanes renew
            #: leases exactly as a re-executed launch would)
            fl.quorum_np = quorum_ok

            # Lease renewal: a won election, or any round in which the
            # leader confirmed its epoch with a quorum — the
            # leader_tick renewal (peer.erl:1092-1095), which covers
            # read-only leaders (reads ride the epoch-check round),
            # not just committers — and idle ones: quorum_ok is E wide
            # on every launch, sliced or not, so a launch renews the
            # ensembles it carried nothing for as well.
            renew = won_np | quorum_ok
            self.lease_until[renew] = fl.now + self.config.lease()
            if fl.active is not None:
                self.lease_renewals_idle += int(
                    np.count_nonzero(renew)
                    - np.count_nonzero(renew[fl.active]))

            # Device-detected integrity failures -> anti-entropy
            # exchange for the affected ensembles (the tree_corrupted
            # -> repair -> exchange flow, peer.erl:1276-1277 +
            # riak_ensemble_exchange): divergent slots re-adopt the
            # newest hash-valid copy and the replicas' trees are
            # rebuilt; unreplaceable (all-copies-bad) slots stay
            # flagged rather than being blessed.
            has_corrupt = corrupt is not None and corrupt.any()
            unpack.end()   # 'unpack' leaves the exchange out
            if has_corrupt:
                jnp = self._jnp
                exchange = span("exchange", rec).begin()
                self.corruptions += int(corrupt.sum())
                run = corrupt.any(1)
                # flagged rows fall off the read fast path until the
                # exchange syncs them — a known-corrupt row's reads
                # must keep taking the device round (its integrity
                # gate vets every access)
                self._corrupt_rows |= run
                self.state, diverged, synced = self.engine.exchange_step(
                    self.state, jnp.asarray(run), self._up_device())
                synced_np = np.asarray(synced)
                self.repairs += int(
                    np.asarray(diverged)[synced_np].sum())
                # rows the exchange synced re-admit fast reads; any
                # residual damage re-flags on its next device access
                self._corrupt_rows &= ~(run & synced_np)
                self._emit("svc_exchange", {"ensembles": int(run.sum())})
                exchange.end()
            self.flushes += 1
            self.flush_triggers[
                "arrival" if rec.get("arrival")
                else "tick" if fl.k else "idle"] += 1
        except BaseException:
            self._rollback_launch(fl.state_snapshot, fl.leader_snapshot,
                                  fl.lease_snapshot)
            raise
        # A won election bumped the row's ballot epoch: the next
        # device access of each object re-versions it (update_key,
        # peer.erl:1564-1596), so the fast path's vsn mirror is stale
        # for the whole row — drop it (want_vsn reads take the device
        # round, whose resolve re-mirrors the rewritten versions;
        # plain value reads stay fast, the rewrite never changes
        # values).  Only on a SUCCESSFUL launch: the except path
        # rolled the election back.
        if won_np.any():
            self._slot_vsn_ok[won_np] = False
            # election-churn mirror (the health verb's signal): one
            # count per won election per row, successful launches only
            self.elections_np[won_np] += 1
        # Leader changes (won elections) notify watchers only on a
        # SUCCESSFUL launch — the except path above rolled the mirror
        # back, and a watcher told of a rolled-back leader would act
        # on state the device never kept.
        self._notify_leader_changes(fl.leader_snapshot)
        self._emit("svc_launch", {
            "k": fl.k, "elections": int(fl.elect.sum()),
            "won": int(won_np.sum()),
            "corrupt_replicas": (int(corrupt.sum())
                                 if corrupt is not None else 0),
        })
        # Launch-side latency record; the flush settle augments the
        # same dict with queue_wait/wal/resolve (bulk execute()
        # callers get the launch components alone).  'enqueue' is a
        # DERIVED mark (h2d + dispatch — the whole enqueue half) kept
        # out of the total sum.
        rec["k"] = fl.k
        rec["enqueue"] = rec.get("h2d", 0.0) + rec.get("dispatch", 0.0)
        rec["total"] = sum(v for c, v in rec.items()
                           if c not in DERIVED_MARKS)
        self.lat_records.append(rec)
        return committed, get_ok, found, value, vsn

    def _emit(self, kind: str, payload: Any) -> None:
        """Feed the runtime's tracing hook (utils.trace.Tracer) when
        one is installed; free otherwise."""
        tr = getattr(self.runtime, "trace", None)
        if tr is not None:
            tr(kind, payload)

    def scrub(self) -> Dict[str, int]:
        """Full anti-entropy sweep — the maintenance form of the
        corruption-triggered exchange (riak_ensemble_exchange +
        peer_tree:do_repair): verify EVERY replica's tree (the BFS
        verify, synctree.erl:549-571), run the exchange over
        ensembles holding damage (newest hash-valid copy wins,
        adopters rebuild), and report what was found/healed.  Reads
        only touch accessed slots, so damage on cold slots is
        invisible to the data path until a scrub or access — the
        operator cadence knob the reference gets from AAE timers."""
        jnp = self._jnp
        # settle in-flight launches: the sweep's damage/heal counters
        # must not race a pending round's own repair bookkeeping
        self._drain_launches()
        self._scrubbed_at_flush = self.flushes
        node_bad, leaf_bad = self.engine.verify_trees(self.state)
        bad = np.asarray(node_bad) | np.asarray(leaf_bad)    # [E, M]
        found = int(bad.sum())
        if not found:
            return {"replicas_damaged": 0, "replicas_healed": 0,
                    "ensembles_swept": 0}
        run = bad.any(1)
        self.corruptions += found
        state_snapshot = self.state
        try:
            self.state, diverged, synced = self.engine.exchange_step(
                self.state, jnp.asarray(run), self._up_device())
            node_bad2, leaf_bad2 = self.engine.verify_trees(self.state)
            still = (np.asarray(node_bad2)
                     | np.asarray(leaf_bad2)) & bad
        except BaseException:
            self.state = state_snapshot
            raise
        healed = found - int(still.sum())
        self.repairs += int(
            np.asarray(diverged)[np.asarray(synced)].sum())
        # the sweep's verdict updates the read fast path's corrupt
        # flags: swept rows with residual damage stay off the fast
        # path, healed ones re-admit it
        self._corrupt_rows = np.where(run, still.any(1),
                                      self._corrupt_rows)
        self._emit("svc_scrub", {"damaged": found, "healed": healed})
        return {"replicas_damaged": found, "replicas_healed": healed,
                "ensembles_swept": int(run.sum())}

    def latency_breakdown(self) -> Dict[str, Dict[str, float]]:
        """Per-component launch-latency percentiles (ms) over the
        recent flushes: where a commit's latency actually goes —
        queue_wait (enqueue → launch), h2d (input build + upload),
        dispatch (async enqueue of the step/pack/transfer), then
        EITHER device_d2h (depth-1: device math + packed result
        fetch, serial) OR inflight_wait (pipelined: the part of the
        device round + transfer the overlap failed to hide — this
        shrinking below device_d2h is the pipeline working), unpack,
        exchange (corruption-triggered), wal (durability barrier),
        resolve (future fan-out).  'enqueue' is a derived mark
        (h2d + dispatch — the whole enqueue half) excluded from the
        'total' sum, as are 'resolve_native'/'resolve_fallback' (the
        resolve half's per-arm share — unpack + mirror scatter + WAL
        encode attributed to whichever arm ran, ARCHITECTURE §12)
        and 'enqueue_native'/'enqueue_fallback' (the slab enqueue
        path's lane-build + op-plane-pack share, already inside
        queue_wait, attributed to whichever pack arm ran — §12b).  ``svc_compaction`` (the deferred WAL fold, a
        rare EVENT rather than a per-launch component) is reported
        over its own occurrences only — averaging it into 1000+
        launch records would both hide the pause (p99 = 0) and
        inject zero samples into every launch component.  This is
        what makes the BASELINE p99 target analyzable before and
        after a platform change (review r2)."""
        recs = list(self.lat_records)
        out: Dict[str, Dict[str, float]] = {}
        events = [r for r in recs if "svc_compaction" in r]
        recs = [r for r in recs if "svc_compaction" not in r]
        if events:
            vals = np.asarray([r["svc_compaction"]
                               for r in events]) * 1e3
            out["svc_compaction"] = {
                "p50_ms": float(np.percentile(vals, 50)),
                "p99_ms": float(np.percentile(vals, 99)),
                "mean_ms": float(vals.mean())}
        if not recs:
            return out
        comps = sorted({c for r in recs for c in r
                        if c not in ("k", "starts", "clock", "reqs")})
        for c in comps:
            vals = np.asarray([r.get(c, 0.0) for r in recs]) * 1e3
            out[c] = {"p50_ms": float(np.percentile(vals, 50)),
                      "p99_ms": float(np.percentile(vals, 99)),
                      "mean_ms": float(vals.mean())}
        return out

    def stats(self) -> Dict[str, Any]:
        """Observability snapshot (the get_info/count_quorum analog
        for the scale path)."""
        wal_stats = (self._wal.stats() if self._wal is not None
                     else None)
        return {
            "flushes": self.flushes,
            "ops_served": self.ops_served,
            "svc_backpressure": dict(self.svc_backpressure),
            "corruptions_detected": self.corruptions,
            "replicas_repaired": self.repairs,
            "live_payloads": len(self.values),
            "ensembles_with_leader": int((self.leader_np >= 0).sum()),
            "membership_changes_in_flight": int(
                (self._desired_mask | self._pending_mask
                 | self._queued_mask).sum()),
            "queued_ops": sum(self._queue_rounds),
            "pipeline_depth": self.pipeline_depth,
            "launches_in_flight": len(self._inflight_launches),
            # whether launches donate the state buffers (platform-
            # dependent default: a failed donated launch has no
            # rollback, see _rollback_launch)
            "donate": self._donate,
            "rmw_conflicts": self.rmw_conflicts,
            "rmw_device_fastpath": self.rmw_device_fastpath,
            "rmw_enqueue_coalesced": self.rmw_enqueue_coalesced,
            # lease-protected read fast path: mirror-served reads vs
            # device-round fallbacks (by reason), and what fraction of
            # live ensembles hold a margin-valid lease right now
            "read_fastpath_hits": self.read_fastpath_hits,
            "read_fastpath_misses": self.read_fastpath_misses,
            "read_fastpath_miss_reasons": dict(
                self.read_fastpath_miss_reasons),
            "lease_valid_fraction": self._lease_valid_fraction(),
            # active-column compaction: packed d2h bytes actually
            # moved vs the full-width [K, E] layout, and the mean
            # packed-grid occupancy (a_width / E; 1.0 = uncompacted)
            "payload_bytes": self.payload_bytes,
            "payload_bytes_full_width": self.payload_bytes_full_width,
            "grid_occupancy": (self._occ_sum / self._occ_launches
                               if self._occ_launches else 1.0),
            "launches_sliced": self.launches_sliced,
            "launches_unsliced": self.launches_unsliced,
            "lease_renewals_idle": self.lease_renewals_idle,
            "launch": {"launches": (self.launches_sliced
                                    + self.launches_unsliced),
                       "uploads": self.launch_uploads,
                       "calls": self.launch_calls},
            "flush_triggers": dict(self.flush_triggers),
            # WAL-compaction pauses (deferred off the hot path; the
            # svc_compaction latency mark carries the same numbers
            # into latency_breakdown())
            "svc_compaction": {
                "count": self.wal_compactions,
                "last_ms": round(self.wal_compaction_ms_last, 3),
                "total_ms": round(self.wal_compaction_ms_total, 3),
            },
            # storage-recovery plane (ARCHITECTURE §15): the WAL
            # store's corruption-handling evidence plus the
            # degradation decision — same payload as health().  One
            # stats() call feeds both keys: each takes the WAL lock,
            # which the flush path holds across the fsync barrier
            "storage": self._storage_health_section(wal_stats),
            "wal": wal_stats,
            # observability plane (docs/ARCHITECTURE.md §11): the
            # full registry exports via the svcnode `metrics` verb;
            # stats() carries the headline plus per-tenant
            # attribution so existing stats consumers see both
            "obs_enabled": self._obs,
            # what the front end moved over the wire, and the
            # collector's pauses since the flush timer started
            # (obs.spans; ARCHITECTURE §11)
            "frontend": self._frontend_stats(),
            "gc": self._gc_watch.stats(),
            "startup": dict(self.startup),
            "slots": self._slot_stats(),
            "tree": dict(self._tree_stats),
            "flight_anomalies": self.flight.anomalies,
            "tenants": self.tenant_stats(top=8),
            # native single-pass resolve kernel (ARCHITECTURE §12):
            # which arm each settled flush's resolve half ran on —
            # the per-flush split rides the resolve_native /
            # resolve_fallback latency marks
            "native_resolve": {
                "enabled": self._native_resolve is not None,
                "flushes": self.native_resolve_flushes,
                "fallback_flushes": self.fallback_resolve_flushes,
            },
            # slab enqueue half (ARCHITECTURE §12): which pack arm
            # each flush's op planes were scattered by (C++ kernel vs
            # numpy lanes; both zero when RETPU_NATIVE_ENQUEUE=0
            # pinned the per-entry oracle pack), and the completion
            # slab's one-wake-per-flush ledger
            "native_enqueue": {
                "slab_path": self._enq_slab,
                "kernel": self._native_enqueue is not None,
                "flushes": self.native_enqueue_flushes,
                "fallback_flushes": self.fallback_enqueue_flushes,
            },
            "completion_slab": {
                "wakes": self.completion_wakes,
                "rows": self.completion_rows,
            },
            # sharded resolve/enqueue workers (ARCHITECTURE §16):
            # pool width and how many flushes actually chunked
            "resolve_shards": {
                "shards": self._resolve_shards,
                "sharded_flushes": self.sharded_flushes,
            },
            **self._mesh_stats(),
        }

    def _slot_stats(self) -> Dict[str, int]:
        """``stats()["slots"]``: the keyspace (every slot of every
        ensemble), the slots keys hold, the ones handed back and
        waiting to go out again, and the bytes of the host's per-slot
        structures: the five mirror slabs (zero pages until a slot is
        written) and what the free-slot marks and the queued-write
        counts hold, which follows what is in use."""
        sized = sys.getsizeof
        fresh = recycled = 0
        held = sum(a.nbytes for a in (
            self._slot_vsn_np, self._slot_vsn_ok, self._inline_value_np,
            self._inline_value_ok, self._inline_np))
        for f in self.free_slots:
            fresh += f.fresh
            recycled += len(f.recycled)
            held += sized(f.recycled)
        held += sum(map(sized, self._pending_writes))
        held += sum(map(sized, self._queued_handle_writes))
        keyspace = self.n_ens * self.n_slots
        return {"keyspace": keyspace,
                "in_use": keyspace - fresh - recycled,
                "recycled": recycled, "host_bytes": held}

    def _frontend_stats(self) -> Dict[str, Any]:
        """The front end's counters and, where a server stamps its
        loop's polls, ``rx_hold_ms``: over the last frames read, how
        long the loop had not looked at its sockets by then.  The
        operator's loop-lag figure: it bounds the wait in the socket
        that ``retpu_op_latency_ms`` cannot see (under open-loop
        arrivals a request waits half of it on average)."""
        fe = dict(self.frontend)
        holds = self.rx_holds
        n = 0 if holds is None else min(fe["frames_in"], len(holds))
        if n:
            ms = holds[:n] * 1e3
            p50, p95 = np.percentile(ms, (50, 95))
            fe["rx_hold_ms"] = {"p50": float(p50), "p95": float(p95),
                                "max": float(ms.max())}
        return fe

    def _mesh_stats(self) -> Dict[str, Any]:
        """``stats()["mesh"]``, on an 'ens'-sharded engine only: how
        its launches ran and, over those that packed at a width, how
        skewed the shards were (the busiest shard's share of the
        columns: 1 / shards when even) and how much of the blocks
        was padding."""
        if not self._mesh_shards:
            return {}
        shaped = self.launches_sliced + self.launches_gathered
        return {"mesh": {
            "shards": self._mesh_shards,
            "e_loc": self.n_ens // self._mesh_shards,
            "state_bytes_per_shard": max(
                self.startup["state_bytes_per_device"]),
            "launches_sliced": self.launches_sliced,
            "launches_pack_gathered": self.launches_gathered,
            "launches_full_grid": (self.launches_unsliced
                                   - self.launches_gathered),
            "busiest_shard_share": (self._busiest_sum / shaped
                                    if shaped else None),
            "pad_share": self._pad_sum / shaped if shaped else None,
        }}

    def _lease_valid_fraction(self) -> float:
        """Fraction of live ensembles whose lease is margin-valid on
        the monotonic clock right now — the fast path's best-case
        coverage (stats observability for the read router)."""
        live = self._live
        if not live.any():
            return 0.0
        horizon = self.runtime.now + self._read_margin
        return float((self.lease_until[live] > horizon).mean())

    def health(self, ens: Optional[int] = None) -> Dict[str, Any]:
        """Ensemble-health snapshot — the scale path's analog of the
        reference's cluster status / ``get_info`` surface, sourced
        ENTIRELY from host mirrors (leader_np, lease_until, the
        corruption flags, the committed-vsn slab): zero device
        rounds, callable on a loaded service at verb rate.

        ``ens=None`` answers the service level: per-row aggregates
        (leadered/electing/corrupt/lease-valid row counts, total
        election churn) plus the service depths a capacity dashboard
        needs — WAL record depth, queued device rounds, launches in
        flight, per-slot pending writes, live payload handles.

        ``ens=N`` answers one row: leader, margin-valid lease (and
        raw remaining seconds), election churn, corrupt flag, queue
        depth, pending writes, live keys, and the row's COMMITTED
        epoch/seq high-water (the max (epoch, seq) over the
        committed-vsn mirror — the host-visible analog of the ballot
        epoch; rows whose mirror was invalidated by a fresh election
        report the pre-election watermark until their next device
        read re-mirrors).

        Everything is plain ints/floats/strings — the svcnode
        ``("health",)`` verb ships it through the restricted wire
        codec verbatim."""
        now = self.runtime.now
        horizon = now + self._read_margin
        if ens is not None:
            assert 0 <= ens < self.n_ens, f"bad ensemble {ens}"
            vsn_row = self._slot_vsn_np[ens]
            ok_row = self._slot_vsn_ok[ens]
            if ok_row.any():
                epochs = vsn_row[ok_row]
                hi = epochs[np.lexsort((epochs[:, 1], epochs[:, 0]))][-1]
                committed = (int(hi[0]), int(hi[1]))
            else:
                committed = (0, 0)
            return {
                "ens": int(ens),
                "live": bool(self._live[ens]),
                "leader": int(self.leader_np[ens]),
                "members": [bool(b) for b in self.member_np[ens]],
                "lease_valid": bool(self.lease_until[ens] > horizon),
                "lease_remaining_s": round(
                    max(0.0, float(self.lease_until[ens]) - now), 6),
                "elections": int(self.elections_np[ens]),
                "corrupt": bool(self._corrupt_rows[ens]),
                "committed_epoch": committed[0],
                "committed_seq": committed[1],
                "queued_ops": int(self._queue_rounds[ens]),
                "pending_writes": len(self._pending_writes[ens]),
                "live_keys": len(self.key_slot[ens]),
                "tenant": self.tenant_label(ens),
            }
        live = self._live
        elect, _cand = self._election_inputs()
        fp = faults.active_plan()
        out = {
            "schema": "retpu-health-v1",
            "n_ens": int(self.n_ens),
            "live_ensembles": int(live.sum()),
            "ensembles_with_leader": int(
                ((self.leader_np >= 0) & live).sum()),
            "electing": int((elect & live).sum()),
            "lease_valid_fraction": round(
                self._lease_valid_fraction(), 4),
            "corrupt_rows": int(self._corrupt_rows.sum()),
            "elections_total": int(self.elections_np.sum()),
            "wal_records": (int(self._wal.count)
                            if self._wal is not None else None),
            "queued_ops": int(sum(self._queue_rounds)),
            "launches_in_flight": len(self._inflight_launches),
            "pending_writes": sum(map(len, self._pending_writes)),
            "live_payloads": len(self.values),
            "flushes": int(self.flushes),
            "ops_served": int(self.ops_served),
            "donate": self._donate,
            # the runtime controller's section (ARCHITECTURE §14):
            # always present — `enabled: false` on a stock service —
            # so a dashboard's queries keep their shape when the
            # controller arms, the fault-gauge discipline
            "controller": self.controller.health_section(),
            # storage-recovery plane (ARCHITECTURE §15): always
            # present (degraded: false on a healthy disk) — same
            # constant-shape discipline as the controller section
            "storage": self._storage_health_section(),
        }
        if fp is not None:
            # active fault-injection plan (docs/ARCHITECTURE.md §13):
            # surfaced so an operator reading the health verb can
            # distinguish a running nemesis (injected drops / RTT /
            # fsync delay) from a real outage.  Absent entirely when
            # no plan is armed — a clean box shows a clean verb.
            out["injected"] = fp.describe()
        return out

    def _storage_health_section(self, wal_stats: Optional[Dict[str,
                                Any]] = None) -> Dict[str, Any]:
        """The health verb's storage-recovery section (§15): the
        degradation decision (or its absence), WAL error counts and
        the store's corruption-handling evidence — constant shape so
        dashboard queries survive a disk incident arming it.
        ``wal_stats`` lets a caller that already paid the WAL-lock
        round (stats()) pass it in; the default path reads the
        LOCK-FREE evidence counters so a health scrape never blocks
        behind a flush's fsync barrier."""
        if wal_stats is None:
            wal_stats = (self._wal.evidence()
                         if self._wal is not None else {})
        wal_stats = wal_stats or {}
        return {
            "degraded": self._storage_degraded is not None,
            "mode": (self._storage_degraded or {}).get("mode"),
            "reason": (self._storage_degraded or {}).get("errno"),
            "at_flush": (self._storage_degraded or {}).get("at_flush"),
            "wal_errors": int(self.wal_storage_errors),
            "wal_quarantines": int(wal_stats.get("quarantines", 0)),
            "wal_truncations": int(wal_stats.get("truncations", 0)),
        }

    # -- observability plane (docs/ARCHITECTURE.md §11) ---------------------

    def _register_obs_metrics(self) -> None:
        """Hook this service's counters into its metrics registry.

        Everything the hot path already maintains as a plain
        attribute exports through a COLLECTOR (read at export time —
        no double-writing on the flush path); only genuinely new
        instruments (the flush histogram, per-tenant planes) record
        directly."""
        self.obs_registry.collect(self._obs_service_collect)
        self.obs_registry.collect(self._obs_tenant_collect)
        self.obs_registry.collect(self._obs_fault_collect)
        self.obs_registry.collect(self.controller.collect)
        # live backend memory (device plane telemetry): reads the
        # default device's allocator stats at export time; backends
        # without memory_stats (CPU) export None/NaN rather than 0
        self.obs_registry.gauge(
            "retpu_backend_mem_bytes",
            "bytes in use on the default jax device (NaN when the "
            "backend reports no memory stats)", fn=_backend_mem_bytes)
        self.obs_registry.collect(self._obs_device_mem_collect)

    def _obs_device_mem_collect(self) -> Dict[str, Any]:
        """Per-device memory family (mesh plane telemetry): one
        sample per local device, so an 'ens'-shard imbalance shows up
        as a device-labeled outlier instead of averaging away in the
        default-device gauge."""
        return {
            "retpu_backend_mem_bytes_per_device": obs.registry.family(
                "gauge",
                "bytes in use per local jax device (NaN when the "
                "backend reports no memory stats)",
                _backend_mem_bytes_per_device(), label="device"),
        }

    def _obs_fault_collect(self) -> Dict[str, Any]:
        """Injected-fault gauges (docs/ARCHITECTURE.md §13): always
        registered — zeros on a clean box — so a dashboard's queries
        don't change shape when a nemesis arms, and a nonzero
        ``retpu_fault_active`` is the one-glance nemesis flag."""
        def fam(typ, help, val):
            return obs.registry.family(typ, help, {None: val})

        fp = faults.plan()
        c = (fp.counters() if fp is not None else {})
        return {
            "retpu_fault_active": fam(
                "gauge", "1 while a fault-injection plan with live "
                "rules is armed in this process",
                int(fp is not None and fp.active())),
            "retpu_fault_dropped_frames_total": fam(
                "counter", "frames blackholed by injected "
                "directional drops", c.get("dropped_frames", 0)),
            "retpu_fault_delayed_frames_total": fam(
                "counter", "frames delayed by injected per-link RTT",
                c.get("delayed_frames", 0)),
            "retpu_fault_delay_injected_ms_total": fam(
                "counter", "total injected per-link delay",
                c.get("delay_injected_ms", 0.0)),
            "retpu_fault_reordered_frames_total": fam(
                "counter", "adjacent frame pairs swapped by injected "
                "reorder", c.get("reordered_frames", 0)),
            "retpu_fault_fsync_delays_total": fam(
                "counter", "WAL fsync barriers delayed by injection",
                c.get("fsync_delays", 0)),
            "retpu_fault_fsync_delay_injected_ms_total": fam(
                "counter", "total injected fsync delay",
                c.get("fsync_delay_injected_ms", 0.0)),
            # storage fault plane + recovery evidence (§15): same
            # always-registered discipline — zeros on a clean box
            "retpu_fault_storage_errors_total": fam(
                "counter", "injected EIO/ENOSPC storage errors "
                "raised on write/fsync paths",
                c.get("storage_errors_injected", 0)),
            "retpu_fault_torn_writes_total": fam(
                "counter", "injected torn (truncated mid-record) "
                "writes", c.get("torn_writes_injected", 0)),
            "retpu_fault_corrupt_reads_total": fam(
                "counter", "injected bit-flip read corruptions",
                c.get("corrupt_reads_injected", 0)),
            "retpu_recovery_degraded": fam(
                "gauge", "1 while the service is storage-degraded "
                "(read-only / stepped down after WAL EIO/ENOSPC)",
                int(self._storage_degraded is not None)),
            "retpu_recovery_wal_errors_total": fam(
                "counter", "WAL OSErrors observed on the ack path",
                self.wal_storage_errors),
            "retpu_recovery_wal_quarantined_total": fam(
                "counter", "unreplayable WAL logs quarantined aside "
                "(.corrupt.<n>)",
                (self._wal.evidence().get("quarantines", 0)
                 if self._wal is not None else 0)),
        }

    def _flight_extras(self) -> Dict[str, Any]:
        """Flight-dump sections beyond the flush ring (schema v2):
        the per-op SLO tail (slowest acked entries with their stage
        splits), the recent compile events, and — while a fault plan
        is armed — the injected-fault state (so an anomaly dump
        captured mid-nemesis indicts the nemesis, not the code)."""
        fp = faults.active_plan()
        return {
            "slow_ops": (self._slo.slowest(5)
                         if self._slo is not None else []),
            "compile_events": list(self._compile_log),
            "injected_faults": (fp.describe()
                                if fp is not None else {}),
            # the controller's newest journaled decisions: an anomaly
            # captured while the control loop was moving knobs shows
            # WHICH knob moved, and why, next to the flush it hit
            "controller_decisions": self.controller.flight_section(),
        }

    def _obs_service_collect(self) -> Dict[str, Any]:
        def fam(typ, help, val):
            # the collector-family shape lives in obs.registry.family
            return obs.registry.family(typ, help, {None: val})

        occ = (self._occ_sum / self._occ_launches
               if self._occ_launches else 1.0)
        return {
            "retpu_flushes_total": fam(
                "counter", "settled device launches", self.flushes),
            "retpu_ops_served_total": fam(
                "counter", "client ops resolved (fast reads included)",
                self.ops_served),
            "retpu_corruptions_total": fam(
                "counter", "integrity-gate detections",
                self.corruptions),
            "retpu_repairs_total": fam(
                "counter", "replicas the exchange healed",
                self.repairs),
            "retpu_read_fastpath_hits_total": fam(
                "counter", "mirror-served leased reads",
                self.read_fastpath_hits),
            "retpu_read_fastpath_misses_total": fam(
                "counter", "fast-path fallbacks to the device round",
                self.read_fastpath_misses),
            "retpu_svc_backpressure_total": obs.registry.family(
                "counter", "front-end backpressure events (inflight-"
                "cap stalls, slow-reader write-buffer drops)",
                dict(self.svc_backpressure), label="kind"),
            "retpu_frontend_frames_total": obs.registry.family(
                "counter", "wire frames the front end decoded (in) "
                "and wrote (out)",
                {"in": self.frontend["frames_in"],
                 "out": self.frontend["frames_out"]}, label="dir"),
            "retpu_frontend_bytes_total": obs.registry.family(
                "counter", "wire bytes, headers included, the front "
                "end read (in) and wrote (out)",
                {"in": self.frontend["bytes_in"],
                 "out": self.frontend["bytes_out"]}, label="dir"),
            "retpu_gc_pause_seconds_total": obs.registry.family(
                "counter", "seconds inside pauses of the Python "
                "collector since the flush timer started",
                {str(g): v for g, v in
                 enumerate(self._gc_watch.seconds)},
                label="generation"),
            "retpu_rmw_conflicts_total": fam(
                "counter", "host-path kmodify CAS retries",
                self.rmw_conflicts),
            "retpu_rmw_device_fastpath_total": fam(
                "counter", "single-round device RMW commits",
                self.rmw_device_fastpath),
            "retpu_payload_bytes_total": fam(
                "counter", "packed d2h bytes actually fetched",
                self.payload_bytes),
            "retpu_payload_bytes_full_width_total": fam(
                "counter", "what the full-width [K, E] layout would "
                "have moved", self.payload_bytes_full_width),
            "retpu_wal_compactions_total": fam(
                "counter", "WAL folds into a fresh checkpoint",
                self.wal_compactions),
            "retpu_flight_anomalies_total": fam(
                "counter", "flight-recorder trigger firings (flush "
                "> 5x rolling p50)", self.flight.anomalies),
            "retpu_queued_ops": fam(
                "gauge", "device rounds currently queued",
                sum(self._queue_rounds[e] for e in self._active)),
            "retpu_launches_in_flight": fam(
                "gauge", "dispatched-but-unresolved launches",
                len(self._inflight_launches)),
            "retpu_lease_valid_fraction": fam(
                "gauge", "live rows holding a margin-valid lease",
                round(self._lease_valid_fraction(), 4)),
            "retpu_lease_renewals_idle_total": fam(
                "counter", "lease renewals launches made outside "
                "their active set (rows they carried no operation "
                "for)", self.lease_renewals_idle),
            "retpu_grid_occupancy": fam(
                "gauge", "mean packed-grid occupancy (a_width / E)",
                round(occ, 4)),
            "retpu_live_payloads": fam(
                "gauge", "host payload-store entries",
                len(self.values)),
            "retpu_ensembles_with_leader": fam(
                "gauge", "rows with a live leader",
                int((self.leader_np >= 0).sum())),
            # process-global by construction (the span store is):
            # every service in the process exports the same counts,
            # which is what a scrape of any one of them should see
            "retpu_span_misses_total": obs.registry.family(
                "counter", "span-store lookups that missed, by "
                "reason (evicted = rolled off the bounded ring; "
                "unknown = this process never recorded the fid)",
                dict(obs.SPANS.misses), label="reason"),
        }

    # -- fleet-scope surfaces (docs/ARCHITECTURE.md §11) --------------------

    def _fleet_self_label(self) -> str:
        """This service's host label in fleet answers: the group
        identity peers dial it by when one exists, else
        hostname:pid — stable within a process, distinct across the
        fleet."""
        addr = getattr(self, "self_addr", None)
        if addr:
            return f"{addr[0]}:{addr[1]}"
        import socket as _socket
        return f"{_socket.gethostname()}:{os.getpid()}"

    def fleet_metrics(self, fmt: Optional[str] = None):
        """Fleet metrics: every host's registry under ``host``
        labels.  On a standalone service the fleet is this host
        alone; :class:`~riak_ensemble_tpu.parallel.repgroup.
        ReplicatedService` overrides the pull to cover its links.
        ``fmt="prometheus"`` answers ONE merged scrape document."""
        label = self._fleet_self_label()
        if fmt == "prometheus":
            return obs.merge_prometheus(
                {label: self.obs_registry.render_prometheus()})
        return {"schema": "retpu-fleet-metrics-v1",
                "hosts": {label: self.obs_registry.snapshot()},
                "clock": {}}

    def fleet_health(self) -> Dict[str, Any]:
        """Fleet health: every host's ``health()`` section keyed by
        host label (standalone: this host alone)."""
        return {"schema": "retpu-fleet-health-v1",
                "hosts": {self._fleet_self_label(): self.health()},
                "clock": {}}

    def fleet_timeline(self, flush_id: int) -> Dict[str, Any]:
        """Clock-aligned cross-host ``obs.timeline``: on a standalone
        service the local record on a trivial axis (in-process
        replica lanes share the store, so their roles ride along);
        the replicated override pulls subprocess replicas' records
        and maps them through the per-link offsets."""
        tl = obs.SPANS.timeline(int(flush_id))
        sides = {} if (not tl or tl.get("miss")) else \
            {r: s for r, s in tl.items() if r != "flush_id"}
        out = obs.align_timeline(int(flush_id), sides, {},
                                 self._fleet_self_label())
        if tl and tl.get("miss"):
            out["miss"] = tl["miss"]
        return out

    def set_tenant_label(self, ens: int, label: Any) -> None:
        """Name a row for per-tenant attribution (dynamic rows are
        already labeled by their create_ensemble name)."""
        self._tenant_labels[int(ens)] = label

    def tenant_label(self, ens: int) -> str:
        lbl = self._tenant_labels.get(ens)
        if lbl is None:
            lbl = self._row_name.get(ens)
        return str(lbl) if lbl is not None else f"ens{ens}"

    def _drop_tenant_series(self, row: int) -> None:
        """Drop ``row``'s labeled registry series on recycle — unless
        another row still serves under the same label.  A tenant
        spanning several ensemble rows is ONE tenant in every export
        (``_tenant_groups``), so recycling one of its rows must not
        reset the survivors' live counters."""
        lbl = self.tenant_label(row)
        for e in set(self._tenant_labels) | set(self._row_name):
            if e != row and 0 <= e < self.n_ens \
                    and self.tenant_label(e) == lbl:
                return
        self.obs_registry.remove_labeled(lbl)

    def _tenant_groups(self, top: int = 16
                       ) -> "List[Tuple[str, List[int]]]":
        """Label -> rows worth exporting: every NAMED tenant plus the
        top-N rows by op count, capped at 64 LABELS ranked by ops (a
        10k-row service exports dozens of tenants, not 10k — and the
        cap keeps the noisy ones, not the lowest row indices).  Rows
        sharing a label group together: a tenant spanning several
        ensemble rows is ONE tenant in every export."""
        rows = set(self._tenant_labels) | set(self._row_name)
        if top and self.tenant_ops.any():
            hot = np.argsort(self.tenant_ops)[-top:]
            rows.update(int(e) for e in hot if self.tenant_ops[e] > 0)
        groups: Dict[str, List[int]] = {}
        for e in rows:
            if 0 <= e < self.n_ens:
                groups.setdefault(self.tenant_label(e), []).append(e)
        ranked = sorted(
            groups.items(),
            key=lambda kv: (-int(self.tenant_ops[kv[1]].sum()),
                            kv[0]))
        return [(lbl, sorted(rr)) for lbl, rr in ranked[:64]]

    def _tenant_pctl(self, rows: List[int], q: float) -> float:
        """Bucket-resolution quantile (ms) over a tenant's (possibly
        multi-row) op-latency histogram — obs.Histogram's estimator."""
        counts = self._tenant_lat[rows].sum(axis=0)
        return obs.registry.percentile_from_counts(
            counts.tolist(), self._lat_edges, q)

    def tenant_stats(self, top: int = 16) -> Dict[str, Dict[str, Any]]:
        """Per-tenant attribution snapshot: ops, committed rounds,
        put payload bytes, device-round share (fraction of this
        service's launches the tenant's rows were active in), and
        p50/p99 op latency — the noisy-neighbor evidence surface."""
        out: Dict[str, Dict[str, Any]] = {}
        launches = max(self._launches_total, 1)
        for lbl, rows in self._tenant_groups(top):
            out[lbl] = {
                "rows": rows,
                "ops": int(self.tenant_ops[rows].sum()),
                "commits": int(self.tenant_commits[rows].sum()),
                "put_bytes": int(self.tenant_bytes[rows].sum()),
                "device_rounds": int(self.tenant_rounds[rows].sum()),
                "device_round_share": round(
                    float(self.tenant_rounds[rows].sum()) / launches,
                    4),
                "p50_ms": round(self._tenant_pctl(rows, 0.5), 3),
                "p99_ms": round(self._tenant_pctl(rows, 0.99), 3),
            }
        return out

    def _obs_tenant_collect(self) -> Dict[str, Any]:
        groups = self._tenant_groups()
        launches = max(self._launches_total, 1)

        def fam(typ, help, per_group):
            return obs.registry.family(
                typ, help, {lbl: per_group(rows)
                            for lbl, rows in groups})

        return {
            "retpu_tenant_ops_total": fam(
                "counter", "keyed + fast-read ops per tenant",
                lambda rr: int(self.tenant_ops[rr].sum())),
            "retpu_tenant_commits_total": fam(
                "counter", "committed device rounds per tenant",
                lambda rr: int(self.tenant_commits[rr].sum())),
            "retpu_tenant_put_bytes_total": fam(
                "counter", "put payload bytes per tenant",
                lambda rr: int(self.tenant_bytes[rr].sum())),
            "retpu_tenant_device_rounds_total": fam(
                "counter", "launches the tenant's rows were active in",
                lambda rr: int(self.tenant_rounds[rr].sum())),
            "retpu_tenant_device_round_share": fam(
                "gauge", "fraction of this service's launches",
                lambda rr: round(
                    float(self.tenant_rounds[rr].sum()) / launches,
                    4)),
            "retpu_tenant_op_p50_ms": fam(
                "gauge", "tenant op latency p50 (each entry charged "
                "its measured time from its first stamp to its ack, "
                "per-op SLO ring)",
                lambda rr: round(self._tenant_pctl(rr, 0.5), 3)),
            "retpu_tenant_op_p99_ms": fam(
                "gauge", "tenant op latency p99 (each entry charged "
                "its measured time from its first stamp to its ack, "
                "per-op SLO ring)",
                lambda rr: round(self._tenant_pctl(rr, 0.99), 3)),
        }

    def _obs_account_taken(self, taken, committed,
                           t_settle: Optional[float] = None,
                           fid: int = 0,
                           t_join: float = 0.0,
                           ent_meta=None) -> None:
        """Per-tenant + per-op attribution for one resolved flush:
        ONE pass over the taken entries (C-level attrgetter per
        entry) feeding vectorized folds — O(|entries|) appends, not
        per-op Python dicts.

        The per-op SLO ring records each entry's measured latency
        from its first stamp to its ack (``t_rx`` where the op came
        through the front end, else the API call's submit; an
        entry's ops share its stamps — batch granularity within an
        entry, entry granularity within the flush; the
        join/settle/ack times are the flush's, shared); the fold
        targets are the per-kind ``retpu_op_latency_ms`` histogram
        and the per-tenant ``[E, B]`` plane, and the ring's rows
        carry the flush id (``OpSloRing.rows_of(fid)`` resolves a
        tail op to queue wait vs flush vs ack beside
        ``obs.timeline(fid)``).  Leased fast reads contribute their
        own samples from the hit hook.  ``t_settle`` is when the
        flush's outcome was known (on a replicated leader: AFTER the
        host quorum — ack stamps land after quorum settle by
        construction)."""
        now = time.perf_counter()
        rows: List[int] = [e for e, _ops in taken]
        if not rows:
            return
        if ent_meta is not None:
            # stamps sourced from the ENQUEUE-time pending slab (the
            # slab path collects the per-entry columns while the
            # flush walk builds its op lanes) — the settle fold never
            # re-walks entries whose futures are completion-slab rows
            kk_l, enss, nn_l, tr_l, ts_l, te_l = ent_meta
        else:
            cols: List[Tuple] = []  # _OP_SLO_FIELDS of each entry
            enss = []
            fields = _OP_SLO_FIELDS
            for e, ops in taken:
                cols.extend(map(fields, ops))
                enss.extend([e] * len(ops))
            if cols:
                kk_l, nn_l, tr_l, ts_l, te_l = zip(*cols)
            else:
                kk_l = nn_l = tr_l = ts_l = te_l = ()
        rr = np.asarray(rows, np.int64)
        if committed is not None:
            np.add.at(self.tenant_commits, rr,
                      committed[:, rr].sum(axis=0).astype(np.int64))
        if not enss:
            return
        w = np.asarray(nn_l, np.int64)
        ee = np.asarray(enss, np.int64)
        np.add.at(self.tenant_ops, ee, w)
        if self._slo is None:
            return
        folded = self._slo.record_flush(
            kk_l, enss, nn_l, tr_l, ts_l, te_l, fid,
            t_join if t_join else (t_settle or now),
            t_settle if t_settle else now, now)
        if folded is None:
            return
        _phys, lat_ms = folded
        bidx = np.searchsorted(self._lat_edges, lat_ms)
        # per-tenant: each entry's ops charged the entry's own
        # measured latency (replacing PR 6's flush-oldest upper
        # bound with the per-entry value)
        np.add.at(self._tenant_lat, (ee, bidx), w)
        # per-kind registry histogram: fold bucket counts per kind
        # present in this flush (<= 5 kinds, B buckets — bounded)
        kk = np.asarray(kk_l, np.int16)
        nb = len(self._lat_edges) + 1
        for kcode in np.unique(kk):
            sel = kk == kcode
            child = self._h_op.labels(
                obs.opslo.KIND_NAMES[int(kcode)])
            counts = np.bincount(bidx[sel], weights=w[sel],
                                 minlength=nb)
            ccounts = child.counts
            for bi in np.nonzero(counts)[0]:
                ccounts[bi] += int(counts[bi])
            child.count += int(w[sel].sum())
            child.sum += float((lat_ms[sel] * w[sel]).sum())

    def _obs_note_put_bytes(self, ens: int, handles) -> None:
        """Attribute queued put payload bytes to the row's tenant
        (handles may include 0 = tombstone and -1-style sentinels —
        both length-less)."""
        values = self.values
        total = 0
        for h in handles:
            if h and h > 0:
                v = values.get(h)
                try:
                    total += len(v)
                except TypeError:
                    pass
        if total:
            self.tenant_bytes[ens] += total

    def _obs_flush_settled(self, fl: _InFlightLaunch) -> None:
        """Feed one settled launch into the obs plane: the flush
        histogram, the leader span record (joined with replica spans
        by flush_id), and the flight-recorder ring + anomaly
        trigger."""
        rec = fl.rec
        total = rec.get("total", 0.0)
        self._h_flush.record(total * 1e3)
        # (re-)attach the dump extras provider: tests replace the
        # recorder to lower its trigger thresholds, and the per-op
        # tail + compile-event sections must survive that
        self.flight.extras = self._flight_extras
        obs.SPANS.record(
            fl.flush_id, "leader",
            # META_FIELDS (incl. the derived 'enqueue' = h2d +
            # dispatch) are identity/derived, not spans — including
            # them would double-count a summed timeline
            [(c, v) for c, v in rec.items()
             if c not in obs.flightrec.META_FIELDS],
            k=fl.k, a_width=fl.a_width, total_s=total,
            payload_bytes=fl.payload_nbytes,
            # where each span began (perf_counter): trace_export
            # lays the timeline out by these
            starts=rec.get("starts"),
            # the fleet-timeline alignment anchor: this role's spans
            # lay out sequentially ENDING here (record time on THIS
            # process's monotonic clock — the clock the per-link
            # offset estimates map between)
            t_mono=time.monotonic())
        ring = {
            "flush_id": fl.flush_id, "t": time.time(),
            "k": fl.k, "payload_bytes": fl.payload_nbytes,
            "queued_rounds": sum(self._queue_rounds[e]
                                 for e in self._active),
            "in_flight": len(self._inflight_launches),
            **rec}
        # (the dump's name for the record's ``a``)
        ring["a_width"] = ring.pop("a", fl.a_width)
        self.flight.record(ring)
        if self._autotune:
            # the runtime controller's cadence: one counted flush,
            # one integer compare; evaluations run every
            # RETPU_AUTOTUNE_CADENCE settled flushes (§14).  Inside
            # the obs-gated settle hook on purpose: the controller
            # CONSUMES the obs plane, so RETPU_OBS=0 starves it too.
            self.controller.tick(fl.flush_id)

    # -- (K, A)-grid pre-compile --------------------------------------------

    def _a_ladder(self) -> List[Optional[int]]:
        """Active-column bucket widths the launch path can pack at:
        full width (None) plus, with compaction on, the pow2 ladder
        from A_BUCKET_MIN strictly below E.  Shard-wise mesh engines
        bucket PER SHARD, so their ladder runs strictly below the
        LOCAL width E/n_shards instead."""
        ladder: List[Optional[int]] = [None]
        if self._compact:
            top = self.n_ens
            if self._mesh_shards:
                top = self.n_ens // self._mesh_shards
            b = A_BUCKET_MIN
            while b < top:
                ladder.append(b)
                b <<= 1
        return ladder

    def warmup(self, buckets=None) -> None:
        """Pre-compile the launch path's XLA programs on a THROWAWAY
        state (never the live one: a warmup launch that mutated
        ``self.state`` outside the real op stream would corrupt it —
        and on a replication-group replica, diverge it from its
        group).

        Flush depths are pow2-bucketed and a launch's ONE program
        (the step and the pack of its results) is keyed by the
        active-column bucket AND the static want_vsn flag, so the grid
        is (K, A) × {vsn, no-vsn}: K in {0, 1, 2, ..., max_k} × A in
        the pow2 ladder below E plus full width; per bucket the
        program the launch would call there (the sliced one where its
        rule slices, else the full-grid one gathering its pack at A).
        The small-K buckets double as the get-only / read-miss flush
        shapes the read fast path's fallback produces, and the
        version-less programs (full width only) are what execute /
        execute_async dispatch — all pre-compiled here so none of
        them pays a first-use compile inside a client's latency
        window.  Without this, the first
        flush at each new (K, A) bucket pays its compile in the
        middle of serving — the dispatch p99 blip the steady-state
        breakdown can't show.

        ``buckets``: optional iterable of ``(k, a_width)`` pairs
        (a_width None = full width) restricting the grid to those
        (and the election-only K 0 launch).  svcnode and the smoke
        share the default full grid.  Compile events recorded during
        warmup land under ``phase="warmup"``.
        """
        self._in_warmup = True
        try:
            self._warmup(buckets)
        finally:
            self._in_warmup = False

    def _warmup(self, buckets) -> None:
        e, m, s = self.n_ens, self.n_peers, self.n_slots
        by_k: Optional[Dict[int, List[Optional[int]]]] = None
        if buckets is not None:
            by_k = {}
            for kb, aw in buckets:
                by_k.setdefault(int(kb), []).append(aw)

        def a_widths(k: int) -> List[Optional[int]]:
            if k == 0:
                return [None]  # no per-round planes to compact
            if by_k is not None:
                return by_k.get(k, [])
            return self._a_ladder()

        # Warm the programs the launch path actually calls — with
        # donation on, the donated executables (donation changes the
        # compiled program's aliasing, so the plain warm wouldn't cover
        # it).  The throwaway state is THREADED through the calls: a
        # donated call consumes its input state.  Per (K, A) bucket
        # the launch calls EITHER the sliced program (A <= E/4 of
        # what a shard holds) OR the full-grid one with its pack
        # gathered at A — warm exactly that, with operands placed and
        # static arguments spelled as _launch_enqueue does (both are
        # part of a program's cache key).
        fns = self._fns
        st = self.engine.init_state(e, m, s)
        up = self._put(np.ones((e, m), bool), "up")
        no_planes = (None,) * len(eng.SLAB_PLANES)

        n_sh = self._mesh_shards or 1
        e_loc = e // n_sh

        def zero_slab(k: int, width: int, idx_row=None, sliced=False):
            """An all-NOOP op slab; sliced: all-pad index rows
            (gathers clip harmlessly, the scatter drops everything —
            state untouched, program compiled)."""
            z = np.zeros((width,), np.int32)
            return self._put(eng.pack_op_slab(
                width, k, z, z, z, no_planes,
                z[:0] if sliced else None, idx_row), "slab")

        def warm(k: int, aw: Optional[int], want_vsn: bool = True):
            """One (K, A) bucket's program: the sliced one where the
            launch path would slice there (its rule, read per shard),
            else the full-grid one, its pack gathered at ``aw`` (by
            all-pad index rows, column 0) or at full width (None)."""
            nonlocal st
            if aw is not None and self._slices(aw):
                width = n_sh * aw
                st, flat = fns.sliced_slab(
                    st, zero_slab(k, width,
                                  np.full((width,), e_loc, np.int32),
                                  sliced=True),
                    up, want_vsn=want_vsn)
            else:
                st, flat = fns.slab(
                    st, zero_slab(k, e, None if aw is None
                                  else np.zeros((e,), np.int32)),
                    up, want_vsn=want_vsn, gather=aw or 0)
            np.asarray(flat)

        k = 0
        while True:
            # The flush path (the read fast path's get-only/read-miss
            # fallback batches included) always packs WITH versions —
            # the (K, A) ladder covers those.  The version-less
            # program is what WAL-less execute/execute_async call:
            # warmed at FULL WIDTH per K bucket, which covers a dense
            # bulk batch without doubling the whole warm grid.
            for aw in a_widths(k):
                warm(k, aw)
                if aw is None:
                    warm(k, None, want_vsn=False)
            if k >= self.max_k:
                break
            k = 1 if k == 0 else k * 2

    def execute(self, kind: np.ndarray, slot: np.ndarray,
                val: np.ndarray,
                exp_epoch: Optional[np.ndarray] = None,
                exp_seq: Optional[np.ndarray] = None
                ) -> Tuple[np.ndarray, np.ndarray,
                           np.ndarray, np.ndarray]:
        """Bulk array API: run ``[K, E]`` op matrices through the
        service in one launch and return ``(committed, get_ok, found,
        value)`` as ``[K, E]`` arrays.

        This is the TPU-native client surface for array-shaped
        workloads: callers address slots directly and carry int32
        payloads inline on the device (no per-op Python objects, no
        host handle store) — the scalar kput/kget API remains for
        keyed/arbitrary-payload use.  Payload 0 is RESERVED as the
        tombstone (a put of 0 is a delete: it commits, and subsequent
        gets return found=False) — puts of live values must use
        1..2^31-1.  OP_RMW rows run the fused single-round
        read-modify-write: ``exp_epoch`` carries the mod-fun table
        code (funref.RMW_*), ``val`` the operand, and the committed
        COMPUTED value comes back in the value plane.  Same semantics
        as queued ops: elections fold in, leases check/renew,
        corruption triggers exchange.

        A ``jax.Array`` handed in is read back once at the door
        (``np.asarray``) and is from there an ordinary host call:
        validated, compacted, launched as one op slab and logged.

        Durability: with a ``data_dir``, a call logs its committed
        writes to the WAL before returning (the result IS the ack).
        """
        # A synchronous execute settles the launch pipeline first, so
        # results land in submission order behind any execute_async /
        # pipelined-flush work already in flight.
        self._drain_launches()
        kind = np.asarray(kind, np.int32)
        val = np.asarray(val, np.int32)
        if ((kind == eng.OP_PUT) & (val < 0)).any():
            raise ValueError("negative put payloads are not encodable "
                             "(int32 handles; 0 = tombstone/delete)")
        if (self._wal is not None
                and self._storage_degraded is not None
                and (((kind == eng.OP_PUT) | (kind == eng.OP_CAS)
                      | (kind == eng.OP_RMW))).any()):
            # read-only (§15): on this path the RESULT is the ack,
            # so a degraded service must refuse before the launch —
            # it cannot make the writes durable
            raise OSError(
                errno.EIO, "service is read-only (storage degraded): "
                "execute() writes cannot be made durable")
        k = int(kind.shape[0])
        slot = np.asarray(slot, np.int32)
        want_vsn = self._wal is not None
        committed, get_ok, found, value, vsn = self._launch(
            kind, slot, val, k, want_vsn=want_vsn,
            exp_e=None if exp_epoch is None
            else np.asarray(exp_epoch, np.int32),
            exp_s=None if exp_seq is None
            else np.asarray(exp_seq, np.int32))
        if self._wal is not None:
            self._log_execute_wal(kind, slot, val, committed, vsn,
                                  value)
        self.ops_served += int((kind != eng.OP_NOOP).sum())
        return committed, get_ok, found, value

    def _log_execute_wal(self, kind, slot, val, committed, vsn,
                         value=None) -> None:
        """WAL records for a bulk execute's committed inline writes
        (shared by the sync path and the execute_async settle).  An
        RMW row logs the value it COMPUTED (the ``value`` result
        plane), not its operand."""
        wmask = (((kind == eng.OP_PUT) | (kind == eng.OP_CAS)
                  | (kind == eng.OP_RMW))
                 & committed)
        wval = (val if value is None
                else np.where(kind == eng.OP_RMW, value, val))
        js, es = np.nonzero(wmask)
        recs = [(("kv", int(e), int(slot[j, e])),
                 (None, int(wval[j, e]), int(vsn[j, e, 0]),
                  int(vsn[j, e, 1]), None, True))
                for j, e in zip(js.tolist(), es.tolist())]
        if recs:
            self._wal.log(recs + self._wal_extra_records())

    def execute_async(self, kind: np.ndarray, slot: np.ndarray,
                      val: np.ndarray,
                      exp_epoch: Optional[np.ndarray] = None,
                      exp_seq: Optional[np.ndarray] = None) -> Future:
        """Pipelined bulk array API: dispatch a ``[K, E]`` batch and
        return a :class:`Future` resolving to ``(committed, get_ok,
        found, value)`` (or ``'failed'`` on a failed launch / WAL
        error).  The enqueue half returns immediately; the resolve
        half (unpack, mirrors, WAL, corruption sweep) runs when a
        later call — or an idle :meth:`flush` — settles the launch,
        so up to ``pipeline_depth`` batches overlap: batch N's d2h
        transfer + host resolve ride under batch N+1's device step.
        Results resolve strictly in submission order.  Same
        argument contract (payload encoding, ``jax.Array`` read back
        at the door, WAL) as :meth:`execute`.
        """
        fut = Future()
        kind = np.asarray(kind, np.int32)
        val = np.asarray(val, np.int32)
        if ((kind == eng.OP_PUT) & (val < 0)).any():
            raise ValueError(
                "negative put payloads are not encodable "
                "(int32 handles; 0 = tombstone/delete)")
        k = int(kind.shape[0])
        slot = np.asarray(slot, np.int32)
        want_vsn = self._wal is not None
        exec_wal = (kind, slot, val) if want_vsn else None
        n_ops = int((kind != eng.OP_NOOP).sum())
        exp_e = (None if exp_epoch is None
                 else np.asarray(exp_epoch, np.int32))
        exp_s = (None if exp_seq is None
                 else np.asarray(exp_seq, np.int32))
        # Same election-mirror discipline as the pipelined flush: an
        # in-flight launch may be about to install a leader; electing
        # again would re-version its objects.
        elect, cand = self._election_inputs()
        if elect.any() and self._inflight_launches:
            self._drain_launches()
            elect, cand = self._election_inputs()
        try:
            fl = self._launch_enqueue(kind, slot, val, k,
                                      want_vsn=want_vsn, exp_e=exp_e,
                                      exp_s=exp_s, elect=elect,
                                      cand=cand)
        except BaseException:
            self._safe_resolve(fut, "failed")
            raise
        fl.exec_fut = fut
        fl.exec_wal = exec_wal
        fl.exec_ops = n_ops
        self._inflight_launches.append(fl)
        self._drain_launches(keep=self.pipeline_depth - 1)
        return fut

    def flush(self) -> int:
        """One device launch for everything queued; returns ops served
        (by launches SETTLED during this call).

        With ``pipeline_depth`` > 1 the launch is only ENQUEUED here:
        its packed result rides the d2h link and its host resolve
        (unpack → WAL → future fan-out) runs while a LATER flush's
        device step is already dispatched — up to ``pipeline_depth``
        launches deep.  The WAL-before-ack barrier and submission
        order are preserved (settles are strictly FIFO); only the ack
        point moves later in wall time.  A flush that empties the
        queues settles everything before returning, so flush-until-
        done callers observe resolved futures exactly as at depth 1.
        """
        self._flush_calls += 1
        self._run_due_retries()
        active = self._active
        caps = self._admission_caps
        admit: Optional[Dict[int, int]] = None
        if not caps:
            k = min(self.max_k,
                    max((self._queue_rounds[e] for e in active),
                        default=0))
        else:
            # tenant-guard flush admission (ARCHITECTURE §14): a
            # capped row contributes at most its token-bucket
            # allowance (refill = cap/flush, burst 2x) both to the
            # batch-depth choice and to the take loop below — a hot
            # tenant's queue stops forcing every flush to its own
            # max depth, which is exactly what the quiet tenants'
            # p99 was paying for
            tokens = self._admission_tokens
            admit = {}
            k = 0
            for e in active:
                qr = self._queue_rounds[e]
                cap = caps.get(e)
                if cap is not None:
                    t = min(tokens.get(e, float(cap)) + cap,
                            2.0 * cap)
                    tokens[e] = t
                    qr = min(qr, int(t))
                admit[e] = qr
                if qr > k:
                    k = qr
            k = min(self.max_k, k)
        served = 0
        if k == 0:
            # Idle flush: settle the launch pipeline first (callers
            # that flush until done must observe resolved futures),
            # then see whether an election-only launch is needed —
            # settles may have chained follow-up ops (kmodify CAS
            # halves), which get their own launch cycle here.
            served += self._drain_launches()
            served += self._chain_flush()
            if not self._election_inputs()[0].any():
                # tail settles count toward maintenance too (their
                # WAL records / flush count advanced just the same)
                self._flush_maintenance()
                return served
        # Bucket the batch depth to the next power of two (capped at
        # max_k): XLA compiles one program per distinct [K, E] shape,
        # so under skewed load a raw longest-queue K would trigger a
        # 20-40 s compile for every new depth seen.  Padding rounds
        # are NOOPs — microseconds of device math vs seconds of
        # compile churn; at most 1+log2(max_k) variants ever compile.
        if k:
            b = 1
            while b < k:
                b <<= 1
            k = min(b, self.max_k)

        rec = self.spans.begin()
        pack = self.spans.span("pack", rec).begin()
        kind = np.zeros((k, self.n_ens), dtype=np.int32)
        slot = np.zeros((k, self.n_ens), dtype=np.int32)
        val = np.zeros((k, self.n_ens), dtype=np.int32)
        exp_e = np.zeros((k, self.n_ens), dtype=np.int32)
        exp_s = np.zeros((k, self.n_ens), dtype=np.int32)
        #: (ensemble, taken ops) pairs — ACTIVE ensembles only (the
        #: op matrices stay full-width [K, E]; only the host loops
        #: skip idle columns)
        taken: List[Tuple[int, List[Any]]] = []
        still_active = set()
        #: slab enqueue path (ARCHITECTURE §12b): instead of a numpy
        #: slice assignment per entry per plane, the walk collects
        #: the PENDING SLAB and the planes scatter from it in one
        #: pass — the C++ kernel's single traversal, or one
        #: numpy-expanded fancy assignment per plane as fallback.
        #: ``offs`` records each taken entry's first slab row
        #: (flattened taken order) — the completion-slab resolve
        #: indexes by it.
        use_slab = self._enq_slab
        #: pending-slab RUN DESCRIPTORS (one per taken entry: its
        #: ensemble column, first plane row, run length, uniform op
        #: kind) over concatenated per-op field lanes — what both
        #: native passes (pack, completion-slab gather) walk; the
        #: Python→C conversion cost scales with entries, not ops
        ent_col: List[int] = []
        ent_row0: List[int] = []
        ent_len: List[int] = []
        ent_kind: List[int] = []
        slot_l: List[int] = []
        val_l: List[int] = []
        expe_l: List[int] = []
        exps_l: List[int] = []
        offs: List[int] = []
        lane_n = 0
        #: per-entry SLO stamp columns (obs.opslo satellite): t_rx/
        #: t_sub/t_enq collected HERE at enqueue time off the pending
        #: entries (kind/ens/weight are the run descriptors above) —
        #: the settle-side fold then sources stamps from the pending
        #: slab instead of re-walking the taken entries after their
        #: futures were replaced by completion-slab rows
        trx_l: List[float] = []
        tsub_l: List[float] = []
        tenq_l: List[float] = []
        for e in sorted(active):
            q = self.queues[e]
            # limit == k on the uncapped path; a tenant-guard cap
            # lowers it to the row's admitted allowance
            limit = k if admit is None else min(k, admit.get(e, k))
            ops: List[Any] = []
            rounds = idx = 0
            while idx < len(q) and rounds < limit:
                op = q[idx]
                if rounds + op.n <= limit:
                    ops.append(op)
                    rounds += op.n
                    idx += 1
                else:
                    # K cap (or the row's admission limit) lands
                    # inside a batch: take the head rounds now; the
                    # tail (same Future/accumulator) leads the next
                    # flush.
                    head, tail = op.split(limit - rounds)
                    ops.append(head)
                    rounds = limit
                    q[idx] = tail
                    break
            self.queues[e] = q[idx:]
            self._queue_rounds[e] -= rounds
            if admit is not None and e in self._admission_tokens:
                self._admission_tokens[e] = max(
                    0.0, self._admission_tokens[e] - rounds)
            if self.queues[e]:
                still_active.add(e)
            if ops:
                taken.append((e, ops))
            j = 0
            if use_slab:
                # pending-slab build: C-level list appends/extends
                # only — per-entry numpy work is zero; one asarray
                # per column below converts the whole flush at once
                for op in ops:
                    n = op.n
                    offs.append(lane_n)
                    lane_n += n
                    ent_col.append(e)
                    ent_row0.append(j)
                    ent_len.append(n)
                    ent_kind.append(op.kind)
                    trx_l.append(op.t_rx)
                    tsub_l.append(op.t_sub)
                    tenq_l.append(op.t_enq)
                    if isinstance(op, _PendingBatch):
                        slot_l.extend(op.slot)
                        val_l.extend(op.handle)
                        if op.exp_e is not None:
                            expe_l.extend(op.exp_e)
                            exps_l.extend(op.exp_s)
                        else:
                            z = [0] * n
                            expe_l.extend(z)
                            exps_l.extend(z)
                    else:
                        slot_l.append(op.slot)
                        val_l.append(op.handle)
                        expe_l.append(op.exp[0])
                        exps_l.append(op.exp[1])
                    j += n
            else:
                for op in ops:
                    if isinstance(op, _PendingBatch):
                        n = op.n
                        kind[j:j + n, e] = op.kind
                        slot[j:j + n, e] = op.slot
                        val[j:j + n, e] = op.handle
                        if op.exp_e is not None:
                            exp_e[j:j + n, e] = op.exp_e
                            exp_s[j:j + n, e] = op.exp_s
                        j += n
                    else:
                        kind[j, e] = op.kind
                        slot[j, e] = op.slot
                        val[j, e] = op.handle
                        exp_e[j, e], exp_s[j, e] = op.exp
                        j += 1

        lanes = None
        pack_mark = None
        if use_slab and lane_n:
            ec = np.asarray(ent_col, np.int32)
            er = np.asarray(ent_row0, np.int32)
            el = np.asarray(ent_len, np.int32)
            ek = np.asarray(ent_kind, np.int32)
            l_slot = np.asarray(slot_l, np.int32)
            l_val = np.asarray(val_l, np.int32)
            l_expe = np.asarray(expe_l, np.int32)
            l_exps = np.asarray(exps_l, np.int32)
            bounds = self._shard_bounds(len(ec))
            if self._native_enqueue is None:
                native_pack = False
            elif bounds is None:
                native_pack = self._native_enqueue.pack(
                    k, self.n_ens, ec, er, el, ek,
                    l_slot, l_val, l_expe, l_exps, kind,
                    slot, val, exp_e, exp_s)
            else:
                # sharded pending-slab build (ARCHITECTURE §16): each
                # chunk's descriptors write disjoint [K, E] cells
                # (rows [er, er+el) of their columns), so concurrent
                # packs into the shared planes never overlap; lanes
                # slice at the chunk's global offset because pack
                # consumes them in descriptor order
                def _pack_chunk(lo, hi):
                    s = offs[lo]
                    t = offs[hi] if hi < len(offs) else lane_n
                    return self._native_enqueue.pack(
                        k, self.n_ens, ec[lo:hi], er[lo:hi],
                        el[lo:hi], ek[lo:hi], l_slot[s:t],
                        l_val[s:t], l_expe[s:t], l_exps[s:t],
                        kind, slot, val, exp_e, exp_s)
                # a chunk falling back is harmless: the numpy rewrite
                # below re-fills EVERY cell with identical values
                native_pack = all(self._shard_map(_pack_chunk,
                                                  bounds))
                self.sharded_flushes += 1
            if not native_pack:
                rows, cols = _lane_indices(ec, er, el)
                kind[rows, cols] = np.repeat(ek, el)
                slot[rows, cols] = l_slot
                val[rows, cols] = l_val
                exp_e[rows, cols] = l_expe
                exp_s[rows, cols] = l_exps
            lanes = (ec, er, el, lane_n, offs,
                     (ent_kind, ent_col, ent_len, trx_l, tsub_l, tenq_l)
                     if self._obs else None)
            if native_pack:
                self.native_enqueue_flushes += 1
                pack_mark = "enqueue_native"
            else:
                self.fallback_enqueue_flushes += 1
                pack_mark = "enqueue_fallback"
        pack.end()

        self._active = still_active
        # Elections plan from the HOST MIRRORS, which in-flight
        # launches may still be about to update (a won election lands
        # at resolve) — settle first, or the same ensemble re-elects
        # and the epoch bump re-versions its objects on first read
        # (spurious CAS failures).  Elections are rare; the
        # steady-state pipelined path never takes this drain.
        elect, cand = self._election_inputs()
        if elect.any() and self._inflight_launches:
            served += self._drain_launches()
            elect, cand = self._election_inputs()
        self._enq_rec = rec
        try:
            fl = self._launch_enqueue(kind, slot, val, k,
                                      want_vsn=True, exp_e=exp_e,
                                      exp_s=exp_s, entries=taken,
                                      elect=elect, cand=cand)
        except BaseException:
            # A failed device launch (XLA error, OOM, dead backend)
            # must not orphan the taken ops: clients would block on
            # their futures forever.  Fail them all — the reference's
            # request_failed path (worker crash -> step_down,
            # peer.erl:1274-1275) — then let the error propagate to
            # whoever drives flush().  The enqueue already rolled the
            # device state back, so the next flush starts clean.
            for e, ops in taken:
                for op in ops:
                    self._fail_entry(e, op)
            raise
        finally:
            self._enq_rec = None
        fl.taken = taken
        fl.lanes = lanes
        if pack_mark is not None:
            # derived A/B mark (flightrec.DERIVED_MARKS — outside the
            # additive total; the wall time is already inside
            # queue_wait): the enqueue half's lane-build + plane-pack
            # share (the 'pack' span), attributed to whichever pack
            # arm ran
            fl.rec[pack_mark] = pack.seconds
        self._inflight_launches.append(fl)
        # Settle: everything when the queues drained (nothing queued
        # to overlap with), else down to depth-1 still in flight —
        # the window the NEXT flush's enqueue overlaps.
        keep = self.pipeline_depth - 1 if self._active else 0
        served += self._drain_launches(keep=keep)
        served += self._chain_flush()
        self._flush_maintenance()
        return served

    def _chain_flush(self) -> int:
        """Same-flush chaining (the kmodify round-halving): when a
        settle's resolutions enqueued follow-up ops — a host-path
        kmodify read's CAS half, or an immediate conflict retry — run
        ONE more bounded launch cycle inside the same flush() call, so
        the follow-up costs this flush instead of the next.  Depth is
        capped at 2 nested cycles: a chain may re-arm once (CAS →
        conflict → fresh read) per level, and the cap bounds rounds
        per flush call while the backoff queue carries the rest."""
        if not self._chain_kick:
            return 0
        self._chain_kick = False
        if not self._active or self._chain_depth >= 2:
            return 0
        self._chain_depth += 1
        try:
            return self.flush()
        finally:
            self._chain_depth -= 1

    def _flush_maintenance(self) -> None:
        """Post-settle upkeep shared by the normal and idle flush
        paths: WAL compaction past the record bound, and the periodic
        scrub against its flush-count watermark."""
        if (self._wal is not None and not self._in_save
                and self._storage_degraded is None
                and self._wal.count >= self.wal_compact_records):
            # degraded gate: a read-only service must never compact —
            # save() would write the same dead/full disk and the
            # OSError would crash the flush loop the degradation
            # exists to protect (reads must keep serving)
            # WAL grew past the compaction bound: fold it into a fresh
            # checkpoint (save() rotates the generation) — but OFF the
            # hot path.  save() is a full checkpoint (hundreds of ms);
            # running it synchronously inside a loaded flush billed
            # the pause to whatever client op was in flight (the
            # mixed-load p99 spike vs a ~20 ms p50).  Defer to an idle
            # flush — queues empty AND launch pipeline drained — and
            # fall back to in-line only past a hard 2x record bound,
            # so sustained load still bounds replay time.
            idle = not self._active and not self._inflight_launches
            if idle or self._wal.count >= 2 * self.wal_compact_records:
                self._compact_wal(idle)
        if (self.scrub_every_flushes
                and self.flushes - self._scrubbed_at_flush
                >= self.scrub_every_flushes):
            self.scrub()
        if (self._retry_at and not self._active
                and not self._inflight_launches):
            # A fully-idle flush with only backed-off kmodify retries
            # parked: there are no concurrent writers left to
            # de-collide from, so the backoff delay is pure latency —
            # and a driver using the `while any(svc.queues): flush()`
            # idiom would stop flushing with the futures unresolved.
            # Collapse the delays: fire every parked retry now, so
            # their reads re-enter the queues (and re-arm the driver's
            # loop condition) before this flush returns.
            parked, self._retry_at = self._retry_at, []
            for _at, _e, fut, thunk in parked:
                if not fut.done:
                    thunk()

    def _compact_wal(self, idle: bool) -> None:
        """Fold the WAL into a fresh checkpoint, timed and marked:
        an ``svc_compaction`` latency record (latency_breakdown) +
        trace event + stats() counters make the pause attributable
        instead of vanishing into some client op's p99."""
        records = self._wal.count
        rec = self.spans.begin()
        with self.spans.span("svc_compaction", rec):
            self.save()
        dt = rec["svc_compaction"]
        self.wal_compactions += 1
        self.wal_compaction_ms_last = dt * 1e3
        self.wal_compaction_ms_total += dt * 1e3
        self.lat_records.append(rec)
        self._emit("svc_compaction",
                   {"ms": round(dt * 1e3, 3), "records": records,
                    "idle": idle})

    # -- launch pipeline (two-phase async service execution) ---------------

    def _drain_launches(self, keep: int = 0) -> int:
        """Settle in-flight launches oldest-first until at most
        ``keep`` remain; returns ops served.

        A DEVICE-side settle failure abandons every LATER in-flight
        launch too: the failed launch rolled the device state back to
        ITS pre-launch snapshot, and the later launches consumed the
        poisoned chain — their ops fail without touching state (their
        snapshots postdate the poison).  A WAL-append failure is
        different: the launch's device commits are REAL (its clients
        got 'failed' — the allowed unacked-commit outcome — but the
        device/host bookkeeping stands), so later launches keep
        settling normally (abandoning them would release handles and
        recycle slots the device still populates); after the drain,
        a fatal-disk errno (EIO/ENOSPC) degrades the service to
        read-only (§15) while any other disk error re-raises to the
        flush driver."""
        served = 0
        wal_err: Optional[BaseException] = None
        fatal_err: Optional[BaseException] = None
        while len(self._inflight_launches) > keep:
            fl = self._inflight_launches.popleft()
            # spans that name no record (the WAL barrier's inside,
            # the front end's replies, a pause of the collector)
            # belong to the launch being settled
            self.spans.settling(fl.rec)
            try:
                n, err = self._settle_launch(fl)
                served += n
                if err is not None:
                    if wal_err is None:
                        wal_err = err
                    if isinstance(err, OSError):
                        self.wal_storage_errors += 1
                        # the fatal bad-disk signal may arrive on a
                        # LATER launch than the first (non-fatal)
                        # error of the drain — it must still win the
                        # degrade decision below, not be masked
                        if (fatal_err is None
                                and getattr(err, "errno", None)
                                in (errno.EIO, errno.ENOSPC)):
                            fatal_err = err
            except BaseException:
                while self._inflight_launches:
                    self._abandon_launch(self._inflight_launches.popleft())
                raise
            finally:
                self.spans.settling(None)
        if fatal_err is not None:
            # a dead/full disk under the WAL: degrade to read-only
            # (journaled, observable) instead of crashing the
            # serving loop — ARCHITECTURE §15
            self._degrade_storage("wal", fatal_err)
        elif wal_err is not None:
            # other disk errors keep the historical
            # raise-to-driver contract
            raise wal_err
        return served

    def _degrade_storage(self, plane: str, exc: BaseException) -> None:
        """Flip the service read-only after a fatal storage error on
        the ack path (EIO/ENOSPC under the WAL): subsequent writes
        fail fast at enqueue, reads keep serving, and the decision is
        journaled — a trace event, the health()["storage"] section,
        and the retpu_recovery_* gauges (ARCHITECTURE §15).  Recovery
        is a restart: restore() replays the WAL onto a healthy disk.
        Idempotent; the first error wins the record."""
        if self._storage_degraded is not None:
            return
        code = getattr(exc, "errno", None)
        self._storage_degraded = {
            "plane": plane,
            "mode": "read_only",
            "errno": errno.errorcode.get(code, str(code)),
            "error": repr(exc)[:200],
            "at_flush": int(self.flushes),
        }
        # the read-only contract covers writes ALREADY QUEUED too:
        # left in place they would flush later, and if the disk
        # flickered back they would WAL-log and ack from a
        # "read_only" service — onto a log whose fate the degrade
        # already distrusts (review r15).  Fail them now through the
        # normal release/recycle path; reads stay queued.
        self._fail_queued_writes()
        # subclass hook next: a replicated leader demotes itself
        # through the group's step-down machinery (repgroup
        # override, which rewrites mode to "step_down") — the
        # journaled decision below must record what actually
        # happened, not the base default
        self._on_storage_degraded()
        self._emit("svc_storage_degraded",
                   dict(self._storage_degraded))

    def _fail_queued_writes(self) -> None:
        """Fail every queued write entry (scalar or batch), keeping
        queued reads — the enqueue half of flipping read-only."""
        for e in list(self._active):
            q = self.queues[e]
            drop = [op for op in q if op.kind != eng.OP_GET]
            if not drop:
                continue
            keep = [op for op in q if op.kind == eng.OP_GET]
            self.queues[e] = keep
            self._queue_rounds[e] = sum(op.n for op in keep)
            for op in drop:
                self._fail_entry(e, op)

    def _on_storage_degraded(self) -> None:
        """Subclass seam: called once when the storage plane
        degrades.  The base service has no leadership to shed beyond
        the per-row device ballots (reads stay served; the row
        leaders are device state, not a group role)."""

    def _abandon_launch(self, fl: _InFlightLaunch) -> None:
        """Fail a poisoned in-flight launch's clients (launch N < this
        one failed and rolled the device state back under it)."""
        if fl.exec_fut is not None:
            self._safe_resolve(fl.exec_fut, "failed")
        if fl.taken:
            for e, ops in fl.taken:
                for op in ops:
                    self._fail_entry(e, op)

    def _settle_launch(self, fl: _InFlightLaunch
                       ) -> Tuple[int, Optional[BaseException]]:
        """Resolve one in-flight launch end to end: block on its
        packed result, then WAL-log and fan out its futures (the
        durability barrier stands — WAL before any ack).  Returns
        (ops served, wal error or None) — a WAL failure is reported,
        not raised, so the drain can keep settling later launches
        whose device commits are independent of this one's disk
        error; the drain then degrades or re-raises per the errno
        (see :meth:`_drain_launches`)."""
        rec = fl.rec
        wait_key = ("inflight_wait" if self.pipeline_depth > 1
                    else "device_d2h")
        try:
            planes = self._launch_resolve(fl, wait_key=wait_key)
        except BaseException:
            self._abandon_launch(fl)
            raise
        if fl.exec_fut is not None:
            return self._settle_execute(fl, planes)
        taken = fl.taken or []
        # Durability barrier: committed writes reach the WAL (synced
        # per wal_sync) BEFORE any future resolves — the never-ack-
        # unpersisted-writes contract (basic_backend.erl:120-125).  If
        # the WAL write itself fails, the commits stand on device (the
        # bookkeeping proceeds) but their clients get 'failed' — an
        # unacked commit is an allowed linearizable outcome; a lost
        # acked one is not — and the drain either degrades the
        # service (fatal EIO/ENOSPC, §15) or re-raises to the flush
        # driver.
        wal_err: Optional[BaseException] = None
        # a degraded (read-only) service must not WAL-log or ack
        # in-flight writes either — if the disk flickered back the
        # append could succeed and ack from a service whose log tail
        # the degrade already distrusts (review r15); their reads
        # still serve (ack=False spares reads by design)
        degraded = self._storage_degraded is not None
        span = self.spans.span
        with span("wal", rec):
            if self._wal is not None and not degraded:
                try:
                    self._log_wal(taken, planes, rec=rec)
                except Exception as exc:
                    wal_err = exc
        with span("resolve", rec):
            served = self._resolve_flush(taken, planes,
                                         ack=wal_err is None
                                         and not degraded,
                                         op_planes=(fl.kind_np,
                                                    fl.op_slot_np),
                                         rec=rec, fid=fl.flush_id,
                                         t_join=fl.t_join,
                                         lanes=fl.lanes)
        # Finish the breakdown the launch recorded: oldest-op queue
        # wait, WAL append+sync, per-future resolve.  Per-component
        # percentiles over these records are what makes a p99 target
        # analyzable (review r2 weak #2).
        t_wal = rec["starts"]["wal"]
        oldest = min((op.t_enq for _e, ops in taken for op in ops
                      if op.t_enq), default=t_wal)
        rec["queue_wait"] = max(0.0, t_wal - oldest
                                - rec.get("total", 0.0))
        rec["starts"]["queue_wait"] = min(oldest, t_wal)
        self._close_record(fl)
        return served, wal_err

    def _close_record(self, fl: _InFlightLaunch) -> None:
        """A launch has settled: take what the loop did since the
        last settle (front end, ``between_flushes``, pauses of the
        collector) into its record, sum ``total`` over the additive
        marks and feed the obs plane, whose own cost is the derived
        mark ``obs``."""
        rec = fl.rec
        self.spans.close(rec)
        rec["total"] = sum(v for c, v in rec.items()
                           if c not in DERIVED_MARKS)
        if self._obs:
            with self.spans.span("obs", rec):
                self._obs_flush_settled(fl)

    def _settle_execute(self, fl: _InFlightLaunch, planes
                        ) -> Tuple[int, Optional[BaseException]]:
        """Resolve one ``execute_async`` launch: WAL-log committed
        writes (host-array path; the resolution IS the ack), then
        resolve the client future with the result planes.  Same
        (served, wal error) reporting contract as
        :meth:`_settle_launch` — an unpersisted commit may never be
        acked (the future resolves 'failed'), but later launches'
        settles proceed."""
        committed, get_ok, found, value, vsn = planes
        if fl.exec_wal is not None and self._wal is not None:
            if self._storage_degraded is not None:
                # read-only: the commit may be real on device, but
                # no ack may ride a distrusted log (see
                # _settle_launch's degraded gate)
                self._safe_resolve(fl.exec_fut, "failed")
                return 0, None
            kind, slot, val = fl.exec_wal
            try:
                self._log_execute_wal(kind, slot, val, committed, vsn,
                                      value)
            except Exception as exc:
                self._safe_resolve(fl.exec_fut, "failed")
                return 0, exc
        self.ops_served += fl.exec_ops
        # the future fan-out is the execute path's whole resolve
        # stage; recording it here gives the pipelined bench loop's
        # latency_breakdown a `resolve` entry like the flush path's
        with self.spans.span("resolve", fl.rec):
            self._safe_resolve(fl.exec_fut,
                               (committed, get_ok, found, value))
        self._close_record(fl)
        return fl.exec_ops, None

    def _wal_extra_records(self) -> List[Tuple[Any, Any]]:
        """Records a subclass wants persisted in the SAME durability
        barrier as a flush's committed writes (one log() call = one
        sync) — the replication group rides its (epoch, seq) meta
        here so a leader restart can never mistake its own data-
        bearing position for an older one."""
        return []

    def _log_wal(self, taken, planes, rec=None) -> None:
        """Append this flush's committed client writes to the WAL
        (latest record per (ens, slot)); called BEFORE any future
        resolves.

        Native arm: batch-only flushes whose keys/payloads fit the
        kernel's pickle subset (str keys, bytes/None payloads) encode
        every record into one byte arena in a single C pass and the
        WAL appends it verbatim (:meth:`ServiceWAL.log_arena`) —
        byte-identical store contents to the Python path below, which
        remains the oracle and the fallback (scalar write ops, exotic
        key/payload types, RETPU_NATIVE_RESOLVE=0) and reads the
        planes at the flush's own lanes only."""
        committed, _get_ok, _found, value, vsn = planes
        if committed is None:
            return
        if (self._native_resolve is not None and vsn is not None
                and self._log_wal_native(taken, planes, rec)):
            return
        encode = self.spans.span("wal_encode", rec).begin()
        writes = (eng.OP_PUT, eng.OP_CAS, eng.OP_RMW)
        recs = []
        #: the flush's scalar write lanes: where in recs, which op,
        #: and the (j, e) to read the planes at
        lane_at: List[int] = []
        lane_op: List[_PendingOp] = []
        lane_j: List[int] = []
        lane_e: List[int] = []
        for e, ops in taken:
            j = -1
            for op in ops:
                if isinstance(op, _PendingBatch):
                    if op.kind in (eng.OP_PUT, eng.OP_CAS):
                        comm = committed[j + 1:j + 1 + op.n, e]
                        vs2 = vsn[j + 1:j + 1 + op.n, e]
                        for i in np.nonzero(comm)[0]:
                            h = int(op.handle[i])
                            recs.append((
                                ("kv", e, int(op.slot[i])),
                                (op.keys[i], h, int(vs2[i, 0]),
                                 int(vs2[i, 1]),
                                 self.values.get(h) if h else None,
                                 False)))
                    elif op.kind == eng.OP_RMW:
                        # keyed inline record: the committed COMPUTED
                        # value (result plane) rides the handle field
                        comm = committed[j + 1:j + 1 + op.n, e]
                        vs2 = vsn[j + 1:j + 1 + op.n, e]
                        vv = value[j + 1:j + 1 + op.n, e]
                        for i in np.nonzero(comm)[0]:
                            recs.append((
                                ("kv", e, int(op.slot[i])),
                                (op.keys[i], int(vv[i]),
                                 int(vs2[i, 0]), int(vs2[i, 1]),
                                 None, True)))
                    j += op.n
                    continue
                j += 1
                if op.kind in writes:
                    # hold the record's place in the walk's order
                    # (scalar and batch records of one (ens, slot)
                    # interleave: latest-per-key within the flush)
                    lane_at.append(len(recs))
                    lane_op.append(op)
                    lane_j.append(j)
                    lane_e.append(e)
                    recs.append(None)
        if lane_at:
            # The planes are [K, E(, 2)] whatever the flush wrote, so
            # all three are read at the flush's own lanes only: one
            # gather per plane, then tolist() of those tens of elements
            # for the Python ints the store pickles.  The barrier costs
            # O(ops in the flush) at every E.  Listing a plane instead
            # builds and frees E lists a round: at E = 10,000 that was
            # 3.0 of the barrier's 3.6 ms and what tripped a 300 ms
            # full collector pass every 3.6 s (PERF.md §6, PR 26).
            lanes = (np.asarray(lane_j), np.asarray(lane_e))
            comm_l = committed[lanes].tolist()
            vsn_l = vsn[lanes].tolist()
            value_l = value[lanes].tolist()
            for at, op, e, comm, (ve, vs), val in zip(
                    lane_at, lane_op, lane_e, comm_l, vsn_l, value_l):
                if not comm:
                    continue
                if op.kind == eng.OP_RMW:
                    recs[at] = (("kv", e, op.slot),
                                (op.key, val, ve, vs, None, True))
                else:
                    payload = (self.values.get(op.handle)
                               if op.handle else None)
                    recs[at] = (("kv", e, op.slot),
                                (op.key, op.handle, ve, vs, payload,
                                 False))
            recs = [r for r in recs if r is not None]
        encode.end()
        if recs:
            self._wal.log(recs + self._wal_extra_records())

    def _log_wal_native(self, taken, planes, rec=None) -> bool:
        """Single-pass WAL encode (docs/ARCHITECTURE.md §12): gather
        the flush's write lanes as flat arrays + joined key/payload
        arenas (bulk C-level string ops, no per-record pickle), hand
        them to the kernel, and append the returned byte arena
        verbatim.  Returns False when any lane is outside the native
        subset — the caller's Python path then logs EVERY record, so
        record order (latest-per-key within the flush) is preserved
        exactly."""
        committed, _get_ok, _found, value, vsn = planes
        with self.spans.span("wal_encode", rec) as encode:
            lane_j: List[int] = []
            lane_e: List[int] = []
            lane_slot: List[int] = []
            lane_f2: List[int] = []
            lane_inl: List[int] = []
            keys: List[str] = []
            pays: List[Any] = []
            values = self.values
            for e, ops in taken:
                j = -1
                for op in ops:
                    if not isinstance(op, _PendingBatch):
                        j += 1
                        if op.kind != eng.OP_GET:
                            # scalar write lanes interleave with batch
                            # records on the same (ens, slot): only the
                            # Python walk preserves that order
                            return False
                        continue
                    if op.kind in (eng.OP_PUT, eng.OP_CAS, eng.OP_RMW):
                        ks = op.keys
                        if ks is None or not all(
                                type(kk) is str for kk in ks):
                            return False
                        if op.kind == eng.OP_RMW:
                            pays.extend([None] * op.n)
                            lane_f2.extend([0] * op.n)
                            lane_inl.extend([1] * op.n)
                        else:
                            for h in op.handle:
                                p = values.get(h) if h else None
                                if p is not None and type(p) is not bytes:
                                    return False
                                pays.append(p)
                            lane_f2.extend(op.handle)
                            lane_inl.extend([0] * op.n)
                        keys.extend(ks)
                        lane_j.extend(range(j + 1, j + 1 + op.n))
                        lane_e.extend([e] * op.n)
                        lane_slot.extend(op.slot)
                    j += op.n
            if not lane_j:
                return True  # read-only flush: nothing to log
            joined = "".join(keys)
            key_arena = joined.encode("utf-8")
            if len(key_arena) != len(joined):
                return False  # non-ascii keys: char lens != byte lens
            n = len(lane_j)
            key_len = np.fromiter(map(len, keys), np.int64, n)
            key_off = np.zeros((n,), np.int64)
            np.cumsum(key_len[:-1], out=key_off[1:])
            pay_len = np.fromiter(
                (-1 if p is None else len(p) for p in pays), np.int64, n)
            if int((key_len + np.maximum(pay_len, 0)).max()) >= 65500:
                # CPython's pickler frames in ~64 KiB units: once a
                # record's body reaches FRAME_SIZE_TARGET it splits
                # frames at opcode boundaries (and writes >= 64 KiB
                # str/bytes out-of-frame entirely).  The kernel emits ONE
                # frame per record body, so oversized records would
                # diverge from the oracle byte-for-byte — route the flush
                # to Python.  65500 = the target minus the record's
                # worst-case non-payload opcode overhead.
                return False
            pay_arena = b"".join(p for p in pays if p is not None)
            pay_off = np.zeros((n,), np.int64)
            np.cumsum(np.maximum(pay_len, 0)[:-1], out=pay_off[1:])
            out = self._native_resolve.wal_encode(
                self.n_ens, np.asarray(lane_j, np.int32),
                np.asarray(lane_e, np.int32),
                np.asarray(lane_slot, np.int32),
                np.asarray(lane_f2, np.int32),
                np.asarray(lane_inl, np.uint8),
                np.zeros((n,), np.uint8), key_off, key_len, key_arena,
                pay_off, pay_len, pay_arena, committed, value, vsn)
            if out is None:
                return False
            arena, idx = out
            idx = idx[idx[:, 1] > 0]  # drop uncommitted lanes
        if rec is not None:
            rec["resolve_native"] = (rec.get("resolve_native", 0.0)
                                     + encode.seconds)
        if len(idx):
            self._wal.log_arena(arena, idx,
                                self._wal_extra_records())
        return True

    def _safe_resolve(self, fut: Future, result: Any) -> None:
        """Resolve a client future, containing waiter exceptions:
        ``Future.resolve`` runs waiters synchronously, and a client
        callback that raises must not abort the resolve loop — that
        would orphan every later op in the batch (and, on the failure
        path, mask the original device error)."""
        try:
            fut.resolve(result)
        except Exception:  # client bug, not ours: trace it with the
            import traceback  # traceback (KeyboardInterrupt/SystemExit
            self._emit("svc_waiter_error",  # propagate)
                       {"error": traceback.format_exc(limit=8)})

    def _fail_entry(self, e: int, op) -> None:
        """Fail one queue entry (scalar op or batch) — launch
        failures and ensemble destruction."""
        if isinstance(op, _PendingBatch):
            self._fail_batch(e, op)
        else:
            self._fail_op(e, op)

    def _fail_batch(self, e: int, op: _PendingBatch) -> None:
        if op.fut.done:
            return
        if op.kind in (eng.OP_PUT, eng.OP_CAS, eng.OP_RMW):
            for i in range(op.n):
                self._unnote_write(e, op.slot[i])
                if op.kind != eng.OP_RMW:
                    # an RMW entry's handle field is its int32
                    # operand, not a payload handle
                    self._release_handle(op.handle[i])
                    if op.handle[i]:
                        self._unnote_handle_write(e, op.slot[i])
                if op.keys is not None:
                    self._queue_recycle(e, (op.keys[i], op.slot[i],
                                            op.gen[i]))
        op.accum.fill(op.fut, op.pos, ["failed"] * op.n,
                      self._safe_resolve)

    def _fail_op(self, e: int, op: _PendingOp) -> None:
        """Resolve one queued op as failed, releasing a put's payload
        and queueing its slot for recycling (shared by the resolve
        loop's uncommitted branch and the launch-failure path)."""
        if op.fut.done:
            return
        if op.kind in (eng.OP_PUT, eng.OP_CAS):
            self._release_handle(op.handle)
            if op.handle:
                self._unnote_handle_write(e, op.slot)
        if op.kind in (eng.OP_PUT, eng.OP_CAS, eng.OP_RMW):
            self._unnote_write(e, op.slot)
            # A failed write that was the slot's last queued write may
            # leave it holding nothing committed (fresh slot, or a
            # tombstone whose delete-side recycle was skipped because
            # this write bumped the generation): queue it for
            # recycling or the slot leaks until the key is deleted.
            # (An RMW's handle field is its operand — nothing to
            # release; the recycle drain's committed-handle check
            # covers the -1 inline sentinel too.)
            if op.key is not None:
                self._queue_recycle(e, (op.key, op.slot, op.gen))
        self._safe_resolve(op.fut, "failed")

    def _resolve_batch(self, e: int, j: int, op: _PendingBatch,
                       planes, ack: bool, ack_reads: bool = True,
                       native_mirrors: bool = False) -> None:
        """Resolve one batch entry from result-plane column slices —
        the vectorized counterpart of the per-op resolve loop.  With
        ``native_mirrors`` the kernel already scattered this flush's
        ``_slot_vsn``/``_inline_value`` slab updates, so the loop
        keeps only the Python-owned bookkeeping (handles, recycles,
        the pending-write index, the storage-class set, the client
        results)."""
        committed, get_ok, found, value, vsn = planes
        n = op.n
        results: List[Any] = []
        append = results.append
        if op.kind in (eng.OP_PUT, eng.OP_CAS):
            comm_l = committed[j:j + n, e].tolist()
            vs_l = vsn[j:j + n, e].tolist()
            slot_l = op.slot
            handle_l = op.handle
            gen_l = op.gen
            keys = op.keys if op.keys is not None else [None] * n
            slot_handle = self.slot_handle[e]
            # direct append binding for the hot loop; one dirty mark
            # covers every recycle this batch queues
            recycle = self._recycle_pending[e].append
            self._recycle_dirty.add(e)
            release = self._release_handle
            inline = self._inline_slots[e]
            inline_row = self._inline_np[e]
            inline_val_np = self._inline_value_np[e]
            inline_val_ok = self._inline_value_ok[e]
            vsn_row = self._slot_vsn_np[e]
            vsn_ok_row = self._slot_vsn_ok[e]
            unnote_w = self._unnote_write
            for comm, s, h, g, key, vs in zip(comm_l, slot_l,
                                              handle_l, gen_l, keys,
                                              vs_l):
                unnote_w(e, s)
                if h:
                    self._unnote_handle_write(e, s)
                if not comm:
                    release(h)
                    if key is not None:
                        recycle((key, s, g))
                    append("failed")
                    continue
                old = slot_handle.pop(s, 0)
                if old != h:
                    release(old)
                if h:
                    slot_handle[s] = h
                inline.discard(s)
                inline_row[s] = False
                if not native_mirrors:
                    inline_val_ok[s] = False
                    vsn_row[s] = vs  # mirror before the ack
                    vsn_ok_row[s] = True
                append(("ok", tuple(vs)) if ack else "failed")
        elif op.kind == eng.OP_RMW:
            comm_l = committed[j:j + n, e].tolist()
            vs_l = vsn[j:j + n, e].tolist()
            val_l = value[j:j + n, e].tolist()
            slot_handle = self.slot_handle[e]
            inline = self._inline_slots[e]
            inline_row = self._inline_np[e]
            inline_val_np = self._inline_value_np[e]
            inline_val_ok = self._inline_value_ok[e]
            vsn_row = self._slot_vsn_np[e]
            vsn_ok_row = self._slot_vsn_ok[e]
            release = self._release_handle
            recycle = self._recycle_pending[e].append
            self._recycle_dirty.add(e)
            unnote_w = self._unnote_write
            keys = op.keys if op.keys is not None else [None] * n
            for comm, s, g, key, vs, v in zip(comm_l, op.slot, op.gen,
                                              keys, vs_l, val_l):
                unnote_w(e, s)
                if not comm:
                    if key is not None:
                        recycle((key, s, g))
                    append("failed")
                    continue
                old = slot_handle.pop(s, 0)
                if old > 0:
                    release(old)
                if v:  # live value; a computed 0 is the tombstone
                    slot_handle[s] = -1
                    if not native_mirrors:  # mirror before the ack
                        inline_val_np[s] = v
                        inline_val_ok[s] = True
                else:
                    if not native_mirrors:
                        inline_val_ok[s] = False
                    if key is not None:  # tombstone: recycle the slot
                        recycle((key, s, g))
                inline.add(s)
                inline_row[s] = True
                if not native_mirrors:
                    vsn_row[s] = vs
                    vsn_ok_row[s] = True
                append(("ok", tuple(vs)) if ack else "failed")
        else:  # OP_GET batch
            ok_l = get_ok[j:j + n, e].tolist()
            found_l = found[j:j + n, e].tolist()
            val_l = value[j:j + n, e].tolist()
            vs_l = (vsn[j:j + n, e].tolist() if vsn is not None
                    else [None] * n)
            values = self.values
            inline = self._inline_slots[e]
            inline_val_np = self._inline_value_np[e]
            inline_val_ok = self._inline_value_ok[e]
            vsn_row = self._slot_vsn_np[e]
            vsn_ok_row = self._slot_vsn_ok[e]
            want_vsn = op.want_vsn
            for ok, fnd, v, vs, s in zip(ok_l, found_l, val_l, vs_l,
                                         op.slot):
                if ok and ack_reads:
                    if fnd and v != 0:
                        if s in inline:
                            out = v
                            if not native_mirrors:  # refresh mirror
                                inline_val_np[s] = v
                                inline_val_ok[s] = True
                        else:
                            out = values.get(v, NOTFOUND)
                    else:
                        out = NOTFOUND
                    if vs is not None and not native_mirrors:
                        vsn_row[s] = vs  # refresh fast mirror
                        vsn_ok_row[s] = True
                    append(("ok", out, tuple(vs)) if want_vsn
                           else ("ok", out))
                else:
                    append("failed")
        op.accum.fill(op.fut, op.pos, results,
                      self._safe_resolve)

    # -- completion-slab resolve (slab enqueue path, ARCH §12) -------------

    def _resolve_taken_slab(self, taken, planes, lanes, ack: bool,
                            ack_reads: bool,
                            native_mirrors: bool) -> int:
        """Resolve every taken entry through the per-flush COMPLETION
        SLAB: each result plane gathers through the flush's op lanes
        ONCE (``[R]`` records, R = taken rounds — one fancy index per
        plane instead of per-op scalar reads or a full ``[K, E]``
        tolist), then the entries walk their row segments with
        vectorized bookkeeping.  Exactly one slab fill (WAKE) per
        flush — ``stats()["completion_slab"]`` counts it, and
        tests/test_native_enqueue.py pins the one-wake-per-flush
        claim.  Scalar ops resolve as thin views over their single
        slab row.  Results and mirror slabs are identical to the
        per-op oracle loops."""
        committed, get_ok, found, value, vsn = planes
        ent_col, ent_row0, ent_len, n_rows, offs = lanes[:5]
        got = lists = None
        bounds = (self._shard_bounds(len(ent_col))
                  if self._native_enqueue is not None else None)
        if bounds is not None:
            # sharded completion-slab gather (ARCHITECTURE §16):
            # chunk [lo, hi) covers global slab rows [offs[lo],
            # offs[hi]) — the native gather AND its bulk tolist run
            # per chunk on the pool (both drop the GIL in C); the
            # main thread concatenates in chunk order, so the lanes
            # and lists are element-identical to the serial gather
            cm_u8, gk_u8, fn_u8 = (_u8view(committed),
                                   _u8view(get_ok), _u8view(found))
            val_c = np.ascontiguousarray(value, np.int32)
            vsn_c = np.ascontiguousarray(vsn, np.int32)
            n_desc = len(ent_col)

            def _gather_chunk(lo, hi):
                s = offs[lo]
                t = offs[hi] if hi < n_desc else n_rows
                g = self._native_enqueue.gather(
                    len(committed), committed.shape[1],
                    ent_col[lo:hi], ent_row0[lo:hi], ent_len[lo:hi],
                    cm_u8, gk_u8, fn_u8, val_c, vsn_c, t - s)
                return (g, [a.tolist() for a in g]) \
                    if g is not None else None

            chunks = self._shard_map(_gather_chunk, bounds)
            if all(c is not None for c in chunks):
                got = tuple(np.concatenate([c[0][i] for c in chunks])
                            for i in range(5))
                lists = [sum((c[1][i] for c in chunks), [])
                         for i in range(5)]
        if got is None and self._native_enqueue is not None:
            got = self._native_enqueue.gather(
                len(committed), committed.shape[1], ent_col,
                ent_row0, ent_len, _u8view(committed),
                _u8view(get_ok), _u8view(found),
                np.ascontiguousarray(value, np.int32),
                np.ascontiguousarray(vsn, np.int32), n_rows)
        if got is None:
            rows, cols = _lane_indices(ent_col, ent_row0, ent_len)
            got = (committed[rows, cols], get_ok[rows, cols],
                   found[rows, cols], value[rows, cols],
                   vsn[rows, cols])
        ok_lane, gok_lane, fnd_lane, val_lane, vsn_lane = got
        # plane→Python conversion happens ONCE per flush per lane
        # (bulk C tolist); every entry below slices plain lists —
        # the per-entry numpy slice + tolist pairs of the oracle
        # loops are gone entirely
        if lists is None:
            lists = [ok_lane.tolist(), gok_lane.tolist(),
                     fnd_lane.tolist(), val_lane.tolist(),
                     vsn_lane.tolist()]
        ok_l, gok_l, fnd_l, val_l, vs_l = lists
        self.completion_wakes += 1
        self.completion_rows += n_rows
        served = 0
        ei = 0
        for e, ops in taken:
            for op in ops:
                off = offs[ei]
                ei += 1
                n = op.n
                end = off + n
                if isinstance(op, _PendingBatch):
                    self._resolve_batch_slab(
                        e, op, ok_l[off:end], gok_l[off:end],
                        fnd_l[off:end], val_l[off:end],
                        vs_l[off:end], ack, ack_reads,
                        native_mirrors,
                        (ok_lane, gok_lane, fnd_lane, val_lane,
                         vsn_lane, off))
                else:
                    self._resolve_scalar_slab(
                        e, op, ok_l[off], gok_l[off], fnd_l[off],
                        val_l[off], tuple(vs_l[off]), ack, ack_reads,
                        native_mirrors)
                served += n
        return served

    def _resolve_batch_slab(self, e: int, op: _PendingBatch, comm_l,
                            gok_l, fnd_l, val_l, vs_l, ack: bool,
                            ack_reads: bool, native_mirrors: bool,
                            np_lanes) -> None:
        """One batch entry from its completion-slab segment — the
        slab-path form of :meth:`_resolve_batch` (identical results
        and byte-identical mirror slabs).  The segments arrive as
        PLAIN LIST slices of the flush's once-converted lanes, so the
        loop body is dict/list work only; ``np_lanes`` is the
        ``(ok, gok, fnd, val, vsn, off)`` numpy lane reference, read
        ONLY on the fallback-resolve mirror path (native mirrors —
        the default — already scattered on the C side).  The
        storage-class set/slab flips run once per entry over the
        committed subset; per-index numpy calls lose to plain loops
        at the tens-of-ops entry sizes this path sees (measured)."""
        n = op.n
        results: List[Any] = []
        append = results.append
        comm_slots: List[int] = []
        if op.kind in (eng.OP_PUT, eng.OP_CAS):
            slot_l = op.slot
            handle_l = op.handle
            gen_l = op.gen
            keys = op.keys if op.keys is not None else [None] * n
            slot_handle = self.slot_handle[e]
            recycle = self._recycle_pending[e].append
            self._recycle_dirty.add(e)
            release = self._release_handle
            pw = self._pending_writes[e]
            qh = self._queued_handle_writes[e]
            for i, comm in enumerate(comm_l):
                h = handle_l[i]
                s = slot_l[i]
                # every op un-notes (committed or not), exactly like
                # the oracle loop (``_unnote``: an unpaired un-note
                # must park reads on the device round, never
                # underflow)
                _unnote(pw, s)
                if h:
                    _unnote(qh, s)
                if not comm:
                    release(h)
                    if keys[i] is not None:
                        recycle((keys[i], s, gen_l[i]))
                    append("failed")
                    continue
                old = slot_handle.pop(s, 0)
                if old != h:
                    release(old)
                if h:
                    slot_handle[s] = h
                comm_slots.append(s)
                append(("ok", tuple(vs_l[i])) if ack else "failed")
            if comm_slots:
                # committed writes flip their slots to handle class:
                # set + slab adopt the committed subset in bulk; the
                # vsn mirror scatters in ROUND order (duplicate
                # slots: numpy fancy assignment keeps the last write,
                # which is what the sequential loop committed last) —
                # mirror-before-ack holds, the accum fill below is
                # the first client-visible effect
                self._inline_slots[e].difference_update(comm_slots)
                self._inline_np[e, comm_slots] = False
                if not native_mirrors:
                    ok_a, _g, _f, _v, vsn_a, off = np_lanes
                    okm = ok_a[off:off + n]
                    self._inline_value_ok[e, comm_slots] = False
                    self._slot_vsn_np[e, comm_slots] = \
                        vsn_a[off:off + n][okm]
                    self._slot_vsn_ok[e, comm_slots] = True
        elif op.kind == eng.OP_RMW:
            slot_l = op.slot
            gen_l = op.gen
            keys = op.keys if op.keys is not None else [None] * n
            slot_handle = self.slot_handle[e]
            release = self._release_handle
            recycle = self._recycle_pending[e].append
            self._recycle_dirty.add(e)
            pw = self._pending_writes[e]
            for i, comm in enumerate(comm_l):
                s = slot_l[i]
                _unnote(pw, s)
                if not comm:
                    if keys[i] is not None:
                        recycle((keys[i], s, gen_l[i]))
                    append("failed")
                    continue
                old = slot_handle.pop(s, 0)
                if old > 0:  # superseded host payload (-1 stays put)
                    release(old)
                if val_l[i]:  # live value; a computed 0 = tombstone
                    slot_handle[s] = -1
                else:
                    if keys[i] is not None:
                        recycle((keys[i], s, gen_l[i]))
                comm_slots.append(s)
                append(("ok", tuple(vs_l[i])) if ack else "failed")
            if comm_slots:
                self._inline_slots[e].update(comm_slots)
                self._inline_np[e, comm_slots] = True
                if not native_mirrors:
                    ok_a, _g, _f, val_a, vsn_a, off = np_lanes
                    okm = ok_a[off:off + n]
                    cvals = val_a[off:off + n][okm]
                    cvs = vsn_a[off:off + n][okm]
                    if len(set(comm_slots)) != len(comm_slots):
                        # duplicate slots in one RMW segment: live/
                        # tombstone interleavings are ROUND-ordered —
                        # only the sequential walk preserves which
                        # state the slot ends in
                        for s, v, vv in zip(comm_slots,
                                            cvals.tolist(),
                                            cvs.tolist()):
                            if v:
                                self._inline_value_np[e, s] = v
                                self._inline_value_ok[e, s] = True
                            else:
                                self._inline_value_ok[e, s] = False
                            self._slot_vsn_np[e, s] = vv
                            self._slot_vsn_ok[e, s] = True
                    else:
                        csl = np.asarray(comm_slots, np.int32)
                        live = cvals != 0
                        lsl = csl[live]
                        if lsl.size:
                            self._inline_value_np[e, lsl] = cvals[live]
                            self._inline_value_ok[e, lsl] = True
                        self._inline_value_ok[e, csl[~live]] = False
                        self._slot_vsn_np[e, csl] = cvs
                        self._slot_vsn_ok[e, csl] = True
        else:  # OP_GET segment
            want_vsn = op.want_vsn
            if not ack_reads:
                gok_l = [False] * n
            slot_l = op.slot
            inline = self._inline_slots[e]
            values = self.values
            served_slots: List[int] = []
            for i, okv in enumerate(gok_l):
                if not okv:
                    append("failed")
                    continue
                v = val_l[i]
                if fnd_l[i] and v != 0:
                    out = v if slot_l[i] in inline \
                        else values.get(v, NOTFOUND)
                else:
                    out = NOTFOUND
                served_slots.append(slot_l[i])
                append(("ok", out, tuple(vs_l[i])) if want_vsn
                       else ("ok", out))
            if not native_mirrors and served_slots:
                # served reads refresh the vsn mirror; reads of live
                # inline slots refresh the inline mirror (identical
                # values for a slot read twice in one segment — no
                # write can interleave inside one entry's round
                # range, so scatter order is moot)
                gok_a, fnd_a, val_a, vsn_a, off = (
                    np_lanes[1], np_lanes[2], np_lanes[3],
                    np_lanes[4], np_lanes[5])
                okm = gok_a[off:off + n]
                self._slot_vsn_np[e, served_slots] = \
                    vsn_a[off:off + n][okm]
                self._slot_vsn_ok[e, served_slots] = True
                sl_a = np.asarray(slot_l, np.intp)
                refr = okm & fnd_a[off:off + n] \
                    & (val_a[off:off + n] != 0) \
                    & self._inline_np[e, sl_a]
                if refr.any():
                    rsl = sl_a[refr]
                    self._inline_value_np[e, rsl] = \
                        val_a[off:off + n][refr]
                    self._inline_value_ok[e, rsl] = True
        op.accum.fill(op.fut, op.pos, results, self._safe_resolve)

    def _resolve_scalar_slab(self, e: int, op: _PendingOp, comm: bool,
                             gok: bool, fnd: bool, v: int, vs,
                             ack: bool, ack_reads: bool,
                             native_mirrors: bool) -> None:
        """One scalar op from its completion-slab row — the thin
        view-future resolve: the client's Future resolves from the
        gathered row alone, so a flush with scalar ops never converts
        the full ``[K, E]`` result planes to Python lists.  Logic is
        the per-op oracle loop's, verbatim."""
        slot_handle = self.slot_handle[e]
        if op.kind in (eng.OP_PUT, eng.OP_CAS):
            if comm:
                self._unnote_write(e, op.slot)
                if op.handle:
                    self._unnote_handle_write(e, op.slot)
                old = slot_handle.pop(op.slot, 0)
                if old != op.handle:
                    self._release_handle(old)
                if op.handle:
                    slot_handle[op.slot] = op.handle
                self._inline_slots[e].discard(op.slot)
                self._inline_np[e, op.slot] = False
                if not native_mirrors:
                    self._inline_value_ok[e, op.slot] = False
                    self._slot_vsn_np[e, op.slot] = vs
                    self._slot_vsn_ok[e, op.slot] = True
                self._safe_resolve(op.fut,
                                   ("ok", vs) if ack else "failed")
            else:
                self._fail_op(e, op)
        elif op.kind == eng.OP_RMW:
            if comm:
                self._unnote_write(e, op.slot)
                old = slot_handle.pop(op.slot, 0)
                if old > 0:
                    self._release_handle(old)
                if v:
                    slot_handle[op.slot] = -1
                    if not native_mirrors:
                        self._inline_value_np[e, op.slot] = v
                        self._inline_value_ok[e, op.slot] = True
                else:
                    if not native_mirrors:
                        self._inline_value_ok[e, op.slot] = False
                    if op.key is not None:
                        self._queue_recycle(e, (op.key, op.slot,
                                                op.gen))
                self._inline_slots[e].add(op.slot)
                self._inline_np[e, op.slot] = True
                if not native_mirrors:
                    self._slot_vsn_np[e, op.slot] = vs
                    self._slot_vsn_ok[e, op.slot] = True
                self._safe_resolve(op.fut,
                                   ("ok", vs) if ack else "failed")
            else:
                self._fail_op(e, op)
        else:  # OP_GET
            if gok and ack_reads:
                if fnd and v != 0:
                    if op.slot in self._inline_slots[e]:
                        out = v
                        if not native_mirrors:
                            self._inline_value_np[e, op.slot] = v
                            self._inline_value_ok[e, op.slot] = True
                    else:
                        out = self.values.get(v, NOTFOUND)
                else:
                    out = NOTFOUND
                if not native_mirrors:
                    self._slot_vsn_np[e, op.slot] = vs
                    self._slot_vsn_ok[e, op.slot] = True
                self._safe_resolve(
                    op.fut, ("ok", out, vs) if op.want_vsn
                    else ("ok", out))
            else:
                self._fail_op(e, op)

    def _resolve_flush(self, taken, planes, ack: bool = True,
                       ack_reads: bool = True, op_planes=None,
                       rec=None, fid: int = 0,
                       t_join: float = 0.0, lanes=None) -> int:
        """Resolve every taken op from the result planes.  With
        ``ack=False`` (the WAL write failed) committed writes keep
        their device-side bookkeeping — the commit is real — but
        resolve 'failed': an ack may never outrun the disk.  Reads
        don't need the disk, so they survive ``ack=False``;
        ``ack_reads=False`` fails them too — the replication group
        uses it when the HOST quorum was lost, where serving a read
        would mean a minority/deposed leader answering clients.

        ``op_planes`` is the launch's host (kind, slot) op-plane pair:
        when present and the native resolve kernel is loaded, one C
        pass scatters every committed mirror update
        (``_slot_vsn``/``_inline_value`` slabs, leased-GET refreshes)
        in the loop's exact per-column round order, and the per-op
        loops below skip their mirror writes — byte-identical slabs
        either way.

        ``lanes`` is the flush's pending-slab record from the slab
        enqueue path — ``(ent_col, ent_row0, ent_len, n_rows, offs,
        ent_meta)``: per-entry run descriptors (ensemble column,
        first plane row, run length), the taken round count, each
        entry's first slab row, and the SLO stamp columns (None with
        obs off).  When present (RETPU_NATIVE_ENQUEUE on), resolution
        runs through the per-flush COMPLETION SLAB — every result
        plane gathered through the runs in ONE pass, one wake per
        flush, per-entry vectorized bookkeeping — instead of the
        per-op loops below, with identical results and mirror slabs
        (tests/test_native_enqueue.py sweeps the equivalence)."""
        # per-op SLO settle stamp: the moment this flush's outcome is
        # known to the host.  On a replicated leader this method runs
        # AFTER the host-quorum decision (_settle_batch), so ack
        # stamps land after quorum settle by construction.
        t_settle = time.perf_counter() if self._obs else 0.0
        committed, get_ok, found, value, vsn = planes

        if committed is None:  # k == 0: election-only launch, no ops
            assert not taken, "ops taken but no result planes"
            self._drain_recycles()
            return 0
        native_mirrors = False
        if (self._native_resolve is not None and taken
                and op_planes is not None
                and op_planes[0] is not None
                and op_planes[1] is not None):
            # the arm's share of the resolve half (see
            # _launch_resolve): named for the arm once it is known
            with self.spans.span("resolve_fallback", rec,
                                 label="svc.scatter_mirrors") as arm:
                n_cols = len(taken)
                cols = np.fromiter((e for e, _ops in taken), np.int32,
                                   n_cols)
                kcounts = np.fromiter(
                    (sum(op.n for op in ops) for _e, ops in taken),
                    np.int32, n_cols)
                bounds = self._shard_bounds(n_cols)
                if bounds is None:
                    native_mirrors = self._native_resolve.scatter_mirrors(
                        self.n_ens, self.n_slots, op_planes[0],
                        op_planes[1], committed, get_ok, found, value,
                        vsn, cols, kcounts, ack_reads,
                        (eng.OP_PUT, eng.OP_CAS, eng.OP_GET, eng.OP_RMW),
                        self._slot_vsn_np, self._slot_vsn_ok,
                        self._inline_value_np, self._inline_value_ok,
                        self._inline_np)
                else:
                    # sharded mirror scatter (ARCHITECTURE §16): chunks
                    # partition the taken COLUMNS, and every ensemble
                    # column appears in `taken` at most once, so chunk
                    # writes land on disjoint mirror rows; a chunk that
                    # falls back just leaves its rows for the Python
                    # mirror walk (state-identical either way)
                    def _scatter_chunk(lo, hi):
                        return self._native_resolve.scatter_mirrors(
                            self.n_ens, self.n_slots, op_planes[0],
                            op_planes[1], committed, get_ok, found,
                            value, vsn, cols[lo:hi], kcounts[lo:hi],
                            ack_reads,
                            (eng.OP_PUT, eng.OP_CAS, eng.OP_GET,
                             eng.OP_RMW),
                            self._slot_vsn_np, self._slot_vsn_ok,
                            self._inline_value_np, self._inline_value_ok,
                            self._inline_np)
                    native_mirrors = all(self._shard_map(_scatter_chunk,
                                                         bounds))
                if native_mirrors:
                    arm.name = "resolve_native"

        if lanes is not None and self._enq_slab and taken \
                and vsn is not None:
            # COMPLETION-SLAB path (ARCHITECTURE §12): the whole
            # flush's results gather through the op lanes — one
            # fancy index per plane, ONE wake — and entries resolve
            # from their slab row segments with vectorized
            # bookkeeping; the per-op loops below stay the oracle.
            served = self._resolve_taken_slab(taken, planes, lanes,
                                              ack, ack_reads,
                                              native_mirrors)
            self.ops_served += served
            if self._obs:
                with self.spans.span("obs", rec):
                    self._obs_account_taken(
                        taken, committed, t_settle, fid, t_join,
                        ent_meta=lanes[5] if len(lanes) > 5 else None)
            self._drain_recycles()
            return served

        # Per-op resolve loop: convert the result planes to plain
        # Python lists ONCE (C-speed bulk conversion) — per-op numpy
        # scalar indexing costs ~5x more than list indexing at
        # thousands of ops per flush.  Batch-only flushes (the keyed
        # vectorized surface) never touch the full planes per op, so
        # the conversion is LAZY: built only when a scalar op exists.
        committed_l = get_ok_l = found_l = value_l = vsn_l = None
        if any(not isinstance(op, _PendingBatch)
               for _e, ops in taken for op in ops):
            committed_l = committed.tolist()
            get_ok_l = get_ok.tolist()
            found_l = found.tolist()
            value_l = value.tolist()
            vsn_l = vsn.tolist()
        served = 0
        puts = (eng.OP_PUT, eng.OP_CAS)
        for e, ops in taken:
            slot_handle = self.slot_handle[e]
            j = -1
            for op in ops:
                if isinstance(op, _PendingBatch):
                    self._resolve_batch(e, j + 1, op, planes, ack,
                                        ack_reads, native_mirrors)
                    served += op.n
                    j += op.n
                    continue
                j += 1
                served += 1
                if op.kind in puts:
                    if committed_l[j][e]:
                        self._unnote_write(e, op.slot)
                        if op.handle:
                            self._unnote_handle_write(e, op.slot)
                        # Release the payload this write superseded
                        # (rounds resolve in device order, so the last
                        # committed handle per slot survives).
                        old = slot_handle.pop(op.slot, 0)
                        if old != op.handle:
                            self._release_handle(old)
                        if op.handle:
                            slot_handle[op.slot] = op.handle
                        # a committed put/CAS flips a device-native
                        # slot back to handle storage
                        self._inline_slots[e].discard(op.slot)
                        self._inline_np[e, op.slot] = False
                        # mirror-before-ack: a fast read issued after
                        # this future resolves must see the write
                        # (the native pass already scattered it)
                        if not native_mirrors:
                            self._inline_value_ok[e, op.slot] = False
                            self._slot_vsn_np[e, op.slot] = \
                                vsn_l[j][e]
                            self._slot_vsn_ok[e, op.slot] = True
                        self._safe_resolve(
                            op.fut, ("ok", tuple(vsn_l[j][e]))
                            if ack else "failed")
                    else:
                        self._fail_op(e, op)
                elif op.kind == eng.OP_RMW:
                    if committed_l[j][e]:
                        self._unnote_write(e, op.slot)
                        old = slot_handle.pop(op.slot, 0)
                        if old > 0:  # superseded host payload
                            self._release_handle(old)
                        # sentinel: LIVE value committed device-side
                        # (no host payload) — blocks recycling like a
                        # live handle, releases as a no-op.  A
                        # computed 0 is the tombstone: no sentinel,
                        # and the slot recycles like a committed
                        # delete (the host fallback's ksafe_delete
                        # arm recycles; the device arm must match).
                        if value_l[j][e]:
                            slot_handle[op.slot] = -1
                            if not native_mirrors:
                                self._inline_value_np[e, op.slot] = \
                                    value_l[j][e]
                                self._inline_value_ok[e, op.slot] = \
                                    True
                        else:
                            if not native_mirrors:
                                self._inline_value_ok[e, op.slot] = \
                                    False
                            if op.key is not None:
                                self._queue_recycle(
                                    e, (op.key, op.slot, op.gen))
                        self._inline_slots[e].add(op.slot)
                        self._inline_np[e, op.slot] = True
                        if not native_mirrors:
                            self._slot_vsn_np[e, op.slot] = \
                                vsn_l[j][e]
                            self._slot_vsn_ok[e, op.slot] = True
                        self._safe_resolve(
                            op.fut, ("ok", tuple(vsn_l[j][e]))
                            if ack else "failed")
                    else:
                        self._fail_op(e, op)
                else:
                    if get_ok_l[j][e] and ack_reads:
                        v = value_l[j][e]
                        if found_l[j][e] and v != 0:
                            # device-native slots carry the value
                            # itself, not a payload handle
                            if op.slot in self._inline_slots[e]:
                                out = v
                                # refresh the fast path's inline
                                # mirror from the device read
                                if not native_mirrors:
                                    self._inline_value_np[
                                        e, op.slot] = v
                                    self._inline_value_ok[
                                        e, op.slot] = True
                            else:
                                out = self.values.get(v, NOTFOUND)
                        else:
                            out = NOTFOUND
                        # vsn is the object's — a tombstone's real
                        # version rides along with NOTFOUND, so CAS
                        # chains (ksafe_delete → kupdate) work.  The
                        # device read also refreshes the fast path's
                        # vsn mirror (repopulating it after the
                        # post-election invalidation).
                        if not native_mirrors:
                            self._slot_vsn_np[e, op.slot] = \
                                vsn_l[j][e]
                            self._slot_vsn_ok[e, op.slot] = True
                        self._safe_resolve(
                            op.fut, ("ok", out, tuple(vsn_l[j][e]))
                            if op.want_vsn else ("ok", out))
                    else:
                        self._fail_op(e, op)
        self.ops_served += served
        if self._obs and taken:
            with self.spans.span("obs", rec):
                self._obs_account_taken(taken, committed, t_settle,
                                        fid, t_join)
        self._drain_recycles()
        return served
