"""Sharding the consensus engine over a ('ens', 'peer') device mesh.

The reference scales by running ensembles/peers across Erlang nodes
with disterl messaging (SURVEY §2.7).  The TPU-native layout:

- **'ens' axis** — ensembles are embarrassingly parallel (independent
  consensus groups); the E axis shards across devices with no
  cross-device traffic (the DP analog).
- **'peer' axis** — one ensemble's M peer replicas can live on
  different chips; quorum vote counting, proposal-epoch broadcast, and
  newest-object selection become ``psum``/``pmax`` collectives over the
  'peer' mesh axis riding ICI (the TP analog; the msg.erl
  quorum fan-out/collect, riak_ensemble_msg.erl:85-97,319-332, as an
  all-reduce).

Cross-host (DCN) deployment uses the same code: ``jax.make_mesh`` over
multi-host device arrays gives a mesh whose 'ens' dim spans hosts —
ensembles never need DCN collectives, and peer-axis collectives stay
intra-slice by construction (put the 'peer' dim innermost).

``ShardedEngine`` wraps the :mod:`riak_ensemble_tpu.ops.engine` kernels
in ``shard_map`` with the peer axis sharded; inputs/outputs that carry
a peer axis use spec ('ens', 'peer'), per-ensemble vectors use
('ens',) and are replicated along 'peer'.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from riak_ensemble_tpu.ops import engine as eng


def _default_exp(kind, exp_epoch, exp_seq):
    """shard_map takes concrete operands: absent CAS expected-version
    arrays materialize as zeros of the op-matrix shape."""
    if exp_epoch is None:
        exp_epoch = jnp.zeros(kind.shape, kind.dtype)
    if exp_seq is None:
        exp_seq = jnp.zeros(kind.shape, kind.dtype)
    return exp_epoch, exp_seq


def make_mesh(n_ens: int, n_peer: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of shape (ens=n_ens, peer=n_peer).

    'peer' is the innermost (fastest-varying) mesh dim so peer-axis
    collectives map to nearest-neighbor ICI links.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    assert devs.size >= n_ens * n_peer, \
        f"need {n_ens * n_peer} devices, have {devs.size}"
    grid = devs[: n_ens * n_peer].reshape(n_ens, n_peer)
    return Mesh(grid, ("ens", "peer"))


# The canonical sharded-pytree layout lives next to the NamedTuples it
# describes (ops/engine.state_specs and friends) so the single-shard
# and mesh paths can never drift apart — these module aliases keep the
# historical names for existing callers.
_STATE_SPECS = eng.state_specs()
_SCAN_RESULT_SPECS = eng.scan_result_specs()


def shard_active_columns(active: np.ndarray, n_ens: int,
                         n_shards: int, a_min: int
                         ) -> Tuple[list, int]:
    """Split a GLOBAL active-column index set into per-ens-shard LOCAL
    index lists with one common pow2 bucket width.

    The mesh keeps E in ``n_shards`` contiguous blocks of
    ``E/n_shards`` rows (NamedSharding over the 'ens' axis), so a
    global column index ``c`` lives on shard ``c // e_loc`` at local
    index ``c % e_loc``.  Compaction-aware sharding computes the |A|
    bucket PER SHARD — every shard packs the same ``a_width`` columns
    (pow2 ≥ the busiest shard's count, floored at ``a_min``, capped at
    ``e_loc``) so the shard_map'd packer sees one static shape while
    each shard's d2h payload stays local.

    Returns ``(per_shard, a_width)``: ``per_shard[s]`` is an int32
    array of ≤ ``a_width`` LOCAL indices (the caller pads to
    ``a_width``); ``a_width == e_loc`` means no compaction wins on
    this flush (every shard at full width).
    """
    e_loc = n_ens // n_shards
    active = np.asarray(active, np.int32)
    shard_of = active // e_loc
    per_shard = [active[shard_of == s] - s * e_loc
                 for s in range(n_shards)]
    busiest = max((p.size for p in per_shard), default=0)
    a_width = 1 << (max(busiest, a_min, 1) - 1).bit_length()
    return per_shard, min(a_width, e_loc)


def _forward_cache_size(wrapper, jitted) -> None:
    """Expose the jitted program's compile-cache probe on a plain
    wrapper function: ``obs.CompileWatch`` detects compiles via
    ``fn._cache_size()`` and silently passes through callables that
    lack it — a mesh step without this forward would serve compiles
    invisibly (the satellite-1 contract is CompileWatch-assertable
    zero serve-phase compiles on the mesh path)."""
    cs = getattr(jitted, "_cache_size", None)
    if cs is not None:
        wrapper._cache_size = cs


class ShardedEngine:
    """Engine kernels shard_map'd over a ('ens', 'peer') mesh — the
    first-class mesh serving engine.

    E must divide by mesh 'ens' size; M by mesh 'peer' size (pad views
    with absent peers if needed — all-zero view columns are inert).

    The fused steps are INSTANCE attributes: ``full_step_slab`` and
    ``full_step_sliced_slab`` (and their ``_donate`` twins), the
    jitted ``(state, op slab, up) -> (state, packed vector)`` programs
    the service launches (the step and the pack of its results, ONE
    program a launch), and the per-plane ``full_step`` /
    ``full_step_donate`` the slab form is compared against (plain
    wrappers that default absent CAS planes and forward
    ``_cache_size`` so ``CompileWatch`` sees mesh compiles).  The
    SLICED step gathers INSIDE ``shard_map``: every shard takes its
    own local rows by the local indices of its own block of the slab
    (``ops/engine.py`` "The op slab"), steps ``[K, a_loc]`` and
    scatters back, so no row crosses a chip and nothing is resharded;
    ``won`` and the result planes are ``n_shards * a_loc`` wide, one
    block per shard, and each shard packs its own block as it is.
    """

    def __init__(self, mesh: Mesh) -> None:
        self.mesh = mesh
        ax = "peer" if mesh.shape["peer"] > 1 else None

        def smap(fn, in_specs, out_specs, donate=False):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False),
                donate_argnums=(0,) if donate else ())

        self._elect = smap(
            lambda st, el, ca, up: eng.elect_step(st, el, ca, up,
                                                  axis_name=ax),
            (_STATE_SPECS, P("ens"), P("ens"), P("ens", "peer")),
            (_STATE_SPECS, P("ens")))
        self._kv = smap(
            lambda st, k, sl, v, lz, up, xe, xs: eng.kv_step_scan(
                st, k, sl, v, lz, up, axis_name=ax, exp_epoch=xe,
                exp_seq=xs),
            (_STATE_SPECS, P(None, "ens"), P(None, "ens"), P(None, "ens"),
             P(None, "ens"), P("ens", "peer"), P(None, "ens"),
             P(None, "ens")),
            (_STATE_SPECS, _SCAN_RESULT_SPECS))
        _full_in = (_STATE_SPECS, P("ens"), P("ens"), P(None, "ens"),
                    P(None, "ens"), P(None, "ens"), P(None, "ens"),
                    P("ens", "peer"), P(None, "ens"), P(None, "ens"))
        _full_out = (_STATE_SPECS, P("ens"), _SCAN_RESULT_SPECS)

        def _full_body(st, el, ca, k, sl, v, lz, up, xe, xs):
            return eng.full_step(st, el, ca, k, sl, v, lz, up,
                                 axis_name=ax, exp_epoch=xe, exp_seq=xs)

        self._full = smap(_full_body, _full_in, _full_out)
        self._full_donate = smap(_full_body, _full_in, _full_out,
                                 donate=True)

        #: where the served launch puts its two host operands: the
        #: step's own specs, so the dispatch places nothing
        self.slab_sharding = NamedSharding(mesh, P(None, "ens"))
        self.up_sharding = NamedSharding(mesh, P("ens", "peer"))
        _slab_in = (_STATE_SPECS, P(None, "ens"), P("ens", "peer"))
        rep = NamedSharding(mesh, P())
        shard_wise = bool(self.pack_shards)

        def served(sliced: bool, donate: bool):
            """One served program, ``(state, slab, up, want_vsn[,
            gather]) -> (state, flat)``: engine's step body over each
            shard's block of the slab under shard_map, and
            ``engine.pack_results`` of what it returned in the SAME
            jitted program (``want_vsn`` and the pack-gather's width
            ``gather`` static, as in engine's own).

            SHARD-WISE ('peer' unsharded): the pack runs per shard
            under the same shard_map: each device bit-packs its own
            block's results with its own LOCAL gather (the index row
            of its own block of the slab), and the vector is the
            per-shard vectors in shard order
            (``batched_host.unpack_results_sharded`` inverts it);
            nothing crosses 'ens'.  GATHERED (the rest): the step's
            result planes leave shard_map with MIXED shardings
            ('ens'-sharded [K, E] planes, peer-sharded corrupt masks);
            raveling those directly leaves GSPMD no expressible output
            sharding and it rematerializes per operand
            (``spmd_partitioner`` warns of each), so each is
            constrained fully replicated first (ordinary all-gathers
            over ICI; the vector is fetched to the host anyway) and
            the pack runs replicated."""
            def program(st, slab, up, want_vsn, gather=0):
                block = dict(sliced=sliced, gather=gather, axis_name=ax)

                def over_shards(body, out_specs):
                    return jax.shard_map(
                        body, mesh=mesh, in_specs=_slab_in,
                        out_specs=out_specs, check_vma=False)(st, slab, up)

                if shard_wise:
                    return over_shards(
                        functools.partial(eng._launch_body,
                                          want_vsn=want_vsn, **block),
                        (_STATE_SPECS, P("ens")))
                st, won, res = over_shards(
                    functools.partial(eng._slab_step_body, **block),
                    _full_out)

                def con(x):
                    return jax.lax.with_sharding_constraint(x, rep)

                aidx = eng.pack_gather_index(slab, gather)
                return st, eng.pack_results(
                    con(won), jax.tree.map(con, res), want_vsn,
                    None if aidx is None else con(aidx))

            return jax.jit(
                program,
                static_argnames=(("want_vsn",) if sliced
                                 else ("want_vsn", "gather")),
                donate_argnums=(0,) if donate else ())

        # the jitted programs as they are (`_cache_size` is their own)
        self.full_step_slab = served(False, donate=False)
        self.full_step_slab_donate = served(False, donate=True)
        self.full_step_sliced_slab = served(True, donate=False)
        self.full_step_sliced_slab_donate = served(True, donate=True)
        # the per-plane reference steps (see class docstring)
        self.full_step = self._make_step(self._full)
        self.full_step_donate = self._make_step(self._full_donate)
        self._reconfig = smap(
            lambda st, pr, nv, up: eng.reconfig_step(st, pr, nv, up,
                                                     axis_name=ax),
            (_STATE_SPECS, P("ens"), P("ens", "peer"), P("ens", "peer")),
            (_STATE_SPECS, P("ens"), P("ens")))
        self._reconfig_propose = smap(
            lambda st, pr, nv, vsn, up: eng.reconfig_propose(
                st, pr, nv, vsn, up, axis_name=ax),
            (_STATE_SPECS, P("ens"), P("ens", "peer"), P("ens"),
             P("ens", "peer")),
            (_STATE_SPECS, P("ens")))
        self._reconfig_transition = smap(
            lambda st, run, up: eng.reconfig_transition(
                st, run, up, axis_name=ax),
            (_STATE_SPECS, P("ens"), P("ens", "peer")),
            (_STATE_SPECS, P("ens")))
        self._exchange = smap(
            lambda st, run, up: eng.exchange_step(st, run, up,
                                                  axis_name=ax),
            (_STATE_SPECS, P("ens"), P("ens", "peer")),
            (_STATE_SPECS, P("ens", "peer"), P("ens")))
        self._verify = smap(
            lambda st: eng.verify_trees(st, axis_name=ax),
            (_STATE_SPECS,),
            (P("ens", "peer"), P("ens", "peer")))
        self._rebuild = smap(
            eng.rebuild_trees,
            (_STATE_SPECS, P("ens", "peer")),
            _STATE_SPECS)
        self._reset = smap(
            eng.reset_rows,
            (_STATE_SPECS, P("ens"), P("ens", "peer")),
            _STATE_SPECS)
        # Placement canonicalizer: shard_state routes host-built
        # states through this identity program so they land on the
        # EXACT sharding the step programs emit.  A device_put with
        # the spelled-out specs places equivalently but spells the
        # spec differently (GSPMD canonicalizes size-1 axes and
        # trailing Nones away), and the differing cache key would
        # force a first-flush recompile after warmup.
        self._canon = smap(lambda st: st, (_STATE_SPECS,),
                           _STATE_SPECS)
        #: init_state's programs by the shard's shape and views
        self._init_programs: dict = {}

    def _make_step(self, jitted):
        """Wrap a shard_map'd fused-step program in the serving-step
        call convention (keyword CAS planes, defaulted to zeros) with
        ``_cache_size`` forwarded for CompileWatch."""
        def step(state, elect, cand, kind, slot, val, lease_ok, up,
                 exp_epoch=None, exp_seq=None):
            exp_epoch, exp_seq = _default_exp(kind, exp_epoch, exp_seq)
            return jitted(state, elect, cand, kind, slot, val,
                          lease_ok, up, exp_epoch, exp_seq)
        _forward_cache_size(step, jitted)
        return step

    # -- placement ---------------------------------------------------------

    @property
    def n_ens_shards(self) -> int:
        """Number of shards along the 'ens' mesh axis."""
        return int(self.mesh.shape["ens"])

    @property
    def n_peer_shards(self) -> int:
        """Number of shards along the 'peer' mesh axis."""
        return int(self.mesh.shape["peer"])

    @property
    def pack_shards(self) -> int:
        """Number of 'ens' shards the SHARD-WISE result pack runs
        over: >1 only where the 'peer' axis is unsharded (each device
        then holds complete ``[e_loc, M, ...]`` rows, so a shard packs
        its own results with no cross-device traffic at all).  0 = the
        gathered pack (a sharded 'peer' axis: the corrupt plane spans
        peer shards there; or one 'ens' shard)."""
        if self.n_peer_shards != 1:
            return 0
        n = self.n_ens_shards
        return n if n > 1 else 0

    def shard_state(self, state: eng.EngineState) -> eng.EngineState:
        """Place a host-built state (a restored checkpoint, a test's)
        onto the mesh with engine specs — via the identity program, so
        the placement's cache key matches the step outputs' bit for
        bit (see ``_canon`` above)."""
        return self._canon(state)

    def init_program(self, n_ensembles: int, n_peers: int, n_slots: int,
                     n_views: int = 2,
                     views: Optional[Sequence[Sequence[int]]] = None):
        """The jitted program :meth:`init_state` runs: no operand,
        :func:`engine.init_state` for a shard's own block as its body,
        the state under the engine's specs as its result."""
        n_e, n_p = self.n_ens_shards, self.n_peer_shards
        assert n_ensembles % n_e == 0
        assert n_peers % n_p == 0
        e_loc, m_loc = n_ensembles // n_e, n_peers // n_p
        vm = eng.init_view_mask(n_peers, n_views, views)       # [V, M]
        key = (e_loc, m_loc, n_slots, n_views, vm.tobytes())
        if key not in self._init_programs:
            def body():
                st = eng.init_state(e_loc, m_loc, n_slots, n_views)
                # a shard's own peers of the views (all of them where
                # the 'peer' axis is not sharded)
                mine = jax.lax.dynamic_slice_in_dim(
                    jnp.asarray(vm), jax.lax.axis_index("peer") * m_loc,
                    m_loc, axis=1)
                return st._replace(view_mask=jnp.broadcast_to(
                    mine, (e_loc, n_views, m_loc)))

            self._init_programs[key] = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(),
                out_specs=_STATE_SPECS, check_vma=False))
        return self._init_programs[key]

    def init_state(self, n_ensembles: int, n_peers: int, n_slots: int,
                   n_views: int = 2,
                   views: Optional[Sequence[Sequence[int]]] = None
                   ) -> eng.EngineState:
        """Fresh state, BUILT where it lives: every device allocates
        its own block of every plane once, and no device ever holds a
        whole one (a ring that needs the mesh is one that a single
        device cannot build).  The result carries the sharding the
        step programs emit, as :meth:`shard_state`'s does."""
        return self.init_program(n_ensembles, n_peers, n_slots, n_views,
                                 views)()

    # -- steps -------------------------------------------------------------

    def elect_step(self, state, elect, cand, up):
        return self._elect(state, elect, cand, up)

    def kv_step_scan(self, state, kind, slot, val, lease_ok, up,
                     exp_epoch=None, exp_seq=None):
        """Ops are [K, E]-shaped (a scan of K rounds), matching
        :func:`riak_ensemble_tpu.ops.engine.kv_step_scan`.  shard_map
        takes concrete operands, so absent CAS versions materialize as
        zeros here."""
        exp_epoch, exp_seq = _default_exp(kind, exp_epoch, exp_seq)
        return self._kv(state, kind, slot, val, lease_ok, up,
                        exp_epoch, exp_seq)

    # full_step / full_step_slab / full_step_sliced_slab (+ _donate)
    # are instance attributes built in __init__ — see the class
    # docstring.

    def reconfig_step(self, state, propose, new_view, up):
        """Joint-consensus membership change over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.reconfig_step`)."""
        return self._reconfig(state, propose, new_view, up)

    def reconfig_propose(self, state, propose, new_view, vsn, up):
        """General views-list cons over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.reconfig_propose`)."""
        return self._reconfig_propose(state, propose, new_view, vsn, up)

    def reconfig_transition(self, state, run, up):
        """Views-list collapse over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.reconfig_transition`)."""
        return self._reconfig_transition(state, run, up)

    def exchange_step(self, state, run, up):
        """Anti-entropy sweep over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.exchange_step`)."""
        return self._exchange(state, run, up)

    def verify_trees(self, state):
        """Integrity sweep over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.verify_trees`)."""
        return self._verify(state)

    def rebuild_trees(self, state, mask):
        """Tree rebuild over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.rebuild_trees`)."""
        return self._rebuild(state, mask)

    def reset_rows(self, state, mask, new_view):
        """Ensemble-row recycle over the mesh
        (:func:`riak_ensemble_tpu.ops.engine.reset_rows`)."""
        return self._reset(state, mask, new_view)


def mesh_engine(n_devices: Optional[int] = None, n_peer: int = 1,
                devices: Optional[Sequence] = None) -> ShardedEngine:
    """Build a serving :class:`ShardedEngine` over the first
    ``n_devices`` local devices (default: all of them), ``n_peer`` of
    the mesh innermost on the 'peer' axis.

    The svcnode/bench entry point (``--mesh-devices``).  On a CPU box
    the devices come from ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` — which must be set BEFORE jax initializes its
    backend, so a too-small device count fails here with the knob
    named rather than deep inside a shard_map.
    """
    devs = list(devices if devices is not None else jax.devices())
    want = len(devs) if n_devices is None else int(n_devices)
    if want > len(devs):
        raise ValueError(
            f"mesh_engine: asked for {want} devices but jax sees only "
            f"{len(devs)} ({devs[0].platform}); on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={want} in the "
            "environment BEFORE the process imports jax")
    if want % n_peer:
        raise ValueError(
            f"mesh_engine: {want} devices do not divide into "
            f"n_peer={n_peer} columns")
    return ShardedEngine(make_mesh(want // n_peer, n_peer,
                                   devices=devs[:want]))
