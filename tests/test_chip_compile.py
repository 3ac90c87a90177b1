"""Compile the served path's programs for a DESCRIBED TPU v5e.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached: what it refuses here (a kernel
Mosaic cannot tile, a program that does not fit 16 GB) would be
refused on the chip, at no chip time.  Nothing runs — these tests say
nothing about results or times.

This is the ONLY file that describes a topology, and it does so inside
a module-scoped fixture: one process at a time may load the TPU
library, and under pytest-xdist only the worker that is handed this
file may do it (never at import, never in a skipif/parametrize).
"""

import functools
import os
import re
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.ops import hash as hashk  # noqa: E402
from riak_ensemble_tpu.ops import pallas_quorum  # noqa: E402

E, M, S, V = 10_000, 5, 128, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The TPU compiler runs a thread per core it may use.  Under the
    # driver's six xdist workers that starved the suite's wall-clock
    # tests (leases, elections: three full runs, three such failures;
    # none without this file).  Threads started from here on inherit
    # this mask, and the compiler's are started below: two cores.
    cores = os.sched_getaffinity(0)
    os.sched_setaffinity(0, set(sorted(cores)[:2]))
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        os.sched_setaffinity(0, cores)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the persistent cache
    # but can never be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    os.sched_setaffinity(0, cores)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(shapes, sharding):
    """A pytree of ShapeDtypeStructs with ``sharding`` (one sharding,
    or a matching pytree of them) attached to every leaf."""
    if not isinstance(sharding, jax.sharding.Sharding):
        # the shardings lead: they name a row plane (`tree_rows`) that
        # a state of 2,032 slots or fewer has not, None in `shapes`
        return jax.tree.map(
            lambda s, x: x if x is None else jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=s),
            sharding, shapes)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        shapes)


def _step_args(e, m, k, place):
    """(elect, cand, kind, slot, val, lease, up, exp_epoch, exp_seq) as
    placed shapes.  ``place(name, shape, dtype)`` returns the
    ShapeDtypeStruct for one operand."""
    ops = [("elect", (e,), jnp.bool_), ("cand", (e,), jnp.int32),
           ("kind", (k, e), jnp.int32), ("slot", (k, e), jnp.int32),
           ("val", (k, e), jnp.int32), ("lease", (k, e), jnp.bool_),
           ("up", (e, m), jnp.bool_),
           ("exp_epoch", (k, e), jnp.int32),
           ("exp_seq", (k, e), jnp.int32)]
    return [place(*op) for op in ops]


def _on(sharding):
    """``place`` for :func:`_step_args`: everything on one sharding."""
    def place(_name, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return place


def _quorum_shapes(sharding):
    valid = jax.ShapeDtypeStruct((E, M), jnp.bool_, sharding=sharding)
    mask = jax.ShapeDtypeStruct((E, V, M), jnp.bool_, sharding=sharding)
    return valid, mask


def test_epallas_kernel_compiles_for_v5e(one_chip):
    valid, mask = _quorum_shapes(one_chip)
    compiled = pallas_quorum.quorum_met_epallas.lower(
        valid, valid, mask, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_kernel_compiles_for_v5e(one_chip):
    valid, _ = _quorum_shapes(one_chip)
    shared = jax.ShapeDtypeStruct((V, M), jnp.bool_, sharding=one_chip)
    self_idx = jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip)
    compiled = pallas_quorum.quorum_met_pallas.lower(
        valid, valid, shared, self_idx, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@functools.cache
def _sliced_step(one_chip, k, a):
    """The donated sliced step of the 10,000 x 5 x 128 state at one
    (K, A) bucket, compiled once for the tests that read it."""
    state = _placed(jax.eval_shape(lambda: eng.init_state(E, M, S)),
                    one_chip)
    place = _on(one_chip)
    return eng.full_step_sliced_slab_donate.lower(
        state, place("slab", (4 + 5 * k, a), jnp.int32),
        place("up", (E, M), jnp.bool_), want_vsn=True).compile()


def test_sliced_donated_step_compiles_at_headline_shape(one_chip):
    """The program most flushes of `svcnode --n-ens 10000` launch:
    A=256 active columns of the 10,000 x 5 x 128 state, K=16."""
    mem = _sliced_step(one_chip, 16, 256).memory_analysis()
    # the state alone is ~0.2 GB; the program must fit the 16 GB chip
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


#: HLO ops that move a whole array: a relayout (`copy`), a move
#: between memory spaces (`copy-start` / `copy-done`), a piecewise one
_MOVES = re.compile(r" (copy|copy-start|copy-done|slice-start)\(")


def _plane_moves(text, planes):
    """``(op, name and result type)`` of each of the compiled
    program's moves whose result holds one of ``planes`` (shape
    prefixes such as ``s32[10000,5,128]``, whatever the layout)."""
    for line in text.splitlines():
        op = _MOVES.search(line)
        # what stands before the op is the instruction's name and type
        if op and any(p in line[:op.start()] for p in planes):
            yield op.group(1), line[:op.start()].strip()


def _whole_plane_moves(text, planes):
    """``{op: count}`` of :func:`_plane_moves`."""
    found = {}
    for op, _ in _plane_moves(text, planes):
        found[op] = found.get(op, 0) + 1
    return found


def _scatter_sources(text, rows):
    """What each scatter fusion of the entry computation that writes a
    ``rows``-shaped plane (``s32[50000,128]``) takes as the plane it
    updates, followed through bitcasts: the defining line."""
    entry = text[text.index("\nENTRY "):]
    defs = {}
    for line in entry.splitlines():
        name, eq, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        if eq:
            defs[name] = rest
    sources = []
    for rest in defs.values():
        if not (rest.startswith(rows) and " fusion(" in rest
                and "scatter_columns" in rest):
            continue
        src = rest.split(" fusion(", 1)[1].split(",")[0].strip(" )")
        while " bitcast(" in defs[src]:
            src = defs[src].split(" bitcast(", 1)[1].split(")")[0]
        sources.append(defs[src])
    return sources


def _assert_object_planes_stay_put(text, e, record_property, tag):
    """The sliced launch reads and writes A rows of each object plane
    where the plane lies (ISSUE 40): no whole-plane move, and each of
    the three scatters updates the donated parameter itself."""
    # a plane as the state holds it, and as `engine._peer_rows` views it
    plane, rows = f"s32[{e},{M},{S}]", f"s32[{e * M},{S}]"
    moves = _whole_plane_moves(text, (plane, rows))
    tree_node = _whole_plane_moves(text, (f"u32[{e},{M},",))
    record_property(f"{tag}_object_plane_moves", moves)
    record_property(f"{tag}_tree_plane_moves", tree_node)
    print(f"{tag} object-plane moves {moves} tree-plane moves {tree_node}")
    assert not moves, moves
    sources = _scatter_sources(text, rows)
    assert len(sources) == 3, sources
    assert all(" parameter(" in s for s in sources), sources


def _assert_idle_quorum_moves_no_plane(text, e, n_slots, record_property,
                                       tag):
    """The sliced program's full-width epoch check
    (``engine._sliced_quorum``, ISSUE 47) is in the compiled program
    and reads the small ballot planes alone: beside the object planes
    (above) ``tree_leaf`` does not move either, the check holds no
    gather (``quorum_met_batch``'s per-row one took 0.09 ms of a launch
    at 10,000 rows on the chip: ``met_only``), and what the program
    moves of the ``[E]`` / ``[E, M]`` / ``[E, V, M]`` planes is
    recorded (the same moves as before ISSUE 47 at both buckets)."""
    checks = [line for line in text.splitlines() if "idle_quorum" in line]
    assert checks
    assert not [line for line in checks if " gather(" in line]
    leaf = _whole_plane_moves(text, (f"u32[{e},{M},{n_slots},4]",))
    small = _whole_plane_moves(
        text, (f"[{e}]", f"[{e},{M}]", f"[{e},{V},{M}]", f"[{e},{V}]"))
    record_property(f"{tag}_tree_leaf_moves", leaf)
    record_property(f"{tag}_small_plane_moves", small)
    print(f"{tag} tree_leaf moves {leaf} small-plane moves {small}")
    assert not leaf, leaf


@pytest.mark.parametrize("k,a", [(1, 8), (16, 256)], ids=["k1a8", "k16a256"])
def test_sliced_step_moves_no_object_plane(one_chip, record_property, k, a):
    """`ycsb-a.ring10k-n5`'s window flush (K 1, A 8) and the headline
    bucket: the compiler stores an object plane M outermost
    (`{2,0,1}`), and the edges address it as `[M * E, S]` rows, a
    bitcast of that layout."""
    compiled = _sliced_step(one_chip, k, a)
    _assert_object_planes_stay_put(compiled.as_text(), E, record_property,
                                   f"k{k}a{a}")
    temp = compiled.memory_analysis().temp_size_in_bytes
    record_property(f"k{k}a{a}_temp_bytes", temp)
    print(f"k{k}a{a} temp_bytes {temp}")
    if (k, a) == (1, 8):        # 125.6 MB before ISSUE 40
        assert temp < 16e6


@pytest.mark.parametrize("k,a", [(1, 8), (16, 256)], ids=["k1a8", "k16a256"])
def test_sliced_step_checks_every_epoch_and_moves_no_plane(
        one_chip, record_property, k, a):
    """The same two programs since ISSUE 47: the epoch check of all
    10,000 ensembles rides in the sliced step, its result E bits of
    the packed vector, and it moves no plane of the keyspace's size."""
    from riak_ensemble_tpu.parallel.batched_host import packed_nbytes

    compiled = _sliced_step(one_chip, k, a)
    text = compiled.as_text()
    _assert_idle_quorum_moves_no_plane(text, E, S, record_property,
                                       f"k{k}a{a}")
    # the packed vector: won and corrupt A wide, the quorum plane E
    flat = packed_nbytes(E, M, k, True, a, sliced=True)
    assert flat == (a + E + a * M + 3 * k * a + 7) // 8 + 12 * k * a
    root = [line for line in text[text.index("\nENTRY "):].splitlines()
            if line.lstrip().startswith("ROOT ")][0]
    assert f"u8[{flat}]" in root, root


#: Riak's default ring with a deep keyspace: `ycsb-a.ring64-n3-deep`
#: (height 4) and `ycsb-a.ring64-n3-h5`, the synctree's own 1M
#: segments (height 5, 5.85 GB of state); with the most the step may
#: need beside its arguments at each
DEEP_E, DEEP_M = 64, 3
DEEP_SLOTS = {"s64k": (65_536, 100e6), "s1m": (1_048_576, 100e6)}


def _row_plane_results(text, shapes):
    """``(op, name and result type)`` of every instruction of the
    compiled program, inside fusions and the `while` body too, whose
    result is a row plane (``shapes``: the 4-D plane and its 2-D view)
    and which is more than a name for one that exists: not a
    parameter, a tuple element, a bitcast, nor a fusion (its root is
    listed)."""
    found = []
    for line in text.splitlines():
        name, eq, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        if not eq or not rest.startswith(shapes):
            continue
        kind, _, after = rest.partition("} ")
        op = after.split("(", 1)[0]
        if op not in ("parameter", "get-tuple-element", "bitcast", "fusion"):
            found.append((op, f"{name} = {kind}}}"))
    return found


@pytest.mark.parametrize("shape", list(DEEP_SLOTS))
@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
def test_deep_ring_round_leaves_the_planes_where_they_lie(
        one_chip, record_property, k, shape):
    """The donated full-width slab step every flush of the deep ring
    launches (64 < `SLICE_MIN_E`), with and without the scan's `while`
    (ISSUE 42).  The chip stores `tree_node` U minor-most
    (`{2,3,1,0}`): a gather or scatter along U moved the whole plane
    to a layout with the 4-wide LANES minor-most, padded to 128 lanes
    (573 MB, every round); the object planes are stored M outermost
    and were relayouted E outermost and back, every round.  The round
    now reads and writes all four where they lie, and `tree_leaf`
    (201 MB) keeps its gather and its scatter.  At 1M slots (ISSUE 43)
    the same holds and the whole program, arguments and temporaries,
    stays under half a chip.  The node levels of 128 nodes and more
    are rows of `tree_rows` (ISSUE 44; 217 MB at 1M slots, where the
    flat node plane took 13-14 passes a round): the program gathers
    the paths' rows and scatters them back into the donated buffer,
    and nothing else in it, in the `while` body or outside, makes an
    array of the plane's size."""
    e, m, (s, temp_limit) = DEEP_E, DEEP_M, DEEP_SLOTS[shape]
    state = _placed(jax.eval_shape(lambda: eng.init_state(e, m, s)),
                    one_chip)
    u = state.tree_node.shape[2]
    place = _on(one_chip)
    # as the cell's window launches it: the pack gathered at A 8 by
    # the slab's index row, in the step's own program (ISSUE 46)
    compiled = eng.full_step_slab_donate.lower(
        state, place("slab", (4 + 5 * k, e), jnp.int32),
        place("up", (e, m), jnp.bool_), want_vsn=True,
        gather=8).compile()
    text = compiled.as_text()
    assert (" while(" in text) == (k > 1)
    node = list(_plane_moves(text, (f"u32[{e},{m},{u},{hashk.LANES}]",)))
    # `{3,...}`: dimension 3, LANES, minor-most
    lanes_minor = [head for _, head in node
                   if re.search(r"\]\{3,", head)]
    objects = _whole_plane_moves(
        text, (f"s32[{e},{m},{s}]", f"s32[{m},{e},{s}]",
               f"s32[{e * m},{s}]"))
    leaf = _whole_plane_moves(text, (f"u32[{e},{m},{s},{hashk.LANES}]",))
    r = state.tree_rows.shape[2]
    assert r == eng.tree_layout(s).rows and r % 8 == 0
    rows = _row_plane_results(
        text, (f"u32[{e},{m},{r},{eng.ROW_WORDS}]",
               f"u32[{e * m * r},{eng.ROW_WORDS}]"))
    node_row_moves = [(op, head) for op, head in rows if op != "scatter"]
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes, mem.argument_size_in_bytes
    for name, value in (("tree_node_moves", [h for _, h in node]),
                        ("node_row_moves", node_row_moves),
                        ("node_row_results", [h for _, h in rows]),
                        ("object_plane_moves", objects),
                        ("tree_leaf_moves", leaf), ("temp_bytes", temp),
                        ("argument_bytes", args)):
        record_property(f"deep_{shape}_k{k}_{name}", value)
        print(f"deep_{shape}_k{k} {name} {value}")
    assert not lanes_minor, lanes_minor
    assert not objects, objects
    assert not leaf, leaf
    # one scatter, in place, and no relayout: whatever else holds the
    # plane holds it in the stored tiles.  Where the plane fits the
    # chip's on-core memory (15.7 MB at 65,536 slots: `S(1)`) the
    # compiler may carry it there for the launch and back, pieces of
    # it (`slice-start`) joined by a `ConcatBitcast`; at 1M slots
    # (217 MB) there is nothing but the scatter.
    assert [op for op, _ in rows].count("scatter") == 1, rows
    assert all(re.search(r"\]\{(3,2,)?1,0:T\(8,128\)(S\(1\))?\}$", head)
               for _, head in rows), rows
    assert all(op in ("copy-done", "custom-call")
               for op, _ in node_row_moves), node_row_moves
    if shape == "s1m":
        assert not node_row_moves, node_row_moves
    # at 65,536 slots 578.5 MB at K 2 before ISSUE 42 (`tree_node`
    # padded to 128 lanes); at 1M slots 9,169 MB then, 579 MB before
    # ISSUE 44 (the scan's moves of the flat node plane)
    assert temp < temp_limit
    assert args + temp < 8e9


def test_step_with_pallas_quorum_lowers_the_kernel(one_chip, monkeypatch):
    """With the gate on, the fused step must carry the Mosaic kernel —
    not the interpreter's expansion, which would pass any compile."""
    monkeypatch.setattr(eng, "PALLAS_QUORUM", True)
    e, k = 1024, 4
    state = _placed(jax.eval_shape(lambda: eng.init_state(e, M, S)),
                    one_chip)

    *pos, xe, xs = _step_args(e, M, k, _on(one_chip))
    # a FRESH jit: the module-level full_step may hold a trace made
    # with the gate off
    compiled = jax.jit(eng._full_step_body).lower(
        state, *pos, exp_epoch=xe, exp_seq=xs).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: HLO ops that move data between devices
_COLLECTIVES = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(-start)?\(")


def test_mesh_step_compiles_on_four_chips_without_ens_collectives(topo):
    """`svcnode --mesh-devices 4` at 10,240 ensembles: the donated
    step over a 4-device 'ens' mesh, 2,560 ensembles per shard.
    ARCHITECTURE §17's claim — ensembles are independent, so nothing
    crosses the 'ens' axis — is read off the compiled program."""
    from riak_ensemble_tpu.parallel.mesh import mesh_engine

    e, k = 10_240, 4
    engine = mesh_engine(4, devices=topo.devices)
    mesh = engine.mesh
    state = _placed(jax.eval_shape(lambda: eng.init_state(e, M, S)),
                    eng.state_sharding(mesh))
    compiled = engine.full_step_slab_donate.lower(
        state,
        jax.ShapeDtypeStruct((4 + 5 * k, e), jnp.int32,
                             sharding=engine.slab_sharding),
        jax.ShapeDtypeStruct((e, M), jnp.bool_,
                             sharding=engine.up_sharding),
        want_vsn=True, gather=1024).compile()
    text = compiled.as_text()
    # the step, and the per-shard pack with its local gather behind it
    assert not _COLLECTIVES.search(text), _COLLECTIVES.findall(text)
    # each device holds its quarter of the state, not all of it
    full = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < full / 4 * 1.1


def test_sliced_mesh_step_compiles_on_four_chips_without_ens_collectives(
        topo, record_property):
    """`ycsb-a.ring40k-n5-mesh4`'s window flush: 40,960 x 5 x 128 over
    the 2x2 mesh, K 1, `a_loc` 8 columns a shard.  Every shard gathers
    its own rows by its own local indices, so the sliced program too
    holds no collective; its compile seconds and temporaries are
    recorded beside the full-grid program's at the same K."""
    from riak_ensemble_tpu.parallel.mesh import mesh_engine

    e, k, a_loc = 40_960, 1, 8
    engine = mesh_engine(4, devices=topo.devices)
    state = _placed(jax.eval_shape(lambda: eng.init_state(e, M, S)),
                    eng.state_sharding(engine.mesh))
    up = jax.ShapeDtypeStruct((e, M), jnp.bool_,
                              sharding=engine.up_sharding)

    def compile_(program, head, width, **static):
        t0 = time.perf_counter()
        compiled = program.lower(
            state, jax.ShapeDtypeStruct((head + 5 * k, width), jnp.int32,
                                        sharding=engine.slab_sharding),
            up, want_vsn=True, **static).compile()
        return compiled, time.perf_counter() - t0

    sliced, sliced_s = compile_(engine.full_step_sliced_slab_donate, 4,
                                4 * a_loc)
    grid, grid_s = compile_(engine.full_step_slab_donate, 3, e, gather=0)
    text = sliced.as_text()
    assert not _COLLECTIVES.search(text), _COLLECTIVES.findall(text)
    mem, grid_mem = sliced.memory_analysis(), grid.memory_analysis()
    for name, value in (
            ("sliced_compile_s", sliced_s), ("grid_compile_s", grid_s),
            ("sliced_temp_bytes", mem.temp_size_in_bytes),
            ("grid_temp_bytes", grid_mem.temp_size_in_bytes),
            ("argument_bytes", mem.argument_size_in_bytes)):
        record_property(name, value)
        print(f"{name} {value}")
    # a quarter of the state a device; the program fits the chip (its
    # temporaries are one chip's sliced program's at 10,240 rows: the
    # compiler's whole-plane copies, PERF.md section 5, not the mesh's)
    full = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert mem.argument_size_in_bytes < full / 4 * 1.1
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_sliced_mesh_step_moves_no_object_plane(topo, record_property):
    """The mesh cell's window flush (K 1, `a_loc` 8): each chip's
    program is one chip's, so each chip's 10,240 x 5 x 128 object
    planes stay where they lie too."""
    from riak_ensemble_tpu.parallel.mesh import mesh_engine

    e, k, a_loc = 40_960, 1, 8
    engine = mesh_engine(4, devices=topo.devices)
    state = _placed(jax.eval_shape(lambda: eng.init_state(e, M, S)),
                    eng.state_sharding(engine.mesh))
    compiled = engine.full_step_sliced_slab_donate.lower(
        state,
        jax.ShapeDtypeStruct((4 + 5 * k, 4 * a_loc), jnp.int32,
                             sharding=engine.slab_sharding),
        jax.ShapeDtypeStruct((e, M), jnp.bool_,
                             sharding=engine.up_sharding),
        want_vsn=True).compile()
    _assert_object_planes_stay_put(compiled.as_text(), e // 4,
                                   record_property, "mesh_k1a8")
    # ... and the per-shard epoch check of a shard's 10,240 rows too
    _assert_idle_quorum_moves_no_plane(compiled.as_text(), e // 4, S,
                                       record_property, "mesh_k1a8")
    assert not _COLLECTIVES.search(compiled.as_text())
    temp = compiled.memory_analysis().temp_size_in_bytes
    record_property("mesh_k1a8_temp_bytes", temp)
    print(f"mesh_k1a8 temp_bytes {temp}")
    assert temp < 16e6


#: `ycsb-a.ring256-n3-h5-mesh4` (ISSUE 45): Riak ring size 256 over the
#: synctree's own 1M segments, 23.4 GB sharded along 'ens' over the
#: 2x2 host, each chip `ring64-n3-h5`'s 64 x 3 x 1,048,576
RING256 = (256, 3, 1_048_576)


def test_mesh_builds_a_ring_no_chip_can_hold_in_place(topo,
                                                      record_property):
    """The mesh engine's `init_state` program at 256 x 3 x 1,048,576:
    no operand (nothing is built on one device and then placed), no
    temporaries, and each device's result its own 5.85 GB of the
    23.4 GB."""
    from riak_ensemble_tpu.parallel.mesh import mesh_engine

    e, m, s = RING256
    engine = mesh_engine(4, devices=topo.devices)
    compiled = engine.init_program(e, m, s).lower().compile()
    mem = compiled.memory_analysis()
    whole = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(lambda: eng.init_state(e, m, s))))
    for name, value in (("output_bytes", mem.output_size_in_bytes),
                        ("temp_bytes", mem.temp_size_in_bytes),
                        ("state_bytes", whole)):
        record_property(f"ring256_init_{name}", value)
        print(f"ring256_init {name} {value}")
    assert whole > 16e9                     # one chip cannot hold it
    assert mem.argument_size_in_bytes == 0
    assert whole / 4 <= mem.output_size_in_bytes < whole / 4 * 1.001
    assert mem.temp_size_in_bytes < 100e6
    assert not _COLLECTIVES.search(compiled.as_text())


@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
def test_mesh_round_over_a_sharded_deep_ring_moves_no_plane(
        topo, record_property, k):
    """The donated full-grid mesh step every flush of that ring
    launches (a shard holds 64 < `SLICE_MIN_E` ensembles): under
    `shard_map` the `[M * E, S]` and `[E * M * R, 512]` views are
    bitcasts of a LOCAL block, and each chip's program stays what
    `ring64-n3-h5`'s is on one chip: no collective, no move of an
    object plane, of `tree_leaf` or of the row plane, under 100 MB of
    temporaries, arguments and temporaries under half a chip."""
    from riak_ensemble_tpu.parallel.mesh import mesh_engine

    e, m, s = RING256
    engine = mesh_engine(4, devices=topo.devices)
    state = _placed(jax.eval_shape(lambda: eng.init_state(e, m, s)),
                    eng.state_sharding(engine.mesh))
    # as the cell's window launches it: each shard's pack gathered at
    # a_loc 8 by its own block's index row, in the step's own program
    compiled = engine.full_step_slab_donate.lower(
        state,
        jax.ShapeDtypeStruct((4 + 5 * k, e), jnp.int32,
                             sharding=engine.slab_sharding),
        jax.ShapeDtypeStruct((e, m), jnp.bool_,
                             sharding=engine.up_sharding),
        want_vsn=True, gather=8).compile()
    text = compiled.as_text()
    assert (" while(" in text) == (k > 1)
    assert not _COLLECTIVES.search(text), _COLLECTIVES.findall(text)
    e_loc, r = e // 4, state.tree_rows.shape[2]
    objects = _whole_plane_moves(
        text, (f"s32[{e_loc},{m},{s}]", f"s32[{m},{e_loc},{s}]",
               f"s32[{e_loc * m},{s}]"))
    leaf = _whole_plane_moves(
        text, (f"u32[{e_loc},{m},{s},{hashk.LANES}]",))
    rows = _row_plane_results(
        text, (f"u32[{e_loc},{m},{r},{eng.ROW_WORDS}]",
               f"u32[{e_loc * m * r},{eng.ROW_WORDS}]"))
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes, mem.argument_size_in_bytes
    for name, value in (("object_plane_moves", objects),
                        ("tree_leaf_moves", leaf),
                        ("node_row_results", [h for _, h in rows]),
                        ("temp_bytes", temp), ("argument_bytes", args)):
        record_property(f"ring256_k{k}_{name}", value)
        print(f"ring256_k{k} {name} {value}")
    assert not objects, objects
    assert not leaf, leaf
    # the row plane: one scatter into the donated block, nothing else
    assert [op for op, _ in rows] == ["scatter"], rows
    assert temp < 100e6
    assert 5.8e9 < args and args + temp < 8e9
