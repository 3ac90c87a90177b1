"""The served path over a synctree of height 5, and the host's slot
bookkeeping sized by what is in use (PR 43).

(a) A service whose ensembles hold 131,072 and 1,048,576 slots (five
levels of interior nodes, the top one two and sixteen wide), driven
through its normal queue and flush with seeded kput / kget / kdelete
on keys whose slots lie in distant segments: every reply is the plain
model's (a dict ordered by the versions the service acknowledged), and
every replica's leaves and interior nodes are the plain bottom-up build
over its own object planes.

(b) The free-slot mark with its recycled slots against the list of
every slot it replaced; allocation, deletion, recycling, the reset of a
row, the checkpoint round trip (this PR's form and the parent's) and the
fast-read gate.

(c) Constructing a service allocates no Python object per slot.
"""

import gc
import pickle
import random
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from riak_ensemble_tpu import save as savelib  # noqa: E402
from riak_ensemble_tpu.config import Config, fast_test_config  # noqa: E402
from riak_ensemble_tpu.ops import engine as eng  # noqa: E402
from riak_ensemble_tpu.ops import hash as hashk  # noqa: E402
from riak_ensemble_tpu.parallel.batched_host import (  # noqa: E402
    BatchedEnsembleService, WallRuntime, _FreeSlots,
)
from riak_ensemble_tpu.runtime import Runtime  # noqa: E402
from riak_ensemble_tpu.types import NOTFOUND  # noqa: E402

#: a lease no wall-clock compile outlasts, inside the safety inequality
WALL_CONFIG = Config(ensemble_tick=1.0, lease_duration=60.0,
                     follower_timeout=300.0)


# -- (a) five levels, through the served path --------------------------------


def plain_uppers(leaves: np.ndarray) -> np.ndarray:
    """Interior nodes leafward -> root over ``[S, LANES]`` leaves: each
    level the fold of its 16 children, a short last block padded with
    zero hashes.  At 16^5 leaves this is ``ops/hash.build`` itself."""
    import jax.numpy as jnp

    outs, cur = [], jnp.asarray(leaves)
    while cur.shape[0] > 1:
        pad = -cur.shape[0] % 16
        if pad:
            cur = jnp.concatenate(
                [cur, jnp.zeros((pad, hashk.LANES), cur.dtype)])
        cur = hashk.fold(cur.reshape(-1, 16, hashk.LANES))
        outs.append(cur)
    return np.concatenate([np.asarray(o) for o in outs])


def settle(svc, futs):
    for _ in range(64):
        if all(f.done for f in futs):
            break
        svc.flush()
    svc.flush()                 # the pipeline's tail
    assert all(f.done for f in futs)
    return [f.value for f in futs]


def distant_slots(rng, n_slots: int, n: int) -> list:
    """``n`` distinct slots: both ends, one in every block of the top
    level, the rest anywhere."""
    top = n_slots // 16
    picked = {0, n_slots - 1}
    picked |= {b * top + int(rng.integers(top)) for b in range(16)}
    while len(picked) < n:
        picked.add(int(rng.integers(n_slots)))
    out = sorted(picked)
    rng.shuffle(out)
    return out[:n]


@pytest.mark.parametrize("n_ens,n_slots,n_keys", [
    (2, 131072, 48),        # top level two wide
    (1, 1048576, 24),       # the synctree's own size
])
def test_served_path_over_five_levels(n_ens, n_slots, n_keys):
    assert len(eng.tree_sizes(n_slots)) == 5
    rng = np.random.default_rng([43, n_slots])
    svc = BatchedEnsembleService(WallRuntime(), n_ens, 3, n_slots,
                                 tick=None, config=WALL_CONFIG)
    slots = []
    for e in range(n_ens):
        slots.append(distant_slots(rng, n_slots, n_keys))
        # which slot a key gets is the allocator's: hand it these, in
        # this order (a free list is a mark plus what was handed back)
        svc.free_slots[e] = _FreeSlots(0, slots[e][::-1])
    keys = [[f"user{e}.{i}" for i in range(n_keys)] for e in range(n_ens)]
    model: dict = {}            # (ens, key) -> (value, vsn) acknowledged

    def put(e, ks, tag):
        vals = [f"{tag}.{e}.{k}".encode() * 4 for k in ks]
        if len(ks) > 3:         # the batch surface and the scalar one
            got = settle(svc, [svc.kput_many(e, ks[:-2], vals[:-2])])[0]
            got += settle(svc, [svc.kput(e, k, v)
                                for k, v in zip(ks[-2:], vals[-2:])])
        else:
            got = settle(svc, [svc.kput(e, k, v)
                               for k, v in zip(ks, vals)])
        for k, v, r in zip(ks, vals, got):
            assert r[0] == "ok", (e, k, r)
            old = model.get((e, k))
            assert old is None or tuple(r[1]) > old[1]
            model[(e, k)] = (v, tuple(r[1]))

    def read(e, ks):
        got = settle(svc, [svc.kget_many(e, ks, want_vsn=True)])[0]
        for k, r in zip(ks, got):
            want = model.get((e, k))
            if want is None or want[0] is NOTFOUND:
                assert r[:2] == ("ok", NOTFOUND), (e, k, r)
            else:
                assert r == ("ok", want[0], want[1]), (e, k, r)

    for e in range(n_ens):
        put(e, keys[e], "load")
        assert [svc.key_slot[e][k] for k in keys[e]] == slots[e]
    svc.set_fast_reads(False)   # every read a device round
    for e in range(n_ens):
        read(e, keys[e])
    # overwrite a seeded third, delete another, read all of it back
    for e in range(n_ens):
        order = [keys[e][i] for i in rng.permutation(n_keys)]
        third = n_keys // 3
        put(e, order[:third], "again")
        gone = order[third:2 * third]
        for k, r in zip(gone, settle(svc, [svc.kdelete_many(e, gone)])[0]):
            assert r[0] == "ok", (e, k, r)
            model[(e, k)] = (NOTFOUND, None)
        read(e, keys[e] + ["never.written"])
    svc.set_fast_reads(True)
    for e in range(n_ens):      # a leased read is the model's too
        live = [k for k in keys[e] if model[(e, k)][0] is not NOTFOUND]
        put(e, live[:2], "leased")
        hits = svc.read_fastpath_hits
        got = settle(svc, [svc.kget(e, k) for k in live[:2]])
        assert got == [("ok", model[(e, k)][0]) for k in live[:2]]
        assert svc.read_fastpath_hits == hits + 2
    assert svc.stats()["corruptions_detected"] == 0

    # every replica's tree is the plain build over its own objects
    st = svc.state
    leaf_want = np.asarray(hashk.obj_leaf_hash(
        st.obj_epoch, st.obj_seq, st.obj_val))
    leaf = np.asarray(st.tree_leaf)
    # the upper levels as stored (those of 128 nodes and more as rows
    # of 128 nodes, the top ones flat) and as the flat plane the plain
    # build gives
    lay = eng.tree_layout(n_slots)
    assert (lay.row_levels, lay.rows, lay.tail_nodes) == {
        131072: (2, 72, 32 + 2 + 1), 1048576: (3, 552, 16 + 1)}[n_slots]
    assert svc.stats()["tree"] == {
        "row_levels": lay.row_levels, "rows": lay.rows,
        "tail_nodes": lay.tail_nodes,
        "rows_per_round": n_ens * 3 * lay.row_levels}
    assert st.tree_rows.shape == (n_ens, 3, lay.rows, eng.ROW_WORDS)
    assert st.tree_node.shape[2] == lay.tail_nodes
    import jax.numpy as jnp
    node = np.asarray(jnp.concatenate(
        eng.rows_to_levels(st.tree_rows, st.tree_node, n_slots), axis=-2))
    assert np.array_equal(leaf, leaf_want)
    offs, total = eng._tree_offsets(n_slots)
    assert node.shape[2] == total
    for e in range(n_ens):
        touched = [svc.key_slot[e].get(k) for k in keys[e]]
        assert len({s // (n_slots // 16) for s in slots[e]}) == 16
        for m in range(3):
            want = plain_uppers(leaf[e, m])
            assert np.array_equal(node[e, m, -1], want[-1])      # root
            for s in slots[e]:          # the verified path, leaf up
                at = s
                for off in offs:
                    at //= 16
                    assert np.array_equal(node[e, m, off + at],
                                          want[off + at]), (e, m, s, off)
            assert np.array_equal(node[e, m], want)
            if n_slots == 16 ** 5:      # ops/hash.build's levels
                levels = hashk.build(leaf[e, m])
                assert np.array_equal(node[e, m, -1], levels[0][0])
                assert np.array_equal(node[e, m, offs[0]:offs[1]],
                                      np.asarray(levels[-2]))
        assert touched.count(None) == n_keys // 3   # deleted, recycled

    # a flipped word in a row node, at each row level of one path: the
    # read through it says so, of that replica alone, and repairs it;
    # a read under another parent does not see it; the sweep does
    node_bad, leaf_bad = svc.engine.verify_trees(st)
    assert not np.asarray(node_bad).any() and not np.asarray(leaf_bad).any()
    e = n_ens - 1
    live = [k for k in keys[e] if (e, k) in model
            and model[(e, k)][0] is not NOTFOUND]
    svc.set_fast_reads(False)
    for level in range(lay.row_levels):
        key = live[level]
        at = svc.key_slot[e][key]
        idx = at // 16 ** (level + 1)
        # (a path checks each parent against its 16 children: a key
        # whose path holds a SIBLING of the node would see it too)
        other = next(k for k in live if svc.key_slot[e][k]
                     // 16 ** (level + 2) != idx // 16)
        row = lay.row_offs[level] + idx // eng.ROW_NODES
        word = 3 * eng.ROW_NODES + idx % eng.ROW_NODES
        was = svc.state.tree_rows
        svc.state = svc.state._replace(
            tree_rows=was.at[e, 1, row, word].set(was[e, 1, row, word] ^ 1))
        bad = np.asarray(svc.engine.verify_trees(svc.state)[0])
        assert bad[e, 1] and bad.sum() == 1
        seen = svc.stats()["corruptions_detected"]
        read(e, [other])
        assert svc.stats()["corruptions_detected"] == seen
        read(e, [key])
        assert svc.stats()["corruptions_detected"] == seen + 1
        assert not np.asarray(svc.engine.verify_trees(svc.state)[0]).any()
        assert np.array_equal(svc.state.tree_rows, was)
    svc.stop()


# -- (b) slot bookkeeping ----------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_free_slots_is_the_list_it_replaced(seed):
    """A seeded walk of pops and appends on the mark-plus-recycled form
    and on ``list(range(n))``: the same slot at every pop, the same
    length and truth, ``IndexError`` when empty."""
    rnd = random.Random(seed)
    n = rnd.choice((1, 7, 64))
    free, plain, out = _FreeSlots(n), list(range(n)), []
    for _ in range(6 * n):
        if out and rnd.random() < 0.4:
            s = out.pop(rnd.randrange(len(out)))
            free.append(s)
            plain.append(s)
        elif plain:
            a, b = free.pop(), plain.pop()
            assert a == b
            out.append(a)
        else:
            with pytest.raises(IndexError):
                free.pop()
        assert len(free) == len(plain) and bool(free) == bool(plain)
    # the parent's checkpoint listed every free slot: same pops after
    again = _FreeSlots.from_list(list(plain))
    assert len(again) == len(plain)
    assert again.fresh + len(again.recycled) == len(plain)
    assert [again.pop() for _ in range(len(plain))] == plain[::-1]


@pytest.mark.parametrize("n,used", [
    (8, set()), (8, {7, 6, 5}), (8, {7, 5, 2}), (8, {0}),
    (8, set(range(8))), (1, set()),
])
def test_free_slots_unused_is_the_comprehension_it_replaced(n, used):
    free = _FreeSlots.unused(n, used)
    plain = [s for s in range(n) if s not in used]
    assert len(free) == len(plain)
    assert [free.pop() for _ in range(len(plain))] == plain[::-1]
    assert not free


def make_service(n_slots=4, **kw):
    runtime = Runtime(seed=43)
    svc = BatchedEnsembleService(runtime, 2, 3, n_slots, tick=0.005,
                                 config=fast_test_config(), **kw)
    return runtime, svc


def ok(runtime, fut):
    r = runtime.await_future(fut, 5.0)
    assert r[0] == "ok", r
    return r


def test_allocate_delete_recycle_and_a_full_ensemble():
    runtime, svc = make_service()
    for i, k in enumerate("abcd"):
        ok(runtime, svc.kput(0, k, k.encode()))
        assert svc.key_slot[0][k] == 3 - i       # the mark walks down
    assert not svc.free_slots[0] and len(svc.free_slots[1]) == 4
    assert svc._slot_for(0, "e", allocate=True) is None
    assert runtime.await_future(svc.kput(0, "e", b"e"), 5.0) == "failed"
    assert svc.stats()["slots"]["in_use"] == 4
    ok(runtime, svc.kdelete(0, "b"))
    runtime.run_for(0.05)                        # the deferred recycle
    assert len(svc.free_slots[0]) == 1
    assert svc.free_slots[0].recycled == [2] and svc.free_slots[0].fresh == 0
    assert svc.stats()["slots"]["recycled"] == 1
    ok(runtime, svc.kput(0, "e", b"e"))
    assert svc.key_slot[0]["e"] == 2             # handed back, out first
    assert runtime.await_future(svc.kget(0, "b"), 5.0) == ("ok", NOTFOUND)
    assert runtime.await_future(svc.kget(0, "e"), 5.0) == ("ok", b"e")
    slots = svc.stats()["slots"]
    assert (slots["keyspace"], slots["in_use"], slots["recycled"]) == (
        8, 4, 0)


def test_fast_read_gate_sees_a_queued_write_and_forgets_it():
    svc = BatchedEnsembleService(WallRuntime(), 1, 3, 8, tick=None,
                                 config=WALL_CONFIG)
    settle(svc, [svc.kput(0, "k", b"v0")])
    slot = svc.key_slot[0]["k"]
    g = svc.kget(0, "k")
    assert g.done and g.value == ("ok", b"v0")   # leased, off the mirror
    f = svc.kput(0, "k", b"v1")
    f2 = svc.kput_many(0, ["k", "j"], [b"v2", b"j"])
    assert svc._pending_writes[0] == {slot: 2, svc.key_slot[0]["j"]: 1}
    assert svc._queued_handle_writes[0] == svc._pending_writes[0]
    assert svc.health()["pending_writes"] == 2
    g = svc.kget(0, "k")
    assert not g.done
    assert svc.read_fastpath_miss_reasons["pending_write"] == 1
    settle(svc, [f, f2, g])
    assert g.value == ("ok", b"v2")
    assert svc._pending_writes[0] == {} == svc._queued_handle_writes[0]
    g = svc.kget(0, "k")
    assert g.done and g.value == ("ok", b"v2")
    # an unpaired un-note parks nothing and underflows nothing
    svc._unnote_write(0, slot)
    svc._unnote_handle_write(0, slot)
    assert svc._pending_writes[0] == {} == svc._queued_handle_writes[0]
    svc.stop()


def test_reset_of_a_row_gives_every_slot_back():
    runtime, svc = make_service(dynamic=True)
    row = svc.create_ensemble("orders")
    runtime.run_for(0.05)
    for k in "abc":
        ok(runtime, svc.kput(row, k, b"x"))
    assert len(svc.free_slots[row]) == 1
    assert svc.destroy_ensemble("orders")
    assert len(svc.free_slots[row]) == 4 and svc.free_slots[row].fresh == 4
    assert svc._pending_writes[row] == {}
    assert svc._queued_handle_writes[row] == {}
    row2 = svc.create_ensemble("carts")
    runtime.run_for(0.05)
    ok(runtime, svc.kput(row2, "a", b"y"))
    assert svc.key_slot[row2]["a"] == 3
    assert runtime.await_future(svc.kget(row2, "b"), 5.0) == ("ok", NOTFOUND)


@pytest.mark.parametrize("form", ["free_marks", "free_slots"])
def test_checkpoint_round_trip_keeps_slots_and_the_next_allocation(
        tmp_path, form):
    """``free_marks`` is what ``save()`` writes; ``free_slots`` (every
    free slot listed) is what the parent wrote, and still loads."""
    d = str(tmp_path)
    svc = BatchedEnsembleService(WallRuntime(), 2, 3, 8, tick=None,
                                 config=WALL_CONFIG, data_dir=d)
    settle(svc, [svc.kput_many(0, list("abcde"), [b"x"] * 5),
                 svc.kput(1, "z", b"z")])
    settle(svc, [svc.kdelete_many(0, ["b", "d"])])
    svc.flush()
    assert svc.free_slots[0].recycled == [6, 4]
    assert svc.free_slots[0].fresh == 3
    svc.save()
    before = [dict(ks) for ks in svc.key_slot]
    free = [[f.fresh, list(f.recycled)] for f in svc.free_slots]
    n = svc._current_ckpt(d)
    path = f"{d}/ckpt.{n}/host"
    host = pickle.loads(savelib.read(path))
    assert "free_marks" in host and "free_slots" not in host
    if form == "free_slots":
        host["free_slots"] = [
            list(range(fresh)) + list(rec)
            for fresh, rec in host.pop("free_marks")]
        savelib.write(path, pickle.dumps(host, protocol=4))
    svc.stop()
    svc2 = BatchedEnsembleService.restore(
        WallRuntime(), d, data_dir=d, tick=None, config=WALL_CONFIG)
    assert [dict(ks) for ks in svc2.key_slot] == before
    assert [[f.fresh, list(f.recycled)] for f in svc2.free_slots] == free
    assert svc2._pending_writes == [{}, {}]
    # restored lease-less: the first read rides a round, then the mirror
    g = svc2.kget(0, "a")
    assert not g.done
    assert settle(svc2, [g]) == [("ok", b"x")]
    settle(svc2, [svc2.kput_many(0, ["p", "q", "r"], [b"n"] * 3)])
    assert [svc2.key_slot[0][k] for k in "pqr"] == [4, 6, 2]
    assert settle(svc2, [svc2.kget(0, "b")]) == [("ok", NOTFOUND)]
    svc2.stop()


# -- (c) no Python object per slot -------------------------------------------


def construct_blocks(n_slots: int):
    gc.collect()
    before = sys.getallocatedblocks()
    svc = BatchedEnsembleService(WallRuntime(), 1, 3, n_slots, tick=None)
    blocks = sys.getallocatedblocks() - before
    slots = svc.stats()["slots"]
    slabs = sum(a.nbytes for a in (
        svc._slot_vsn_np, svc._slot_vsn_ok, svc._inline_value_np,
        svc._inline_value_ok, svc._inline_np))
    svc.stop()
    return blocks, slots, slabs


def test_constructing_a_service_allocates_nothing_per_slot():
    # (each shape twice: the first build of a shape also fills the
    # eager operations' compile caches, a few thousand blocks)
    construct_blocks(1024)
    small, _, _ = construct_blocks(1024)
    construct_blocks(1048576)
    large, slots, slabs = construct_blocks(1048576)
    # an int in a list is a block: the parent's three lists were
    # 1,048,576 + of them
    assert large < 50_000
    assert large <= small + 2_000
    assert slots["keyspace"] == 1048576 and slots["in_use"] == 0
    # 15 B a slot in the five mirror slabs; the rest is three empty
    # containers an ensemble
    assert slabs == 15 * 1048576
    assert slabs <= slots["host_bytes"] <= slabs + 1_000
