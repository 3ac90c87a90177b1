"""The generator against YCSB's own constants, and the schedule as a
function of the seed alone."""

import hashlib
import json
import os

import numpy as np
import pytest

import ycsb


def test_fnvhash64_matches_the_java_arithmetic():
    # Utils.fnvhash64, by hand in Python integers
    def ref(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * 1099511628211) & ((1 << 64) - 1)
            v >>= 8
        h = h - (1 << 64) if h >= 1 << 63 else h
        return abs(h)
    vals = [0, 1, 255, 256, 320_000, 10**10 - 1]
    assert ycsb.fnvhash64(np.array(vals)).tolist() == [ref(v) for v in vals]


def test_key_hash_is_fnv1a_of_the_key_bytes():
    keys = ycsb.key_names(50) + ["user1", "u"]
    got = ycsb.fnv1a64_keys(keys).tolist()
    assert got == [ycsb.fnv1a64_bytes(k.encode()) for k in keys]
    assert ycsb.fnv1a64_bytes(b"") == 0xCBF29CE484222325
    assert ycsb.fnv1a64_bytes(b"a") == 0xAF63DC4C8601EC8C


def test_published_zetan_is_the_zeta_of_ten_billion_items():
    # zeta(n) = sum i**-0.99: the head summed, the tail by Euler-Maclaurin
    n, m, th = ycsb.ITEM_COUNT, 10_000_000, ycsb.ZIPFIAN_CONSTANT
    tail = ((n ** (1 - th) - m ** (1 - th)) / (1 - th)
            + 0.5 * (n ** -th - m ** -th))
    assert ycsb.zeta(m) + tail == pytest.approx(ycsb.ZETAN, rel=1e-9)


@pytest.mark.parametrize("items", [1_000, 320_000])
def test_zipfian_item_0_share(items):
    """ZipfianGenerator over ``items``: item 0 is drawn with
    probability 1/zeta(items) (0.1338 at 1,000; 0.0789 at 320,000)."""
    u = np.random.default_rng(1).random(2_000_000)
    ranks = ycsb.zipfian(u, items, ycsb.zeta(items))
    assert ranks.min() == 0 and ranks.max() < items
    share = float((ranks == 0).mean())
    assert share == pytest.approx(1.0 / ycsb.zeta(items), rel=0.01)
    assert float((ranks == 1).mean()) == pytest.approx(
        0.5 ** 0.99 / ycsb.zeta(items), rel=0.02)


@pytest.mark.parametrize("recordcount", [1_000, 320_000])
def test_scrambled_zipfian_hottest_record(recordcount):
    """ScrambledZipfianGenerator: rank 0 of the 10**10-item Zipfian
    lands on record fnvhash64(0) % recordcount with share 1/ZETAN
    (3.78%), plus whatever else hashes there."""
    u = np.random.default_rng(2).random(1_000_000)
    recs = ycsb.scrambled_zipfian(u, recordcount)
    assert recs.min() >= 0 and recs.max() < recordcount
    hot = int(ycsb.fnvhash64(np.array([0]))[0] % recordcount)
    counts = np.bincount(recs, minlength=recordcount)
    assert int(counts.argmax()) == hot
    share = counts[hot] / recs.size
    assert 1.0 / ycsb.ZETAN * 0.98 < share < 1.0 / ycsb.ZETAN * 1.10


def test_schedule_is_a_function_of_the_seed_alone():
    a = ycsb.schedule(3_000_000_001, 2, 500.0, 4.0, 1000, 0.5)
    b = ycsb.schedule(3_000_000_001, 2, 500.0, 4.0, 1000, 0.5)
    c = ycsb.schedule(3_000_000_002, 2, 500.0, 4.0, 1000, 0.5)
    warm = ycsb.schedule(3_000_000_001, 1, 500.0, 4.0, 1000, 0.5)
    for x, y in zip(a, b):
        assert (x == y).all()
    assert not (a[2] == c[2]).all() and not (a[2] == warm[2]).all()
    due, is_read, keynum = a
    # every seed offers the same amount of work
    assert due.size == c[0].size == 2000
    assert is_read.sum() == c[1].sum() == 1000
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] < 4.0


def test_records_round_trip_and_reject_altered_bytes():
    r = ycsb.Records(7, 100, 8)
    v = r.value(12345)
    assert len(v) == ycsb.RECORD_BYTES and r.decode(v) == 12345
    assert r.decode(v[:-1] + bytes([v[-1] ^ 1])) == -1
    assert r.decode(b"short") == -1
    assert ycsb.Records(8, 100, 8).value(12345) != v
    groups = r.by_ensemble(np.arange(100))
    assert sorted(k for ks in groups.values() for k in ks) == list(range(100))
    assert all(int(r.ens[k]) == e for e, ks in groups.items() for k in ks)


# -- the accepted cells' schedules are pinned; inserts and ``latest`` ------

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
with open(os.path.join(BENCH, "testdata", "schedule_hashes.json")) as _f:
    #: "<traffic>/<seed>/<stream>" -> [arrivals, sha256 of due + is_read
    #: + keynum], from the ``ycsb.schedule`` of before PR 49 (stream 2
    #: over 45 s is the window, stream 100 the first steady warm-up)
    PINNED = json.load(_f)


def _cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "configs", w["config"] + ".json")) as f:
            cfg = json.load(f)
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            out[w["traffic"]] = (cfg["records_per_ens"] * cfg["n_ens"],
                                 json.load(f))
    return out


@pytest.mark.parametrize("pinned", sorted(PINNED))
def test_accepted_traffic_keeps_its_schedule_bit_for_bit(pinned):
    traffic, seed, stream = pinned.split("/")
    recordcount, t = _cells()[traffic]
    assert "insertproportion" not in t
    seconds = 45.0 if stream == "2" else float(t["warm_seconds"])
    due, is_read, keynum = ycsb.schedule(
        int(seed), int(stream), t["rate"], seconds, recordcount,
        t["readproportion"], t["requestdistribution"],
        t.get("insertproportion", 0.0), 0)
    digest = hashlib.sha256(due.tobytes() + is_read.tobytes()
                            + keynum.astype(np.int64).tobytes())
    assert [int(due.size), digest.hexdigest()] == PINNED[pinned]


def _plain_zipfian(u, items, theta=0.99):
    """``ZipfianGenerator.nextLong(itemcount)`` as the Java reads, one
    draw, zeta summed afresh."""
    zetan = sum(1.0 / i ** theta for i in range(1, items + 1))
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    uz = u * zetan
    if uz < 1.0:
        return 0
    if uz < 1.0 + 0.5 ** theta:
        return 1
    return int(items * (eta * u - eta + 1) ** alpha)


def test_inserts_take_the_next_key_numbers_in_due_order():
    rc, done = 1_000, 7
    due, is_read, keynum = ycsb.schedule(11, 2, 400, 5.0, rc, 0.95,
                                         "latest", 0.05, done)
    n = due.size
    assert n == 2_000 and int(is_read.sum()) == 1_900
    ins = ~is_read & (keynum >= rc + done)
    assert int(ins.sum()) == 100 and not (~is_read & ~ins).any()
    assert keynum[ins].tolist() == list(range(rc + done, rc + done + 100))
    assert (np.diff(due) >= 0).all()
    # YCSB-B with 5% inserts on top: updates stay updates of loaded keys
    due, is_read, keynum = ycsb.schedule(11, 2, 400, 5.0, rc, 0.5,
                                         "zipfian", 0.1, 0)
    ins = ~is_read & (keynum >= rc)
    assert int(ins.sum()) == 200 and int((~is_read & ~ins).sum()) == 800
    assert keynum[~ins].max() < rc


def test_latest_is_the_newest_due_key_less_a_zipfian_rank():
    """``SkewedLatestGenerator`` restated plainly: every request takes
    ``newest - ZipfianGenerator.nextLong(newest)``, where ``newest`` is
    the last key number due so far (``recordcount - 1`` and then the
    inserts) and the Zipfian's zeta grows with the keys."""
    rc, done = 300, 4
    rng = np.random.default_rng([5, 0x4C4F4144, 9])   # schedule's own
    n = 600
    rng.random(n)                                      # the arrivals
    u = rng.random(n)
    due, is_read, keynum = ycsb.schedule(5, 9, 200, 3.0, rc, 0.9,
                                         "latest", 0.1, done)
    newest = rc - 1 + done
    for i in range(n):
        if not is_read[i]:
            newest += 1
            assert keynum[i] == newest
        else:
            assert keynum[i] == newest - _plain_zipfian(u[i], newest)
            assert 0 < keynum[i] <= newest
    reads = keynum[is_read]
    age = (rc - 1 + done + np.cumsum(~is_read))[is_read] - reads
    assert float((age == 0).mean()) > 0.1      # the newest key is hot


def test_records_grow_names_and_places_inserted_keys():
    r = ycsb.Records(3, 100, 8)
    r.grow(130)
    r.grow(120)                                 # never shrinks
    assert r.recordcount == 100 and len(r.keys) == 130 == r.ens.size
    assert r.keys == ycsb.key_names(130)
    assert r.ens.tolist() == [ycsb.fnv1a64_bytes(k.encode()) % 8
                              for k in r.keys]
    assert set(r.by_ensemble([5, 125, 129])) <= set(range(8))
