"""YCSB's core workload generator (Cooper et al., SoCC 2010;
``site.ycsb.workloads.CoreWorkload`` and ``site.ycsb.generator``), in
numpy, from one seed.

What follows the source: ``recordcount`` records named
``user<fnvhash64(i)>`` (``insertorder=hashed``), request keys drawn by
``ScrambledZipfianGenerator`` (a Zipfian with constant 0.99 over
10**10 items whose published zeta is ``ZETAN``, folded into the record
range by ``fnvhash64``), 10 fields of 100 bytes per record, and the
read/update/insert proportions of the traffic file.  An insert takes
the next key number after the loaded ones (``CoreWorkload``'s
``transactioninsertkeysequence``: ``recordcount``, ``recordcount + 1``,
...), named and placed as every key is; ``requestdistribution:
"latest"`` is ``SkewedLatestGenerator``: the newest key number less a
Zipfian 0.99 rank over the keys there are (workload D).

``assumed`` (what the source leaves to its database binding):

- a record is one opaque 1,000-byte object (as YCSB's Riak binding
  stores it), an update is a blind ``kput`` of the whole record and a
  read is a ``kget``;
- the key's ensemble is the FNV-1a 64 hash of the key's bytes modulo
  the number of ensembles (a stable hash, as Riak maps a key to a
  preflist; never Python's salted ``hash``);
- the first 8 bytes of every record written carry a write id unique
  in the run, so a value that comes back names the one write that
  carried it; the other 992 bytes are seeded and are checked too;
- under ``latest`` the newest key is the last insert DUE before the
  request, not YCSB's last ACKNOWLEDGED one
  (``AcknowledgedCounterGenerator``): an open loop fixes its schedule
  before it sends anything, so what the server has acknowledged by
  then is not knowable; a read that overtakes its key's insert reads
  notfound and the check counts it (``reads_before_insert``).

This module imports no JAX and nothing of the program.
"""

from __future__ import annotations

import struct

import numpy as np

ZIPFIAN_CONSTANT = 0.99
#: ScrambledZipfianGenerator.ITEM_COUNT and its precomputed ZETAN
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
FIELD_COUNT = 10
FIELD_LENGTH = 100
RECORD_BYTES = FIELD_COUNT * FIELD_LENGTH
ID_BYTES = 8

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)
_M64 = (1 << 64) - 1


def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """``site.ycsb.Utils.fnvhash64`` over an array of longs: FNV-1a
    over the value's eight octets, low octet first, then ``Math.abs``
    of the signed result."""
    v = np.asarray(vals).astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * _FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def fnv1a64_bytes(data: bytes) -> int:
    """FNV-1a 64 of a byte string (the key -> ensemble hash)."""
    h = 0xCBF29CE484222325
    for b in data:
        h = ((h ^ b) * 1099511628211) & _M64
    return h


def fnv1a64_keys(keys: list) -> np.ndarray:
    """:func:`fnv1a64_bytes` of every ascii key, vectorised over the
    keys (one pass per byte position)."""
    n = len(keys)
    lens = np.fromiter(map(len, keys), np.int64, n)
    width = int(lens.max()) if n else 0
    mat = np.zeros((n, width), np.uint8)
    flat = np.frombuffer("".join(keys).encode("ascii"), np.uint8)
    starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
    cols = np.arange(width)
    mask = cols[None, :] < lens[:, None]
    mat[mask] = flat[(starts[:, None] + cols[None, :])[mask]]
    h = np.full(n, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for j in range(width):
            nxt = (h ^ mat[:, j].astype(np.uint64)) * _FNV_PRIME
            h = np.where(mask[:, j], nxt, h)
    return h


def zeta(n: int, theta: float = ZIPFIAN_CONSTANT) -> float:
    """``ZipfianGenerator.zetastatic``: sum of 1/i**theta, i = 1..n."""
    return float((1.0 / np.arange(1, n + 1, dtype=np.float64)
                  ** theta).sum())


def zipfian(u: np.ndarray, items: int, zetan: float,
            theta: float = ZIPFIAN_CONSTANT) -> np.ndarray:
    """``ZipfianGenerator.nextLong`` for uniform draws ``u``: ranks in
    [0, items), rank 0 the most popular."""
    zeta2 = zeta(2, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = ((1.0 - (2.0 / items) ** (1.0 - theta))
           / (1.0 - zeta2 / zetan))
    uz = u * zetan
    ret = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** theta, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def scrambled_zipfian(u: np.ndarray, recordcount: int) -> np.ndarray:
    """``ScrambledZipfianGenerator.nextValue``: record numbers in
    [0, recordcount)."""
    return fnvhash64(zipfian(u, ITEM_COUNT, ZETAN)) % recordcount


def key_names(recordcount: int, first: int = 0) -> list:
    """``CoreWorkload.buildKeyName`` with hashed insert order, for the
    key numbers ``first`` to ``recordcount - 1``."""
    return [f"user{h}"
            for h in fnvhash64(np.arange(first, recordcount)).tolist()]


class Records:
    """The data set of one run: key names, each key's ensemble, and the
    bytes of every write by its id.  Ids 0..recordcount-1 are the
    loaded records; later writes take the ids after them.  Keys
    0..recordcount-1 are loaded; an insert's key number follows them
    (:meth:`grow` names and places it before it is sent)."""

    def __init__(self, seed: int, recordcount: int, n_ens: int) -> None:
        self.recordcount = recordcount
        self.n_ens = n_ens
        self.keys: list = []
        self.ens = np.zeros(0, np.int64)
        self.grow(recordcount)
        rng = np.random.default_rng([int(seed), 0x59435342])
        #: every record's tail is a 992-byte slice of this pool
        self._pool = rng.bytes(1 << 20)
        self._span = len(self._pool) - (RECORD_BYTES - ID_BYTES)

    def grow(self, n_keys: int) -> None:
        """Name and place the key numbers up to ``n_keys`` (inserts)."""
        if n_keys <= len(self.keys):
            return
        new = key_names(n_keys, len(self.keys))
        self.keys += new
        self.ens = np.concatenate([self.ens, (
            fnv1a64_keys(new) % np.uint64(self.n_ens)).astype(np.int64)])

    def value(self, write_id: int, stub: bool = False) -> bytes:
        """The bytes write ``write_id`` carries.  A ``stub`` is the id
        alone: what the warm-up's depth bursts write under the full
        record that ends each of them (no read ever finds one unless a
        write was lost or reordered, and then the check says so)."""
        head = struct.pack(">Q", write_id)
        if stub:
            return head
        off = (write_id * 7919) % self._span
        return head + self._pool[off:off + RECORD_BYTES - ID_BYTES]

    def decode(self, value) -> int:
        """The write id a returned value names, or -1 where the bytes
        are not the bytes that write carried."""
        if type(value) is not bytes \
                or len(value) not in (ID_BYTES, RECORD_BYTES):
            return -1
        (wid,) = struct.unpack_from(">Q", value)
        return wid if value == self.value(wid, len(value) == ID_BYTES) \
            else -1

    def by_ensemble(self, keynums) -> dict:
        """ensemble -> sorted record numbers, for the batched verbs."""
        keynums = np.asarray(keynums, np.int64)
        order = np.lexsort((keynums, self.ens[keynums]))
        out: dict = {}
        for kn in keynums[order].tolist():
            out.setdefault(int(self.ens[kn]), []).append(kn)
        return out


def latest(u: np.ndarray, newest: np.ndarray) -> np.ndarray:
    """``SkewedLatestGenerator.nextValue`` for uniform draws ``u``:
    ``newest`` (the last key number there is, per draw) less a Zipfian
    rank over ``newest`` items, whose zeta grows with them as
    ``ZipfianGenerator.nextLong(itemcount)`` recomputes it."""
    newest = np.asarray(newest, np.int64)
    top = int(newest.max()) if newest.size else 0
    zetas = np.concatenate(([0.0], np.cumsum(
        1.0 / np.arange(1, top + 1, dtype=np.float64)
        ** ZIPFIAN_CONSTANT)))
    return newest - zipfian(u, newest, zetas[newest])


def schedule(seed: int, stream: int, rate: float, seconds: float,
             recordcount: int, read_share: float,
             distribution: str = "zipfian", insert_share: float = 0.0,
             inserted: int = 0):
    """The open-loop schedule of one phase, a function of its
    arguments alone: Poisson arrivals at ``rate`` for ``seconds``
    (``due``, seconds from the phase's start), each a read or a write
    (``is_read``) of one record (``keynum``, drawn by YCSB's
    ``requestdistribution``: ``zipfian``, ``uniform`` or ``latest``).
    The process is conditioned on its count — exactly ``rate *
    seconds`` arrivals at sorted uniform instants, exactly
    ``read_share`` of them reads and ``insert_share`` of them inserts
    in a seeded order — so every seed offers the same amount of work.
    A write whose ``keynum`` is ``recordcount + inserted`` or more is
    an insert: the run's ``inserted``-th and on, in due order (the
    phases before this one made ``inserted``).  Under ``latest`` a
    request takes the newest key DUE before it less a Zipfian rank.
    ``stream`` keeps the warm-up's draws apart from the window's.
    Without inserts and ``latest`` nothing is drawn that was not drawn
    before there were any: an old traffic file keeps its schedule."""
    rng = np.random.default_rng([int(seed), 0x4C4F4144, int(stream)])
    n = int(round(rate * seconds))
    due = np.sort(rng.random(n)) * seconds
    u = rng.random(n)
    if distribution == "zipfian":
        keynum = scrambled_zipfian(u, recordcount)
    elif distribution == "uniform":
        keynum = (u * recordcount).astype(np.int64)
    elif distribution != "latest":
        raise ValueError(f"requestdistribution {distribution!r}")
    is_read = np.zeros(n, bool)
    is_read[:int(round(n * read_share))] = True
    rng.shuffle(is_read)
    is_insert = np.zeros(n, bool)
    if insert_share > 0:
        writes = np.flatnonzero(~is_read)
        n_ins = min(int(round(n * insert_share)), writes.size)
        is_insert[writes[rng.permutation(writes.size)[:n_ins]]] = True
    made = inserted + np.cumsum(is_insert)
    if distribution == "latest":
        keynum = latest(u, recordcount - 1 + made)
    keynum = np.where(is_insert, recordcount - 1 + made, keynum)
    return due, is_read, keynum
