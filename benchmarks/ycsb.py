"""YCSB's core workload generator (Cooper et al., SoCC 2010;
``site.ycsb.workloads.CoreWorkload`` and ``site.ycsb.generator``), in
numpy, from one seed.

What follows the source: ``recordcount`` records named
``user<fnvhash64(i)>`` (``insertorder=hashed``), request keys drawn by
``ScrambledZipfianGenerator`` (a Zipfian with constant 0.99 over
10**10 items whose published zeta is ``ZETAN``, folded into the record
range by ``fnvhash64``), 10 fields of 100 bytes per record, and the
read/update proportions of the traffic file.

``assumed`` (what the source leaves to its database binding):

- a record is one opaque 1,000-byte object (as YCSB's Riak binding
  stores it), an update is a blind ``kput`` of the whole record and a
  read is a ``kget``;
- the key's ensemble is the FNV-1a 64 hash of the key's bytes modulo
  the number of ensembles (a stable hash, as Riak maps a key to a
  preflist; never Python's salted ``hash``);
- the first 8 bytes of every record written carry a write id unique
  in the run, so a value that comes back names the one write that
  carried it; the other 992 bytes are seeded and are checked too.

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


def key_names(recordcount: int) -> list:
    """``CoreWorkload.buildKeyName`` with hashed insert order."""
    return [f"user{h}" for h in fnvhash64(np.arange(recordcount)).tolist()]


class Records:
    """The data set of one run: key names, each key's ensemble, and the
    bytes of every write by its id.  Ids 0..recordcount-1 are the
    loaded records; later writes take the ids after them."""

    def __init__(self, seed: int, recordcount: int, n_ens: int) -> None:
        self.recordcount = recordcount
        self.n_ens = n_ens
        self.keys = key_names(recordcount)
        self.ens = (fnv1a64_keys(self.keys)
                    % np.uint64(n_ens)).astype(np.int64)
        rng = np.random.default_rng([int(seed), 0x59435342])
        #: every record's tail is a 992-byte slice of this pool
        self._pool = rng.bytes(1 << 20)
        self._span = len(self._pool) - (RECORD_BYTES - ID_BYTES)

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


def schedule(seed: int, stream: int, rate: float, seconds: float,
             recordcount: int, read_share: float,
             distribution: str = "zipfian"):
    """The open-loop schedule of one phase, a function of its
    arguments alone: Poisson arrivals at ``rate`` for ``seconds``
    (``due``, seconds from the phase's start), each a read or an
    update (``is_read``) of one record (``keynum``, drawn by YCSB's
    ``requestdistribution``: ``zipfian`` or ``uniform``).  The process is
    conditioned on its count — exactly ``rate * seconds`` arrivals at
    sorted uniform instants, exactly ``read_share`` of them reads in
    a seeded order — so every seed offers the same amount of work.
    ``stream`` keeps the warm-up's draws apart from the window's."""
    rng = np.random.default_rng([int(seed), 0x4C4F4144, int(stream)])
    n = int(round(rate * seconds))
    due = np.sort(rng.random(n)) * seconds
    if distribution == "zipfian":
        keynum = scrambled_zipfian(rng.random(n), recordcount)
    elif distribution == "uniform":
        keynum = (rng.random(n) * recordcount).astype(np.int64)
    else:
        raise ValueError(f"requestdistribution {distribution!r}")
    is_read = np.zeros(n, bool)
    is_read[:int(round(n * read_share))] = True
    rng.shuffle(is_read)
    return due, is_read, keynum
