"""Restricted wire codec: roundtrips, allowlist rejection, hostile
frames.  The codec replaces pickle on the TCP transport so a peer that
can reach the node port can inject at worst a protocol message, never
code (disterl's property, advice r1)."""

import pytest

from riak_ensemble_tpu import wire
from riak_ensemble_tpu.state import ClusterState
from riak_ensemble_tpu.types import (EnsembleInfo, Fact, NOTFOUND, Obj,
                                     PeerId)


CASES = [
    None, True, False, 0, 1, -1, 2 ** 80, -(2 ** 80), 1.5, -0.0,
    "", "node0", "ünïcode", b"", b"\x00\xffpayload",
    (), (1, 2), [1, [2, [3]]], {"a": 1, 2: (3,)}, {1, 2}, frozenset({3}),
    NOTFOUND,
    PeerId(1, "node0"), PeerId("root", "node1"),
    Obj(epoch=3, seq=7, key="k", value=b"v"),
    Obj(epoch=1, seq=1, key=("composite", 2), value=NOTFOUND),
    Fact(epoch=2, seq=5, leader=PeerId(0, "n0"),
         views=((PeerId(0, "n0"), PeerId(1, "n1")),),
         view_vsn=(1, 0), pend_vsn=None, commit_vsn=(0, 0),
         pending=((2, 1), ((PeerId(1, "n1"),),))),
    EnsembleInfo(vsn=(1, 2), leader=None, views=((PeerId(0, "n0"),),),
                 seq=None),
    ClusterState(id=("node0", 123.5), enabled=True, members_vsn=(1, 0),
                 members=frozenset({"node0", "node1"}),
                 ensembles={"root": EnsembleInfo(
                     vsn=(0, 1), leader=PeerId("root", "node0"),
                     views=((PeerId("root", "node0"),),), seq=(1, 1))},
                 pending={"root": ((1, 1), ((PeerId(2, "node2"),),))}),
]


@pytest.mark.parametrize("value", CASES, ids=lambda v: repr(v)[:40])
def test_roundtrip(value):
    out = wire.decode(wire.encode(value))
    assert out == value
    assert type(out) is type(value)


def test_notfound_stays_singleton():
    assert wire.decode(wire.encode(NOTFOUND)) is NOTFOUND


def test_nested_message_shape():
    # a realistic wire frame: (dst, msg) with a reply-from tuple
    frame = (("peer", "kv", PeerId(1, "node1")),
             ("get", "k", (("collector", "node0", 42), 7), 3))
    assert wire.decode(wire.encode(frame)) == frame


def test_rejects_unencodable():
    class Evil:
        pass
    with pytest.raises(wire.WireError):
        wire.encode(Evil())
    with pytest.raises(wire.WireError):
        wire.encode(lambda: None)  # closures never cross the wire


def test_rejects_unknown_tag():
    with pytest.raises(wire.WireError):
        wire.decode(b"Q")


def test_rejects_truncated():
    payload = wire.encode((1, "abc", b"xyz"))
    for cut in range(len(payload)):
        with pytest.raises(wire.WireError):
            wire.decode(payload[:cut])


def test_rejects_trailing_garbage():
    with pytest.raises(wire.WireError):
        wire.decode(wire.encode(1) + b"N")


def test_rejects_unknown_record_code():
    with pytest.raises(wire.WireError):
        wire.decode(b"R\x7f")


def test_rejects_deep_nesting_bomb():
    payload = b"t\x01" * 64 + b"N"
    with pytest.raises(wire.WireError):
        wire.decode(payload)


def test_rejects_oversized_count():
    # claims 2^40 tuple elements with no bodies: must fail cleanly,
    # not allocate
    payload = b"t" + bytes([0x80, 0x80, 0x80, 0x80, 0x80, 0x01])
    with pytest.raises(wire.WireError):
        wire.decode(payload)


def test_no_pickle_in_transport():
    import riak_ensemble_tpu.netruntime as nrt
    import inspect
    assert "pickle" not in inspect.getsource(nrt)


def test_funref_roundtrip_and_resolve():
    """Modify callbacks cross the wire as ("fn", name, bound) data —
    the MFA analog (root.erl:82,104) — and resolve by registry."""
    from riak_ensemble_tpu import funref
    import riak_ensemble_tpu.root  # noqa: F401  (registers root:*)

    spec = funref.ref("root:join", "node9")
    got = wire.decode(wire.encode(spec))
    assert got == spec
    fn = funref.resolve(got)
    from riak_ensemble_tpu import state as statelib
    cs = statelib.new_state(("c", 1.0))
    out = fn((1, 0), cs)
    assert "node9" in out.members


def test_funref_rejects_unregistered():
    from riak_ensemble_tpu import funref
    with pytest.raises(ValueError):
        funref.resolve(("fn", "no:such", ()))
    with pytest.raises(ValueError):
        funref.resolve("not-a-spec")


def test_encode_rejects_nesting_bomb():
    """Pathological user values must become WireError (dropped frame),
    not RecursionError (dead sender task)."""
    v = []
    for _ in range(1000):
        v = [v]
    with pytest.raises(wire.WireError):
        wire.encode(v)


def test_encode_rejects_self_reference():
    v = []
    v.append(v)
    with pytest.raises(wire.WireError):
        wire.encode(v)


def test_decode_malformed_raises_wireerror_only():
    """The documented contract: anything malformed raises WireError —
    not UnicodeDecodeError / TypeError — so callers can catch narrowly."""
    bad = [
        b"s\x01\xff",          # invalid utf-8 in str
        b"e\x01l\x00",         # set containing a list (unhashable)
        b"z\x01l\x00",         # frozenset containing a list
        b"d\x01l\x00N",        # dict with unhashable key
    ]
    for payload in bad:
        with pytest.raises(wire.WireError):
            wire.decode(payload)


@pytest.mark.parametrize("seed", range(4))
def test_fuzz_roundtrip_random_structures(seed):
    """Seeded structural fuzz: random nested allowlisted values must
    round-trip exactly (type-preserving)."""
    import numpy as _np

    rng = _np.random.default_rng(seed)

    def gen(depth=0):
        choices = 10 if depth < 4 else 6  # leaves only when deep
        c = int(rng.integers(choices))
        if c == 0:
            return None
        if c == 1:
            return bool(rng.integers(2))
        if c == 2:
            return int(rng.integers(-2**40, 2**40))
        if c == 3:
            return float(rng.normal())
        if c == 4:
            return bytes(rng.integers(0, 256, int(rng.integers(0, 12)),
                                      dtype=_np.uint8))
        if c == 5:
            return "".join(chr(int(rng.integers(32, 1000)))
                           for _ in range(int(rng.integers(0, 8))))
        n = int(rng.integers(0, 4))
        if c == 6:
            return tuple(gen(depth + 1) for _ in range(n))
        if c == 7:
            return [gen(depth + 1) for _ in range(n)]
        if c == 8:
            return {int(rng.integers(100)): gen(depth + 1)
                    for _ in range(n)}
        return PeerId(int(rng.integers(10)), f"n{int(rng.integers(4))}")

    for _ in range(200):
        v = gen()
        out = wire.decode(wire.encode(v))
        assert out == v and type(out) is type(v)


# -- native codec (native/wirecodec.cc) differential tests ---------------

def _native():
    mod = wire._native_codec()
    if mod is None:
        pytest.skip("native wire codec unavailable (no toolchain)")
    return mod


@pytest.mark.parametrize("seed", range(4))
def test_native_encode_byte_exact_with_python(seed):
    """Native and Python frames must be interchangeable on the wire:
    identical bytes for identical values (same tags, varints, int
    widths, container order)."""
    import numpy as _np

    mod = _native()
    rng = _np.random.default_rng(seed)

    def gen(depth=0):
        choices = 11 if depth < 4 else 6
        c = int(rng.integers(choices))
        if c == 0:
            return None
        if c == 1:
            return bool(rng.integers(2))
        if c == 2:
            # spans the small-int fast path, the 8-byte boundary, and
            # the arbitrary-precision slow path
            return int(rng.integers(-2**40, 2**40)) << int(rng.integers(40))
        if c == 3:
            return float(rng.normal())
        if c == 4:
            return bytes(rng.integers(0, 256, int(rng.integers(0, 12)),
                                      dtype=_np.uint8))
        if c == 5:
            return "".join(chr(int(rng.integers(32, 1000)))
                           for _ in range(int(rng.integers(0, 8))))
        n = int(rng.integers(0, 4))
        if c == 6:
            return tuple(gen(depth + 1) for _ in range(n))
        if c == 7:
            return [gen(depth + 1) for _ in range(n)]
        if c == 8:
            return {int(rng.integers(100)): gen(depth + 1)
                    for _ in range(n)}
        if c == 9:
            return frozenset(int(rng.integers(1000)) for _ in range(n))
        return PeerId(int(rng.integers(10)), f"n{int(rng.integers(4))}")

    for _ in range(300):
        v = gen()
        py = wire.encode_py(v)
        assert mod.encode(v) == py, v
        got = mod.decode(py)
        assert got == v and type(got) is type(v)


def test_native_int_edges_byte_exact():
    mod = _native()
    edges = [0, 1, -1, 127, 128, 129, -127, -128, -129, 255, 256,
             2**31 - 1, 2**31, -2**31, -2**31 - 1, 2**62, 2**63 - 1,
             2**63, -2**63, -2**63 - 1, 2**64, 2**200, -2**200]
    for v in edges:
        assert mod.encode(v) == wire.encode_py(v), v
        assert mod.decode(wire.encode_py(v)) == v, v


@pytest.mark.parametrize("seed", range(3))
def test_native_decode_error_parity_on_random_bytes(seed):
    """Hostile-input agreement: for random byte soup both decoders
    either produce the same value or both raise WireError (and the
    native one never raises anything else, segfaults excepted by
    construction)."""
    import numpy as _np

    mod = _native()
    rng = _np.random.default_rng(1000 + seed)
    for _ in range(2000):
        blob = bytes(rng.integers(0, 256, int(rng.integers(1, 40)),
                                  dtype=_np.uint8))
        try:
            a = ("ok", wire.decode_py(blob))
        except wire.WireError:
            a = ("err",)
        try:
            b = ("ok", mod.decode(blob))
        except wire.WireError:
            b = ("err",)
        if a[0] == b[0] == "ok":
            assert wire.encode_py(a[1]) == wire.encode_py(b[1]), \
                (blob.hex(), a, b)
        else:
            assert a[0] == b[0], (blob.hex(), a, b)


def test_native_mutated_valid_frames_error_parity():
    """Mutations of VALID frames (bit flips, truncation, extension)
    hit deeper decode paths than raw byte soup."""
    import numpy as _np

    mod = _native()
    rng = _np.random.default_rng(4242)
    base = wire.encode_py(
        {"k": (PeerId(1, "n1"), [1.5, NOTFOUND, -2**70, "déjà"],
               frozenset({1, 2}), b"\x00\xff")})
    for _ in range(3000):
        blob = bytearray(base)
        for _m in range(int(rng.integers(1, 4))):
            op = int(rng.integers(3))
            if op == 0 and blob:
                blob[int(rng.integers(len(blob)))] ^= \
                    1 << int(rng.integers(8))
            elif op == 1 and len(blob) > 1:
                del blob[int(rng.integers(len(blob))):]
            else:
                blob.extend(rng.integers(0, 256, 2, dtype=_np.uint8))
        blob = bytes(blob)
        try:
            a = ("ok", wire.decode_py(blob))
        except wire.WireError:
            a = ("err",)
        try:
            b = ("ok", mod.decode(blob))
        except wire.WireError:
            b = ("err",)
        # NaN-safe equivalence: compare canonical re-encodings (two
        # separately built NaNs are != even inside equal structures)
        if a[0] == b[0] == "ok":
            assert wire.encode_py(a[1]) == wire.encode_py(b[1]), \
                (blob.hex(), a, b)
        else:
            assert a[0] == b[0], (blob.hex(), a, b)


def test_native_depth_limits_match():
    mod = _native()
    v = []
    for _ in range(1000):
        v = [v]
    with pytest.raises(wire.WireError):
        mod.encode(v)
    deep = b"l\x01" * 40 + b"N"
    for dec in (wire.decode_py, mod.decode):
        with pytest.raises(wire.WireError):
            dec(deep)
