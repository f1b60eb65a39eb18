"""ECUtil stripe math, batched encode/decode, HashInfo, native crc32c.

Golden crc32c values come from reference src/test/common/test_crc32c.cc
(Small/PartialWord/Big cases), pinning our kernel to ceph_crc32c
bit-for-bit.  Encode/decode layout equivalence is checked against a
hand-rolled per-stripe loop over the plugin's own encode() (the
reference ECUtil.cc:123-162 algorithm).
"""

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.ec import registry as ec_registry  # singleton instance
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.ecutil import HashInfo, StripeInfo


# -- crc32c ------------------------------------------------------------------

REFERENCE_CRC_VECTORS = [
    # (seed, payload, expected) from test_crc32c.cc:21-43
    (0, b"foo bar baz", 4119623852),
    (1234, b"foo bar baz", 881700046),
    (0, b"whiz bang boom", 2360230088),
    (5678, b"whiz bang boom", 3743019208),
    (0, b"\x01" * 5, 2715569182),
    (0, b"\x01" * 35, 440531800),
    (0, b"\x01" * 4096000, 31583199),
    (1234, b"\x01" * 4096000, 1400919119),
]


def test_crc32c_reference_vectors():
    for seed, payload, want in REFERENCE_CRC_VECTORS:
        assert native.crc32c(payload, seed) == want, (seed, len(payload))


def test_crc32c_python_fallback_matches_native():
    rng = np.random.default_rng(1)
    for n in (0, 1, 7, 8, 9, 63, 1024):
        buf = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert native._py_crc32c(buf, 0xFFFFFFFF) == native.crc32c(buf)


def test_crc32c_zeros_matches_explicit_buffer():
    for n in (0, 1, 15, 16, 17, 4096):
        for seed in (0, 1234, 0xFFFFFFFF):
            assert native.crc32c_zeros(n, seed) == native.crc32c(b"\0" * n, seed)


def test_crc32c_chaining_splits():
    # crc(seed, a+b) == crc(crc(seed, a), b) — the HashInfo append chain
    rng = np.random.default_rng(2)
    buf = rng.integers(0, 256, 1000, dtype=np.uint8).tobytes()
    whole = native.crc32c(buf)
    for cut in (0, 1, 8, 500, 999, 1000):
        assert native.crc32c(buf[cut:], native.crc32c(buf[:cut])) == whole


# the CPU's CRC32C instruction against its two oracles: the slice-by-8
# tables (`table=True`, an argument of the C entry point) and the
# pure-Python byte loop.  Lengths straddle the three-stream block
# (3 x 2048 bytes) and its multiples.
CRC_LENGTHS = [0, 1, 7, 8, 9, 63, 2047, 2048, 6143, 6144, 6145, 12288,
               18433, 65536, 70000]


def _crc_bytes(n: int, salt: int = 0) -> bytes:
    return np.random.default_rng(1000 + n + salt).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def test_crc_backend_names_a_real_backend():
    assert native.available()
    assert native.crc_backend() in ("sse4.2", "armv8", "table")
    with open("/proc/cpuinfo") as f:
        flags = f.read()
    if "sse4_2" in flags:
        assert native.crc_backend() == "sse4.2"


@pytest.mark.parametrize("n", CRC_LENGTHS)
def test_crc32c_hardware_equals_table_equals_python(n):
    buf = _crc_bytes(n)
    rng = np.random.default_rng(n)
    for seed in (0, 0xFFFFFFFF, -1, int(rng.integers(0, 2**32))):
        want = native._py_crc32c(buf, seed)
        assert native.crc32c(buf, seed, table=True) == want, (n, seed)
        assert native.crc32c(buf, seed) == want, (n, seed)


def test_crc32c_random_lengths_hardware_equals_table():
    rng = np.random.default_rng(7)
    for n in rng.integers(0, 70001, 200):
        buf = _crc_bytes(int(n), salt=1)
        seed = int(rng.integers(0, 2**32))
        assert native.crc32c(buf, seed) == native.crc32c(
            buf, seed, table=True), (n, seed)


def test_crc32c_4MiB_all_paths_agree():
    buf = _crc_bytes(4 << 20)
    want = native._py_crc32c(buf, 0xFFFFFFFF)
    assert native.crc32c(buf, table=True) == want
    assert native.crc32c(buf) == want
    assert native.crc32c(buf, 0) == native.crc32c(buf, 0, table=True)


@pytest.mark.parametrize("align", range(16))
def test_crc32c_every_alignment_of_an_offset_memoryview(align):
    n = 3 * 6144 + 77
    raw = bytearray(_crc_bytes(n + 16))
    for view in (memoryview(raw)[align:align + n],
                 memoryview(bytes(raw))[align:align + n]):
        want = native.crc32c(bytes(view), table=True)
        assert native.crc32c(view) == want
        assert native.crc32c(view, table=True) == want
    assert native._py_crc32c(raw[align:align + 500], 0xFFFFFFFF) == \
        native.crc32c(memoryview(raw)[align:align + 500])


@pytest.mark.parametrize("kind", ["bytes", "bytearray", "memoryview",
                                  "readonly_memoryview", "numpy",
                                  "numpy_strided", "numpy_2d"])
def test_crc32c_input_kinds(kind):
    buf = _crc_bytes(20000)
    want = native.crc32c(buf, 99, table=True)
    arr = np.frombuffer(buf, dtype=np.uint8)
    data = {
        "bytes": buf,
        "bytearray": bytearray(buf),
        "memoryview": memoryview(bytearray(buf)),
        "readonly_memoryview": memoryview(buf),
        "numpy": arr,
        "numpy_strided": np.repeat(arr, 2)[::2],
        "numpy_2d": arr.reshape(100, 200),
    }[kind]
    assert native.crc32c(data, 99) == want
    assert native.crc32c(data, 99, table=True) == want
    empty = {"bytes": b"", "bytearray": bytearray(),
             "memoryview": memoryview(bytearray()),
             "readonly_memoryview": memoryview(b"")}.get(kind)
    if empty is not None:
        assert native.crc32c(empty, 99) == 99


@pytest.mark.parametrize("kind", ["bytes", "memoryview", "numpy"])
@pytest.mark.parametrize("n", [native._GIL_KEPT_BELOW - 1,
                               native._GIL_KEPT_BELOW])
def test_crc32c_same_value_with_the_gil_kept_or_let_go(n, kind):
    """Below the threshold the call keeps the GIL (a PyDLL handle of
    the same function), from it on it lets go: one value either way."""
    buf = _crc_bytes(n, salt=3)
    data = {"bytes": buf, "memoryview": memoryview(bytearray(buf)),
            "numpy": np.frombuffer(buf, dtype=np.uint8)}[kind]
    lib = native._load()
    kept = n < native._GIL_KEPT_BELOW
    assert (native._crc_fn(lib, n) is lib.crc32c_gil_kept) == kept
    assert (native._crc_fn(lib, n) is lib.ceph_tpu_crc32c) != kept
    assert native.crc32c(data, 5) == native._py_crc32c(buf, 5)
    assert native.crc32c(data, 5, table=True) == native._py_crc32c(buf, 5)


@pytest.mark.parametrize("n", [1000, 6144, 3 * 6144 + 5, 70000])
def test_crc32c_chaining_across_paths(n):
    # crc(b, crc(a)) == crc(a + b), whichever path computed either part
    buf = _crc_bytes(n, salt=2)
    whole = native.crc32c(buf, table=True)
    for cut in (0, 1, 8, n // 3, n // 2, n - 1, n):
        a, b = buf[:cut], buf[cut:]
        assert native.crc32c(b, native.crc32c(a)) == whole
        assert native.crc32c(b, native.crc32c(a, table=True)) == whole
        assert native.crc32c(b, native.crc32c(a), table=True) == whole


@pytest.mark.parametrize("seed", [0, 1, 1234, 0xFFFFFFFF])
def test_crc32c_zeros_unchanged(seed):
    # the nullptr path never reaches the instruction: same loop as
    # before, and equal to both paths over an explicit zero buffer
    for n in (0, 1, 15, 16, 17, 4096, 6144, 100000):
        z = native.crc32c_zeros(n, seed)
        assert z == native.crc32c(b"\0" * n, seed)
        assert z == native.crc32c(b"\0" * n, seed, table=True)


def test_xor_region():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 256, 4097, dtype=np.uint8)
    b = rng.integers(0, 256, 4097, dtype=np.uint8)
    want = a ^ b
    native.xor_region(a, b)
    assert np.array_equal(a, want)


# -- StripeInfo --------------------------------------------------------------


def test_stripe_info_offsets():
    si = StripeInfo(4, 4096)  # k=4, chunk 1024
    assert si.chunk_size == 1024
    assert si.logical_to_prev_chunk_offset(10000) == 2 * 1024
    assert si.logical_to_next_chunk_offset(10000) == 3 * 1024
    assert si.logical_to_prev_stripe_offset(10000) == 8192
    assert si.logical_to_next_stripe_offset(10000) == 12288
    assert si.logical_to_next_stripe_offset(8192) == 8192
    assert si.aligned_logical_offset_to_chunk_offset(8192) == 2048
    assert si.aligned_chunk_offset_to_logical_offset(2048) == 8192
    assert si.offset_len_to_stripe_bounds(5000, 2000) == (4096, 4096)
    assert si.offset_len_to_stripe_bounds(4095, 2) == (0, 8192)
    assert si.offset_len_to_stripe_bounds(4095, 1) == (0, 4096)


# -- batched encode/decode ---------------------------------------------------


def _mk(plugin, profile):
    return ec_registry.factory(plugin, dict(profile))


PROFILES = [
    ("jerasure", {"k": "4", "m": "2", "technique": "reed_sol_van"}),
    ("jerasure", {"k": "3", "m": "2", "technique": "cauchy_good",
                  "packetsize": "32"}),
    ("isa", {"k": "8", "m": "3"}),
    ("jax", {"k": "4", "m": "2"}),
]


@pytest.mark.parametrize("plugin,profile", PROFILES)
def test_encode_matches_per_stripe_loop(plugin, profile):
    ec = _mk(plugin, profile)
    k, n = ec.get_data_chunk_count(), ec.get_chunk_count()
    cs = ec.get_chunk_size(4096)
    si = StripeInfo(k, k * cs)
    ns = 5
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, ns * si.stripe_width, dtype=np.uint8)

    got = ecutil.encode(si, ec, data)
    assert set(got) == set(range(n))

    # reference algorithm: per-stripe plugin encode, concat per shard
    want: dict[int, list] = {}
    for s in range(ns):
        enc = ec.encode(
            set(range(n)), data[s * si.stripe_width : (s + 1) * si.stripe_width]
        )
        for shard, chunk in enc.items():
            want.setdefault(shard, []).append(chunk)
    for shard in range(n):
        assert np.array_equal(got[shard], np.concatenate(want[shard])), shard


@pytest.mark.parametrize("plugin,profile", PROFILES)
def test_decode_concat_roundtrip_and_degraded(plugin, profile):
    ec = _mk(plugin, profile)
    k, n = ec.get_data_chunk_count(), ec.get_chunk_count()
    cs = ec.get_chunk_size(4096)
    si = StripeInfo(k, k * cs)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 4 * si.stripe_width, dtype=np.uint8)
    shards = ecutil.encode(si, ec, data)

    # healthy read
    assert np.array_equal(ecutil.decode_concat(si, ec, shards), data)
    # degraded: drop m shards
    m = n - k
    lost = set(rng.choice(n, size=m, replace=False).tolist())
    avail = {s: c for s, c in shards.items() if s not in lost}
    assert np.array_equal(ecutil.decode_concat(si, ec, avail), data)


@pytest.mark.parametrize("plugin,profile", PROFILES)
def test_decode_shards_recovery(plugin, profile):
    ec = _mk(plugin, profile)
    k, n = ec.get_data_chunk_count(), ec.get_chunk_count()
    cs = ec.get_chunk_size(4096)
    si = StripeInfo(k, k * cs)
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, 3 * si.stripe_width, dtype=np.uint8)
    shards = ecutil.encode(si, ec, data)

    lost = set(rng.choice(n, size=n - k, replace=False).tolist())
    avail = {s: c for s, c in shards.items() if s not in lost}
    rebuilt = ecutil.decode_shards(si, ec, avail, lost)
    for s in lost:
        assert np.array_equal(rebuilt[s], shards[s]), s


# -- HashInfo ----------------------------------------------------------------


def test_hashinfo_append_chain_and_serialize():
    ec = _mk("isa", {"k": "2", "m": "1"})
    si = StripeInfo(2, 2 * ec.get_chunk_size(2048))
    rng = np.random.default_rng(7)
    a = rng.integers(0, 256, si.stripe_width, dtype=np.uint8)
    b = rng.integers(0, 256, 2 * si.stripe_width, dtype=np.uint8)

    hi = HashInfo(3)
    sh_a = ecutil.encode(si, ec, a)
    hi.append(0, sh_a)
    sh_b = ecutil.encode(si, ec, b)
    hi.append(si.chunk_size, sh_b)
    assert hi.get_total_chunk_size() == 3 * si.chunk_size

    # chained crc == crc of full concatenated shard payload
    full = ecutil.encode(
        si, ec, np.concatenate([a, b])
    )
    for shard in range(3):
        assert hi.get_chunk_hash(shard) == native.crc32c(full[shard])

    rt = HashInfo.from_bytes(hi.to_bytes())
    assert rt.cumulative_shard_hashes == hi.cumulative_shard_hashes
    assert rt.get_total_chunk_size() == hi.get_total_chunk_size()


def test_hashinfo_append_size_mismatch_asserts():
    hi = HashInfo(2)
    hi.append(0, {0: np.zeros(8, np.uint8), 1: np.zeros(8, np.uint8)})
    with pytest.raises(AssertionError):
        hi.append(4, {0: np.zeros(8, np.uint8), 1: np.zeros(8, np.uint8)})
