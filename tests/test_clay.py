"""CLAY coupled-layer MSR code tests.

Mirrors the reference's TestErasureCodeClay.cc coverage: parameter
geometry (q, t, nu, sub_chunk_no), encode/decode round-trips across
erasure patterns, the bandwidth-optimal single-chunk repair path (reads
exactly sub_chunk_no/q sub-chunks of each of d helpers), and
minimum_to_decode's sub-chunk (offset, count) runs — plus the ECUtil
recovery plumbing end-to-end with partial helper payloads.
"""

import itertools

import numpy as np
import pytest

from ceph_tpu.ec import ECError, registry
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.ecutil import StripeInfo


def make(k, m, d, **extra):
    profile = {"k": str(k), "m": str(m), "d": str(d), **extra}
    return registry.factory("clay", profile)


# -- geometry ----------------------------------------------------------------


def test_parameter_geometry():
    ec = make(4, 2, 5)
    assert (ec.q, ec.t, ec.nu) == (2, 3, 0)
    assert ec.get_sub_chunk_count() == 8
    assert ec.get_chunk_count() == 6
    assert ec.get_data_chunk_count() == 4

    ec = make(8, 4, 11)
    assert (ec.q, ec.t, ec.nu) == (4, 3, 0)
    assert ec.get_sub_chunk_count() == 64

    # shortened code: k+m not divisible by q
    ec = make(3, 3, 5)
    assert (ec.q, ec.nu) == (3, 0)
    ec = make(4, 3, 6)
    assert ec.q == 3
    assert ec.nu == 2  # (3 - 7%3) % 3
    assert (ec.k + ec.m + ec.nu) % ec.q == 0


def test_d_range_validation():
    with pytest.raises(ECError):
        make(4, 2, 3)  # d < k
    with pytest.raises(ECError):
        make(4, 2, 6)  # d > k+m-1
    with pytest.raises(ECError):
        make(4, 2, 5, scalar_mds="nope")


def test_default_d_is_k_plus_m_minus_1():
    profile = {"k": "4", "m": "2"}
    ec = registry.factory("clay", profile)
    assert ec.d == 5
    assert profile["d"] == "5"


# -- round trips -------------------------------------------------------------

CONFIGS = [
    (4, 2, 5, {}),
    (4, 2, 5, {"scalar_mds": "isa"}),
    (3, 3, 5, {}),   # q=3, t=2
    (4, 3, 6, {}),   # shortened (nu=2)
    (8, 4, 11, {}),  # the BASELINE.json repair scenario
]


@pytest.mark.parametrize("k,m,d,extra", CONFIGS, ids=lambda c: str(c))
def test_encode_decode_roundtrip(k, m, d, extra):
    ec = make(k, m, d, **extra)
    cs = ec.get_chunk_size(1)
    rng = np.random.default_rng(k * 100 + m * 10 + d)
    data = rng.integers(0, 256, k * cs, dtype=np.uint8)
    encoded = ec.encode(set(range(k + m)), data)
    assert set(encoded) == set(range(k + m))
    assert all(len(c) == cs for c in encoded.values())

    # all data present: passthrough
    got = ec.decode_concat(encoded)
    assert np.array_equal(got[: len(data)], data)

    # every single and double erasure pattern (m>=2)
    pats = list(itertools.combinations(range(k + m), 1)) + list(
        itertools.combinations(range(k + m), 2)
    )
    for lost in pats[: 12 if k > 4 else None]:
        avail = {i: c for i, c in encoded.items() if i not in lost}
        dec = ec.decode(set(lost), avail, cs)
        for i in lost:
            assert np.array_equal(dec[i], encoded[i]), (lost, i)


def test_triple_erasure_with_m3():
    ec = make(4, 3, 6)
    cs = ec.get_chunk_size(1)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, 4 * cs, dtype=np.uint8)
    encoded = ec.encode(set(range(7)), data)
    for lost in [(0, 1, 2), (0, 3, 5), (4, 5, 6), (1, 4, 6)]:
        avail = {i: c for i, c in encoded.items() if i not in lost}
        dec = ec.decode(set(lost), avail, cs)
        for i in lost:
            assert np.array_equal(dec[i], encoded[i]), lost


def test_too_many_erasures_raises():
    ec = make(4, 2, 5)
    cs = ec.get_chunk_size(1)
    data = np.zeros(4 * cs, dtype=np.uint8)
    encoded = ec.encode(set(range(6)), data)
    avail = {i: c for i, c in encoded.items() if i >= 3}  # only 3 chunks
    with pytest.raises(ECError):
        ec.decode({0, 1, 2}, avail, cs)


# -- repair path -------------------------------------------------------------


@pytest.mark.parametrize("k,m,d,extra", CONFIGS, ids=lambda c: str(c))
def test_single_chunk_repair_reads_minimum(k, m, d, extra):
    """Repair of one chunk must read only sub_chunk_no/q of each of d
    helpers and reconstruct bit-exactly (the MSR property)."""
    ec = make(k, m, d, **extra)
    cs = ec.get_chunk_size(1)
    sub = ec.get_sub_chunk_count()
    sc_size = cs // sub
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, k * cs, dtype=np.uint8)
    encoded = ec.encode(set(range(k + m)), data)

    for lost in range(k + m):
        avail = set(range(k + m)) - {lost}
        minimum = ec.minimum_to_decode({lost}, avail)
        assert len(minimum) == d, lost
        # each helper contributes exactly sub/q sub-chunks
        for node, runs in minimum.items():
            assert sum(c for _, c in runs) == sub // ec.q, (lost, node)
        # gather only those sub-chunk runs (what the OSD would read)
        helper = {}
        for node, runs in minimum.items():
            parts = [
                encoded[node][off * sc_size : (off + cnt) * sc_size]
                for off, cnt in runs
            ]
            helper[node] = np.concatenate(parts)
        dec = ec.decode({lost}, helper, cs)
        assert np.array_equal(dec[lost], encoded[lost]), lost


def test_repair_vs_full_decode_agree():
    """The sub-chunk repair path and the full-payload decode must
    produce the same bytes for the same lost chunk."""
    ec = make(4, 2, 5)
    cs = ec.get_chunk_size(1)
    sub = ec.get_sub_chunk_count()
    sc_size = cs // sub
    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, 4 * cs, dtype=np.uint8)
    encoded = ec.encode(set(range(6)), data)
    for lost in (0, 2, 5):
        # full-payload decode (no sub-chunk savings)
        avail_full = {i: c for i, c in encoded.items() if i != lost}
        full = ec.decode({lost}, avail_full, cs)
        # partial-read repair via minimum_to_decode runs
        minimum = ec.minimum_to_decode({lost}, set(range(6)) - {lost})
        helper = {
            node: np.concatenate(
                [encoded[node][o * sc_size : (o + c) * sc_size] for o, c in runs]
            )
            for node, runs in minimum.items()
        }
        rep = ec.decode({lost}, helper, cs)
        assert np.array_equal(full[lost], rep[lost]), lost
        assert np.array_equal(rep[lost], encoded[lost]), lost


def test_is_repair_predicate():
    ec = make(4, 2, 5)
    # multi-chunk wants are never repair
    assert not ec.is_repair({0, 1}, {2, 3, 4, 5})
    # want present: not repair
    assert not ec.is_repair({0}, {0, 1, 2, 3, 4})
    # fewer than d helpers: not repair
    assert not ec.is_repair({0}, {1, 2, 3})
    # d helpers incl. the lost node's q-group: repair
    assert ec.is_repair({0}, {1, 2, 3, 4, 5})


# -- ECUtil integration (recovery flow with partial reads) -------------------


def test_ecutil_decode_shards_with_subchunk_reads():
    ec = make(4, 2, 5)
    k = 4
    cs = ec.get_chunk_size(1)
    si = StripeInfo(k, k * cs)
    sub = ec.get_sub_chunk_count()
    sc_size = cs // sub
    rng = np.random.default_rng(31)
    ns = 3  # three stripes in the shard payloads
    data = rng.integers(0, 256, ns * si.stripe_width, dtype=np.uint8)
    shards = ecutil.encode(si, ec, data)

    lost = 1
    minimum = ec.minimum_to_decode({lost}, set(range(6)) - {lost})
    # simulate the OSD reading only the minimum sub-chunk runs of each
    # helper shard, per stripe-chunk
    helper_payloads = {}
    for node, runs in minimum.items():
        pieces = []
        for s in range(ns):
            base = s * cs
            for off, cnt in runs:
                pieces.append(
                    shards[node][base + off * sc_size : base + (off + cnt) * sc_size]
                )
        helper_payloads[node] = np.concatenate(pieces)

    rebuilt = ecutil.decode_shards(
        si, ec, helper_payloads, {lost}, packed_repair=True
    )
    assert np.array_equal(rebuilt[lost], shards[lost])


def test_ecutil_encode_decode_concat_clay():
    ec = make(4, 2, 5)
    cs = ec.get_chunk_size(1)
    si = StripeInfo(4, 4 * cs)
    rng = np.random.default_rng(37)
    data = rng.integers(0, 256, 2 * si.stripe_width, dtype=np.uint8)
    shards = ecutil.encode(si, ec, data)
    assert np.array_equal(ecutil.decode_concat(si, ec, shards), data)
    avail = {s: c for s, c in shards.items() if s not in (0, 5)}
    assert np.array_equal(ecutil.decode_concat(si, ec, avail), data)
