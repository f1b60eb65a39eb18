"""A vector code on the served device path (PR 34): CLAY's encode and
its single-chunk repair filed with the encode service and the decode
aggregator as matrices over sub-chunk rows, held to the plain reference
of the benchmark (``benchmarks/references/clay8411.py``, loaded by path:
it imports nothing of the program) and to the host loops, tiny, on the
CPU backend.  Nothing here is a device number.
"""

from __future__ import annotations

import asyncio
import importlib.util
import os

import numpy as np
import pytest

from ceph_tpu.ec import registry
from ceph_tpu.ec.interface import ECError
from ceph_tpu.osd import ecutil
from ceph_tpu.parallel.decode_batcher import DecodeAggregator
from ceph_tpu.parallel.encode_service import EncodeService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: (k, m, d, stripe unit): q=2 t=3, 8 sub-chunks of 32 B; and the
#: benchmark's code, q=4 t=3, 64 sub-chunks of 64 B
GEOMETRIES = {"k4m2d5": (4, 2, 5, 256), "k8m4d11": (8, 4, 11, 64 * 64)}
STRIPES = 2
LOSSES = [(g, lost) for g, (k, m, _d, _u) in GEOMETRIES.items()
          for lost in range(k + m)]


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_clay8411",
        os.path.join(ROOT, "benchmarks", "references", "clay8411.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _code(geometry: str):
    k, m, d, unit = GEOMETRIES[geometry]
    ec = registry.factory("clay", {
        "plugin": "clay", "k": str(k), "m": str(m), "d": str(d),
        "stripe_unit": str(unit)})
    return ec, ecutil.stripe_info(ec)


def _blob(geometry: str, seed: int) -> bytes:
    k, _m, _d, unit = GEOMETRIES[geometry]
    return np.random.default_rng([34, seed]).integers(
        0, 256, STRIPES * k * unit, dtype=np.uint8).tobytes()


def _services():
    import jax

    # a service on the CPU device, taking every payload: what a TPU
    # host's shared() is, at this test's sizes
    return (EncodeService(device=jax.devices()[0], min_bytes=1),
            DecodeAggregator())


def _packed_helpers(ec, sinfo, shards, lost: int) -> dict:
    """What recovery's ranged reads bring: for each helper the repair
    sub-chunk runs of every stripe, stripe-major.  ``shards`` maps (or
    lists) chunk id -> its whole payload."""
    cs = sinfo.chunk_size
    sub = cs // ec.get_sub_chunk_count()
    ids = range(len(shards)) if isinstance(shards, list) else shards
    out = {}
    for h, runs in ec.minimum_to_decode({lost}, set(ids) - {lost}).items():
        buf = np.frombuffer(shards[h], np.uint8)
        out[h] = np.concatenate([
            buf[s * cs + o * sub: s * cs + (o + c) * sub]
            for s in range(len(buf) // cs) for o, c in runs])
    return out


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_served_encode_equals_the_reference_and_the_host_loop(geometry):
    ec, sinfo = _code(geometry)
    k, m, d, unit = GEOMETRIES[geometry]
    svc, _agg = _services()
    blobs = [_blob(geometry, i) for i in range(3)]

    async def go():
        one = await ecutil.encode_async(sinfo, ec, blobs[0], service=svc)
        first = svc.stats["single_dispatches"]
        window = await asyncio.gather(*(
            ecutil.encode_async(sinfo, ec, b, service=svc)
            for b in blobs[1:]))
        return one, first, window

    one, first, window = asyncio.run(go())
    assert first == 1 and svc.stats["fallbacks"] == 0
    # a window's requests lie side by side in one launch
    assert svc.stats["single_dispatches"] == 2
    assert svc.stats["coalesced"] == len(blobs)
    for blob, got in zip(blobs, [one, *window]):
        want = REF.clay_shards(blob, k, m, d, unit)
        host = ecutil.encode(sinfo, ec, blob)
        assert sorted(got) == list(range(k + m))
        for i in range(k + m):
            assert got[i].tobytes() == want[i], (geometry, i)
            assert got[i].tobytes() == host[i].tobytes(), (geometry, i)
    # want= cuts the answer, not the launch
    some = asyncio.run(ecutil.encode_async(
        sinfo, ec, blobs[0], {0, k}, service=svc))
    assert sorted(some) == [0, k] and some[k].tobytes() == \
        REF.clay_shards(blobs[0], k, m, d, unit)[k]


@pytest.mark.parametrize("geometry,lost", LOSSES,
                         ids=[f"{g}-lost{n}" for g, n in LOSSES])
def test_served_repair_equals_the_reference_and_the_host_loop(
        geometry, lost):
    ec, sinfo = _code(geometry)
    k, m, d, unit = GEOMETRIES[geometry]
    svc, agg = _services()
    objects = [REF.clay_shards(_blob(geometry, i), k, m, d, unit)
               for i in range(3)]
    helpers = [_packed_helpers(ec, sinfo, shards, lost)
               for shards in objects]
    # the code's promise: d helpers, 1/q of a chunk each
    assert len(helpers[0]) == d and all(
        v.nbytes * ec.q == STRIPES * unit for v in helpers[0].values())

    async def repair(h):
        return await ecutil.decode_shards_async(
            sinfo, ec, h, {lost}, packed_repair=True,
            service=svc, aggregator=agg)

    async def go():
        one = await repair(helpers[0])
        first = agg.stats["launches"]
        return one, first, await asyncio.gather(*map(repair, helpers[1:]))

    one, first, window = asyncio.run(go())
    assert first == 1 and agg.stats["fallbacks"] == 0
    assert agg.stats["launches"] == 2       # the window shared a launch
    assert agg.stats["batched_requests"] == len(objects)
    for shards, h, got in zip(objects, helpers, [one, *window]):
        assert list(got) == [lost]
        assert got[lost].tobytes() == shards[lost], (geometry, lost)
        host = ecutil.decode_shards(sinfo, ec, h, {lost},
                                    packed_repair=True)
        assert got[lost].tobytes() == host[lost].tobytes()


def test_repair_launch_is_tagged_and_prewarmed_shapes_stay_warm():
    from ceph_tpu.common.tracing import device_tracer

    ec, sinfo = _code("k4m2d5")
    k, m, d, unit = GEOMETRIES["k4m2d5"]
    _svc, agg = _services()
    assert agg.prewarm(ec, [sinfo.chunk_size // ec.get_sub_chunk_count()],
                       batches=(1,)) > 0
    shards = REF.clay_shards(_blob("k4m2d5", 9), k, m, d, unit)
    h = _packed_helpers(ec, sinfo, shards, 3)
    before = len(device_tracer().find(kind="clay_repair"))
    got = asyncio.run(ecutil.decode_shards_async(
        sinfo, ec, h, {3}, packed_repair=True, aggregator=agg))
    assert got[3].tobytes() == shards[3]
    assert agg.stats["cold_launches"] == 0
    spans = device_tracer().find(kind="clay_repair")
    assert len(spans) == before + 1
    tags = spans[-1].tags
    assert (tags["lost_node"], tags["objects"]) == (3, 1)
    assert tags["helper_bytes"] == sum(v.nbytes for v in h.values())
    assert tags["rebuilt_bytes"] == len(shards[3])
    assert tags["helper_bytes"] / tags["rebuilt_bytes"] == d / ec.q


@pytest.mark.parametrize("case", ["two_lost", "full_chunks", "aloof"])
def test_what_is_not_one_matrix_stays_on_the_host_loop(case):
    """Several losses, whole-chunk reads and d < k+m-1 keep the host
    path (recovery counts them: tests/integration/test_clay_repair.py)."""
    k, m, unit = 4, 2, 256
    d = 4 if case == "aloof" else 5
    ec = registry.factory("clay", {"plugin": "clay", "k": str(k),
                                   "m": str(m), "d": str(d)})
    sinfo = ecutil.StripeInfo(k, ec.get_chunk_size(unit * k) * k)
    _svc, agg = _services()
    blob = np.random.default_rng(5).integers(
        0, 256, 2 * sinfo.stripe_width, dtype=np.uint8).tobytes()
    shards = ecutil.encode(sinfo, ec, blob)
    need = {1, 4} if case == "two_lost" else {1}
    if case == "aloof":
        assert ec.repair_matrix(1) is None
        have = _packed_helpers(ec, sinfo, shards, 1)
        packed = True
    else:
        have = {i: v for i, v in shards.items() if i not in need}
        packed = False
    got = asyncio.run(ecutil.decode_shards_async(
        sinfo, ec, have, need, packed_repair=packed, aggregator=agg))
    assert agg.stats["requests"] == 0
    for i in need:
        assert got[i].tobytes() == shards[i].tobytes()


def test_matrices_are_derived_once_a_process():
    a, _ = _code("k8m4d11")
    b, _ = _code("k8m4d11")
    assert a.encode_matrix() is b.encode_matrix()
    assert a.encode_matrix().shape == (4 * 64, 8 * 64)
    assert a.repair_matrix(7) is b.repair_matrix(7)
    assert a.repair_matrix(7).shape == (64, 11 * 16)
    other = registry.factory("clay", {"plugin": "clay", "k": "8", "m": "4",
                                      "d": "11", "scalar_mds": "isa"})
    assert other.encode_matrix() is not a.encode_matrix()


def test_a_large_bit_matrix_takes_the_xla_kernel_not_the_fused_one():
    import jax.numpy as jnp

    from ceph_tpu.ops import rs_kernels
    from ceph_tpu.ops.gf256 import gf_matmul, gf_matrix_to_bitmatrix

    ec, _ = _code("k8m4d11")
    E = ec.encode_matrix()
    assert gf_matrix_to_bitmatrix(E).size > rs_kernels._PALLAS_MAX_BITS
    assert gf_matrix_to_bitmatrix(
        np.ones((24, 64), np.uint8)).size <= rs_kernels._PALLAS_MAX_BITS
    rows = np.random.default_rng(2).integers(0, 256, (512, 32), np.uint8)
    out = rs_kernels.BitmatrixCodec._apply(
        jnp.asarray(gf_matrix_to_bitmatrix(E)), jnp.asarray(rows), None)
    assert np.array_equal(np.asarray(out), gf_matmul(E, rows))


# -- the pool's stripe unit ----------------------------------------------------

def test_stripe_unit_in_a_profile_changes_that_pools_stripe_only():
    plain = registry.factory("jax", {"plugin": "jax", "technique": "cauchy",
                                     "k": "8", "m": "3"})
    assert ecutil.stripe_info(plain).chunk_size == 4096
    assert ecutil.stripe_info(plain).stripe_width == 8 * 4096
    clay, _ = _code("k8m4d11")
    big = registry.factory("clay", {"plugin": "clay", "k": "8", "m": "4",
                                    "d": "11", "stripe_unit": "262144"})
    default = registry.factory("clay", {"plugin": "clay", "k": "8",
                                        "m": "4", "d": "11"})
    assert ecutil.stripe_info(clay).chunk_size == 4096
    assert ecutil.stripe_info(big).chunk_size == 262144
    assert ecutil.stripe_info(big).stripe_width == 2 << 20
    # no key: upstream's osd_pool_erasure_code_stripe_unit
    assert ecutil.stripe_info(default).chunk_size == \
        default.get_chunk_size(4096 * 8)
    wide = registry.factory("jax", {"plugin": "jax", "technique": "cauchy",
                                    "k": "8", "m": "3",
                                    "stripe_unit": "65536"})
    assert ecutil.stripe_info(wide).chunk_size == 65536
    assert ecutil.stripe_info(plain).chunk_size == 4096


@pytest.mark.parametrize("unit,ok", [("262144", True), ("4096", True),
                                     ("2048", True), ("1000", False),
                                     ("4100", False), ("0", False)])
def test_a_profiles_stripe_unit_is_checked_against_the_plugins_alignment(
        unit, ok):
    ec = registry.factory("clay", {"plugin": "clay", "k": "8", "m": "4",
                                   "d": "11", "stripe_unit": unit})
    if ok:
        ecutil.check_stripe_unit(ec)
    else:
        with pytest.raises(ECError, match="stripe_unit"):
            ecutil.check_stripe_unit(ec)
