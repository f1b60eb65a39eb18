"""Recovery-decode aggregator: bucketed batched decode (CPU path).

Pins the tentpole contract of ceph_tpu/parallel/decode_batcher.py:

- concurrent decodes sharing an erasure signature coalesce into ONE
  fixed-shape batched launch (>= 4 objects per launch);
- the batched result is bit-identical to per-object
  ecutil.decode_shards;
- after prewarm, dispatching only warm shapes performs ZERO cold
  compiles (the no-XLA-compile-in-the-I/O-path discipline, asserted
  via the aggregator's cold_launches counter).
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.ec import registry
from ceph_tpu.osd import ecutil
from ceph_tpu.parallel.batcher import pow2_bucket
from ceph_tpu.parallel.decode_batcher import DecodeAggregator


def _ec(k=4, m=2):
    return registry.factory("jax", {"k": str(k), "m": str(m)})


def _encoded_object(ec, seed, nbytes):
    sinfo = ecutil.StripeInfo(
        ec.get_data_chunk_count(),
        ec.get_chunk_size(nbytes) * ec.get_data_chunk_count())
    rng = np.random.default_rng(seed)
    aligned = sinfo.logical_to_next_stripe_offset(nbytes)
    data = rng.integers(0, 256, aligned, dtype=np.uint8)
    shards = ecutil.encode(sinfo, ec, data)
    return sinfo, shards


class TestPow2Bucket:
    def test_bucketing(self):
        assert pow2_bucket(1, 1) == 1
        assert pow2_bucket(5, 1) == 8
        assert pow2_bucket(8, 1) == 8
        assert pow2_bucket(100, 4096) == 4096
        assert pow2_bucket(4097, 4096) == 8192


class TestAggregatorBitExact:
    @pytest.mark.parametrize("lost", [{0}, {2}, {1, 5}])
    def test_batched_equals_per_object(self, lost):
        """>=4 concurrent decodes of one signature: one batched launch,
        outputs bit-identical to the per-object sync decode."""
        ec = _ec()
        objs = [_encoded_object(ec, i, 40000 + 8192 * i) for i in range(6)]
        agg = DecodeAggregator(window_s=0.005)

        async def go():
            async def one(sinfo, shards):
                avail = {s: c for s, c in shards.items() if s not in lost}
                return await ecutil.decode_shards_async(
                    sinfo, ec, avail, set(lost), aggregator=agg)

            return await asyncio.gather(*(one(s, sh) for s, sh in objs))

        outs = asyncio.run(go())
        for (sinfo, shards), rebuilt in zip(objs, outs):
            avail = {s: c for s, c in shards.items() if s not in lost}
            ref = ecutil.decode_shards(sinfo, ec, avail, set(lost))
            assert set(rebuilt) == set(ref) == set(lost)
            for s in lost:
                assert np.array_equal(rebuilt[s], shards[s]), s
                assert np.array_equal(rebuilt[s], ref[s]), s
        # all six decodes share the signature: they must have coalesced
        # into batched launches of >= 4 objects on average
        assert agg.stats["requests"] == 6
        assert agg.stats["launches"] <= 2, dict(agg.stats)
        assert agg.stats["batched_requests"] / agg.stats["launches"] >= 4 \
            or agg.stats["launches"] == 2

    def test_min_four_objects_one_launch(self):
        """The acceptance-criterion shape: 4 same-sized objects, one
        signature -> exactly ONE batched launch."""
        ec = _ec()
        objs = [_encoded_object(ec, 10 + i, 65536) for i in range(4)]
        agg = DecodeAggregator(window_s=0.005)

        async def go():
            async def one(sinfo, shards):
                avail = {s: c for s, c in shards.items() if s != 1}
                return await ecutil.decode_shards_async(
                    sinfo, ec, avail, {1}, aggregator=agg)

            return await asyncio.gather(*(one(s, sh) for s, sh in objs))

        outs = asyncio.run(go())
        for (sinfo, shards), rebuilt in zip(objs, outs):
            assert np.array_equal(rebuilt[1], shards[1])
        assert agg.stats["launches"] == 1, dict(agg.stats)
        assert agg.stats["batched_requests"] == 4

    def test_mixed_signatures_separate_launches(self):
        """Different erasure signatures never share a launch (their
        decode matrices differ) but each still decodes bit-exactly."""
        ec = _ec()
        objs = [_encoded_object(ec, 20 + i, 32768) for i in range(4)]
        losses = [{0}, {0}, {3}, {3}]
        agg = DecodeAggregator(window_s=0.005)

        async def go():
            async def one(args):
                (sinfo, shards), lost = args
                avail = {s: c for s, c in shards.items() if s not in lost}
                return await ecutil.decode_shards_async(
                    sinfo, ec, avail, set(lost), aggregator=agg)

            return await asyncio.gather(*(one(a) for a in zip(objs, losses)))

        outs = asyncio.run(go())
        for (sinfo, shards), lost, rebuilt in zip(objs, losses, outs):
            for s in lost:
                assert np.array_equal(rebuilt[s], shards[s])
        assert agg.stats["launches"] == 2, dict(agg.stats)


class TestNoCompileAfterWarmup:
    def test_prewarm_then_zero_cold_launches(self):
        """After prewarm covers the profile's bucket shapes, recovery
        decodes hit only warm shapes — the compile counter stays 0."""
        ec = _ec()
        agg = DecodeAggregator(window_s=0.005)
        # prewarm the buckets an object of ~64 KiB will land in
        sinfo, shards = _encoded_object(ec, 30, 65536)
        cs = len(next(iter(shards.values())))
        n = agg.prewarm(ec, [cs], erasure_counts=(1,))
        assert n > 0
        assert agg.stats["cold_launches"] == 0

        async def go():
            async def one(seed):
                s, sh = _encoded_object(ec, seed, 65536)
                avail = {i: c for i, c in sh.items() if i != 2}
                out = await ecutil.decode_shards_async(
                    s, ec, avail, {2}, aggregator=agg)
                assert np.array_equal(out[2], sh[2])

            await asyncio.gather(*(one(40 + i) for i in range(5)))

        asyncio.run(go())
        assert agg.stats["launches"] >= 1
        assert agg.stats["cold_launches"] == 0, dict(agg.stats)

    def test_cold_launch_counted_without_warmup(self):
        """Sanity for the counter itself: an unwarmed shape counts."""
        ec = _ec()
        agg = DecodeAggregator(window_s=0.001)
        sinfo, shards = _encoded_object(ec, 50, 4096)

        async def go():
            avail = {i: c for i, c in shards.items() if i != 0}
            await ecutil.decode_shards_async(
                sinfo, ec, avail, {0}, aggregator=agg)

        asyncio.run(go())
        assert agg.stats["cold_launches"] == 1


class TestEncodeServiceWarmup:
    def test_single_device_prewarm_then_zero_cold(self):
        """The encode farm side of the discipline: after prewarm, the
        single-device coalescing path launches only warm shapes."""
        import jax

        from ceph_tpu.models import isa_cauchy_matrix
        from ceph_tpu.ops.gf256 import gf_matmul
        from ceph_tpu.parallel import encode_service as es

        async def go():
            svc = es.EncodeService(
                device=jax.devices()[0], min_bytes=1, window_s=0.005)
            M = isa_cauchy_matrix(4, 2)
            svc.prewarm(M, [4096], coalesce=8)
            assert svc.stats["prewarmed_shapes"] > 0
            assert svc.stats["cold_launches"] == 0
            rng = np.random.default_rng(5)
            reqs = [rng.integers(0, 256, (4, 4096), dtype=np.uint8)
                    for _ in range(6)]
            outs = await asyncio.gather(*(svc.apply(M, r) for r in reqs))
            for r, o in zip(reqs, outs):
                assert np.array_equal(o, gf_matmul(M, r))
            assert svc.stats["single_dispatches"] >= 1
            assert svc.stats["cold_launches"] == 0, dict(svc.stats)

        asyncio.run(go())


class TestMetricsWiring:
    def test_bucket_counters_report_efficiency(self):
        ec = _ec()
        agg = DecodeAggregator(window_s=0.005)

        async def go():
            async def one(seed):
                s, sh = _encoded_object(ec, seed, 32768)
                avail = {i: c for i, c in sh.items() if i != 1}
                await ecutil.decode_shards_async(
                    s, ec, avail, {1}, aggregator=agg)

            await asyncio.gather(*(one(60 + i) for i in range(4)))

        asyncio.run(go())
        eff = agg.metrics.efficiency()
        assert eff["launches"] >= 1
        assert 0 < eff["lane_occupancy"] <= 1
        assert 0 < eff["byte_occupancy"] <= 1
        # per-bucket keys are exposed for prometheus/perf dump
        assert any(k.startswith("launches_") for k in agg.metrics.dump())
