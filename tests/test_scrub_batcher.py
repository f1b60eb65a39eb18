"""Deep-scrub verification batcher: bucketed batched crc32c + parity
re-encode (CPU path).

Pins the tentpole contract of ceph_tpu/parallel/scrub_batcher.py:

- batched per-shard crc32c is bit-identical to the per-object host
  loop (native.crc32c), including pow2 padding and >64 KiB column-lane
  splits (crc32c's GF(2) linearity makes both exact);
- the batched parity re-encode flags exactly the parity shards the
  host re-encode-and-compare flags, returning masks, not parity;
- concurrent object verifications coalesce into fixed-shape launches
  (>= 4 objects per encode-compare launch);
- after prewarm, scrub dispatch performs ZERO cold compiles (the
  no-XLA-compile-in-the-scrub-path discipline, via cold_launches).
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.ec import registry
from ceph_tpu.native import crc32c
from ceph_tpu.osd import ecutil
from ceph_tpu.parallel.batcher import bucket_lanes
from ceph_tpu.parallel.scrub_batcher import ScrubVerifier


def _ec(k=3, m=2):
    return registry.factory("jax", {"k": str(k), "m": str(m)})


def _encoded_object(ec, seed, nbytes):
    k = ec.get_data_chunk_count()
    sinfo = ecutil.StripeInfo(k, ec.get_chunk_size(nbytes) * k)
    rng = np.random.default_rng(seed)
    data = rng.integers(
        0, 256, sinfo.logical_to_next_stripe_offset(nbytes), dtype=np.uint8)
    return ecutil.encode(sinfo, ec, data)


def _host_parity_bad(ec, shards):
    """The scrubber's host re-encode path, reduced to the mismatch set."""
    k = ec.get_data_chunk_count()
    cs = len(next(iter(shards.values())))
    sinfo = ecutil.StripeInfo(k, cs * k)
    logical = ecutil.decode_concat(sinfo, ec, {s: shards[s] for s in range(k)})
    expect = ecutil.encode(sinfo, ec, logical)
    return {
        s for s, p in shards.items()
        if s in expect and expect[s].tobytes() != np.asarray(p).tobytes()
    }


class TestBucketLanes:
    def test_closed_ladder(self):
        assert bucket_lanes(0, min_bucket=4096, tile_cap=65536) == []
        assert bucket_lanes(100, min_bucket=4096, tile_cap=65536) == [
            (0, 100, 4096)]
        assert bucket_lanes(4097, min_bucket=4096, tile_cap=65536) == [
            (0, 4097, 8192)]
        assert bucket_lanes(65536, min_bucket=4096, tile_cap=65536) == [
            (0, 65536, 65536)]
        lanes = bucket_lanes(150000, min_bucket=4096, tile_cap=65536)
        assert lanes == [(0, 65536, 65536), (65536, 65536, 65536),
                         (131072, 18928, 65536)]
        # every bucket is on the pow2 ladder => prewarm covers them all
        for _off, width, bucket in lanes:
            assert bucket & (bucket - 1) == 0 and width <= bucket


class TestBitExact:
    @pytest.mark.parametrize("nbytes", [5000, 40000, 200000])
    def test_crcs_match_host_loop(self, nbytes):
        """Batched crc32c == native per-shard crc32c for sizes below,
        at, and above the column-lane tile cap."""
        ec = _ec()
        shards = _encoded_object(ec, 1, nbytes)
        ver = ScrubVerifier(window_s=0.002)

        async def go():
            return await ver.verify_object(ec, shards)

        check = asyncio.run(go())
        assert check is not None
        for s, p in shards.items():
            assert check.crcs[s] == crc32c(p), s
        assert check.parity_bad == frozenset()

    def test_bytes_payloads(self):
        """The scrubber hands bytes (wire payloads), not arrays."""
        ec = _ec()
        shards = {s: c.tobytes() for s, c in
                  _encoded_object(ec, 2, 12345).items()}

        async def go():
            return await ScrubVerifier().verify_object(ec, shards)

        check = asyncio.run(go())
        for s, p in shards.items():
            assert check.crcs[s] == crc32c(p)

    @pytest.mark.parametrize("victim", [0, 3, 4])
    def test_parity_mask_matches_host_reencode(self, victim):
        """Corrupting any one shard flags exactly the parity shards the
        host re-encode-and-compare path flags (a corrupt DATA shard
        shows up as divergent parity — silent rot the crc chain alone
        cannot attribute)."""
        ec = _ec()
        shards = _encoded_object(ec, 3, 30000)
        shards[victim] = shards[victim].copy()
        shards[victim][7] ^= 0xA5

        async def go():
            return await ScrubVerifier().verify_object(ec, shards)

        check = asyncio.run(go())
        assert check.parity_bad == frozenset(_host_parity_bad(ec, shards))
        assert check.parity_bad  # some parity equation must break
        # crc still pinpoints the rotted shard itself
        assert check.crcs[victim] == crc32c(shards[victim])

    def test_partial_object_skips_parity_not_crc(self):
        """A shard missing => parity equations aren't checkable batched
        (parity_bad None -> scrubber host fallback), but the present
        shards' crcs still verify batched."""
        ec = _ec()
        shards = _encoded_object(ec, 4, 20000)
        del shards[2]

        async def go():
            return await ScrubVerifier().verify_object(ec, shards)

        check = asyncio.run(go())
        assert check.parity_bad is None
        for s, p in shards.items():
            assert check.crcs[s] == crc32c(p)

    def test_no_ec_impl_still_crcs(self):
        shards = {0: np.arange(1000, dtype=np.uint8) % 251}

        async def go():
            return await ScrubVerifier().verify_object(None, shards)

        check = asyncio.run(go())
        assert check.parity_bad is None
        assert check.crcs[0] == crc32c(shards[0])

    def test_empty_payload(self):
        async def go():
            return await ScrubVerifier().verify_object(
                None, {0: b"", 1: b"x"})

        check = asyncio.run(go())
        assert check.crcs[0] == crc32c(b"")
        assert check.crcs[1] == crc32c(b"x")


class TestCoalescing:
    def test_objects_share_launches_across_callers(self):
        """>= 4 concurrent same-profile objects: their encode-compare
        items coalesce into ONE batched launch; crc lanes of every
        shard coalesce into a couple of launches, not one per shard."""
        ec = _ec()
        objs = [_encoded_object(ec, 10 + i, 32768) for i in range(6)]
        ver = ScrubVerifier(window_s=0.005)

        async def go():
            return await asyncio.gather(*(
                ver.verify_object(ec, o) for o in objs))

        checks = asyncio.run(go())
        for o, ch in zip(objs, checks):
            for s, p in o.items():
                assert ch.crcs[s] == crc32c(p)
            assert ch.parity_bad == frozenset()
        assert ver.stats["objects"] == 6
        assert ver.stats["enc_launches"] == 1, dict(ver.stats)
        # 6 objects x 5 shards = 30 crc lanes in one 32-lane launch
        assert ver.stats["crc_launches"] == 1, dict(ver.stats)
        eff = ver.metrics.efficiency()
        assert 0 < eff["lane_occupancy"] <= 1
        assert 0 < eff["byte_occupancy"] <= 1
        assert any(k.startswith("launches_") for k in ver.metrics.dump())

    def test_cross_profile_groups_split(self):
        """Objects of different EC profiles share crc launches (crc is
        profile-agnostic) but never an encode-compare launch."""
        ec_a, ec_b = _ec(3, 2), _ec(4, 2)
        # sizes chosen so both profiles' chunks land in the same pow2
        # bucket (8 KiB): the crc layer sees ONE group
        objs_a = [_encoded_object(ec_a, 20 + i, 16384) for i in range(2)]
        objs_b = [_encoded_object(ec_b, 30 + i, 28000) for i in range(2)]
        ver = ScrubVerifier(window_s=0.005)

        async def go():
            return await asyncio.gather(
                *(ver.verify_object(ec_a, o) for o in objs_a),
                *(ver.verify_object(ec_b, o) for o in objs_b),
            )

        checks = asyncio.run(go())
        assert all(c.parity_bad == frozenset() for c in checks)
        assert ver.stats["enc_launches"] == 2, dict(ver.stats)
        assert ver.stats["crc_launches"] == 1, dict(ver.stats)


class TestNoCompileAfterWarmup:
    def test_prewarm_then_zero_cold_launches(self):
        """After prewarm covers the ladder, deep-scrub verification
        dispatches only warm shapes — the compile counter stays 0,
        including for >tile-cap lane splits and the b=1 stragglers."""
        ec = _ec()
        ver = ScrubVerifier(window_s=0.002)
        n = ver.prewarm(ec)
        assert n > 0
        assert ver.stats["cold_launches"] == 0

        objs = [_encoded_object(ec, 40 + i, sz)
                for i, sz in enumerate([5000, 40000, 40000, 300000])]

        async def go():
            return await asyncio.gather(*(
                ver.verify_object(ec, o) for o in objs))

        checks = asyncio.run(go())
        for o, ch in zip(objs, checks):
            for s, p in o.items():
                assert ch.crcs[s] == crc32c(p)
        assert ver.stats["launches"] >= 2
        assert ver.stats["cold_launches"] == 0, dict(ver.stats)

    def test_cold_launch_counted_without_warmup(self):
        ver = ScrubVerifier(window_s=0.001)

        async def go():
            return await ver.verify_object(
                None, {0: np.zeros(100, np.uint8)})

        asyncio.run(go())
        assert ver.stats["cold_launches"] == 1, dict(ver.stats)


class TestHostFallbackIdentity:
    def test_dispatch_failure_answers_from_host(self, monkeypatch):
        """A broken device path must not change results: the host
        fallback folds identically (same padded-crc algebra)."""
        ver = ScrubVerifier(window_s=0.002)
        monkeypatch.setattr(
            ScrubVerifier, "_run_crc_group",
            lambda self, w, g: (_ for _ in ()).throw(RuntimeError("boom")))
        monkeypatch.setattr(
            ScrubVerifier, "_run_enc_group",
            lambda self, w, g: (_ for _ in ()).throw(RuntimeError("boom")))
        ec = _ec()
        shards = _encoded_object(ec, 50, 150000)
        shards[3] = shards[3].copy()
        shards[3][0] ^= 1

        async def go():
            return await ver.verify_object(ec, shards)

        check = asyncio.run(go())
        for s, p in shards.items():
            assert check.crcs[s] == crc32c(p)
        assert check.parity_bad == frozenset(_host_parity_bad(ec, shards))
        assert ver.stats["dispatch_fallbacks"] >= 2, dict(ver.stats)
