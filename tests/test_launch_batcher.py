"""The one launch-batching skeleton (ceph_tpu/parallel/batcher.py) under
its three engines: every case runs against the encode service (one
device, and the column-split mesh on 4 virtual devices), the decode
aggregator and the scrub verifier through one small adapter each, so a
part of the skeleton that one engine stops using fails here by name.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from ceph_tpu.common import tracing
from ceph_tpu.ec import registry
from ceph_tpu.native import crc32c
from ceph_tpu.ops.gf256 import gf_matmul
from ceph_tpu.osd import ecutil
from ceph_tpu.parallel import batcher, decode_batcher, encode_service
from ceph_tpu.parallel import scrub_batcher

K, WIDTH = 4, 4096


def _ec(m: int):
    return registry.factory("jax", {"k": str(K), "m": str(m)})


def _rows(seed: int, width: int = WIDTH) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (K, width), dtype=np.uint8)


class _MatMulEngine:
    """Encode and decode: a request is ``M @ rows``; code ``m`` gives
    the matrix (its coding matrix: another m, another matrix)."""

    wait_name: str
    tags = {"w", "b_real", "cold", "parents"}

    async def ask(self, eng, m: int, seed: int):
        M = np.asarray(_ec(m).coding_matrix, np.uint8)
        rows = _rows(seed)
        out = await eng.apply(M, rows)
        assert np.array_equal(out, gf_matmul(M, rows))

    def fallbacks(self, eng) -> int:
        return eng.stats["fallbacks"]


class _Encode(_MatMulEngine):
    wait_name = "encode_batch_wait"
    kind = "encode_single"

    def make(self):
        return encode_service.EncodeService(
            device=jax.devices()[0], min_bytes=0, window_s=0.02)

    def launches(self, eng) -> int:
        return eng.stats["single_dispatches"] + eng.stats["dp_dispatches"]

    def prewarm(self, eng) -> int:
        return eng.prewarm(
            np.asarray(_ec(2).coding_matrix, np.uint8), [WIDTH], coalesce=4)


class _EncodeMesh(_Encode):
    kind = "encode_dp"
    tags = _MatMulEngine.tags | {"devices", "pad_bytes"}

    def make(self):
        devs = jax.devices()
        if len(devs) < 4:
            pytest.skip("needs 4 virtual devices")
        return encode_service.EncodeService(
            Mesh(np.asarray(devs[:4]), ("cols",)), min_bytes=0,
            window_s=0.02)


class _Decode(_MatMulEngine):
    wait_name = "decode_batch_wait"
    kind = "decode_batch"
    tags = _MatMulEngine.tags | {"b", "occupancy"}

    def make(self):
        return decode_batcher.DecodeAggregator(window_s=0.02)

    def launches(self, eng) -> int:
        return eng.stats["launches"]

    def prewarm(self, eng) -> int:
        return eng.prewarm(_ec(2), erasure_counts=(2,))


class _Scrub:
    """A request is one object's check; the profile's coding matrix is
    what groups the re-encode launches (crc lanes know no matrix)."""

    wait_name = None
    kind = "scrub_enc"
    tags = {"w", "b", "b_real", "occupancy", "cold"}

    def make(self):
        return scrub_batcher.ScrubVerifier(window_s=0.02)

    async def ask(self, eng, m: int, seed: int):
        ec = _ec(m)
        sinfo = ecutil.StripeInfo(K, WIDTH * K)
        shards = ecutil.encode(sinfo, ec, _rows(seed).reshape(-1))
        check = await eng.verify_object(ec, shards)
        assert check.parity_bad == frozenset()
        assert all(check.crcs[s] == crc32c(p) for s, p in shards.items())

    def launches(self, eng) -> int:
        return eng.stats["enc_launches"]

    def fallbacks(self, eng) -> int:
        return eng.stats["dispatch_fallbacks"]

    def prewarm(self, eng) -> int:
        return eng.prewarm(_ec(2))


ENGINES = {"encode": _Encode(), "encode_mesh": _EncodeMesh(),
           "decode": _Decode(), "scrub": _Scrub()}


@pytest.fixture(params=list(ENGINES))
def engine(request):
    return ENGINES[request.param]


def test_one_window_one_launch_and_another_matrix_another(engine):
    eng = engine.make()

    async def go():
        await asyncio.gather(*(engine.ask(eng, 2, i) for i in range(4)))
        assert engine.launches(eng) == 1, dict(eng.stats)
        await asyncio.gather(engine.ask(eng, 2, 7), engine.ask(eng, 2, 8),
                             engine.ask(eng, 1, 9))
        assert engine.launches(eng) == 3, dict(eng.stats)

    asyncio.run(go())
    assert engine.fallbacks(eng) == 0


def test_a_plan_that_raises_is_answered_from_the_host(engine, monkeypatch):
    eng = engine.make()
    groups = []

    def boom(key, group):
        groups.append(len(group))
        raise RuntimeError("boom")

    monkeypatch.setattr(eng, "_run_group", boom)

    async def go():
        # every ask checks its own answer against the host product
        await asyncio.gather(*(engine.ask(eng, 2, i) for i in range(3)))

    asyncio.run(go())
    assert engine.fallbacks(eng) == len(groups) >= 1
    assert engine.launches(eng) == 0


def test_two_threads_prewarm_one_ladder_compile_each_shape_once(
        engine, monkeypatch):
    eng = engine.make()
    compiles = []
    real = jax.block_until_ready

    def slow(x):
        compiles.append(threading.get_ident())
        time.sleep(0.01)      # hold the claim while the other arrives
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", slow)
    seen: list[tuple[int, int]] = []

    def warm():
        n = engine.prewarm(eng)
        seen.append((n, len(eng._warm)))      # warm set at MY return

    threads = [threading.Thread(target=warm) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    ladder = len(eng._warm)
    assert ladder > 1 and len(compiles) == ladder
    assert sum(n for n, _ in seen) == ladder
    # both returned only once the whole ladder was warm
    assert [warm_then for _, warm_then in seen] == [ladder, ladder]
    assert eng.stats["prewarmed_shapes"] == ladder
    assert not eng._warm_claimed


def test_a_warmed_shape_launches_warm_and_an_unwarmed_one_counts(engine):
    warmed, cold = engine.make(), engine.make()
    assert engine.prewarm(warmed) > 0
    assert engine.prewarm(warmed) == 0

    async def go(eng):
        await asyncio.gather(*(engine.ask(eng, 2, i) for i in range(2)))

    asyncio.run(go(warmed))
    assert engine.launches(warmed) >= 1
    assert warmed.stats["cold_launches"] == 0, dict(warmed.stats)
    asyncio.run(go(cold))
    assert cold.stats["cold_launches"] >= 1


def test_launch_span_tags_and_the_waiters_queue_children(engine):
    eng = engine.make()
    dev, mine = tracing.device_tracer(), tracing.get_tracer("batcher-test")
    old = dev.sample_rate
    dev.sample_rate = mine.sample_rate = 1.0

    async def one(seed):
        with mine.span("caller_op") as sp, tracing.scope(sp):
            await engine.ask(eng, 2, seed)
        return sp

    async def go():
        return await asyncio.gather(one(1), one(2))

    try:
        callers = asyncio.run(go())
    finally:
        dev.sample_rate = old
    launch = [s for s in dev.find(kind=engine.kind)
              if s.name == "xla_launch"][-1]
    assert engine.tags <= set(launch.tags), launch.tags
    assert launch.tags["stage"] == "device" and launch.tags["b_real"] == 2
    assert launch.tags["cold"] is True
    ids = {sp.span_id for sp in callers}
    waits = [s for s in mine.dump(limit=64)
             if s["name"] == engine.wait_name and s["parent_id"] in ids]
    if engine.wait_name is None:
        assert "parents" not in launch.tags and not waits
        return
    assert set(launch.tags["parents"]) == ids
    assert {s["parent_id"] for s in waits} == ids
    assert all(s["tags"]["stage"] == "queue" for s in waits)


def test_requests_ride_under_field_names(engine):
    eng = engine.make()
    seen = []
    run = eng._run_group

    def spy(key, group):
        seen.extend(group)
        return run(key, group)

    eng._run_group = spy
    t0 = time.monotonic()
    asyncio.run(engine.ask(eng, 2, 0))
    assert seen
    for req in seen:
        assert isinstance(req, batcher.Request)
        assert req.fut.done() and req.span is None
        assert t0 <= req.arrived <= time.monotonic()
        assert req.item._fields            # the engine's item, by name


# -- the device-matrix LRU ---------------------------------------------------

def test_device_matrix_lru_is_bounded_and_evicts_oldest_first():
    lru = batcher.DeviceMatrixCache(size=3)
    built = []

    def get(i):
        return lru.get(("m", i), lambda: built.append(i) or np.full(
            (2, 2), i, np.uint8))

    for i in range(3):
        get(i)
    get(0)                       # 0 is now the newest, 1 the oldest
    get(3)                       # evicts 1
    assert len(lru._lru) == 3 and built == [0, 1, 2, 3]
    get(0), get(2), get(3)
    assert built == [0, 1, 2, 3]           # all three were hits
    get(1)
    assert built == [0, 1, 2, 3, 1] and len(lru._lru) == 3
    assert np.asarray(get(3))[0, 0] == 3


def test_device_matrix_lru_is_the_one_every_path_fills():
    """An engine's bit-matrix, the scrub crc matrix and the plugin's
    per-op sync path all land in ``batcher.device_matrices``; a second
    engine and the plugin find what the first one put there."""
    lru = batcher.device_matrices
    ec = _ec(3)
    C = np.asarray(ec.coding_matrix, np.uint8)
    key = (batcher.matrix_key(C), None)
    lru._lru.pop(key, None)
    dec, ver = ENGINES["decode"].make(), ENGINES["scrub"].make()
    bits = dec._bits(C)
    assert lru._lru[key] is bits and ver._bits(C) is bits
    assert ver._crc_mat(4096) is lru._lru[(("crc", 4096), None)]
    n = len(lru._lru)
    ec.device_min_bytes = 0
    rows = _rows(5)
    assert np.array_equal(ec._apply_matrix(C, rows), gf_matmul(C, rows))
    assert len(lru._lru) == n and not hasattr(ec, "_device_bits")
    # a mesh service keeps its replicated copy under its own placement
    mesh = ENGINES["encode_mesh"].make()
    assert mesh._bits(C) is lru._lru[(key[0], mesh._placement)]
    assert mesh._bits(C) is not bits
    assert lru.size == batcher._BITS_CACHE_SIZE >= len(lru._lru)


# -- the process-wide engines ------------------------------------------------

@pytest.mark.parametrize("mod", [encode_service, decode_batcher,
                                 scrub_batcher])
def test_shared_engines_live_in_one_registry(mod):
    others = [m for m in (encode_service, decode_batcher, scrub_batcher)
              if m is not mod]
    kept = [m.shared() for m in others]
    first = mod.shared()
    assert mod.shared() is first and first in batcher._shared.values()
    mod.reset_shared()
    assert first not in batcher._shared.values()
    assert [m.shared() for m in others] == kept
    assert mod.shared() is not first
    mod.reset_shared()


def test_a_build_that_raises_leaves_nothing_and_raises_again():
    def build():
        raise RuntimeError("no backend")

    for _ in range(2):
        with pytest.raises(RuntimeError):
            batcher.shared("broken", build)
    assert "broken" not in batcher._shared


# -- PR 37: the launch decision is the group's --------------------------------

def _service(window_s: float = 0.02):
    return encode_service.EncodeService(
        device=jax.devices()[0], window_s=window_s)


@pytest.mark.parametrize("widths,launches,host", [
    ([4096], 0, (1, 1)),                # a lone 16 KiB stripe: the host
    ([4096, 4096, 4096], 1, (0, 0)),    # three in one window: ONE launch
    ([1 << 20], 1, (0, 0)),             # a lone 4 MiB object: a launch
    ([1024, 2048, 2048], 0, (1, 3)),    # 20 KiB in all: one host group
], ids=["lone_16k", "three_16k", "lone_4m", "group_under_32k"])
def test_a_flushed_group_launches_by_what_it_carries(widths, launches, host):
    """``min_bytes`` (32 KiB, no option) is asked of the flushed group's
    summed bytes, never of one request, and a group under it is answered
    on the host at the flush: counted, never as a fallback."""
    svc = _service()
    assert svc.min_bytes == encode_service.DEFAULT_MIN_BYTES == 32768
    M = np.asarray(_ec(2).coding_matrix, np.uint8)
    reqs = [_rows(70 + i, w) for i, w in enumerate(widths)]

    async def go():
        threads = []
        real = svc._host_group

        def host_group(key, group):
            threads.append(threading.get_ident())
            return real(key, group)

        svc._host_group = host_group
        outs = await asyncio.gather(*(svc.apply(M, r) for r in reqs))
        assert threads == [threading.get_ident()] * host[0]   # inline
        return outs

    for r, out in zip(reqs, asyncio.run(go())):
        assert np.array_equal(out, gf_matmul(M, r))
    assert svc.stats["single_dispatches"] == launches
    assert svc.stats["coalesced"] == (len(reqs) if launches else 0)
    assert (svc.stats["host_groups"], svc.stats["host_requests"]) == host
    assert svc.stats["fallbacks"] == 0


def test_two_matrices_in_one_window_are_two_decisions():
    """Groups are per matrix: 48 KiB under one launches while 16 KiB
    under another, flushed in the same window, goes to the host."""
    svc = _service()
    A = np.asarray(_ec(2).coding_matrix, np.uint8)
    B = np.asarray(_ec(3).coding_matrix, np.uint8)

    async def go():
        return await asyncio.gather(
            *(svc.apply(A, _rows(80 + i)) for i in range(3)),
            svc.apply(B, _rows(90)))

    outs = asyncio.run(go())
    assert np.array_equal(outs[3], gf_matmul(B, _rows(90)))
    assert np.array_equal(outs[0], gf_matmul(A, _rows(80)))
    assert (svc.stats["single_dispatches"], svc.stats["coalesced"],
            svc.stats["host_groups"], svc.stats["host_requests"],
            svc.stats["fallbacks"]) == (1, 3, 1, 1, 0)


def test_an_encode_launchs_span_says_what_it_carried():
    svc = _service()
    M = np.asarray(_ec(2).coding_matrix, np.uint8)
    tracer = tracing.device_tracer()
    tracer.set_ring_max(1 << 12)

    async def go():
        with tracing.get_tracer("t37").span("op") as sp, tracing.scope(sp):
            await asyncio.gather(*(svc.apply(M, _rows(i)) for i in range(3)))
        return sp.trace_id, sp.span_id

    _trace, parent = asyncio.run(go())
    launch = [s for s in tracer.dump(limit=1 << 12)
              if s["name"] == "xla_launch"
              and parent in s["tags"].get("parents", ())]
    assert len(launch) == 1
    assert launch[0]["tags"]["real_bytes"] == 3 * K * WIDTH
    assert launch[0]["tags"]["kind"] == "encode_single"


def test_ecutil_files_every_request_with_an_active_service():
    """``_service_takes`` asks no size: a 16 KiB read-modify-write
    stripe reaches the service (and is answered there on the host)."""
    ec = registry.factory("jerasure", {
        "technique": "reed_sol_van", "k": "4", "m": "2"})
    sinfo = ecutil.StripeInfo(4, ec.get_chunk_size(4096 * 4) * 4)
    svc = _service(0.002)
    data = np.random.default_rng(3).integers(0, 256, 16384, dtype=np.uint8)

    async def go():
        return await ecutil.encode_async(sinfo, ec, data, service=svc)

    got = asyncio.run(go())
    want = ecutil.encode(sinfo, ec, data)
    assert all(np.array_equal(got[s], want[s]) for s in want)
    assert svc.stats["host_requests"] == 1 and svc.stats["fallbacks"] == 0
    assert "nbytes" not in ecutil._service_takes.__code__.co_varnames


def test_the_daemons_ladder_covers_what_32_overwrites_can_gather():
    """``_warm_ec_profiles`` compiles widths cs/4, cs, 4 cs at 1..16
    requests: every power-of-two bucket that 2..32 stripes of a (4, 2)
    pool's 4 KiB chunks land in is among them, so no read-modify-write
    launch is cold."""
    svc = _service()
    cs = 4096
    warmed = {svc._bucket(w << f) for w in (cs >> 2, cs, cs << 2)
              for f in range((16).bit_length())}
    assert {svc._bucket(cs * n) for n in range(2, 33)} <= warmed
