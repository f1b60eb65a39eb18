#!/usr/bin/env python
"""All five BASELINE.md bench configs, one JSON line each.

Clone of the reference harness surfaces:
- ceph_erasure_code_benchmark (src/test/erasure-code/
  ceph_erasure_code_benchmark.cc:155-324): encode + decode workloads,
  GB/s as in qa/workunits/erasure-code/bench.sh:170;
- osdmaptool --test-map-pgs (src/tools/osdmaptool.cc:42-44) /
  ParallelPGMapper (src/osd/OSDMapMapping.h) for the whole-map remap;
- the thrash suites' recovery measurement (qa/tasks/ceph_manager.py)
  for end-to-end 1-OSD-down recovery.

Each config runs in its own subprocess so device selection is exact:
device configs inherit the environment's JAX platform; CPU baselines
force JAX_PLATFORMS=cpu.

  python tools/bench_all.py            # run everything
  python tools/bench_all.py <config>   # one of: jerasure_cpu,
                                       #   decode_tpu, clay_repair,
                                       #   remap, recovery
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _emit(metric: str, value: float, unit: str, vs_baseline: float) -> None:
    print(json.dumps({
        "metric": metric, "value": round(value, 2), "unit": unit,
        "vs_baseline": round(vs_baseline, 3),
    }), flush=True)


# -- config 1: jerasure RS(4,2), 4 MiB stripes, host CPU reference ----------

def bench_jerasure_cpu() -> None:
    import numpy as np

    from ceph_tpu.ec import registry

    ec = registry.factory("jerasure", {
        "k": "4", "m": "2", "technique": "reed_sol_van",
    })
    size = 4 * 2**20
    cs = ec.get_chunk_size(size)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, 4 * cs, dtype=np.uint8)
    n, best = 8, float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            ec.encode(set(range(6)), data)
        best = min(best, (time.perf_counter() - t0) / n)
    _emit(
        "jerasure RS(4,2) 4MiB stripe encode, host CPU reference",
        data.nbytes / best / 1e6, "MB/s", 1.0,
    )


# -- config 2b: RS(8,3) 1-erasure decode on TPU -----------------------------

def bench_decode_tpu() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.models import isa_cauchy_matrix
    from ceph_tpu.ops import rs_kernels as rk

    k, m = 8, 3
    codec = rk.BitmatrixCodec(isa_cauchy_matrix(k, m))
    on_tpu = jax.default_backend() == "tpu"
    S = (256 * 2**20) if on_tpu else 2**16  # 2 GiB of survivor input

    gen = jax.jit(lambda key: jax.random.bits(key, (k, S), jnp.uint8))
    data = gen(jax.random.key(1))
    jax.block_until_ready(data)
    # survivors: 7 data chunks + parity 0 reconstruct data chunk 3
    survivors, dbits = codec.decode_bits((3,))
    parity = jax.jit(
        lambda d: codec.encode(d, pallas=on_tpu)
    )(data)
    jax.block_until_ready(parity)
    sub = jnp.concatenate(
        [data[:3], data[4:], parity[0:1]], axis=0
    )  # the 8 survivor payloads in codec order for erasure {3}
    jax.block_until_ready(sub)
    ref = np.asarray(data[3, :4096])  # host copy, then free HBM
    del data, parity

    decode = jax.jit(
        lambda c: rk.BitmatrixCodec._apply(dbits, c, on_tpu or None)
    )
    out = decode(sub)
    jax.block_until_ready(out)
    assert np.array_equal(np.asarray(out[0, :4096]), ref), "decode mismatch"
    del out

    if not on_tpu:
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = decode(sub)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        gbs = (k * S) / best / 1e9
    else:
        # one-launch timed loop: fold the loop into one launch with
        # an aliased carry, exactly like bench.py's encode
        from jax import lax

        ITERS, TILE = 32, 262144

        @jax.jit
        def loop_decode(c, n):
            acc = jnp.zeros((dbits.shape[0] // 8, c.shape[1]), jnp.uint8)

            def body(i, acc):
                return rk.gf_bitmatmul_pallas_acc(
                    dbits, c, acc, jnp.array([i], jnp.int32), tile_s=TILE)

            return lax.fori_loop(0, n, body, acc)

        out = loop_decode(sub, jnp.int32(ITERS))
        jax.block_until_ready(out)
        best = float("inf")
        for r in range(6):
            t0 = time.perf_counter()
            out = loop_decode(sub, jnp.int32(ITERS))
            jax.block_until_ready(out)
            _ = np.asarray(out[0, :8])
            best = min(best, time.perf_counter() - t0)
            if r < 5:
                time.sleep(3.0)
        gbs = (k * S * ITERS) / best / 1e9
    _emit(
        "RS(8,3) 1-erasure decode throughput, 1 chip",
        gbs, "GB/s (survivor bytes)", gbs / 40.0,
    )


# -- config 3: CLAY (8,4,11) repair, TPU vs CPU -----------------------------

def _clay_repair_once(device: bool, chunk_mib: int) -> float:
    """Returns seconds per single-chunk repair."""
    import numpy as np

    if not device:
        os.environ["CEPH_TPU_EC_DEVICE_MIN_BYTES"] = str(1 << 62)
    from ceph_tpu.ec import registry

    ec = registry.factory("clay", {
        "k": "8", "m": "4", "d": "11", "scalar_mds": "jax",
    })
    cs = ec.get_chunk_size(8 * chunk_mib * 2**20)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, 8 * cs, dtype=np.uint8)
    enc = ec.encode(set(range(12)), data)
    lost = 3
    minimum = ec.minimum_to_decode({lost}, set(range(12)) - {lost})
    sub = cs // ec.get_sub_chunk_count()
    helpers = {
        c: np.concatenate([enc[c][o*sub:(o+n)*sub] for o, n in runs])
        for c, runs in minimum.items()
    }
    # warm (compiles on device; populates decode-matrix caches)
    out = ec.decode({lost}, helpers, cs)
    assert np.array_equal(out[lost], enc[lost]), "repair mismatch"
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        ec.decode({lost}, helpers, cs)
        best = min(best, time.perf_counter() - t0)
    return best, cs


def bench_clay_repair() -> None:
    # CPU baseline runs in a subprocess with the device stripped
    cpu = json.loads(subprocess.run(
        [sys.executable, __file__, "_clay_cpu"],
        capture_output=True, text=True, env=_cpu_env(), check=True,
    ).stdout.strip().splitlines()[-1])

    # device: the single-dispatch jitted repair over staged helpers
    # (clay_jit) — the TPU-native formulation of repair_one_lost_chunk
    import jax
    import numpy as np

    from ceph_tpu.ec import registry
    from ceph_tpu.ec.plugins.clay_jit import ClayRepairProgram

    ec = registry.factory("clay", {
        "k": "8", "m": "4", "d": "11", "scalar_mds": "jax",
    })
    cs = ec.get_chunk_size(8 * 32 * 2**20)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, 8 * cs, dtype=np.uint8)
    enc = ec.encode(set(range(12)), data)
    lost = 3
    minimum = ec.minimum_to_decode({lost}, set(range(12)) - {lost})
    sub = cs // ec.get_sub_chunk_count()
    helpers = {
        c: np.concatenate([enc[c][o*sub:(o+n)*sub] for o, n in runs])
        for c, runs in minimum.items()
    }
    prog = ClayRepairProgram(ec, lost)
    out = prog.repair(helpers)   # warm + compile + correctness
    assert np.array_equal(out, enc[lost]), "jit repair mismatch"
    H = prog.stage(helpers)
    jax.block_until_ready(H)
    best = float("inf")
    for r in range(6):
        t0 = time.perf_counter()
        dev = prog.repair_device(H)
        jax.block_until_ready(dev)
        _ = np.asarray(dev[0, :8])
        best = min(best, time.perf_counter() - t0)
        if r < 5:
            time.sleep(2.0)
    speedup = cpu["seconds"] / best
    _emit(
        f"CLAY(8,4,11) single-chunk repair, {cs>>20} MiB chunk: "
        "single-dispatch TPU program vs CPU",
        speedup, "x speedup", speedup / 10.0,
    )


def bench_clay_cpu_probe() -> None:
    t, cs = _clay_repair_once(device=False, chunk_mib=32)
    print(json.dumps({"seconds": t, "chunk": cs}), flush=True)


# -- config 3b: batched recovery decode vs per-object CPU plugin decode -----

def bench_decode_batch() -> None:
    """The ISSUE-1 acceptance microbench: the recovery-decode
    aggregator's bucketed batched decode vs the per-object CPU plugin
    decode on the SAME stripes.  With an accelerator the ratio must
    clear 10x; on CPU-only hosts the gate is structural — the
    aggregator must coalesce >= 4 objects per launch and match the
    per-object decode bit-exactly (both asserted here)."""
    import asyncio

    import jax
    import numpy as np

    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil
    from ceph_tpu.parallel.decode_batcher import DecodeAggregator

    k, m = 8, 3
    on_tpu = jax.default_backend() == "tpu"
    n_obj = 16
    obj_bytes = (8 * 2**20) if on_tpu else 512 * 1024
    ec = registry.factory("jax", {"k": str(k), "m": str(m)})
    sinfo = ecutil.StripeInfo(k, ec.get_chunk_size(obj_bytes) * k)
    rng = np.random.default_rng(7)
    objs = []
    for _ in range(n_obj):
        data = rng.integers(
            0, 256, sinfo.logical_to_next_stripe_offset(obj_bytes),
            dtype=np.uint8)
        shards = ecutil.encode(sinfo, ec, data)
        objs.append({s: c for s, c in shards.items() if s != 2})

    # per-object host plugin decode (the CPU reference on this machine)
    ec_host = registry.factory("jax", {"k": str(k), "m": str(m)})
    ec_host.device_min_bytes = 1 << 62  # pin the numpy GF path
    best_host = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        host_out = [
            ecutil.decode_shards(sinfo, ec_host, avail, {2})
            for avail in objs
        ]
        best_host = min(best_host, time.perf_counter() - t0)

    # aggregator: concurrent per-object decodes coalesce into batched
    # fixed-shape launches; prewarmed, so zero in-path compiles
    agg = DecodeAggregator(window_s=0.002)
    cs = len(next(iter(objs[0].values())))
    agg.prewarm(ec, [cs], erasure_counts=(1,))

    async def batched_once():
        return await asyncio.gather(*(
            ecutil.decode_shards_async(
                sinfo, ec, avail, {2}, aggregator=agg)
            for avail in objs
        ))

    outs = asyncio.run(batched_once())  # warm + correctness
    for got, avail, ref in zip(outs, objs, host_out):
        assert np.array_equal(got[2], ref[2]), "batched decode mismatch"
    best_batch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        outs = asyncio.run(batched_once())
        best_batch = min(best_batch, time.perf_counter() - t0)
    launches = agg.stats["launches"]
    mean_batch = agg.stats["batched_requests"] / max(launches, 1)
    assert mean_batch >= 4, (
        f"aggregator batched only {mean_batch:.1f} obj/launch")
    assert agg.stats["cold_launches"] == 0, dict(agg.stats)
    ratio = best_host / best_batch
    survivor_bytes = sum(
        sum(c.nbytes for c in o.values()) for o in objs)
    _emit(
        f"batched recovery decode, {n_obj} x {obj_bytes >> 10} KiB "
        f"objects EC({k},{m}) 1-erasure on "
        f"{jax.default_backend()}: aggregator "
        f"({mean_batch:.1f} obj/launch, 0 in-path compiles, "
        f"{survivor_bytes / best_batch / 1e6:.0f} MB/s survivor bytes) "
        "vs per-object CPU plugin decode",
        ratio, "x speedup", ratio / 10.0,
    )


# -- config 3c: batched deep-scrub verification vs per-object host ----------

def bench_scrub_verify() -> None:
    """The ISSUE-2 acceptance microbench: the scrub verifier's batched
    device verification (crc32c over every shard + parity re-encode
    compare) vs the per-object host path on IDENTICAL chunks.  With an
    accelerator the throughput ratio is the claim; on CPU-only hosts
    the gate is structural — the verifier must coalesce >= 4 objects
    per re-encode launch, report the same rot/mismatch sets
    bit-exactly, and perform zero in-path compiles (all asserted)."""
    import asyncio

    import jax
    import numpy as np

    from ceph_tpu.ec import registry
    from ceph_tpu.native import crc32c
    from ceph_tpu.osd import ecutil
    from ceph_tpu.parallel.scrub_batcher import ScrubVerifier

    k, m = 8, 3
    on_tpu = jax.default_backend() == "tpu"
    n_obj = 16
    obj_bytes = (8 * 2**20) if on_tpu else 512 * 1024
    ec = registry.factory("jax", {"k": str(k), "m": str(m)})
    sinfo = ecutil.StripeInfo(k, ec.get_chunk_size(obj_bytes) * k)
    rng = np.random.default_rng(12)
    objs = []
    for _ in range(n_obj):
        data = rng.integers(
            0, 256, sinfo.logical_to_next_stripe_offset(obj_bytes),
            dtype=np.uint8)
        objs.append(ecutil.encode(sinfo, ec, data))
    # silent rot to detect: one data shard and one parity shard
    objs[3][1] = objs[3][1].copy()
    objs[3][1][100] ^= 0x5A
    objs[7][k + 1] = objs[7][k + 1].copy()
    objs[7][k + 1][9] ^= 0xA5

    # per-object host path (the scrubber's pre-batching verification):
    # native crc32c per shard + re-encode and compare for parity
    def host_verify(shards):
        crcs = {s: crc32c(p) for s, p in shards.items()}
        logical = ecutil.decode_concat(
            sinfo, ec, {s: shards[s] for s in range(k)})
        expect = ecutil.encode(sinfo, ec, logical)
        bad = frozenset(
            s for s, p in shards.items()
            if s in expect and expect[s].tobytes() != p.tobytes())
        return crcs, bad

    best_host = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        host_out = [host_verify(o) for o in objs]
        best_host = min(best_host, time.perf_counter() - t0)

    ver = ScrubVerifier(window_s=0.002)
    cs = len(objs[0][0])
    ver.prewarm(ec, [cs])

    async def batched_once():
        return await asyncio.gather(*(
            ver.verify_object(ec, o) for o in objs))

    checks = asyncio.run(batched_once())  # warm + correctness
    for (h_crcs, h_bad), ch in zip(host_out, checks):
        assert ch is not None and ch.crcs == h_crcs, "crc mismatch"
        assert ch.parity_bad == h_bad, (ch.parity_bad, h_bad)
    best_batch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        asyncio.run(batched_once())
        best_batch = min(best_batch, time.perf_counter() - t0)
    enc_launch = ver.stats["enc_launches"]
    mean_batch = (4 * n_obj) / max(enc_launch, 1)  # 4 batched rounds ran
    assert mean_batch >= 4, (
        f"verifier batched only {mean_batch:.1f} obj/launch")
    assert ver.stats["cold_launches"] == 0, dict(ver.stats)
    shard_bytes = sum(sum(p.nbytes for p in o.values()) for o in objs)
    ratio = best_host / best_batch
    _emit(
        f"batched deep-scrub verify, {n_obj} x {obj_bytes >> 10} KiB "
        f"objects EC({k},{m}) crc32c+parity-re-encode on "
        f"{jax.default_backend()}: verifier "
        f"({mean_batch:.1f} obj/launch, 0 in-path compiles, "
        f"{shard_bytes / best_batch / 1e6:.0f} MB/s shard bytes) "
        "vs per-object host crc+re-encode "
        f"({shard_bytes / best_host / 1e6:.0f} MB/s)",
        ratio, "x speedup", ratio / 10.0,
    )


# -- config 4: 10k PGs x 1024 OSDs whole-map remap --------------------------

def bench_remap() -> None:
    from ceph_tpu.osd.remap import BatchedClusterMapper
    from ceph_tpu.osd.types import pg_t
    from chip_smoke import build_remap_map   # config 4's one definition

    om = build_remap_map(n_hosts=128, osds_per_host=8, rep_pgs=8192,
                         ec_pgs=2048, ec_size=11, ec_min_size=8)
    n_pgs = 8192 + 2048
    mapper = BatchedClusterMapper(om)
    t0 = time.perf_counter()
    res = mapper.map_cluster()
    t_warm = time.perf_counter() - t0  # includes compile
    assert sum(len(pm.up_cnt) for pm in res.values()) == n_pgs

    # parity gate before any speed claim (BASELINE.md protocol):
    # batched rows == scalar pipeline on a sample of both pools,
    # including the MSR pool
    for pid in (1, 2):
        pm = res[pid]
        for ps in range(0, om.pools[pid].pg_num, 257):
            ref = om.pg_to_up_acting_osds(pg_t(pid, ps), folded=True)
            assert pm.rows(ps) == ref, (pid, ps, pm.rows(ps), ref)

    # steady state: new epochs with changed osd state / weights reuse
    # the compiled program (_crush_fingerprint cache) — the cadence a
    # mon/balancer actually runs at
    best = float("inf")
    for i in range(3):
        om.epoch += 1
        om.mark_down(17 + i)
        om.osd_weight[40 + i] = 0x8000
        mapper2 = BatchedClusterMapper(om)
        t0 = time.perf_counter()
        res2 = mapper2.map_cluster()
        best = min(best, time.perf_counter() - t0)
    assert sum(len(pm.up_cnt) for pm in res2.values()) == n_pgs

    # scalar python mapper on a PG sample, extrapolated (the full scalar
    # sweep takes minutes; the reference compares against its
    # thread-pooled C++ mapper, so the honest denominator here is the
    # same-machine scalar path), weighted over both pools
    sample = 128
    t0 = time.perf_counter()
    for ps in range(sample):
        om.pg_to_up_acting_osds(pg_t(1, ps))
    t_rep = (time.perf_counter() - t0) / sample
    t0 = time.perf_counter()
    for ps in range(sample):
        om.pg_to_up_acting_osds(pg_t(2, ps))
    t_msr = (time.perf_counter() - t0) / sample
    t_scalar = t_rep * 8192 + t_msr * 2048
    import jax

    _emit(
        "whole-map remap 10240 PGs (8192 rep + 2048 EC-MSR) x 1024 "
        f"OSDs on {jax.default_backend()}: per-epoch batched vs scalar "
        f"(batched {best*1e3:.0f} ms cached-program, first-epoch "
        f"{t_warm:.1f} s incl. compile)",
        t_scalar / best, "x speedup", 1.0,
    )


# -- config 5: e2e 1-OSD-down recovery MB/s (multi-process) -----------------
#
# Round-3 weak #1 closed: OSDs run in separate PROCESSES (8 per worker,
# the victim alone), so the e2e number is not one-core-runs-everything;
# the decode stage is timed INSIDE the running daemons
# (recovery_decode_seconds/bytes perf counters at the
# handle_recovery_read_complete seam) and read back over the admin
# sockets; the device-vs-host decode ratio comes from running the SAME
# scenario twice with the EC profile's device-min-bytes flipping the
# plugin between chip and host GF paths.

def _bench_ec_profile() -> tuple[int, int]:
    """EC(k, m) for config 5, scaled to the cluster: the headline is
    EC(8,3) on 64 OSDs (BASELINE.md), but a small debug cluster
    (BENCH_RECOVERY_OSDS=8) cannot host 11 distinct shards across
    single-OSD failure domains — placement would hole out and the
    cluster could never go clean."""
    n_osds = int(os.environ.get("BENCH_RECOVERY_OSDS", "64"))
    if n_osds >= 12:
        return 8, 3
    return 4, 2


def _osd_group_main(argv: list[str]) -> int:
    """Worker process: host a group of OSDs until SIGTERM."""
    import asyncio
    import signal

    host, port, admin_dir, ids = argv[0], int(argv[1]), argv[2], argv[3]
    osd_ids = [int(s) for s in ids.split(",")]
    from ceph_tpu.common.cpumesh import claim_jax_backend

    print(f"[osd-group {ids}] {claim_jax_backend('the first osd group')}",
          file=sys.stderr, flush=True)

    async def run() -> None:
        from ceph_tpu.common import ConfigProxy
        from ceph_tpu.osd.daemon import OSDDaemon

        # kernel WARMUP, not just plugin preload (the reference's
        # osd_erasure_code_plugins daemon-start preload, taken one
        # step further): run a real encode + 1-erasure decode at the
        # bench's chunk scale so every XLA compile this worker will
        # need happens NOW, sequentially, before any client op exists.
        # Compiling lazily inside the I/O path stalls the event loop
        # for tens of seconds on a contended core — handshakes time
        # out, peers file false failure reports, the mon churns maps,
        # and the cluster never settles.
        import numpy as _np

        from ceph_tpu.ec import registry as _ecreg

        _k, _m = _bench_ec_profile()
        _ec = _ecreg.factory("jax", {"k": str(_k), "m": str(_m)})
        try:
            _probe = _np.zeros(512 * 1024, dtype=_np.uint8)
            _enc = _ec.encode(set(range(_k + _m)), _probe)
            _cs = len(_enc[0])
            _dec_in = {i: _enc[i] for i in range(_k + _m) if i != 2}
            _ec.decode({2}, _dec_in, _cs)
            # fixed-bucket prewarm: compile every batched decode /
            # farm shape the aggregator and encode service can launch
            # for this profile NOW, before any client op exists (the
            # daemon repeats this at map install, but doing it here
            # guarantees the order even for ops racing the first map)
            from ceph_tpu.parallel import decode_batcher as _db
            from ceph_tpu.parallel import encode_service as _es

            _agg = _db.shared()
            _agg.prewarm(_ec, [max(_cs >> 2, 1), _cs, _cs << 2])
            _svc = _es.shared()
            if _svc.active() and hasattr(_ec, "coding_matrix"):
                _svc.prewarm(_ec.coding_matrix, [_cs])
        except Exception:
            pass  # host-only environments still run (numpy path)

        conf = {
            "admin_socket": os.path.join(admin_dir, "osd.$id.asok"),
            # one physical core hosts every process here: peer pings
            # starve and mass-report false failures; the bench drives
            # the failure explicitly (osd down/out), so detection is
            # out of scope — beacons stay on for the pg-stats plane
            "osd_heartbeat_interval": 0.0,
            # residual compile/dispatch stalls still freeze the loop
            # for seconds at a time; a 10s handshake budget would turn
            # those into false failure cascades
            "ms_connection_ready_timeout": 120.0,
            # farm ON (ISSUE 1 tentpole): the farm + decode aggregator
            # now pad into FIXED power-of-two buckets, and every bucket
            # shape is compiled at daemon warmup (map-install prewarm +
            # the plugin warmup above), so no XLA compile can occur
            # inside the I/O path — the failure mode that previously
            # forced this off (variable-width coalescing triggering
            # ~30 s compiles mid-recovery) is structurally gone; the
            # aggregator's cold_launches counter in dump_decode_batch
            # verifies it per run
            "osd_ec_encode_farm": "on",
        }
        osds = []
        for i in osd_ids:
            o = OSDDaemon(i, (host, port), conf=ConfigProxy(dict(conf)))
            await o.start()
            osds.append(o)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        loop.add_signal_handler(signal.SIGTERM, stop.set)

        async def lag_probe():
            import faulthandler
            debug = os.environ.get("BENCH_DEBUG_LAG")
            while True:
                t0 = loop.time()
                if debug:
                    # armed BEFORE the sleep: if the loop stalls >2s the
                    # timer fires DURING the stall and dumps the stack
                    # actually holding the loop
                    faulthandler.dump_traceback_later(2.0, file=sys.stderr)
                await asyncio.sleep(0.1)
                if debug:
                    faulthandler.cancel_dump_traceback_later()
                drift = loop.time() - t0 - 0.1
                if drift > 0.5 and debug:
                    print(f"[osd-group {ids}] loop stalled {drift:.2f}s",
                          file=sys.stderr, flush=True)

        probe = asyncio.ensure_future(lag_probe())
        await stop.wait()
        probe.cancel()
        for o in osds:
            await o.stop()

    asyncio.run(run())
    return 0


async def _sum_decode_counters(admin_dir: str, osd_ids) -> tuple[float, float]:
    from ceph_tpu.common import admin_command

    secs = byts = 0.0
    for i in osd_ids:
        path = os.path.join(admin_dir, f"osd.{i}.asok")
        try:
            perf = await admin_command(path, "perf dump")
        except (OSError, ConnectionError):
            continue
        c = perf.get(f"osd.{i}", perf if isinstance(perf, dict) else {})
        if isinstance(c, dict):
            secs += float(c.get("recovery_decode_seconds", 0.0))
            byts += float(c.get("recovery_decode_bytes", 0.0))
    return secs, byts


async def _sum_batch_stats(admin_dir: str, osd_ids) -> dict:
    """Merge the recovery-decode aggregator stats across worker
    PROCESSES (daemons co-hosted in one process share the aggregator,
    so sockets are deduped by pid)."""
    from ceph_tpu.common import admin_command

    seen_pids: set[int] = set()
    total: dict[str, float] = {}
    for i in osd_ids:
        path = os.path.join(admin_dir, f"osd.{i}.asok")
        try:
            d = await admin_command(path, "dump_decode_batch")
        except (OSError, ConnectionError):
            continue
        if not isinstance(d, dict) or not d.get("active"):
            continue
        pid = d.get("pid")
        if pid in seen_pids:
            continue
        seen_pids.add(pid)
        for k, v in (d.get("stats") or {}).items():
            total[k] = total.get(k, 0.0) + float(v)
    out = dict(total)
    if total.get("launches"):
        out["mean_batch"] = (
            total.get("batched_requests", 0.0) / total["launches"])
    return out


async def _recovery_scenario(profile_extra: dict,
                             decode_batch: str = "on"):
    """One full multi-process 1-OSD-down run.  Returns
    (seconds_to_clean, bytes_written, decode_seconds, decode_bytes,
    decode_batch_stats).  ``decode_batch`` flips the workers'
    osd_recovery_decode_batch (the host-baseline run measures the
    per-object plugin decode, aggregator off)."""
    import asyncio
    import random
    import signal
    import tempfile

    from ceph_tpu.client import RadosClient
    from ceph_tpu.crush import builder as B
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.mon import Monitor

    n_osds = int(os.environ.get("BENCH_RECOVERY_OSDS", "64"))
    # worker processes scale with the machine: on a 1-core box more
    # processes only add scheduling quanta to every message hop; the
    # victim is ALWAYS its own process so the failure is a real
    # process kill
    workers = max(1, min(8, os.cpu_count() or 1))
    group = max(1, -(-(n_osds - 1) // workers))
    from ceph_tpu.common import ConfigProxy as _CP

    crush = CrushMap()
    B.build_hierarchy(crush, osds_per_host=1, n_hosts=n_osds)
    mon = Monitor(crush=crush, conf=_CP(
        {"ms_connection_ready_timeout": 120.0}))
    await mon.start()
    admin_dir = tempfile.mkdtemp(prefix="bench5-asok-")
    victim = n_osds - 1
    procs = []
    groups = [
        list(range(g, min(g + group, n_osds - 1)))
        for g in range(0, n_osds - 1, group)
    ] + [[victim]]
    worker_env = dict(os.environ)
    worker_env["CEPH_TPU_OSD_RECOVERY_DECODE_BATCH"] = decode_batch
    # the first OSD group owns the accelerator, every later worker
    # (the victim included) is CPU-pinned (common/cpumesh.py)
    cpu_env = dict(worker_env, JAX_PLATFORMS="cpu")
    for n, ids in enumerate(groups):
        procs.append(subprocess.Popen(
            [sys.executable, __file__, "_osd_group",
             mon.addr[0], str(mon.addr[1]), admin_dir,
             ",".join(map(str, ids))],
            env=worker_env if n == 0 else cpu_env,
        ))
    victim_proc = procs[-1]
    cl = RadosClient(client_id=55, handshake_timeout=120.0)
    # workers need a beat to boot + connect
    deadline = time.perf_counter() + 120
    while True:
        try:
            await cl.connect(*mon.addr)
            break
        except Exception:
            if time.perf_counter() > deadline:
                raise
            await asyncio.sleep(0.5)
    while time.perf_counter() < deadline:
        if sum(1 for o in range(n_osds)
               if cl.osdmap and cl.osdmap.max_osd > o
               and cl.osdmap.is_up(o)) == n_osds:
            break
        await asyncio.sleep(0.5)
        await cl._wait_new_map(0, timeout=1)
    try:
        return await _recovery_run(
            cl, mon, procs, victim, victim_proc, admin_dir, n_osds,
            profile_extra)
    finally:
        import signal as _sig

        for p in procs:
            if p.poll() is None:
                p.send_signal(_sig.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        try:
            await cl.shutdown()
        except Exception:
            pass
        try:
            await mon.stop()
        except Exception:
            pass


async def _recovery_run(cl, mon, procs, victim, victim_proc, admin_dir,
                        n_osds, profile_extra):
    import asyncio
    import random
    import signal

    k, m = _bench_ec_profile()
    profile = {"plugin": "jax", "k": str(k), "m": str(m)}
    profile.update(profile_extra)
    print("bench5: cluster up, writing", file=sys.stderr, flush=True)
    await cl.ec_profile_set("p", profile)
    await cl.pool_create("bench", pg_num=32, pool_type="erasure",
                         erasure_code_profile="p")
    io = cl.ioctx("bench")
    rng = random.Random(9)
    obj_size = 512 * 1024
    n_objects = int(os.environ.get("BENCH_RECOVERY_OBJECTS", "128"))
    total = 0
    for i in range(n_objects):
        data = rng.randbytes(obj_size)
        await io.write_full(f"o{i}", data)
        total += len(data)
    print("bench5: written, waiting clean", file=sys.stderr, flush=True)
    await cl.wait_clean(timeout=600)
    print("bench5: clean, killing victim", file=sys.stderr, flush=True)

    victim_proc.send_signal(signal.SIGKILL)
    t0 = time.perf_counter()
    await cl.command({"prefix": "osd down", "id": str(victim)})
    await cl.command({"prefix": "osd out", "id": str(victim)})
    # every pg report must post-date the out-epoch: stale pre-kill
    # active+clean reports otherwise satisfy the wait instantly
    import json as _json

    code, _rs, data = await cl.command({"prefix": "status"})
    kill_epoch = _json.loads(data)["epoch"] if code == 0 else 0
    await cl.wait_clean(timeout=900, min_epoch=kill_epoch)
    print("bench5: recovered", file=sys.stderr, flush=True)
    dt = time.perf_counter() - t0
    dsec, dbytes = await _sum_decode_counters(
        admin_dir, range(n_osds - 1))
    batch = await _sum_batch_stats(admin_dir, range(n_osds - 1))
    print(f"bench5: decode-batch stats {batch}", file=sys.stderr,
          flush=True)
    return dt, total, dsec, dbytes, batch


def bench_recovery() -> None:
    import asyncio

    # run A: batched decode (the aggregator coalesces concurrent
    # recovery decodes into fixed-shape launches; with an accelerator
    # present the batched matmul runs on the chip, farm ON)
    dt, total, dsec, dbytes, batch = asyncio.run(
        _recovery_scenario({"device-min-bytes": "4096"}))
    dev_mbs = (dbytes / dsec / 1e6) if dsec > 0 else 0.0
    # run B: host decode (device-min-bytes huge -> numpy GF path, the
    # reference engine's role on this machine; aggregator bypassed so
    # the decode stage is the per-object CPU plugin path)
    dt_h, total_h, dsec_h, dbytes_h, _b = asyncio.run(
        _recovery_scenario({"device-min-bytes": str(1 << 40)},
                           decode_batch="off"))
    host_mbs = (dbytes_h / dsec_h / 1e6) if dsec_h > 0 else 0.0
    ratio = dev_mbs / host_mbs if host_mbs > 0 else 0.0
    k, m = _bench_ec_profile()
    mb = batch.get("mean_batch", 0.0)
    cold = batch.get("cold_launches", 0.0)
    _emit(
        f"e2e 1-OSD-down recovery, {os.environ.get('BENCH_RECOVERY_OSDS', '64')} "
        f"OSDs in separate processes, EC({k},{m}), encode farm ON, "
        f"{total // 2**20} MiB user data: to-clean "
        f"(in-daemon batched decode stage {dev_mbs:.1f} MB/s vs "
        f"{host_mbs:.1f} MB/s per-object host = {ratio:.1f}x; "
        f"aggregator mean batch {mb:.1f} obj/launch, "
        f"{cold:.0f} cold compiles in-path; host-run e2e "
        f"{total_h / dt_h / 1e6:.1f} MB/s)",
        total / dt / 1e6, "MB/s to clean", 1.0,
    )


def _cpu_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


CONFIGS = {
    "jerasure_cpu": (bench_jerasure_cpu, False),
    "decode_tpu": (bench_decode_tpu, True),
    "clay_repair": (bench_clay_repair, True),
    "_clay_cpu": (bench_clay_cpu_probe, False),
    # batched recovery decode (ISSUE 1): aggregator vs per-object CPU
    "decode_batch": (bench_decode_batch, True),
    # batched deep-scrub verification (ISSUE 2): scrub verifier vs
    # per-object host crc32c + re-encode on identical chunks
    "scrub_verify": (bench_scrub_verify, True),
    # remap runs on the device: with the epoch-spanning program cache
    # (ceph_tpu/osd/remap.py _crush_fingerprint) a steady-state epoch
    # is a couple of launches
    "remap": (bench_remap, True),
    # multi-process e2e: the first OSD group owns the chip, the other
    # workers are CPU-pinned (_recovery_scenario)
    "recovery": (bench_recovery, True),
}


def main(argv: list[str]) -> int:
    if argv and argv[0] == "_osd_group":
        return _osd_group_main(argv[1:])
    if argv:
        fn, _ = CONFIGS[argv[0]]
        fn()
        return 0
    failed = False
    for name, (_fn, on_device) in CONFIGS.items():
        if name.startswith("_"):
            continue
        env = dict(os.environ) if on_device else _cpu_env()
        r = subprocess.run(
            [sys.executable, __file__, name],
            capture_output=True, text=True, env=env,
        )
        for line in r.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
        if r.returncode != 0:
            failed = True
            print(json.dumps({
                "metric": name, "error": r.stderr.strip().splitlines()[-1:],
            }), flush=True)
    return int(failed)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
