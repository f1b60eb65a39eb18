#!/usr/bin/env python
"""chip_smoke: does the EC pool's served path run on this TPU?

One process on one accelerator host (the ``tools/vstart.py`` mapping:
every daemon in the process shares ``ceph_tpu/parallel/*.shared()``).
It boots 1 monitor + 12 OSDs on BlockStore, creates an EC pool
``plugin=jax technique=cauchy k=8 m=3`` (4 KiB stripe unit, 128 PGs,
failure domain host) and drives the main path through the entry points
a user calls — ``RadosClient``/``IoCtx``, mon commands — at upstream's
``rados bench`` shape: 4 MiB objects, 16 in flight, >= 64 objects
(256 MiB of user data, 352 MiB stored).

Phases (one JSON line each, the device identity on every line):

  device    platform must be ``tpu`` — there is no CPU mode
  kernels   every kernel the EC path can select, byte-exact at S =
            512 KiB and 1 MiB for RS(8,3), RS(4,2) and RS(2,1), encode
            and decode with 1..m erasures
  write     write all objects, read all back; stored shards equal the
            host reference encode
  degraded  stop one OSD, read every object again (device decode)
  recovery  mark it out, wait for clean; rebuilt shards equal the
            host reference
  scrub     ``pg deep-scrub`` on every PG, nothing inconsistent
  remap     the BASELINE.md config-4 map (10,240 PGs x 1,024 OSDs)
            through the batched mapper, equal to the scalar pipeline

Every comparison is byte-for-byte against the plain host reference
(``ops/gf256.gf_matmul`` via a host-pinned plugin, scalar
``crush/mapper.py``) and sits outside any timed region.  Every
device-to-host fallback on the path is counted and must be 0.  Times
printed here are smoke timings of one run — never a benchmark metric.
Any failed check, exception or phase timeout exits non-zero.

After the phases comes one ``summary`` line (sizes, any cut, total
smoke seconds, ``"claim": null``), and the last stdout line is the
verdict alone, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.  A run that fails prints no verdict:
it ends in a traceback and a non-zero exit code.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

POOL = "smoke"


class NoAccelerator(RuntimeError):
    """JAX found no TPU: the smoke has nothing to say."""


class Report:
    """Device identity + the per-phase JSON lines."""

    def __init__(self) -> None:
        self.device: dict = {}
        self.phases: dict[str, dict] = {}

    def emit(self, phase: str, **fields) -> dict:
        self.phases[phase] = fields
        print(json.dumps({"phase": phase, "device": self.device, **fields}),
              flush=True)
        return fields


def check(cond: bool, what: str) -> None:
    """A smoke assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


def _delta(after: dict, before: dict, always=()) -> dict:
    """Counters that moved, plus the ``always`` ones even at 0 (the
    fallback counts the phase asserts on are printed, not implied)."""
    d = {k: v - before.get(k, 0) for k, v in after.items()
         if v - before.get(k, 0)}
    for k in always:
        d.setdefault(k, 0)
    return d


# -- phase 1: device ---------------------------------------------------------

def phase_device(rep: Report) -> dict:
    t0 = time.perf_counter()
    import jax
    import jaxlib

    devs = jax.devices()
    init_s = time.perf_counter() - t0
    rep.device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"chip_smoke: needs a TPU, JAX found platform "
            f"{devs[0].platform!r} (there is no CPU mode)")

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None

    from ceph_tpu import native
    from ceph_tpu.ops.compile_cache import ensure_persistent_cache

    check(native.available(),
          "native runtime did not build: crc32c would run per byte in "
          "Python")
    check(ensure_persistent_cache(), "persistent compile cache is off")

    # round trip of a warmed trivial launch, host clock
    bump = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.zeros((8, 128), np.int32))
    jax.block_until_ready(bump(x))
    samples = []
    for _ in range(200):
        t0 = time.perf_counter()
        jax.block_until_ready(bump(x))
        samples.append(time.perf_counter() - t0)
    return rep.emit(
        "device",
        versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "libtpu": libtpu,
                  "python": sys.version.split()[0]},
        native_available=True,
        compile_cache_dir=_cache_dir(),
        smoke_backend_init_s=round(init_s, 3),
        smoke_launch_round_trip_us_median=round(
            float(np.median(samples)) * 1e6, 1),
    )


def _cache_dir() -> str:
    from ceph_tpu.ops.compile_cache import DEFAULT_DIR

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def _cache_entries() -> int | None:
    try:
        return len(os.listdir(_cache_dir()))
    except FileNotFoundError:
        return None


# -- phase 1b: kernels -------------------------------------------------------

def phase_kernels(rep: Report, *, codes=((8, 3), (4, 2), (2, 1)),
                  widths=(512 << 10, 1 << 20), seed: int = 0) -> dict:
    """Every GF kernel ``BitmatrixCodec._apply`` can select for 2-D
    data on this backend, against ``gf_matmul``."""
    import jax

    from ceph_tpu.models import isa_cauchy_matrix
    from ceph_tpu.models.matrices import decode_matrix_for
    from ceph_tpu.ops.gf256 import gf_matmul, gf_matrix_to_bitmatrix
    from ceph_tpu.ops.rs_kernels import BitmatrixCodec

    rng = np.random.default_rng([seed, 1])
    first_s: dict[str, float] = {}
    checked = 0
    for k, m in codes:
        C = isa_cauchy_matrix(k, m)
        for S in widths:
            data = rng.integers(0, 256, (k, S), dtype=np.uint8)
            word = np.concatenate([data, gf_matmul(C, data)])
            cases = [("enc", C, data, word[k:])]
            for e in range(1, m + 1):
                erased = list(range(e))
                survivors = [i for i in range(k + m) if i >= e][:k]
                cases.append((f"dec{e}", decode_matrix_for(C, erased),
                              word[survivors], word[erased]))
            for name, M, rows, want in cases:
                bits = jax.device_put(gf_matrix_to_bitmatrix(M))
                t0 = time.perf_counter()
                got = jax.device_get(BitmatrixCodec._apply(
                    bits, jax.device_put(rows), None))
                first_s[f"rs({k},{m}) {name} S={S}"] = round(
                    time.perf_counter() - t0, 3)
                check(np.array_equal(got, want),
                      f"kernel rs({k},{m}) {name} S={S} differs from "
                      "the host reference")
                checked += 1
    return rep.emit("kernels", byte_exact=checked,
                    selected="pallas" if rep.device["platform"] == "tpu"
                    else "xla",
                    smoke_first_launch_s=first_s)


# -- the cluster -------------------------------------------------------------

class SmokeCluster:
    """1 mon + n OSDs on BlockStore in this process, plus a client."""

    #: vstart's clocks: 1 s beacons, 4 beacons of grace at the mon
    BEACON = 1.0

    def __init__(self, n_osds: int, data_dir: str, *, encode_service=None):
        self.n_osds = n_osds
        self.data_dir = data_dir
        self._injected_service = encode_service
        self.mon = None
        self.osds: list = []
        self.stores: list = []
        self.client = None
        self.io = None
        self.ec = None         # host-pinned reference plugin instance
        self.profile: dict = {}

    async def __aenter__(self) -> "SmokeCluster":
        from ceph_tpu.client import RadosClient
        from ceph_tpu.crush import builder as B
        from ceph_tpu.crush.types import CrushMap
        from ceph_tpu.mon import Monitor
        from ceph_tpu.osd.daemon import OSDDaemon
        from ceph_tpu.store.blockstore import BlockStore

        crush = CrushMap()
        B.build_hierarchy(crush, osds_per_host=1, n_hosts=self.n_osds)
        self.mon = Monitor(crush=crush, beacon_grace=4 * self.BEACON,
                           out_interval=0.0)
        await self.mon.start()
        for i in range(self.n_osds):
            store = BlockStore(os.path.join(self.data_dir, f"osd{i}"))
            store.mount()
            self.stores.append(store)
            osd = OSDDaemon(i, self.mon.addr, store=store,
                            beacon_interval=self.BEACON,
                            encode_service=self._injected_service)
            await osd.start()
            self.osds.append(osd)
        self.client = RadosClient(client_id=2121)
        await self.client.connect(*self.mon.addr)
        return self

    async def __aexit__(self, *exc) -> None:
        if self.client is not None:
            await self.client.shutdown()
        for osd in self.osds:
            if osd is not None:
                await osd.stop()
        if self.mon is not None:
            await self.mon.stop()
        for store in self.stores:
            store.umount()

    @property
    def encode_service(self):
        if self._injected_service is not None:
            return self._injected_service
        from ceph_tpu.parallel import encode_service as es

        return es.shared()

    def live_osds(self):
        return [o for o in self.osds if o is not None]

    async def create_pool(self, k: int, m: int, pg_num: int) -> None:
        from ceph_tpu.ec import registry

        self.profile = {"plugin": "jax", "technique": "cauchy",
                        "k": str(k), "m": str(m),
                        "crush-failure-domain": "host"}
        await self.client.ec_profile_set(POOL, dict(self.profile))
        await self.client.pool_create(
            POOL, pg_num=pg_num, pool_type="erasure",
            erasure_code_profile=POOL)
        self.io = self.client.ioctx(POOL)
        # the plain host reference: same plugin, device path shut off
        self.ec = registry.factory("jax", dict(self.profile))
        self.ec.device_min_bytes = 1 << 62

    async def wait_warm(self, timeout: float) -> None:
        """Every daemon's map-install EC warm-up done, none failed."""
        deadline = time.monotonic() + timeout
        while True:
            tasks = [t for o in self.live_osds() for t in o._warm_tasks]
            if tasks:
                await asyncio.wait_for(
                    asyncio.gather(*tasks),
                    max(deadline - time.monotonic(), 0.001))
                # a gather of tasks that are all done completes without
                # a yield, while the done callbacks that take them out
                # of _warm_tasks are still queued: let those run, or
                # this loop never leaves the event loop's one step
                await asyncio.sleep(0)
            elif all(POOL in o._warmed_profiles for o in self.live_osds()):
                break
            else:
                check(time.monotonic() < deadline,
                      "OSDs never saw the EC profile")
                await asyncio.sleep(0.05)
        failed = sum(o.perf.dump().get("ec_warmup_failures", 0)
                     for o in self.live_osds())
        check(failed == 0, f"{failed} EC warm-ups failed (see log)")

    def acting_of(self, i: int):
        """(folded pg, acting OSDs by shard) of object ``i`` under the
        client's current map."""
        from ceph_tpu.osd.daemon import object_to_pg

        om = self.client.osdmap
        pool = om.get_pg_pool(self.io.pool_id)
        pg = pool.raw_pg_to_pg(object_to_pg(pool, oid(i)))
        return pg, om.pg_to_up_acting_osds(pg, folded=True)[2]

    def engine_stats(self, key: str) -> dict:
        """One counter across the three launch-batching engines."""
        from ceph_tpu.parallel import decode_batcher as db
        from ceph_tpu.parallel import scrub_batcher as sb

        return {"encode_service": self.encode_service.stats[key],
                "decode_aggregator": db.shared().stats[key],
                "scrub_verifier": sb.shared().stats[key]}

    def up_osds(self) -> int:
        om = self.mon.osdmap
        return sum(1 for o in range(self.n_osds)
                   if om.max_osd > o and om.is_up(o))


def payload(seed: int, i: int, nbytes: int) -> bytes:
    return np.random.default_rng([seed, 2, i]).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


def oid(i: int) -> str:
    return f"smoke-{i:05d}"


async def _bounded(n: int, in_flight: int, fn) -> list:
    sem = asyncio.Semaphore(in_flight)

    async def one(i):
        async with sem:
            return await fn(i)

    return await asyncio.gather(*(one(i) for i in range(n)))


async def read_all_equal(c: SmokeCluster, blobs: list[bytes],
                         in_flight: int, when: str) -> float:
    """Read every object ``in_flight`` at a time; each must equal what
    was written.  Returns the reads' wall seconds (compare excluded)."""
    t0 = time.perf_counter()
    got = await _bounded(len(blobs), in_flight,
                         lambda i: c.io.read(oid(i)))
    dt = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(got, blobs)):
        check(a == b, f"{oid(i)} read {when} differs from what was written")
    return dt


def verify_stored_shards(c: SmokeCluster, blobs: list[bytes]) -> int:
    """Every shard of every object, as it sits in the acting OSDs'
    stores, against the host-reference encode of the object's bytes.
    Returns the number of shards compared."""
    from ceph_tpu.osd import ecutil
    from ceph_tpu.osd.pgutil import STRIPE_UNIT
    from ceph_tpu.store import coll_t, ghobject_t

    k = c.ec.get_data_chunk_count()
    sinfo = ecutil.StripeInfo(k, c.ec.get_chunk_size(STRIPE_UNIT * k) * k)
    n = 0
    for i, blob in enumerate(blobs):
        want = ecutil.encode(sinfo, c.ec, blob)
        pg, acting = c.acting_of(i)
        check(len(acting) == len(want)
              and all(0 <= o < c.n_osds for o in acting),
              f"{oid(i)}: acting set {acting} has holes")
        for shard, osd in enumerate(acting):
            got = c.osds[osd].store.read(
                coll_t(pg.pool, pg.ps, shard),
                ghobject_t(oid(i), shard=shard))
            check(bytes(got) == want[shard].tobytes(),
                  f"{oid(i)} shard {shard} on osd.{osd} differs from "
                  "the host reference encode")
            n += 1
    return n


def _check_farm(svc, d: dict, min_requests: int) -> None:
    """One phase's encode-service delta ``d``: the work ran on the
    device(s), all of it, with no host fallback."""
    check(svc.active(), "encode service is inactive: EC math would run "
          "on host numpy")
    if svc.mesh is None:
        launches = d.get("single_dispatches", 0)
    else:
        launches = d.get("dp_dispatches", 0)
        check(svc.stats["mesh_devices_used"] == svc.mesh.size,
              f"farm launches sat on {svc.stats['mesh_devices_used']} of "
              f"{svc.mesh.size} devices")
    check(launches > 0,
          f"no encode-service device launch in this phase: {d}")
    served = d.get("coalesced", 0)
    check(served >= min_requests,
          f"encode service served {served} < {min_requests} requests: {d}")
    check(d["fallbacks"] == 0, f"encode service fell back: {d}")


# -- set-up: the shapes the smoke's own widths need --------------------------

async def warm_payload_shapes(c: SmokeCluster, obj_bytes: int,
                              in_flight: int) -> dict:
    """The daemons' map-install ladder stops at 64 x the stripe-unit
    chunk; a 4 MiB object is wider.  Compile the encode-service shapes
    this run's widths reach now, one by one, so each cold compile is
    printed and none lands inside a phase's timing."""
    svc = c.encode_service
    k = c.ec.get_data_chunk_count()
    S = obj_bytes // k
    mats = {"enc": np.asarray(c.ec.coding_matrix, np.uint8),
            "dec1": c.ec.decode_matrix((0,))}
    compile_s: dict[str, float] = {}
    for name, M in mats.items():
        if svc.mesh is not None:
            t0 = time.perf_counter()
            n = await asyncio.to_thread(
                svc.prewarm, M, [S], coalesce=in_flight)
            compile_s[f"{name} S={S} mesh x{n}"] = round(
                time.perf_counter() - t0, 3)
            continue
        f = 1
        while f <= in_flight:
            t0 = time.perf_counter()
            n = await asyncio.to_thread(
                svc.prewarm, M, [S * f], coalesce=1)
            if n:
                compile_s[f"{name} S={S * f}"] = round(
                    time.perf_counter() - t0, 3)
            f <<= 1
    return compile_s


# -- phase 2: write / read ---------------------------------------------------

async def phase_write_read(rep: Report, c: SmokeCluster, blobs: list[bytes],
                           in_flight: int) -> dict:
    svc = c.encode_service
    before = dict(svc.stats)
    t0 = time.perf_counter()
    await _bounded(len(blobs), in_flight,
                   lambda i: c.io.write_full(oid(i), blobs[i]))
    t_write = time.perf_counter() - t0
    t_read = await read_all_equal(c, blobs, in_flight, "back")
    d = _delta(dict(svc.stats), before, always=("fallbacks",))
    shards = await asyncio.to_thread(verify_stored_shards, c, blobs)
    _check_farm(svc, d, len(blobs))
    return rep.emit(
        "write", objects=len(blobs), object_bytes=len(blobs[0]),
        in_flight=in_flight, read_back_equal=len(blobs),
        stored_shards_equal_host_reference=shards,
        encode_service=d,
        smoke_write_s=round(t_write, 3), smoke_read_s=round(t_read, 3))


# -- phase 3: degraded read --------------------------------------------------

async def phase_degraded_read(rep: Report, c: SmokeCluster,
                              blobs: list[bytes], in_flight: int,
                              victim: int, timeout: float) -> dict:
    k = c.ec.get_data_chunk_count()
    need_decode = sum(victim in c.acting_of(i)[1][:k]
                      for i in range(len(blobs)))
    check(need_decode > 0, f"osd.{victim} holds no data shard: the "
          "degraded phase would decode nothing")

    svc = c.encode_service
    before = dict(svc.stats)
    await c.osds[victim].stop()
    c.osds[victim] = None
    # the mon notices by itself: missed beacons / peer failure reports
    deadline = time.monotonic() + timeout
    while c.client.osdmap.is_up(victim):
        check(time.monotonic() < deadline,
              f"mon never marked osd.{victim} down")
        await c.client._wait_new_map(c.client.osdmap.epoch, timeout=1.0)
    t_read = await read_all_equal(c, blobs, in_flight, "degraded")
    d = _delta(dict(svc.stats), before, always=("fallbacks",))
    _check_farm(svc, d, need_decode)
    return rep.emit(
        "degraded", stopped_osd=victim, read_equal=len(blobs),
        objects_needing_decode=need_decode, encode_service=d,
        smoke_read_s=round(t_read, 3))


# -- phase 4: recovery -------------------------------------------------------

async def phase_recovery(rep: Report, c: SmokeCluster, blobs: list[bytes],
                         in_flight: int, victim: int,
                         timeout: float) -> dict:
    from ceph_tpu.parallel import decode_batcher as db

    agg = db.shared()
    before = dict(agg.stats)
    t0 = time.perf_counter()
    code, rs, _ = await c.client.command(
        {"prefix": "osd out", "id": str(victim)})
    check(code == 0, f"osd out: {rs}")
    code, rs, data = await c.client.command({"prefix": "status"})
    check(code == 0, f"status: {rs}")
    out_epoch = json.loads(data)["epoch"]
    # every pg report must post-date the out-epoch: stale pre-out
    # active+clean reports would satisfy the wait instantly
    await c.client.wait_clean(timeout=timeout, min_epoch=out_epoch)
    t_clean = time.perf_counter() - t0
    d = _delta(dict(agg.stats), before, always=("fallbacks",))
    check(d.get("launches", 0) > 0,
          f"recovery made no batched decode launch: {d}")
    check(d["fallbacks"] == 0, f"decode aggregator fell back: {d}")
    await read_all_equal(c, blobs, in_flight, "after recovery")
    # the client's map may trail the mon's by the out-epoch
    while c.client.osdmap.epoch < out_epoch:
        await c.client._wait_new_map(c.client.osdmap.epoch, timeout=1.0)
    shards = await asyncio.to_thread(verify_stored_shards, c, blobs)
    return rep.emit(
        "recovery", marked_out=victim, read_equal=len(blobs),
        stored_shards_equal_host_reference=shards, decode_aggregator=d,
        placement="default device", smoke_to_clean_s=round(t_clean, 3))


# -- phase 5: deep scrub -----------------------------------------------------

async def phase_deep_scrub(rep: Report, c: SmokeCluster, pg_num: int,
                           in_flight: int) -> dict:
    from ceph_tpu.parallel import scrub_batcher as sb

    ver = sb.shared()
    before = dict(ver.stats)

    async def scrub(ps: int) -> int:
        code, rs, data = await c.client.command({
            "prefix": "pg deep-scrub", "pgid": f"{c.io.pool_id}.{ps}"})
        check(code == 0, f"pg deep-scrub {c.io.pool_id}.{ps}: {code} {rs}")
        report = json.loads(data)
        check(report["inconsistencies"] == [],
              f"pg {c.io.pool_id}.{ps} inconsistent: {report}")
        return int(report.get("objects", 0))

    t0 = time.perf_counter()
    objects = await _bounded(pg_num, in_flight, scrub)
    t_scrub = time.perf_counter() - t0
    d = _delta(dict(ver.stats), before,
               always=("fallbacks", "dispatch_fallbacks"))
    check(d.get("launches", 0) > 0, f"scrub made no device launch: {d}")
    check(d["fallbacks"] == 0 and d["dispatch_fallbacks"] == 0,
          f"scrub verifier fell back: {d}")
    return rep.emit(
        "scrub", pgs=pg_num, objects_scrubbed=sum(objects),
        inconsistencies=0, scrub_verifier=d, placement="default device",
        smoke_scrub_s=round(t_scrub, 3))


# -- phase 6: whole-map remap ------------------------------------------------

def build_remap_map(*, n_hosts: int, osds_per_host: int, rep_pgs: int,
                    ec_pgs: int, ec_size: int, ec_min_size: int):
    """BASELINE.md config 4's shape: one size-3 replicated pool
    (chooseleaf firstn host) and one wide-EC MSR pool (one OSD in each
    of ``ec_size`` hosts; left out when ``ec_pgs`` is 0) over a
    root -> host -> osd tree."""
    from ceph_tpu.crush import builder as B
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import PgPool, PoolType

    crush = CrushMap()
    B.build_hierarchy(crush, osds_per_host=osds_per_host, n_hosts=n_hosts)
    om = OSDMap(crush=crush)
    for osd in range(n_hosts * osds_per_host):
        om.new_osd(osd, weight=0x10000, up=True)
    root = om.crush.bucket_names["default"]
    fd = om.crush.type_id("host")
    rule = B.add_simple_rule(om.crush, root, fd, mode="firstn")
    om.pools[1] = PgPool(
        id=1, type=PoolType.REPLICATED, size=3, min_size=2,
        crush_rule=rule, pg_num=rep_pgs, pgp_num=rep_pgs)
    om.pool_names[1] = "rep"
    if ec_pgs:
        msr_rule = B.add_osd_multi_per_domain_rule(
            om.crush, root, fd, num_per_domain=1, num_domains=ec_size)
        om.pools[2] = PgPool(
            id=2, type=PoolType.ERASURE, size=ec_size,
            min_size=ec_min_size, crush_rule=msr_rule, pg_num=ec_pgs,
            pgp_num=ec_pgs)
        om.pool_names[2] = "ec-msr"
    return om


def phase_remap(rep: Report, om, *, sample: int, seed: int) -> dict:
    from ceph_tpu.osd.remap import BatchedClusterMapper
    from ceph_tpu.osd.types import pg_t

    mapper = BatchedClusterMapper(om)
    t0 = time.perf_counter()
    res = mapper.map_cluster()
    t_first = time.perf_counter() - t0      # compile included: set-up
    check(mapper.cc is not None, "CRUSH map fell outside the batched engine")
    # a later epoch (osd state + weight change) reuses the program
    om.epoch += 1
    om.mark_down(1)
    om.osd_weight[2] = 0x8000
    mapper2 = BatchedClusterMapper(om)
    t0 = time.perf_counter()
    res = mapper2.map_cluster()
    t_epoch = time.perf_counter() - t0
    stats = mapper.stats + mapper2.stats
    # scalar_pools counts both ways out of the batched path: a map the
    # engine does not support and a launch that raised (logged as
    # "batched remap unavailable")
    check(stats["scalar_pools"] == 0
          and stats["batched_pools"] == 2 * len(om.pools),
          f"remap pools not all batched: {dict(stats)}")
    rng = np.random.default_rng([seed, 3])
    compared = 0
    for pid, pool in om.pools.items():
        n = min(sample, pool.pg_num)
        for ps in rng.choice(pool.pg_num, n, replace=False):
            ref = om.pg_to_up_acting_osds(pg_t(pid, int(ps)), folded=True)
            check(res[pid].rows(int(ps)) == ref,
                  f"pg {pid}.{ps}: batched {res[pid].rows(int(ps))} != "
                  f"scalar {ref}")
            compared += 1
    return rep.emit(
        "remap", osds=om.max_osd,
        pgs={om.pool_names[p]: om.pools[p].pg_num for p in om.pools},
        pgs_equal_scalar=compared, remap=dict(stats),
        placement="default device",
        smoke_first_epoch_s_compile_included=round(t_first, 3),
        smoke_next_epoch_s=round(t_epoch, 3))


# -- the run -----------------------------------------------------------------

async def run_cluster_phases(
        rep: Report, *, data_dir: str, seed: int, n_osds: int, k: int,
        m: int, pg_num: int, n_objects: int, obj_bytes: int,
        in_flight: int, phase_timeout: float,
        encode_service=None) -> None:
    """Set-up + phases 2-5 on one in-process cluster."""
    from ceph_tpu.common import transfer_guard
    from ceph_tpu.ec.plugins.matrix_base import MatrixErasureCode

    blobs = [payload(seed, i, obj_bytes) for i in range(n_objects)]
    cache_before = _cache_entries()
    guard_before = transfer_guard.snapshot()
    plugin_before = dict(MatrixErasureCode.device_stats)
    t_setup = time.perf_counter()
    async with SmokeCluster(n_osds, data_dir,
                            encode_service=encode_service) as c:
        await c.create_pool(k, m, pg_num)
        t0 = time.perf_counter()
        await c.wait_warm(phase_timeout)
        t_warm = time.perf_counter() - t0
        compile_s = await asyncio.wait_for(
            warm_payload_shapes(c, obj_bytes, in_flight),
            phase_timeout)
        await c.client.wait_clean(timeout=phase_timeout)
        check(c.up_osds() == n_osds,
              f"only {c.up_osds()}/{n_osds} OSDs up after warm-up: the "
              "backend start stalled the beacons")
        svc = c.encode_service
        if encode_service is None:
            check((svc.mesh is not None) if rep.device["count"] > 1
                  else (svc.device is not None),
                  f"encode service did not take the {rep.device} it "
                  "was given")
        cold_before = c.engine_stats("cold_launches")
        rep.emit(
            "setup", osds=n_osds, pool=c.profile, pg_num=pg_num,
            store="BlockStore",
            encode_service_mode="mesh" if svc.mesh is not None
            else "single-device" if svc.device is not None else "inactive",
            prewarmed_shapes=c.engine_stats("prewarmed_shapes"),
            smoke_daemon_warmup_s=round(t_warm, 3),
            smoke_payload_shape_compile_s=compile_s,
            smoke_setup_s=round(time.perf_counter() - t_setup, 3),
            compile_cache_entries={"before": cache_before,
                                   "after": _cache_entries()})

        await asyncio.wait_for(
            phase_write_read(rep, c, blobs, in_flight), phase_timeout)
        # any OSD must be losable: the pool's rule has one spare host
        victim = n_osds - 1
        await asyncio.wait_for(
            phase_degraded_read(rep, c, blobs, in_flight, victim,
                                phase_timeout / 2),
            phase_timeout)
        await asyncio.wait_for(
            phase_recovery(rep, c, blobs, in_flight, victim,
                           phase_timeout / 2), phase_timeout)
        await asyncio.wait_for(
            phase_deep_scrub(rep, c, pg_num, in_flight), phase_timeout)
        check(c.up_osds() == n_osds - 1,
              f"{c.up_osds()} OSDs up at the end, expected {n_osds - 1}")

        guard = _delta(transfer_guard.snapshot(), guard_before,
                       always=("host_transfers",))
        cold = _delta(c.engine_stats("cold_launches"), cold_before)
        plugin = _delta(dict(MatrixErasureCode.device_stats), plugin_before,
                        always=("fallbacks",))
        check(guard["host_transfers"] == 0,
              f"implicit host<->device transfers on the I/O path: {guard}")
        check(guard.get("guard_windows", 0) > 0,
              "the transfer guard never armed: host_transfers == 0 "
              "would be vacuous")
        check(plugin["fallbacks"] == 0,
              f"EC plugin device path fell back to numpy: {plugin}")
        rep.emit("counters", transfer_guard=guard,
                 cold_launches_in_phases=cold,
                 plugin_sync_device_path=plugin)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every payload and sample")
    ap.add_argument("--objects", type=int, default=64,
                    help="4 MiB objects to write (the one size that may "
                         "be cut; a cut is printed)")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")

    rep = Report()
    t_all = time.perf_counter()
    try:
        phase_device(rep)
    except NoAccelerator as exc:
        print(exc, file=sys.stderr)
        return 1
    phase_kernels(rep, seed=args.seed)

    data_dir = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        asyncio.run(run_cluster_phases(
            rep, data_dir=data_dir, seed=args.seed, n_osds=12, k=8, m=3,
            pg_num=128, n_objects=args.objects, obj_bytes=4 << 20,
            in_flight=16, phase_timeout=300.0))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    phase_remap(
        rep,
        build_remap_map(n_hosts=128, osds_per_host=8, rep_pgs=8192,
                        ec_pgs=2048, ec_size=11, ec_min_size=8),
        sample=256, seed=args.seed)

    rep.emit(
        "summary", phases=list(rep.phases),
        user_data_bytes=args.objects * (4 << 20),
        cut=None if args.objects >= 64 else
        f"objects cut from 64 to {args.objects}",
        compile_cache_entries=_cache_entries(),
        smoke_total_s=round(time.perf_counter() - t_all, 3),
        claim=None)
    print(verdict_line(rep), flush=True)
    return 0


def verdict_line(rep: Report) -> str:
    """The last stdout line: these two keys and the device's three,
    nothing else — the chip check parses it strictly."""
    return json.dumps({"ok": True, "device": {
        "platform": str(rep.device["platform"]),
        "kind": str(rep.device["kind"]),
        "count": int(rep.device["count"])}})


if __name__ == "__main__":
    sys.exit(main())
