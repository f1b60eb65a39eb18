"""A CLAY degraded read on the served device path: the data chunks a read
lost rebuilt from the k fetched chunks as ONE matrix over sub-chunk rows
(``ErasureCodeClay.linear_plan``), filed with the encode
service by ``ecutil.decode_concat_async``; held to the plain reference
of the benchmark (``benchmarks/references/clay8411.py``, loaded by path:
it imports nothing of the program) and to the host's layered decode,
tiny, on the CPU backend, at both of ``test_clay_served.py``'s
geometries.  Nothing here is a device number.
"""

from __future__ import annotations

import asyncio
import itertools
import os

import jax
import numpy as np
import pytest

from ceph_tpu.common.tracing import device_tracer
from ceph_tpu.ec import registry
from ceph_tpu.osd import ecutil
from ceph_tpu.parallel.encode_service import EncodeService
from tests.test_clay_served import GEOMETRIES, REF, STRIPES, _blob, _code

#: (geometry, how many chunks are lost): every erasure set of that size
CASES = [(g, n) for g, (_k, m, _d, _u) in GEOMETRIES.items()
         for n in range(1, m + 1)]


def _service() -> EncodeService:
    # the CPU device, taking every payload: what a TPU host's shared()
    # is, at this test's sizes
    return EncodeService(device=jax.devices()[0], min_bytes=1)


def _fetch(ec, shards, lost: tuple) -> dict:
    """What ``_ec_fetch`` brings for a whole-object read with ``lost``
    gone: minimum_to_decode's chunks, as read-only views of the wire."""
    k = ec.get_data_chunk_count()
    avail = set(range(ec.get_chunk_count())) - set(lost)
    return {c: np.frombuffer(shards[c], np.uint8)
            for c in sorted(ec.minimum_to_decode(set(range(k)), avail))}


@pytest.mark.parametrize("geometry,n_lost", CASES,
                         ids=[f"{g}-lost{n}" for g, n in CASES])
def test_served_read_equals_the_reference_and_the_host_loop(
        geometry, n_lost):
    ec, sinfo = _code(geometry)
    k, m, d, unit = GEOMETRIES[geometry]
    svc = _service()
    blob = _blob(geometry, 42 + n_lost)
    shards = REF.clay_shards(blob, k, m, d, unit)

    async def read(td: dict, how: dict) -> np.ndarray:
        return await ecutil.decode_concat_async(
            sinfo, ec, td, service=svc, how=how)

    decoded = 0
    for lost in itertools.combinations(range(k + m), n_lost):
        td = _fetch(ec, shards, lost)
        lost_data = [c for c in range(k) if c not in td]
        before = svc.stats["coalesced"]
        how: dict = {}
        got = asyncio.run(read(td, how))
        assert got.tobytes() == blob, lost
        assert got.tobytes() == ecutil.decode_concat(
            sinfo, ec, td).tobytes(), lost
        # one request a read that lost data, none where only parity went
        assert svc.stats["coalesced"] - before == (1 if lost_data else 0)
        assert how == {
            "path": "device" if lost_data else "none", "lost": lost_data,
            "rebuilt_bytes": len(lost_data) * STRIPES * unit}, lost
        decoded += bool(lost_data)
    assert decoded and svc.stats["fallbacks"] == 0
    assert svc.stats["host_groups"] == 0


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_read_decode_matrix_is_derived_once_a_process(geometry):
    a, _ = _code(geometry)
    b, _ = _code(geometry)
    k, m, _d, _u = GEOMETRIES[geometry]
    alpha = a.get_sub_chunk_count()
    data = set(range(k))
    for n in range(1, m + 1):
        lost = set(range(n))
        fetched, D = a.linear_plan(data, set(range(k + m)) - lost)
        assert sorted(data - set(fetched)) == sorted(lost)
        assert len(fetched) == k
        assert fetched == sorted(set(range(k + m)) - lost)[:k]
        assert D.shape == (alpha * n, alpha * k)
        assert b.linear_plan(data, set(range(k + m)) - lost)[1] is D
        assert not D.flags.writeable
    # only parity lost: the data is fetched and nothing is decoded
    assert a.linear_plan(data, set(range(k + m)) - {k}) == (
        list(range(k)), None)
    other = registry.factory("clay", {
        "plugin": "clay", "k": str(k), "m": str(m), "d": str(_d),
        "scalar_mds": "isa"})
    assert other.linear_plan(data, set(range(1, k + m)))[1] \
        is not a.linear_plan(data, set(range(1, k + m)))[1]


def test_the_launch_is_tagged_and_one_prewarmed_shape_serves_every_loss():
    """The map-install warm-up compiles the (alpha, alpha * k) shape
    with the matrix of one lost chunk; a read that lost another reuses
    the program (the bit-matrix is an argument, not a constant)."""
    ec, sinfo = _code("k4m2d5")
    k, m, d, unit = GEOMETRIES["k4m2d5"]
    svc = _service()
    plan = ec.linear_plan(set(range(k)), set(range(1, k + m)))
    widths = [sinfo.chunk_size // ec.get_sub_chunk_count()]
    assert svc.prewarm(plan[1], widths) > 0
    blob = _blob("k4m2d5", 7)
    shards = REF.clay_shards(blob, k, m, d, unit)
    before = len(device_tracer().find(kind="subchunk_decode"))
    td = _fetch(ec, shards, (2,))
    lowered = []

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        got = asyncio.run(
            ecutil.decode_concat_async(sinfo, ec, td, service=svc))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert got.tobytes() == blob
    assert svc.stats["cold_launches"] == 0 and lowered == []
    spans = device_tracer().find(kind="subchunk_decode")
    assert len(spans) == before + 1
    tags = spans[-1].tags
    assert tags["helper_bytes"] == k * STRIPES * unit
    assert tags["rebuilt_bytes"] == STRIPES * unit
    assert tags["real_bytes"] == tags["helper_bytes"]


def test_a_read_with_no_service_takes_the_host_loop_and_says_so():
    ec, sinfo = _code("k4m2d5")
    k, m, d, unit = GEOMETRIES["k4m2d5"]
    blob = _blob("k4m2d5", 8)
    td = _fetch(ec, REF.clay_shards(blob, k, m, d, unit), (0, 5))
    how: dict = {}
    got = asyncio.run(ecutil.decode_concat_async(sinfo, ec, td, how=how))
    assert got.tobytes() == blob
    assert how == {"path": "host", "lost": [0],
                   "rebuilt_bytes": STRIPES * unit}


# -- a CLAY pool with an OSD down and in ---------------------------------------

def test_a_clay_pool_with_an_osd_down_and_in_reads_through_the_device(
        tmp_path):
    from ceph_tpu.client import RadosClient
    from ceph_tpu.crush import builder as B
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.mon import Monitor
    from ceph_tpu.osd.daemon import OSDDaemon, object_to_pg
    from ceph_tpu.store.blockstore import BlockStore

    k, m, d, unit = GEOMETRIES["k4m2d5"]
    n_osds, n_objects = k + m + 1, 12
    rng = np.random.default_rng(11)
    payloads = {f"obj{i}": rng.integers(
        0, 256, STRIPES * k * unit * (1 + i % 3), np.uint8).tobytes()
        for i in range(n_objects)}

    async def go():
        crush = CrushMap()
        B.build_hierarchy(crush, osds_per_host=1, n_hosts=n_osds)
        mon = Monitor(crush=crush)
        await mon.start()
        svc = _service()
        osds, stores = [], []
        for i in range(n_osds):
            store = BlockStore(os.path.join(str(tmp_path), f"osd{i}"))
            store.mount()
            stores.append(store)
            osd = OSDDaemon(i, mon.addr, store=store, encode_service=svc)
            await osd.start()
            osds.append(osd)
        client = RadosClient(client_id=4343, trace_sample_rate=1.0)
        await client.connect(*mon.addr)
        try:
            await client.ec_profile_set("clay", {
                "plugin": "clay", "k": str(k), "m": str(m), "d": str(d),
                "stripe_unit": str(unit), "crush-failure-domain": "host"})
            await client.pool_create("cp", pg_num=8, pool_type="erasure",
                                     erasure_code_profile="clay")
            io = client.ioctx("cp")
            for _ in range(1200):       # the daemons' EC warm-up
                if all("clay" in o._warmed_profiles and not o._warm_tasks
                       for o in osds):
                    break
                await asyncio.sleep(0.05)
            await client.wait_clean(timeout=60)
            for name, data in payloads.items():
                await io.write_full(name, data)
            # the OSD that holds data chunk 0 of the first object stops
            # and stays in: its PGs serve degraded
            om = client.osdmap
            pool = om.get_pg_pool(io.pool_id)
            acting = om.pg_to_up_acting_osds(object_to_pg(pool, "obj0"))[2]
            primary = om.pg_to_up_acting_osds(object_to_pg(pool, "obj0"))[3]
            victim = next(o for o in acting[:k] if o != primary)
            epoch = om.epoch
            await osds[victim].stop()
            osds[victim] = None
            await client.command({"prefix": "osd down", "id": str(victim)})
            await client._wait_new_map(epoch, timeout=10)
            live = [o for o in osds if o is not None]
            before = [o.perf.dump() for o in live]
            for name, data in payloads.items():
                assert await io.read(name) == data, name
            grew = {key: sum(o.perf.dump().get(key, 0) - b.get(key, 0)
                             for o, b in zip(live, before))
                    for key in ("ec_read_subchunk_decodes",
                                "ec_read_host_decodes")}
            decodes = [s for o in live for s in o.tracer.dump(limit=1 << 16)
                       if s["name"] == "ec_decode"
                       and s["tags"].get("path") == "device"]
            return grew, decodes
        finally:
            await client.shutdown()
            for o in osds:
                if o is not None:
                    await o.stop()
            await mon.stop()
            for store in stores:
                store.umount()

    loop = asyncio.new_event_loop()
    try:
        grew, decodes = loop.run_until_complete(
            asyncio.wait_for(go(), 120))
    finally:
        loop.close()
    assert grew["ec_read_subchunk_decodes"] >= 1
    assert grew["ec_read_host_decodes"] == 0
    assert decodes and all(
        s["tags"]["lost"] and s["tags"]["rebuilt_bytes"] > 0
        for s in decodes)
