"""An LRC(4,2,3) pool on the served path, tiny, on the CPU backend: its
reads take the data chunks where the code's chunk mapping puts them (0,
1, 4 and 5), its layered encode and a lost chunk's repair from its local
group of 3 are matrices read off the host code (``linear_plan``) and
filed with the encode service and the decode aggregator, and a whole-
chunk repair of any code reads minimum_to_decode's shards.  Held to the
benchmark's plain reference (``benchmarks/references/lrc423.py``, loaded
by path: it imports nothing of the program) and to the layered host
code.  Nothing here is a device number.
"""

from __future__ import annotations

import asyncio
import importlib.util
import itertools
import os

import jax
import numpy as np
import pytest

from ceph_tpu.common.tracing import device_tracer
from ceph_tpu.ec import registry
from ceph_tpu.ops.gf256 import gf_matmul
from ceph_tpu.osd import ecutil
from ceph_tpu.parallel.decode_batcher import DecodeAggregator
from ceph_tpu.parallel.encode_service import EncodeService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROFILE = {"plugin": "lrc", "k": "4", "m": "2", "l": "3",
           "crush-failure-domain": "host"}
POOL = {"type": "erasure", "plugin": "lrc", "k": 4, "m": 2,
        "stripe_unit": 4096, "profile": {"l": "3"}}
N, K, UNIT, STRIPES = 8, 4, 4096, 3


def _load(path: str):
    """A module of the benchmark by its path under ``benchmarks/``."""
    spec = importlib.util.spec_from_file_location(
        "served_" + path.replace("/", "_"),
        os.path.join(ROOT, "benchmarks", path + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("references/lrc423")


def _code():
    ec = registry.factory("lrc", dict(PROFILE))
    return ec, ecutil.stripe_info(ec)


def _blob(seed: int, stripes: int = STRIPES) -> bytes:
    return np.random.default_rng([44, seed]).integers(
        0, 256, stripes * K * UNIT, dtype=np.uint8).tobytes()


def _service() -> EncodeService:
    # the CPU device, taking every payload: what a TPU host's shared()
    # is, at this test's sizes
    return EncodeService(device=jax.devices()[0], min_bytes=1)


# -- the plan against the layered host code --------------------------------

def _erasure_sets():
    """Every set of lost positions that minimum_to_decode accepts."""
    ec, _ = _code()
    out = []
    for n in range(1, N - K + 1):
        for lost in itertools.combinations(range(N), n):
            try:
                ec.minimum_to_decode(set(lost), set(range(N)) - set(lost))
            except OSError:
                continue
            out.append(lost)
    return out


ERASURES = _erasure_sets()


def test_the_plan_covers_every_accepted_erasure_set_and_a_single_loss_reads_3():
    ec, _ = _code()
    assert len(ERASURES) > N and all((c,) in ERASURES for c in range(N))
    for c in range(N):
        helpers, M = ec.linear_plan({c}, set(range(N)) - {c})
        group = next(g for g in REF.GROUPS if c in g)
        assert helpers == [h for h in group if h != c]
        assert M.shape == (1, 3) and M.tolist() == [[1, 1, 1]]
    # nothing lost: no matrix
    assert ec.linear_plan({0, 1}, set(range(N)) - {4}) == ([0, 1], None)


@pytest.mark.parametrize("lost", ERASURES, ids=lambda e: "-".join(map(str, e)))
def test_a_plan_matrix_rebuilds_what_the_layered_decode_does(lost):
    ec, _ = _code()
    rng = np.random.default_rng([44, *lost])
    data = {p: rng.integers(0, 256, 96, dtype=np.uint8) for p in REF.DATA}
    enc = {p: data.get(p, np.zeros(96, np.uint8)).copy() for p in range(N)}
    ec.encode_chunks(set(range(N)), enc)
    avail = {p: enc[p] for p in range(N) if p not in lost}
    helpers, M = ec.linear_plan(set(lost), set(avail))
    got = gf_matmul(M, np.stack([avail[h] for h in helpers]))
    host = ec.decode(set(lost), {h: avail[h] for h in helpers}, 96)
    for i, c in enumerate(sorted(lost)):
        assert got[i].tobytes() == host[c].tobytes() == enc[c].tobytes()


def test_the_encode_matrix_is_the_layered_encode():
    ec, _ = _code()
    E = ec.encode_matrix()
    assert E.shape == (4, 4) and E is ec.encode_matrix()
    assert not E.flags.writeable
    rng = np.random.default_rng(443)
    data = rng.integers(0, 256, (K, 200), dtype=np.uint8)
    enc = {p: np.zeros(200, np.uint8) for p in range(N)}
    for i in range(K):
        enc[ec.chunk_index(i)][:] = data[i]
    ec.encode_chunks(set(range(N)), enc)
    got = gf_matmul(E, data)
    for j in range(N - K):
        assert got[j].tobytes() == enc[ec.chunk_index(K + j)].tobytes()


# -- the engines ------------------------------------------------------------

def test_served_encode_and_every_single_loss_read_equal_the_reference():
    ec, sinfo = _code()
    svc = _service()
    blob = _blob(1)
    ref = REF.expected_copies(POOL, blob)

    async def go():
        shards = await ecutil.encode_async(sinfo, ec, blob, service=svc)
        reads = []
        for lost in range(N):
            avail = set(range(N)) - {lost}
            fetched = sorted(ec.minimum_to_decode(set(REF.DATA), avail))
            how: dict = {}
            got = await ecutil.decode_concat_async(
                sinfo, ec, {c: shards[c] for c in fetched}, service=svc,
                how=how)
            reads.append((lost, got.tobytes(), how))
        return shards, reads

    before = svc.stats["coalesced"]
    shards, reads = asyncio.run(go())
    assert [shards[p].tobytes() for p in range(N)] == ref
    for lost, got, how in reads:
        assert got == blob, lost
        assert how["path"] == ("device" if lost in REF.DATA else "none")
        assert how["lost"] == ([lost] if lost in REF.DATA else [])
    # one encode and one decode for each lost data chunk, no fallback
    assert svc.stats["coalesced"] - before == 1 + len(REF.DATA)
    assert svc.stats["fallbacks"] == svc.stats["host_groups"] == 0


def test_a_read_with_no_service_takes_the_host_loop_and_says_so():
    ec, sinfo = _code()
    blob = _blob(2)
    ref = REF.expected_copies(POOL, blob)
    fetched = ec.minimum_to_decode(set(REF.DATA), set(range(N)) - {5})
    how: dict = {}
    got = asyncio.run(ecutil.decode_concat_async(
        sinfo, ec, {c: np.frombuffer(ref[c], np.uint8) for c in fetched},
        how=how))
    assert got.tobytes() == blob
    assert how == {"path": "host", "lost": [5],
                   "rebuilt_bytes": STRIPES * UNIT}


def test_a_local_repair_is_one_tagged_launch_of_a_prewarmed_shape():
    ec, sinfo = _code()
    agg = DecodeAggregator()
    agg.prewarm(ec, [UNIT])
    blob = _blob(3)
    ref = REF.expected_copies(POOL, blob)
    tracer = device_tracer()
    seen = len(tracer.dump(limit=1 << 16))

    async def repair(lost):
        helpers = ec.minimum_to_decode({lost}, set(range(N)) - {lost})
        how: dict = {}
        got = await ecutil.decode_shards_async(
            sinfo, ec, {h: np.frombuffer(ref[h], np.uint8) for h in helpers},
            {lost}, aggregator=agg, how=how)
        return got, how

    async def go():
        return await asyncio.gather(*(repair(c) for c in range(N)))

    cold = agg.stats["cold_launches"]
    for lost, (got, how) in zip(range(N), asyncio.run(go())):
        assert set(got) == {lost} and got[lost].tobytes() == ref[lost]
        assert how == {"kind": "local_repair", "helpers": 3}
    assert agg.stats["cold_launches"] == cold
    launches = [s for s in tracer.dump(limit=1 << 16)[seen:]
                if s["name"] == "xla_launch"
                and s["tags"].get("kind") == "local_repair"]
    assert launches
    assert sum(s["tags"]["objects"] for s in launches) == N
    assert sum(s["tags"]["helper_bytes"] for s in launches) == \
        3 * sum(s["tags"]["rebuilt_bytes"] for s in launches) \
        == 3 * N * STRIPES * UNIT


# -- a cluster ----------------------------------------------------------------

def _counters(osds, keys) -> dict:
    return {k: sum(o.perf.dump().get(k, 0) for o in osds if o is not None)
            for k in keys}


RECOVERY_KEYS = ("recovery_read_bytes", "recovery_rebuilt_bytes",
                 "recovery_rebuilt_shards", "recovery_local_repairs",
                 "recovery_decode_bytes")


def _cold_launches() -> int:
    """Launches of a shape the daemons' warm-up did not compile."""
    from ceph_tpu.parallel import decode_batcher

    return decode_batcher.shared().stats["cold_launches"]


async def _boot(tmp_path, n_osds: int, svc):
    from ceph_tpu.client import RadosClient
    from ceph_tpu.crush import builder as B
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.mon import Monitor
    from ceph_tpu.osd.daemon import OSDDaemon
    from ceph_tpu.store.blockstore import BlockStore

    crush = CrushMap()
    B.build_hierarchy(crush, osds_per_host=1, n_hosts=n_osds)
    mon = Monitor(crush=crush, out_interval=0.0)
    await mon.start()
    osds, stores = [], []
    for i in range(n_osds):
        store = BlockStore(os.path.join(str(tmp_path), f"osd{i}"))
        store.mount()
        stores.append(store)
        osd = OSDDaemon(i, mon.addr, store=store, encode_service=svc)
        await osd.start()
        osds.append(osd)
    client = RadosClient(client_id=4444, trace_sample_rate=1.0)
    await client.connect(*mon.addr)
    return mon, osds, stores, client


async def _shutdown(mon, osds, stores, client):
    await client.shutdown()
    for o in osds:
        if o is not None:
            await o.stop()
    await mon.stop()
    for store in stores:
        store.umount()


async def _pool(client, osds, name: str, profile: dict):
    await client.ec_profile_set(name, dict(profile))
    await client.pool_create(name, pg_num=8, pool_type="erasure",
                             erasure_code_profile=name)
    for _ in range(1200):       # the daemons' EC warm-up
        if all(name in o._warmed_profiles and not o._warm_tasks
               for o in osds):
            break
        await asyncio.sleep(0.05)
    await client.wait_clean(timeout=60)
    return client.ioctx(name)


def _acting(client, io, names) -> dict:
    from ceph_tpu.osd.daemon import object_to_pg

    om = client.osdmap
    pool = om.get_pg_pool(io.pool_id)
    return {n: om.pg_to_up_acting_osds(object_to_pg(pool, n))[2]
            for n in names}


async def _out_and_recover(client, osds, io, victim: int, before) -> tuple:
    """Stop ``victim`` (if it runs), mark it down and out, wait until
    every position that moved since ``before`` (name -> acting OSDs)
    sits on its new holder; returns (the counters' growth, the moved
    positions as (store, coll, obj, whether ``victim`` held it))."""
    from ceph_tpu.osd.daemon import object_to_pg
    from ceph_tpu.store import coll_t, ghobject_t

    if osds[victim] is not None:
        await osds[victim].stop()
        osds[victim] = None
    was = {**_counters(osds, RECOVERY_KEYS), "cold": _cold_launches()}
    await client.command({"prefix": "osd down", "id": str(victim)})
    await client.command({"prefix": "osd out", "id": str(victim)})
    deadline = asyncio.get_running_loop().time() + 10
    while not client.osdmap.is_out(victim):
        assert asyncio.get_running_loop().time() < deadline, "never out"
        await client._wait_new_map(client.osdmap.epoch, timeout=1)
    om = client.osdmap
    pool = om.get_pg_pool(io.pool_id)
    want = []
    for n in before:
        pg = object_to_pg(pool, n)
        after = om.pg_to_up_acting_osds(pg)[2]
        for s, (o0, o1) in enumerate(zip(before[n], after)):
            if o0 != o1:
                want.append((osds[o1].store, coll_t(
                    pool.id, pool.raw_pg_to_pg(pg).ps, s),
                    ghobject_t(n, shard=s), o0 == victim))
    deadline = asyncio.get_running_loop().time() + 60
    while not all(st.exists(c, o) for st, c, o, _l in want):
        assert asyncio.get_running_loop().time() < deadline, "no recovery"
        await asyncio.sleep(0.2)
    await client.wait_clean(timeout=60)
    got = {k: v - was[k] for k, v in {**_counters(osds, RECOVERY_KEYS),
                                      "cold": _cold_launches()}.items()}
    return got, want


def _stored(client, osds, io, name: str) -> list[bytes | None]:
    """The shards of ``name`` by position, as its acting OSDs hold them."""
    from ceph_tpu.osd.daemon import object_to_pg
    from ceph_tpu.store import coll_t, ghobject_t

    om = client.osdmap
    pool = om.get_pg_pool(io.pool_id)
    pg = pool.raw_pg_to_pg(object_to_pg(pool, name))
    out = []
    for s, o in enumerate(om.pg_to_up_acting_osds(pg, folded=True)[2]):
        st = osds[o].store
        c, obj = coll_t(pool.id, pg.ps, s), ghobject_t(name, shard=s)
        out.append(bytes(st.read(c, obj)) if st.exists(c, obj) else None)
    return out


@pytest.fixture(scope="module")
def lrc_cluster(tmp_path_factory):
    """One LRC(4,2,3) pool on 9 OSDs: objects written and read back
    healthy, then with one OSD down and in, then that OSD marked out and
    recovered.  What each stage saw."""
    from ceph_tpu.osd.daemon import object_to_pg

    rng = np.random.default_rng(4423)
    payloads = {f"lrc{i}": rng.integers(
        0, 256, K * UNIT * (1 + i % 3), np.uint8).tobytes()
        for i in range(12)}
    svc = _service()
    out: dict = {}

    async def go():
        mon, osds, stores, client = await _boot(
            tmp_path_factory.mktemp("lrc"), 9, svc)
        try:
            io = await _pool(client, osds, "lrc", PROFILE)
            for name, data in payloads.items():
                await io.write_full(name, data)
            out["healthy"] = {n: await io.read(n) for n in payloads}
            # the OSD that holds data position 4 of the first object
            # stops and stays in: its PGs serve degraded
            om = client.osdmap
            pool = om.get_pg_pool(io.pool_id)
            _, _, acting, primary = om.pg_to_up_acting_osds(
                object_to_pg(pool, "lrc0"))
            victim = acting[4] if acting[4] != primary else acting[5]
            before = _acting(client, io, payloads)
            epoch = om.epoch
            await osds[victim].stop()
            osds[victim] = None
            live = [o for o in osds if o is not None]
            await client.command({"prefix": "osd down", "id": str(victim)})
            await client._wait_new_map(epoch, timeout=10)
            keys = ("ec_read_host_decodes",)
            was = _counters(live, keys)
            seen = {id(o): len(o.tracer.dump(limit=1 << 16)) for o in live}
            out["degraded"] = {n: await io.read(n) for n in payloads}
            out["degraded_counters"] = {
                k: v - was[k] for k, v in _counters(live, keys).items()}
            out["decodes"] = [
                s for o in live for s in o.tracer.dump(limit=1 << 16)[
                    seen[id(o)]:] if s["name"] == "ec_decode"]
            # then out: recovery rebuilds what it held
            out["recovery"], out["moved"] = await _out_and_recover(
                client, osds, io, victim, before)
            out["recovered"] = {n: await io.read(n) for n in payloads}
            out["stored"] = {n: _stored(client, osds, io, n)
                             for n in payloads}
            out["spans"] = [s for o in osds if o is not None
                            for s in o.tracer.dump(limit=1 << 16)
                            if s["name"] == "recovery_decode"]
        finally:
            await _shutdown(mon, osds, stores, client)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(go(), 150))
    finally:
        loop.close()
    out["payloads"] = payloads
    return out


def test_a_healthy_lrc_pool_reads_back_what_it_wrote(lrc_cluster):
    assert lrc_cluster["healthy"] == lrc_cluster["payloads"]


def test_with_one_osd_down_and_in_every_read_is_equal_and_decodes_on_the_device(
        lrc_cluster):
    assert lrc_cluster["degraded"] == lrc_cluster["payloads"]
    assert lrc_cluster["degraded_counters"]["ec_read_host_decodes"] == 0
    rebuilt = [s["tags"] for s in lrc_cluster["decodes"]
               if s["tags"].get("path") not in (None, "none")]
    assert rebuilt and all(t["path"] == "device" and t["lost"]
                           and t["rebuilt_bytes"] > 0 for t in rebuilt)


def test_osd_out_recovery_leaves_every_shard_equal_to_the_reference(
        lrc_cluster):
    assert lrc_cluster["recovered"] == lrc_cluster["payloads"]
    for name, blob in lrc_cluster["payloads"].items():
        assert lrc_cluster["stored"][name] == \
            REF.expected_copies(POOL, blob), name


def test_a_lost_chunk_is_rebuilt_from_its_local_group_of_three(lrc_cluster):
    got = lrc_cluster["recovery"]
    lost = sum(1 for *_x, was_victim in lrc_cluster["moved"] if was_victim)
    assert lost and got["recovery_rebuilt_shards"] == lost
    assert got["recovery_local_repairs"] == lost
    assert got["recovery_read_bytes"] == 3 * got["recovery_rebuilt_bytes"]
    assert got["cold"] == 0     # the (1, 3) shape was warmed at map install
    tagged = [s["tags"] for s in lrc_cluster["spans"]
              if s["tags"].get("kind") == "local_repair"]
    assert len(tagged) == lost
    assert all(t["helper_bytes"] == 3 * t["rebuilt_bytes"] > 0
               and t["objects"] == 1 and "lost_node" in t for t in tagged)


def test_an_ec83_repair_reads_k_shards_and_rebuilds_the_same_bytes(tmp_path):
    """A scalar MDS code's whole-chunk repair reads minimum_to_decode's
    k shards, not every surviving one, and rebuilds each lost shard as
    the harness's reference of the pool says."""
    ref = _load("harness/reference")
    pool = {"type": "erasure", "k": 8, "m": 3, "stripe_unit": 4096}
    rng = np.random.default_rng(83)
    payloads = {f"ec{i}": rng.integers(0, 256, 8 * 4096 * (1 + i % 2),
                                       np.uint8).tobytes()
                for i in range(8)}
    got: dict = {}

    async def go():
        mon, osds, stores, client = await _boot(tmp_path, 12, _service())
        try:
            io = await _pool(client, osds, "ec83", {
                "plugin": "jax", "technique": "cauchy", "k": "8", "m": "3",
                "crush-failure-domain": "host"})
            for name, data in payloads.items():
                await io.write_full(name, data)
            got["recovery"], got["moved"] = await _out_and_recover(
                client, osds, io, 11, _acting(client, io, payloads))
            got["stored"] = {n: _stored(client, osds, io, n)
                             for n in payloads}
        finally:
            await _shutdown(mon, osds, stores, client)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(go(), 150))
    finally:
        loop.close()
    rec = got["recovery"]
    lost = sum(1 for *_x, was_victim in got["moved"] if was_victim)
    assert lost and rec["recovery_rebuilt_shards"] == lost
    assert rec["recovery_read_bytes"] == 8 * rec["recovery_rebuilt_bytes"]
    assert rec["recovery_local_repairs"] == 0
    # a (1, k) matrix a lost shard, as when it read every survivor: the
    # shape the daemons' warm-up compiled
    assert rec["cold"] == 0
    for name, blob in payloads.items():
        assert got["stored"][name] == ref.expected_copies(pool, blob), name
