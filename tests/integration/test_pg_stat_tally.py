"""A PG's objects and bytes, as the OSD reports them, against a plain
scan of its store after every step of a mixed sequence; and what the
report costs when nothing, or little, changed.

The report (``OSDDaemon._collect_pg_stats``) keeps tallies from what the
OSD's transactions change (``osd/pgstats.py``).  The reference here is
the plain scan: for each PG an OSD leads, the distinct object names of
its collection but the PG's meta object, with the size of each head
(0 where only clones remain), times k on an EC pool.
"""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from ceph_tpu.osd.daemon import OSDDaemon
from ceph_tpu.osd.pglog import PGMETA_OID
from ceph_tpu.osd.pgutil import object_to_pg
from ceph_tpu.osd.types import pg_t
from ceph_tpu.store import coll_t, ghobject_t

from .test_mini_cluster import Cluster, run

N_OSDS = 6


def _blockstores(tmp_path):
    from ceph_tpu.store.blockstore import BlockStore

    def factory(i):
        s = BlockStore(str(tmp_path / f"osd{i}"))
        s.mount()
        return s
    return factory


def _scan(osd) -> dict[str, tuple[int, int]]:
    """{pgid: (objects, bytes)} of the PGs ``osd`` leads, from
    ``collection_list`` and ``stat`` alone."""
    om = osd.osdmap
    out = {}
    for pid, pool in om.pools.items():
        k = 1
        if pool.is_erasure():
            k = int(om.erasure_code_profiles[pool.erasure_code_profile]["k"])
        for ps in range(pool.pg_num):
            _u, _p, acting, primary = om.pg_to_up_acting_osds(
                pg_t(pid, ps), folded=True)
            if primary != osd.id:
                continue
            shard = acting.index(osd.id) if pool.is_erasure() else -1
            c = coll_t(pid, ps, shard)
            names = set()
            if osd.store.collection_exists(c):
                names = {o.name for o in osd.store.collection_list(c)}
                names.discard(PGMETA_OID)
            nbytes = 0
            for name in names:
                try:
                    nbytes += osd.store.stat(c, ghobject_t(name, shard=shard))
                except FileNotFoundError:
                    pass
            out[f"{pid}.{ps}"] = (len(names), nbytes * k)
    return out


def _reported(osd) -> dict[str, tuple[int, int]]:
    return {pgid: (st["objects"], st["bytes"])
            for pgid, st in json.loads(osd._collect_pg_stats()).items()}


def _live(c):
    return [o for o in c.osds if o is not None and not o.stopping]


async def _assert_exact(c, step: str) -> None:
    """Every live OSD's report equals its scan, and so do the mon's
    ``pg stat`` book and ``df`` once the beacons have carried it.  A
    commit still on a worker thread may land between report and scan:
    the two are compared again until they agree, for a few seconds."""
    for _ in range(50):
        bad = {o.id: (got, want) for o in _live(c)
               if (got := _reported(o)) != (want := _scan(o))}
        if not bad:
            break
        await asyncio.sleep(0.1)
    assert not bad, (step, bad)
    want = {}
    for o in _live(c):
        want.update(_scan(o))
    pool_names = c.client.osdmap.pool_names
    per_pool: dict[str, list[int]] = {}
    for pgid, (n, b) in want.items():
        tot = per_pool.setdefault(pool_names[int(pgid.split(".")[0])], [0, 0])
        tot[0] += n
        tot[1] += b
    for _ in range(100):
        _c, _r, out = await c.client.command({"prefix": "pg stat"})
        book = json.loads(out)["pg_stats"]
        _c, _r, out = await c.client.command({"prefix": "df"})
        df = {name: [p["objects"], p["bytes_used"]]
              for name, p in json.loads(out)["pools"].items()}
        got = {pgid: (book[pgid]["objects"], book[pgid]["bytes"])
               for pgid in want if pgid in book}
        if got == want and df == per_pool:
            return
        await asyncio.sleep(0.1)
    assert got == want and df == per_pool, (step, got, want, df, per_pool)


def _blob(rng, n: int) -> bytes:
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


async def _restart(c, i: int) -> None:
    store = c.osds[i].store
    await c.osds[i].stop()
    epoch = c.client.osdmap.epoch
    await c.client.command({"prefix": "osd down", "id": str(i)})
    await c.wait_epoch(epoch + 1)
    c.osds[i] = OSDDaemon(i, c.mon.addr, store=store)
    await c.osds[i].start()


@pytest.mark.parametrize("store", ["memstore", "blockstore"])
@pytest.mark.parametrize("kind", ["replicated", "erasure"])
def test_reported_usage_equals_a_plain_scan_after_every_step(
        kind, store, tmp_path):
    async def go():
        factory = _blockstores(tmp_path) if store == "blockstore" else None
        async with Cluster(n_osds=N_OSDS, store_factory=factory) as c:
            if kind == "erasure":
                await c.client.ec_profile_set(
                    "p", {"plugin": "jax", "k": "3", "m": "2"})
                await c.client.pool_create(
                    "t", pg_num=4, pool_type="erasure",
                    erasure_code_profile="p")
            else:
                await c.client.pool_create("t", pg_num=4, size=3)
            io = c.client.ioctx("t")
            rng = np.random.default_rng(41)
            names = [f"o{i}" for i in range(12)]
            for name in names:
                await io.write_full(name, _blob(rng, int(rng.integers(1, 30_000))))
            await c.client.wait_clean(timeout=60)
            await _assert_exact(c, "write_full")

            await io.write(names[0], _blob(rng, 5000), 7000)   # EC: RMW
            await io.write(names[1], _blob(rng, 100), 40_000)  # grows
            await _assert_exact(c, "partial overwrite")

            await io.truncate(names[2], 1234)
            await io.truncate(names[3], 0)
            await _assert_exact(c, "truncate")

            await io.remove(names[4])
            await _assert_exact(c, "delete")

            snap = await io.selfmanaged_snap_create()
            io.set_snap_context(snap, [snap])
            await io.write_full(names[5], _blob(rng, 3000))    # clone
            await io.remove(names[6])                           # whiteout
            await io.remove(names[5])
            await _assert_exact(c, "snapshot clone, head deleted")

            # the primary of names[0]'s PG goes out: a new primary
            # takes the PG and recovery pushes onto the new acting set
            om = c.client.osdmap
            pool = om.get_pg_pool(io.pool_id)
            _u, _p, _a, primary = om.pg_to_up_acting_osds(
                object_to_pg(pool, names[0]))
            await c.client.command({"prefix": "osd out", "id": str(primary)})
            await c.client.wait_clean(timeout=90)
            await _assert_exact(c, "osd out, recovery")
            await c.client.command({"prefix": "osd in", "id": str(primary)})
            await c.client.wait_clean(timeout=90)
            await _assert_exact(c, "osd in, recovery")

            code, rs, _ = await c.client.command({
                "prefix": "osd pool set", "pool": "t",
                "var": "pg_num", "val": "8"})
            assert code == 0, rs
            await c.client.wait_clean(timeout=90)
            await _assert_exact(c, "pg split")

            await _restart(c, primary)
            await c.client.wait_clean(timeout=90)
            await _assert_exact(c, "osd restart")
            for name in names[7:]:
                await io.write_full(name, _blob(rng, 2222))
            await _assert_exact(c, "overwrite after restart")

    run(go())


def _counts(osds) -> tuple[float, float]:
    d = [o.perf.dump() for o in osds]
    return (sum(x["pg_stat_objects_stated"] for x in d),
            sum(x["pg_stat_collections_listed"] for x in d))


def test_a_report_stats_only_what_changed():
    """Two reports with no write between: the second stats and lists
    nothing.  After k writes to one PG, the reports that follow stat at
    most k objects, and list nothing."""
    async def go():
        async with Cluster(n_osds=N_OSDS) as c:
            await c.client.pool_create("t", pg_num=4, size=3)
            io = c.client.ioctx("t")
            for i in range(20):
                await io.write_full(f"pre{i}", b"x" * 100)
            await c.client.wait_clean(timeout=60)
            osds = _live(c)
            for o in osds:
                o._collect_pg_stats()
            before = _counts(osds)
            for o in osds:
                o._collect_pg_stats()
            assert _counts(osds) == before

            pool = c.client.osdmap.get_pg_pool(io.pool_id)

            def ps(name):
                return pool.raw_pg_to_pg(object_to_pg(pool, name)).ps
            names = [n for n in (f"new{i}" for i in range(200))
                     if ps(n) == ps("pre0")][:5]
            assert len(names) == 5
            before = _counts(osds)
            for n in names:
                await io.write_full(n, b"y" * 300)
            for o in osds:
                o._collect_pg_stats()
            stated, listed = (a - b for a, b in zip(_counts(osds), before))
            assert 0 < stated <= len(names)
            assert listed == 0
            await _assert_exact(c, "after k writes")

    run(go())
