"""Writes to new object names map each PG once an epoch, not each name.

The client and the primary both ask the OSDMap where a raw pg lives;
the map memoizes the answer under the PG's folds, so a burst of new
names runs the placement pipeline at most once per PG an OSD maps.  An
OSD's ``perf dump`` counts memo lookups and pipeline runs over every map
it has held.  Each object is read back, and its stored copies sit on the
OSDs that a fresh map's pipeline names for it.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from ceph_tpu.common import admin_command
from ceph_tpu.osd.mapenc import decode_osdmap, encode_osdmap
from ceph_tpu.osd.pgutil import object_to_pg
from ceph_tpu.store import coll_t, ghobject_t

from .test_mini_cluster import Cluster, run

N_OSDS = 6
PG_NUM = 8
N_OBJECTS = 200


async def _perf(path: str) -> tuple[float, float]:
    d = await admin_command(path, "perf dump")
    return d["pg_mapping_lookups"], d["pg_mapping_runs"]


async def _perfs(paths) -> list[tuple[float, float]]:
    return [await _perf(p) for p in paths]


async def _settle(c, epoch: int) -> None:
    for _ in range(200):
        if all(o.epoch >= epoch for o in c.osds):
            return
        await asyncio.sleep(0.05)
    raise AssertionError([o.epoch for o in c.osds])


def _holders(c, pool, name: str) -> dict[int, int]:
    """{osd: shard} of every OSD whose store holds ``name``."""
    out = {}
    for o in c.osds:
        for pg in range(pool.pg_num):
            for shard in (range(pool.size) if pool.is_erasure() else [-1]):
                try:
                    o.store.stat(coll_t(pool.id, pg, shard),
                                 ghobject_t(name, shard=shard))
                except FileNotFoundError:
                    continue
                out[o.id] = shard
    return out


@pytest.mark.parametrize("kind", ["replicated", "erasure"])
def test_new_names_map_each_pg_once_an_epoch(kind, tmp_path):
    async def go():
        sock = str(tmp_path) + "/osd.$id.asok"
        paths = [str(tmp_path) + f"/osd.{i}.asok" for i in range(N_OSDS)]
        async with Cluster(n_osds=N_OSDS,
                           osd_conf={"admin_socket": sock}) as c:
            if kind == "erasure":
                await c.client.ec_profile_set(
                    "p", {"plugin": "jax", "k": "2", "m": "1"})
                await c.client.pool_create(
                    "t", pg_num=PG_NUM, pool_type="erasure",
                    erasure_code_profile="p")
            else:
                await c.client.pool_create("t", pg_num=PG_NUM, size=3)
            await c.client.wait_clean(timeout=60)
            await _settle(c, c.client.osdmap.epoch)
            io = c.client.ioctx("t")
            rng = np.random.default_rng(43)
            blobs = {f"new-{kind}-{i}": rng.integers(
                0, 256, int(rng.integers(1, 5000)), dtype=np.uint8).tobytes()
                for i in range(N_OBJECTS)}
            epochs = [o.epoch for o in c.osds]
            before = await _perfs(paths)
            for name, blob in blobs.items():
                await io.write_full(name, blob)
            after = await _perfs(paths)
            assert [o.epoch for o in c.osds] == epochs
            runs = [a[1] - b[1] for a, b in zip(after, before)]
            lookups = sum(a[0] - b[0] for a, b in zip(after, before))
            assert lookups >= N_OBJECTS     # every primary asked
            assert all(r <= PG_NUM for r in runs), runs
            assert sum(runs) < N_OBJECTS / 4, runs

            for name, blob in blobs.items():
                assert await io.read(name) == blob, name
            fresh = decode_osdmap(encode_osdmap(c.mon.osdmap))
            pool = fresh.get_pg_pool(io.pool_id)
            for name in blobs:
                fresh.invalidate_mapping_cache()
                _u, _p, acting, _ap = fresh.pg_to_up_acting_osds(
                    object_to_pg(pool, name))
                want = ({o: i for i, o in enumerate(acting)}
                        if pool.is_erasure() else dict.fromkeys(acting, -1))
                assert _holders(c, pool, name) == want, name

            # a new epoch: the counts carry over; the new map runs anew
            epoch = c.client.osdmap.epoch
            await c.client.pool_create("other", pg_num=4, size=3)
            await _settle(c, epoch + 1)
            assert all(o.osdmap.mapping_perf is o.perf for o in c.osds)
            carried = await _perfs(paths)
            assert all(n >= a for n, a in zip(carried, after))
            for i in range(20):
                await io.write_full(f"later-{kind}-{i}", b"z" * 100)
            later = await _perfs(paths)
            runs = [n[1] - a[1] for n, a in zip(later, after)]
            assert 0 < sum(runs) and all(r <= PG_NUM + 4 for r in runs), runs
            for name in blobs:
                assert await io.read(name) == blobs[name], name

    run(go())
