"""A served read is one call of the store (PR 35):
``OSDDaemon._store_read`` asks ``store.read_object`` for the bytes and
the attrs, on the event loop; a BlockStore answers with one ``pread``
and the blob it verified, ``store_read_ops`` / ``store_read_bytes``
count it, and ``store_read`` is filed under the reader's span.
"""

from __future__ import annotations

import errno
import threading

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.osd.daemon import object_to_pg
from ceph_tpu.store.blockstore import BlockStore
from ceph_tpu.store.memstore import MemStore

from .test_disk_faults import _blockstore_factory
from .test_mini_cluster import Cluster, run

BIG = bytes(range(256)) * 128      # 32 KiB: shards of 16 KiB, one blob each
TINY = b"lives in the kv"     # a shard of one 4 KiB stripe unit: a piece
STORES = ["blockstore", "memstore"]


def _factory(kind, tmp_path):
    return _blockstore_factory(tmp_path) if kind == "blockstore" else None


def _counts(c) -> tuple:
    """(store_read_ops, store_read_bytes) summed over the OSDs (the perf
    collections outlive one test's daemons)."""
    dumps = [o.perf.dump() for o in c.osds]
    return tuple(sum(d.get(k, 0) for d in dumps)
                 for k in ("store_read_ops", "store_read_bytes"))


def _grew(c, before) -> tuple:
    return tuple(now - was for now, was in zip(_counts(c), before))


def _record_threads(monkeypatch, cls, name) -> list:
    seen, real = [], getattr(cls, name)

    def recording(self, *a, **kw):
        seen.append(threading.get_ident())
        return real(self, *a, **kw)

    monkeypatch.setattr(cls, name, recording)
    return seen


async def _ec_pool(c, name="ecrp"):
    await c.client.ec_profile_set(
        "rpp", {"plugin": "jax", "k": "2", "m": "1"})
    await c.client.pool_create(
        name, pg_num=4, pool_type="erasure", erasure_code_profile="rpp")
    return c.client.ioctx(name)


def _placement(c, io, oid):
    om = c.client.osdmap
    pool = om.get_pg_pool(io.pool_id)
    pg = object_to_pg(pool, oid)
    _u, _up, acting, primary = om.pg_to_up_acting_osds(pg)
    return pool, pg, acting, primary


class TestAServedRead:
    def test_ec_shard_reads_one_pread_each_under_a_span(
            self, tmp_path, monkeypatch):
        async def go():
            async with Cluster(
                n_osds=4, store_factory=_blockstore_factory(tmp_path)
            ) as c:
                io = await _ec_pool(c)
                await io.write_full("big", BIG)
                await io.write_full("tiny", TINY)
                loop_thread = threading.get_ident()
                blobs = _record_threads(monkeypatch, BlockStore, "_read_extent")
                before = _counts(c)
                assert await io.read("big") == BIG
                # k = 2 shards of 16 KiB, each one pread, on the loop
                assert blobs == [loop_thread] * 2
                assert _grew(c, before) == (2, len(BIG))

                # a sub-read over the wire, by itself: the remote OSD
                # says what its store's call took in a span
                pool, pg, acting, primary = _placement(c, io, "big")
                shard, peer = next((s, o) for s, o in enumerate(acting)
                                   if o != primary)
                osd = c.osds[primary]
                del blobs[:]
                with osd.tracer.span("test_reader") as sp, tracing.scope(sp):
                    data, attrs, eno = await osd._read_shard(
                        pool, pg, shard, peer, "big")
                assert eno == 0 and len(data) == len(BIG) // 2 and attrs
                assert len(blobs) == 1
                spans = [s for s in c.osds[peer].tracer.dump(limit=4096)
                         if s["name"] == "store_read"
                         and s["trace_id"] == sp.trace_id]
                assert len(spans) == 1
                tags = spans[0]["tags"]
                assert tags["stage"] == "store" and tags["copies"] == 0
                assert tags["bytes"] == len(data) and tags["read_ms"] > 0
                took = 1e3 * (spans[0]["end_mono"] - spans[0]["start_mono"])
                assert took == pytest.approx(tags["read_ms"], abs=0.5)
                # its parent is the primary's ec_sub_read round trip
                sub = [s for s in osd.tracer.dump(limit=4096)
                       if s["name"] == "ec_sub_read"
                       and s["trace_id"] == sp.trace_id]
                assert [s["span_id"] for s in sub] == [spans[0]["parent_id"]]

                # runs of one shard: one covering read, sliced (1) and
                # laid end to end (2)
                with osd.tracer.span("test_reader") as sp, tracing.scope(sp):
                    data, _a, eno = await osd._read_shard(
                        pool, pg, shard, peer, "big",
                        extents=[(8, 100), (4096, 50)])
                whole = (await osd._read_shard(pool, pg, shard, peer, "big"))[0]
                assert eno == 0 and bytes(data) == bytes(
                    whole[8:108]) + bytes(whole[4096:4146])
                runs = [s for s in c.osds[peer].tracer.dump(limit=4096)
                        if s["name"] == "store_read"
                        and s["trace_id"] == sp.trace_id]
                assert [s["tags"]["copies"] for s in runs] == [2]

                # an object of pieces alone: kv values, no pread at all
                # (the stores' own totals; the perf collections, which
                # outlive a test's daemons, grow by the same)
                def disk():
                    return (sum(o.store.stats["read_disk_bytes"]
                                for o in c.osds),
                            sum(o.perf.dump().get("store_read_disk_bytes", 0)
                                for o in c.osds))

                before, disk_before = _counts(c), disk()
                assert await io.read("tiny") == TINY
                assert _grew(c, before)[0] == 2 and disk() == disk_before
                assert await io.read("big") == BIG
                grew = [now - was for now, was in zip(disk(), disk_before)]
                assert grew == [len(BIG)] * 2

        run(go())

    def test_replicated_read_whole_and_ranged(self, tmp_path, monkeypatch):
        async def go():
            async with Cluster(
                n_osds=3, store_factory=_blockstore_factory(tmp_path)
            ) as c:
                await c.client.pool_create("rp", pg_num=4, size=2)
                io = c.client.ioctx("rp")
                await io.write_full("big", BIG)
                blobs = _record_threads(monkeypatch, BlockStore, "_read_extent")
                before = _counts(c)
                assert await io.read("big") == BIG
                assert await io.read("big", 100, 1000) == BIG[100:1100]
                assert len(blobs) == 2
                assert _grew(c, before) == (2, len(BIG) + 1000)
                _pool, _pg, _acting, primary = _placement(c, io, "big")
                reads = [s for s in c.osds[primary].tracer.dump(limit=4096)
                         if s["name"] == "store_read"]
                assert [s["tags"]["copies"] for s in reads[-2:]] == [0, 1]

        run(go())

    def test_memstore_reads_are_counted_too(self, monkeypatch):
        async def go():
            async with Cluster(n_osds=4) as c:
                io = await _ec_pool(c)
                await io.write_full("big", BIG)
                reads = _record_threads(monkeypatch, MemStore, "read")
                before = _counts(c)
                assert await io.read("big") == BIG
                assert len(reads) == 2
                assert _grew(c, before) == (2, len(BIG))

        run(go())


@pytest.mark.parametrize("kind", STORES)
def test_an_absent_shard_is_enoent_here_and_over_the_wire(tmp_path, kind):
    async def go():
        async with Cluster(
                n_osds=4, store_factory=_factory(kind, tmp_path)) as c:
            io = await _ec_pool(c)
            await io.write_full("big", BIG)
            pool, pg, acting, primary = _placement(c, io, "big")
            osd = c.osds[primary]
            before = _counts(c)
            for shard, holder in enumerate(acting):     # local leg and peers
                assert await osd._read_shard(
                    pool, pg, shard, holder, "no-such-object") == (
                        None, None, errno.ENOENT)
            assert _grew(c, before) == (0, 0)       # nothing was read
            assert osd._read_error_ledger == {}     # and nothing is damaged
            with pytest.raises(Exception) as ei:
                await io.read("no-such-object")
            assert getattr(ei.value, "errno", None) == errno.ENOENT

    run(go())
