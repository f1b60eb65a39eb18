"""A 4 MiB object through both pool types, end to end in one process:
the payload rides the frames' data segments (no large blob goes
through denc's copies), every stored copy equals the host reference,
and a store that keeps what it is lent holds its own bytes."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.msg.messages import OP_WRITE_FULL
from ceph_tpu.ops.gf256 import gf_matmul
from ceph_tpu.osd.daemon import object_to_pg
from ceph_tpu.store import coll_t, ghobject_t
from tests.integration.test_mini_cluster import Cluster, run

MiB = 1 << 20
K, M = 2, 1
STRIPE_UNIT = 4096


def _payload(seed: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, 4 * MiB, dtype=np.uint8).tobytes()


def _blockstores(tmp_path):
    from ceph_tpu.store.blockstore import BlockStore

    def factory(i):
        s = BlockStore(str(tmp_path / f"osd{i}"))
        s.mount()
        return s
    return factory


def _wire(c: Cluster) -> dict:
    """The data-segment counters: summed over the OSDs' messengers,
    and the copies counted by anyone's (client and mon too)."""
    osds = [o.messenger.stats for o in c.osds]
    everyone = osds + [c.client.messenger.stats, c.mon.messenger.stats]
    return {
        "osd_data_bytes_in": sum(s["data_bytes_in"] for s in osds),
        "osd_data_segs_in": sum(s["data_segs_in"] for s in osds),
        "copied": sum(s["blob_copied_bytes"] for s in everyone),
    }


def _reference_copies(c: Cluster, pool, blob: bytes) -> list[bytes]:
    """What position 0..n-1 of the acting set must hold, computed on
    the host: the k data shards (chunk i of every stripe) and the
    coding matrix applied to them; or the object itself, size times."""
    if not pool.is_erasure():
        return [blob] * pool.size
    ec = c.osds[0]._ec_for(pool)
    data = np.frombuffer(blob, np.uint8).reshape(
        -1, K, STRIPE_UNIT).transpose(1, 0, 2).reshape(K, -1)
    rows = np.concatenate([data, gf_matmul(ec.coding_matrix, data)])
    return [rows[ec.chunk_index(i)].tobytes() for i in range(K + M)]


def _stored_copies(c: Cluster, pool, oid: str) -> list[bytes]:
    pg = pool.raw_pg_to_pg(object_to_pg(pool, oid))
    acting = c.client.osdmap.pg_to_up_acting_osds(pg, folded=True)[2]
    out = []
    for pos, osd in enumerate(acting):
        shard = pos if pool.is_erasure() else -1
        out.append(bytes(c.osds[osd].store.read(
            coll_t(pg.pool, pg.ps, shard), ghobject_t(oid, shard=shard))))
    return out


def _assert_moved(before: dict, after: dict, expect_mib: int) -> None:
    """The write's payload, and beside it at most the 1-byte probe
    replies of a recovery pass that happens to run meanwhile."""
    moved = after["osd_data_bytes_in"] - before["osd_data_bytes_in"]
    assert expect_mib * MiB <= moved < expect_mib * MiB + 4096, moved


async def _pools(c: Cluster):
    await c.client.ec_profile_set("p", {
        "plugin": "jax", "k": str(K), "m": str(M),
        "crush-failure-domain": "host"})
    await c.client.pool_create(
        "ecp", pg_num=4, pool_type="erasure", erasure_code_profile="p")
    await c.client.pool_create("rep", pg_num=4, size=3)
    # one small write each, so every connection the large one uses is up
    for name in ("ecp", "rep"):
        await c.client.ioctx(name).write_full("warm", b"w" * 8192)
    await c.client.wait_clean(timeout=60)


# (pool, MiB the OSDs receive in data segments for one 4 MiB write:
# the client's 4 and the primary's fan-out to the other members)
CASES = [("ecp", 4 + 4 * (K + M - 1) // K), ("rep", 4 + 2 * 4)]


class TestPayloadByReference:
    @pytest.mark.parametrize("pool_name,expect_mib", CASES)
    def test_blockstore_4MiB_uncopied_and_equal_to_reference(
            self, tmp_path, pool_name, expect_mib):
        async def go():
            async with Cluster(
                    n_osds=4, store_factory=_blockstores(tmp_path)) as c:
                await _pools(c)
                io = c.client.ioctx(pool_name)
                pool = c.client.osdmap.get_pg_pool(io.pool_id)
                blob = _payload(7)
                before = _wire(c)
                await io.write_full("big", blob)
                after = _wire(c)
                assert after["copied"] == 0
                _assert_moved(before, after, expect_mib)
                assert _stored_copies(c, pool, "big") == \
                    _reference_copies(c, pool, blob)
                got = await io.read("big")
                assert type(got) is bytes and got == blob
                assert _wire(c)["copied"] == 0
                assert c.client.messenger.stats["data_bytes_in"] >= 4 * MiB
                for osd in c.osds:
                    dump = {**osd.messenger.perf_dump()}
                    assert dump["msgr_blob_copied_bytes"] == 0
                    assert "msgr_data_segs_in" in dump

        run(go())

    @pytest.mark.parametrize("pool_name,expect_mib", CASES)
    def test_memstore_keeps_its_own_copy(self, pool_name, expect_mib):
        """MemStore keeps the bytes it is lent, so it copies them as it
        applies the op: what the sender does to its buffer after the
        ack, and the frame buffers going away, change nothing."""
        async def go():
            async with Cluster(n_osds=4) as c:
                await _pools(c)
                io = c.client.ioctx(pool_name)
                pool = c.client.osdmap.get_pg_pool(io.pool_id)
                buf = bytearray(_payload(11))
                blob = bytes(buf)
                before = _wire(c)
                # the raw op, so the client's own bytes() of the
                # argument does not stand between buffer and wire
                reply = await c.client._submit(io.pool_id, io._msg(
                    "big", op=OP_WRITE_FULL, data=memoryview(buf)))
                assert reply.result == 0
                buf[:] = bytes(len(buf))
                after = _wire(c)
                assert after["copied"] == 0
                _assert_moved(before, after, expect_mib)
                assert _stored_copies(c, pool, "big") == \
                    _reference_copies(c, pool, blob)
                assert await io.read("big") == blob

        run(go())
