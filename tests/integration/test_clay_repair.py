"""CLAY sub-chunk repair end-to-end: the bandwidth-optimal property
must show up ON THE WIRE, not just in minimum_to_decode's math.

Reference: ECCommon.cc:262-299 threads the per-shard (offset, count)
runs down to shard reads; ErasureCodeClay::repair_one_lost_chunk
(ErasureCodeClay.cc:462) consumes them.  Here one OSD loses a single
object's shard (store corruption) and restarts; the recovery pass
regenerates exactly that shard — run twice (sub-chunk reads enabled
and disabled), the helpers' served-byte counters must show the
regenerating read moving ~d/q chunk-equivalents instead of k+.
"""

from __future__ import annotations

import asyncio
import random

import pytest

from ceph_tpu.osd.daemon import OSDDaemon, object_to_pg
from ceph_tpu.store import coll_t, ghobject_t

from .test_mini_cluster import Cluster, run

K, M, D = 4, 2, 5  # q=2, t=3, sub_chunk_no=8; repair reads 1/2 per helper
OBJ_SIZE = 3 * 65536


async def _run_repair(c: Cluster, disable_subchunk: bool) -> int:
    """Drop one shard of one object from a peer's store, restart the
    peer, wait for regeneration; returns helper bytes served."""
    for o in c.osds:
        o.disable_subchunk_repair = disable_subchunk
    await c.client.ec_profile_set("clayprof", {
        "plugin": "clay", "k": str(K), "m": str(M), "d": str(D),
        "scalar_mds": "jax", "crush-failure-domain": "host",
    })
    await c.client.pool_create(
        "claypool", pg_num=4, pool_type="erasure",
        erasure_code_profile="clayprof",
    )
    io = c.client.ioctx("claypool")
    rng = random.Random(77)
    payload = rng.randbytes(OBJ_SIZE)
    await io.write_full("c0", payload)

    om = c.client.osdmap
    pool = om.get_pg_pool(io.pool_id)
    pg = object_to_pg(pool, "c0")
    _, _, acting, primary = om.pg_to_up_acting_osds(pg)
    shard, victim = next(
        (s, o) for s, o in enumerate(acting) if o != primary
    )

    def sub_read_bytes() -> int:
        return int(sum(
            o.perf.dump().get("subop_read_bytes", 0)
            for o in c.osds if o is not None
        ))

    # drop the shard from the victim's store, then restart the daemon:
    # the re-peer pass finds it missing and regenerates it in place
    daemon = c.osds[victim]
    store = daemon.store
    await daemon.stop()
    coll = daemon._shard_coll(pool, pool.raw_pg_to_pg(pg), shard)
    obj = ghobject_t("c0", shard=shard)
    assert store.exists(coll, obj), "victim does not hold the shard"
    shard_len = store.stat(coll, obj)
    from ceph_tpu.osd.pglog import PGMETA_OID
    from ceph_tpu.store import Transaction

    t = Transaction()
    t.remove(coll, obj)
    # drop the shard's pg log too: peering then sees the member behind
    # (log delta names c0) and reconciles it — data loss with an intact
    # log is scrub territory, not peering's
    meta = ghobject_t(PGMETA_OID, shard=shard)
    if store.exists(coll, meta):
        t.remove(coll, meta)
    store.queue_transaction(t)

    before = sub_read_bytes()
    c.osds[victim] = OSDDaemon(victim, c.mon.addr, store=store)
    for o in c.osds:
        o.disable_subchunk_repair = disable_subchunk
    await c.osds[victim].start()
    deadline = asyncio.get_running_loop().time() + 30
    while not store.exists(coll, obj):
        assert asyncio.get_running_loop().time() < deadline, "no repair"
        await asyncio.sleep(0.2)
    await asyncio.sleep(0.5)  # let trailing recovery I/O settle
    assert await io.read("c0") == payload
    # read() itself fans out ranged reads; subtract by sampling before
    delta = sub_read_bytes() - before
    return delta, shard_len


def _recovery_counters(c: Cluster) -> dict:
    keys = ("recovery_subchunk_repairs", "recovery_fullchunk_repairs",
            "recovery_read_bytes", "recovery_rebuilt_bytes",
            "recovery_decode_bytes")
    return {k: sum(o.perf.dump().get(k, 0) for o in c.osds
                   if o is not None) for k in keys}


class TestClaySubChunkRepair:
    def test_repair_reads_subchunk_fraction(self):
        async def go():
            # counters of one process outlive a cluster: take growth
            async with Cluster(n_osds=K + M + 2) as c:
                was = _recovery_counters(c)
                full_delta, shard_len = await _run_repair(
                    c, disable_subchunk=True)
                full = {k: v - was[k]
                        for k, v in _recovery_counters(c).items()}
            async with Cluster(n_osds=K + M + 2) as c:
                was = _recovery_counters(c)
                sub_delta, _ = await _run_repair(c, disable_subchunk=False)
                sub = {k: v - was[k]
                       for k, v in _recovery_counters(c).items()}
            # regenerating read: d helpers x 1/q each = 2.5 chunks;
            # full reconstruction reads every consistent source (5).
            # The final client read adds the same k-chunk fan-out to
            # both runs.
            assert sub_delta < 0.75 * full_delta, (
                sub_delta, full_delta, shard_len,
            )
            # the fallback to whole chunks is never silent: each repair
            # of a code with sub-chunks counts as one or the other
            assert full["recovery_fullchunk_repairs"] >= 1, full
            assert full["recovery_subchunk_repairs"] == 0, full
            assert sub["recovery_subchunk_repairs"] >= 1, sub
            assert sub["recovery_fullchunk_repairs"] == 0, sub
            # d helpers x 1/q of a chunk each, local reads counted too
            assert sub["recovery_read_bytes"] == \
                D / (D - K + 1) * sub["recovery_rebuilt_bytes"], sub
            assert full["recovery_read_bytes"] >= \
                K * full["recovery_rebuilt_bytes"], full
            # nothing moved here: every byte handed over was rebuilt
            assert sub["recovery_decode_bytes"] == \
                sub["recovery_rebuilt_bytes"], sub

        run(go())

    def test_repaired_shard_bit_exact(self):
        async def go():
            async with Cluster(n_osds=K + M + 2) as c:
                await _run_repair(c, disable_subchunk=False)
                import json

                pool_id = c.client.osdmap.lookup_pg_pool_name("claypool")
                pool = c.client.osdmap.get_pg_pool(pool_id)
                for ps in range(pool.pg_num):
                    code, rs, data = await c.client.command({
                        "prefix": "pg deep-scrub",
                        "pgid": f"{pool_id}.{ps}",
                    })
                    assert code == 0, (rs, data)
                    rep = json.loads(data)
                    assert rep["inconsistencies"] == [], rep

        run(go())


PROFILES = {
    "scalar": {"plugin": "jax", "k": str(K), "m": str(M)},
    "clay": {"plugin": "clay", "k": str(K), "m": str(M), "d": str(D),
             "scalar_mds": "jax"},
}


@pytest.mark.parametrize("code", sorted(PROFILES))
def test_a_shard_that_only_moved_is_passed_on_whatever_the_code(code):
    """Marking an OSD out of k+m+1 hosts moves a second, live position
    of some PGs.  One rule for every code: the shard its old holder
    still has is read there and handed on; only the lost one is rebuilt
    (CLAY: from sub-chunk reads, though the object has two targets)."""
    async def go():
        async with Cluster(n_osds=K + M + 1) as c:
            await c.client.ec_profile_set("p", {
                **PROFILES[code], "crush-failure-domain": "host"})
            await c.client.pool_create(
                "movepool", pg_num=8, pool_type="erasure",
                erasure_code_profile="p")
            io = c.client.ioctx("movepool")
            rng = random.Random(5)
            names = [f"m{i}" for i in range(16)]
            payload = {n: rng.randbytes(OBJ_SIZE) for n in names}
            for n in names:
                await io.write_full(n, payload[n])
            om = c.client.osdmap
            pool = om.get_pg_pool(io.pool_id)
            before = {n: om.pg_to_up_acting_osds(
                object_to_pg(pool, n))[2] for n in names}
            victim = K + M
            epoch = om.epoch
            await c.osds[victim].stop()
            c.osds[victim] = None
            # counters of one process outlive a cluster: take growth,
            # over the OSDs that stay
            was = _recovery_counters(c)
            await c.client.command({"prefix": "osd down", "id": str(victim)})
            await c.client.command({"prefix": "osd out", "id": str(victim)})
            await c.wait_epoch(epoch + 2)
            om = c.client.osdmap
            lost = moved = 0
            want = []
            for n in names:
                pg = object_to_pg(pool, n)
                after = om.pg_to_up_acting_osds(pg)[2]
                for s, (o0, o1) in enumerate(zip(before[n], after)):
                    if o0 != o1:
                        lost += o0 == victim
                        moved += o0 != victim
                        want.append((c.osds[o1].store, coll_t(
                            pool.id, pool.raw_pg_to_pg(pg).ps, s),
                            ghobject_t(n, shard=s)))
            assert lost and moved, (lost, moved)
            deadline = asyncio.get_running_loop().time() + 60
            while not all(st.exists(cl, o) for st, cl, o in want):
                assert asyncio.get_running_loop().time() < deadline, \
                    "no recovery"
                await asyncio.sleep(0.2)
            await asyncio.sleep(0.5)  # the last push's counters
            for n in names:
                assert await io.read(n) == payload[n]
            got = {k: v - was[k] for k, v in _recovery_counters(c).items()}
            shard_len = want[0][0].stat(want[0][1], want[0][2])
            assert got["recovery_rebuilt_bytes"] == lost * shard_len, got
            assert got["recovery_decode_bytes"] == \
                (lost + moved) * shard_len, got
            if code == "clay":
                assert got["recovery_fullchunk_repairs"] == 0, got
                assert got["recovery_subchunk_repairs"] == lost, got
                assert got["recovery_read_bytes"] == \
                    D / (D - K + 1) * got["recovery_rebuilt_bytes"], got
            else:
                assert got["recovery_read_bytes"] >= \
                    K * got["recovery_rebuilt_bytes"], got

    run(go())
