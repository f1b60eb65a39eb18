"""Backfill reservations + async-recovery budgeting (reference
src/common/AsyncReserver.h, MBackfillReserve handshake,
doc/dev/osd_internals/backfill_reservation.rst): concurrent PG
recoveries per OSD stay bounded by osd_max_backfills on BOTH sides of
the wire, and client I/O keeps flowing while recovery runs."""

import asyncio

import numpy as np

from ceph_tpu.osd.types import pg_t
from tests.integration.test_mini_cluster import Cluster, run


async def _total_remap(c, io, n_osds: int) -> None:
    """Move every PG of the pool to a disjoint acting set so every PG
    needs a full backfill at once — maximal reservation pressure."""
    om = c.client.osdmap
    pool = om.get_pg_pool(io.pool_id)
    epoch0 = om.epoch
    for ps in range(pool.pg_num):
        _, _, acting, _ = om.pg_to_up_acting_osds(
            pg_t(io.pool_id, ps), folded=True)
        spare = [o for o in range(n_osds) if o not in acting]
        pairs = " ".join(
            f"{frm} {to}" for frm, to in zip(acting, spare))
        code, rs, _ = await c.client.command({
            "prefix": "osd pg-upmap-items",
            "pgid": f"{io.pool_id}.{ps}",
            "pairs": pairs})
        assert code == 0, rs
    await c.wait_epoch(epoch0 + 1)


def _queued(c) -> float:
    return sum(o.perf.dump().get("backfill_reserve_queued", 0)
               for o in c.osds)


class TestBackfillReservation:
    def test_concurrent_backfills_bounded(self):
        async def go():
            async with Cluster(n_osds=6, osd_conf={
                "osd_max_backfills": 1,
                # slow each reconciliation slightly so PG recoveries
                # genuinely overlap in time and must queue
                "osd_recovery_sleep": 0.01,
                "osd_backfill_retry_interval": 0.05,
            }) as c:
                await c.client.pool_create("bf", pg_num=8, size=2)
                io = c.client.ioctx("bf")
                for i in range(24):
                    await io.write_full(
                        f"o{i}",
                        np.random.default_rng(i).integers(
                            0, 256, 8192, dtype=np.uint8).tobytes())
                await c.client.wait_clean(timeout=30)
                # perf collections outlive a test's daemons: growth
                queued0 = _queued(c)
                await _total_remap(c, io, 6)
                await c.client.wait_clean(timeout=90)
                peaks_l = [o.recovery_stats["peak_local"] for o in c.osds]
                peaks_r = [o.recovery_stats["peak_remote"] for o in c.osds]
                recovered = sum(
                    o.recovery_stats["pgs_recovered"] for o in c.osds)
                # every granted reservation respected the cap
                assert max(peaks_l) <= 1, peaks_l
                assert max(peaks_r) <= 1, peaks_r
                assert recovered >= 8, recovered
                # 8 PGs re-homing through 1-slot reservers MUST have
                # contended somewhere: a busy peer queued the ask, and
                # no store is backfillfull, so nothing was rejected
                assert _queued(c) > queued0
                rejects = sum(
                    o.recovery_stats["reservation_rejects"]
                    for o in c.osds)
                assert rejects == 0
                for i in range(24):
                    data = np.random.default_rng(i).integers(
                        0, 256, 8192, dtype=np.uint8).tobytes()
                    assert await io.read(f"o{i}") == data, f"o{i}"

        run(go())

    def test_client_io_not_starved_during_recovery(self):
        async def go():
            async with Cluster(n_osds=6, osd_conf={
                "osd_max_backfills": 1,
                "osd_recovery_sleep": 0.05,  # recovery deliberately slow
                "osd_backfill_retry_interval": 0.05,
            }) as c:
                await c.client.pool_create("live", pg_num=8, size=2)
                io = c.client.ioctx("live")
                for i in range(32):
                    await io.write_full(f"o{i}", b"x" * 4096)
                await c.client.wait_clean(timeout=30)
                await _total_remap(c, io, 6)
                # recovery is now in progress (32 objects * 50ms sleep
                # through 1-slot reservers takes seconds); client ops
                # must complete promptly anyway
                lat = []
                loop = asyncio.get_running_loop()
                for i in range(10):
                    t0 = loop.time()
                    await io.write_full(f"live{i}", b"y" * 2048)
                    assert await io.read(f"live{i}") == b"y" * 2048
                    lat.append(loop.time() - t0)
                # some OSD must still be recovering, or this proved
                # nothing (sleep budget: 32 objs x 50ms >> test I/O)
                assert any(
                    o.recovery_stats["pgs_recovered"] < 8 for o in c.osds
                ) or any(o._recovering_pgs for o in c.osds)
                assert max(lat) < 5.0, lat
                await c.client.wait_clean(timeout=90)

        run(go())

    def test_runtime_max_backfills_change(self):
        async def go():
            # default osd_max_backfills=1; no cmdline override (that
            # layer would outrank the mon's central value)
            async with Cluster(n_osds=4) as c:
                # central config raises the cap; live reservers follow
                code, _rs, _ = await c.client.command({
                    "prefix": "config set", "who": "osd",
                    "name": "osd_max_backfills", "value": "3"})
                assert code == 0
                for _ in range(100):
                    if all(
                        o.local_reserver.max_allowed == 3 and
                        o.remote_reserver.max_allowed == 3
                        for o in c.osds
                    ):
                        break
                    await asyncio.sleep(0.1)
                assert all(
                    o.local_reserver.max_allowed == 3 for o in c.osds)

        run(go())
