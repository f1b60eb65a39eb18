"""One deployment in this process: 1 mon + n OSDs on BlockStore and a
client, the pool built from the configuration file: ``pool`` gives type,
size or plugin, k, m, failure domain, PGs, and for an EC pool optionally
``technique`` and ``profile``, a dict of strings that goes into the
erasure-code profile as it is (``d``, ``scalar_mds``, ``l``, ...).
``reference`` names the file that says what its stored copies must be
(``harness/verify.py``).

A copy of ``chip_smoke.SmokeCluster`` (PR 21), kept here so that later
PRs can change the program's smoke without moving the yardstick.  The
daemons share ``ceph_tpu/parallel/*.shared()``, the ``tools/vstart.py``
mapping of a cluster onto one accelerator host.
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np

POOL = "bench"
CLIENT_ID = 2424


class Cluster:
    #: vstart's clocks: 1 s beacons, 4 beacons of grace at the mon
    BEACON = 1.0

    def __init__(self, config: dict, data_dir: str, *, op_timeout: float,
                 encode_service=None):
        self.pool = config["pool"]
        self.reference = config.get("reference")
        self.n_osds = int(config["osds"])
        self.data_dir = data_dir
        self.op_timeout = op_timeout
        self._injected_service = encode_service
        self.mon = None
        self.osds: list = []
        self.stores: list = []
        self.client = None
        self.io = None
        self.ec = None      # the program's plugin, used to warm shapes only

    @property
    def erasure(self) -> bool:
        return self.pool["type"] == "erasure"

    async def __aenter__(self) -> "Cluster":
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
        self.client = RadosClient(client_id=CLIENT_ID,
                                  op_timeout=self.op_timeout)
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

    def live_osds(self) -> list:
        return [o for o in self.osds if o is not None]

    def tracers(self) -> list:
        from ceph_tpu.common.tracing import device_tracer

        return ([o.tracer for o in self.live_osds()]
                + [self.client.tracer, device_tracer()])

    async def create_pool(self) -> None:
        p = self.pool
        if self.erasure:
            from ceph_tpu.ec import registry

            profile = {"plugin": p["plugin"], "k": str(p["k"]),
                       "m": str(p["m"]),
                       "crush-failure-domain": p["failure_domain"],
                       **p.get("profile", {})}
            if "technique" in p:
                profile["technique"] = p["technique"]
            await self.client.ec_profile_set(POOL, dict(profile))
            await self.client.pool_create(
                POOL, pg_num=p["pg_num"], pool_type="erasure",
                erasure_code_profile=POOL)
            self.ec = registry.factory(p["plugin"], dict(profile))
        else:
            await self.client.pool_create(
                POOL, pg_num=p["pg_num"], pool_type="replicated",
                size=p["size"])
        self.io = self.client.ioctx(POOL)

    async def wait_warm(self, timeout: float) -> None:
        """Every daemon's map-install EC warm-up done, none failed."""
        deadline = time.monotonic() + timeout
        while True:
            tasks = [t for o in self.live_osds() for t in o._warm_tasks]
            if tasks:
                await asyncio.wait_for(
                    asyncio.gather(*tasks),
                    max(deadline - time.monotonic(), 0.001))
            elif all(POOL in o._warmed_profiles for o in self.live_osds()):
                break
            elif time.monotonic() >= deadline:
                raise TimeoutError("OSDs never saw the EC profile")
            else:
                await asyncio.sleep(0.05)
        failed = sum(o.perf.dump().get("ec_warmup_failures", 0)
                     for o in self.live_osds())
        if failed:
            raise RuntimeError(f"{failed} EC warm-ups failed (see log)")

    async def warm_shapes(self, matrices: list[str], obj_bytes: int,
                          in_flight: int) -> dict:
        """The daemons' ladder stops at 64 x the stripe-unit chunk; an
        object of ``obj_bytes`` is wider.  Compile the encode-service
        shapes this cell's traffic reaches (1..in_flight requests
        coalesced), for ``encode`` and/or ``decode<e>`` (e erasures)."""
        svc = self.encode_service
        S = obj_bytes // self.pool["k"]
        compile_s: dict[str, float] = {}
        for name in matrices:
            M = (np.asarray(self.ec.coding_matrix, np.uint8)
                 if name == "encode" else self.ec.decode_matrix(
                     tuple(range(int(name.removeprefix("decode"))))))
            if svc.mesh is not None:
                t0 = time.perf_counter()
                await asyncio.to_thread(svc.prewarm, M, [S],
                                        coalesce=in_flight)
                compile_s[f"{name} mesh"] = time.perf_counter() - t0
                continue
            f = 1
            while f <= in_flight:
                t0 = time.perf_counter()
                if await asyncio.to_thread(svc.prewarm, M, [S * f],
                                           coalesce=1):
                    compile_s[f"{name} S={S * f}"] = round(
                        time.perf_counter() - t0, 3)
                f <<= 1
        return compile_s

    def pg_of(self, name: str):
        """The folded pg of an object under the client's current map."""
        from ceph_tpu.osd.daemon import object_to_pg

        pool = self.client.osdmap.get_pg_pool(self.io.pool_id)
        return pool.raw_pg_to_pg(object_to_pg(pool, name))

    def acting_of(self, name: str):
        """(folded pg, acting OSDs by position) of an object."""
        pg = self.pg_of(name)
        return pg, self.client.osdmap.pg_to_up_acting_osds(
            pg, folded=True)[2]

    def up_osds(self) -> int:
        om = self.mon.osdmap
        return sum(1 for o in range(self.n_osds)
                   if om.max_osd > o and om.is_up(o))

    def counters(self) -> dict:
        """Every counter the program keeps, as one flat dict of running
        totals: the engines' and the guard's under their own prefixes,
        every numeric key of the live OSDs' ``perf.dump()`` summed under
        ``osd.<key>``, every key of the messengers' ``stats`` (live OSDs,
        mon, client) summed under ``msgr.<key>``.  A key nobody has
        counted yet is absent: read it with ``.get(key, 0)``."""
        from ceph_tpu.common import transfer_guard
        from ceph_tpu.ec.plugins.matrix_base import MatrixErasureCode
        from ceph_tpu.parallel import decode_batcher as db

        out = {f"encode.{k}": v for k, v in self.encode_service.stats.items()}
        out.update({f"decode.{k}": v for k, v in db.shared().stats.items()})
        out.update({f"guard.{k}": v
                    for k, v in transfer_guard.snapshot().items()})
        out.update({f"plugin.{k}": v
                    for k, v in MatrixErasureCode.device_stats.items()})
        for osd in self.live_osds():
            for k, v in osd.perf.dump().items():
                if isinstance(v, (int, float)):
                    key = "osd." + k
                    out[key] = out.get(key, 0) + v
        for daemon in (*self.live_osds(), self.mon, self.client):
            for k, v in daemon.messenger.stats.items():
                key = "msgr." + k
                out[key] = out.get(key, 0) + v
        return out
