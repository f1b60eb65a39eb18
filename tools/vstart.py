#!/usr/bin/env python
"""vstart: boot a dev mini-cluster (mons + OSDs) in one process.

The src/vstart.sh analogue: starts a monitor quorum, N OSDs and M
manager daemons on localhost, prints the monmap for `ceph.py -m`, and
runs until interrupted.

  vstart.py [--mons 1] [--osds 8] [--mgrs 1] [--beacon 1.0]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


async def amain(args) -> int:
    from ceph_tpu.crush import builder as B
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.mon import Monitor
    from ceph_tpu.osd.daemon import OSDDaemon
    from ceph_tpu.parallel import encode_service

    # Start the JAX backend before any daemon's clock runs.  Left to
    # the daemons it starts at their first EC map, on the event loop
    # all of them share, and a TPU runtime takes 9-16 s to come up
    # against a beacon grace of 4 beacons.  A chip that cannot be had
    # raises here.
    encode_service.shared()

    crush = CrushMap()
    B.build_hierarchy(
        crush, osds_per_host=args.osds_per_host,
        n_hosts=(args.osds + args.osds_per_host - 1) // args.osds_per_host,
    )

    def _store(name: str):
        if not args.data:
            return None
        if getattr(args, "store", "file") == "kstore":
            from ceph_tpu.kv import FileDB
            from ceph_tpu.store.kstore import KStore

            s = KStore(FileDB(os.path.join(args.data, name)))
        elif getattr(args, "store", "file") == "block":
            from ceph_tpu.store.blockstore import BlockStore

            s = BlockStore(os.path.join(args.data, name))
        else:
            from ceph_tpu.store.filestore import FileStore

            s = FileStore(os.path.join(args.data, name))
        s.mount()
        return s

    mons = [
        Monitor(
            crush=crush.copy(), rank=r, n_mons=args.mons,
            beacon_grace=args.beacon * 4 if args.beacon else 0.0,
            out_interval=args.out_interval,
            store=_store(f"mon{r}"),
        )
        for r in range(args.mons)
    ]
    for m in mons:
        await m.start()
    monmap = [m.addr for m in mons]
    for m in mons:
        await m.open_quorum(monmap)
    for m in mons:
        await m.wait_stable()
    mgrs = []
    if args.mgrs:
        from ceph_tpu.mgr.daemon import MgrDaemon

        for i in range(args.mgrs):
            mgr = MgrDaemon(chr(ord("x") + i), monmap)
            await mgr.start()
            mgrs.append(mgr)
    osds = []
    for i in range(args.osds):
        osd = OSDDaemon(
            i, monmap, beacon_interval=args.beacon,
            store=_store(f"osd{i}"),
        )
        await osd.start()
        osds.append(osd)
    spec = ",".join(f"{h}:{p}" for h, p in monmap)
    print(f"vstart: cluster up — mons at {spec}", flush=True)
    if mgrs:
        print(f"vstart: mgrs {', '.join(m.name for m in mgrs)} "
              f"(active is the mon's call — `ceph.py mgr stat`)",
              flush=True)
    print(f"vstart: try  python tools/ceph.py -m {spec} status", flush=True)
    dash = None
    if args.dashboard:
        from ceph_tpu.mgr.dashboard import Dashboard

        dash = Dashboard(mons[0])
        dh, dp = await dash.start(port=args.dashboard_port)
        print(f"vstart: dashboard at http://{dh}:{dp}/", flush=True)
    try:
        while True:
            await asyncio.sleep(3600)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        if dash is not None:
            await dash.stop()
        for o in osds:
            await o.stop()
        for g in mgrs:
            await g.stop()
        for m in mons:
            await m.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mons", type=int, default=1)
    ap.add_argument("--osds", type=int, default=8)
    ap.add_argument("--mgrs", type=int, default=1,
                    help="manager daemons (first to beacon goes "
                         "active, the rest stand by)")
    ap.add_argument("--osds-per-host", type=int, default=1)
    ap.add_argument("--beacon", type=float, default=1.0)
    ap.add_argument("--out-interval", type=float, default=0.0)
    ap.add_argument(
        "--data", default="",
        help="data directory: daemons run on durable stores and the "
             "cluster survives restart (default: volatile MemStores)",
    )
    ap.add_argument(
        "--store", choices=("file", "kstore", "block"), default="file",
        help="durable engine under --data: file = FileStore WAL, "
             "kstore = objects-in-kv over FileDB (src/os/kstore twin), "
             "block = BlockStore (extents + checksums-at-rest, the "
             "BlueStore-grade engine)",
    )
    ap.add_argument(
        "--dashboard", action="store_true",
        help="serve the read-only web dashboard from the rank-0 mon "
             "(ceph_tpu/mgr/dashboard.py)",
    )
    ap.add_argument("--dashboard-port", type=int, default=0,
                    help="dashboard port (default: ephemeral)")
    args = ap.parse_args(argv)
    try:
        return asyncio.run(amain(args))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
