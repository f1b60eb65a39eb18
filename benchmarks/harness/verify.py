"""What decides ``correct``: acknowledged objects against the plain
reference, byte for byte, outside the window — read back through the
client, and as they sit in the OSDs' stores, from as many shards or
replicas as the configuration's guarantees state."""

from __future__ import annotations

import asyncio

from . import reference


def expected_copies(pool: dict, blob: bytes) -> list[bytes]:
    """What position 0..n-1 of the acting set must hold."""
    if pool["type"] == "erasure":
        return reference.ec_shards(blob, pool["k"], pool["m"],
                                   pool["stripe_unit"])
    return [blob] * pool["size"]


def stored_copies(c, name: str, blob: bytes, *, moved_from=None) -> dict:
    """Compare every copy of ``name`` present on its acting OSDs with
    the reference.  Returns counts: ``equal``, ``differ``, ``absent``
    (no such OSD, OSD stopped, or object not there yet) and ``rebuilt``
    (equal copies on another OSD than ``moved_from`` had there)."""
    from ceph_tpu.store import coll_t, ghobject_t

    want = expected_copies(c.pool, blob)
    pg, acting = c.acting_of(name)
    n = {"equal": 0, "differ": 0, "absent": 0, "rebuilt": 0}
    if len(acting) != len(want):
        n["differ"] += 1
        return n
    for pos, osd in enumerate(acting):
        shard = pos if c.erasure else -1
        coll, obj = coll_t(pg.pool, pg.ps, shard), ghobject_t(name, shard=shard)
        if not (0 <= osd < c.n_osds) or c.osds[osd] is None \
                or not c.osds[osd].store.exists(coll, obj):
            n["absent"] += 1
        elif bytes(c.osds[osd].store.read(coll, obj)) == want[pos]:
            n["equal"] += 1
            if moved_from is not None and moved_from[pos] != osd:
                n["rebuilt"] += 1
        else:
            n["differ"] += 1
    return n


def moved_bytes_present(c, acting_before: dict) -> int:
    """Bytes of every copy that sits on another OSD than before the
    loss: the benchmark's own count of what recovery rebuilt, which the
    program's byte counter may not exceed."""
    from ceph_tpu.store import coll_t, ghobject_t

    total = 0
    for name, before in acting_before.items():
        pg, acting = c.acting_of(name)
        for pos, osd in enumerate(acting):
            shard = pos if c.erasure else -1
            coll = coll_t(pg.pool, pg.ps, shard)
            obj = ghobject_t(name, shard=shard)
            if osd != before[pos] and 0 <= osd < c.n_osds \
                    and c.osds[osd] is not None \
                    and c.osds[osd].store.exists(coll, obj):
                total += c.osds[osd].store.stat(coll, obj)
    return total


async def verify_sample(c, sample: dict[str, bytes], *, in_flight: int,
                        acting_before: dict | None = None) -> dict:
    """``sample`` maps object names to the bytes acknowledged for them.
    With ``acting_before`` (a loss happened) copies may still be absent
    and at least one rebuilt copy must be found; without it every copy
    the guarantees state must be there."""
    names = list(sample)
    sem = asyncio.Semaphore(in_flight)

    async def read(name):
        async with sem:
            return await c.io.read(name)

    got = await asyncio.gather(*(read(n) for n in names))
    read_equal = sum(a == sample[n] for a, n in zip(got, names))
    total = {"equal": 0, "differ": 0, "absent": 0, "rebuilt": 0}
    for name in names:
        counts = await asyncio.to_thread(
            stored_copies, c, name, sample[name],
            moved_from=(acting_before or {}).get(name))
        for k, v in counts.items():
            total[k] += v
    ok = read_equal == len(names) and total["differ"] == 0 and (
        total["rebuilt"] > 0 if acting_before is not None
        else total["absent"] == 0)
    out = {"ok": bool(ok and names), "objects": len(names),
           "read_back_equal": read_equal, "stored": total}
    if acting_before is not None:
        out["moved_bytes_present"] = await asyncio.to_thread(
            moved_bytes_present, c, acting_before)
    return out
