"""What decides ``correct``: acknowledged objects against the plain
reference, byte for byte, outside the window — read back through the
client, and as they sit in the OSDs' stores, from as many shards or
replicas as the configuration's guarantees state."""

from __future__ import annotations

import asyncio
import functools
import importlib.util
import logging
import os

from . import reference

log = logging.getLogger("bench")
REFERENCES_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "references")
COUNTS = ("equal", "differ", "absent", "absent_live", "rebuilt")


@functools.lru_cache(maxsize=None)
def load_reference(name: str | None):
    """The module whose ``expected_copies(pool, blob) -> list[bytes]``
    says what position 0..n-1 of the acting set must hold: a
    configuration's own (its file's ``"reference": "<name>"`` is
    ``benchmarks/references/<name>.py``, loaded by path), or
    ``harness/reference.py``.  A reference imports nothing of the
    program."""
    if name is None:
        return reference
    spec = importlib.util.spec_from_file_location(
        "reference_" + name.replace(".", "_"),
        os.path.join(REFERENCES_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stored_copies(c, name: str, blob: bytes, *, moved_from=None,
                  lost_osd=None) -> dict:
    """Compare every copy of ``name`` present on its acting OSDs with
    the reference.  Returns counts: ``equal``, ``differ``, ``absent``
    (no such OSD, OSD stopped, or object not there yet), ``absent_live``
    (those of them at a position that ``lost_osd`` did not hold in
    ``moved_from``) and ``rebuilt`` (equal copies on another OSD than
    ``moved_from`` had there)."""
    from ceph_tpu.store import coll_t, ghobject_t

    want = load_reference(c.reference).expected_copies(c.pool, blob)
    pg, acting = c.acting_of(name)
    n = dict.fromkeys(COUNTS, 0)
    if len(acting) != len(want):
        n["differ"] += 1
        return n
    for pos, osd in enumerate(acting):
        shard = pos if c.erasure else -1
        coll, obj = coll_t(pg.pool, pg.ps, shard), ghobject_t(name, shard=shard)
        if not (0 <= osd < c.n_osds) or c.osds[osd] is None \
                or not c.osds[osd].store.exists(coll, obj):
            n["absent"] += 1
            if moved_from is None or moved_from[pos] != lost_osd:
                n["absent_live"] += 1
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
                        acting_before: dict | None = None,
                        lost_osd: int | None = None,
                        loss: str | None = None) -> dict:
    """``sample`` maps object names to the bytes acknowledged for them;
    ``loss`` says what became of ``lost_osd`` (``"recovers"``: marked
    out, ``"stays_degraded"``: down and in).  Returns what was counted;
    ``limits`` says what each count may be."""
    names = list(sample)
    sem = asyncio.Semaphore(in_flight)

    async def read(name):
        async with sem:
            try:
                return await c.io.read(name)
            except Exception as exc:    # no answer is not the answer
                log.warning("read back of %s failed: %r", name, exc)
                return None

    got = await asyncio.gather(*(read(n) for n in names))
    read_equal = sum(a == sample[n] for a, n in zip(got, names))
    total = dict.fromkeys(COUNTS, 0)
    for name in names:
        counts = await asyncio.to_thread(
            stored_copies, c, name, sample[name],
            moved_from=(acting_before or {}).get(name), lost_osd=lost_osd)
        for k, v in counts.items():
            total[k] += v
    out = {"objects": len(names), "read_back_equal": read_equal,
           "stored": total, "loss": loss}
    if acting_before is not None:
        out["moved_bytes_present"] = await asyncio.to_thread(
            moved_bytes_present, c, acting_before)
    return out


def limits(verdict: dict) -> dict:
    """Each number ``verify_sample`` counted beside its limit (``max``
    or ``min``).  No loss: every copy the guarantees state is there.
    A loss that recovers (the OSD marked out): copies may still be
    absent, at least one rebuilt copy is found.  A loss that stays
    degraded (down and in): copies are absent only where the stopped
    OSD held them, and none is rebuilt."""
    stored, loss = verdict["stored"], verdict["loss"]
    out = {"objects_compared": {"value": verdict["objects"], "min": 1},
           "read_back_differ": {"value": verdict["objects"]
                                - verdict["read_back_equal"], "max": 0},
           "stored_differ": {"value": stored["differ"], "max": 0}}
    if loss is None:
        out["stored_absent"] = {"value": stored["absent"], "max": 0}
    elif loss == "recovers":
        out["stored_rebuilt"] = {"value": stored["rebuilt"], "min": 1}
    else:
        out["stored_absent_live"] = {"value": stored["absent_live"], "max": 0}
        out["stored_rebuilt"] = {"value": stored["rebuilt"], "max": 0}
    return out


def within(compared: dict) -> bool:
    """Every number inside its limit."""
    return all(x["value"] <= x["max"] if "max" in x else
               x["value"] >= x["min"] for x in compared.values())
