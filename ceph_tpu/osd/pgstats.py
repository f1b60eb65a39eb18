"""A PG's objects and bytes for the stat report, kept from what the OSD's
own transactions change.

The reference does not count a PG's objects to report them: each op's
``delta_stats`` is added to the PG's ``info.stats`` as it finishes
(``PrimaryLogPG::finish_ctx``) and ``PG::publish_stats_to_osd`` reports
the tallies.  Here every transaction the OSD applies goes through one
seam (``OSDDaemon._apply_txn``), which hands it to :meth:`PgStatTally.note`
once the store has applied it: an O(ops) walk that records the names the
transaction touched, with no store call.  A report re-stats only those
names, and lists a collection in full only when it is new to the table
(at boot, when this OSD becomes a PG's primary, or after a transaction
created or removed the collection).  The cost follows the change rate,
not the population.

What a collection's usage is, exactly as a scan of it gives: every
distinct object name it holds but ``PGMETA_OID``, each counted once
whatever clones or rollback sidecars it has, with the size of its head
(``ghobject_t(name, shard=c.shard)``), or 0 where only clones remain.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

from ceph_tpu.osd.pglog import PGMETA_OID
from ceph_tpu.store import Transaction, TxOp, coll_t, ghobject_t

_HEAD = ghobject_t("")


def _is_head(o: ghobject_t, c: coll_t) -> bool:
    return o.snap == _HEAD.snap and o.gen == _HEAD.gen and o.shard == c.shard


@dataclass
class _Coll:
    """One collection's table.  ``sizes``, ``others`` and ``nbytes`` are
    the report's, touched on the loop thread alone; ``noted`` is filled
    by whichever thread applied a transaction, under the tally's lock."""

    sizes: dict[str, int] = field(default_factory=dict)    # name -> head size
    # name -> its ghobjects other than the head that may still exist
    # (clones, rollback sidecars): asked about only when the head is gone
    others: dict[str, set] = field(default_factory=dict)
    nbytes: int = 0
    noted: dict[str, set] = field(default_factory=dict)


class PgStatTally:
    """Per collection of a PG this OSD leads: (objects, head bytes).
    ``perf`` counts the work: ``pg_stat_objects_stated`` (names a report
    re-stat'ed) and ``pg_stat_collections_listed`` (full listings)."""

    def __init__(self, store, perf):
        self.store = store
        self.perf = perf
        self._lock = threading.Lock()
        self._colls: dict[coll_t, _Coll] = {}
        perf.inc("pg_stat_objects_stated", 0)
        perf.inc("pg_stat_collections_listed", 0)

    def note(self, t: Transaction) -> None:
        """Record what ``t`` names; call it once the store has applied
        ``t`` (or failed to), on the thread that applied it.  A report
        that stat'ed a name while ``t`` was in flight meets the name
        again at the next report, so no tally stays stale."""
        with self._lock:
            if not self._colls:
                return
            for op in t.ops:
                kind = op[0]
                if kind is TxOp.MKCOLL or kind is TxOp.RMCOLL:
                    # created, removed, split into or merged: list anew
                    self._colls.pop(op[1], None)
                elif kind is TxOp.COLL_MOVE_RENAME:
                    self._note(op[1], op[2])
                    self._note(op[3], op[4])
                elif kind is TxOp.CLONE:
                    self._note(op[1], op[3])
                else:
                    self._note(op[1], op[2])

    def _note(self, c: coll_t, o: ghobject_t) -> None:
        ent = self._colls.get(c)
        if ent is None or o.name == PGMETA_OID:
            return
        others = ent.noted.get(o.name)
        if others is None:
            others = ent.noted[o.name] = set()
        if not _is_head(o, c):
            others.add(o)

    def usage(self, c: coll_t) -> tuple[int, int]:
        """(objects, head bytes) of ``c``; loop thread only."""
        with self._lock:
            ent = self._colls.get(c)
            fresh = ent is None
            if fresh:
                # registered before the listing, so what a transaction
                # applies meanwhile is noted and re-stat'ed next time
                ent = self._colls[c] = _Coll()
            else:
                noted, ent.noted = ent.noted, {}
        if fresh:
            self.perf.inc("pg_stat_collections_listed")
            try:
                listed = self.store.collection_list(c)
            except FileNotFoundError:
                listed = ()
            noted = {}
            for o in listed:
                if o.name == PGMETA_OID:
                    continue
                others = noted.setdefault(o.name, set())
                if not _is_head(o, c):
                    others.add(o)
        self.perf.inc("pg_stat_objects_stated", len(noted))
        for name, others in noted.items():
            self._restat(c, ent, name, others)
        return len(ent.sizes), ent.nbytes

    def _restat(self, c: coll_t, ent: _Coll, name: str, others: set) -> None:
        known = ent.others.get(name)
        if known:
            others = known | others
        try:
            size = self.store.stat(c, ghobject_t(name, shard=c.shard))
            live = True
        except FileNotFoundError:
            size = 0
            others = {o for o in others if self.store.exists(c, o)}
            live = bool(others)
        if others:
            ent.others[name] = others
        else:
            ent.others.pop(name, None)
        ent.nbytes -= ent.sizes.pop(name, 0)
        if live:
            ent.sizes[name] = size
            ent.nbytes += size

    def retain(self, colls: set) -> None:
        """Forget every collection but ``colls`` (the PGs led now)."""
        with self._lock:
            for c in [c for c in self._colls if c not in colls]:
                del self._colls[c]
