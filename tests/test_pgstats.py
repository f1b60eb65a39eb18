"""``PgStatTally`` (osd/pgstats.py) on a lone MemStore: after every
transaction its usage of a collection equals a plain scan of the store,
through clones whose head is gone, rollback sidecars, moves between
collections, a collection removed and made again; a report with nothing
noted stats nothing; and worker threads applying transactions while the
report runs leave no tally stale.
"""

from __future__ import annotations

import random
import sys
import threading

from ceph_tpu.common.metrics import PerfCounters
from ceph_tpu.osd.pglog import PGMETA_OID
from ceph_tpu.osd.pgstats import PgStatTally
from ceph_tpu.osd.pgutil import RB_SNAP
from ceph_tpu.store import MemStore, Transaction, coll_t, ghobject_t

C = coll_t(1, 0, 2)     # an EC shard collection: heads carry shard 2
C2 = coll_t(1, 4, 2)    # its split child
META = ghobject_t(PGMETA_OID, shard=2)


def _o(name: str, snap: int | None = None) -> ghobject_t:
    if snap is None:
        return ghobject_t(name, shard=2)
    return ghobject_t(name, snap=snap, shard=2)


def _scan(store, c) -> tuple[int, int]:
    if not store.collection_exists(c):
        return 0, 0
    names = {o.name for o in store.collection_list(c)} - {PGMETA_OID}
    nbytes = 0
    for name in names:
        try:
            nbytes += store.stat(c, _o(name))
        except FileNotFoundError:
            pass
    return len(names), nbytes


class _Osd:
    """Applies a transaction, then notes it, as ``OSDDaemon._apply_txn``."""

    def __init__(self):
        self.store = MemStore()
        self.perf = PerfCounters("test")
        self.tally = PgStatTally(self.store, self.perf)

    def apply(self, t: Transaction) -> None:
        try:
            self.store.queue_transaction(t)
        finally:
            self.tally.note(t)

    def counts(self) -> tuple[float, float]:
        d = self.perf.dump()
        return d["pg_stat_objects_stated"], d["pg_stat_collections_listed"]


def _check(osd, *colls) -> None:
    for c in colls:
        assert osd.tally.usage(c) == _scan(osd.store, c), c


def test_usage_equals_a_plain_scan_after_every_transaction():
    osd = _Osd()
    _check(osd, C, C2)          # led before the collections exist
    assert osd.counts() == (0, 2)
    osd.apply(Transaction().create_collection(C)
              .write(C, _o("a"), 0, b"a" * 100)
              .write(C, _o("b"), 0, b"b" * 50)
              .omap_setkeys(C, META, {"k": b"v"}))
    _check(osd, C)
    assert osd.tally.usage(C) == (2, 150)
    # a clone, then its head removed: the name stays, with 0 bytes
    osd.apply(Transaction().clone(C, _o("a"), _o("a", 5)))
    osd.apply(Transaction().remove(C, _o("a")))
    _check(osd, C)
    assert osd.tally.usage(C) == (2, 50)
    osd.apply(Transaction().remove(C, _o("a", 5)))
    _check(osd, C)
    assert osd.tally.usage(C) == (1, 50)
    # a rollback sidecar outlives its head, then goes
    osd.apply(Transaction().clone(C, _o("b"), _o("b", RB_SNAP))
              .truncate(C, _o("b"), 10))
    _check(osd, C)
    osd.apply(Transaction().remove(C, _o("b")))
    _check(osd, C)
    assert osd.tally.usage(C) == (1, 0)
    osd.apply(Transaction().remove(C, _o("b", RB_SNAP)))
    _check(osd, C)
    assert osd.tally.usage(C) == (0, 0)
    # a split moves names into a child made in the same transaction
    osd.apply(Transaction().write(C, _o("x"), 0, b"x" * 7)
              .write(C, _o("y"), 0, b"y" * 9))
    _check(osd, C)
    osd.apply(Transaction().create_collection(C2)
              .collection_move_rename(C, _o("x"), C2, _o("x")))
    _check(osd, C, C2)
    assert osd.tally.usage(C2) == (1, 7)
    # the child dissolves back (a merge), its collection removed
    osd.apply(Transaction().collection_move_rename(C2, _o("x"), C, _o("x"))
              .remove_collection(C2))
    _check(osd, C, C2)
    assert osd.tally.usage(C) == (2, 16)


def test_a_report_with_nothing_noted_stats_nothing():
    osd = _Osd()
    osd.apply(Transaction().create_collection(C))
    for i in range(50):
        osd.apply(Transaction().write(C, _o(f"o{i}"), 0, b"z" * i))
    _check(osd, C)
    before = osd.counts()
    _check(osd, C)
    assert osd.counts() == before
    # k writes: k names stat'ed, no listing
    for i in range(3):
        osd.apply(Transaction().write(C, _o(f"o{i}"), 0, b"w" * 99))
    _check(osd, C)
    assert osd.counts() == (before[0] + 3, before[1])
    # what is not led any more is forgotten, and listed when led again
    osd.tally.retain(set())
    _check(osd, C)
    assert osd.counts() == (before[0] + 3 + 50, before[1] + 1)


def test_commits_on_worker_threads_leave_no_tally_stale():
    osd = _Osd()
    osd.apply(Transaction().create_collection(C))
    _check(osd, C)

    def writer(w: int) -> None:
        rng = random.Random(w)
        for _ in range(300):
            o = _o(f"w{w}-{rng.randrange(8)}")
            t = Transaction()
            r = rng.random()
            if r < 0.5:
                t.write(C, o, 0, b"d" * rng.randrange(1, 64))
            elif r < 0.7 and osd.store.exists(C, o):
                t.truncate(C, o, rng.randrange(64))
            elif osd.store.exists(C, o):
                t.remove(C, o)
            if not t.empty():
                osd.apply(t)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(16)]
        for th in threads:
            th.start()
        while any(th.is_alive() for th in threads):
            osd.tally.usage(C)
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    _check(osd, C)
