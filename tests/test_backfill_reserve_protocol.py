"""The remote backfill reservation as a queue (reference
doc/dev/osd_internals/backfill_reservation.rst): a busy replica queues a
REQUEST and GRANTs it when its slot frees, a RELEASE withdraws a queued
ask, a dead requester's ask is swept, only a backfillfull store answers
REJECT_TOOFULL, and a pass whose map moved on while it waited runs no
recovery (ROADMAP D14).

The OSDs here are ``RecoveryMixin`` over an in-process fake network:
a message is handled as it is sent, the reply goes back over a fake
connection, and the "map" is an epoch and a set of down OSDs.
"""

from __future__ import annotations

import asyncio
import itertools

from ceph_tpu.common.metrics import PerfCounters
from ceph_tpu.common.reserver import AsyncReserver
from ceph_tpu.common.tracing import Tracer
from ceph_tpu.msg.messages import MBackfillReserve
from ceph_tpu.osd.recovery import RecoveryMixin
from ceph_tpu.osd.types import pg_t

REQ, GRANT, REL = (MBackfillReserve.REQUEST, MBackfillReserve.GRANT,
                   MBackfillReserve.RELEASE)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 20))
    finally:
        loop.close()


class _Map:
    def __init__(self):
        self.epoch = 1
        self.down: set[int] = set()

    def is_up(self, o: int) -> bool:
        return o not in self.down


class _Net:
    """Every node by id, and the one map they share."""

    def __init__(self):
        self.nodes: dict[int, _Node] = {}
        self.osdmap = _Map()
        self.after_grant = None  # hook: runs once a GRANT is delivered

    def new_epoch(self, down=()) -> None:
        self.osdmap.epoch += 1
        self.osdmap.down |= set(down)
        for n in self.nodes.values():
            n._wake_reserve_waits()  # as OSDDaemon._handle_map does


class _Conn:
    """``src``'s link to ``dst``; the handler runs as the frame lands."""

    def __init__(self, net: _Net, src: int, dst: int, log=None):
        self.net, self.src, self.dst, self.log = net, src, dst, log

    async def send_message(self, m) -> None:
        if self.log is not None:
            self.log.append(m)
        node = self.net.nodes.get(self.dst)
        if node is None:
            return
        m.conn = _Conn(self.net, self.dst, self.src)
        await node._handle_backfill_reserve(m)
        if m.op == GRANT and self.net.after_grant is not None:
            self.net.after_grant(m)


class _Node(RecoveryMixin):
    def __init__(self, net: _Net, osd_id: int):
        self.net, self.id = net, osd_id
        net.nodes[osd_id] = self
        self.conf = {"mon_osd_backfillfull_ratio": 0.90,
                     "osd_backfill_grant_timeout": 60.0,
                     "osd_backfill_retry_interval": 0.01}
        self.perf = PerfCounters(f"fake.{osd_id}")
        self.full = 0.0
        self.local_reserver = AsyncReserver(max_allowed=1)
        self.remote_reserver = AsyncReserver(max_allowed=1)
        self._remote_grants: dict = {}
        self._remote_asks: dict = {}
        self._reserve_waits: set = set()
        self._waiters: dict = {}
        self._tids = itertools.count(1)
        self._repair_tasks: set = set()
        self._recovering_pgs: set = set()
        self._clean_epoch: dict = {}
        self.recovery_stats = {
            "reservation_rejects": 0, "pgs_recovered": 0,
            "peak_local": 0, "peak_remote": 0, "grants_swept": 0}
        self.stopping = False
        self.tracer = Tracer(f"fake.{osd_id}")
        self.recovered: list[list[int]] = []  # acting sets recovered with
        self.dig_outs = 0
        self.on_dig_out = None

    @property
    def osdmap(self):
        return self.net.osdmap

    @property
    def epoch(self) -> int:
        return self.net.osdmap.epoch

    def _full_ratio(self) -> float:
        return self.full

    async def _osd_conn(self, o: int) -> _Conn:
        return _Conn(self.net, self.id, o)

    def _spawn_repair_task(self, coro) -> None:
        t = asyncio.ensure_future(coro)
        self._repair_tasks.add(t)
        t.add_done_callback(self._repair_tasks.discard)

    async def _recover_pg(self, pool, pg, acting) -> bool:
        self.recovered.append(list(acting))
        return True

    async def _recover_pg_deletes(self, pool, pg, acting) -> None:
        self.dig_outs += 1
        if self.on_dig_out is not None:
            self.on_dig_out()

    def counter(self, name: str) -> float:
        return self.perf.dump().get(name, 0)


def _ask(replica: _Node, frm: int, tid: int, op=REQ, ps: int = 0,
         log=None) -> MBackfillReserve:
    """Deliver one message from OSD ``frm`` to ``replica``; replies land
    in ``log``."""
    m = MBackfillReserve(tid=tid, op=op, pool=1, ps=ps, from_osd=frm,
                         priority=1)
    m.conn = _Conn(replica.net, replica.id, frm,
                   log=[] if log is None else log)
    return m


async def _settle():
    for _ in range(5):
        await asyncio.sleep(0)


def test_a_busy_slot_queues_and_grants_in_fifo_order_within_the_cap():
    async def go():
        net = _Net()
        r = _Node(net, 9)
        replies: list = []
        for frm, tid in ((1, 11), (2, 12), (3, 13)):
            await r._handle_backfill_reserve(
                _ask(r, frm, tid, ps=frm, log=replies))
        await _settle()
        assert [m.tid for m in replies] == [11]  # 12, 13 queued, unanswered
        assert r.remote_reserver.in_use == 1
        assert r.remote_reserver.queued() == 2
        assert r.counter("backfill_reserve_requests") == 3
        assert r.counter("backfill_reserve_queued") == 2
        for frm in (1, 2, 3):
            await r._handle_backfill_reserve(_ask(r, frm, 0, REL, ps=frm))
            await _settle()
            assert r.remote_reserver.in_use <= 1
        assert [(m.op, m.tid) for m in replies] == [
            (GRANT, 11), (GRANT, 12), (GRANT, 13)]
        assert r.recovery_stats["peak_remote"] == 1
        assert r.remote_reserver.peak_granted == 1
        assert (r.remote_reserver.in_use, r.remote_reserver.queued()) == (0, 0)

    run(go())


def test_an_ask_repeated_while_queued_queues_once_and_is_answered_under_every_tid():
    async def go():
        net = _Net()
        r = _Node(net, 9)
        replies: list = []
        await r._handle_backfill_reserve(_ask(r, 1, 11, log=replies))
        await r._handle_backfill_reserve(_ask(r, 2, 21, ps=2, log=replies))
        await r._handle_backfill_reserve(_ask(r, 2, 22, ps=2, log=replies))
        await r._handle_backfill_reserve(_ask(r, 2, 22, ps=2, log=replies))
        await _settle()
        assert r.remote_reserver.queued() == 1
        assert r.counter("backfill_reserve_queued") == 1
        await r._handle_backfill_reserve(_ask(r, 1, 0, REL))
        await _settle()
        assert [(m.op, m.tid) for m in replies] == [
            (GRANT, 11), (GRANT, 21), (GRANT, 22)]
        assert r.remote_reserver.in_use == 1  # one slot for the key

    run(go())


def test_two_passes_of_one_pg_queued_at_a_peer_both_run_when_it_frees():
    async def go():
        net = _Net()
        p = _Node(net, 0)
        r1, r2 = _Node(net, 1), _Node(net, 2)
        await r2._handle_backfill_reserve(_ask(r2, 7, 71, ps=5))
        # the map's pass and a floored-replica pass (_queue_pg_pass)
        passes = [asyncio.ensure_future(
            p._recover_pg_reserved(None, pg_t(1, 0), [0, 1, 2], 1))
            for _ in range(2)]
        await _settle()
        assert r2.remote_reserver.queued() == 1
        await r2._handle_backfill_reserve(_ask(r2, 7, 0, REL, ps=5))
        await asyncio.wait_for(asyncio.gather(*passes), 5)
        assert p.recovered == [[0, 1, 2], [0, 1, 2]]
        for r in (r1, r2):
            assert (r.remote_reserver.in_use, r.remote_reserver.queued()) \
                == (0, 0)

    run(go())


def test_release_of_a_queued_ask_cancels_it_and_leaks_no_slot():
    async def go():
        net = _Net()
        r = _Node(net, 9)
        replies: list = []
        await r._handle_backfill_reserve(_ask(r, 1, 11, log=replies))
        await r._handle_backfill_reserve(_ask(r, 2, 21, ps=2, log=replies))
        await _settle()
        assert r.remote_reserver.queued() == 1
        await r._handle_backfill_reserve(_ask(r, 2, 0, REL, ps=2))
        await _settle()
        assert r.remote_reserver.queued() == 0
        assert not r._remote_asks and not r._repair_tasks
        await r._handle_backfill_reserve(_ask(r, 1, 0, REL))
        await _settle()
        assert (r.remote_reserver.in_use, r.remote_reserver.queued()) == (0, 0)
        assert [m.tid for m in replies] == [11]  # the withdrawn ask: never
        # the slot is free: the next ask is granted at once
        await r._handle_backfill_reserve(_ask(r, 3, 31, ps=3, log=replies))
        assert replies[-1].op == GRANT and r.remote_reserver.in_use == 1

    run(go())


def test_an_ask_withdrawn_before_its_wait_ran_leaves_the_next_asks_grant_held():
    async def go():
        net = _Net()
        r = _Node(net, 9)
        replies: list = []
        await r._handle_backfill_reserve(_ask(r, 1, 11, log=replies))
        # queued, and withdrawn before its wait first runs; then the slot
        # frees and the same primary asks again: granted at once
        await r._handle_backfill_reserve(_ask(r, 2, 21, ps=2, log=replies))
        await r._handle_backfill_reserve(_ask(r, 2, 0, REL, ps=2))
        await r._handle_backfill_reserve(_ask(r, 1, 0, REL))
        await r._handle_backfill_reserve(_ask(r, 2, 22, ps=2, log=replies))
        await _settle()  # the withdrawn ask's wait runs now
        assert [(m.op, m.tid) for m in replies] == [(GRANT, 11), (GRANT, 22)]
        assert (1, 2, 2) in r._remote_grants
        assert r.remote_reserver.in_use == 1
        await r._handle_backfill_reserve(_ask(r, 3, 31, ps=3, log=replies))
        await _settle()
        assert replies[-1].tid == 22  # osd.3 queues behind the live grant
        assert r.remote_reserver.peak_granted == 1

    run(go())


def test_a_requester_marked_down_while_queued_is_swept():
    async def go():
        net = _Net()
        r = _Node(net, 9)
        replies: list = []
        await r._handle_backfill_reserve(_ask(r, 1, 11, log=replies))
        await r._handle_backfill_reserve(_ask(r, 2, 21, ps=2, log=replies))
        await _settle()
        net.new_epoch(down=[2])
        r._sweep_remote_grants()
        await _settle()
        assert r.remote_reserver.queued() == 0 and not r._remote_asks
        assert (1, 0, 1) in r._remote_grants  # the live holder keeps it
        await r._handle_backfill_reserve(_ask(r, 1, 0, REL))
        await _settle()
        assert (r.remote_reserver.in_use, r.remote_reserver.queued()) == (0, 0)
        assert [m.tid for m in replies] == [11]

    run(go())


def test_a_pass_holds_its_lower_peers_while_queued_and_runs_when_granted():
    async def go():
        net = _Net()
        p = _Node(net, 0)
        r1, r2 = _Node(net, 1), _Node(net, 2)
        # another primary (osd.7) holds osd.2's one slot
        await r2._handle_backfill_reserve(_ask(r2, 7, 71, ps=5))
        task = asyncio.ensure_future(
            p._recover_pg_reserved(None, pg_t(1, 0), [0, 1, 2], 1))
        await _settle()
        assert (1, 0, 0) in r1._remote_grants       # held while it waits
        assert (1, 0, 0) in r2._remote_asks         # queued, not rejected
        assert not p.recovered
        await r2._handle_backfill_reserve(_ask(r2, 7, 0, REL, ps=5))
        await asyncio.wait_for(task, 5)
        assert p.recovered == [[0, 1, 2]]
        assert p.recovery_stats["reservation_rejects"] == 0
        assert r2.counter("backfill_reserve_queued") == 1
        for r in (r1, r2):
            assert (r.remote_reserver.in_use, r.remote_reserver.queued()) \
                == (0, 0)

    run(go())


def test_a_backfillfull_replica_rejects_and_the_primary_retries_after_the_dig_out():
    async def go():
        net = _Net()
        p = _Node(net, 0)
        r1, r2 = _Node(net, 1), _Node(net, 2)
        r2.full = 0.95

        def dig_out():
            r2.full = 0.5  # the replayed deletes freed its space

        p.on_dig_out = dig_out
        await asyncio.wait_for(
            p._recover_pg_reserved(None, pg_t(1, 0), [0, 1, 2], 1), 5)
        assert r2.counter("backfill_reject_toofull") == 1
        assert p.recovery_stats["reservation_rejects"] == 1
        assert p.dig_outs == 1
        assert p.recovered == [[0, 1, 2]]
        for r in (r1, r2):
            assert (r.remote_reserver.in_use, r.remote_reserver.queued()) \
                == (0, 0)
            assert r.counter("backfill_reserve_queued") == 0

    run(go())


def test_a_map_change_while_queued_supersedes_the_pass():
    async def go():
        net = _Net()
        p = _Node(net, 0)
        r1, r2 = _Node(net, 1), _Node(net, 2)
        await r2._handle_backfill_reserve(_ask(r2, 7, 71, ps=5))
        task = asyncio.ensure_future(
            p._recover_pg_reserved(None, pg_t(1, 0), [0, 1, 2], 1))
        await _settle()
        assert (1, 0, 0) in r2._remote_asks
        net.new_epoch()
        await asyncio.wait_for(task, 5)
        assert p.recovered == []
        # every peer held or queued at was released
        assert not r1._remote_grants and not r2._remote_asks
        assert r1.remote_reserver.in_use == 0
        assert r2.remote_reserver.queued() == 0
        assert [s.tags.get("result") for s in p.tracer.find()
                if s.name == "recover_pg"] == ["superseded"]

    run(go())


def test_a_map_change_at_the_last_grant_runs_no_recovery_with_the_old_acting_set():
    async def go():
        net = _Net()
        p = _Node(net, 0)
        r1, r2 = _Node(net, 1), _Node(net, 2)
        await r2._handle_backfill_reserve(_ask(r2, 7, 71, ps=5))
        task = asyncio.ensure_future(
            p._recover_pg_reserved(None, pg_t(1, 0), [0, 1, 2], 1))
        await _settle()

        def map_lands_after_the_grant(m):
            # the primary's wait has its GRANT; it has not resumed yet
            if m.from_osd == 2 and m.ps == 0:
                net.after_grant = None
                net.new_epoch()

        net.after_grant = map_lands_after_the_grant
        await r2._handle_backfill_reserve(_ask(r2, 7, 0, REL, ps=5))
        await asyncio.wait_for(task, 5)
        assert p.recovered == []  # D14: the grant came under the old map
        for r in (r1, r2):
            assert (r.remote_reserver.in_use, r.remote_reserver.queued()) \
                == (0, 0)
        assert [s.tags.get("result") for s in p.tracer.find()
                if s.name == "recover_pg"] == ["superseded"]

    run(go())
