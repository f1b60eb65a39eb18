"""The event loop's own clock (common/tracing.py): every callback the loop
runs is charged to the daemon and role that own it, the periodic timers
count how late the loop wakes them, a long turn is a ``loop_turn`` span,
and with every tracer off nothing is installed on the loop.
"""

import asyncio
import time

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.tracing import LOOP_ROLES, Tracer, loop_role
from ceph_tpu.msg.messages import MOSDECSubOpRead, MOSDOpReply
from ceph_tpu.msg.messenger import Messenger

SPIN_S = 0.005


def _spin(seconds: float) -> None:
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def _messenger(n: int, tracer: Tracer | None = None) -> Messenger:
    m = Messenger(("osd", n))
    m.tracer = tracer
    return m


def _loop_s(m: Messenger) -> dict:
    return {r: m.stats[f"loop_s_{r}"] for r in LOOP_ROLES}


@pytest.fixture
def clock_on(monkeypatch):
    """The wrapper installed for this test and taken off after it, with
    process totals of the test's own."""
    monkeypatch.setattr(asyncio.events.Handle, "_run",
                        asyncio.events.Handle._run)
    monkeypatch.setattr(tracing, "_LOOP_S", {"total": 0.0, "unowned": 0.0})
    tracing.start_loop_clock(Tracer("on"))
    assert asyncio.events.Handle._run is tracing._timed_run


def test_two_owners_on_one_loop_are_each_charged_their_own_spin(clock_on):
    a, b = _messenger(1), _messenger(2)

    async def main():
        loop = asyncio.get_running_loop()
        for _ in range(5):
            with loop_role("op", a):
                loop.call_soon(_spin, SPIN_S)
            with loop_role("recovery", b):
                loop.call_soon(_spin, SPIN_S)
        await asyncio.sleep(0.01)
    asyncio.run(main())
    assert a.stats["loop_s_op"] == pytest.approx(5 * SPIN_S, rel=0.2)
    assert b.stats["loop_s_recovery"] == pytest.approx(5 * SPIN_S, rel=0.2)
    assert a.stats["loop_callbacks"] == b.stats["loop_callbacks"] == 5
    for m, mine in ((a, "op"), (b, "recovery")):
        assert all(v == 0 for r, v in _loop_s(m).items() if r != mine)


def test_owners_and_the_unowned_add_up_to_the_wrapper_exactly(
        clock_on, monkeypatch):
    # a clock of whole 2**-10 s steps: float sums of its differences are
    # exact, so any second lost or charged twice shows as inequality
    ticks = iter(range(1 << 20, 1 << 30))
    monkeypatch.setattr(tracing, "_clock", lambda: next(ticks) / 1024)
    # a's turns of two ticks or more are long: filing them is in the sum
    a, b = _messenger(1, Tracer("a")), _messenger(2)

    async def worker(m):
        for role in LOOP_ROLES:
            with loop_role(role, m):
                await asyncio.sleep(0)
                with loop_role("op"):       # nested, across awaits
                    await asyncio.sleep(0)
                asyncio.get_running_loop().call_soon(lambda: None)
            await asyncio.sleep(0)

    async def main():
        with loop_role("background", a):
            owned = [asyncio.ensure_future(worker(a))]
        owned.append(asyncio.ensure_future(worker(b)))   # main's owner
        await asyncio.gather(*owned, asyncio.sleep(0))
    asyncio.run(main())
    owned = sum(sum(_loop_s(m).values()) for m in (a, b))
    assert owned > 0 and tracing.loop_unowned() > 0
    assert [s for s in a.tracer.dump() if s["name"] == "loop_turn"]
    assert owned + tracing.loop_unowned() == tracing._LOOP_S["total"]


def test_with_every_tracer_off_nothing_is_installed(monkeypatch):
    monkeypatch.setattr(asyncio.events.Handle, "_run", tracing._HANDLE_RUN)
    off = Tracer("off", sample_rate=0.0, tail_slow_s=None)

    async def main():
        got = asyncio.Queue()

        async def dispatch(msg):
            await got.put(msg)

        server = _messenger(0, off)
        server.dispatcher = dispatch
        await server.bind()
        client = Messenger(("client", 99))
        client.tracer = off
        conn = await client.connect(*server.addr)
        for tid in range(8):
            await conn.send_message(MOSDOpReply(tid=tid, result=0, data=b"x"))
            await asyncio.wait_for(got.get(), 5)
        await client.shutdown()
        await server.shutdown()
        return server, client
    server, client = asyncio.run(main())
    assert asyncio.events.Handle._run is tracing._HANDLE_RUN
    for m in (server, client):
        assert m.stats["frames_in"] > 0
        assert all(v == 0 for v in _loop_s(m).values())
        assert m.stats["loop_callbacks"] == 0


def test_a_timer_due_while_the_loop_is_blocked_counts_its_lateness():
    m = _messenger(3)

    async def main():
        with loop_role("background", m):
            timer = asyncio.ensure_future(tracing.tick(0.01))
        asyncio.get_running_loop().call_soon(time.sleep, 0.2)
        await timer
    asyncio.run(main())
    assert m.stats["loop_timer_fires"] == 1
    assert m.stats["loop_timer_late_s"] >= 0.15
    assert m.stats["loop_timer_late_over_1s"] == 0


def test_a_long_turn_is_one_root_span_without_a_stage(clock_on):
    tr = Tracer("osd.4")
    m = _messenger(4, tr)

    async def long_step():
        _spin(2 * tracing.LONG_TURN_S)

    async def main():
        with loop_role("op", m):
            asyncio.get_running_loop().call_soon(lambda: None)   # short
            await asyncio.ensure_future(long_step())
        await asyncio.sleep(0)
    asyncio.run(main())
    turns = [s for s in tr.dump() if s["name"] == "loop_turn"]
    (turn,) = [s for s in turns if s["tags"]["callback"].endswith("long_step")]
    assert not [s for s in turns if "lambda" in s["tags"]["callback"]]
    assert turn["parent_id"] is None and "stage" not in turn["tags"]
    assert turn["tags"]["kind"] == "op"
    assert turn["tags"]["ms"] >= 1e3 * tracing.LONG_TURN_S
    assert turn["duration_ms"] == pytest.approx(turn["tags"]["ms"], abs=1e-3)


def test_a_long_turn_draws_no_sample_and_an_off_tracer_files_none(clock_on):
    on, off = Tracer("osd.5"), Tracer("osd.6", sample_rate=0.0,
                                      tail_slow_s=None)
    a, b = _messenger(5, on), _messenger(6, off)

    async def main():
        loop = asyncio.get_running_loop()
        with loop_role("op", a):
            loop.call_soon(_spin, 2 * tracing.LONG_TURN_S)
        with loop_role("op", b):
            loop.call_soon(_spin, 2 * tracing.LONG_TURN_S)
        await asyncio.sleep(0.01)
    asyncio.run(main())
    assert [s["name"] for s in on.dump()] == ["loop_turn"]
    assert on.dump()[0]["sampled"] is True
    assert off.dump() == []
    for t in (on, off):
        assert t.counters["sampler_accept"] == 0
        assert t.counters["sampler_reject"] == 0
    assert b.stats["loop_s_op"] >= 2 * tracing.LONG_TURN_S


@pytest.mark.parametrize("asked, charged", [
    ("recovery", "recovery"), ("op", "op"), ("background", "background"),
    ("no such role", "op"),
])
def test_a_shard_charges_a_sub_read_to_the_role_that_asked(
        clock_on, asked, charged):
    """The role rides the sub-read; the shard's dispatch handles it under
    that role (one it does not know is an op's)."""
    from types import SimpleNamespace

    from ceph_tpu.msg.denc import Decoder, Encoder
    from ceph_tpu.osd.daemon import OSDDaemon

    enc = Encoder()
    MOSDECSubOpRead(tid=7, oid="o", loop_role=asked).encode_payload(enc)
    msg = MOSDECSubOpRead.decode_payload(Decoder(enc.bytes()))
    assert msg.loop_role == asked
    m = _messenger(7)

    async def handle_sub_read(_msg):
        _spin(SPIN_S)

    shard = SimpleNamespace(id=7, _handle_sub_read=handle_sub_read)

    async def main():
        with loop_role("msgr", m):
            await OSDDaemon._dispatch(shard, msg)
    asyncio.run(main())
    assert m.stats[f"loop_s_{charged}"] >= SPIN_S
    assert all(v < SPIN_S for r, v in _loop_s(m).items() if r != charged)


def test_a_one_write_cluster_owns_most_of_its_loop_time(clock_on):
    """From a clean cluster's first instant to its one write's ack (the
    harness's own task built the daemons, the pool, and waited: that is
    the harness's time, as in a benchmark's set-up)."""
    from tests.integration.test_mini_cluster import Cluster, run

    def snap(messengers) -> tuple[dict, dict]:
        return ({r: sum(m.stats[f"loop_s_{r}"] for m in messengers)
                 for r in LOOP_ROLES}, dict(tracing._LOOP_S))

    async def go():
        async with Cluster(n_osds=3) as c:
            ms = [*(o.messenger for o in c.osds), c.mon.messenger,
                  c.client.messenger]
            await c.client.pool_create("rp", pg_num=4, size=3)
            await c.client.wait_clean(timeout=30)
            before = snap(ms)
            await c.client.ioctx("rp").write_full("obj", b"x" * 4096)
            return ms, before, snap(ms)
    ms, (roles0, loop0), (roles1, loop1) = run(go())
    by_role = {r: roles1[r] - roles0[r] for r in LOOP_ROLES}
    total = loop1["total"] - loop0["total"]
    unowned = loop1["unowned"] - loop0["unowned"]
    assert sum(by_role.values()) + unowned == pytest.approx(total)
    assert sum(by_role.values()) >= 0.9 * total, (by_role, total)
    assert all(by_role[r] > 0 for r in ("msgr", "op", "client")), by_role
    # every OSD's periodic loops (heartbeat, mgr report) woke through
    # the timer helper
    assert all(m.stats["loop_timer_fires"] > 0 for m in ms[:3])
