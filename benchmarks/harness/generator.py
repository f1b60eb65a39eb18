"""The one traffic generator.  A traffic mix is a data file
(``benchmarks/traffic/<name>.json``) of these parameters:

  object_bytes, distinct_payloads, object_name   what is written
  prefill_objects                 written in set-up, ``in_flight`` at a time,
                                  after one object to every PG (see
                                  ``touch_every_pg``)
  loop: {op} | null               closed loop, ``in_flight`` ops, through
                                  warm-up and window.  ``write_full``: a new
                                  object per op.  ``read``: one whole
                                  prefilled object per op, drawn uniformly
                                  with replacement by a generator seeded by a
                                  constant; every read's bytes are compared
                                  with the payload acknowledged for that name
  warmup_ops                      loop ops that must end before the window
  fault: {stop_osd, out} | null   the OSD is stopped and marked down by the
                                  mon.  ``out`` true (or absent): then marked
                                  out, and the window opens when that is
                                  acknowledged.  ``out`` false: it stays in
                                  (degraded, nothing may recover), and the
                                  window opens once the client's map shows
                                  it down.  Applied last in set-up; before a
                                  ``read`` loop starts, so that no read is in
                                  flight to an OSD that stops
  counter, counter_metric         a ``Cluster.counters()`` key read through
                                  the window, and the end-to-end metric its
                                  growth per second is reported as
  attempted_counter               the key whose growth is ``attempted`` then
  in_flight                       ops in flight, in set-up and in the loop
  warm_matrices                   encode-service shapes to compile first
  slice_seconds, op_timeout_s, verify_sample
  trace: {start_s, seconds}       where in the window a traced run traces

Object names, and which of them a read loop reads in what order, never
depend on the seed, so every op lands on the same PG and OSDs in every
run; the seed makes the bytes.  A mix with another object size, a read
loop or an OSD that is down and in is a data file; a pool with another
code or plugin is a configuration file (``harness/cluster.py``) and, for
what its shards must hold, a reference beside it (``harness/verify.py``).
"""

from __future__ import annotations

import asyncio
import gc
import json
import logging
import time

import numpy as np

from . import verify
from .window import Window

log = logging.getLogger("bench")
SET_UP_TIMEOUT = 300.0
POLL_S = 0.05       # how often the traffic's counter is read
DONE_S = 1.0        # a counter still for this long at the end has ended
READ_ORDER_SEED = 33    # which objects a read loop reads: never ``--seed``


class WrongBytes(Exception):
    """A read was answered, with other bytes than were acknowledged."""


def payload(seed: int, i: int, nbytes: int) -> bytes:
    return np.random.default_rng([seed, 2, i]).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


class Traffic:
    def __init__(self, c, params: dict, seed: int):
        self.c = c
        self.p = params
        self.seed = seed
        self.blobs = [payload(seed, i, params["object_bytes"])
                      for i in range(params["distinct_payloads"])]
        self.next_n = 0
        self.acked: dict[str, int] = {}     # name -> index of its payload
        self.readable: list[str] = []       # the prefilled names, in order
        self.ended = 0
        self.wrong = 0      # reads answered with other bytes, at any time
        self.reads = (params.get("loop") or {}).get("op") == "read"
        self.lost_osd: int | None = None
        self.loss: str | None = None    # "recovers" | "stays_degraded"
        self.window: Window | None = None
        self.stop = False
        self._workers: list[asyncio.Task] = []
        self._read_order = np.random.default_rng(READ_ORDER_SEED)
        self._loop_ops = {"write_full": self._write_one,
                          "read": self._read_one}

    def name(self, n: int) -> str:
        return self.p["object_name"].format(n=n)

    async def _timed(self, name: str, op) -> bool:
        """One op from submit to answer: ``op()`` returns the bytes it
        moved.  An op that raises is counted as failed, the run goes on."""
        t0 = time.monotonic()
        try:
            nbytes, ok = await op(), True
        except Exception as exc:
            log.warning("%s failed: %r", name, exc)
            nbytes, ok = 0, False
        t1 = time.monotonic()
        self.ended += 1
        if self.window is not None:
            self.window.op_ended(t1, t1 - t0, nbytes, ok)
        return ok

    async def _write_one(self, name: str | None = None) -> None:
        n, self.next_n = self.next_n, self.next_n + 1
        name, i = name or self.name(n), n % len(self.blobs)

        async def op() -> int:
            await self.c.io.write_full(name, self.blobs[i])
            return len(self.blobs[i])

        if await self._timed(name, op):
            self.acked[name] = i

    async def _read_one(self) -> None:
        name = self.readable[int(self._read_order.integers(
            len(self.readable)))]
        want = self.blobs[self.acked[name]]

        async def op() -> int:
            got = await self.c.io.read(name)
            if got != want:
                self.wrong += 1
                raise WrongBytes(f"{len(got)} bytes read are not the "
                                 f"{len(want)} acknowledged")
            return len(got)

        await self._timed(name, op)

    async def _bounded(self, names: list) -> None:
        sem = asyncio.Semaphore(self.p["in_flight"])

        async def one(name):
            async with sem:
                await self._write_one(name)

        before = len(self.acked)
        await asyncio.gather(*(one(name) for name in names))
        if len(self.acked) - before != len(names):
            raise RuntimeError("set-up: some writes were not acknowledged")

    async def touch_every_pg(self) -> None:
        """One object to every PG, no two of them to one PG.  The first
        write to a PG creates its collections on the acting OSDs, and
        two first writes racing there fail one of them (EIO from
        ``FileExistsError: collection ... exists``, chip run of PR 24):
        a first-touch cost, so it is paid in set-up, once per PG."""
        by_pg: dict[int, str] = {}
        n = 0
        while len(by_pg) < self.c.pool["pg_num"]:
            name = f"benchmark_touch_object{n}"
            by_pg.setdefault(self.c.pg_of(name).ps, name)
            n += 1
        await self._bounded(list(by_pg.values()))

    async def prefill(self) -> None:
        first, n = self.next_n, self.p["prefill_objects"]
        await self._bounded([None] * n)
        self.readable = [self.name(i) for i in range(first, first + n)]

    async def _worker(self, one) -> None:
        while not self.stop:
            await one()

    async def start_loop_and_warm_up(self) -> None:
        loop = self.p.get("loop")
        if not loop:
            return
        one = self._loop_ops.get(loop["op"])
        if one is None:
            raise ValueError(f"unknown loop op {loop['op']!r}")
        if self.reads and not self.readable:
            raise ValueError("a read loop needs prefill_objects")
        target = self.ended + self.p["warmup_ops"]
        self._workers = [asyncio.ensure_future(self._worker(one))
                         for _ in range(self.p["in_flight"])]
        deadline = time.monotonic() + SET_UP_TIMEOUT
        while self.ended < target:
            if time.monotonic() > deadline:
                raise TimeoutError("warm-up ops never ended")
            await asyncio.sleep(0.01)

    async def apply_fault(self) -> int | None:
        """Stop the OSD, wait until the client's map shows it down and,
        unless the fault says ``"out": false``, mark it out.  Returns
        the epoch the client's map must reach before verify."""
        fault = self.p.get("fault")
        if not fault:
            return None
        c, victim = self.c, fault["stop_osd"] % self.c.n_osds
        self.lost_osd = victim
        self.loss = "recovers" if fault.get("out", True) \
            else "stays_degraded"
        await c.osds[victim].stop()
        c.osds[victim] = None
        deadline = time.monotonic() + SET_UP_TIMEOUT
        while c.client.osdmap.is_up(victim):
            if time.monotonic() > deadline:
                raise TimeoutError(f"mon never marked osd.{victim} down")
            await c.client._wait_new_map(c.client.osdmap.epoch, timeout=1.0)
        if self.loss == "stays_degraded":
            return None
        code, rs, _ = await c.client.command(
            {"prefix": "osd out", "id": str(victim)})
        if code != 0:
            raise RuntimeError(f"osd out: {rs}")
        code, rs, data = await c.client.command({"prefix": "status"})
        if code != 0:
            raise RuntimeError(f"status: {rs}")
        return json.loads(data)["epoch"]

    async def run_window(self, seconds: float, on_open=None) -> Window:
        """Open the window now and run it to its end.  The traffic's
        counter is read every 50 ms: a reading is kept at every slice
        boundary, and if the counter stopped growing before the end
        (recovery done), the last instant it grew ends the work."""
        gc.collect()
        w = Window(time.monotonic(), seconds, self.p["slice_seconds"])
        self.window = w
        if on_open is not None:
            on_open(w)
        key = self.p.get("counter")
        value, grew_at = None, w.t0
        while (now := time.monotonic()) < w.t_end:
            if key:
                read = self.c.counters().get(key, 0)
                if value is None or read > value:
                    value, grew_at = read, now
                if now >= w.t0 + len(w.readings) * w.slice_s:
                    w.readings.append((now, read))
            await asyncio.sleep(min(POLL_S if key else seconds,
                                    w.t_end - now))
        if key:
            w.readings.append((time.monotonic(),
                               self.c.counters().get(key, 0)))
            if w.readings[-1][1] == value and w.t_end - grew_at > DONE_S:
                w.t_done = grew_at
        self.stop = True
        return w

    async def drain(self) -> None:
        """Let the ops in flight at the window's close end; they are
        neither attempted nor failed."""
        if self._workers:
            await asyncio.wait_for(asyncio.gather(*self._workers),
                                   self.p["op_timeout_s"] + 5)

    def sample(self) -> dict[str, bytes]:
        """A seeded sample of acknowledged objects and their bytes."""
        rng = np.random.default_rng([self.seed, 3])
        names = sorted(self.acked)
        pick = rng.choice(len(names), min(self.p["verify_sample"],
                                          len(names)), replace=False)
        return {names[i]: self.blobs[self.acked[names[i]]] for i in pick}

    async def verify(self, acting_before: dict | None) -> dict:
        return await verify.verify_sample(
            self.c, self.sample(), in_flight=self.p["in_flight"],
            acting_before=acting_before, lost_osd=self.lost_osd,
            loss=self.loss)
