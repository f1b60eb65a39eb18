"""Span tracing — the blkin/jaeger/OpenTelemetry role (reference §5 aux).

The reference stacks three generations of tracing (LTTng tracepoints,
blkin/Zipkin spans, jaeger/opentelemetry — src/common/tracer.h, the
OSD's global ``tracing::Tracer`` at src/osd/osd_tracer.cc:9, EC
sub-reads opening child spans per shard at src/osd/ECCommon.cc:440-445).
This module provides the same capability TPU-side, now **cluster-wide**:

- every span belongs to a ``trace_id``; a compact :class:`TraceContext`
  (trace_id, parent span_id, sampled flag, reqid) rides the message
  frame header (msg/messenger.py ``encode_message``), so one client op
  yields ONE span tree spanning client, primary OSD, replica OSDs and
  the store commit — the jaeger context-propagation role of
  ``tracing::Tracer::add_span(name, parent_ctx)``;
- spans carry a wall-clock start AND a monotonic start/end pair:
  cross-daemon assembly orders spans by the monotonic stamps (shared
  within a process, immune to wall-clock steps) and falls back to wall
  time across processes — no clock-skew reordering artifacts;
- **head sampling** (``trace_sample_rate``) decides at the root whether
  a trace is exported; **tail capture** additionally exports any span
  that ends slower than ``tail_slow_s`` even when unsampled, so slow
  ops always leave forensics (the reference's osd_op_complaint_time
  slow-op history role, fused into the tracing plane);
- finished spans land in a bounded ring (``trace_ring_max``) for the
  ``dump_traces`` admin command, and sampled/slow spans additionally
  queue in an export buffer the daemon's MgrClient drains into
  MMgrReport — the mgr's TraceCollector (mgr/tracer.py) assembles the
  cluster-wide trees.  A tracer held by MgrClients queues only while
  one of them has an active mgr to send to.

- an interval that is only known once it has ended (a wait, a phase a
  worker thread ran) is filed afterwards with :meth:`Tracer.record`;
- with head sampling at 0 and tail capture off the hot path builds no
  :class:`Span` at all: every constructor hands out the one shared
  :data:`INERT` span, whose children and wire context are inert too;
- **the event loop's own clock** (the reference AsyncMessenger workers'
  ``msgr_running_*_time``, src/msg/async/Stack.h): every callback the
  loop runs is charged to the daemon and role in :data:`LOOP_OWNER`
  (``msgr``, ``op``, ``recovery``, ``background``, ``client``) in that
  daemon's ``Messenger.stats``; a callback that holds the loop for
  :data:`LONG_TURN_S` or more is a ``loop_turn`` span; periodic loops
  sleep through :func:`tick`, which counts how late the loop woke them.

Usage::

    tracer = get_tracer("osd.3")
    with tracer.span("do_op", ctx=msg.trace, reqid=msg.reqid) as sp:
        ...
        with tracer.span("ec_sub_write", parent=sp, shard=2) as child:
            sub_msg.trace = tracer.ctx_for(child)
            ...
        tracer.record("admit_wait", parent=sp, start_mono=t0,
                      end_mono=time.monotonic(), stage="queue")
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field

#: default ring capacity; per-tracer override via ``trace_ring_max``
#: (config) -> Tracer(ring_max=...) — satellite of the observability PR
DEFAULT_RING_MAX = 2048

#: export-buffer bound (spans waiting for the next MMgrReport drain);
#: overflow is counted in ``export_dropped``, never blocks the I/O path
DEFAULT_EXPORT_MAX = 4096

#: stage vocabulary for critical-path breakdowns (mgr/tracer.py): every
#: span may tag ``stage`` with one of these; unknown stages fold into
#: "other"
STAGES = ("net", "queue", "device", "store", "other")

# span/trace ids are unique per process by construction (counter) and
# across processes with overwhelming probability (random 24-bit salt in
# the high bits) — the mgr assembles spans from many daemons by id
_ID_SALT = random.getrandbits(24) << 38
_IDS = itertools.count(1)


def _next_id() -> int:
    return _ID_SALT | next(_IDS)


@dataclass(frozen=True)
class TraceContext:
    """The compact wire context (the jaeger SpanContext role): enough
    for a remote daemon to open a child span of a foreign parent."""

    trace_id: int
    span_id: int          # the PARENT span on the sending side
    sampled: bool = True
    reqid: str = ""

    def encode(self, enc) -> None:
        enc.u64(self.trace_id)
        enc.u64(self.span_id)
        enc.bool_(self.sampled)
        enc.str_(self.reqid)

    @classmethod
    def decode(cls, dec) -> "TraceContext":
        return cls(dec.u64(), dec.u64(), dec.bool_(), dec.str_())


@dataclass(slots=True)
class Span:
    name: str
    span_id: int
    parent_id: int | None
    start: float                      # wall clock (time.time)
    trace_id: int = 0
    sampled: bool = True
    daemon: str = ""
    start_mono: float = 0.0           # monotonic, for skew-free ordering
    end_mono: float | None = None
    tags: dict = field(default_factory=dict)
    duration: float | None = None
    #: the tracer that files this span, so code that only holds the
    #: span (a batching service serving many daemons) can file a child
    #: where the parent lives; None on the inert span
    tracer: "Tracer | None" = field(default=None, repr=False, compare=False)

    def tag(self, **kv) -> None:
        self.tags.update(kv)

    def dump(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "daemon": self.daemon,
            "start": self.start,
            "start_mono": self.start_mono,
            "end_mono": self.end_mono,
            "sampled": self.sampled,
            "duration_ms": (
                round(self.duration * 1e3, 3)
                if self.duration is not None else None
            ),
            "tags": dict(self.tags),
        }


class _InertSpan(Span):
    """What every constructor returns when nothing would keep the span
    (trace unsampled, tail capture off): one shared object that takes
    no tags, is its own no-op context manager, and makes its children
    and its wire context inert too."""

    __slots__ = ()

    def tag(self, **kv) -> None:
        pass

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        return False


INERT: Span = _InertSpan(
    name="", span_id=0, parent_id=None, start=0.0, sampled=False)

#: the span that work started by the running task is filed under: the
#: op's ``do_op`` while an OSD executes it, narrowed to ``ec_encode``,
#: ``recover_object`` or ``recovery_decode`` around the calls whose
#: callees (the batching services, sub-reads, pushes) parent their own
#: spans there.  Tasks inherit it at creation, so a gather of sub-ops
#: sees its op's span and nothing crosses between concurrent ops.
CURRENT_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "ceph_tpu_current_span", default=None)


class scope:
    """``with scope(span):`` makes ``span`` the running task's
    :data:`CURRENT_SPAN` for the block."""

    __slots__ = ("span", "token")

    def __init__(self, span: Span | None):
        self.span = span

    def __enter__(self) -> Span | None:
        self.token = CURRENT_SPAN.set(self.span)
        return self.span

    def __exit__(self, *exc) -> bool:
        try:
            CURRENT_SPAN.reset(self.token)
        except ValueError:
            # a task garbage-collected at loop teardown runs this exit
            # in a foreign Context; the var dies with the task
            pass
        return False


# -- the event loop's own clock ------------------------------------------------

#: whom the loop time of the running task or callback is charged to:
#: ``(messenger, role)`` of a daemon, whose ``Messenger.stats`` keeps the
#: seconds and whose ``tracer`` files the long turns.  Tasks inherit it
#: at creation, like :data:`CURRENT_SPAN`; None outside every daemon (a
#: harness's own tasks, the handle a worker thread schedules to complete
#: its future)
LOOP_OWNER: contextvars.ContextVar = contextvars.ContextVar(
    "ceph_tpu_loop_owner", default=None)

#: what a daemon's loop time is spent on
LOOP_ROLES = ("msgr", "op", "recovery", "background", "client")

#: a callback that holds the loop this long or longer is a ``loop_turn``
LONG_TURN_S = 1e-3

#: the counters every messenger's ``stats`` starts with beside the wire's
LOOP_STATS = (*(f"loop_s_{r}" for r in LOOP_ROLES), "loop_callbacks",
              "loop_timer_fires", "loop_timer_late_s",
              "loop_timer_late_over_1s")

_ROLE_KEY = {r: f"loop_s_{r}" for r in LOOP_ROLES}
_HANDLE_RUN = asyncio.events.Handle._run
#: the process's timed loop seconds: all of them, and those of no owner
_LOOP_S = {"total": 0.0, "unowned": 0.0}
_clock = time.monotonic         # the spans' clock


class _Turn:
    """The callback one thread's loop is running: the owner its time is
    charged to since ``t`` (None: no callback is being timed)."""

    __slots__ = ("owner", "t")

    def __init__(self):
        self.owner = self.t = None


class _Turns(threading.local):
    def __init__(self):
        self.turn = _Turn()


_TURNS = _Turns()


def _charge(owner, seconds: float) -> None:
    if owner is None:
        _LOOP_S["unowned"] += seconds
    else:
        owner[0].stats[_ROLE_KEY[owner[1]]] += seconds


def _switch(owner) -> None:
    """The running task's owner changes inside a callback: the piece so
    far is the old owner's."""
    turn = _TURNS.turn
    if turn.t is not None:
        now = _clock()
        _charge(turn.owner, now - turn.t)
        turn.owner, turn.t = owner, now


def _timed_run(handle) -> None:
    """``asyncio.events.Handle._run`` with the loop's clock on: the
    callback's wall time (a wait for the GIL a worker thread holds
    included) goes to its owner's ``loop_s_<role>``, split where a
    :class:`loop_role` scope changes the owner.  A long turn's span is
    filed inside the timed interval, so that filing it is charged too."""
    turn = _TURNS.turn
    owner = turn.owner = handle._context.get(LOOP_OWNER)
    t0 = turn.t = _clock()
    _HANDLE_RUN(handle)
    t1 = _clock()
    last = turn.owner
    if t1 - t0 >= LONG_TURN_S:
        _long_turn(handle, last, t0, t1)
        t1 = _clock()
    piece = t1 - turn.t
    turn.t = None
    _LOOP_S["total"] += t1 - t0
    if last is None:            # _charge, inline: this runs every callback
        _LOOP_S["unowned"] += piece
    else:
        last[0].stats[_ROLE_KEY[last[1]]] += piece
    if owner is not None:
        owner[0].stats["loop_callbacks"] += 1


def _long_turn(handle, owner, t0: float, t1: float) -> None:
    """A turn that held the loop ``LONG_TURN_S`` or more: a root
    ``loop_turn`` span in the tracer of the owner it ended under, with
    no stage, so no op's tree and no stage sum sees it; ``idle_gaps`` of
    a device trace names the device's idle time under it
    ``loop_turn:<role>``.  No head-sampling draw: a turn is a sampled
    trace where its tracer samples every trace, else tail capture's."""
    tracer = None if owner is None else owner[0].tracer
    if tracer is None or not tracer.on:
        return
    cb = handle._callback
    task = getattr(cb, "__self__", None)
    if isinstance(task, asyncio.Task):
        cb = task.get_coro()
    tracer.record(
        "loop_turn", start_mono=t0, end_mono=t1,
        sampled=tracer.sample_rate >= 1.0, kind=owner[1],
        callback=getattr(cb, "__qualname__", type(cb).__name__),
        ms=1e3 * (t1 - t0))


def start_loop_clock(tracer) -> None:
    """Time every callback the loop runs from now on, if ``tracer`` (that
    of a messenger that starts serving) keeps spans: installed once a
    process, and never where every tracer is off."""
    if tracer is not None and tracer.on \
            and asyncio.events.Handle._run is not _timed_run:
        asyncio.events.Handle._run = _timed_run


def loop_role_now() -> str | None:
    """The role the running task's loop time is charged to, if any."""
    owner = LOOP_OWNER.get()
    return None if owner is None else owner[1]


def loop_unowned() -> float:
    """Timed loop seconds of this process that no daemon owned."""
    return _LOOP_S["unowned"]


class loop_role:
    """``with loop_role(role):`` charges the running task's loop time,
    and that of the tasks it creates, to ``role`` of its owner (of
    ``messenger``, where given) until the block ends."""

    __slots__ = ("owner", "token")

    def __init__(self, role: str, messenger=None):
        if messenger is None:
            messenger = (LOOP_OWNER.get() or (None,))[0]
        self.owner = None if messenger is None else (messenger, role)

    def __enter__(self):
        _switch(self.owner)
        self.token = LOOP_OWNER.set(self.owner)
        return self

    def __exit__(self, *exc) -> bool:
        try:
            LOOP_OWNER.reset(self.token)
        except ValueError:
            pass        # a foreign Context at loop teardown, as scope's
        _switch(LOOP_OWNER.get())
        return False


async def tick(interval: float) -> None:
    """``asyncio.sleep(interval)`` for a loop that wakes on a period,
    counting into its owner's stats how late the loop woke it:
    ``loop_timer_fires``, ``loop_timer_late_s`` and
    ``loop_timer_late_over_1s``, kept whether tracing is on or off."""
    t0 = time.monotonic()
    await asyncio.sleep(interval)
    owner = LOOP_OWNER.get()
    if owner is not None:
        late = max(time.monotonic() - t0 - interval, 0.0)
        stats = owner[0].stats
        stats["loop_timer_fires"] += 1
        stats["loop_timer_late_s"] += late
        if late > 1.0:
            stats["loop_timer_late_over_1s"] += 1


class _OpenSpan:
    """``with tracer.span(...) as sp``: finishes the span on exit."""

    __slots__ = ("tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer, self.span = tracer, span

    def __enter__(self) -> Span:
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.span.tags["error"] = exc_type.__name__
        self.tracer.finish_span(self.span)
        return False


class Tracer:
    """One per daemon (the osd_tracer.cc global's role).

    ``sample_rate``: head-sampling probability for NEW traces started
    here (joined traces inherit the context's verdict).
    ``tail_slow_s``: spans slower than this export even when their
    trace is unsampled (tail capture; None disables).
    """

    def __init__(self, name: str, *, ring_max: int | None = None,
                 sample_rate: float = 1.0,
                 tail_slow_s: float | None = 1.0):
        self.name = name
        self.sample_rate = sample_rate
        self.tail_slow_s = tail_slow_s
        self._ring: deque[Span] = deque(
            maxlen=ring_max if ring_max else DEFAULT_RING_MAX)
        self._export: deque[Span] = deque()
        self._export_max = DEFAULT_EXPORT_MAX
        #: who drains the export buffer: None for a tracer no MgrClient
        #: holds (a client's; its owner drains it), else the MgrClients
        #: holding it that have an active mgr — none, and nothing queues
        self.drainers: set | None = None
        self._lock = threading.Lock()
        self._rng = random.Random()
        #: the tracing plane's own telemetry (exported by the
        #: prometheus module: spans recorded/dropped, sampler verdicts)
        self.counters: dict[str, int] = {
            "spans_recorded": 0, "spans_dropped": 0,
            "sampler_accept": 0, "sampler_reject": 0,
            "spans_exported": 0, "export_dropped": 0,
        }

    def set_ring_max(self, n: int) -> None:
        """Re-bound the ring (``trace_ring_max`` live update)."""
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(int(n), 1))

    # -- span construction ---------------------------------------------

    def _head_sample(self) -> bool:
        ok = self._rng.random() < self.sample_rate
        self.counters["sampler_accept" if ok else "sampler_reject"] += 1
        return ok

    @property
    def on(self) -> bool:
        """Whether this tracer keeps any span at all."""
        return self.sample_rate > 0 or self.tail_slow_s is not None

    def wants(self, sampled: bool) -> bool:
        """Whether a span of a trace with this head verdict is built at
        all: an unsampled one only for tail capture to look at."""
        return sampled or self.tail_slow_s is not None

    def _make_span(self, name: str, parent: Span | None,
                   ctx: TraceContext | None, tags: dict,
                   start_mono: float | None = None,
                   sampled: bool | None = None) -> Span:
        if parent is not None:
            if parent is INERT:
                return INERT
            trace_id, parent_id, sampled = (
                parent.trace_id, parent.span_id, parent.sampled)
        elif ctx is not None:
            trace_id, parent_id, sampled = (
                ctx.trace_id, ctx.span_id, ctx.sampled)
            if ctx.reqid and "reqid" not in tags:
                tags["reqid"] = ctx.reqid
        else:
            trace_id, parent_id = _next_id(), None
            if sampled is None:
                sampled = self._head_sample()
        if not self.wants(sampled):
            return INERT
        now = time.monotonic()
        if start_mono is None:
            start_mono = now
        return Span(
            name=name, span_id=_next_id(), parent_id=parent_id,
            trace_id=trace_id, sampled=sampled, daemon=self.name,
            start=time.time() - (now - start_mono), start_mono=start_mono,
            tags=tags, tracer=self,
        )

    def span(self, name: str, parent: Span | None = None,
             ctx: TraceContext | None = None, **tags):
        """Context manager around the spanned work; yields the span
        (the inert one when nothing would keep it)."""
        sp = self._make_span(name, parent, ctx, tags)
        return sp if sp is INERT else _OpenSpan(self, sp)

    def start_span(self, name: str, parent: Span | None = None,
                   ctx: TraceContext | None = None, **tags) -> Span:
        """Non-contextmanager form (spans closed by :meth:`finish_span`
        — callers whose open/close straddle callbacks)."""
        return self._make_span(name, parent, ctx, tags)

    def finish_span(self, sp: Span) -> None:
        if sp is INERT:
            return
        sp.end_mono = time.monotonic()
        sp.duration = max(sp.end_mono - sp.start_mono, 0.0)
        self.finish(sp)

    def record(self, name: str, *, parent: Span | None = None,
               ctx: TraceContext | None = None, start_mono: float,
               end_mono: float, sampled: bool | None = None,
               **tags) -> Span:
        """File a finished span whose interval is already known: a wait
        (only known once it has ended) or a phase a worker thread ran
        (it cannot hold a context manager across the loop).  A root
        given ``sampled`` takes that verdict and draws none."""
        sp = self._make_span(name, parent, ctx, tags, start_mono, sampled)
        if sp is not INERT:
            sp.end_mono = end_mono
            sp.duration = max(end_mono - start_mono, 0.0)
            self.finish(sp)
        return sp

    def ctx_for(self, sp: Span | None) -> TraceContext | None:
        """The wire context making ``sp`` the remote side's parent;
        None (the message rides untraced) for no span or the inert
        one."""
        if sp is None or sp is INERT:
            return None
        return TraceContext(
            trace_id=sp.trace_id, span_id=sp.span_id,
            sampled=sp.sampled, reqid=str(sp.tags.get("reqid", "")),
        )

    # -- the sink ------------------------------------------------------

    def finish(self, sp: Span) -> None:
        slow = (
            self.tail_slow_s is not None
            and sp.duration is not None
            and sp.duration >= self.tail_slow_s
        )
        if slow:
            sp.tags.setdefault("slow", True)
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.counters["spans_dropped"] += 1
            self._ring.append(sp)
            self.counters["spans_recorded"] += 1
            if (sp.sampled or slow) and (self.drainers is None
                                         or self.drainers):
                if len(self._export) >= self._export_max:
                    self._export.popleft()
                    self.counters["export_dropped"] += 1
                self._export.append(sp)
                self.counters["spans_exported"] += 1

    def drain_export(self, limit: int = 512) -> list[dict]:
        """Consume up to ``limit`` exported spans (the MgrClient's
        MMgrReport feed); each is a ``Span.dump()`` dict."""
        out: list[Span] = []
        with self._lock:
            while self._export and len(out) < limit:
                out.append(self._export.popleft())
        return [s.dump() for s in out]

    def dump(self, limit: int = 200) -> list[dict]:
        with self._lock:
            spans = list(self._ring)[-limit:]
        return [s.dump() for s in spans]

    def find(self, **tags) -> list[Span]:
        """Test/forensics helper: spans whose tags contain all of
        ``tags``."""
        with self._lock:
            return [
                s for s in self._ring
                if all(s.tags.get(k) == v for k, v in tags.items())
            ]


_TRACERS: dict[str, Tracer] = {}
_REG_LOCK = threading.Lock()


def get_tracer(name: str) -> Tracer:
    with _REG_LOCK:
        t = _TRACERS.get(name)
        if t is None:
            t = _TRACERS[name] = Tracer(name)
        return t


def device_tracer() -> Tracer:
    """The process-wide device-launch profiling ring: the decode/scrub
    batchers, the encode farm and the mgr analytics engine wrap each
    XLA launch in a span here, tagged with bucket shape, occupancy and
    block-until-ready duration — batch padding and host<->device copy
    waste become directly visible (the BENCH_ALL gap diagnosis plane)."""
    return get_tracer("device")


def launch_span(wait_name: str, waiters, **tags):
    """The device tracer's ``xla_launch`` span around one launch of a
    batching service.  ``waiters`` are ``(parent span or None, arrival
    on the monotonic clock)`` of the requests the launch serves: each
    traced one gets a ``wait_name`` child (``stage="queue"``: coalescing
    window + executor queue + host packing) from its arrival to now,
    filed where its parent lives, and the launch is tagged with their
    ids as ``parents``."""
    now = time.monotonic()
    parents = []
    for parent, arrived in waiters:
        if parent is not None and parent is not INERT:
            parent.tracer.record(wait_name, parent=parent, stage="queue",
                                 start_mono=arrived, end_mono=now)
            parents.append(parent.span_id)
    if parents:
        tags["parents"] = parents
    return device_tracer().span("xla_launch", stage="device", **tags)
