"""LaunchBatcher: the one skeleton under the encode, decode and scrub
engines (encode_service.py, decode_batcher.py, scrub_batcher.py).

Concurrent asyncio callers hand in small independent pieces of device
work; pieces filed under one key within one coalescing window are served
by ONE fixed-shape launch.  What the engines share is here, once:

- *request side*: :meth:`LaunchBatcher.submit` files a :class:`Request`
  (item, future, the caller's span in scope, arrival — by name) and arms
  the one ``call_later(window_s)``;
- *dispatch side*: the flush hands every group to a worker thread, where
  the engine's plan (``_run_group``) packs, launches and cuts back; a
  plan that raises is counted and every waiter is answered from the
  engine's host path (``_host_group``), so no caller fails.  Whether a
  launch is worth its cost is the GROUP's question: one whose requests
  together carry fewer than the engine's ``min_bytes`` is answered by
  ``_host_group`` at the flush, inline (``host_groups`` /
  ``host_requests``: a decision, not a fallback);
- :data:`device_matrices`, the process's one LRU of device-resident
  operand matrices (``MatrixErasureCode._apply_device`` uses it too);
- the warm set: ``_prewarm`` compiles a ladder of shape keys once
  however many threads ask (one process's OSDs all prewarm the shared
  engine); ``_launching`` counts a launch outside the set as cold, bumps
  the per-bucket counters and opens the ``xla_launch`` span;
- the pow2 bucket helpers, and the registry behind each module's
  ``shared()`` / ``reset_shared()``.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import threading
import time
from typing import NamedTuple

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.metrics import BucketCounters

#: narrower payloads share this bucket (no pow2 shape per small size)
DEFAULT_MIN_BUCKET = 4096
#: widest bucket; wider payloads split into TILE_CAP-wide lanes (GF
#: matmuls and crc folds compose by columns), so the launch-shape set is
#: CLOSED: log2(TILE_CAP/MIN_BUCKET)+1 buckets, all prewarmed
DEFAULT_TILE_CAP = 1 << 16
#: ceiling on the batch dimension of one launch; larger groups split
#: into several full launches (shapes stay fixed either way)
DEFAULT_MAX_BATCH = 8
#: entries of the device-matrix LRU: erasure signatures rotate during
#: multi-PG recovery, so a few slots would thrash re-uploads
_BITS_CACHE_SIZE = 256


# -- bucket helpers ----------------------------------------------------------

def pow2_bucket(n: int, floor: int = DEFAULT_MIN_BUCKET) -> int:
    """Smallest power-of-two >= max(n, floor)."""
    n = max(n, floor, 1)
    return 1 << (n - 1).bit_length()


def bucket_lanes(nbytes: int, *, min_bucket: int, tile_cap: int) -> list:
    """Split ``nbytes`` columns into lanes of ``(offset, width, bucket)``,
    every bucket from the CLOSED ladder [min_bucket .. tile_cap]: wider
    payloads split into full tile_cap lanes, narrower ones pad up to
    their pow2 bucket — a prewarmed ladder covers every payload size."""
    if nbytes <= 0:
        return []
    if nbytes <= tile_cap:
        return [(0, nbytes, pow2_bucket(nbytes, min_bucket))]
    return [(off, min(tile_cap, nbytes - off), tile_cap)
            for off in range(0, nbytes, tile_cap)]


def bucket_ladder(min_bucket: int, tile_cap: int, widths=None) -> list[int]:
    """Every bucket of :func:`bucket_lanes` (and of ``widths``), sorted."""
    buckets = {pow2_bucket(min(x, tile_cap), min_bucket)
               for x in widths or ()}
    w = pow2_bucket(min_bucket, 1)
    while w <= tile_cap:
        buckets.add(w)
        w <<= 1
    return sorted(buckets)


def batch_chunks(items: list, cap: int):
    """Cut ``items`` into launches of ``(chunk, b)``: two batch shapes
    only (1 and ``cap``), so a bucket's launches share ONE program."""
    for at in range(0, len(items), cap):
        chunk = items[at:at + cap]
        yield chunk, 1 if len(chunk) == 1 else cap


# -- the device-matrix LRU ---------------------------------------------------

def matrix_key(M: np.ndarray) -> bytes:
    """A byte matrix's identity (an erasure code's signature): groups
    requests and keys the device LRU."""
    return M.shape[0].to_bytes(2, "little") + M.tobytes()


class DeviceMatrixCache:
    """LRU of operand matrices resident where launches want them: the
    default device, or a sharding (replicated over a mesh at fill time)."""

    def __init__(self, size: int = _BITS_CACHE_SIZE):
        self.size = size
        self._lru: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()   # the dict's; never held uploading

    def get(self, key, build, placement=None):
        """The array under ``(key, placement)``; a miss uploads build()."""
        import jax

        key = (key, placement)
        with self._lock:
            hit = self._lru.get(key)
            if hit is not None:
                self._lru.move_to_end(key)
                return hit
        hit = jax.device_put(build(), placement)
        with self._lock:
            self._lru[key] = hit
            while len(self._lru) > self.size:
                self._lru.popitem(last=False)
        return hit


#: the process's one cache (engines and the plugin's sync path)
device_matrices = DeviceMatrixCache()


# -- the skeleton ------------------------------------------------------------

class MatMul(NamedTuple):
    """Encode's and decode's item: (out, k) bytes @ (k, S) over GF(2^8).
    ``tags`` say what the matrix is where the caller knows more than its
    shape (``kind``, ``lost_node``): they go on the launch's span."""
    M: np.ndarray
    rows: np.ndarray
    tags: dict | None = None


class Request(NamedTuple):
    """One waiter of a launch."""
    item: tuple             #: the engine's own payload
    fut: asyncio.Future
    span: object            #: the caller's span in scope, or None
    arrived: float          #: on the monotonic clock


def host_matmul_group(_key, group: list[Request]) -> list[np.ndarray]:
    """The host answer to a group of :class:`MatMul` requests."""
    from ceph_tpu.ops.gf256 import gf_matmul

    return [gf_matmul(req.item.M, req.item.rows) for req in group]


class LaunchBatcher:
    """Window, dispatch, warm set and launch bookkeeping of one engine.

    A subclass supplies ``_run_group(key, group) -> outs`` (worker
    thread: ONE or a few fixed-shape launches for the group, each inside
    :meth:`_launching`; outs in request order) and ``_host_group(key,
    group) -> outs`` (the always-correct host answer)."""

    #: the stats key a plan that raised is counted under
    fallback_stat = "fallbacks"
    #: the ``stage="queue"`` child span filed under each traced waiter a
    #: launch serves (None: the engine's callers are not traced per op)
    wait_name: str | None = None
    #: a flushed group whose requests together carry fewer real bytes
    #: (``_group_bytes``) is answered on the host at the flush: a launch
    #: costs more than so small a product does.  0: every group launches
    min_bytes = 0

    def __init__(self, family: str, *, window_s: float, placement=None):
        self.window_s = window_s
        self.stats = collections.Counter()
        self.metrics = BucketCounters(family)
        self._placement = placement
        self._pending: dict[object, list[Request]] = {}
        self._flush_handle = None
        #: shapes compiled by prewarm or an earlier launch; a launch
        #: outside it pays an XLA compile in the I/O path
        self._warm: set[tuple] = set()
        self._warm_claimed: set[tuple] = set()
        # guards ONLY those two sets: never held across a compile
        self._warm_cv = threading.Condition()

    # -- request side --------------------------------------------------

    def submit(self, key, item) -> asyncio.Future:
        """File ``item`` under ``key`` for the next flush; the first
        launch that serves it files its wait under the caller's span."""
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._pending.setdefault(key, []).append(Request(
            item, fut, tracing.CURRENT_SPAN.get(), time.monotonic()))
        if self._flush_handle is None:
            self._flush_handle = loop.call_later(self.window_s, self._flush)
        return fut

    # -- dispatch side -------------------------------------------------

    def _flush(self) -> None:
        """call_later callback.  The JAX dispatch (and any cold compile)
        must NOT run on the event loop — it would stall heartbeats and
        op processing for every daemon in the process."""
        self._flush_handle = None
        pending, self._pending = self._pending, {}
        loop = asyncio.get_running_loop()
        for key, group in pending.items():
            if self.min_bytes and self._group_bytes(group) < self.min_bytes:
                # here, inline: so small a product is a tenth of a
                # millisecond of numpy, a hand-off to a worker thread
                # waits ten times that for its thread (PERF.md, PR 35)
                self.stats["host_groups"] += 1
                self.stats["host_requests"] += len(group)
                self._answer(group, self._host_group(key, group))
            else:
                loop.create_task(self._dispatch(key, group))

    def _group_bytes(self, group: list[Request]) -> int:
        """The real bytes a launch for ``group`` would carry (engines
        with a ``min_bytes`` say how to count theirs)."""
        raise NotImplementedError

    @staticmethod
    def _answer(group: list[Request], outs) -> None:
        for req, out in zip(group, outs):
            if not req.fut.done():
                req.fut.set_result(out)

    async def _dispatch(self, key, group: list[Request]) -> None:
        try:
            outs = await asyncio.to_thread(self._run_group, key, group)
        except Exception:
            # device failure: answer every waiter from the host path
            # (always correct), don't fail client ops
            self.stats[self.fallback_stat] += 1
            outs = await asyncio.to_thread(self._host_group, key, group)
        self._answer(group, outs)

    def _matrix(self, key, build):
        """``build()`` resident where this engine's launches want it."""
        from ceph_tpu.ops.compile_cache import ensure_persistent_cache

        ensure_persistent_cache()
        return device_matrices.get(key, build, self._placement)

    def _bits(self, M: np.ndarray):
        """``M``'s GF(2) bit-matrix expansion, resident likewise."""
        from ceph_tpu.ops.gf256 import gf_matrix_to_bitmatrix

        return self._matrix(matrix_key(M), lambda: gf_matrix_to_bitmatrix(M))

    @contextlib.contextmanager
    def _launching(self, shape_key: tuple, waiters, *, kind: str,
                   guard: str, w: int, b_real: int, real_bytes: int,
                   padded_bytes: int, b: int | None = None, **labels):
        """Wraps ONE launch: counts it cold when ``shape_key`` is outside
        the warm set, opens the ``xla_launch`` span (each traced one of
        ``waiters`` gets its ``wait_name`` child, arrival -> here)
        inside the ``guard`` transfer-guard window (an implicit transfer
        between the explicit upload and gather is a counted violation
        and a host fallback), and counts the launch per (w[, b][,
        labels]) bucket once it is back.  The span says what the launch
        carried (``real_bytes``).  Yields the span."""
        from ceph_tpu.common.transfer_guard import no_implicit_transfers

        labels["w"] = w
        tags = {}
        if b is not None:
            labels["b"] = b
            tags = {"b": b, "occupancy": round(b_real / b, 3)}
        with self._warm_cv:
            cold = shape_key not in self._warm
            self._warm.add(shape_key)
        if cold:
            self.stats["cold_launches"] += 1
            self.metrics.inc("cold_launches", **labels)
        with tracing.launch_span(
            self.wait_name, [(r.span, r.arrived) for r in waiters],
            kind=kind, w=w, b_real=b_real, real_bytes=real_bytes,
            cold=cold, **tags,
        ) as span, no_implicit_transfers(guard):
            yield span
        self.metrics.inc("launches", **labels)
        if b is not None:
            self.metrics.inc("occupied_lanes", by=b_real, **labels)
            self.metrics.inc("padded_lanes", by=b, **labels)
        self.metrics.inc("occupied_bytes", by=real_bytes, **labels)
        self.metrics.inc("padded_bytes", by=padded_bytes, **labels)

    # -- warmup --------------------------------------------------------

    def _prewarm(self, shapes: list[tuple], compile_one) -> int:
        """See every key of ``shapes`` warm: claim the missing ones
        under the lock, run ``compile_one(key)`` (a launch of that shape
        on zeros) outside it, and wait for shapes another thread claimed
        first — "prewarm returned => no cold launch".  Blocking: daemon
        warmup only.  Returns the number THIS call compiled."""
        import jax

        from ceph_tpu.ops.compile_cache import ensure_persistent_cache

        ensure_persistent_cache()   # a restart then warm-starts from disk
        with self._warm_cv:
            todo = [key for key in dict.fromkeys(shapes)
                    if key not in self._warm
                    and key not in self._warm_claimed]
            self._warm_claimed.update(todo)
        n = 0
        try:
            for key in todo:
                jax.block_until_ready(compile_one(key))
                with self._warm_cv:
                    self._warm.add(key)
                    self._warm_cv.notify_all()
                n += 1
        finally:
            with self._warm_cv:
                self._warm_claimed.difference_update(todo)
                self._warm_cv.notify_all()
        with self._warm_cv:
            self._warm_cv.wait_for(lambda: all(
                key in self._warm or key not in self._warm_claimed
                for key in shapes), timeout=120.0)
        self.stats["prewarmed_shapes"] += n
        self.metrics.inc("prewarmed_shapes", by=n)
        return n


# -- the process-wide engines ------------------------------------------------

_shared: dict[str, LaunchBatcher] = {}


def shared(name: str, build) -> LaunchBatcher:
    """The process-wide engine ``name`` (co-hosted daemons coalesce in
    it), built on first use; a ``build`` that raises leaves nothing
    behind and raises again on the next use."""
    inst = _shared.get(name)
    if inst is None:
        inst = _shared[name] = build()
    return inst


def reset_shared(name: str) -> None:
    """Test hook: drop the process-wide engine ``name``."""
    _shared.pop(name, None)
