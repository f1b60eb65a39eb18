"""DecodeAggregator: batched recovery-decode dispatch with fixed shapes.

Recovery reconstructs objects one at a time (`RecoveryMixin`
`_reconcile_object` -> `ecutil.decode_shards_async`), so the decode
stage of a degraded PG is a stream of small per-object GF matmuls —
exactly the launch-bound regime "Repair Pipelining for Erasure-Coded
Storage" (arxiv 1908.01527) shows is won by batching repair traffic,
and whose launch/shape overheads arxiv 2108.02692 attacks around the
kernel.  This module is that layer for the TPU path:

- concurrent in-flight decodes that share an **erasure signature**
  (same decode matrix — k, m, missing-shard pattern and sub-chunk
  layout all feed the matrix, so matrix identity IS the signature)
  are collected during a short coalescing window;
- each request's stripe payload is padded into a **fixed power-of-two
  width bucket** (payloads wider than the tile cap split into
  fixed-width column lanes — the GF matmul is column-independent), the
  group is stacked into a (B, k, W) batch, and ONE batched launch per
  (signature, bucket) reconstructs every lane in the group;
- compiled-program shapes are therefore drawn from a tiny fixed set
  (#erasure-counts x #width-buckets x #batch-buckets), all of which
  :meth:`prewarm` compiles at daemon warmup — after warmup no XLA
  compile can occur inside the recovery I/O path, and the
  ``cold_launches`` counter proves it;
- decode matrices per erasure pattern come precomputed from the
  plugin's LRU cache (``MatrixErasureCode.decode_matrix``, the
  ErasureCodeIsaTableCache twin) and the compiled executables persist
  across processes via ops/compile_cache.py.

Padding is exact: the decode matrix applied to zero columns yields
zero columns, so slicing the first S columns of each lane returns the
bit-identical per-object ``decode_shards`` result (pinned by
tests/test_decode_batcher.py).
"""

from __future__ import annotations

import asyncio
import collections
import threading
import time

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.metrics import BucketCounters

#: padded widths below this stay in one bucket — tiny decodes all share
#: one shape instead of minting pow2 shapes per small size
DEFAULT_MIN_BUCKET = 4096

#: widest bucket; payloads wider than this split into TILE_CAP-wide
#: lanes (the GF matmul is column-independent), so the launch-shape set
#: is CLOSED: every possible payload lands in one of the
#: log2(TILE_CAP/MIN_BUCKET)+1 buckets and prewarm covers them all
DEFAULT_TILE_CAP = 1 << 16

#: ceiling on the batch dimension of one launch; larger groups split
#: into several full launches (shapes stay fixed either way)
DEFAULT_MAX_BATCH = 8

_BITS_CACHE_SIZE = 64


def pow2_bucket(n: int, floor: int = DEFAULT_MIN_BUCKET) -> int:
    """Smallest power-of-two >= max(n, floor)."""
    n = max(n, floor, 1)
    return 1 << (n - 1).bit_length()


class DecodeAggregator:
    """Coalesces concurrent ``D @ rows`` decode matmuls into fixed-shape
    batched launches.

    Device-agnostic: the batched kernel is the jitted XLA path
    (``ops.rs_kernels.gf_bitmatmul``) which runs bit-exactly on CPU and
    TPU; any dispatch failure answers every waiter from the numpy host
    path, so behavior is always identical to per-object decode.
    """

    def __init__(self, *, window_s: float = 0.002,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 tile_cap: int = DEFAULT_TILE_CAP):
        self.window_s = window_s
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self.tile_cap = tile_cap
        self._pending: dict[bytes, list[tuple]] = {}
        self._flush_handle = None
        self._bits_cache: collections.OrderedDict = collections.OrderedDict()
        #: (matrix shape, B, k, W) shapes already compiled (by prewarm or
        #: a previous launch); a launch outside this set is a cold
        #: compile — zero of those must happen after daemon warmup
        self._warm: set[tuple] = set()
        # _warm_lock guards ONLY the warm/claimed sets — never hold it
        # across a compile/launch (device-sync-under-lock): prewarm
        # claims missing shapes under the lock, compiles outside it,
        # and concurrent prewarmers wait on the condition for claims
        # they skipped to resolve
        self._warm_lock = threading.Lock()
        self._warm_cv = threading.Condition(self._warm_lock)
        self._warm_claimed: set[tuple] = set()
        self.stats = collections.Counter()
        self.metrics = BucketCounters("recovery_decode_batch")

    # -- gating --------------------------------------------------------

    def active(self) -> bool:
        return True

    # -- request side --------------------------------------------------

    async def apply(self, D: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """``D @ rows`` over GF(2^8), batched with concurrent callers
        that share the decode matrix.

        D is an (out, k) byte matrix (the plugin's cached decode matrix
        for one erasure signature); rows is (k, S) uint8.  Returns
        (out, S) uint8, bit-identical to ``gf_matmul(D, rows)``.
        """
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        key = D.shape[0].to_bytes(2, "little") + D.tobytes()
        # the caller's span in scope (recovery_decode) and the arrival
        # ride along: the first launch that serves this request files
        # its wait under it
        self._pending.setdefault(key, []).append(
            (D, rows, fut, tracing.CURRENT_SPAN.get(), time.monotonic()))
        self.stats["requests"] += 1
        if self._flush_handle is None:
            self._flush_handle = loop.call_later(self.window_s, self._flush)
        return await fut

    # -- dispatch side -------------------------------------------------

    def _bits(self, D: np.ndarray):
        import jax.numpy as jnp

        from ceph_tpu.ops.gf256 import gf_matrix_to_bitmatrix

        key = D.shape[0].to_bytes(2, "little") + D.tobytes()
        hit = self._bits_cache.get(key)
        if hit is None:
            from ceph_tpu.ops.compile_cache import ensure_persistent_cache

            ensure_persistent_cache()
            hit = jnp.asarray(gf_matrix_to_bitmatrix(D))
            self._bits_cache[key] = hit
            if len(self._bits_cache) > _BITS_CACHE_SIZE:
                self._bits_cache.popitem(last=False)
        else:
            self._bits_cache.move_to_end(key)
        return hit

    def _flush(self) -> None:
        """call_later callback: hand every pending signature group to a
        worker thread — the JAX dispatch (and any cold compile) must not
        run on the event loop."""
        self._flush_handle = None
        pending, self._pending = self._pending, {}
        loop = asyncio.get_running_loop()
        for group in pending.values():
            loop.create_task(self._dispatch_group(group))

    async def _dispatch_group(self, group: list[tuple]) -> None:
        try:
            outs = await asyncio.to_thread(self._run_group, group)
        except Exception:
            from ceph_tpu.ops.gf256 import gf_matmul

            self.stats["fallbacks"] += 1
            outs = await asyncio.to_thread(
                lambda: [gf_matmul(D, rows) for D, rows, *_ in group])
        for (_, _, fut, *_), out in zip(group, outs):
            if not fut.done():
                fut.set_result(out)

    def _bucket_plan(
        self, group: list[tuple]
    ) -> dict[int, list[tuple[int, int, int]]]:
        """Bucket width -> [(group index, column offset, width), ...].

        Payloads wider than ``tile_cap`` split into tile_cap-wide
        column lanes (the GF matmul is column-independent, so slicing
        columns is exact); narrower payloads pad up to their pow2
        bucket.  Every lane therefore lands in the CLOSED ladder
        [min_bucket .. tile_cap] that prewarm compiles in full."""
        plan: dict[int, list[tuple[int, int, int]]] = {}
        for i, (_, rows, *_) in enumerate(group):
            s = rows.shape[1]
            if s <= self.tile_cap:
                w = pow2_bucket(s, self.min_bucket)
                plan.setdefault(w, []).append((i, 0, s))
            else:
                for off in range(0, s, self.tile_cap):
                    plan.setdefault(self.tile_cap, []).append(
                        (i, off, min(self.tile_cap, s - off)))
        return plan

    def _run_group(self, group: list[tuple]) -> list[np.ndarray]:
        """Worker-thread body: one batched launch per (signature,
        bucket, max_batch lanes); returns per-request outputs in
        request order."""
        import jax

        from ceph_tpu.ops.rs_kernels import gf_bitmatmul

        D = group[0][0]
        bits = self._bits(D)
        k = group[0][1].shape[0]
        out_rows = bits.shape[0] // 8
        outs = [
            np.empty((out_rows, rows.shape[1]), np.uint8)
            for _, rows, *_ in group
        ]
        waiting = set(range(len(group)))   # not yet served by a launch
        for w, lanes in self._bucket_plan(group).items():
            for at in range(0, len(lanes), self.max_batch):
                chunk = lanes[at:at + self.max_batch]
                b_real = len(chunk)
                # two batch shapes only (1 and max): every multi-lane
                # launch shares ONE compiled program per bucket, so the
                # warmup set stays tiny even on a slow-compile backend
                b = 1 if b_real == 1 else self.max_batch
                batch = np.zeros((b, k, w), np.uint8)
                for j, (gi, off, width) in enumerate(chunk):
                    batch[j, :, :width] = group[gi][1][:, off:off + width]
                shape_key = (bits.shape, b, k, w)
                cold = shape_key not in self._warm
                if cold:
                    self._warm.add(shape_key)
                    self.stats["cold_launches"] += 1
                    self.metrics.inc("cold_launches", w=w, b=b)
                # device-launch profiling span: bucket shape, lane
                # occupancy and block-until-ready time, per launch —
                # padding waste becomes visible in `ceph trace`/mgr
                from ceph_tpu.common.transfer_guard import (
                    no_implicit_transfers,
                )

                served = {gi for gi, _, _ in chunk} & waiting
                waiting -= served
                # transfers are EXPLICIT by construction: device_put
                # uploads the padded batch, device_get gathers the
                # whole launch result once (the by-design host exit —
                # rebuilt shards persist to the store); the guard
                # turns any implicit transfer sneaking in between
                # into a counted violation + host fallback
                with tracing.launch_span(
                    "decode_batch_wait",
                    [group[gi][3:] for gi in sorted(served)],
                    kind="decode_batch", w=w, b=b, b_real=b_real,
                    occupancy=round(b_real / b, 3), cold=cold,
                ), no_implicit_transfers("decode_batch"):
                    out = jax.device_get(jax.block_until_ready(
                        gf_bitmatmul(bits, jax.device_put(batch))))
                self.stats["launches"] += 1
                self.stats["batched_requests"] += b_real
                self.metrics.inc("launches", w=w, b=b)
                self.metrics.inc("occupied_lanes", w=w, b=b, by=b_real)
                self.metrics.inc("padded_lanes", w=w, b=b, by=b)
                real = sum(width for _, _, width in chunk)
                self.metrics.inc("occupied_bytes", w=w, b=b, by=real * k)
                self.metrics.inc("padded_bytes", w=w, b=b, by=b * k * w)
                for j, (gi, off, width) in enumerate(chunk):
                    outs[gi][:, off:off + width] = out[j, :, :width]
        return outs

    # -- warmup --------------------------------------------------------

    def prewarm(self, ec_impl, widths=None, *, erasure_counts=(1, 2),
                batches=None) -> int:
        """Compile every (signature-shape, batch, bucket) combination
        this aggregator can launch for ``ec_impl``'s code, so no XLA
        compile happens in the recovery path afterwards.  Blocking —
        call from daemon warmup (or via to_thread), never the I/O path.

        The bucket ladder [min_bucket .. tile_cap] is CLOSED (wider
        payloads split into tile_cap lanes), so warming the whole
        ladder covers every payload size this aggregator can ever see;
        ``widths`` is accepted as a hint for extra buckets but is not
        required.  ``erasure_counts`` covers the missing-shard
        multiplicities to warm (the decode matrix SHAPE — all XLA
        cares about — depends only on the count).  Returns the number
        of programs compiled.
        """
        import jax
        import jax.numpy as jnp

        from ceph_tpu.ops.compile_cache import ensure_persistent_cache
        from ceph_tpu.ops.rs_kernels import gf_bitmatmul

        # warmed executables persist to the on-disk XLA cache: a daemon
        # restart warm-starts from disk instead of recompiling
        ensure_persistent_cache()
        k = ec_impl.get_data_chunk_count()
        r = getattr(ec_impl, "rows_per_chunk", 1)
        if batches is None:
            batches = [1, self.max_batch]
        buckets = set()
        w = pow2_bucket(self.min_bucket, 1)
        while w <= self.tile_cap:
            buckets.add(w)
            w <<= 1
        for x in widths or ():
            buckets.add(pow2_bucket(min(x, self.tile_cap),
                                    self.min_bucket))
        n = 0
        wanted: list[tuple] = []   # every shape this call must see warm
        todo: list[tuple] = []     # the subset THIS thread compiles
        with self._warm_cv:
            for e in erasure_counts:
                if e > ec_impl.get_chunk_count() - k:
                    # impossible signature: more erasures than parity
                    continue
                bits_shape = (8 * e * r, 8 * k * r)
                for w in sorted(buckets):
                    for b in batches:
                        shape_key = (bits_shape, b, k * r, w)
                        wanted.append(shape_key)
                        if (shape_key in self._warm
                                or shape_key in self._warm_claimed):
                            continue
                        self._warm_claimed.add(shape_key)
                        todo.append(shape_key)
        try:
            for shape_key in todo:
                bits_shape, b, kr, w = shape_key
                jax.block_until_ready(gf_bitmatmul(
                    jnp.zeros(bits_shape, np.uint8),
                    jnp.zeros((b, kr, w), np.uint8)))
                with self._warm_cv:
                    self._warm.add(shape_key)
                    self._warm_cv.notify_all()
                n += 1
        finally:
            with self._warm_cv:
                self._warm_claimed.difference_update(todo)
                self._warm_cv.notify_all()
        # shapes another prewarm thread claimed first: wait for them —
        # callers rely on "prewarm returned => no cold launch"
        with self._warm_cv:
            self._warm_cv.wait_for(lambda: all(
                key in self._warm or key not in self._warm_claimed
                for key in wanted), timeout=120.0)
        self.stats["prewarmed_shapes"] += n
        self.metrics.inc("prewarmed_shapes", by=n)
        return n


_shared: DecodeAggregator | None = None


def shared() -> DecodeAggregator:
    """Process-wide aggregator (one compiled-shape set per process)."""
    global _shared
    if _shared is None:
        _shared = DecodeAggregator()
    return _shared


def reset_shared() -> None:
    """Test hook: drop the process-wide aggregator."""
    global _shared
    _shared = None
