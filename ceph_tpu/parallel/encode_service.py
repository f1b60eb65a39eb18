"""EncodeService: the in-daemon microbatching bridge onto the device(s).

OSD write/recovery ops running as concurrent asyncio tasks enqueue their
GF(2^8) matrix applications here; requests that land within one
coalescing window and share a matrix are laid side by side along the
column dimension S (a GF matmul is independent column by column), padded
to a fixed power-of-two width bucket and dispatched as ONE launch.  With
several local devices that launch is the column-split mesh program of
ceph_tpu/parallel/encode_farm.py (:func:`mesh_encode_cols`: every device
applies the replicated bit-matrix to its own column block, no
collective, no batch padding), a lone request and a full window alike;
with one accelerator it is the same kernel on that device.

This is the seam the reference implements as the ECSubWrite fan-out /
per-op `ECUtil::encode` loop (reference src/osd/ECCommon.cc:749
generate_transactions -> ECTransaction.cc:37 encode_and_write, and
src/osd/OSDMapMapping.h:18 ParallelPGMapper for the batch-parallel
pattern): independent per-PG ops become one batched TPU computation.

Cpu-only single-device processes (or payloads under ``min_bytes``) fall
back to the caller's host/1-chip path — the service is then inactive
and ``apply`` is never awaited (callers check :meth:`active`).
"""

from __future__ import annotations

import asyncio
import collections
import time

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.metrics import BucketCounters
from ceph_tpu.parallel.decode_batcher import pow2_bucket

#: payloads smaller than this stay on the caller's local path — TPU/mesh
#: dispatch overhead dwarfs the math (SURVEY.md §7 hard part 3)
DEFAULT_MIN_BYTES = 32768

_BITS_CACHE_SIZE = 64

#: a launch's transfer-guard window, by what the launch counts itself as
_GUARD_KIND = {"single": "encode_single", "dp": "encode_mesh"}


class EncodeService:
    """Coalesces concurrent GF matrix applications into one launch.

    ``mesh`` is any ``jax.sharding.Mesh``: a launch cuts its columns
    over every device of it, whatever its axes.  ``device`` (no mesh)
    is single-device mode.  With neither the service is inactive and
    callers use their local path.
    """

    def __init__(self, mesh=None, *, device=None,
                 min_bytes: int = DEFAULT_MIN_BYTES,
                 window_s: float = 0.001):
        self.mesh = mesh
        # single-device mode: with one accelerator and no mesh, the
        # microbatching window still coalesces concurrent per-PG ops
        # into ONE dispatch
        self.device = device
        self.min_bytes = min_bytes
        self.window_s = window_s
        self._pending: dict[bytes, list[tuple]] = {}
        self._flush_handle = None
        self._bits_cache: collections.OrderedDict = collections.OrderedDict()
        self.stats = collections.Counter()
        #: compiled dispatch shapes (by prewarm or earlier launches); a
        #: launch outside this set pays an XLA compile — the warmup
        #: discipline (daemon map-time prewarm) keeps this at zero
        #: inside the I/O path
        self._warm: set[tuple] = set()
        self.metrics = BucketCounters("encode_farm")

    # -- gating --------------------------------------------------------

    def active(self) -> bool:
        return self.mesh is not None or self.device is not None

    def usable(self, rows: np.ndarray) -> bool:
        return self.active() and rows.size >= self.min_bytes

    # -- request side --------------------------------------------------

    async def apply(self, M: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """``M @ rows`` over GF(2^8), batched with concurrent callers.

        M is an (out, k) byte matrix (coding or cached decode matrix);
        rows is (k, S) uint8.  Returns (out, S) uint8.
        """
        assert self.active()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        key = M.shape[0].to_bytes(2, "little") + M.tobytes()
        # the caller's span in scope (the op's ec_encode) and the arrival
        # ride along: the launch files this request's wait under it
        self._pending.setdefault(key, []).append(
            (M, rows, fut, tracing.CURRENT_SPAN.get(), time.monotonic()))
        if self._flush_handle is None:
            self._flush_handle = loop.call_later(self.window_s, self._flush)
        return await fut

    # -- dispatch side -------------------------------------------------

    def _bits(self, M: np.ndarray):
        import jax

        from ceph_tpu.ops.gf256 import gf_matrix_to_bitmatrix

        key = M.shape[0].to_bytes(2, "little") + M.tobytes()
        hit = self._bits_cache.get(key)
        if hit is None:
            bits = gf_matrix_to_bitmatrix(M)
            if self.mesh is not None:
                # replicate across the mesh at cache-fill time so no
                # launch pays a per-dispatch reshard of the matrix
                from ceph_tpu.parallel.encode_farm import (
                    replicated_sharding,
                )

                hit = jax.device_put(bits, replicated_sharding(self.mesh))
            else:
                hit = jax.device_put(bits)
            self._bits_cache[key] = hit
            if len(self._bits_cache) > _BITS_CACHE_SIZE:
                self._bits_cache.popitem(last=False)
        else:
            self._bits_cache.move_to_end(key)
        return hit

    def _flush(self) -> None:
        """call_later callback: hand every pending group to a worker
        thread.  The JAX dispatch (and any first-use XLA compile) must
        NOT run on the event loop — it would stall heartbeats and op
        processing for every daemon in the process."""
        self._flush_handle = None
        pending, self._pending = self._pending, {}
        loop = asyncio.get_running_loop()
        for group in pending.values():
            loop.create_task(self._dispatch_group(group))

    async def _dispatch_group(self, group: list[tuple]) -> None:
        try:
            outs = await asyncio.to_thread(self._run_group, group)
        except Exception:
            # farm failure: answer every waiter from the host path
            # (always correct), don't fail client ops
            from ceph_tpu.ops.gf256 import gf_matmul

            self.stats["fallbacks"] += 1
            outs = await asyncio.to_thread(
                lambda: [gf_matmul(M, rows) for M, rows, *_ in group])
        for (_, _, fut, *_), out in zip(group, outs):
            if not fut.done():
                fut.set_result(out)

    def _run_group(self, group: list[tuple]) -> list[np.ndarray]:
        """Worker-thread body: ONE launch for the whole group.  The
        requests' rows are laid side by side along S (a GF matmul is
        independent column by column, so there is no batch dimension
        and no batch padding) and padded to the launch's fixed width
        bucket, so jit shapes stay bounded; on a mesh the columns are
        then cut into one block per device.  Returns per-request
        outputs in order."""
        import jax

        from ceph_tpu.common.transfer_guard import no_implicit_transfers

        M = group[0][0]
        bits = self._bits(M)
        k = M.shape[1]
        widths = [rows.shape[1] for _, rows, *_ in group]
        total = sum(widths)
        S = self._bucket(total)
        big = np.zeros((k, S), np.uint8)
        off = 0
        for (_, rows, *_), w in zip(group, widths):
            big[:, off:off + w] = rows
            off += w
        kind = self._kind()
        with self._note_shape((kind, bits.shape, k, S), group, w=S) as span, \
                no_implicit_transfers(_GUARD_KIND[kind]):
            res = self._launch(bits, big)
            out = jax.device_get(res)
            if self.mesh is not None:
                ndev = len(res.sharding.device_set)
                span.tag(devices=ndev, pad_bytes=(S - total) * k)
        self.stats[f"{kind}_dispatches"] += 1
        self.stats["coalesced"] += len(group)
        if self.mesh is not None:
            # a mesh whose launches land on its first device only is
            # not a farm: keep the most devices any result has sat on
            self.stats["mesh_devices_used"] = max(
                self.stats["mesh_devices_used"], ndev)
            self.stats["mesh_occupied_bytes"] += total * k
            self.stats["mesh_padded_bytes"] += S * k
        self.metrics.inc("launches", w=S)
        self.metrics.inc("occupied_bytes", w=S, by=total * k)
        self.metrics.inc("padded_bytes", w=S, by=k * S)
        outs = []
        off = 0
        for w in widths:
            outs.append(np.ascontiguousarray(out[:, off:off + w]))
            off += w
        return outs

    def _kind(self) -> str:
        """What a launch counts itself as (``<kind>_dispatches``, the
        ``xla_launch`` span's ``encode_<kind>``): ``dp`` over a mesh,
        ``single`` on one device."""
        return "single" if self.mesh is None else "dp"

    def _bucket(self, total: int) -> int:
        """The fixed launch width holding ``total`` real columns."""
        if self.mesh is None:
            return pow2_bucket(total, 1)
        from ceph_tpu.parallel.encode_farm import cols_width

        return cols_width(self.mesh, total)

    def _launch(self, bits, big: np.ndarray):
        """Upload ``big`` where the program wants it and launch: the
        column-split mesh program, or the one device's kernel.  The
        warm-up and the I/O path both come through here, so they agree
        on shapes and shardings."""
        import jax

        if self.mesh is None:
            from ceph_tpu.ops.rs_kernels import BitmatrixCodec

            return BitmatrixCodec._apply(bits, jax.device_put(big), None)
        from ceph_tpu.parallel.encode_farm import (
            cols_sharding,
            mesh_encode_cols,
        )

        return mesh_encode_cols(
            self.mesh, bits, jax.device_put(big, cols_sharding(self.mesh)))

    def _note_shape(self, shape_key: tuple, group: list[tuple], *, w: int):
        """Track whether a launch shape was already compiled (a miss is
        a cold in-path compile the warmup should have covered) and
        return the device-launch profiling span wrapping the launch;
        every traced request of ``group`` gets its ``encode_batch_wait``
        filed (arrival -> here) and the launch names their spans."""
        cold = shape_key not in self._warm
        if cold:
            self._warm.add(shape_key)
            self.stats["cold_launches"] += 1
            self.metrics.inc("cold_launches", w=w)
        return tracing.launch_span(
            "encode_batch_wait", [req[3:] for req in group],
            kind=f"encode_{shape_key[0]}", w=w, b_real=len(group),
            cold=cold,
        )

    # -- warmup --------------------------------------------------------

    def prewarm(self, M: np.ndarray, widths, *, coalesce: int = 16) -> int:
        """Compile the fixed-bucket launch shapes this service can hit
        for matrix ``M`` and per-request payload widths ``widths``
        (a window concatenates up to ``coalesce`` concurrent requests).
        Blocking — run at daemon warmup, never in the I/O path.
        Returns the number of programs compiled."""
        if not self.active():
            return 0
        import jax

        from ceph_tpu.ops.compile_cache import ensure_persistent_cache

        ensure_persistent_cache()  # warmed programs persist across runs

        bits = self._bits(np.asarray(M, np.uint8))
        k = M.shape[1]
        buckets: set[int] = set()
        for w in widths:
            f = 1
            while f <= coalesce:
                buckets.add(self._bucket(w * f))
                f <<= 1
        n = 0
        for S in sorted(buckets):
            key = (self._kind(), bits.shape, k, S)
            if key in self._warm:
                continue
            jax.block_until_ready(
                self._launch(bits, np.zeros((k, S), np.uint8)))
            self._warm.add(key)
            n += 1
        self.stats["prewarmed_shapes"] += n
        self.metrics.inc("prewarmed_shapes", by=n)
        return n


_shared: EncodeService | None = None


def shared() -> EncodeService:
    """Process-wide service; builds a one-axis mesh over all local
    devices on first use (the device count the process sees is the only
    thing that chooses the launch path).  A single TPU gets single-device coalescing mode; a
    cpu-only process (one CPU device, or no jax at all) stays inactive
    so host paths keep their exact semantics/costs.  A backend that
    fails to start (chip held by another process, bad platform env)
    raises: serving the whole cluster from host numpy because the chip
    could not be reached is never a silent outcome."""
    global _shared
    if _shared is None:
        mesh = None
        device = None
        try:
            import jax
        except ImportError:
            jax = None
        if jax is not None:
            from jax.sharding import Mesh

            devs = jax.devices()
            if len(devs) > 1:
                mesh = Mesh(np.asarray(devs), ("cols",))
            elif devs[0].platform == "tpu":
                device = devs[0]
        _shared = EncodeService(mesh, device=device)
    return _shared


def reset_shared() -> None:
    """Test hook: drop the process-wide service."""
    global _shared
    _shared = None
