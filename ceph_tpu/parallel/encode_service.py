"""EncodeService: the in-daemon microbatching bridge onto the encode farm.

This is the production wiring of the multi-chip shardings
(ceph_tpu/parallel/encode_farm.py) into the I/O path: OSD write/recovery
ops running as concurrent asyncio tasks enqueue their GF(2^8) matrix
applications here; requests that land within one coalescing window and
share a matrix are padded into a single (B, k, S) batch and dispatched
through :func:`batch_encode_dp` over the device mesh.  A lone large
request takes the chunk-sharded :func:`sharded_encode_tp` path instead
(partial GF sums psum-combined over ICI).

This is the seam the reference implements as the ECSubWrite fan-out /
per-op `ECUtil::encode` loop (reference src/osd/ECCommon.cc:749
generate_transactions -> ECTransaction.cc:37 encode_and_write, and
src/osd/OSDMapMapping.h:18 ParallelPGMapper for the batch-parallel
pattern): independent per-PG ops become one batched TPU computation.

Single-device processes (or payloads under ``min_bytes``) fall back to
the caller's host/1-chip path — the service is then inactive and
``apply`` is never awaited (callers check :meth:`active`).
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


class EncodeService:
    """Coalesces concurrent GF matrix applications onto a device mesh.

    ``mesh`` must have a ``pg`` axis (stripe-batch data parallelism) and
    may have a ``shard`` axis (chunk sharding for the tp path).  With
    ``mesh=None`` the service is inactive and callers use their local
    path.
    """

    def __init__(self, mesh=None, *, device=None,
                 min_bytes: int = DEFAULT_MIN_BYTES,
                 window_s: float = 0.001):
        self.mesh = mesh
        # single-device mode: with one accelerator and no mesh, the
        # microbatching window still coalesces concurrent per-PG ops
        # into ONE dispatch.  Requests concatenate along S (GF matmul
        # is column-independent), so no batch padding at all.
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
        """Worker-thread body: one farm dispatch for the whole group;
        returns per-request outputs in order."""
        import jax

        from ceph_tpu.parallel.encode_farm import (
            batch_encode_dp,
            sharded_encode_tp,
        )

        # NOTE on guard coverage: the mesh (shard_map) dispatches below
        # are NOT wrapped in no_implicit_transfers — XLA's multi-device
        # execution path ships tiny internal scalar constants
        # (observed: replicated uint8[] avals) host->device on every
        # dispatch, which the guard cannot tell apart from real payload
        # round-trips.  Payload transfers here are explicit and
        # mesh-sharded at source (device_put with NamedSharding, no
        # reshard hop); the single-device paths — where the
        # batched-vs-host gap actually lives — run fully guarded
        # (_run_group_single, decode/scrub batchers, mgr analytics).

        M = group[0][0]
        bits = self._bits(M)
        k = M.shape[1]

        if self.mesh is None:
            return self._run_group_single(group, bits, k)

        if len(group) == 1 and "shard" in self.mesh.shape:
            rows = group[0][1]
            nsh = self.mesh.shape["shard"]
            if nsh > 1 and k % nsh == 0:
                # same fixed-bucket discipline as the dp path: pad S to
                # its pow2 bucket so the tp program shape set is bounded
                S = pow2_bucket(rows.shape[1], 1)
                if S != rows.shape[1]:
                    padded = np.zeros((rows.shape[0], S), np.uint8)
                    padded[:, : rows.shape[1]] = rows
                else:
                    padded = rows
                from ceph_tpu.parallel.encode_farm import (
                    tp_data_sharding,
                )

                with self._note_shape(("tp", bits.shape, k, S), group,
                                      w=S):
                    res = sharded_encode_tp(
                        self.mesh, bits, jax.device_put(
                            padded, tp_data_sharding(self.mesh)))
                    out = jax.device_get(res)
                self._note_mesh_devices(res)
                self.stats["tp_dispatches"] += 1
                self.metrics.inc("launches", w=S)
                return [np.ascontiguousarray(out[:, : rows.shape[1]])]

        # data-parallel batch: pad each request's S to a fixed
        # power-of-two width bucket and the batch dim to a power-of-two
        # multiple of the device count, one sharded dispatch — launch
        # shapes come from a tiny fixed set, so every compile happens
        # at prewarm, never mid-I/O
        ndev = 1
        for ax in self.mesh.shape.values():
            ndev *= ax
        widths = [rows.shape[1] for _, rows, *_ in group]
        S = pow2_bucket(max(widths), 1)
        B = ndev * pow2_bucket(-(-len(group) // ndev), 1)
        batch = np.zeros((B, k, S), np.uint8)
        for i, (_, rows, *_) in enumerate(group):
            batch[i, :, : rows.shape[1]] = rows
        axes = tuple(a for a in ("pg", "shard") if a in self.mesh.shape)
        from ceph_tpu.parallel.encode_farm import dp_batch_sharding

        with self._note_shape(("dp", bits.shape, B, k, S), group, w=S,
                              b=B):
            res = batch_encode_dp(
                self.mesh, bits, jax.device_put(
                    batch, dp_batch_sharding(self.mesh, axes)),
                axis=axes)
            out = jax.device_get(res)
        self._note_mesh_devices(res)
        self.stats["dp_dispatches"] += 1
        self.stats["coalesced"] += len(group)
        self.metrics.inc("launches", w=S, b=B)
        self.metrics.inc("occupied_lanes", w=S, b=B, by=len(group))
        self.metrics.inc("padded_lanes", w=S, b=B, by=B)
        self.metrics.inc("occupied_bytes", w=S, b=B, by=sum(widths) * k)
        self.metrics.inc("padded_bytes", w=S, b=B, by=B * k * S)
        return [
            np.ascontiguousarray(out[i, :, : rows.shape[1]])
            for i, (_, rows, _) in enumerate(group)
        ]

    def _note_mesh_devices(self, res) -> None:
        """Most devices any farm launch's result has sat on: a mesh
        whose launches land on its first device only is not a farm."""
        self.stats["mesh_devices_used"] = max(
            self.stats["mesh_devices_used"], len(res.sharding.device_set))

    def _note_shape(self, shape_key: tuple, group: list[tuple], *,
                    w: int, b: int = 1):
        """Track whether a launch shape was already compiled (a miss is
        a cold in-path compile the warmup should have covered) and
        return the device-launch profiling span wrapping the launch;
        every traced request of ``group`` gets its ``encode_batch_wait``
        filed (arrival -> here) and the launch names their spans."""
        cold = shape_key not in self._warm
        if cold:
            self._warm.add(shape_key)
            self.stats["cold_launches"] += 1
            self.metrics.inc("cold_launches", w=w, b=b)
        b_real = len(group)
        return tracing.launch_span(
            "encode_batch_wait", [req[3:] for req in group],
            kind=f"encode_{shape_key[0]}", w=w, b=b, b_real=b_real,
            occupancy=round(b_real / max(b, 1), 3), cold=cold,
        )

    def _run_group_single(self, group: list[tuple], bits, k) -> list[np.ndarray]:
        """Single-device dispatch: concatenate every request's rows
        along S (column-independent GF matmul), pad to a power-of-two
        width so jit shapes stay bounded, ONE kernel launch for the
        whole window."""
        import jax

        from ceph_tpu.common.transfer_guard import no_implicit_transfers
        from ceph_tpu.ops.rs_kernels import BitmatrixCodec

        widths = [rows.shape[1] for _, rows, *_ in group]
        total = sum(widths)
        S = pow2_bucket(total, 1)  # fixed pow2 width bucket
        big = np.zeros((k, S), np.uint8)
        off = 0
        for (_, rows, *_), w in zip(group, widths):
            big[:, off:off + w] = rows
            off += w
        with self._note_shape(("single", bits.shape, k, S), group,
                              w=S), \
                no_implicit_transfers("encode_single"):
            out = jax.device_get(BitmatrixCodec._apply(
                bits, jax.device_put(big), None))
        self.stats["single_dispatches"] += 1
        self.stats["coalesced"] += len(group)
        self.metrics.inc("launches", w=S)
        self.metrics.inc("occupied_bytes", w=S, by=total * k)
        self.metrics.inc("padded_bytes", w=S, by=k * S)
        outs = []
        off = 0
        for w in widths:
            outs.append(np.ascontiguousarray(out[:, off:off + w]))
            off += w
        return outs

    # -- warmup --------------------------------------------------------

    def prewarm(self, M: np.ndarray, widths, *, coalesce: int = 16) -> int:
        """Compile the fixed-bucket launch shapes this service can hit
        for matrix ``M`` and per-request payload widths ``widths``
        (coalescing concatenates/batches up to ``coalesce`` concurrent
        requests).  Blocking — run at daemon warmup, never in the I/O
        path.  Returns the number of programs compiled."""
        if not self.active():
            return 0
        import jax
        import jax.numpy as jnp

        from ceph_tpu.ops.compile_cache import ensure_persistent_cache
        from ceph_tpu.ops.rs_kernels import BitmatrixCodec
        from ceph_tpu.parallel.encode_farm import batch_encode_dp

        ensure_persistent_cache()  # warmed programs persist across runs

        bits = self._bits(np.asarray(M, np.uint8))
        k = M.shape[1]
        buckets: set[int] = set()
        for w in widths:
            f = 1
            while f <= coalesce:
                buckets.add(pow2_bucket(w * f, 1))
                f <<= 1
        n = 0
        if self.mesh is not None:
            from ceph_tpu.parallel.encode_farm import dp_batch_sharding

            ndev = 1
            for ax in self.mesh.shape.values():
                ndev *= ax
            axes = tuple(
                a for a in ("pg", "shard") if a in self.mesh.shape)
            bbs = sorted({
                ndev * pow2_bucket(-(-g // ndev), 1)
                for g in range(1, coalesce + 1)
            })
            # warm with the SAME input shardings the dispatch path
            # uses (executables are keyed by sharding, not just shape)
            dp_spec = dp_batch_sharding(self.mesh, axes)
            for S in sorted(pow2_bucket(w, 1) for w in widths):
                for B in bbs:
                    key = ("dp", bits.shape, B, k, S)
                    if key in self._warm:
                        continue
                    jax.block_until_ready(batch_encode_dp(
                        self.mesh, bits,
                        jax.device_put(
                            np.zeros((B, k, S), np.uint8), dp_spec),
                        axis=axes))
                    self._warm.add(key)
                    n += 1
            nsh = self.mesh.shape.get("shard", 1)
            if nsh > 1 and k % nsh == 0:
                from ceph_tpu.parallel.encode_farm import (
                    sharded_encode_tp,
                    tp_data_sharding,
                )

                tp_spec = tp_data_sharding(self.mesh)
                for S in sorted(pow2_bucket(w, 1) for w in widths):
                    key = ("tp", bits.shape, k, S)
                    if key in self._warm:
                        continue
                    jax.block_until_ready(sharded_encode_tp(
                        self.mesh, bits, jax.device_put(
                            np.zeros((k, S), np.uint8), tp_spec)))
                    self._warm.add(key)
                    n += 1
        else:
            for S in sorted(buckets):
                key = ("single", bits.shape, k, S)
                if key in self._warm:
                    continue
                jax.block_until_ready(BitmatrixCodec._apply(
                    bits, jnp.zeros((k, S), np.uint8), None))
                self._warm.add(key)
                n += 1
        self.stats["prewarmed_shapes"] += n
        self.metrics.inc("prewarmed_shapes", by=n)
        return n


_shared: EncodeService | None = None


def shared() -> EncodeService:
    """Process-wide service; builds a mesh over all local devices on
    first use.  A single TPU gets single-device coalescing mode; a
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
                nsh = 2 if len(devs) % 2 == 0 else 1
                devgrid = np.asarray(devs).reshape(len(devs) // nsh, nsh)
                mesh = Mesh(devgrid, ("pg", "shard"))
            elif devs[0].platform == "tpu":
                device = devs[0]
        _shared = EncodeService(mesh, device=device)
    return _shared


def reset_shared() -> None:
    """Test hook: drop the process-wide service."""
    global _shared
    _shared = None
