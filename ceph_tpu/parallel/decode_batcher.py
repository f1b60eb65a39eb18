"""DecodeAggregator: batched recovery-decode dispatch with fixed shapes.

Recovery reconstructs objects one at a time (`RecoveryMixin`
`_reconcile_object` -> `ecutil.decode_shards_async`): a stream of small
per-object GF matmuls, the launch-bound regime "Repair Pipelining for
Erasure-Coded Storage" (arxiv 1908.01527) wins by batching repair
traffic.  This engine's plan on the skeleton of parallel/batcher.py:

- concurrent decodes that share an **erasure signature** (the decode
  matrix: k, m, missing-shard pattern, sub-chunk layout) form one group;
- each payload is padded into a **fixed power-of-two width bucket**
  (wider than the tile cap: fixed-width column lanes), lanes stack into
  a (B, k, W) batch with B in {1, max_batch}, and ONE launch per
  (signature, bucket) reconstructs every lane;
- the shape set (#erasure-counts x #buckets x 2) is CLOSED:
  :meth:`DecodeAggregator.prewarm` compiles all of it at daemon warmup,
  and ``cold_launches`` then stays 0 in the recovery I/O path.

Padding is exact (the decode matrix applied to zero columns yields zero
columns): the first S columns of a lane are bit-identical to per-object
``decode_shards`` (tests/test_decode_batcher.py).
"""

from __future__ import annotations

import numpy as np

from ceph_tpu.parallel import batcher
from ceph_tpu.parallel.batcher import LaunchBatcher, MatMul, Request


class DecodeAggregator(LaunchBatcher):
    """Coalesces concurrent ``D @ rows`` decode matmuls into fixed-shape
    batched launches of the jitted XLA ``ops.rs_kernels.gf_bitmatmul``
    (bit-exact on CPU and TPU; a failed dispatch answers from numpy)."""

    wait_name = "decode_batch_wait"

    def __init__(self, *, window_s: float = 0.002,
                 max_batch: int = batcher.DEFAULT_MAX_BATCH,
                 min_bucket: int = batcher.DEFAULT_MIN_BUCKET,
                 tile_cap: int = batcher.DEFAULT_TILE_CAP):
        super().__init__("recovery_decode_batch", window_s=window_s)
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self.tile_cap = tile_cap

    async def apply(self, D: np.ndarray, rows: np.ndarray,
                    **tags) -> np.ndarray:
        """``D @ rows`` over GF(2^8), batched with concurrent callers
        that share D, the plugin's cached (out, k) decode matrix of one
        erasure signature (or a vector code's repair matrix of one lost
        node); rows is (k, S) uint8.  Returns (out, S) uint8,
        bit-identical to ``gf_matmul(D, rows)``.  ``tags`` (``kind``,
        ``lost_node``: the same for every caller of one D) go on the
        launches' spans."""
        self.stats["requests"] += 1
        return await self.submit(batcher.matrix_key(D),
                                 MatMul(D, rows, tags or None))

    # -- the plan ------------------------------------------------------

    def _bucket_plan(self, group: list[Request]) -> dict[int, list[tuple]]:
        """Bucket width -> [(group index, column offset, width), ...]:
        every request's lanes filed by the bucket they land in."""
        plan: dict[int, list[tuple]] = {}
        for i, req in enumerate(group):
            for off, width, w in batcher.bucket_lanes(
                    req.item.rows.shape[1], min_bucket=self.min_bucket,
                    tile_cap=self.tile_cap):
                plan.setdefault(w, []).append((i, off, width))
        return plan

    def _run_group(self, _key, group: list[Request]) -> list[np.ndarray]:
        """Worker-thread body: one batched launch per (signature,
        bucket, max_batch lanes); per-request outputs in order."""
        import jax

        from ceph_tpu.ops.rs_kernels import gf_bitmatmul

        bits = self._bits(group[0].item.M)
        k = group[0].item.rows.shape[0]
        out_rows = bits.shape[0] // 8
        tags = dict(group[0].item.tags or ())
        kind = tags.pop("kind", "decode_batch")
        outs = [np.empty((out_rows, req.item.rows.shape[1]), np.uint8)
                for req in group]
        waiting = set(range(len(group)))   # not yet served by a launch
        for w, lanes in self._bucket_plan(group).items():
            for chunk, b in batcher.batch_chunks(lanes, self.max_batch):
                batch = np.zeros((b, k, w), np.uint8)
                for j, (gi, off, width) in enumerate(chunk):
                    batch[j, :, :width] = \
                        group[gi].item.rows[:, off:off + width]
                served = {gi for gi, _, _ in chunk} & waiting
                waiting -= served
                real = sum(width for _, _, width in chunk)
                # one upload of the padded batch, one gather of the
                # result (by design: rebuilt shards persist to the store)
                with self._launching(
                    (bits.shape, b, k, w),
                    [group[gi] for gi in sorted(served)],
                    kind=kind, guard="decode_batch", w=w, b=b,
                    b_real=len(chunk), real_bytes=k * real,
                    padded_bytes=b * k * w,
                ) as span:
                    # the bytes the launch is for, pads left out
                    span.tag(objects=len({gi for gi, _, _ in chunk}),
                             helper_bytes=k * real,
                             rebuilt_bytes=out_rows * real, **tags)
                    out = jax.device_get(jax.block_until_ready(
                        gf_bitmatmul(bits, jax.device_put(batch))))
                self.stats["launches"] += 1
                self.stats["batched_requests"] += len(chunk)
                for j, (gi, off, width) in enumerate(chunk):
                    outs[gi][:, off:off + width] = out[j, :, :width]
        return outs

    _host_group = staticmethod(batcher.host_matmul_group)

    # -- warmup --------------------------------------------------------

    def prewarm(self, ec_impl, widths=None, *, erasure_counts=(1, 2),
                batches=None) -> int:
        """Compile every (signature-shape, batch, bucket) this
        aggregator can launch for ``ec_impl``'s code: the whole CLOSED
        ladder (``widths`` only hints at extra buckets) for each of
        ``erasure_counts`` (the decode matrix SHAPE depends only on the
        count; one above the code's parity is skipped), for a vector
        code the one shape of its repair matrices, for a scalar linear
        code (LRC) those of its single-chunk repairs.  Blocking: warmup
        only.  Returns the number of programs compiled."""
        import jax.numpy as jnp

        from ceph_tpu.ops.rs_kernels import gf_bitmatmul

        k = ec_impl.get_data_chunk_count()
        r = getattr(ec_impl, "rows_per_chunk", 1)
        buckets = batcher.bucket_ladder(
            self.min_bucket, self.tile_cap, widths)
        if hasattr(ec_impl, "repair_matrix"):
            # a vector code: the one shape of its single-chunk repair
            # (its other decodes never come here)
            R = ec_impl.repair_matrix(0)
            mats = [] if R is None else [R.shape]
        elif hasattr(ec_impl, "linear_plan"):
            # a scalar linear code: one chunk lost, whichever
            n = ec_impl.get_chunk_count()
            mats = sorted({ec_impl.linear_plan(
                {c}, set(range(n)) - {c})[1].shape for c in range(n)})
        else:
            mats = [(e * r, k * r) for e in erasure_counts
                    if e <= ec_impl.get_chunk_count() - k]
        return self._prewarm(
            [((8 * out, 8 * rows), b, rows, w)
             for out, rows in mats
             for w in buckets
             for b in batches or (1, self.max_batch)],
            lambda key: gf_bitmatmul(jnp.zeros(key[0], np.uint8),
                                     jnp.zeros(key[1:], np.uint8)))


def shared() -> DecodeAggregator:
    """Process-wide aggregator (one compiled-shape set per process)."""
    return batcher.shared("decode", DecodeAggregator)


def reset_shared() -> None:
    """Test hook: drop the process-wide aggregator."""
    batcher.reset_shared("decode")
