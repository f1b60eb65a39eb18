"""ScrubVerifier: batched deep-scrub verification with fixed shapes.

Scrub chunks are a stream of small independent checks, the launch-bound
regime the decode aggregator batches too (arxiv 1908.01527, 2108.02692).
Checks in flight in one window — across objects AND PGs: the verifier is
process-wide — split every shard payload into the CLOSED bucket ladder
(`batcher.bucket_lanes`) and share two kinds of launches on the skeleton
of parallel/batcher.py:

- **batched crc32c**: a (B, W) stack of payload lanes is ONE GF(2)
  bit-matmul (`ops.hashing.batched_crc32c_device`; crc32c is linear over
  GF(2)); folding with native ``crc32c_zeros`` / ``crc32c_unadvance``
  on the host gives the exact per-shard crc32c;
- **RS re-encode compare**: (B, k, W) data lanes re-encode through the
  profile's bit-matrix and compare with the stored (B, m, W) parity on
  device (`ops.rs_kernels.gf_encode_compare`); only a (B, m) mismatch
  mask comes back: silent parity divergence, which crc chains miss.

:meth:`ScrubVerifier.prewarm` compiles the whole shape set (#buckets x 2
batch shapes [x #profiles]) at map install; ``cold_launches`` then stays
0 in the scrub path.  Padding is exact in both kernels (encode of zero
columns is zero columns; the crc of a zero-padded lane is the injective
linear advance of the true crc): results are bit-identical to the
per-object host path (tests/test_scrub_batcher.py).
"""

from __future__ import annotations

import asyncio
from typing import NamedTuple

import numpy as np

from ceph_tpu.parallel import batcher
from ceph_tpu.parallel.batcher import LaunchBatcher, Request

#: ceiling on the lanes of one crc launch (single shard payloads: many
#: more fit per launch than the (k, W) re-encode items)
DEFAULT_CRC_LANES = 32

_SEED = 0xFFFFFFFF


class ObjectCheck(NamedTuple):
    """One object's batched verification result."""
    #: shard id -> crc32c of its payload (seed -1, ceph_crc32c semantics)
    crcs: dict[int, int]
    #: shard ids whose stored parity disagrees with a re-encode of the
    #: data shards; None when the check did not apply (host path then)
    parity_bad: frozenset[int] | None


class CrcLane(NamedTuple):
    """Item of a ``("crc", bucket)`` group: one payload lane."""
    arr: np.ndarray
    width: int


class EncLane(NamedTuple):
    """Item of an ``("enc", matrix signature, bucket)`` group: one
    object's (k, width) data and (m, width) stored parity lanes."""
    C: np.ndarray
    data: np.ndarray
    parity: np.ndarray


class ScrubVerifier(LaunchBatcher):
    """Coalesces concurrent deep-scrub checks into fixed-shape batched
    crc32c + re-encode-compare launches (jitted XLA, bit-exact on CPU
    and TPU; a failed dispatch answers its lanes from the host path)."""

    fallback_stat = "dispatch_fallbacks"   # ``fallbacks``: whole objects

    def __init__(self, *, window_s: float = 0.002,
                 max_batch: int = batcher.DEFAULT_MAX_BATCH,
                 crc_lanes: int = DEFAULT_CRC_LANES,
                 min_bucket: int = batcher.DEFAULT_MIN_BUCKET,
                 tile_cap: int = batcher.DEFAULT_TILE_CAP):
        super().__init__("scrub_verify_batch", window_s=window_s)
        self.max_batch = max_batch
        self.crc_lanes = crc_lanes
        self.min_bucket = min_bucket
        self.tile_cap = tile_cap

    @staticmethod
    def _parity_eligible(ec_impl, payloads) -> bool:
        """The re-encode compare covers plain matrix codes with every
        shard present at one length; anything else answers
        ``parity_bad=None`` and the scrubber keeps its host path."""
        from ceph_tpu.ec.plugins.matrix_base import MatrixErasureCode

        if (not isinstance(ec_impl, MatrixErasureCode)
                or ec_impl.rows_per_chunk != 1
                or ec_impl.get_sub_chunk_count() != 1):
            return False
        n = ec_impl.get_chunk_count()
        if set(payloads) != {ec_impl.chunk_index(c) for c in range(n)}:
            return False
        sizes = {len(p) for p in payloads.values()}
        return len(sizes) == 1 and sizes.pop() > 0

    def _lanes(self, nbytes: int) -> list[tuple[int, int, int]]:
        return batcher.bucket_lanes(
            nbytes, min_bucket=self.min_bucket, tile_cap=self.tile_cap)

    # -- request side --------------------------------------------------

    async def verify_object(
        self, ec_impl, payloads: dict[int, np.ndarray]
    ) -> ObjectCheck | None:
        """Verify one object's shard payloads, batched with every other
        concurrent caller's.  None: take the per-object host path."""
        from ceph_tpu.native import crc32c_zeros
        from ceph_tpu.ops.hashing import crc32c_unadvance

        arrs = {
            s: (np.frombuffer(bytes(p), dtype=np.uint8)
                if isinstance(p, (bytes, bytearray, memoryview))
                else np.ascontiguousarray(
                    np.asarray(p, dtype=np.uint8).reshape(-1)))
            for s, p in payloads.items()
        }
        crc_futs = {
            s: [(width, bucket, self.submit(
                    ("crc", bucket), CrcLane(arr[off:off + width], width)))
                for off, width, bucket in self._lanes(arr.nbytes)]
            for s, arr in arrs.items()
        }
        enc_futs: list[asyncio.Future] | None = None
        if ec_impl is not None and self._parity_eligible(ec_impl, arrs):
            k = ec_impl.get_data_chunk_count()
            parity_ids = [ec_impl.chunk_index(c)
                          for c in range(k, ec_impl.get_chunk_count())]
            C = np.asarray(ec_impl.coding_matrix, dtype=np.uint8)
            sig = batcher.matrix_key(C)
            enc_futs = [
                self.submit(("enc", sig, bucket), EncLane(
                    C,
                    np.stack([arrs[ec_impl.chunk_index(c)][off:off + width]
                              for c in range(k)]),
                    np.stack([arrs[s][off:off + width]
                              for s in parity_ids])))
                for off, width, bucket in self._lanes(
                    len(next(iter(arrs.values()))))
            ]
        self.stats["objects"] += 1
        try:
            crcs: dict[int, int] = {}
            for s, futs in crc_futs.items():
                c, pad = _SEED, 0
                for width, bucket, fut in futs:
                    c = crc32c_zeros(bucket, c) ^ await fut
                    pad = bucket - width
                crcs[s] = crc32c_unadvance(c, pad)
            if enc_futs is None:
                return ObjectCheck(crcs, None)
            bad: set[int] = set()
            for fut in enc_futs:
                mask = await fut
                bad.update(s for s, hit in zip(parity_ids, mask) if hit)
            return ObjectCheck(crcs, frozenset(bad))
        except Exception:
            self.stats["fallbacks"] += 1
            return None

    # -- the plans -----------------------------------------------------

    def _run_group(self, key, group: list[Request]) -> list:
        return getattr(self, f"_run_{key[0]}_group")(key[-1], group)

    def _host_group(self, key, group: list[Request]) -> list:
        return getattr(self, f"_host_{key[0]}_group")(key[-1], group)

    def _crc_mat(self, bucket: int):
        from ceph_tpu.ops.hashing import crc32c_matrix

        return self._matrix(("crc", bucket), lambda: crc32c_matrix(bucket))

    def _count(self, kind: str, b_real: int) -> None:
        self.stats["launches"] += 1
        self.stats[f"{kind}_launches"] += 1
        self.stats["batched_lanes"] += b_real

    def _run_crc_group(self, w: int, group: list[Request]) -> list[int]:
        """Worker-thread body: batched crc32c launches over one bucket;
        returns each lane's raw device crc (L_W of the padded lane)."""
        import jax

        from ceph_tpu.ops.hashing import batched_crc32c_device

        mat = self._crc_mat(w)
        outs: list[int] = []
        for chunk, b in batcher.batch_chunks(group, self.crc_lanes):
            batch = np.zeros((b, w), np.uint8)
            for j, req in enumerate(chunk):
                batch[j, :req.item.width] = req.item.arr
            # explicit put/get only: one upload of the lane batch, one
            # (B,)-word gather (by design: crcs fold on the host)
            with self._launching(
                ("crc", b, w), (), kind="scrub_crc", guard="scrub_crc",
                w=w, b=b, k="crc", b_real=len(chunk),
                real_bytes=sum(req.item.width for req in chunk),
                padded_bytes=b * w,
            ):
                out = jax.device_get(jax.block_until_ready(
                    batched_crc32c_device(mat, jax.device_put(batch))))
            self._count("crc", len(chunk))
            outs += [int(c) for c in out[:len(chunk)]]
        return outs

    @staticmethod
    def _host_crc_group(w: int, group: list[Request]) -> list[int]:
        from ceph_tpu.native import crc32c, crc32c_zeros

        # L_W of the padded lane == advance of the seed-0 crc through
        # the pad, so the host answer folds identically downstream
        return [crc32c_zeros(w - req.item.width, crc32c(req.item.arr, 0))
                for req in group]

    def _run_enc_group(self, w: int,
                       group: list[Request]) -> list[np.ndarray]:
        """Worker-thread body: batched re-encode-compare launches for
        one (profile, bucket); returns each item's (m,) mismatch mask."""
        import jax

        from ceph_tpu.ops.rs_kernels import gf_encode_compare

        C = group[0].item.C
        bits = self._bits(C)
        m, k = C.shape
        outs: list[np.ndarray] = []
        for chunk, b in batcher.batch_chunks(group, self.max_batch):
            data = np.zeros((b, k, w), np.uint8)
            parity = np.zeros((b, m, w), np.uint8)
            for j, req in enumerate(chunk):
                data[j, :, :req.item.data.shape[1]] = req.item.data
                parity[j, :, :req.item.parity.shape[1]] = req.item.parity
            # explicit put/get only; the gather is the tiny (B, m)
            # mismatch mask — parity itself never leaves the device
            with self._launching(
                (bits.shape, b, k, w), (), kind="scrub_enc",
                guard="scrub_enc", w=w, b=b, k="enc", b_real=len(chunk),
                real_bytes=sum(
                    (k + m) * req.item.data.shape[1] for req in chunk),
                padded_bytes=b * (k + m) * w,
            ):
                out = jax.device_get(jax.block_until_ready(
                    gf_encode_compare(bits, jax.device_put(data),
                                      jax.device_put(parity))))
            self._count("enc", len(chunk))
            outs += list(out[:len(chunk)])
        return outs

    @staticmethod
    def _host_enc_group(_w: int, group: list[Request]) -> list[np.ndarray]:
        from ceph_tpu.ops.gf256 import gf_matmul

        return [np.any(gf_matmul(req.item.C, req.item.data)
                       != req.item.parity, axis=-1) for req in group]

    # -- warmup --------------------------------------------------------

    def prewarm(self, ec_impl=None, widths=None, *, batches=None) -> int:
        """Compile every launch shape this verifier can dispatch: crc
        over the full bucket ladder, plus the re-encode compare of
        ``ec_impl``'s code.  Blocking: warmup only.  Returns the count."""
        import jax.numpy as jnp

        from ceph_tpu.ops.hashing import batched_crc32c_device
        from ceph_tpu.ops.rs_kernels import gf_encode_compare

        buckets = batcher.bucket_ladder(
            self.min_bucket, self.tile_cap, widths)
        shapes = [("crc", b, w)
                  for w in buckets for b in (1, self.crc_lanes)]
        if ec_impl is not None and getattr(
                ec_impl, "rows_per_chunk", 1) == 1 and hasattr(
                ec_impl, "coding_matrix"):
            C = np.asarray(ec_impl.coding_matrix, dtype=np.uint8)
            ec_m, ec_k = C.shape
            ec_bits = self._bits(C)
            shapes += [(ec_bits.shape, b, ec_k, w) for w in buckets
                       for b in batches or (1, self.max_batch)]

        def compile_one(key: tuple):
            if key[0] == "crc":
                _, b, w = key
                return batched_crc32c_device(
                    self._crc_mat(w), jnp.zeros((b, w), np.uint8))
            _, b, k, w = key
            return gf_encode_compare(
                ec_bits, jnp.zeros((b, k, w), np.uint8),
                jnp.zeros((b, ec_m, w), np.uint8))

        return self._prewarm(shapes, compile_one)


def shared() -> ScrubVerifier:
    """Process-wide verifier (co-hosted daemons' scrubs coalesce across
    PGs in it)."""
    return batcher.shared("scrub", ScrubVerifier)


def reset_shared() -> None:
    """Test hook: drop the process-wide verifier."""
    batcher.reset_shared("scrub")
