"""EncodeService: the in-daemon microbatching bridge onto the device(s).

OSD write/recovery ops running as concurrent asyncio tasks enqueue their
GF(2^8) matrix applications here; requests that land within one window
and share a matrix are laid side by side along the column dimension S (a
GF matmul is independent column by column), padded to a fixed
power-of-two width bucket and dispatched as ONE launch: on several
devices the column-split mesh program of parallel/encode_farm.py (every
device applies the replicated bit-matrix to its own column block, no
collective, no batch padding), on one accelerator the same kernel there.
Window, dispatch, warm set and bookkeeping are parallel/batcher.py's.

This is the seam the reference implements as the ECSubWrite fan-out /
per-op `ECUtil::encode` loop (src/osd/ECCommon.cc:749
generate_transactions -> ECTransaction.cc:37 encode_and_write;
src/osd/OSDMapMapping.h:18 ParallelPGMapper for the batch pattern):
independent per-PG ops become one batched TPU computation.

In a cpu-only single-device process the service is inactive and callers
keep their host/1-chip path.  An active service is filed every request;
a flushed group that carries fewer than ``min_bytes`` in all is answered
on the host at the flush (parallel/batcher.py): the decision is the
group's, so 4 KiB overwrites that arrive together share a launch that
none of them would be worth alone.
"""

from __future__ import annotations

import itertools

import numpy as np

from ceph_tpu.parallel import batcher, encode_farm
from ceph_tpu.parallel.batcher import LaunchBatcher, MatMul, Request

#: a flushed group that carries fewer bytes than this is answered on the
#: host — TPU/mesh dispatch overhead dwarfs the math (SURVEY.md §7 hard
#: part 3)
DEFAULT_MIN_BYTES = 32768


class EncodeService(LaunchBatcher):
    """Coalesces concurrent GF matrix applications into one launch.

    ``mesh`` is any ``jax.sharding.Mesh``: a launch cuts its columns
    over every device of it, whatever its axes.  ``device`` (no mesh)
    is single-device mode: the window still coalesces concurrent per-PG
    ops into ONE dispatch.  With neither the service is inactive."""

    wait_name = "encode_batch_wait"

    def __init__(self, mesh=None, *, device=None,
                 min_bytes: int = DEFAULT_MIN_BYTES,
                 window_s: float = 0.001):
        super().__init__(
            "encode_farm", window_s=window_s,
            placement=None if mesh is None
            else encode_farm.replicated_sharding(mesh))
        self.mesh = mesh
        self.device = device
        self.min_bytes = min_bytes
        # what a launch counts itself as (``<kind>_dispatches``, the
        # span's ``encode_<kind>``), and its transfer-guard window
        self._kind, self._guard = (("single", "encode_single")
                                   if mesh is None else ("dp", "encode_mesh"))

    def active(self) -> bool:
        return self.mesh is not None or self.device is not None

    async def apply(self, M: np.ndarray, rows: np.ndarray,
                    **tags) -> np.ndarray:
        """``M @ rows`` over GF(2^8), batched with concurrent callers:
        M an (out, k) byte matrix (coding or cached decode matrix), rows
        (k, S) uint8.  Returns (out, S) uint8.  ``tags`` (``kind``: the
        same for every caller of one M) go on the launch's span, which
        then says what it took in and gave back (``helper_bytes``,
        ``rebuilt_bytes``)."""
        assert self.active()
        return await self.submit(batcher.matrix_key(M),
                                 MatMul(M, rows, tags or None))

    # -- the plan ------------------------------------------------------

    def _run_group(self, _key, group: list[Request]) -> list[np.ndarray]:
        """Worker-thread body: ONE launch for the whole group, its rows
        side by side along S (no batch dimension, no batch padding),
        padded to the fixed width bucket so jit shapes stay bounded; a
        mesh then cuts the columns into one block per device."""
        import jax

        M = group[0].item.M
        bits = self._bits(M)
        k = M.shape[1]
        offs = [0, *itertools.accumulate(
            req.item.rows.shape[1] for req in group)]
        total = offs[-1]
        S = self._bucket(total)
        big = np.zeros((k, S), np.uint8)
        for req, lo, hi in zip(group, offs, offs[1:]):
            big[:, lo:hi] = req.item.rows
        tags = dict(group[0].item.tags or ())
        with self._launching(
            (self._kind, bits.shape, k, S), group,
            kind=tags.pop("kind", f"encode_{self._kind}"),
            guard=self._guard, w=S,
            b_real=len(group), real_bytes=total * k, padded_bytes=k * S,
        ) as span:
            if group[0].item.tags:
                # the bytes the launch is for, pads left out
                span.tag(helper_bytes=total * k,
                         rebuilt_bytes=M.shape[0] * total, **tags)
            res = self._launch(bits, big)
            out = jax.device_get(res)
            if self.mesh is not None:
                ndev = len(res.sharding.device_set)
                span.tag(devices=ndev, pad_bytes=(S - total) * k)
        self.stats[f"{self._kind}_dispatches"] += 1
        self.stats["coalesced"] += len(group)
        if self.mesh is not None:
            # a mesh whose launches land on its first device only is
            # not a farm: keep the most devices any result has sat on
            self.stats["mesh_devices_used"] = max(
                self.stats["mesh_devices_used"], ndev)
            self.stats["mesh_occupied_bytes"] += total * k
            self.stats["mesh_padded_bytes"] += S * k
        return [np.ascontiguousarray(out[:, lo:hi])
                for lo, hi in zip(offs, offs[1:])]

    _host_group = staticmethod(batcher.host_matmul_group)

    def _group_bytes(self, group: list[Request]) -> int:
        return sum(req.item.rows.size for req in group)

    def _bucket(self, total: int) -> int:
        """The fixed launch width holding ``total`` real columns."""
        if self.mesh is None:
            return batcher.pow2_bucket(total, 1)
        return encode_farm.cols_width(self.mesh, total)

    def _launch(self, bits, big: np.ndarray):
        """Upload ``big`` where the program wants it and launch: the
        column-split mesh program, or the one device's kernel.  Warm-up
        and I/O path both come here, so shapes and shardings agree."""
        import jax

        if self.mesh is None:
            from ceph_tpu.ops.rs_kernels import BitmatrixCodec

            return BitmatrixCodec._apply(bits, jax.device_put(big), None)
        return encode_farm.mesh_encode_cols(self.mesh, bits, jax.device_put(
            big, encode_farm.cols_sharding(self.mesh)))

    # -- warmup --------------------------------------------------------

    def prewarm(self, M: np.ndarray, widths, *, coalesce: int = 16) -> int:
        """Compile the fixed-bucket launch shapes this service can hit
        for matrix ``M`` and per-request payload widths ``widths`` (a
        window concatenates up to ``coalesce`` requests).  Blocking —
        daemon warmup only.  Returns the number of programs compiled."""
        if not self.active():
            return 0
        bits = self._bits(np.asarray(M, np.uint8))
        k = M.shape[1]
        buckets = {self._bucket(w << f) for w in widths
                   for f in range(coalesce.bit_length())}
        return self._prewarm(
            [(self._kind, bits.shape, k, S) for S in sorted(buckets)],
            lambda key: self._launch(
                bits, np.zeros((k, key[-1]), np.uint8)))


def _build() -> EncodeService:
    import jax

    devs = jax.devices()
    if len(devs) > 1:
        return EncodeService(jax.sharding.Mesh(np.asarray(devs), ("cols",)))
    return EncodeService(
        device=devs[0] if devs[0].platform == "tpu" else None)


def shared() -> EncodeService:
    """Process-wide service: a one-axis mesh over all local devices (the
    device count the process sees is the only thing that chooses the
    launch path), single-device mode on a lone TPU, inactive in a
    cpu-only process (one CPU device) so host paths keep their exact
    semantics/costs.  A backend that fails to start (chip
    held by another process, bad platform env) raises, now and on the
    next use: serving the cluster from host numpy because the chip
    could not be reached is never a silent outcome."""
    return batcher.shared("encode", _build)


def reset_shared() -> None:
    """Test hook: drop the process-wide service."""
    batcher.reset_shared("encode")
