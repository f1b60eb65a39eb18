"""The native TPU plugin — registered as ``jax``.

This is the plugin the TPU build defaults to (the reference's
``plugin=jax`` slot in an EC profile: the registry seam at
src/osd/PGBackend.cc:570-594 / src/mon/OSDMonitor.cc:7502-7523 means a
profile naming this plugin is reachable end-to-end).  It speaks the same
interface as the compat plugins but is tuned TPU-first:

- ISA-L Cauchy generator by default (MDS for every k+m <= 256, and the
  construction the driver's RS(8,3) north-star benchmark pins);
- chunk sizes aligned to 512 B so stripe batches tile cleanly into the
  fused pallas kernel's lane blocks (ceph_tpu/ops/rs_kernels.py);
- the batched stripe API (``encode_stripes``/``decode_stripes``) keeps
  whole (batch, chunk, S) tensors on device — the OSD EC backend feeds
  coalesced stripes through it so per-op dispatch overhead amortizes;
- host numpy fallback below ``device_min_bytes`` for tiny one-off ops
  (same rationale as SURVEY.md §7 hard part 3).
"""

from __future__ import annotations

import errno

from ceph_tpu.ec.interface import ECError
from ceph_tpu.ec.plugins.matrix_base import MatrixErasureCode
from ceph_tpu.models.matrices import isa_cauchy_matrix, isa_rs_vandermonde_matrix

__erasure_code_version__ = "0.1.0"

#: pallas lane-tile friendliness (rs_kernels._pick_tile needs S with a
#: power-of-two factor >= 512 for the fused path)
TPU_LANE_ALIGN = 512


class ErasureCodeJax(MatrixErasureCode):
    DEFAULT_K = "8"
    DEFAULT_M = "3"

    def parse(self, profile: dict) -> None:
        super().parse(profile)
        self.k = self.to_int("k", profile, self.DEFAULT_K)
        self.m = self.to_int("m", profile, self.DEFAULT_M)
        self.sanity_check_k_m(self.k, self.m)
        if self.k + self.m > 256:
            raise ECError(errno.EINVAL, f"k+m={self.k + self.m} must be <= 256")
        technique = profile.setdefault("technique", "cauchy")
        if technique == "cauchy":
            self.prepare(isa_cauchy_matrix(self.k, self.m))
        elif technique == "reed_sol_van":
            self.prepare(isa_rs_vandermonde_matrix(self.k, self.m))
        else:
            raise ECError(
                errno.ENOENT,
                f"technique={technique} is not a valid coding technique. "
                "Choose one of cauchy, reed_sol_van",
            )

    def get_alignment(self) -> int:
        return TPU_LANE_ALIGN

    def get_chunk_size(self, object_size: int) -> int:
        chunk_size = -(-object_size // self.k)
        modulo = chunk_size % TPU_LANE_ALIGN
        if modulo:
            chunk_size += TPU_LANE_ALIGN - modulo
        return chunk_size


def __erasure_code_init__(name: str, registry) -> None:
    from ceph_tpu.ec.registry import ErasureCodePlugin

    class JaxPlugin(ErasureCodePlugin):
        def factory(self, profile: dict):
            ec = ErasureCodeJax()
            ec.init(profile)
            return ec

    registry.add(name, JaxPlugin())
