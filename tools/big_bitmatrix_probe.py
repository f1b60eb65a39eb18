#!/usr/bin/env python
"""big_bitmatrix_probe: the fused Pallas kernels against XLA's
``gf_bitmatmul`` on bit-matrices around ``ops/rs_kernels.
_PALLAS_MAX_BITS``: whether Mosaic compiles them, whether the bytes
equal ``gf_matmul``, the first launch (compile) and the median launch.
One matrix shape a process, since a refused kernel can take the
process with it.  TPU only; through the chip tool:

  for s in w8_packet clay_repair clay_encode; do
    python tools/big_bitmatrix_probe.py $s; done

Times are host-clock probe readings of one run, not benchmark metrics.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {  # GF(2^8) matrix (out, in) and the byte width of one object
    "w8_packet": ((24, 64), 65536),        # (192, 512) bits
    "clay_repair": ((64, 176), 8192),      # (512, 1408)
    "clay_encode": ((256, 512), 8192),     # (2048, 4096)
}
LAUNCHES = 10


def main(name: str) -> int:
    import jax

    from ceph_tpu.ops import rs_kernels as rk
    from ceph_tpu.ops.gf256 import gf_matmul, gf_matrix_to_bitmatrix

    if jax.devices()[0].platform != "tpu":
        print("big_bitmatrix_probe: needs a TPU", file=sys.stderr)
        return 1
    (out, rows_in), width = SHAPES[name]
    rng = np.random.default_rng(1)
    M = rng.integers(1, 256, (out, rows_in), dtype=np.uint8)
    rows = rng.integers(0, 256, (rows_in, width), dtype=np.uint8)
    want = gf_matmul(M, rows)
    bits = jax.device_put(gf_matrix_to_bitmatrix(M))
    x = jax.device_put(rows)
    tries = [("xla", lambda: rk.gf_bitmatmul(bits, x))]
    for tile in sorted({rk._pick_tile(width), 2048, 512}, reverse=True):
        tries.append((f"pallas_tile{tile}", lambda t=tile:
                      rk.gf_bitmatmul_pallas(bits, x, tile_s=t)))
    for label, fn in tries:
        rec = {"shape": name, "bits": int(bits.size), "S": width,
               "kernel": label}
        try:
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn())
            rec["first_s"] = round(time.perf_counter() - t0, 3)
            times = []
            for _ in range(LAUNCHES):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                times.append(time.perf_counter() - t0)
            rec.update(ok=True, median_ms=round(
                sorted(times)[LAUNCHES // 2] * 1e3, 3),
                equal=bool(np.array_equal(np.asarray(got), want)))
        except Exception as e:      # Mosaic refused it: that is a reading
            rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:300])
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
