#!/usr/bin/env python
"""tile_probe: which Pallas block widths this TPU's compiler accepts.

The evidence behind ``ops/rs_kernels._pick_tile``'s cap.  For every
code, matrix shape (encode, and the 1-row decode: the narrowest output
block) and real op width it runs what ``BitmatrixCodec._apply`` would
select under each candidate cap, and records whether Mosaic compiled
it, whether the bytes equal ``gf_matmul``, and the median launch time.
TPU only; through the chip tool:

  python tools/tile_probe.py        # -> chiprun_out/tile_probe.json

Times are host-clock probe readings of one run, not benchmark metrics.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CODES = ((2, 1), (3, 2), (4, 2), (6, 3), (8, 3), (10, 4))
WIDTHS = (512 << 10, 1 << 20)       # a 4 MiB object's shard, and 2x
CAPS = (32768, 65536, 262144)       # the cap, 2x headroom, the seed's
LAUNCHES = 30


def main() -> int:
    import jax

    from ceph_tpu.models import isa_cauchy_matrix
    from ceph_tpu.models.matrices import decode_matrix_for
    from ceph_tpu.ops import rs_kernels as rk
    from ceph_tpu.ops.gf256 import gf_matmul, gf_matrix_to_bitmatrix

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"tile_probe: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    pick_tile = rk._pick_tile
    rng = np.random.default_rng(0)
    rows_out = []
    for k, m in CODES:
        C = isa_cauchy_matrix(k, m)
        for S in WIDTHS:
            data = rng.integers(0, 256, (k, S), dtype=np.uint8)
            word = np.concatenate([data, gf_matmul(C, data)])
            cases = (("enc", C, data, word[k:]),
                     ("dec1", decode_matrix_for(C, [0]), word[1:k + 1],
                      word[:1]))
            for name, M, rows, want in cases:
                bits = jax.device_put(gf_matrix_to_bitmatrix(M))
                x = jax.device_put(rows)
                for cap in CAPS:
                    # _apply reads the module global at call time
                    rk._pick_tile = lambda s, cap=cap: pick_tile(s, cap)
                    rec = {"code": [k, m], "matrix": name, "S": S,
                           "cap": cap, "groups": rk._pick_groups(
                               k, M.shape[0], S, pick_tile(S, cap))}
                    try:
                        t0 = time.perf_counter()
                        out = jax.block_until_ready(
                            rk.BitmatrixCodec._apply(bits, x, True))
                        rec["first_launch_s"] = time.perf_counter() - t0
                    except Exception as exc:    # a refusal is a result
                        rec["refused"] = (f"{type(exc).__name__}: "
                                          + str(exc).split("\n")[0][:300])
                    else:
                        ts = []
                        for _ in range(LAUNCHES):
                            t0 = time.perf_counter()
                            jax.block_until_ready(
                                rk.BitmatrixCodec._apply(bits, x, True))
                            ts.append(time.perf_counter() - t0)
                        rec["launch_ms_median"] = float(np.median(ts)) * 1e3
                        rec["byte_exact"] = bool(
                            np.array_equal(jax.device_get(out), want))
                    finally:
                        rk._pick_tile = pick_tile
                    print(json.dumps(rec), flush=True)
                    rows_out.append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/tile_probe.json", "w") as f:
        json.dump({"device": dev.device_kind, "jax": jax.__version__,
                   "results": rows_out}, f, indent=1)
    bad = [r for r in rows_out if r["cap"] == CAPS[0]
           and not r.get("byte_exact")]
    print(json.dumps({"cap": CAPS[0], "cases_at_cap": len(rows_out) // 3,
                      "refused_or_wrong_at_cap": len(bad)}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
