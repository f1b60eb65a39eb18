#!/usr/bin/env python
"""North-star benchmark: RS(k=8, m=3) erasure encode GB/s on one chip.

Clone of the reference harness semantics (ceph_erasure_code_benchmark,
reference src/test/erasure-code/ceph_erasure_code_benchmark.cc:155-193:
encode a buffer in a timed loop, report bytes/second; qa/workunits/
erasure-code/bench.sh:170 computes GiB/s).

Harness design: the timed encode loop runs as ONE launch —
``lax.fori_loop`` over an aliased-carry kernel,

    carry = carry ^ encode(data ^ iteration_seed)

where the per-iteration seed stops XLA hoisting the encode out of the
loop and the carry fold keeps every iteration's parity live; both fuse
into the kernel's existing VPU pass, so each iteration does a full,
honest k*S-byte encode with one extra m*S carry read.  32 iterations
per launch keep the per-launch cost out of the kernel's number.

Runs on a TPU only: without one it exits non-zero (a CPU timing is not
this benchmark's metric).

Input data is generated on-device (threefry); correctness of the
kernel vs the host GF(2^8) reference is asserted on a slice first.

Prints ONE JSON line:
  {"metric": ..., "value": GB/s, "unit": "GB/s", "vs_baseline": value/40}
(vs_baseline: BASELINE.json's driver target is >=40 GB/s/chip.)
"""

import json
import sys
import time

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ceph_tpu.models import isa_cauchy_matrix
    from ceph_tpu.ops import rs_kernels as rk

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py: needs a TPU, JAX found platform {platform!r}",
              file=sys.stderr)
        return 1

    k, m = 8, 3
    codec = rk.BitmatrixCodec(isa_cauchy_matrix(k, m))

    # sanity: kernel output must match the host-reference GF(2^8) encode
    from ceph_tpu.ops.gf256 import gf_matmul

    probe = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (k, 2**20), dtype=np.uint8))
    got = np.asarray(codec.encode(probe, pallas=True))
    ref = gf_matmul(codec.C, np.asarray(probe))
    assert np.array_equal(got, ref), "kernel/host encode mismatch"

    TILE = 262144
    ITERS = 32

    @jax.jit
    def loop_encode(d, n):
        c = jnp.zeros((m, d.shape[1]), jnp.uint8)

        def body(i, c):
            return rk.gf_bitmatmul_pallas_acc(
                codec.encode_bits, d, c,
                jnp.array([i], jnp.int32), tile_s=TILE)

        return lax.fori_loop(0, n, body, c)

    # fold-correctness of the loop harness itself on a small buffer
    small = probe[:, : 2**18]
    got2 = np.asarray(loop_encode(small, jnp.int32(2)))
    r0 = gf_matmul(codec.C, np.asarray(small))
    r1 = gf_matmul(codec.C, np.asarray(small) ^ 1)
    assert np.array_equal(got2, r0 ^ r1), "loop harness fold mismatch"

    data = None
    for s_rows in (256 * 2**20, 64 * 2**20, 16 * 2**20):
        try:
            gen = jax.jit(
                lambda key, S=s_rows: jax.random.bits(key, (k, S), jnp.uint8))
            data = gen(jax.random.key(0))
            jax.block_until_ready(data)
            out = loop_encode(data, jnp.int32(ITERS))
            jax.block_until_ready(out)  # warm + compile
            S = s_rows
            break
        except jax.errors.JaxRuntimeError as exc:
            # only "did not fit" moves down the ladder; a compile or
            # runtime error is a failure, not a size verdict
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            data = out = None  # drop the failed attempt's buffers too
    assert data is not None, "no batch size fit in device memory"

    times = []
    rounds, pause = 6, 3.0
    for r in range(rounds):
        t0 = time.perf_counter()
        out = loop_encode(data, jnp.int32(ITERS))
        jax.block_until_ready(out)
        _ = np.asarray(out[0, :8])  # host round-trip barrier
        times.append(time.perf_counter() - t0)
        if r < rounds - 1:
            time.sleep(pause)
    samples = sorted((k * S * ITERS) / t / 1e9 for t in times)
    gbs = samples[-1]  # best-of-6

    # full spread in the artifact so the headline survives scrutiny
    extra = {
        "samples_gb_s": [round(s, 2) for s in samples],
        "median_gb_s": round(
            float(np.median(np.asarray(samples))), 2),
        "min_gb_s": round(samples[0], 2),
    }
    print(json.dumps({
        "metric": "RS(8,3) erasure encode throughput, 1 chip",
        "value": round(gbs, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbs / 40.0, 3),
        **extra,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
