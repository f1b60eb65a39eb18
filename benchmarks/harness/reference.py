"""The configurations' plain reference: what an acknowledged object's
stored bytes must be, computed on the host with numpy tables and no
code of the program.

An EC pool ``plugin=jax technique=cauchy k m`` stores, for shard ``i``
of an object, the concatenation over the object's stripes of chunk
``i`` (``stripe_unit`` bytes each); shards ``k..k+m-1`` are ISA-L's
``gf_gen_cauchy1_matrix`` coding rows (``C[i][j] = 1 / ((k+i) ^ j)``
over GF(2^8), polynomial 0x11d) applied to the k data shards.  A
replicated pool stores the object's bytes on every replica.

This is the reference of every configuration whose file names no other;
one that does (``"reference": "<name>"``) brings
``benchmarks/references/<name>.py`` with the same ``expected_copies``.
"""

from __future__ import annotations

import numpy as np

GF_POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, np.uint8)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def gf_scale(c: int, v: np.ndarray) -> np.ndarray:
    """``c * v`` over GF(2^8), element by element."""
    if c == 0:
        return np.zeros_like(v)
    out = EXP[LOG[v] + LOG[c]]
    out[v == 0] = 0
    return out


def cauchy_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) coding rows of ISA-L's cauchy1 matrix."""
    C = np.zeros((m, k), np.uint8)
    for i in range(m):
        for j in range(k):
            C[i, j] = EXP[255 - LOG[(k + i) ^ j]]
    return C


def ec_shards(blob: bytes, k: int, m: int, stripe_unit: int) -> list[bytes]:
    """The k+m shard payloads of one object whose length is a whole
    number of stripes."""
    arr = np.frombuffer(blob, np.uint8)
    if arr.size % (k * stripe_unit):
        raise ValueError(f"{arr.size} bytes is not a whole number of "
                         f"{k} x {stripe_unit} stripes")
    data = arr.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1)
    C = cauchy_matrix(k, m)
    shards = [data[i].tobytes() for i in range(k)]
    for row in C:
        acc = np.zeros(data.shape[1], np.uint8)
        for j in range(k):
            acc ^= gf_scale(int(row[j]), data[j])
        shards.append(acc.tobytes())
    return shards


def expected_copies(pool: dict, blob: bytes) -> list[bytes]:
    """What position 0..n-1 of the acting set must hold."""
    if pool["type"] == "erasure":
        return ec_shards(blob, pool["k"], pool["m"], pool["stripe_unit"])
    return [blob] * pool["size"]
