"""The plain reference of a ``plugin=jerasure technique=reed_sol_van k m``
pool (w = 8): what each of an acknowledged object's k+m stored shards
must be, written from jerasure's construction of the code
(``reed_sol_vandermonde_coding_matrix``: Plank's "A Tutorial on
Reed-Solomon Coding for Fault-Tolerance in RAID-like Systems" and its
2003 correction; upstream Ceph's ``src/erasure-code/jerasure``, recalled,
not read here) with numpy tables over GF(2^8), polynomial 0x11d, and no
code of the program or of another reference.

The construction: the (k+m) x k extended Vandermonde matrix (row 0 is
e_0, row k+m-1 is e_{k-1}, row i between them is 1, i, i^2, ...) is
brought to systematic form by column operations, which keep every k of
its rows independent; then every column is scaled so that the first
coding row is all ones, and every later coding row so that its first
entry is one.  For k=4 m=2 the two coding rows are ``1 1 1 1`` (plain
xor, RAID-5's parity) and ``1 70 143 200`` (hexadecimal 01 46 8f c8).

Striping is the pool's: shard ``i`` of an object is the concatenation
over the object's stripes of chunk ``i`` (``stripe_unit`` bytes each);
shards ``k..k+m-1`` are the coding rows applied to the k data shards.
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


def mul(a: int, b: int) -> int:
    return 0 if a == 0 or b == 0 else int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def scale(c: int, v: np.ndarray) -> np.ndarray:
    """``c * v`` over GF(2^8), element by element."""
    if c == 0:
        return np.zeros_like(v)
    out = EXP[LOG[v] + LOG[c]]
    out[v == 0] = 0
    return out


def generator_matrix(k: int, m: int) -> list[list[int]]:
    """All k+m rows of jerasure's ``reed_sol_van`` generator for w = 8:
    the identity on top, the m coding rows under it."""
    rows, cols = k + m, k
    d = [[0] * cols for _ in range(rows)]
    d[0][0] = 1
    d[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        p = 1
        for j in range(cols):
            d[i][j] = p
            p = mul(p, i)
    for i in range(1, cols):        # the top k rows become the identity
        j = next(r for r in range(i, rows) if d[r][i])
        d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:
            c = inv(d[i][i])
            for r in range(rows):
                d[r][i] = mul(c, d[r][i])
        for j in range(cols):
            c = d[i][j]
            if j != i and c:
                for r in range(rows):
                    d[r][j] ^= mul(c, d[r][i])
    for j in range(cols):           # the first coding row becomes ones
        if d[cols][j] != 1:
            c = inv(d[cols][j])
            for r in range(cols, rows):
                d[r][j] = mul(c, d[r][j])
    for r in range(cols + 1, rows):  # and the first coding column
        if d[r][0] != 1:
            c = inv(d[r][0])
            d[r] = [mul(c, v) for v in d[r]]
    return d


def coding_rows(k: int, m: int) -> list[list[int]]:
    """The (m, k) coding rows."""
    return generator_matrix(k, m)[k:]


def ec_shards(blob: bytes, k: int, m: int, stripe_unit: int) -> list[bytes]:
    """The k+m shard payloads of one object whose length is a whole
    number of stripes."""
    arr = np.frombuffer(blob, np.uint8)
    if arr.size % (k * stripe_unit):
        raise ValueError(f"{arr.size} bytes is not a whole number of "
                         f"{k} x {stripe_unit} stripes")
    data = arr.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1)
    shards = [data[i].tobytes() for i in range(k)]
    for row in coding_rows(k, m):
        acc = np.zeros(data.shape[1], np.uint8)
        for j in range(k):
            acc ^= scale(row[j], data[j])
        shards.append(acc.tobytes())
    return shards


def expected_copies(pool: dict, blob: bytes) -> list[bytes]:
    """What position 0..k+m-1 of the acting set must hold."""
    if (pool.get("type"), pool.get("plugin"), pool.get("technique")) != (
            "erasure", "jerasure", "reed_sol_van"):
        raise ValueError("rs_van42 is the reference of a jerasure "
                         "reed_sol_van pool, not of " + repr(pool))
    return ec_shards(blob, pool["k"], pool["m"], pool["stripe_unit"])
