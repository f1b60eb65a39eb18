"""The plain reference of a ``plugin=clay k m d`` pool (d = k+m-1): what
each of an acknowledged object's k+m stored shards must be, written from
the construction of the code (Vajha et al., "Clay Codes: Moulding MDS
Codes to Yield an MSR Code", FAST '18; upstream Ceph's
``src/erasure-code/clay/ErasureCodeClay.cc``, recalled, not read here)
with numpy tables over GF(2^8), polynomial 0x11d, and no code of the
program.

The construction, for q = d-k+1, nu = (-(k+m)) mod q, t = (k+m+nu)/q and
alpha = q^t sub-chunks a chunk:

- a codeword is a q x t array of nodes, node (x, y) = y*q + x: data
  chunks are nodes 0..k-1, nodes k..k+nu-1 are virtual and all zero (the
  code is shortened), the m = q parity chunks are the last row y = t-1;
- a node's chunk is alpha sub-chunks, one per plane z; plane z has the
  base-q digits (z_0, ..., z_{t-1}), most significant first;
- in plane z node (x, y) is paired with node (z_y, y) in the plane z'
  that is z with digit y replaced by x; where z_y = x the node is alone
  ("dotted").  The stored (coupled) values C and the uncoupled values U
  of a pair are one codeword (C_hi, C_lo, U_hi, U_lo) of the (2,2)
  ``reed_sol_van`` code, hi being the node with the larger x: so
  (U_hi, U_lo) = P (C_hi, C_lo), and for a dotted node U = C;
- every plane of U is a codeword of the scalar (k+nu, m)
  ``reed_sol_van`` code.

So the parities are the layered decode of the m erased parity nodes:
every plane has exactly one of them dotted (intersection score 1), so
one level does it: uncouple the data rows, encode each plane of U, couple
the parity row back.  An object's stripes are treated as one vector: a
sub-chunk here is the concatenation of that sub-chunk over the stripes.

Departures from upstream: none in the bytes.  In the computing: upstream
runs its generic ``decode_layered`` over all planes in order of
intersection score and solves each pair through the (2,2) code's
decoder; here the two 2x2 maps (P and its inverse) are applied outright,
plane by plane, and only d = k+m-1 is covered (the parities are then a
whole row).  ``reed_sol_van`` is jerasure's: the extended Vandermonde
matrix brought to systematic form by column operations, then scaled so
that the first coding row and the first coding column are all ones.
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


def reed_sol_van(k: int, m: int) -> list[list[int]]:
    """The (m, k) coding rows of jerasure's ``reed_sol_van`` for w = 8."""
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
    return d[cols:]


def inverse_2x2(p: list[list[int]]) -> list[list[int]]:
    det = inv(mul(p[0][0], p[1][1]) ^ mul(p[0][1], p[1][0]))
    return [[mul(det, p[1][1]), mul(det, p[0][1])],
            [mul(det, p[1][0]), mul(det, p[0][0])]]


def geometry(k: int, m: int, d: int) -> tuple[int, int, int, int]:
    """(q, t, nu, alpha)."""
    if d != k + m - 1:
        raise ValueError("this reference covers d = k+m-1 only")
    q = d - k + 1
    nu = (q - (k + m) % q) % q
    t = (k + m + nu) // q
    return q, t, nu, q ** t


def digits(z: int, q: int, t: int) -> list[int]:
    """Base-q digits of plane z, most significant first."""
    out = [0] * t
    for i in range(t):
        out[t - 1 - i] = z % q
        z //= q
    return out


def repair_planes(node: int, q: int, t: int) -> list[int]:
    """The alpha/q planes in which node (x, y) is dotted: the sub-chunks
    every helper sends to rebuild it."""
    y, x = divmod(node, q)
    return [z for z in range(q ** t) if digits(z, q, t)[y] == x]


def _pair(tr: list[list[int]], x: int, xs: int, mine: np.ndarray,
          partner: np.ndarray) -> np.ndarray:
    """One output of the 2x2 map ``tr`` for the node at x whose partner
    sits at xs: row 0 and the first operand belong to the larger x."""
    if x > xs:
        return scale(tr[0][0], mine) ^ scale(tr[0][1], partner)
    return scale(tr[1][0], partner) ^ scale(tr[1][1], mine)


def clay_shards(blob: bytes, k: int, m: int, d: int,
                stripe_unit: int) -> list[bytes]:
    """The k+m shard payloads of one object whose length is a whole
    number of stripes of k chunks of ``stripe_unit`` bytes."""
    q, t, nu, alpha = geometry(k, m, d)
    arr = np.frombuffer(blob, np.uint8)
    if arr.size % (k * stripe_unit) or stripe_unit % alpha:
        raise ValueError(f"{arr.size} bytes is not a whole number of {k} x "
                         f"{stripe_unit} stripes of {alpha} sub-chunks")
    sc = stripe_unit // alpha
    n_data = k + nu
    # C[node][z]: sub-chunk z of every stripe, side by side
    C = np.zeros((q * t, alpha, arr.size // (k * stripe_unit) * sc), np.uint8)
    C[:k] = arr.reshape(-1, k, alpha, sc).transpose(1, 2, 0, 3).reshape(
        k, alpha, -1)
    U = np.zeros_like(C)
    P = reed_sol_van(2, 2)
    G = reed_sol_van(n_data, m)
    step = [q ** (t - 1 - y) for y in range(t)]
    for z in range(alpha):          # uncouple the data rows, encode U
        zv = digits(z, q, t)
        for node in range(n_data):
            y, x = divmod(node, q)
            if zv[y] == x:
                U[node, z] = C[node, z]
            else:
                U[node, z] = _pair(
                    P, x, zv[y], C[node, z],
                    C[y * q + zv[y], z + (x - zv[y]) * step[y]])
        for j in range(m):
            for i in range(n_data):
                U[n_data + j, z] ^= scale(G[j][i], U[i, z])
    Pinv = inverse_2x2(P)
    for z in range(alpha):          # couple the parity row back
        zv = digits(z, q, t)
        for node in range(n_data, q * t):
            y, x = divmod(node, q)
            if zv[y] == x:
                C[node, z] = U[node, z]
            else:
                C[node, z] = _pair(
                    Pinv, x, zv[y], U[node, z],
                    U[y * q + zv[y], z + (x - zv[y]) * step[y]])
    stored = [n for n in range(q * t) if not k <= n < n_data]
    return [C[n].reshape(alpha, -1, sc).transpose(1, 0, 2).tobytes()
            for n in stored]


def expected_copies(pool: dict, blob: bytes) -> list[bytes]:
    """What position 0..k+m-1 of the acting set must hold."""
    if pool["type"] != "erasure" or pool["plugin"] != "clay":
        raise ValueError("the reference of a clay pool")
    return clay_shards(blob, pool["k"], pool["m"], int(pool["profile"]["d"]),
                       pool["stripe_unit"])
