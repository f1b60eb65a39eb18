"""The plain reference of a ``plugin=lrc k=4 m=2 l=3`` pool: what each of
an acknowledged object's 8 stored shards must be, written from the
construction that Ceph's ``doc/rados/operations/erasure-code-lrc.rst``
describes for the profile ("Reduce recovery bandwidth between hosts"),
with numpy tables over GF(2^8), polynomial 0x11d, and no code of the
program or of another reference.

The k/m/l profile is three layers over 8 positions (k + m + (k+m)/l):

- the data at positions 0, 1, 4 and 5 (mapping ``DD__DD__``): chunk i of
  a stripe goes to the i-th ``D``;
- a global RS(4, 2) layer ``DDc_DDc_``: positions 2 and 6 are the coding
  rows of jerasure's ``reed_sol_van`` for k=4 m=2, ``1 1 1 1`` and
  ``1 70 143 200`` (hexadecimal 01 46 8f c8), over the data in position
  order;
- two local layers ``DDDc____`` and ``____DDDc``: positions 3 and 7 are
  ``reed_sol_van``'s one coding row for k=3 m=1, ``1 1 1`` (plain xor),
  over positions 0, 1, 2 and 4, 5, 6.

Each layer's inner code is upstream's default, jerasure ``reed_sol_van``
(w = 8), whose coding rows are computed here from its construction
(``reed_sol_vandermonde_coding_matrix``; Plank's "A Tutorial on
Reed-Solomon Coding for Fault-Tolerance in RAID-like Systems" and its
2003 correction; recalled, not read here): the (k+m) x k extended
Vandermonde matrix (row 0 is e_0, row k+m-1 is e_{k-1}, row i between
them is 1, i, i^2, ...) brought to systematic form by column
operations, every column then scaled so that the first coding row is
all ones, and every later coding row so that its first entry is one.

The layers are applied bottom up (the global one first, whose parities
the local ones cover), per stripe of ``stripe_unit`` bytes a chunk;
every step is byte-wise linear, so a shard is the concatenation over the
object's stripes of its chunk, computed here over whole shards at once.
A lost chunk is rebuilt from the 3 others of its local group.
"""

from __future__ import annotations

import numpy as np

GF_POLY = 0x11D
N_CHUNKS = 8
DATA = (0, 1, 4, 5)
GROUPS = ((0, 1, 2, 3), (4, 5, 6, 7))


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


def reed_sol_van_rows(k: int, m: int) -> list[list[int]]:
    """The (m, k) coding rows of jerasure's ``reed_sol_van``, w = 8."""
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


def scale(c: int, v: np.ndarray) -> np.ndarray:
    """``c * v`` over GF(2^8), element by element."""
    if c == 0:
        return np.zeros_like(v)
    out = EXP[LOG[v] + LOG[c]]
    out[v == 0] = 0
    return out


#: position -> (coding row, the positions it covers), the global layer's
#: first: the local rows cover its parity at 2 and 6
_GLOBAL, _LOCAL = reed_sol_van_rows(4, 2), reed_sol_van_rows(3, 1)[0]
PARITY = {2: (_GLOBAL[0], DATA), 6: (_GLOBAL[1], DATA),
          3: (_LOCAL, (0, 1, 2)), 7: (_LOCAL, (4, 5, 6))}


def lrc_shards(blob: bytes, stripe_unit: int) -> list[bytes]:
    """The 8 shard payloads, by position, of one object whose length is
    a whole number of 4 x ``stripe_unit`` stripes."""
    arr = np.frombuffer(blob, np.uint8)
    k = len(DATA)
    if arr.size % (k * stripe_unit):
        raise ValueError(f"{arr.size} bytes is not a whole number of "
                         f"{k} x {stripe_unit} stripes")
    data = arr.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1)
    shards: dict[int, np.ndarray] = dict(zip(DATA, data))
    for pos, (row, over) in PARITY.items():
        acc = np.zeros(data.shape[1], np.uint8)
        for c, src in zip(row, over):
            acc ^= scale(c, shards[src])
        shards[pos] = acc
    return [shards[p].tobytes() for p in range(N_CHUNKS)]


def expected_copies(pool: dict, blob: bytes) -> list[bytes]:
    """What position 0..7 of the acting set must hold."""
    if (pool.get("type"), pool.get("plugin"), pool.get("k"), pool.get("m"),
            pool.get("profile", {}).get("l")) != (
            "erasure", "lrc", 4, 2, "3"):
        raise ValueError("lrc423 is the reference of a plugin=lrc k=4 m=2 "
                         "l=3 pool, not of " + repr(pool))
    return lrc_shards(blob, pool["stripe_unit"])
