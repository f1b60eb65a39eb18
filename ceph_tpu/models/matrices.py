"""Generator-matrix constructions for systematic MDS codes over GF(2^8).

All return the (m, k) *coding* part C of the systematic (k+m, k)
distribution matrix [I; C]: parity_i = XOR_j C[i,j] * data_j.

Provenance of each construction (bit-compat lineage):

- :func:`isa_rs_vandermonde_matrix` / :func:`isa_cauchy_matrix` follow
  Intel ISA-L's ``gf_gen_rs_matrix`` / ``gf_gen_cauchy1_matrix`` exactly
  (used by the reference ISA plugin, src/erasure-code/isa/
  ErasureCodeIsa.cc:384-387).
- :func:`jerasure_rs_vandermonde_matrix` follows jerasure's
  ``reed_sol_vandermonde_coding_matrix`` (Plank & Ding's corrected
  Vandermonde construction; used at src/erasure-code/jerasure/
  ErasureCodeJerasure.cc:203).
- :func:`cauchy_original_matrix` follows jerasure's
  ``cauchy_original_coding_matrix`` (ErasureCodeJerasure.cc:323).
- :func:`cauchy_good_matrix` follows jerasure's
  ``cauchy_good_general_coding_matrix`` optimization
  (ErasureCodeJerasure.cc:333): scale rows/columns to minimize the number
  of ones in the bit-matrix expansion.

The jerasure/gf-complete submodules are empty in the reference checkout,
so the jerasure-lineage constructions are re-derived from the published
algorithms; MDS + round-trip properties are enforced by tests
(tests/test_matrices.py), corpus bit-exactness is asserted structurally
(known identities: first RS-Vandermonde coding row is all-ones, etc.).
"""

from __future__ import annotations

import numpy as np

from ceph_tpu.ops.gf256 import (
    gf_const_to_bitmatrix,
    gf_div,
    gf_inv,
    gf_mat_inv,
    gf_matmul,
    gf_mul,
)


def _check_km(k: int, m: int) -> None:
    if k + m > 256:
        raise ValueError("k+m must be <= 256 for GF(2^8) codes")
    if k < 1 or m < 1:
        raise ValueError("k and m must be >= 1")


def isa_rs_vandermonde_matrix(k: int, m: int) -> np.ndarray:
    """ISA-L ``gf_gen_rs_matrix`` coding part: row s is the geometric
    sequence (2^s)^j, j=0..k-1.  MDS only for the (k,m) ranges ISA-L
    supports; the reference plugin restricts Vandermonde to m<=2 beyond
    which it forces Cauchy (ErasureCodeIsa.cc:206)."""
    _check_km(k, m)
    C = np.zeros((m, k), dtype=np.uint8)
    gen = np.uint8(1)  # row s uses ratio 2^s: rows are 1^j, 2^j, 4^j, ...
    for s in range(m):
        p = np.uint8(1)
        for j in range(k):
            C[s, j] = p
            p = gf_mul(p, gen)
        gen = gf_mul(gen, np.uint8(2))
    return C


def isa_cauchy_matrix(k: int, m: int) -> np.ndarray:
    """ISA-L ``gf_gen_cauchy1_matrix`` coding part: C[i,j] = 1/((k+i) ^ j)."""
    _check_km(k, m)
    i = np.arange(k, k + m, dtype=np.int32)[:, None]
    j = np.arange(k, dtype=np.int32)[None, :]
    return gf_inv((i ^ j).astype(np.uint8))


def _big_vandermonde_distribution_matrix(rows: int, cols: int) -> np.ndarray:
    """Plank's corrected Vandermonde construction (jerasure
    ``reed_sol_big_vandermonde_distribution_matrix``): start from the
    EXTENDED Vandermonde matrix (``reed_sol_extended_vandermonde_
    matrix``: V[i,j] = i^j, but the last row is 0, ..., 0, 1), reduce
    the top cols x cols to identity with elementary column operations,
    then normalize so the first coding row and the first coding column
    are all ones.  (Until PR 34 the last row was (rows-1)^j as well, so
    the last coding row of every m >= 2 code differed from jerasure's:
    ``reed_sol_01 7 7 8`` of its manual ends 1 187 104 210 211 105 186.)"""
    if cols >= rows:
        raise ValueError("need rows > cols")
    V = np.zeros((rows, cols), dtype=np.uint8)
    for i in range(rows - 1):
        V[i, 0] = 1
        for j in range(1, cols):
            V[i, j] = gf_mul(V[i, j - 1], np.uint8(i))
    V[rows - 1, cols - 1] = 1
    # top cols x cols -> identity by column ops
    for i in range(cols):
        if V[i, i] == 0:
            nz = [j for j in range(i + 1, cols) if V[i, j] != 0]
            if not nz:
                raise np.linalg.LinAlgError("vandermonde reduction failed")
            V[:, [i, nz[0]]] = V[:, [nz[0], i]]
        if V[i, i] != 1:
            V[:, i] = gf_mul(V[:, i], gf_inv(V[i, i]))
        for j in range(cols):
            if j != i and V[i, j] != 0:
                V[:, j] ^= gf_mul(np.uint8(V[i, j]), V[:, i])
    # first coding row -> all ones (scale the coding part of each column)
    for j in range(cols):
        t = V[cols, j]
        if t == 0:
            raise np.linalg.LinAlgError("zero in first coding row")
        if t != 1:
            V[cols:, j] = gf_mul(V[cols:, j], gf_inv(t))
    # first coding column -> all ones (scale each later coding row)
    for i in range(cols + 1, rows):
        t = V[i, 0]
        if t != 0 and t != 1:
            V[i, :] = gf_mul(V[i, :], gf_inv(t))
    return V


def jerasure_rs_vandermonde_matrix(k: int, m: int) -> np.ndarray:
    """jerasure ``reed_sol_vandermonde_coding_matrix(k, m, w=8)``."""
    _check_km(k, m)
    return _big_vandermonde_distribution_matrix(k + m, k)[k:, :]


def jerasure_rs_r6_matrix(k: int) -> np.ndarray:
    """jerasure ``reed_sol_r6_coding_matrix(k, w)``: the RAID6 P/Q pair —
    row 0 all ones (P = XOR), row 1 the geometric sequence 2^j (Q).
    Used by the reed_sol_r6_op technique (ErasureCodeJerasure.cc:255)."""
    _check_km(k, 2)
    C = np.ones((2, k), dtype=np.uint8)
    for j in range(1, k):
        C[1, j] = gf_mul(C[1, j - 1], np.uint8(2))
    return C


def cauchy_original_matrix(k: int, m: int) -> np.ndarray:
    """jerasure ``cauchy_original_coding_matrix``: C[i,j] = 1/(i ^ (m+j))."""
    _check_km(k, m)
    i = np.arange(m, dtype=np.int32)[:, None]
    j = np.arange(k, dtype=np.int32)[None, :]
    return gf_inv((i ^ (m + j)).astype(np.uint8))


def _bitmatrix_ones(c: int) -> int:
    return int(gf_const_to_bitmatrix(c).sum())


def cauchy_good_matrix(k: int, m: int) -> np.ndarray:
    """jerasure ``cauchy_good_general_coding_matrix``: start from the
    original Cauchy matrix and apply its "improvement" — divide each
    column by its row-0 element (making row 0 all ones), then scale every
    other row by the element whose bit-matrix has the fewest ones."""
    C = cauchy_original_matrix(k, m).copy()
    # make row 0 all ones
    for j in range(k):
        if C[0, j] != 1:
            C[:, j] = gf_div(C[:, j], C[0, j])
    # optimize remaining rows: choose divisor minimizing total bitmatrix ones
    for i in range(1, m):
        best_row, best_ones = C[i], sum(_bitmatrix_ones(int(c)) for c in C[i])
        for j in range(k):
            d = C[i, j]
            if d in (0, 1):
                continue
            cand = gf_div(C[i], d)
            ones = sum(_bitmatrix_ones(int(c)) for c in cand)
            if ones < best_ones:
                best_row, best_ones = cand, ones
        C[i] = best_row
    return C


def decode_matrix_for(C: np.ndarray, erasures: list[int]) -> np.ndarray:
    """Rows that reconstruct the erased chunks from k surviving chunks.

    ``C`` is the (m,k) coding part; chunk indices 0..k-1 are data,
    k..k+m-1 parity.  Returns (len(erasures), k): multiply by the first k
    *surviving* chunks (in index order) to reconstruct each erased chunk
    (data or parity).  This is the algebra behind jerasure's
    ``jerasure_matrix_decode`` and ISA-L's decode-table construction
    (ErasureCodeIsa.cc:227-310); plugin layers cache it per erasure
    signature.
    """
    m, k = C.shape
    full = np.concatenate([np.eye(k, dtype=np.uint8), C], axis=0)
    erased = set(erasures)
    survivors = [i for i in range(k + m) if i not in erased][:k]
    if len(survivors) < k:
        raise ValueError("not enough surviving chunks to decode")
    B = full[survivors]          # (k, k): survivors = B @ data
    Binv = gf_mat_inv(B)         # data = Binv @ survivors
    return gf_matmul(full[list(erasures)], Binv)


# --- SHEC (shingled erasure code) ------------------------------------------


def shec_recovery_efficiency(k: int, m1: int, m2: int, c1: int, c2: int) -> float:
    """SHEC's r_e1 metric: mean chunks read to recover one lost chunk,
    for a split of the parity rows into two shingle groups (m1,c1) and
    (m2,c2) (reference src/erasure-code/shec/ErasureCodeShec.cc
    shec_calc_recovery_efficiency1)."""
    if m1 < c1 or m2 < c2:
        return -1.0
    if (m1 == 0 and c1 != 0) or (m2 == 0 and c2 != 0):
        return -1.0
    r_eff_k = [10**8] * k
    r_e1 = 0.0
    for m_g, c_g in ((m1, c1), (m2, c2)):
        for rr in range(m_g):
            start = ((rr * k) // m_g) % k
            end = (((rr + c_g) * k) // m_g) % k
            cc = start
            first = True
            while first or cc != end:
                first = False
                r_eff_k[cc] = min(r_eff_k[cc], ((rr + c_g) * k) // m_g - (rr * k) // m_g)
                cc = (cc + 1) % k
            r_e1 += ((rr + c_g) * k) // m_g - (rr * k) // m_g
    r_e1 += sum(r_eff_k)
    return r_e1 / (k + m1 + m2)


def shec_coding_matrix(k: int, m: int, c: int, single: bool = False) -> np.ndarray:
    """SHEC's shingled (m, k) coding matrix: the jerasure RS-Vandermonde
    matrix with, per parity row, all columns outside that row's shingle
    window zeroed (reference ErasureCodeShec.cc
    shec_reedsolomon_coding_matrix).  ``single`` keeps one shingle group
    (technique=single); otherwise the (m1,c1)/(m2,c2) split minimizing
    :func:`shec_recovery_efficiency` is chosen, scanning c1 in 0..c/2 and
    m1 in 0..m exactly as the reference does."""
    if single:
        m1, c1 = 0, 0
    else:
        best = (-1, -1)
        min_r = 100.0
        eps = np.finfo(float).eps
        for c1 in range(c // 2 + 1):
            for m1 in range(m + 1):
                c2, m2 = c - c1, m - m1
                if m1 < c1 or m2 < c2:
                    continue
                if (m1 == 0 and c1 != 0) or (m2 == 0 and c2 != 0):
                    continue
                if (m1 != 0 and c1 == 0) or (m2 != 0 and c2 == 0):
                    continue
                r = shec_recovery_efficiency(k, m1, m2, c1, c2)
                if min_r - r > eps and r < min_r:
                    min_r = r
                    best = (c1, m1)
        c1, m1 = best
    m2, c2 = m - m1, c - c1
    M = jerasure_rs_vandermonde_matrix(k, m)
    for off, m_g, c_g in ((0, m1, c1), (m1, m2, c2)):
        for rr in range(m_g):
            end = ((rr * k) // m_g) % k
            cc = (((rr + c_g) * k) // m_g) % k
            while cc != end:
                M[off + rr, cc] = 0
                cc = (cc + 1) % k
    return M
