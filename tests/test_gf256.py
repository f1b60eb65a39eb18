"""Field-algebra properties of the GF(2^8) core."""

import numpy as np
import pytest

from ceph_tpu.ops import gf256 as gf


def test_exp_log_roundtrip():
    exp = gf.gf_exp_table()
    log = gf.gf_log_table()
    for a in range(1, 256):
        assert exp[log[a]] == a
    # exp cycles with period 255
    assert len({int(exp[i]) for i in range(255)}) == 255


def test_mul_distributes_and_commutes():
    rng = np.random.default_rng(0)
    a, b, c = (rng.integers(0, 256, 200, dtype=np.uint8) for _ in range(3))
    assert np.array_equal(gf.gf_mul(a, b), gf.gf_mul(b, a))
    assert np.array_equal(
        gf.gf_mul(a, b ^ c), gf.gf_mul(a, b) ^ gf.gf_mul(a, c)
    )
    assert np.array_equal(
        gf.gf_mul(gf.gf_mul(a, b), c), gf.gf_mul(a, gf.gf_mul(b, c))
    )


def test_known_products_poly_0x11d():
    # 2*128 = 256 -> reduced by 0x11d -> 0x1d
    assert gf.gf_mul(2, 128) == 0x1D
    assert gf.gf_mul(0, 77) == 0
    assert gf.gf_mul(1, 77) == 77


def test_div_inverse():
    rng = np.random.default_rng(1)
    a = rng.integers(1, 256, 200, dtype=np.uint8)
    b = rng.integers(1, 256, 200, dtype=np.uint8)
    assert np.array_equal(gf.gf_mul(gf.gf_div(a, b), b), a)
    assert np.all(gf.gf_mul(a, gf.gf_inv(a)) == 1)
    with pytest.raises(ZeroDivisionError):
        gf.gf_div(1, 0)


def test_pow():
    assert gf.gf_pow(2, 0) == 1
    assert gf.gf_pow(2, 1) == 2
    assert gf.gf_pow(2, 8) == gf.gf_mul(gf.gf_pow(2, 4), gf.gf_pow(2, 4))
    assert gf.gf_pow(0, 3) == 0


def test_matmul_and_inverse():
    rng = np.random.default_rng(2)
    for n in (2, 3, 5, 8):
        while True:
            M = rng.integers(0, 256, (n, n), dtype=np.uint8)
            try:
                Minv = gf.gf_mat_inv(M)
                break
            except np.linalg.LinAlgError:
                continue
        assert np.array_equal(gf.gf_matmul(M, Minv), np.eye(n, dtype=np.uint8))


def test_bitmatrix_agrees_with_field_mul():
    rng = np.random.default_rng(3)
    for _ in range(20):
        c = int(rng.integers(0, 256))
        x = int(rng.integers(0, 256))
        M = gf.gf_const_to_bitmatrix(c)
        xbits = gf.bytes_to_bits(np.array([x], dtype=np.uint8))
        prod_bits = (M @ xbits) % 2
        prod = gf.bits_to_bytes(prod_bits.astype(np.uint8))[0]
        assert prod == gf.gf_mul(c, x), (c, x)


def test_matrix_bitmatrix_is_the_blockwise_expansion():
    """The gather (PR 34) against the loop it replaced: block (i, j) is
    the 8x8 matrix of entry (i, j)."""
    rng = np.random.default_rng(34)
    M = rng.integers(0, 256, (5, 9), dtype=np.uint8)
    M[0, 0], M[4, 8] = 0, 1
    B = gf.gf_matrix_to_bitmatrix(M)
    assert B.shape == (40, 72) and B.dtype == np.uint8
    for i in range(5):
        for j in range(9):
            assert np.array_equal(
                B[8 * i:8 * i + 8, 8 * j:8 * j + 8],
                gf.gf_const_to_bitmatrix(int(M[i, j]))), (i, j)
    B[0, 0] ^= 1        # a result is the caller's own


def test_matrix_bitmatrix_encode_equivalence():
    rng = np.random.default_rng(4)
    k, m, n = 4, 2, 16
    C = rng.integers(0, 256, (m, k), dtype=np.uint8)
    D = rng.integers(0, 256, (k, n), dtype=np.uint8)
    parity = gf.gf_matmul(C, D)
    B = gf.gf_matrix_to_bitmatrix(C)  # (8m, 8k)
    Dbits = np.stack([gf.bytes_to_bits(D[:, t]) for t in range(n)], axis=1)
    Pbits = (B.astype(np.int32) @ Dbits.astype(np.int32)) % 2
    P2 = np.stack(
        [gf.bits_to_bytes(Pbits[:, t].astype(np.uint8)) for t in range(n)], axis=1
    )
    assert np.array_equal(parity, P2)


def test_bits_bytes_roundtrip():
    rng = np.random.default_rng(5)
    a = rng.integers(0, 256, (3, 17), dtype=np.uint8)
    assert np.array_equal(gf.bits_to_bytes(gf.bytes_to_bits(a)), a)
