"""CRUSH's Robert Jenkins 32-bit mix hash, vectorized.

Behavioral twin of the reference's rjenkins1 hash family
(src/crush/hash.c:12-90): crush_hash32_1..5 built from the classic
Jenkins 96-bit mix with seed 1315423911 and the fixed x=231232,
y=1232 padding words.  Placement is a pure function of these hashes, so
they must match the reference bit-for-bit; tests/test_crush_golden.py
checks them against vectors generated from the reference's own C.

Two implementations with identical semantics:

- numpy (uint32 wraparound arithmetic) — host/oracle path;
- jax (int32 lanes, wraparound is native) — used inside the batched
  placement engine (ceph_tpu/crush/jaxmapper.py), vmappable over x.
"""

from __future__ import annotations

import numpy as np

HASH_SEED = np.uint32(1315423911)
_X = 231232
_Y = 1232
_M32 = 0xFFFFFFFF
_SEED_INT = 1315423911


def _mix_int(a: int, b: int, c: int) -> tuple[int, int, int]:
    """One Jenkins mix round on plain Python ints (scalar fast path:
    the numpy scalar version pays ~µs of ufunc dispatch per op — 135
    per hash — which made per-PG scalar CRUSH mapping stall OSD event
    loops for seconds; BASELINE.md config 5).  Values are
    kept masked to 32 bits so >> is a logical shift."""
    a = (a - b - c) & _M32; a ^= c >> 13
    b = (b - c - a) & _M32; b ^= (a << 8) & _M32
    c = (c - a - b) & _M32; c ^= b >> 13
    a = (a - b - c) & _M32; a ^= c >> 12
    b = (b - c - a) & _M32; b ^= (a << 16) & _M32
    c = (c - a - b) & _M32; c ^= b >> 5
    a = (a - b - c) & _M32; a ^= c >> 3
    b = (b - c - a) & _M32; b ^= (a << 10) & _M32
    c = (c - a - b) & _M32; c ^= b >> 15
    return a, b, c


def _mix_np(a, b, c):
    """One Jenkins mix round on uint32 numpy arrays (in-place semantics)."""
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(13))
    b = b - c; b = b - a; b = b ^ (a << np.uint32(8))
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(13))
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(12))
    b = b - c; b = b - a; b = b ^ (a << np.uint32(16))
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(5))
    a = a - b; a = a - c; a = a ^ (c >> np.uint32(3))
    b = b - c; b = b - a; b = b ^ (a << np.uint32(10))
    c = c - a; c = c - b; c = c ^ (b >> np.uint32(15))
    return a, b, c


import functools


def _wrapping(fn):
    """uint32 wraparound is the point; silence numpy overflow warnings
    inside the hash only.  The scalar (all-plain-int) fast path skips
    the errstate context entirely — entering it costs more than the
    whole int hash."""
    @functools.wraps(fn)
    def inner(*a):
        for v in a:
            if type(v) is not int:
                with np.errstate(over="ignore"):
                    return fn(*a)
        return fn(*a)
    return inner


def _u32(x):
    return np.asarray(x).astype(np.uint32)


@_wrapping
def crush_hash32(a):
    if type(a) is int:
        a &= _M32
        h = (_SEED_INT ^ a) & _M32
        b, x, y = a, _X, _Y
        b, x, h = _mix_int(b, x, h)
        y, a, h = _mix_int(y, a, h)
        return h
    a = _u32(a)
    h = HASH_SEED ^ a
    b = a
    x = np.uint32(_X)
    y = np.uint32(_Y)
    b, x, h = _mix_np(b, x, h)
    y, a, h = _mix_np(y, a, h)
    return h


@_wrapping
def crush_hash32_2(a, b):
    if type(a) is int and type(b) is int:
        a &= _M32; b &= _M32
        h = (_SEED_INT ^ a ^ b) & _M32
        x, y = _X, _Y
        a, b, h = _mix_int(a, b, h)
        x, a, h = _mix_int(x, a, h)
        b, y, h = _mix_int(b, y, h)
        return h
    a, b = _u32(a), _u32(b)
    h = HASH_SEED ^ a ^ b
    x = np.uint32(_X)
    y = np.uint32(_Y)
    a, b, h = _mix_np(a, b, h)
    x, a, h = _mix_np(x, a, h)
    b, y, h = _mix_np(b, y, h)
    return h


@_wrapping
def crush_hash32_3(a, b, c):
    if type(a) is int and type(b) is int and type(c) is int:
        a &= _M32; b &= _M32; c &= _M32
        h = (_SEED_INT ^ a ^ b ^ c) & _M32
        x, y = _X, _Y
        a, b, h = _mix_int(a, b, h)
        c, x, h = _mix_int(c, x, h)
        y, a, h = _mix_int(y, a, h)
        b, x, h = _mix_int(b, x, h)
        y, c, h = _mix_int(y, c, h)
        return h
    a, b, c = _u32(a), _u32(b), _u32(c)
    h = HASH_SEED ^ a ^ b ^ c
    x = np.uint32(_X)
    y = np.uint32(_Y)
    a, b, h = _mix_np(a, b, h)
    c, x, h = _mix_np(c, x, h)
    y, a, h = _mix_np(y, a, h)
    b, x, h = _mix_np(b, x, h)
    y, c, h = _mix_np(y, c, h)
    return h


@_wrapping
def crush_hash32_4(a, b, c, d):
    if (type(a) is int and type(b) is int and type(c) is int
            and type(d) is int):
        a &= _M32; b &= _M32; c &= _M32; d &= _M32
        h = (_SEED_INT ^ a ^ b ^ c ^ d) & _M32
        x, y = _X, _Y
        a, b, h = _mix_int(a, b, h)
        c, d, h = _mix_int(c, d, h)
        a, x, h = _mix_int(a, x, h)
        y, b, h = _mix_int(y, b, h)
        c, x, h = _mix_int(c, x, h)
        y, d, h = _mix_int(y, d, h)
        return h
    a, b, c, d = _u32(a), _u32(b), _u32(c), _u32(d)
    h = HASH_SEED ^ a ^ b ^ c ^ d
    x = np.uint32(_X)
    y = np.uint32(_Y)
    a, b, h = _mix_np(a, b, h)
    c, d, h = _mix_np(c, d, h)
    a, x, h = _mix_np(a, x, h)
    y, b, h = _mix_np(y, b, h)
    c, x, h = _mix_np(c, x, h)
    y, d, h = _mix_np(y, d, h)
    return h


@_wrapping
def crush_hash32_5(a, b, c, d, e):
    if (type(a) is int and type(b) is int and type(c) is int
            and type(d) is int and type(e) is int):
        a &= _M32; b &= _M32; c &= _M32; d &= _M32; e &= _M32
        h = (_SEED_INT ^ a ^ b ^ c ^ d ^ e) & _M32
        x, y = _X, _Y
        a, b, h = _mix_int(a, b, h)
        c, d, h = _mix_int(c, d, h)
        e, x, h = _mix_int(e, x, h)
        y, a, h = _mix_int(y, a, h)
        b, x, h = _mix_int(b, x, h)
        y, c, h = _mix_int(y, c, h)
        d, x, h = _mix_int(d, x, h)
        y, e, h = _mix_int(y, e, h)
        return h
    a, b, c, d, e = _u32(a), _u32(b), _u32(c), _u32(d), _u32(e)
    h = HASH_SEED ^ a ^ b ^ c ^ d ^ e
    x = np.uint32(_X)
    y = np.uint32(_Y)
    a, b, h = _mix_np(a, b, h)
    c, d, h = _mix_np(c, d, h)
    e, x, h = _mix_np(e, x, h)
    y, a, h = _mix_np(y, a, h)
    b, x, h = _mix_np(b, x, h)
    y, c, h = _mix_np(y, c, h)
    d, x, h = _mix_np(d, x, h)
    y, e, h = _mix_np(y, e, h)
    return h


# --- JAX twins -------------------------------------------------------------
#
# int32 arithmetic wraps identically to uint32 for +,-,^,<<; >> must be
# a *logical* shift, so shifts go through a uint32 view.

def _jax_mod():
    import jax.numpy as jnp
    return jnp


def _mix_jax(a, b, c):
    jnp = _jax_mod()

    def rs(v, n):  # logical right shift on int32 lanes
        return jnp.bitwise_and(v >> n, (1 << (32 - n)) - 1)

    a = a - b; a = a - c; a = a ^ rs(c, 13)
    b = b - c; b = b - a; b = b ^ (a << 8)
    c = c - a; c = c - b; c = c ^ rs(b, 13)
    a = a - b; a = a - c; a = a ^ rs(c, 12)
    b = b - c; b = b - a; b = b ^ (a << 16)
    c = c - a; c = c - b; c = c ^ rs(b, 5)
    a = a - b; a = a - c; a = a ^ rs(c, 3)
    b = b - c; b = b - a; b = b ^ (a << 10)
    c = c - a; c = c - b; c = c ^ rs(b, 15)
    return a, b, c


def crush_hash32_3_jax(a, b, c):
    """int32-lane jax version of crush_hash32_3 (vectorizes/vmaps)."""
    jnp = _jax_mod()
    a = jnp.asarray(a, dtype=jnp.int32)
    b = jnp.asarray(b, dtype=jnp.int32)
    c = jnp.asarray(c, dtype=jnp.int32)
    seed = jnp.int32(np.int32(np.uint32(HASH_SEED)))
    h = seed ^ a ^ b ^ c
    x = jnp.int32(_X)
    y = jnp.int32(_Y)
    a, b, h = _mix_jax(a, b, h)
    c, x, h = _mix_jax(c, x, h)
    y, a, h = _mix_jax(y, a, h)
    b, x, h = _mix_jax(b, x, h)
    y, c, h = _mix_jax(y, c, h)
    return h


def crush_hash32_2_jax(a, b):
    jnp = _jax_mod()
    a = jnp.asarray(a, dtype=jnp.int32)
    b = jnp.asarray(b, dtype=jnp.int32)
    seed = jnp.int32(np.int32(np.uint32(HASH_SEED)))
    h = seed ^ a ^ b
    x = jnp.int32(_X)
    y = jnp.int32(_Y)
    a, b, h = _mix_jax(a, b, h)
    x, a, h = _mix_jax(x, a, h)
    b, y, h = _mix_jax(b, y, h)
    return h


# --- batched crc32c as a GF(2) bit-matrix matmul ---------------------------
#
# crc32c's table update is GF(2)-linear in (state, data):
# T[a ^ b] = T[a] ^ T[b], so the crc of a W-byte message with seed s is
#
#     crc = S_W @ bits(s)  ^  M_W @ bits(message)     (mod 2)
#
# with S_W the 32x32 "advance through W zero bytes" operator and M_W a
# 32x8W matrix.  That turns deep-scrub's per-shard host crc loop into
# the repo's standard bit-matmul launch shape: a (B, W) batch of
# payload lanes is one (32, 8W) x (8W, B) int8 MXU/XLA matmul — the
# scrub analogue of rs_kernels.gf_bitmatmul.  Matrices build host-side
# by doubling (M_2W = [S_W M_W | M_W], S_2W = S_W^2), so the 64 KiB
# bucket costs 17 tiny numpy matmuls, cached per width.
#
# Padding discipline (parallel/scrub_batcher.py): lanes are right-
# padded with zeros into their pow2 bucket, and crc(d || 0^p, s) ==
# advance_zeros(p, crc(d, s)) — an injective linear map — so equality
# against a stored crc is checked via native crc32c_zeros(p, stored),
# and the true crc is recovered exactly with :func:`crc32c_unadvance`.

_CRC_SEED_DEFAULT = 0xFFFFFFFF


def _crc_bits(v: int, n: int = 32) -> np.ndarray:
    return np.array([(v >> i) & 1 for i in range(n)], dtype=np.uint8)


def _gf2_mm(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return ((a.astype(np.uint32) @ b.astype(np.uint32)) & 1).astype(np.uint8)


@functools.lru_cache(maxsize=1)
def _crc_base() -> tuple[np.ndarray, np.ndarray]:
    """(M_1 (32,8), S_1 (32,32)): single-byte crc data/state operators."""
    from ceph_tpu.native import crc32c, crc32c_zeros

    m1 = np.zeros((32, 8), dtype=np.uint8)
    for b in range(8):
        m1[:, b] = _crc_bits(crc32c(bytes([1 << b]), 0))
    s1 = np.zeros((32, 32), dtype=np.uint8)
    for i in range(32):
        s1[:, i] = _crc_bits(crc32c_zeros(1, 1 << i))
    return m1, s1


@functools.lru_cache(maxsize=32)
def _crc_ops(width: int) -> tuple[np.ndarray, np.ndarray]:
    """(M_W (32, 8W), S_W (32, 32)) for a power-of-two ``width``."""
    assert width >= 1 and (width & (width - 1)) == 0, width
    if width == 1:
        return _crc_base()
    m_half, s_half = _crc_ops(width // 2)
    return (
        np.concatenate([_gf2_mm(s_half, m_half), m_half], axis=1),
        _gf2_mm(s_half, s_half),
    )


def crc32c_matrix(width: int) -> np.ndarray:
    """The (32, 8*width) GF(2) matrix M_W: crc contribution of a
    width-byte message at seed 0, bit j of byte i at column 8i+j."""
    return _crc_ops(width)[0]


@functools.lru_cache(maxsize=64)
def _crc_unadvance_op(n: int) -> np.ndarray:
    """32x32 inverse of the advance-by-n-zero-bytes operator S_n."""
    if n == 0:
        return np.eye(32, dtype=np.uint8)
    # S_1^{-1} by GF(2) Gaussian elimination (S is invertible: the crc
    # register update is a bijection), then binary decomposition
    if n == 1:
        s1 = _crc_base()[1]
        aug = np.concatenate([s1.copy(), np.eye(32, dtype=np.uint8)], axis=1)
        for col in range(32):
            piv = next(r for r in range(col, 32) if aug[r, col])
            aug[[col, piv]] = aug[[piv, col]]
            for r in range(32):
                if r != col and aug[r, col]:
                    aug[r] ^= aug[col]
        return np.ascontiguousarray(aug[:, 32:])
    if n & (n - 1) == 0:
        h = _crc_unadvance_op(n // 2)
        return _gf2_mm(h, h)
    lsb = n & -n
    return _gf2_mm(_crc_unadvance_op(n - lsb), _crc_unadvance_op(lsb))


def crc32c_unadvance(crc: int, n: int) -> int:
    """Invert ``crc32c_zeros(n, x) == crc``: the crc BEFORE advancing
    through ``n`` zero bytes (exact; the advance is injective)."""
    if n == 0:
        return crc
    out = _gf2_mm(_crc_unadvance_op(n), _crc_bits(crc).reshape(32, 1))
    return int(sum(int(b) << i for i, b in enumerate(out.reshape(32))))


def batched_crc32c_device(mat, data):
    """Device kernel: (B, W) uint8 payload lanes -> (B,) uint32 crc
    contributions M_W @ bits(lane) (seed 0; callers fold seeds/padding
    host-side via crc32c_zeros / crc32c_unadvance).  Jitted per (B, W)
    shape; bit-exact with native crc32c on every backend."""
    import jax

    return _crc_kernel_jit()(jax.numpy.asarray(mat), data)


@functools.lru_cache(maxsize=1)
def _crc_kernel_jit():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kern(mat, data):
        b, w = data.shape
        shifts = jnp.arange(8, dtype=jnp.uint8)
        # byte i bit j (LSB first) -> column 8i+j, matching crc32c_matrix
        bits = ((data[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1))
        bits = bits.reshape(b, w * 8).astype(jnp.int8)
        acc = jnp.einsum(
            "bq,pq->bp", bits, mat.astype(jnp.int8),
            preferred_element_type=jnp.int32,
        ) & 1
        weights = jnp.left_shift(
            jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
        return jnp.sum(acc.astype(jnp.uint32) * weights[None, :], axis=1,
                       dtype=jnp.uint32)

    return kern


def ceph_str_hash_rjenkins(data: bytes | str) -> int:
    """Object-name hash (reference src/common/ceph_hash.cc
    ceph_str_hash_rjenkins): Jenkins lookup2 over 12-byte blocks with
    the length folded into c — the hash that places objects into PGs
    (object_locator_to_pg, src/osd/osd_types.cc).  Pure-int (scalar
    hot path: runs once per client op)."""
    if isinstance(data, str):
        data = data.encode("utf-8")
    k = data
    length = len(k)
    a = 0x9E3779B9
    b = 0x9E3779B9
    c = 0
    off = 0
    ln = length
    while ln >= 12:
        a = (a + int.from_bytes(k[off : off + 4], "little")) & _M32
        b = (b + int.from_bytes(k[off + 4 : off + 8], "little")) & _M32
        c = (c + int.from_bytes(k[off + 8 : off + 12], "little")) & _M32
        a, b, c = _mix_int(a, b, c)
        off += 12
        ln -= 12
    c = (c + length) & _M32
    tail = k[off:]
    t = tail + b"\0" * (11 - len(tail))
    if ln >= 9:
        # the first byte of c is reserved for the length
        c = (c + (
            (t[8] << 8) | (t[9] << 16 if ln >= 10 else 0) | (t[10] << 24 if ln >= 11 else 0)
        )) & _M32
    if ln >= 5:
        b = (b + (
            t[4] | (t[5] << 8 if ln >= 6 else 0) | (t[6] << 16 if ln >= 7 else 0)
            | (t[7] << 24 if ln >= 8 else 0)
        )) & _M32
    if ln >= 1:
        a = (a + (
            t[0] | (t[1] << 8 if ln >= 2 else 0) | (t[2] << 16 if ln >= 3 else 0)
            | (t[3] << 24 if ln >= 4 else 0)
        )) & _M32
    a, b, c = _mix_int(a, b, c)
    return c
