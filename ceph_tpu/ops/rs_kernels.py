"""TPU erasure-code kernels: GF(2^8) codes as GF(2) bit-matrix matmuls.

The encode hot loop of the reference is a GF(2^8) matrix multiply over
chunk bytes (jerasure_matrix_encode /ISA-L ec_encode_data, reference:
src/erasure-code/jerasure/ErasureCodeJerasure.cc:105-113,
src/erasure-code/isa/ErasureCodeIsa.cc:119-131).  CPU libraries use
PSHUFB nibble tables; those are gather-shaped and map poorly onto a TPU.
Instead we exploit that multiplication by a constant in GF(2^8) is
GF(2)-linear on the operand's bits: expanding the (m,k) byte generator
into an (8m,8k) 0/1 matrix turns erasure encode into

    parity_bits = (B @ data_bits) mod 2

— one int8/int32 matmul on the MXU plus cheap bit (un)packing on the VPU.
Decode is the same kernel with a per-erasure-signature matrix (inverted
host-side and cached, mirroring ErasureCodeIsaTableCache semantics).

Two execution paths:

- :func:`gf_bitmatmul` — pure XLA (jit); works on CPU/TPU, used by tests
  and as the universal fallback.
- :func:`gf_bitmatmul_pallas` — fused pallas TPU kernel that unpacks,
  multiplies and packs tile-by-tile in VMEM, avoiding the 8x HBM
  inflation of materialized bit tensors.

Both paths are bit-exact w.r.t. the numpy host reference
(ceph_tpu.ops.gf256.gf_matmul); see tests/test_rs_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ops.gf256 import gf_matrix_to_bitmatrix


def unpack_bits(data: jax.Array) -> jax.Array:
    """(..., k, S) uint8 -> (..., 8k, S) uint8 of 0/1; byte i bit b (LSB
    first) lands at row 8i+b, matching gf_matrix_to_bitmatrix layout."""
    *lead, k, s = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(*lead, k * 8, s)


def pack_bits(bits: jax.Array) -> jax.Array:
    """(..., 8m, S) ints in {0,1} -> (..., m, S) uint8 (LSB-first)."""
    *lead, m8, s = bits.shape
    b = bits.reshape(*lead, m8 // 8, 8, s).astype(jnp.uint8)
    weights = jnp.left_shift(jnp.uint8(1), jnp.arange(8, dtype=jnp.uint8))
    # bit positions are disjoint, so sum == bitwise OR; uint8 never wraps
    return jnp.sum(b * weights[:, None], axis=-2, dtype=jnp.uint8)


@jax.jit
def gf_bitmatmul(bitmat: jax.Array, data: jax.Array) -> jax.Array:
    """Apply an (8m, 8k) GF(2) bit-matrix to (..., k, S) uint8 chunk data,
    returning (..., m, S) uint8.  XLA path."""
    bits = unpack_bits(data).astype(jnp.int8)
    acc = jnp.einsum(
        "pq,...qs->...ps",
        bitmat.astype(jnp.int8),
        bits,
        preferred_element_type=jnp.int32,
    )
    return pack_bits(acc & 1)


@jax.jit
def gf_encode_compare(bitmat: jax.Array, data: jax.Array,
                      parity: jax.Array) -> jax.Array:
    """Batched re-encode-and-compare for deep scrub: apply the (8m, 8k)
    encode bit-matrix to (B, k, S) data-shard lanes and compare against
    the stored (B, m, S) parity lanes, returning a (B, m) bool mismatch
    mask — the expected parity never leaves the device.  Zero-padded
    columns are exact (encode(0) == 0 == padded parity), so bucketed
    lanes report the same mask as the unpadded per-object compare."""
    expect = gf_bitmatmul(bitmat, data)
    return jnp.any(expect != parity, axis=-1)


# ---------------------------------------------------------------------------
# Pallas fused kernel
# ---------------------------------------------------------------------------

def _bit_major_perm(n: int) -> "np.ndarray":
    """Permutation mapping bit-major index b*n+j -> byte-major index 8*j+b.

    The pallas kernel builds its bit tensor as 8 stacked copies of the
    data tile masked per bit (row r = b*n + i), so the (8m, 8k)
    byte-major bit-matrix is permuted host-side to match."""
    idx = np.empty(8 * n, dtype=np.int64)
    for b in range(8):
        for j in range(n):
            idx[b * n + j] = 8 * j + b
    return idx


def _encode_tile(bm, d, m):
    """Core of the fused kernels: (k, T) uint8 tile -> (m, T) uint8 parity
    via the bit-major (8m, 8k) GF(2) matrix ``bm``.

    Measured on v5e-1 (see bench.py): the naive formulation (uint8 ->
    int32 cast, 8 shift/and planes, per-plane int8 casts) spends ~85% of
    its time in VPU relayouts.  This formulation avoids every relayout
    Mosaic can't fuse:

    - bit extraction stays in the 8-bit domain (int8 ops run 4-per-lane
      on the VPU; int8/uint8 *shifts* are illegal in Mosaic but & and
      compare are fine): X = concat([d]*8) once, mask per row group,
      compare != 0;
    - one (8m, 8k) @ (8k, T) int8 MXU matmul with int32 accumulation;
    - mod-2 and byte re-pack on the (8m, T) accumulator (small).
    """
    kk = d.shape[0]
    X = jnp.concatenate([d] * 8, axis=0)                  # (8k, T)
    r = jax.lax.broadcasted_iota(jnp.int32, (8 * kk, 1), 0)
    mask = (jnp.int32(1) << (r // kk)).astype(jnp.uint8)  # row r -> bit r//k
    bits = ((X & mask) != 0).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bm,
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1                                                 # (8m, T) bit-major
    out = acc[0:m]
    for b in range(1, 8):
        out = out | (acc[b * m:(b + 1) * m] << b)
    return out.astype(jnp.uint8)


def _bitmatmul_kernel(bm_ref, data_ref, out_ref):
    """One S-tile of the fused encode/decode (see :func:`_encode_tile`)."""
    out_ref[:] = _encode_tile(bm_ref[:], data_ref[:], out_ref.shape[0])


def _grouped_kernel(bm_ref, data_ref, out_ref):
    """Block-diagonal g-group variant of :func:`_bitmatmul_kernel`.

    The (8m, 8k) stationary operand uses only 8m of 128 MXU rows and 8k
    of 128 columns; for RS(8,3) that is 9% utilization and the kernel is
    bound by MXU column streaming.  Packing ``g`` independent column
    groups as ``blockdiag(C, ..., C)`` widens the stationary operand to
    (8mg, 8kg) and cuts streamed columns by g.  For k=8 (g=2) the
    contraction dim is exactly 128 — full MXU width.

    Everything stays strictly 2-D: group j is the contiguous column
    sub-tile [j*T, (j+1)*T) of the (k, g*T) block, so building the bit
    tensor needs only lane-dim slicing at tile multiples plus sublane
    concatenation — no transposes, no narrow-sublane 3-D blocks (both
    of which send Mosaic compile times through the roof).
    """
    d = data_ref[:]                                       # (k, g*T) uint8
    kk = d.shape[0]
    m, gt = out_ref.shape
    g = bm_ref.shape[0] // (8 * m)
    t = gt // g
    X = jnp.concatenate(
        [jnp.concatenate([d[:, j * t:(j + 1) * t]] * 8, axis=0)
         for j in range(g)],
        axis=0,
    )                                                     # (8kg, T), row j*8k + b*k + i
    r = jax.lax.broadcasted_iota(jnp.int32, (8 * kk * g, 1), 0)
    mask = (jnp.int32(1) << ((r % (8 * kk)) // kk)).astype(jnp.uint8)
    bits = ((X & mask) != 0).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bm_ref[:],
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1                                                 # row j*8m + b*m + u
    outs = []
    for j in range(g):
        a = acc[j * 8 * m:(j + 1) * 8 * m]
        o = a[0:m]
        for b in range(1, 8):
            o = o | (a[b * m:(b + 1) * m] << b)
        outs.append(o)                                    # (m, T) bytes
    out_ref[:] = jnp.concatenate(outs, axis=1).astype(jnp.uint8)


def _grouped_perm(n: int, g: int) -> "np.ndarray":
    """Kernel bit order j*8n + (b*n + i) -> blockdiag byte-major index
    j*8n + 8i + b: the per-group bit-major permutation, block-shifted."""
    base = _bit_major_perm(n)
    return np.concatenate([j * 8 * n + base for j in range(g)])


def _pick_groups(k: int, m: int, s: int, tile_s: int) -> int:
    """Largest power-of-two g with full blocks: 8kg <= 128, 8mg <= 128,
    g | s/tile_s.  Power-of-two so g always divides the power-of-two
    tile (callers split tile_s by g)."""
    g = max(1, min(128 // (8 * k), 128 // (8 * m)))
    g = 1 << (g.bit_length() - 1)
    while g > 1 and ((s // tile_s) % g != 0):
        g //= 2
    return g


@functools.partial(jax.jit, static_argnames=("tile_s", "groups", "interpret"))
def gf_bitmatmul_pallas_grouped(
    bitmat: jax.Array,
    data: jax.Array,
    *,
    tile_s: int,
    groups: int,
    interpret: bool = False,
) -> jax.Array:
    """Grouped (block-diagonal) pallas path; bit-exact with the others.

    ``data`` is (k, S) with S a multiple of ``groups * tile_s``; group j
    of grid step i covers columns [i*g*T + j*T, i*g*T + (j+1)*T).
    ``bitmat`` is the plain byte-major (8m, 8k) matrix of the code.
    """
    from jax.experimental import pallas as pl

    k, s = data.shape
    m8, k8 = bitmat.shape
    m, g = m8 // 8, groups
    assert s % (g * tile_s) == 0, (s, g, tile_s)
    # blockdiag(C, ..., C) in bit space: (8mg, 8kg) with group-major rows
    bd = jnp.zeros((m8 * g, k8 * g), dtype=bitmat.dtype)
    for j in range(g):
        bd = bd.at[j * m8:(j + 1) * m8, j * k8:(j + 1) * k8].set(bitmat)
    bm_perm = bd[jnp.asarray(_grouped_perm(m, g))][:, jnp.asarray(_grouped_perm(k, g))]
    return pl.pallas_call(
        _grouped_kernel,
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.uint8),
        grid=(s // (g * tile_s),),
        in_specs=[
            pl.BlockSpec((m8 * g, k8 * g), lambda i: (0, 0)),
            pl.BlockSpec((k, g * tile_s), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, g * tile_s), lambda i: (0, i)),
        interpret=interpret,
    )(bm_perm.astype(jnp.int8), data)


def _pick_tile(s: int, max_tile: int = 32768) -> int | None:
    """Largest power-of-two tile <= max_tile dividing s (None if s has no
    even tiling >= 512 -- callers then fall back to the XLA path).

    The cap is what Mosaic accepts on a v5e, not a speed tuning: the
    kernels' scoped-VMEM need grows with the block width and the chip's
    scoped limit is 16 MiB.  ``tools/tile_probe.py`` on the chip, what
    ``_apply`` selects for k = 2, 3, 4, 6, 8, 10 (encode and the 1-row
    decode, S = 512 KiB and 1 MiB): at 32768 lanes all compile and are
    byte-exact; at 65536 every k <= 4 code is refused ("ran out of
    memory in memory space vmem"), so there is no power of two of
    headroom for them; k >= 6 compiles up to the old cap of 262144.
    Launch time is the same at every width that compiles (0.59-0.84 ms,
    the launch round trip itself), while the first launch's Mosaic
    compile falls from 3-10 s at 262144 to 0.4-0.7 s here (k >= 6)."""
    t = max_tile
    while t >= 512:
        if s % t == 0:
            return t
        t //= 2
    return None


@functools.partial(jax.jit, static_argnames=("tile_s", "interpret"))
def gf_bitmatmul_pallas(
    bitmat: jax.Array, data: jax.Array, *, tile_s: int, interpret: bool = False
) -> jax.Array:
    """Fused pallas TPU path of :func:`gf_bitmatmul` for 2-D (k, S) data.

    S must be a multiple of ``tile_s`` (the EC layer pads stripes,
    mirroring ErasureCode::encode_prepare alignment, reference
    src/erasure-code/ErasureCode.cc:170-205).  ``bitmat`` is the
    byte-major (8m, 8k) matrix; it is permuted into the kernel's
    bit-major layout here (tiny; traced once under jit).
    """
    from jax.experimental import pallas as pl

    k, s = data.shape
    m8, k8 = bitmat.shape
    m = m8 // 8
    assert s % tile_s == 0, (s, tile_s)
    bm_perm = bitmat[jnp.asarray(_bit_major_perm(m))][:, jnp.asarray(_bit_major_perm(k))]
    return pl.pallas_call(
        _bitmatmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.uint8),
        grid=(s // tile_s,),
        in_specs=[
            pl.BlockSpec((m8, k8), lambda i: (0, 0)),
            pl.BlockSpec((k, tile_s), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, tile_s), lambda i: (0, i)),
        interpret=interpret,
    )(bm_perm.astype(jnp.int8), data)


@functools.partial(jax.jit, static_argnames=("tile_s", "interpret"))
def gf_bitmatmul_pallas_acc(
    bitmat: jax.Array,
    data: jax.Array,
    carry: jax.Array,
    seed: jax.Array,
    *,
    tile_s: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused ``carry ^ encode(data ^ seed)`` with the carry buffer aliased
    to the output (under an enclosing jit loop the carry is updated in
    place — no extra HBM allocation per iteration).

    This is the loop body of the sustained-throughput benchmark harness:
    the reference harness's timed encode loop
    (ceph_erasure_code_benchmark.cc:186-191) is expressed as ONE launch
    of ``lax.fori_loop`` over this kernel, so launch cost stays out of
    the kernel's number.  The per-iteration seed is
    XORed into every loaded data byte so XLA cannot hoist the encode out
    of the loop as loop-invariant; the carry fold makes every iteration's
    parity live.  Both are cheap VPU ops fused into the same pass over
    the tile, so per-iteration HBM traffic (read k·S, read+write m·S)
    matches a plain encode-and-write within 27%.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, s = data.shape
    m8, k8 = bitmat.shape
    m = m8 // 8
    assert s % tile_s == 0, (s, tile_s)
    bm_perm = bitmat[jnp.asarray(_bit_major_perm(m))][:, jnp.asarray(_bit_major_perm(k))]

    def kern(seed_ref, bm_ref, d_ref, c_ref, o_ref):
        sd = seed_ref[0].astype(jnp.uint8)
        o_ref[:] = _encode_tile(bm_ref[:], d_ref[:] ^ sd, m) ^ c_ref[:]

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s // tile_s,),
            in_specs=[
                pl.BlockSpec((m8, k8), lambda i, *_: (0, 0)),
                pl.BlockSpec((k, tile_s), lambda i, *_: (0, i)),
                pl.BlockSpec((m, tile_s), lambda i, *_: (0, i)),
            ],
            out_specs=pl.BlockSpec((m, tile_s), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, s), jnp.uint8),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(seed, bm_perm.astype(jnp.int8), data, carry)


# ---------------------------------------------------------------------------
# Encoder/decoder objects (host-side matrix prep, cached)
# ---------------------------------------------------------------------------

#: largest bit-matrix the fused kernels are chosen for: between the
#: largest a scalar code gives (a w=8 packet code's (192, 512), 98,304
#: bits) and the smallest a vector code gives (CLAY(8,4,11)'s repair,
#: (512, 1408); its encode is (2048, 4096)).  The fused kernels take
#: those too, byte-exact and no faster (``tools/big_bitmatrix_probe.py``
#: on the v5e, one 4 MiB object: encode 1.09 ms against XLA's 1.24,
#: repair 0.71 against 0.64, the launch round trip either way), but
#: Mosaic's first compile grows with the stationary operand: 112 s for
#: the encode matrix at the tile ``_pick_tile`` gives (20 s at 2048
#: lanes, 5.5 s at 512), once a width of the bucket ladder, where XLA's
#: program takes 3 s: an OSD would install its map for minutes
_PALLAS_MAX_BITS = 1 << 17


class BitmatrixCodec:
    """Precomputed bit-matrices for one (k, m, generator) code.

    Encode uses the fixed generator; decode matrices are derived and
    cached per erasure signature — the TPU analogue of the ISA plugin's
    LRU decode-table cache (reference: ErasureCodeIsaTableCache.cc).
    """

    def __init__(self, coding_matrix: np.ndarray):
        # pallas kernels recompile per (shape, tile) on a cold process;
        # persist executables so daemons/benches warm-start
        from ceph_tpu.ops.compile_cache import ensure_persistent_cache

        ensure_persistent_cache()
        self.C = np.asarray(coding_matrix, dtype=np.uint8)
        self.m, self.k = self.C.shape
        self.encode_bits = jnp.asarray(gf_matrix_to_bitmatrix(self.C))
        self._decode_cache: dict[tuple[int, ...], tuple[list[int], jax.Array]] = {}

    def decode_bits(self, erasures: tuple[int, ...]) -> tuple[list[int], jax.Array]:
        """(survivor chunk ids, bit-matrix mapping survivors->erased)."""
        key = tuple(sorted(erasures))
        hit = self._decode_cache.get(key)
        if hit is None:
            from ceph_tpu.models.matrices import decode_matrix_for

            D = decode_matrix_for(self.C, list(key))
            survivors = [
                i for i in range(self.k + self.m) if i not in set(key)
            ][: self.k]
            hit = (survivors, jnp.asarray(gf_matrix_to_bitmatrix(D)))
            self._decode_cache[key] = hit
        return hit

    def encode(self, data: jax.Array, *, pallas: bool | None = None) -> jax.Array:
        """(..., k, S) uint8 -> (..., m, S) parity.

        ``pallas=None`` auto-selects: the fused TPU kernel when running
        on TPU with a tileable S, else the XLA path."""
        return self._apply(self.encode_bits, data, pallas)

    def decode_batch(
        self, batch: jax.Array, erasures: tuple[int, ...]
    ) -> jax.Array:
        """Batched recovery decode: (B, k, S) survivor payload lanes
        (survivors in codec order for this signature) -> (B, e, S)
        reconstructed chunks, one XLA launch for the whole batch.  The
        per-signature decode matrix comes from the same LRU cache the
        per-object path uses (:meth:`decode_bits`), so a signature's
        matrix is derived once no matter how many batches hit it —
        the aggregator's fixed-shape dispatch rides this."""
        _survivors, dbits = self.decode_bits(erasures)
        return gf_bitmatmul(dbits, batch)

    def decode(
        self, chunks: jax.Array, erasures: tuple[int, ...], *, pallas: bool | None = None
    ) -> jax.Array:
        """Reconstruct erased chunks from the full (..., k+m, S) array in
        which erased rows are ignored.  Returns (..., len(erasures), S)
        with rows in the order *requested*, not sorted order."""
        survivors, dbits = self.decode_bits(erasures)
        sub = chunks[..., jnp.asarray(survivors), :]
        rec = self._apply(dbits, sub, pallas)
        key = tuple(sorted(set(erasures)))
        if key != tuple(erasures):
            order = [key.index(e) for e in erasures]
            rec = rec[..., jnp.asarray(order), :]
        return rec

    @staticmethod
    def _apply(bits_matrix: jax.Array, data: jax.Array, pallas: bool | None) -> jax.Array:
        if pallas is None:
            # a vector code's hundreds of sub-chunk rows (CLAY(8,4,11)
            # encode: (2048, 4096) bits) take XLA's kernel: as fast, and
            # compiled in seconds (_PALLAS_MAX_BITS)
            pallas = (data.ndim == 2 and jax.default_backend() == "tpu"
                      and bits_matrix.size <= _PALLAS_MAX_BITS)
        if pallas and data.ndim == 2:
            tile = _pick_tile(data.shape[-1])
            if tile is not None:
                m8, k8 = bits_matrix.shape
                g = _pick_groups(k8 // 8, m8 // 8, data.shape[-1], tile)
                # keep the block footprint (g * sub-tile) at the tuned
                # width: the grouped kernel's VMEM residency per step
                # matches the ungrouped one
                if g > 1 and tile // g >= 512:
                    return gf_bitmatmul_pallas_grouped(
                        bits_matrix, data, tile_s=tile // g, groups=g)
                return gf_bitmatmul_pallas(bits_matrix, data, tile_s=tile)
        return gf_bitmatmul(bits_matrix, data)
