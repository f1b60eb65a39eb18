"""Multi-chip erasure-encode farms over a jax.sharding.Mesh.

Two sharding strategies, composable on a 2-D mesh ('pg', 'shard'):

- **Data parallel over stripes** (:func:`batch_encode_dp`): a batch of
  independent stripes (B, k, S) is sharded on B; every device encodes
  its stripes locally, no communication.  This is the TPU analogue of
  Ceph farming independent PG writes across OSD worker shards
  (reference: src/osd/OSD.cc op_shardedwq, src/osd/OSDMapMapping.h:18
  ParallelPGMapper).

- **Chunk-sharded ("tensor parallel") encode**
  (:func:`sharded_encode_tp`): the k data chunks of one huge object are
  sharded across devices; each device computes the partial GF(2)
  bit-matmul for its chunk slice and the partial int32 accumulators are
  combined with ``psum`` over ICI before the mod-2 — GF(2^8) addition is
  XOR, and XOR == integer-sum mod 2, so the collective is a plain psum.
  This is the seam where Ceph's ECSubWrite shard fan-out over TCP
  (src/osd/ECBackend.cc:943, ECCommon.cc:749) becomes an XLA collective
  when shard owners live on one slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ops.rs_kernels import pack_bits, unpack_bits


# -- input shardings --------------------------------------------------------
#
# Callers must device_put operands with THESE shardings (ctlint's
# transfer discipline: explicit, correctly-placed uploads — an
# unsharded put costs a reshard hop on every dispatch, and compiled
# executables are keyed by input sharding, so prewarm and dispatch
# must agree).  Single-homed here, beside the in_specs they mirror.

def dp_batch_sharding(mesh: Mesh, axis="pg") -> NamedSharding:
    """Sharding for :func:`batch_encode_dp`'s (B, k, S) stripe batch."""
    return NamedSharding(mesh, P(axis, None, None))


def tp_data_sharding(mesh: Mesh, axis: str = "shard") -> NamedSharding:
    """Sharding for :func:`sharded_encode_tp`'s (k, S) chunk rows."""
    return NamedSharding(mesh, P(axis, None))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Full replication (the bit-matrix operand of the dp path)."""
    return NamedSharding(mesh, P())


def batch_encode_dp(mesh: Mesh, bitmat: jax.Array, batch: jax.Array, axis: str = "pg"):
    """Encode a (B, k, S) stripe batch sharded over ``axis``; returns
    (B, m, S) parity with the same batch sharding."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis, None, None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )
    def _encode(bm, local):
        bits = unpack_bits(local).astype(jnp.int8)
        acc = jnp.einsum(
            "pq,bqs->bps", bm.astype(jnp.int8), bits,
            preferred_element_type=jnp.int32,
        )
        return pack_bits(acc & 1)

    return _encode(bitmat, batch)


def sharded_encode_tp(mesh: Mesh, bitmat: jax.Array, data: jax.Array, axis: str = "shard"):
    """Encode (k, S) data whose chunk dimension k is sharded over
    ``axis``; partial int32 accumulators are psum-combined then reduced
    mod 2.  Returns replicated (m, S) parity."""
    n = mesh.shape[axis]
    k = data.shape[0]
    assert k % n == 0, "k (data chunk rows) must divide the shard axis size"

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(),
        check_vma=False,
    )
    def _encode(bm_cols, local_chunks):
        # bm_cols: (8m, 8k/n) — this device's columns of the bit-matrix.
        # local_chunks: (k/n, S).
        bits = unpack_bits(local_chunks).astype(jnp.int8)
        partial = jnp.einsum(
            "pq,qs->ps", bm_cols.astype(jnp.int8), bits,
            preferred_element_type=jnp.int32,
        )
        total = jax.lax.psum(partial, axis)   # XOR == sum mod 2
        return pack_bits(total & 1)

    return _encode(bitmat, data)
