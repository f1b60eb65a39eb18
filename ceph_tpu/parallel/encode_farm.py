"""The multi-chip erasure-encode farm: one compiled launch, split by columns.

A GF(2^8) matrix product is independent column by column, so a product
over several chips needs neither a collective nor a batch dimension:
the ``(k, S)`` operand is cut into one equal column block per device of
the mesh, every device applies the same replicated bit-matrix to its
block with the kernel a single chip would run
(``BitmatrixCodec._apply``: the fused Pallas kernel on a TPU, the XLA
path elsewhere), and the ``(out, S)`` result comes back cut the same
way.  This is the TPU analogue of Ceph farming independent PG writes
across OSD worker shards (reference: src/osd/OSD.cc op_shardedwq,
src/osd/OSDMapMapping.h:18 ParallelPGMapper), with the window's
requests laid side by side along ``S`` instead of queued per shard.

The program is built and jitted once per mesh (:func:`_program`); a
launch is one compiled executable per (matrix shape, width), which
``EncodeService.prewarm`` compiles ahead of the I/O path.
"""

from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ops.rs_kernels import BitmatrixCodec
from ceph_tpu.parallel.batcher import pow2_bucket


# -- input shardings --------------------------------------------------------
#
# Callers must device_put operands with THESE shardings (ctlint's
# transfer discipline: explicit, correctly-placed uploads — an
# unsharded put costs a reshard hop on every dispatch, and compiled
# executables are keyed by input sharding, so prewarm and dispatch
# must agree).  Single-homed here, beside the in_specs they mirror.

def cols_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of :func:`mesh_encode_cols`'s (k, S) operand and its
    (out, S) result: S cut over every device of the mesh, whatever the
    mesh's axes are."""
    return NamedSharding(mesh, P(None, mesh.axis_names))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Full replication (the bit-matrix operand)."""
    return NamedSharding(mesh, P())


def cols_width(mesh: Mesh, total: int) -> int:
    """The launch width that holds ``total`` real columns: every device
    gets the same power-of-two block, so the program shape set stays
    bounded for any device count and any ragged total."""
    return mesh.size * pow2_bucket(-(-total // mesh.size), 1)


@functools.lru_cache(maxsize=None)
def _program(mesh: Mesh):
    """The jitted column-split program of one mesh (jit then keeps one
    executable per operand shape)."""
    cols = P(None, mesh.axis_names)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(P(), cols),
                       out_specs=cols, check_vma=False)
    def encode_mesh_cols(bitmat, block):
        return BitmatrixCodec._apply(bitmat, block, None)

    return encode_mesh_cols


def mesh_encode_cols(mesh: Mesh, bitmat: jax.Array, data: jax.Array):
    """Apply the replicated (8 out, 8k) bit-matrix to (k, S) data whose
    columns are cut over the mesh (:func:`cols_sharding`; S a multiple
    of :func:`cols_width`'s block); returns (out, S) cut the same way.
    One launch of one compiled program, no communication."""
    return _program(mesh)(bitmat, data)
