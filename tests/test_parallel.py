"""Multi-device encode farms on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ceph_tpu.models import matrices as mx
from ceph_tpu.ops import gf256 as gf
from ceph_tpu.ops.rs_kernels import BitmatrixCodec
from ceph_tpu.parallel import encode_farm as ef


@pytest.fixture(scope="module")
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.array(devs[:8]).reshape(8), ("pg",))


@pytest.fixture(scope="module")
def mesh2x4():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.array(devs[:8]).reshape(2, 4), ("pg", "shard"))


@pytest.mark.parametrize("meshname", ["mesh8", "mesh2x4"])
def test_mesh_encode_cols_matches_host(meshname, request):
    """The column-split launch cuts S over every device of the mesh,
    whatever its axes: 8 x 1-D and 2 x 4 give the same bytes."""
    mesh = request.getfixturevalue(meshname)
    rng = np.random.default_rng(0)
    k, m = 8, 3
    codec = BitmatrixCodec(mx.isa_cauchy_matrix(k, m))
    S = ef.cols_width(mesh, 3000)           # 8 blocks of 512
    assert S == 4096
    data = np.zeros((k, S), np.uint8)
    data[:, :3000] = rng.integers(0, 256, (k, 3000), dtype=np.uint8)
    out = ef.mesh_encode_cols(
        mesh, jax.device_put(codec.encode_bits, ef.replicated_sharding(mesh)),
        jax.device_put(data, ef.cols_sharding(mesh)))
    assert len(out.sharding.device_set) == 8
    assert np.array_equal(np.asarray(out), gf.gf_matmul(codec.C, data))


def test_mesh_encode_then_decode_roundtrip(mesh2x4):
    rng = np.random.default_rng(2)
    k, m = 8, 3
    codec = BitmatrixCodec(mx.jerasure_rs_vandermonde_matrix(k, m))
    data = rng.integers(0, 256, (k, 512), dtype=np.uint8)
    parity = np.asarray(ef.mesh_encode_cols(
        mesh2x4, codec.encode_bits,
        jax.device_put(data, ef.cols_sharding(mesh2x4))))
    chunks = np.concatenate([data, parity], axis=0)
    rec = np.asarray(codec.decode(jnp.asarray(chunks), (1, 6, 9)))
    assert np.array_equal(rec, chunks[[1, 6, 9]])
