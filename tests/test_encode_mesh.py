"""The column-split mesh launch of the encode service (PR 27), on 4 of
the 8 virtual CPU devices: one compiled program per launch, for a lone
request and a window alike, byte-equal to ``ops/gf256.gf_matmul`` and
to the benchmark's plain reference (``benchmarks/harness/reference.py``)
at the EC(8,3) pool's shapes (k=8 m=3, 4 KiB stripe unit)."""

import asyncio
import os
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from ceph_tpu.common import transfer_guard
from ceph_tpu.ec import registry
from ceph_tpu.ops.gf256 import gf_matmul, gf_matrix_to_bitmatrix
from ceph_tpu.parallel import encode_farm as ef
from ceph_tpu.parallel import encode_service as es

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from harness import reference  # noqa: E402

K, M, UNIT = 8, 3, 4096


@pytest.fixture(scope="module")
def lowerings():
    """Counts the programs JAX lowers from here on, as
    ``benchmarks/run.py`` does to hold a cell's window to zero."""
    seen = {"n": 0}

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            seen["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


@pytest.fixture(scope="module")
def mesh4():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    return Mesh(np.asarray(devs[:4]), ("cols",))


@pytest.fixture(scope="module")
def ec():
    code = registry.factory("jax", {"plugin": "jax", "technique": "cauchy",
                                    "k": str(K), "m": str(M)})
    assert np.array_equal(np.asarray(code.coding_matrix, np.uint8),
                          reference.cauchy_matrix(K, M))
    return code


@pytest.fixture(scope="module")
def svc(mesh4):
    return es.EncodeService(mesh4, min_bytes=0)


def _request(rng, matrix: str, i: int, ec):
    """One request against the reference: (M, rows, the rows M @ rows
    must give).  Widths are ragged and odd: ``i + 1`` stripes less a
    few columns, which a column-wise product may be cut to."""
    stripes = 1 + i % 4
    blob = rng.integers(0, 256, stripes * K * UNIT, dtype=np.uint8).tobytes()
    shards = np.stack([np.frombuffer(s, np.uint8) for s in
                       reference.ec_shards(blob, K, M, UNIT)])
    shards = shards[:, : stripes * UNIT - 3 * i - 1]
    if matrix == "encode":
        return (np.asarray(ec.coding_matrix, np.uint8),
                np.ascontiguousarray(shards[:K]), shards[K:])
    lost = {"decode1": (0,), "decode2": (2, 9), "decode3": (1, 5, 10)}[matrix]
    survivors = [c for c in range(K + M) if c not in lost][:K]
    return (ec.decode_matrix(lost), np.ascontiguousarray(shards[survivors]),
            shards[list(lost)])


@pytest.mark.parametrize("matrix", ["encode", "decode1", "decode2", "decode3"])
@pytest.mark.parametrize("group", [1, 2, 3, 5, 16])
def test_mesh_launch_equals_host_and_reference(svc, ec, matrix, group):
    rng = np.random.default_rng(1000 * group + len(matrix))
    reqs = [_request(rng, matrix, i, ec) for i in range(group)]
    before = dict(svc.stats)

    async def go():
        return await asyncio.gather(*(
            svc.apply(Mx, rows) for Mx, rows, _ in reqs))

    outs = asyncio.run(go())
    for (Mx, rows, want), out in zip(reqs, outs):
        assert out.shape == want.shape
        assert np.array_equal(out, gf_matmul(Mx, rows))
        assert np.array_equal(out, want)        # the plain reference's
    # one launch for the window, lone or not; no fallback, all 4 devices
    assert svc.stats["dp_dispatches"] - before.get("dp_dispatches", 0) == 1
    assert svc.stats["coalesced"] - before.get("coalesced", 0) == group
    assert svc.stats["fallbacks"] == 0
    assert svc.stats["mesh_devices_used"] == 4
    assert "tp_dispatches" not in svc.stats


@pytest.mark.parametrize("width", [4096, 4099, 1, 13001])
def test_column_blocks_concatenate_to_the_uncut_product(mesh4, ec, width):
    """Each device computes its own column block; side by side they are
    the uncut product, and a width 4 does not divide is padded to the
    bucket and cut back exactly."""
    rng = np.random.default_rng(width)
    C = np.asarray(ec.coding_matrix, np.uint8)
    rows = rng.integers(0, 256, (K, width), dtype=np.uint8)
    S = ef.cols_width(mesh4, width)
    assert S >= width and S % 4 == 0 and (S // 4) & (S // 4 - 1) == 0
    big = np.zeros((K, S), np.uint8)
    big[:, :width] = rows
    bits = jax.device_put(gf_matrix_to_bitmatrix(C),
                          ef.replicated_sharding(mesh4))
    res = ef.mesh_encode_cols(
        mesh4, bits, jax.device_put(big, ef.cols_sharding(mesh4)))
    blocks = sorted(res.addressable_shards, key=lambda s: s.index[1].start)
    assert len(blocks) == 4
    assert {b.data.shape for b in blocks} == {(M, S // 4)}
    assert len({b.device for b in blocks}) == 4
    whole = np.concatenate([np.asarray(b.data) for b in blocks], axis=1)
    want = gf_matmul(C, rows)
    assert np.array_equal(whole[:, :width], want)
    assert not whole[:, width:].any()           # encode(0) == 0
    assert np.array_equal(np.asarray(res)[:, :width], want)


def test_warmed_dispatches_lower_nothing_and_pass_the_guard(
        mesh4, ec, lowerings):
    svc = es.EncodeService(mesh4, min_bytes=0)
    C = np.asarray(ec.coding_matrix, np.uint8)
    assert svc.prewarm(C, [UNIT], coalesce=4) == 3      # 1, 2, 4 units
    assert svc.prewarm(C, [UNIT], coalesce=4) == 0
    rng = np.random.default_rng(7)
    transfer_guard.configure("on")
    try:
        guard0 = transfer_guard.snapshot()
        n0 = lowerings["n"]

        async def go():
            for i in range(10):
                rows = [rng.integers(0, 256, (K, UNIT - 5 * j), dtype=np.uint8)
                        for j in range(1 + i % 4)]
                outs = await asyncio.gather(*(svc.apply(C, r) for r in rows))
                for r, o in zip(rows, outs):
                    assert np.array_equal(o, gf_matmul(C, r))

        asyncio.run(go())
        guard1 = transfer_guard.snapshot()
    finally:
        transfer_guard.disarm()
    assert lowerings["n"] == n0
    assert svc.stats["cold_launches"] == 0
    assert svc.stats["dp_dispatches"] == 10 and svc.stats["fallbacks"] == 0
    # every mesh launch ran inside a guard window; none moved a buffer
    # implicitly
    assert guard1["guard_windows"] - guard0["guard_windows"] == 10
    assert guard1["host_transfers"] == guard0["host_transfers"]
    # running totals the pad-share metric reads
    assert 0 < svc.stats["mesh_occupied_bytes"] \
        <= svc.stats["mesh_padded_bytes"]


def test_launch_span_carries_devices_and_pad_bytes(mesh4, ec):
    from ceph_tpu.common.tracing import device_tracer

    svc = es.EncodeService(mesh4, min_bytes=0)
    C = np.asarray(ec.coding_matrix, np.uint8)
    rows = np.random.default_rng(3).integers(
        0, 256, (K, 3 * UNIT + 17), dtype=np.uint8)
    tracer = device_tracer()
    old = tracer.sample_rate
    tracer.sample_rate = 1.0
    try:
        asyncio.run(svc.apply(C, rows))
    finally:
        tracer.sample_rate = old
    span = [s for s in tracer.dump(limit=64) if s["name"] == "xla_launch"
            and s["tags"].get("kind") == "encode_dp"][-1]
    S = ef.cols_width(mesh4, rows.shape[1])
    assert span["tags"]["devices"] == 4
    assert span["tags"]["w"] == S and span["tags"]["b_real"] == 1
    assert span["tags"]["pad_bytes"] == (S - rows.shape[1]) * K
    assert span["tags"]["cold"] is True


def test_cluster_writes_through_the_mesh_service_store_reference_shards(
        mesh4):
    """A small in-process cluster whose OSDs share the mesh service:
    every stored shard of every object equals the plain reference."""
    from ceph_tpu.osd.daemon import object_to_pg
    from ceph_tpu.store import coll_t, ghobject_t
    from tests.integration.test_mini_cluster import Cluster, run

    k, m = 4, 2
    svc = es.EncodeService(mesh4, min_bytes=4096, window_s=0.005)

    async def go():
        async with Cluster(n_osds=6) as c:
            for o in c.osds:
                o._encode_service = svc
                o._encode_service_resolved = True
            await c.client.ec_profile_set("p", {
                "plugin": "jax", "technique": "cauchy", "k": str(k),
                "m": str(m), "crush-failure-domain": "host"})
            await c.client.pool_create("meshp", pg_num=8,
                                       pool_type="erasure",
                                       erasure_code_profile="p")
            io = c.client.ioctx("meshp")
            rng = np.random.default_rng(11)
            blobs = {f"o{i}": rng.integers(
                0, 256, (1 + i % 3) * k * UNIT, dtype=np.uint8).tobytes()
                for i in range(10)}
            await asyncio.gather(*(
                io.write_full(name, b) for name, b in blobs.items()))
            assert svc.stats["dp_dispatches"] > 0
            assert svc.stats["coalesced"] >= len(blobs)
            assert svc.stats["fallbacks"] == 0
            om = c.client.osdmap
            pool = om.get_pg_pool(io.pool_id)
            compared = 0
            for name, blob in blobs.items():
                assert await io.read(name) == blob
                pg = pool.raw_pg_to_pg(object_to_pg(pool, name))
                acting = om.pg_to_up_acting_osds(pg, folded=True)[2]
                want = reference.ec_shards(blob, k, m, UNIT)
                assert len(acting) == k + m
                for shard, osd in enumerate(acting):
                    got = c.osds[osd].store.read(
                        coll_t(pg.pool, pg.ps, shard),
                        ghobject_t(name, shard=shard))
                    assert bytes(got) == want[shard], (name, shard)
                    compared += 1
            assert compared == len(blobs) * (k + m)

    run(go())
