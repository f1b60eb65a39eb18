"""An RBD image's writes on an EC(4,2) overwrite pool, tiny (PR 37):
``plugin=jerasure technique=reed_sol_van k=4 m=2`` under overlapping 4 KiB
read-modify-writes, every stored shard held to the benchmark's plain
reference ``benchmarks/references/rs_van42.py``; and that reference
against a hand check of jerasure's matrix.
"""

from __future__ import annotations

import asyncio
import importlib.util
import itertools
import os

import numpy as np
import pytest

from ceph_tpu.store import coll_t, ghobject_t
from tests.integration.test_mini_cluster import Cluster, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, M, UNIT = 4, 2, 4096
POOL = {"type": "erasure", "plugin": "jerasure", "technique": "reed_sol_van",
        "k": K, "m": M, "stripe_unit": UNIT}


def _load():
    path = os.path.join(ROOT, "benchmarks", "references", "rs_van42.py")
    spec = importlib.util.spec_from_file_location("reference_rs_van42", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, path


REF, REF_PATH = _load()


def _rank(rows: list[list[int]]) -> int:
    """Gaussian elimination over GF(2^8) with the reference's tables."""
    rows = [list(r) for r in rows]
    rank = 0
    for col in range(len(rows[0])):
        piv = next((r for r in range(rank, len(rows)) if rows[r][col]), None)
        if piv is None:
            continue
        rows[rank], rows[piv] = rows[piv], rows[rank]
        inv = REF.inv(rows[rank][col])
        rows[rank] = [REF.mul(inv, v) for v in rows[rank]]
        for r in range(len(rows)):
            if r != rank and rows[r][col]:
                c = rows[r][col]
                rows[r] = [v ^ REF.mul(c, w)
                           for v, w in zip(rows[r], rows[rank])]
        rank += 1
    return rank


def test_the_reference_imports_nothing_of_the_program_or_another_reference():
    with open(REF_PATH) as f:
        src = f.read()
    assert "ceph_tpu" not in src and "clay8411" not in src
    assert "import" not in src.replace("from __future__ import annotations",
                                       "").replace("import numpy as np", "")


def test_the_generator_is_jerasures_6_by_4_matrix():
    G = REF.generator_matrix(K, M)
    assert G[:K] == [[int(i == j) for j in range(K)] for i in range(K)]
    # first coding row and first coding column all ones
    assert G[K] == [1] * K and [row[0] for row in G[K:]] == [1] * M
    # the second row, by hand: the extended Vandermonde matrix's rows
    # 1 i i^2 i^3 (i = 4: 1 4 16 64) after the elimination and the
    # two scalings
    assert G[K + 1] == [1, 70, 143, 200] == [0x01, 0x46, 0x8F, 0xC8]
    # MDS: any 4 of the 6 rows are invertible
    for rows in itertools.combinations(range(K + M), K):
        assert _rank([G[r] for r in rows]) == K, rows
    # the field is the one of polynomial 0x11d
    assert REF.mul(0x80, 2) == 0x1D and REF.mul(REF.inv(200), 200) == 1


def test_the_reference_is_the_programs_host_encode_and_not_cauchys():
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil

    ec = registry.factory("jerasure", {
        "plugin": "jerasure", "technique": "reed_sol_van",
        "k": str(K), "m": str(M)})
    ec.device_min_bytes = 1 << 62
    blob = np.random.default_rng(37).integers(
        0, 256, 64 << 10, dtype=np.uint8).tobytes()
    sinfo = ecutil.StripeInfo(K, ec.get_chunk_size(UNIT * K) * K)
    want = ecutil.encode(sinfo, ec, blob)
    got = REF.expected_copies(POOL, blob)
    assert [want[i].tobytes() for i in range(K + M)] == got
    with pytest.raises(ValueError):     # it is no other pool's reference
        REF.expected_copies({**POOL, "plugin": "jax", "technique": "cauchy"},
                            blob)


def test_200_overlapping_4k_writes_leave_every_shard_as_the_reference_says():
    """Depth 8 over 6 objects of 64 KiB (4 stripes): most writes share an
    object with another in flight, none shares a block."""
    size, n_obj, depth, n_ops = 64 << 10, 6, 8, 200
    rng = np.random.default_rng(42)
    model = {f"rbd_data.t.{i:016x}": bytearray(
        rng.integers(0, 256, size, dtype=np.uint8).tobytes())
        for i in range(n_obj)}
    names = list(model)
    ops = [(names[int(rng.integers(n_obj))], int(rng.integers(size // 4096)),
            rng.integers(0, 256, 4096, dtype=np.uint8).tobytes())
           for _ in range(n_ops)]

    async def go():
        async with Cluster(n_osds=7) as c:
            await c.client.ec_profile_set("rbdp", {
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": str(K), "m": str(M)})
            await c.client.pool_create(
                "rbd", pg_num=4, pool_type="erasure",
                erasure_code_profile="rbdp")
            io = c.client.ioctx("rbd")
            for name, data in model.items():
                await io.write_full(name, bytes(data))
            busy, todo = set(), list(ops)

            def counted(key):  # perf collections outlive a test's daemons
                return sum(o.perf.dump().get(key, 0) for o in c.osds)

            before = counted("ec_rmw_ops"), counted("store_read_ops")

            async def worker():
                while todo:
                    at = next((i for i, (n, b, _d) in enumerate(todo)
                               if (n, b) not in busy), None)
                    if at is None:
                        await asyncio.sleep(0)
                        continue
                    name, block, data = todo.pop(at)
                    busy.add((name, block))
                    await io.write(name, data, block * 4096)
                    model[name][block * 4096:(block + 1) * 4096] = data
                    busy.discard((name, block))

            await asyncio.gather(*(worker() for _ in range(depth)))
            om = c.client.osdmap
            pool = om.get_pg_pool(io.pool_id)
            assert counted("ec_rmw_ops") - before[0] == n_ops
            # ONE round of k sub-reads a write: the probe of the object
            # brings the old stripes along (two rounds only where the
            # extent cache looked as if it held them and did not)
            reads = counted("store_read_ops") - before[1]
            assert K * n_ops <= reads <= 1.15 * K * n_ops, reads
            from ceph_tpu.osd.daemon import object_to_pg

            for name, data in model.items():
                assert await io.read(name) == bytes(data), name
                assert await io.read(name, off=8192, length=4096) == \
                    bytes(data[8192:12288])
                pg = pool.raw_pg_to_pg(object_to_pg(pool, name))
                acting = om.pg_to_up_acting_osds(pg, folded=True)[2]
                want = REF.expected_copies(POOL, bytes(data))
                for shard, osd in enumerate(acting):
                    got = c.osds[osd].store.read(
                        coll_t(pg.pool, pg.ps, shard),
                        ghobject_t(name, shard=shard))
                    assert bytes(got) == want[shard], (name, shard)

    run(go())
