"""The benchmark's plain reference of the LRC(4,2,3) pool
(``benchmarks/references/lrc423.py``) is the code that Ceph's
erasure-code-lrc.rst describes, and not a copy of the program's bytes.
From the reference alone: its generator (unit vectors encoded at one
byte a chunk) puts the data at 0, 1, 4, 5, satisfies every layer's
equation, and, by Gaussian elimination over GF(2^8), determines each
lost chunk from the 3 others of its local group (the bandwidth the cell's
``recovery_read_bytes_per_rebuilt_byte`` reads).  Then the program's
host plugin and its device path store what the reference says.
"""

from __future__ import annotations

import asyncio
import importlib.util
import os

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POOL = {"type": "erasure", "plugin": "lrc", "k": 4, "m": 2,
        "stripe_unit": 4096, "profile": {"l": "3"}}


def _load():
    spec = importlib.util.spec_from_file_location(
        "reference_lrc423_alone",
        os.path.join(ROOT, "benchmarks", "references", "lrc423.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load()


def _mul_table() -> np.ndarray:
    t = np.zeros((256, 256), np.uint8)
    for a in range(1, 256):
        t[a, 1:] = REF.EXP[REF.LOG[a] + REF.LOG[np.arange(1, 256)]]
    return t


MUL = _mul_table()


def rank(rows: np.ndarray) -> int:
    """Rank over GF(2^8) by Gaussian elimination."""
    a = rows.copy()
    r = 0
    for col in range(a.shape[1]):
        nz = np.nonzero(a[r:, col])[0]
        if nz.size == 0:
            continue
        p = r + nz[0]
        a[[r, p]] = a[[p, r]]
        a[r] = MUL[REF.inv(int(a[r, col])), a[r]]
        others = np.nonzero(a[:, col])[0]
        others = others[others != r]
        a[others] ^= MUL[a[others, col][:, None], a[r][None, :]]
        r += 1
        if r == a.shape[0]:
            break
    return r


def generator() -> np.ndarray:
    """(8, 4): row p is what position p stores of data chunk i = 1 and
    the others 0, at one-byte chunks (a stripe unit of 1)."""
    G = np.zeros((REF.N_CHUNKS, 4), np.uint8)
    for i in range(4):
        blob = np.zeros(4, np.uint8)
        blob[i] = 1
        for p, shard in enumerate(REF.lrc_shards(blob.tobytes(), 1)):
            G[p, i] = shard[0]
    return G


def test_the_data_sits_where_the_mapping_puts_it_and_every_layer_holds():
    G = generator()
    assert G[list(REF.DATA)].tolist() == np.eye(4, dtype=int).tolist()
    # jerasure reed_sol_van, k=4 m=2: 01 01 01 01 and 01 46 8f c8
    assert G[2].tolist() == [1, 1, 1, 1]
    assert G[6].tolist() == [0x01, 0x46, 0x8F, 0xC8]
    # each local group of 4 xors to zero (its k=3 m=1 row is 1 1 1)
    for group in REF.GROUPS:
        acc = np.zeros(4, np.uint8)
        for p in group:
            acc ^= G[p]
        assert not acc.any(), group
    assert REF.reed_sol_van_rows(3, 1) == [[1, 1, 1]]


def test_the_global_layer_is_mds_over_its_six_positions():
    """Any 2 of positions 0, 1, 2, 4, 5, 6 lost, the other 4 determine
    the object (RS(4, 2))."""
    G = generator()
    assert rank(G) == 4
    glob = [0, 1, 2, 4, 5, 6]
    for a in range(len(glob)):
        for b in range(a + 1, len(glob)):
            rest = [p for p in glob if p not in (glob[a], glob[b])]
            assert rank(G[rest]) == 4, (glob[a], glob[b])


@pytest.mark.parametrize("lost", range(8))
def test_each_lost_chunk_is_determined_by_the_3_others_of_its_group(lost):
    G = generator()
    group = next(g for g in REF.GROUPS if lost in g)
    helpers = [p for p in group if p != lost]
    # the lost row lies in the span of the 3 helpers' rows ...
    assert rank(G[helpers]) == rank(G[helpers + [lost]])
    # ... and no 2 of them span it: the repair reads all 3
    for skip in helpers:
        two = [h for h in helpers if h != skip]
        assert rank(G[two]) < rank(G[two + [lost]]), two
    # the bytes: the group's xor of a seeded object
    blob = np.random.default_rng([423, lost]).integers(
        0, 256, 2 * 4 * 4096, dtype=np.uint8).tobytes()
    shards = [np.frombuffer(s, np.uint8)
              for s in REF.expected_copies(POOL, blob)]
    rebuilt = shards[helpers[0]] ^ shards[helpers[1]] ^ shards[helpers[2]]
    assert rebuilt.tobytes() == shards[lost].tobytes()


@pytest.mark.parametrize("stripes", [1, 5])
def test_the_programs_host_and_device_paths_store_the_references_copies(
        stripes):
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil
    from ceph_tpu.parallel.encode_service import EncodeService

    ec = registry.factory("lrc", {"plugin": "lrc", "k": "4", "m": "2",
                                  "l": "3"})
    sinfo = ecutil.stripe_info(ec)
    svc = EncodeService(device=jax.devices()[0], min_bytes=1)
    blob = np.random.default_rng([4423, stripes]).integers(
        0, 256, stripes * 4 * 4096, dtype=np.uint8).tobytes()
    want = REF.expected_copies(POOL, blob)
    host = ecutil.encode(sinfo, ec, blob)
    device = asyncio.run(ecutil.encode_async(sinfo, ec, blob, service=svc))
    assert [host[p].tobytes() for p in range(8)] == want
    assert [device[p].tobytes() for p in range(8)] == want
    with pytest.raises(ValueError):
        REF.expected_copies({**POOL, "plugin": "jerasure"}, blob)
