"""The benchmark's plain reference of the CLAY pool is a Clay code, and
not a copy of the program's bytes (PR 34).  From the reference alone:
its generator matrix (the unit vectors encoded at one-byte sub-chunks),
then Gaussian elimination over GF(2^8) shows the two properties the
code is chosen for: any m chunks lost are determined by the other k
(MDS), and each single chunk is determined by the alpha/q repair
sub-chunks of the other d = k+m-1 (MSR repair, the bandwidth the cell's
``recovery_read_bytes_per_rebuilt_byte`` reads).
"""

from __future__ import annotations

import importlib.util
import itertools
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "reference_clay8411_alone",
        os.path.join(ROOT, "benchmarks", "references", "clay8411.py"))
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


@pytest.fixture(scope="module", params=[(4, 2, 5), (8, 4, 11)],
                ids=["k4m2d5", "k8m4d11"])
def generator(request):
    """(k, m, d, q, alpha, G): G[n] is the (alpha, k*alpha) block of
    chunk n's sub-chunks as combinations of the data sub-chunks."""
    k, m, d = request.param
    q, _t, nu, alpha = REF.geometry(k, m, d)
    assert nu == 0
    n = k * alpha
    # unit vector j = data sub-chunk j set to 1: one stripe an input,
    # all n inputs side by side as n stripes of one-byte sub-chunks
    blob = np.zeros((n, k, alpha), np.uint8)
    blob.reshape(n, n)[np.arange(n), np.arange(n)] = 1
    shards = REF.clay_shards(blob.tobytes(), k, m, d, alpha)
    G = [np.frombuffer(s, np.uint8).reshape(n, alpha).T.copy()
         for s in shards]
    return k, m, d, q, alpha, G


def test_reference_is_systematic_and_mds_on_a_sample_of_losses(generator):
    k, m, _d, _q, alpha, G = generator
    n = k * alpha
    assert np.array_equal(np.concatenate(G[:k]), np.eye(n, dtype=np.uint8))
    losses = list(itertools.combinations(range(k + m), m))
    pick = np.random.default_rng(34).choice(len(losses), 6, replace=False)
    sample = [losses[i] for i in pick] + [tuple(range(m)),
                                          tuple(range(k, k + m))]
    for lost in sample:
        rest = np.concatenate([G[i] for i in range(k + m)
                               if i not in lost])
        assert rank(rest) == n, lost      # the other k determine all


def test_each_chunk_is_determined_by_the_repair_planes_of_the_others(
        generator):
    k, m, d, q, alpha, G = generator
    t = (k + m) // q
    for lost in range(k + m):
        planes = REF.repair_planes(lost, q, t)
        assert len(planes) == alpha // q
        helpers = np.concatenate([G[i][planes] for i in range(k + m)
                                  if i != lost])
        assert helpers.shape[0] == d * alpha // q
        r = rank(helpers)
        assert rank(np.concatenate([helpers, G[lost]])) == r, lost
        # and no fewer planes do: one helper's sub-chunks left out
        # leaves the lost chunk undetermined
        fewer = helpers[alpha // q:]
        assert rank(np.concatenate([fewer, G[lost]])) > rank(fewer), lost


def test_reed_sol_van_is_jerasures():
    """``reed_sol_01 7 7 8`` of jerasure's manual: the last rows of the
    distribution matrix; and the two codes the construction uses."""
    rows = REF.reed_sol_van(7, 7)
    assert rows[0] == [1] * 7
    assert rows[1] == [1, 199, 210, 240, 105, 121, 248]
    assert rows[6] == [1, 187, 104, 210, 211, 105, 186]
    assert REF.reed_sol_van(2, 2) == [[1, 1], [1, 143]]
    from ceph_tpu.models.matrices import jerasure_rs_vandermonde_matrix

    for k, m in ((2, 2), (8, 4), (7, 7), (4, 2), (10, 4)):
        assert jerasure_rs_vandermonde_matrix(k, m).tolist() == \
            REF.reed_sol_van(k, m)
