"""EC non-regression corpus: frozen known-answer chunk bytes.

The reference pins encoded chunks in ceph-erasure-code-corpus and
checks them with ceph_erasure_code_non_regression.cc (both empty in
this checkout — SURVEY.md §4 ring 5).  Stand-in, per VERDICT r1 #9:

1. every plugin's encoded bytes for fixed inputs are frozen in
   tests/golden/ec_kats.json (tools/gen_ec_golden.py) — a silent
   generator-matrix or GF-kernel change fails here;
2. cross-plugin byte-equality: the `jax` TPU plugin follows the ISA
   matrix lineage, so its bytes must equal the `isa` plugin's for the
   same (technique, k, m);
3. an in-test, from-the-textbook GF(2^8) oracle (log/antilog over
   0x11d, written independently of ceph_tpu.ops.gf256) re-derives one
   full encode byte-for-byte.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

from ceph_tpu.ec import registry

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "ec_kats.json")


def _payloads() -> dict[str, bytes]:
    # MUST mirror tools/gen_ec_golden.py exactly
    ramp = bytes(range(256)) * 17 + b"\x00\x01\x02"
    rnd = np.random.default_rng(0xCEF).integers(
        0, 256, 8192, dtype=np.uint8
    ).tobytes()
    return {"ramp4355": ramp, "rand8192": rnd}


def _corpus() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


CORPUS = _corpus()


@pytest.mark.parametrize("key", sorted(CORPUS), ids=lambda s: s[:60])
def test_pinned_bytes(key):
    entry = CORPUS[key]
    ec = registry.factory(entry["plugin"], dict(entry["profile"]))
    n = ec.get_chunk_count()
    for pname, payload in _payloads().items():
        want = entry["chunks"][pname]
        enc = ec.encode(set(range(n)), payload)
        assert set(map(str, enc)) == set(want), (key, pname)
        for i, chunk in enc.items():
            w = want[str(i)]
            raw = chunk.tobytes()
            assert len(raw) == w["len"], (key, pname, i)
            assert raw[:32].hex() == w["head"], (key, pname, i)
            assert hashlib.sha256(raw).hexdigest() == w["sha256"], (
                f"{key} {pname} chunk {i}: encoded bytes drifted from "
                f"the pinned corpus"
            )


MANUAL = os.path.join(os.path.dirname(__file__), "golden",
                      "jerasure_manual.json")


@pytest.mark.parametrize("col", range(7))
def test_reed_sol_van_bytes_rest_on_the_jerasure_manuals_matrix(col):
    """The pinned corpus above is the program's own output; this vector
    is not: jerasure's manual prints the coding rows of ``reed_sol_01 7
    7 8``.  Data chunk ``col`` all ones and the rest zero must store
    coding chunk i as the constant M[i][col] (PR 34 put the last row
    right; before, it stored 1 230 79 86 43 115 171 there)."""
    with open(MANUAL) as f:
        M = json.load(f)["reed_sol_01_7_7_8"]
    ec = registry.factory("jerasure", {
        "plugin": "jerasure", "technique": "reed_sol_van",
        "k": "7", "m": "7", "w": "8"})
    cs = ec.get_chunk_size(7 * 64)
    data = np.zeros((7, cs), np.uint8)
    data[col] = 1
    enc = ec.encode(set(range(14)), data.tobytes())
    for i in range(7):
        assert set(enc[7 + i].tolist()) == {M[i][col]}, (i, col)


def test_corpus_covers_every_shipped_plugin():
    plugins = {e["plugin"] for e in CORPUS.values()}
    assert {"jerasure", "isa", "jax", "shec", "lrc", "clay"} <= plugins


@pytest.mark.parametrize("technique,k,m", [("cauchy", 8, 3), ("reed_sol_van", 4, 2)])
def test_jax_plugin_matches_isa_bytes(technique, k, m):
    """The TPU plugin's ISA-lineage contract, as live byte-equality.

    Plugins may pad chunks differently (ISA aligns to 16B rows, the
    TPU plugin to its tile granularity), so the comparison uses a
    payload already aligned for both — equal chunk sizes make the
    parity bytes directly comparable."""
    prof = {"technique": technique, "k": str(k), "m": str(m)}
    a = registry.factory("jax", dict(prof))
    b = registry.factory("isa", dict(prof))
    payload = np.random.default_rng(3).integers(
        0, 256, k * 4096, dtype=np.uint8
    ).tobytes()
    ea = a.encode(set(range(k + m)), payload)
    eb = b.encode(set(range(k + m)), payload)
    assert len(ea[0]) == len(eb[0]) == 4096, "alignment assumption broke"
    for i in range(k + m):
        assert np.array_equal(ea[i], eb[i]), (technique, k, m, i)


# -- independent GF(2^8) oracle ---------------------------------------------

def _tables():
    """Textbook log/antilog for GF(2^8)/0x11d, generator 2 — written
    from the definition, shares no code with ceph_tpu.ops.gf256."""
    exp = [0] * 512
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    return exp, log


def _gf_mul(a: int, b: int, exp, log) -> int:
    if a == 0 or b == 0:
        return 0
    return exp[log[a] + log[b]]


def test_independent_oracle_jerasure_rs_van():
    ec = registry.factory(
        "jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"}
    )
    payload = _payloads()["ramp4355"]
    enc = ec.encode(set(range(6)), payload)
    exp, log = _tables()
    from ceph_tpu.models.matrices import jerasure_rs_vandermonde_matrix

    C = jerasure_rs_vandermonde_matrix(4, 2)
    data = [enc[i] for i in range(4)]
    for r in range(2):
        want = np.zeros(len(data[0]), dtype=np.uint8)
        for c in range(4):
            coef = int(C[r, c])
            col = np.frombuffer(data[c].tobytes(), np.uint8)
            prod = np.array(
                [_gf_mul(coef, int(v), exp, log) for v in col], np.uint8
            )
            want ^= prod
        assert np.array_equal(want, enc[4 + r]), f"parity row {r} drifted"
