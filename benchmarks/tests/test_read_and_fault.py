"""The seams PR 33 gave the harness, rehearsed tiny on the CPU backend: a
read loop whose every answer is compared, an OSD that is down and stays
in, a configuration's own reference and profile keys, every counter —
and the faults that must make a run not ``correct``.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import json

import pytest
from test_bench_harness import _bench, _run, _tiny  # sets the path first

import faults  # noqa: I001
import run as bench_run
from harness import verify

CELL = "ec83_degraded_read"
NEW = ("read_decode_share_pct", "ec_decode_ms_per_op",
       "ec_sub_read_ms_per_op", "decode_ops_per_launch.read",
       "gf_bitmatmul_roofline.read_decode")
XOR_REFERENCE = '''"""k=2 m=1 reed_sol_van: the one coding row is all ones."""
import numpy as np


def expected_copies(pool, blob):
    unit = pool["stripe_unit"]
    data = np.frombuffer(blob, np.uint8).reshape(-1, 2, unit)
    a, b = data[:, 0].reshape(-1), data[:, 1].reshape(-1)
    return [a.tobytes(), b.tobytes(), (a ^ b).tobytes()]
'''


def test_the_cells_are_declared_as_the_issue_names_them():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert list(cells) == ["ec83_write", "rep3_write_4k", "ec83_recovery",
                           "ec83_write_4chip", CELL]
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == (
        "ec83_12osd", "rados_bench_rand_degraded", 1)
    assert (cells["rep3_write_4k"]["config"],
            cells["rep3_write_4k"]["traffic"]) == (
        "rep3_12osd", "rados_bench_write_4k")
    small, big = (bench_run.load_cell(c)["traffic"]
                  for c in ("rep3_write_4k", "ec83_write"))
    assert {k for k in big if big[k] != small[k]} == {
        "source", "object_bytes"} and small["object_bytes"] == 4096
    p = bench_run.load_cell(CELL)["traffic"]
    assert (p["loop"], p["fault"], p["prefill_objects"], p["in_flight"]) == (
        {"op": "read"}, {"stop_osd": 11, "out": False}, 256, 16)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and \
                m["moves"] == "throughput_MiB_s"
    assert {m["name"] for m in bench_run.load_cell(CELL)["per_layer"]} \
        >= set(NEW)
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert (bench["run_seconds"], by_name["throughput_MiB_s"]["bound"],
            by_name["recovery_MiB_s"]["bound"],
            by_name["setup_s"]["bound"]) == (51, 0.2, 0.2, 0.25)


def test_degraded_read_tiny(tmp_path, capsys):
    out = _run(_tiny(CELL), tmp_path, trace=True, seconds=3.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    win = next(ln for ln in lines if ln["line"] == "window")
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    # every read of the window was compared, none differed
    assert win["reads_compared"] == out["attempted"] and \
        win["reads_wrong"] == 0
    got = out["compared"]
    assert got["device_launches"]["value"] > 0          # a decode launched
    assert got["must_be_0.osd.recovery_ops"] == {"value": 0, "max": 0}
    assert got["stored_rebuilt"] == {"value": 0, "max": 0}
    assert got["stored_absent_live"] == {"value": 0, "max": 0}
    stored = win["verify"]["stored"]
    assert 0 < stored["absent"] < stored["equal"] and stored["differ"] == 0
    # every counter, not three
    assert win["counters"]["msgr.frames_in"] > 0
    assert win["counters"]["osd.op_r"] > 0
    metrics = {k: v for k, (v, _unit) in out["metrics"].items()}
    # no peaks for the CPU: the roofline's reader has nothing to read
    assert set(NEW) - set(metrics) == {"gf_bitmatmul_roofline.read_decode"}
    assert {m["name"] for m in bench_run.load_cell(CELL)["per_layer"]} \
        - set(metrics) == {"gf_bitmatmul_roofline.read_decode"}
    assert 0 < metrics["read_decode_share_pct"] < 100
    assert metrics["decode_ops_per_launch.read"] >= 1
    assert metrics["ec_decode_ms_per_op"] > 0
    assert metrics["ec_sub_read_ms_per_op"] > 0
    assert metrics["compiles_in_window.write"] == 0
    assert out["metrics"]["read_decode_share_pct"][1] == "%"


def test_read_decode_roofline_on_a_hand_made_trace():
    reader = bench_run.load_layer_metric(
        bench_run.load_cell(CELL)["metrics_dir"],
        "gf_bitmatmul_roofline.read_decode")
    spec = bench_run.load_cell(CELL)
    trace = {"devices": {"/device:TPU:0": [
        ("jit_gf_bitmatmul_pallas/fusion", 10.1, 0.001),
        ("jit_gf_bitmatmul/fusion.2", 10.2, 0.001),
        ("jit_bench_device_probe/add", 10.5, 0.004)]}, "planes": {}}
    launch = {"name": "xla_launch", "start_mono": 10.1, "end_mono": 10.3,
              "tags": {"kind": "encode_single", "b_real": 2}}
    run = {"trace_t0": 10.0, "trace_t1": 11.0, "config": spec["config"],
           "traffic": spec["traffic"],
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    # two reads a launch, each rebuilds one 512 KiB chunk from 8: bound
    # by bytes, (8 + 1) x 1 MiB over 819 GB/s against 2 ms of kernel
    want = 100 * (9 * (1 << 20) / 819e9) / 0.002
    assert reader.compute([launch], {}, trace, run) == pytest.approx(want)
    assert reader.compute([], {}, trace, run) is None
    assert reader.compute([launch], {}, None, run) is None
    assert reader.compute([launch], {}, trace, {**run, "peaks": None}) is None


@pytest.mark.parametrize("cell", [CELL, "rep3_write_4k", "ec83_write"])
def test_a_flipped_byte_of_one_stored_copy_is_not_correct(cell, tmp_path):
    with faults.planted("flip"):
        out = _run(_tiny(cell), tmp_path, trace=False)
    assert not out["correct"] and out["failed"] == 0
    assert out["compared"]["stored_differ"] == {"value": 1, "max": 0}
    assert out["compared"]["reads_wrong"]["value"] == 0


def test_an_acknowledged_write_with_a_replica_missing_is_not_correct(
        tmp_path):
    """The guarantee ``rep3_12osd`` states: all 3 replicas committed."""
    with faults.planted("remove"):
        out = _run(_tiny("rep3_write_4k"), tmp_path, trace=False)
    assert not out["correct"] and out["failed"] == 0
    assert out["compared"]["stored_absent"] == {"value": 1, "max": 0}
    assert out["compared"]["stored_differ"]["value"] == 0
    # the copy removed was the primary's: the read back has no answer
    assert out["compared"]["read_back_differ"] == {"value": 1, "max": 0}


def test_a_read_answered_with_wrong_bytes_is_not_correct(tmp_path):
    with faults.planted("wrong_read"):
        out = _run(_tiny(CELL), tmp_path, trace=False)
    assert out["attempted"] > faults.WRONG_READ_AT
    assert not out["correct"] and out["failed"] == 1
    assert out["compared"]["reads_wrong"] == {"value": 1, "max": 0}
    assert out["compared"]["stored_differ"]["value"] == 0


def test_a_configurations_own_reference_and_profile_keys(
        tmp_path, monkeypatch):
    """``plugin=jerasure technique=reed_sol_van k=2 m=1``, the technique
    given as a profile key: the pool's parity is the XOR of its two data
    shards.  Its own reference says so; the cauchy one does not."""
    refs = tmp_path / "references"
    refs.mkdir()
    (refs / "xor_k2m1.py").write_text(XOR_REFERENCE)
    monkeypatch.setattr(verify, "REFERENCES_DIR", str(refs))
    verify.load_reference.cache_clear()
    spec = _tiny("ec83_write")
    pool = spec["config"]["pool"]
    del pool["technique"]
    pool.update(plugin="jerasure", profile={"technique": "reed_sol_van"})
    try:
        spec["config"]["reference"] = "xor_k2m1"
        mine = _run(spec, tmp_path / "a", trace=False)
        del spec["config"]["reference"]
        cauchy = _run(spec, tmp_path / "b", trace=False)
    finally:
        verify.load_reference.cache_clear()
    assert mine["correct"] and mine["failed"] == 0
    assert mine["compared"]["device_launches"]["value"] > 0
    assert not cauchy["correct"] and cauchy["failed"] == 0
    assert cauchy["compared"]["stored_differ"]["value"] == 16
    assert cauchy["compared"]["read_back_differ"]["value"] == 0
