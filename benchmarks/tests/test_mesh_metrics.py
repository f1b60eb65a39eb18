"""The four readers of the four-chip cell (PR 27), rehearsed on the CPU
backend: on a hand-made trace with four device planes and hand-made
counters and spans, and on the cell itself run tiny through a 4-device
mesh of virtual CPU devices.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import asyncio

import jax
import numpy as np
import pytest
from test_bench_harness import _bench, _tiny

import run as bench_run
from harness import reduce, window

CELL = "ec83_write_4chip"
NEW = ("gf_bitmatmul_roofline.encode_mesh", "device_balance_pct.write",
       "encode_mesh_pad_share_pct", "encode_launch_host_ms")
MESH_PROGRAM = "jit_encode_mesh_cols/"


def _reader(name: str):
    return bench_run.load_layer_metric(
        bench_run.load_cell(CELL)["metrics_dir"], name)


def _run_dict(**over) -> dict:
    spec = bench_run.load_cell(CELL)
    return {"trace_t0": 10.0, "trace_t1": 11.0, "config": spec["config"],
            "traffic": spec["traffic"], "acked_ops": 4,
            "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}, **over}


def _planes(busy_ms: list[float]) -> dict:
    """One mesh-program op of ``busy_ms[i]`` ms on plane i, and a probe
    launch of the harness on plane 0."""
    devices = {f"/device:TPU:{i}": (
        [(MESH_PROGRAM + "fusion.1", 10.1, ms / 1e3)] if ms else [])
        for i, ms in enumerate(busy_ms)}
    devices["/device:TPU:0"].append(("jit_bench_device_probe/add", 10.5, 0.0))
    return {"devices": devices, "planes": {}}


def _launch(t0: float, ms: float, b_real: int, kind: str = "encode_dp"):
    return {"name": "xla_launch", "start_mono": t0, "end_mono": t0 + ms / 1e3,
            "tags": {"kind": kind, "b_real": b_real, "stage": "device"}}


def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ec83_12osd_4chip", "rados_bench_write", 4)
    spec = bench_run.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "throughput_MiB_s", "setup_s"}
    mine = {m["name"] for m in spec["per_layer"]}
    control = {m["name"] for m in bench_run.load_cell("ec83_write")[
        "per_layer"]}
    assert mine == (control - {"gf_bitmatmul_roofline.encode"}) | set(NEW)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    # the deployment's pool, store and guarantees are ec83_12osd's
    a, b = spec["config"], bench_run.load_cell("ec83_write")["config"]
    for key in ("pool", "osds", "hosts", "mons", "processes", "store",
                "store_dir", "store_free_bytes_min", "guarantees"):
        assert a[key] == b[key], key
    assert (a["chips"], b["chips"], a["architecture"]) == (4, 1, None)
    assert sorted(a["reduced"]) == ["hosts", "osds", "processes"]


def test_balance_on_four_planes():
    balance = _reader("device_balance_pct.write").compute
    run = _run_dict()
    assert balance([], {}, _planes([2.0, 2.0, 2.0, 2.0]), run) == \
        pytest.approx(100.0)
    assert balance([], {}, _planes([4.0, 2.0, 3.0, 1.0]), run) == \
        pytest.approx(25.0)
    # one chip never worked: its plane is empty, or is not there at all
    assert balance([], {}, _planes([2.0, 2.0, 2.0, 0.0]), run) == 0.0
    three = _planes([2.0, 2.0, 2.0])
    assert balance([], {}, three, run) == 0.0
    # work outside the traced window is not counted
    late = _planes([2.0, 2.0, 2.0, 2.0])
    late["devices"]["/device:TPU:3"] = [(MESH_PROGRAM + "fusion.1", 11.5, 1.0)]
    assert balance([], {}, late, run) == 0.0
    # no planes, no trace, nothing busy: nothing to read
    assert balance([], {}, {"devices": {}, "planes": {}}, run) is None
    assert balance([], {}, None, run) is None
    assert balance([], {}, _planes([0, 0, 0, 0]), run) is None


def test_roofline_sums_the_mesh_program_over_the_planes():
    roofline = _reader("gf_bitmatmul_roofline.encode_mesh").compute
    run = _run_dict()
    S = run["traffic"]["object_bytes"] // 8
    spans = [_launch(10.2, 5.0, 2), _launch(10.6, 5.0, 1),
             _launch(9.5, 5.0, 4),                  # before the trace
             _launch(10.7, 1.0, 9, kind="decode_batch")]
    _ops, nbytes = reduce.gf_matmul_cost(8, 3, 3 * S)
    trace = _planes([0.05, 0.05, 0.05, 0.05])       # 0.2 ms of mesh program
    # another program on a plane is not the mesh program's time
    trace["devices"]["/device:TPU:1"].append(
        ("jit_gf_bitmatmul_pallas_grouped/custom-call", 10.3, 0.5))
    got = roofline(spans, {}, trace, run)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 0.2e-3)
    assert 0 < got <= 100
    # a perfect four-way split of one chip's least time reads 100%
    least = nbytes / 819e9
    even = _planes([1e3 * least / 4] * 4)
    assert roofline(spans, {}, even, run) == pytest.approx(100.0)
    # the parent's program, or the one-chip kernel, is not read
    other = {"devices": {"/device:TPU:0": [
        ("jit_gf_bitmatmul_pallas_grouped/custom-call", 10.3, 0.5),
        ("jit__encode/fusion", 10.4, 0.5)]}, "planes": {}}
    assert roofline(spans, {}, other, run) is None
    assert roofline([], {}, trace, run) is None             # no launches
    assert roofline(spans, {}, None, run) is None           # no trace
    assert roofline(spans, {}, trace, _run_dict(peaks=None)) is None  # CPU


def test_pad_share_and_launch_host_ms():
    pad = _reader("encode_mesh_pad_share_pct").compute
    assert pad([], {"encode.mesh_occupied_bytes": 3 << 22,
                    "encode.mesh_padded_bytes": 4 << 22}, None, {}) == \
        pytest.approx(25.0)
    assert pad([], {"encode.mesh_occupied_bytes": 1 << 22,
                    "encode.mesh_padded_bytes": 1 << 22}, None, {}) == 0.0
    assert pad([], {}, None, {}) is None        # the parent: no such counter
    assert pad([], {"encode.mesh_padded_bytes": 0}, None, {}) is None
    host = _reader("encode_launch_host_ms").compute
    spans = [_launch(10.0, 4.0, 1), _launch(10.1, 8.0, 3),
             _launch(10.2, 50.0, 1, kind="encode_single"),
             {"name": "ec_encode", "start_mono": 10.0, "end_mono": 10.1,
              "tags": {}}]
    assert host(spans, {}, None, {}) == pytest.approx(6.0)
    assert host(spans[2:], {}, None, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_nothing(name):
    empty = {"acked_ops": 5, "window": window.Window(0.0, 4.0, 1.0)}
    assert _reader(name).compute([], {}, None, empty) is None


def test_the_cell_rehearsed_tiny_on_a_four_device_mesh(tmp_path):
    """The cell's own files through ``run_cell`` with the encode service
    on a mesh of 4 virtual CPU devices: warm-up covers every launch
    shape (no lowering, no cold launch in the window), the launches pass
    the transfer guard, and the line holds every per-layer metric of the
    cell but the roofline share, which only a chip's peaks give."""
    from jax.sharding import Mesh

    from ceph_tpu.parallel import encode_service as es

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices (XLA_FLAGS was set elsewhere)")
    spec = _tiny(CELL)
    svc = es.EncodeService(Mesh(np.asarray(devs[:4]), ("cols",)))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    out = asyncio.run(asyncio.wait_for(bench_run.run_cell(
        spec, seed=(1 << 31) + 27, seconds=3.0, trace=True,
        data_dir=str(tmp_path), device=device, encode_service=svc), 180))
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert svc.stats["dp_dispatches"] > 0 and svc.stats["fallbacks"] == 0
    assert svc.stats["mesh_devices_used"] == 4
    assert "single_dispatches" not in svc.stats
    want = {m["name"] for m in spec["per_layer"]} - {
        "gf_bitmatmul_roofline.encode_mesh"}
    assert set(out["metrics"]) == want
    got = {k: v for k, (v, _unit) in out["metrics"].items()}
    assert got["compiles_in_window.write"] == 0
    assert 0 <= got["encode_mesh_pad_share_pct"] < 100
    assert 0 < got["encode_launch_host_ms"] < 1e3 * got["ec_path_ms_per_op"]
    assert got["encode_ops_per_launch"] >= 1
    assert got["device_balance_pct.write"] == 0.0   # one CPU plane, 4 chips
