"""Rehearsal of the benchmark harness on the CPU backend, tiny: the same
functions ``benchmarks/run.py`` runs on the chip (its ``main`` refuses
the CPU, which a test pins).  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import asyncio
import copy
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import jax  # noqa: E402
import pytest  # noqa: E402

import run as bench_run  # noqa: E402
from harness import reduce, reference, window  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                  "compared"}


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _tiny(workload: str) -> dict:
    """The cell's own files, cut to 6 OSDs, 64 KiB objects."""
    spec = copy.deepcopy(bench_run.load_cell(workload))
    spec["config"]["osds"] = 6
    pool = spec["config"]["pool"]
    pool["pg_num"] = 8
    if pool["type"] == "erasure":
        pool["k"], pool["m"] = 2, 1
        spec["traffic"]["warm_matrices"] = [    # at most m erasures
            "decode1" if name.startswith("decode") else name
            for name in spec["traffic"]["warm_matrices"]]
    spec["traffic"].update(
        object_bytes=min(64 << 10, spec["traffic"]["object_bytes"]),
        distinct_payloads=4, in_flight=4,
        warmup_ops=4, verify_sample=16, slice_seconds=0.5,
        trace={"start_s": 0.5, "seconds": 1.0})
    if spec["traffic"]["prefill_objects"]:
        spec["traffic"]["prefill_objects"] = 24
    return spec


def _run(spec: dict, tmp_path, *, trace: bool, seconds: float = 2.0) -> dict:
    from ceph_tpu.parallel import encode_service as es

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    return asyncio.run(asyncio.wait_for(bench_run.run_cell(
        spec, seed=(1 << 31) + 7, seconds=seconds, trace=trace,
        data_dir=str(tmp_path), device=device,
        encode_service=es.EncodeService(device=devs[0])), 180))


@pytest.mark.parametrize("workload", ["ec83_write", "rep3_write_4k"])
def test_write_cells_tiny(workload, tmp_path):
    spec = _tiny(workload)
    out = _run(spec, tmp_path, trace=False)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"throughput_MiB_s", "setup_s"}
    assert all(v > 0 for v, _unit in out["metrics"].values())
    line = json.loads(window.last_line(**out))
    assert set(line) == LAST_LINE_KEYS and list(line)[-1] == "compared"
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert all(set(x) in ({"value", "max"}, {"value", "min"})
               for x in line["compared"].values())


def test_traced_write_reports_per_layer_metrics_and_breakdown(tmp_path):
    out = _run(_tiny("ec83_write"), tmp_path, trace=True, seconds=3.0)
    assert out["correct"]
    got = set(out["metrics"])
    assert {"client_op_p50_ms", "client_op_p95_ms", "net_ms_per_op",
            "store_ms_per_op",
            "ec_path_ms_per_op", "encode_ops_per_launch", "disk_fsync_ms",
            "compiles_in_window.write", "device_idle_pct.write"} <= got
    assert out["metrics"]["compiles_in_window.write"][0] == 0
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    line = json.loads(window.last_line(**out))
    assert set(line) == LAST_LINE_KEYS | {"breakdown"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert 0 < len(line["breakdown"]["idle_gaps"]) <= 10


def test_recovery_cell_tiny(tmp_path):
    out = _run(_tiny("ec83_recovery"), tmp_path, trace=True, seconds=4.0)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["decode_lanes_per_launch"][0] >= 1
    assert out["metrics"]["compiles_in_window.recovery"][0] == 0


def test_main_refuses_the_cpu_backend(capsys):
    assert bench_run.main(["--workload", "ec83_write", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "needs 1 TPU" in err


def test_window_arithmetic():
    w = window.Window(100.0, 7.0, 3.0)
    assert w.n_slices == 2
    mib = 1 << 20
    for t, lat in ((100.5, 0.1), (101.0, 0.2), (103.5, 0.3), (106.5, 0.4)):
        w.op_ended(t, lat, 3 * mib, True)
    w.op_ended(102.0, 9.0, 3 * mib, False)      # failed: no bytes, no latency
    w.op_ended(107.5, 9.0, 3 * mib, True)       # after the window: nothing
    assert len(w.ops) == 5 and len(w.acked) == 4
    assert w.throughput_MiB_s() == pytest.approx(12 / 7)
    assert w.slice_rates_MiB_s() == [2.0, 1.0]  # the part-slice is left out
    assert w.latency_ms(50) == pytest.approx(200.0)
    assert w.latency_ms(95) == pytest.approx(400.0)
    assert window.percentile(range(1, 101), 95) == 95.0
    w.readings = [(100.0, 0), (103.0, 6 * mib), (106.0, 9 * mib),
                  (107.0, 9 * mib)]
    assert w.counter_rate_MiB_s() == pytest.approx(9 / 7)
    assert w.counter_slice_rates_MiB_s() == [2.0, 1.0]
    w.t_done = 104.0
    assert w.counter_rate_MiB_s() == pytest.approx(9 / 4)
    assert w.counter_slice_rates_MiB_s() == [2.0]


def test_reduce_on_a_hand_made_trace():
    trace = {"devices": {"/device:TPU:0": [
        ("jit_k/op.1", 10.000, 0.002), ("jit_k/op.2", 10.001, 0.002),
        ("jit_other/op", 10.500, 0.001)]}, "planes": {}}
    run = {"trace_t0": 10.0, "trace_t1": 11.0,
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    assert reduce.busy_seconds(trace, 10.0, 11.0) == pytest.approx(0.004)
    assert reduce.idle_pct(trace, run) == pytest.approx(99.6)
    assert reduce.kernel_seconds(trace, r"^jit_k/", 10.0, 11.0) == \
        pytest.approx(0.004)
    assert reduce.device_ops(trace, 10.0, 11.0)[0][0] == "jit_k/op.1"
    spans = [
        {"name": "do_op", "start_mono": 10.1, "end_mono": 10.9, "tags": {}},
        {"name": "store_commit", "start_mono": 10.2, "end_mono": 10.6,
         "tags": {"stage": "store"}}]
    gaps = dict(reduce.idle_gaps(trace, spans, 10.0, 11.0))
    assert gaps["store_commit"] == pytest.approx(0.4, abs=0.003)
    assert gaps["do_op"] == pytest.approx(0.4, abs=0.003)
    assert gaps["no_span_open"] == pytest.approx(0.2, abs=0.006)
    assert reduce.ms_per_op(spans, {"acked_ops": 4}, stage="store") == \
        pytest.approx(100.0)
    # RS(8,3) on one 512 KiB-per-shard object: bound by bytes, 7.04 us
    ops, nbytes = reduce.gf_matmul_cost(8, 3, 512 << 10)
    assert (ops, nbytes) == (2.0 * 24 * 64 * (512 << 10), 11.0 * (512 << 10))
    pct = reduce.roofline_pct(trace, run, pattern=r"^jit_k/",
                              products=[(8, 3, 512 << 10)])
    assert pct == pytest.approx(100 * (nbytes / 819e9) / 0.004)
    with pytest.raises(KeyError):
        reduce.load_peaks("no such chip")


def test_reference_encode_equals_the_programs_host_encode():
    import numpy as np

    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil

    ec = registry.factory("jax", {"plugin": "jax", "technique": "cauchy",
                                  "k": "8", "m": "3"})
    ec.device_min_bytes = 1 << 62
    blob = np.random.default_rng(5).integers(
        0, 256, 256 << 10, dtype=np.uint8).tobytes()
    sinfo = ecutil.StripeInfo(8, ec.get_chunk_size(4096 * 8) * 8)
    want = ecutil.encode(sinfo, ec, blob)
    got = reference.ec_shards(blob, 8, 3, 4096)
    assert [want[i].tobytes() for i in range(11)] == got


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_resolves_to_configuration_traffic_reference_and_readers(cell):
    from harness import generator, verify

    spec = bench_run.load_cell(cell)
    loop = spec["traffic"]["loop"]
    assert loop is None or loop["op"] in ("write_full", "read")
    assert set(spec["traffic"].get("fault") or {}) <= {"stop_osd", "out"}
    assert generator.Traffic.name(
        type("T", (), {"p": spec["traffic"]}), 3).endswith("3")
    ref = verify.load_reference(spec["config"].get("reference"))
    assert callable(ref.expected_copies)
    with open(ref.__file__) as f:
        assert "ceph_tpu" not in f.read()       # nothing of the program
    assert spec["per_layer"] and len(spec["end_to_end"]) >= 2
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(
            spec["metrics_dir"], m["name"] + ".py")), m["name"]


def test_every_cell_resolves_to_files_and_every_name_is_allowed():
    bench = _bench()
    assert "rep3_write" not in json.dumps(bench).replace("rep3_write_4k", "")
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "ec83_write_4chip"]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for entry in (bench["configs"] + bench["workloads"]
                  + bench["end_to_end"] + bench["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        assert UNIT.match(entry.get("unit", "s")), entry
        assert len(entry.get("why", "x")) <= 200
        assert set(entry.get("workloads", [])) <= cells
    for cell in bench["workloads"]:
        spec = bench_run.load_cell(cell["name"])     # config + traffic files
        assert spec["config"]["name"] == cell["config"]
        assert any(m["name"] != "setup_s" for m in spec["end_to_end"])
        for m in spec["per_layer"]:
            mod = bench_run.load_layer_metric(spec["metrics_dir"], m["name"])
            assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
                m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
            assert m["moves"] in e2e
            assert m["moves"] in {x["name"] for x in spec["end_to_end"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH,
               "layer_metrics")) if f.endswith(".py")}
    assert on_disk == {m["name"] for m in bench["per_layer"]}
