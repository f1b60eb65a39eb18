"""The CLAY cell (PR 34), rehearsed tiny on the CPU backend: the cell's
own files cut to k=4 m=2 d=5 over 7 OSDs (``test_bench_harness._tiny``
cuts an EC pool to k=2 m=1, which no CLAY profile with ``d`` takes), the
planted fault and the wrong reference that must make a run not
``correct``, and the roofline's reader on a hand-made trace.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import copy
import json

import pytest
from test_bench_harness import _bench, _run  # sets the path first

import faults  # noqa: I001
import run as bench_run

CELL = "clay8411_recovery"
NEW = ("recovery_read_bytes_per_rebuilt_byte",
       "recovery_subchunk_repair_share_pct",
       "recovery_read_extents_per_object", "subchunk_repair_roofline")


def _tiny_clay() -> dict:
    """q=2 t=3: 8 sub-chunks of 512 B, 64 KiB objects of 4 stripes."""
    spec = copy.deepcopy(bench_run.load_cell(CELL))
    spec["config"]["osds"] = 7
    pool = spec["config"]["pool"]
    pool.update(k=4, m=2, pg_num=8, stripe_unit=4096,
                profile={"d": "5", "stripe_unit": "4096"})
    spec["traffic"].update(
        object_bytes=64 << 10, distinct_payloads=4, in_flight=4,
        prefill_objects=24, verify_sample=16, slice_seconds=0.5,
        trace={"start_s": 0.2, "seconds": 2.0})
    return spec


def test_the_cell_is_declared_as_the_issue_names_it():
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "clay8411_13osd", "osd_out_recovery_384", 1)
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    spec = bench_run.load_cell(CELL)
    pool = spec["config"]["pool"]
    assert (pool["plugin"], pool["k"], pool["m"], pool["stripe_unit"],
            pool["profile"]) == ("clay", 8, 4, 262144,
                                 {"d": "11", "stripe_unit": "262144"})
    assert "technique" not in pool and spec["config"]["osds"] == 13
    assert spec["config"]["reference"] == "clay8411"
    p, old = spec["traffic"], bench_run.load_cell("ec83_recovery")["traffic"]
    # osd_out_recovery's parameters, but three times the objects, the
    # harness warms no shape and the trace opens early
    assert {k for k in old if old[k] != p[k]} == {
        "source", "prefill_objects", "warm_matrices", "trace"}
    assert (p["prefill_objects"], p["warm_matrices"], p["trace"]) == (
        384, [], {"start_s": 3, "seconds": 6})
    mine = {m["name"] for m in spec["per_layer"]}
    theirs = {m["name"] for m in
              bench_run.load_cell("ec83_recovery")["per_layer"]}
    assert mine - theirs == set(NEW)
    assert theirs - mine == {"gf_bitmatmul_roofline.decode"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and \
                m["moves"] == "recovery_MiB_s"
    assert [m["name"] for m in spec["end_to_end"]] == [
        "recovery_MiB_s", "setup_s"]


def test_clay_recovery_tiny(tmp_path, capsys):
    out = _run(_tiny_clay(), tmp_path, trace=True, seconds=5.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    win = next(ln for ln in lines if ln["line"] == "window")
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    got = out["compared"]
    assert got["stored_differ"] == {"value": 0, "max": 0}
    assert got["read_back_differ"] == {"value": 0, "max": 0}
    assert got["stored_rebuilt"]["value"] >= 1
    assert got["device_launches"]["value"] >= 1
    assert got["counter_bytes"]["value"] <= got["counter_bytes"]["max"]
    assert all(x["value"] == 0 for k, x in got.items()
               if k.startswith("must_be_0."))
    counters = win["counters"]
    assert counters["osd.recovery_subchunk_repairs"] == out["attempted"]
    assert counters.get("osd.recovery_fullchunk_repairs", 0) == 0
    metrics = {k: v for k, (v, _unit) in out["metrics"].items()}
    # no peaks for the CPU: the roofline's reader has nothing to read
    assert {m["name"] for m in bench_run.load_cell(CELL)["per_layer"]} \
        - set(metrics) == {"subchunk_repair_roofline"}
    assert metrics["recovery_subchunk_repair_share_pct"] == 100
    # d helpers send 1/q of a chunk each: 5/2 chunks read a chunk rebuilt
    assert metrics["recovery_read_bytes_per_rebuilt_byte"] == 2.5
    # 5 helpers x 4 stripes x (1, 2 or 4 runs, by the lost node's row)
    # (and one whole read for a shard that only moved)
    assert 20 <= metrics["recovery_read_extents_per_object"] <= 81
    assert metrics["compiles_in_window.recovery"] == 0
    assert metrics["decode_lanes_per_launch"] >= 1
    assert metrics["recovery_decode_ms"] > 0


def test_a_flipped_byte_of_one_stored_shard_is_not_correct(tmp_path):
    with faults.planted("flip"):
        out = _run(_tiny_clay(), tmp_path, trace=False, seconds=3.0)
    assert not out["correct"] and out["failed"] == 0
    assert out["compared"]["stored_differ"] == {"value": 1, "max": 0}


def test_the_clay_pool_held_to_the_cauchy_reference_is_not_correct(
        tmp_path):
    """Its shards are not a scalar code's: ``harness/reference.py``
    reads ``stored_differ`` on every object, read-backs stay equal."""
    spec = _tiny_clay()
    del spec["config"]["reference"]
    out = _run(spec, tmp_path, trace=False, seconds=3.0)
    assert not out["correct"] and out["failed"] == 0
    assert out["compared"]["stored_differ"]["value"] >= \
        out["compared"]["objects_compared"]["value"] == 16
    assert out["compared"]["read_back_differ"]["value"] == 0


def test_repair_roofline_on_a_hand_made_trace():
    spec = bench_run.load_cell(CELL)
    reader = bench_run.load_layer_metric(spec["metrics_dir"],
                                         "subchunk_repair_roofline")
    trace = {"devices": {"/device:TPU:0": [
        ("jit_gf_bitmatmul/fusion", 10.10, 0.0004),
        ("jit_gf_bitmatmul/fusion.1", 10.12, 0.0006),
        ("jit_gf_bitmatmul/fusion", 10.40, 0.0010),     # outside a launch
        ("jit_bench_device_probe/add", 10.50, 0.004)]}, "planes": {}}

    def launch(t0, t1, kind, **tags):
        return {"name": "xla_launch", "start_mono": t0, "end_mono": t1,
                "tags": {"kind": kind, **tags}}

    spans = [launch(10.09, 10.20, "clay_repair", helper_bytes=11 << 17,
                    rebuilt_bytes=1 << 19, objects=1, lost_node=3),
             launch(10.39, 10.45, "decode_batch", helper_bytes=1 << 22,
                    rebuilt_bytes=1 << 19),
             launch(10.95, 11.05, "clay_repair", helper_bytes=11 << 17,
                    rebuilt_bytes=1 << 19)]     # ends after the trace
    run = {"trace_t0": 10.0, "trace_t1": 11.0, "config": spec["config"],
           "traffic": spec["traffic"],
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    want = 100 * ((11 << 17) + (1 << 19)) / 819e9 / 0.001
    assert reader.compute(spans, {}, trace, run) == pytest.approx(want)
    assert reader.compute([], {}, trace, run) is None
    assert reader.compute(spans[1:2], {}, trace, run) is None
    assert reader.compute(spans, {}, None, run) is None
    assert reader.compute(spans, {}, trace, {**run, "peaks": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent commit counts no helper read and tags no launch."""
    from harness import window

    reader = bench_run.load_layer_metric(
        bench_run.load_cell(CELL)["metrics_dir"], name)
    run = {"acked_ops": 0, "window": window.Window(0.0, 4.0, 1.0),
           "trace_t0": 0.0, "trace_t1": 1.0,
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    spans = [{"name": "recovery_read", "start_mono": 0.1, "end_mono": 0.2,
              "tags": {}, "span_id": 2, "parent_id": 1},
             {"name": "recover_object", "start_mono": 0.1, "end_mono": 0.5,
              "tags": {}, "span_id": 1, "parent_id": None},
             {"name": "xla_launch", "start_mono": 0.3, "end_mono": 0.4,
              "tags": {"kind": "decode_batch", "w": 4096, "b_real": 1}}]
    trace = {"devices": {"/device:TPU:0": [("jit_gf_bitmatmul/f", 0.31,
                                            0.001)]}, "planes": {}}
    counters = {"osd.recovery_decode_bytes": 1 << 20, "osd.recovery_ops": 2}
    assert reader.compute(spans, counters, trace, run) is None
