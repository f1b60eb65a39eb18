"""The LRC cell, rehearsed tiny on the CPU backend with its own cut: the
shared ``test_bench_harness._cut`` makes an EC pool k=2 m=1, which is no
longer the code the cell exists for, so this keeps the real profile
(k=4 m=2 l=3, 8 chunks, 4 KiB stripe unit) and the 12 OSDs, and cuts the
objects to 64 KiB and 24 of them over 8 PGs.  Then the two new readers on
hand-made spans and traces, and on a program that does not count what
they read.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import copy
import json

import pytest
from test_bench_harness import _bench, _run  # sets the path first

import run as bench_run  # noqa: I001
from harness import reduce

CELL = "lrc423_recovery"
NEW = ("recovery_local_repair_share_pct", "local_repair_roofline")


def _tiny_lrc() -> dict:
    spec = copy.deepcopy(bench_run.load_cell(CELL))
    spec["config"]["pool"]["pg_num"] = 8
    spec["traffic"].update(
        object_bytes=64 << 10, distinct_payloads=4, in_flight=4,
        warmup_ops=4, verify_sample=16, slice_seconds=0.5,
        prefill_objects=24, trace={"start_s": 0.2, "seconds": 2.0})
    return spec


def test_the_cell_and_its_readers_are_declared():
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lrc423_12osd", "osd_out_recovery_512", 1)
    spec = bench_run.load_cell(CELL)
    pool = spec["config"]["pool"]
    assert (pool["plugin"], pool["k"], pool["m"], pool["stripe_unit"],
            pool["profile"], pool["pg_num"]) == (
        "lrc", 4, 2, 4096, {"l": "3"}, 128)
    assert spec["config"]["osds"] == 12
    assert spec["config"]["reference"] == "lrc423"
    assert spec["config"]["guarantees"]["copies_compared"] == 8
    p = spec["traffic"]
    old = bench_run.load_cell("clay8411_recovery")["traffic"]
    assert {k for k in old if old[k] != p[k]} == {"source",
                                                  "prefill_objects"}
    assert p["prefill_objects"] == 512 and p["warm_matrices"] == []
    mine = {m["name"] for m in spec["per_layer"]}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and \
                m["moves"] == "recovery_MiB_s"
    assert set(NEW) <= mine
    assert [m["name"] for m in spec["end_to_end"]] == [
        "recovery_MiB_s", "setup_s"]


def test_lrc_recovery_tiny(tmp_path, capsys, monkeypatch):
    seen, spans_in = {}, reduce.spans_in
    monkeypatch.setattr(reduce, "spans_in", lambda *a: seen.setdefault(
        "spans", spans_in(*a)))     # what the readers are given
    spec = _tiny_lrc()
    out = _run(spec, tmp_path, trace=True, seconds=5.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    win = next(ln for ln in lines if ln["line"] == "window")
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    got = out["compared"]
    assert got["stored_differ"] == {"value": 0, "max": 0}
    assert got["read_back_differ"] == {"value": 0, "max": 0}
    assert got["stored_rebuilt"]["value"] >= 1
    assert got["device_launches"]["value"] >= 1
    assert all(x["value"] == 0 for k, x in got.items()
               if k.startswith("must_be_0."))
    counters = win["counters"]
    assert counters["osd.recovery_local_repairs"] == \
        counters["osd.recovery_rebuilt_shards"] > 0
    metrics = {k: v for k, (v, _unit) in out["metrics"].items()}
    # no peaks for the CPU: the roofline's reader has nothing to read;
    # and a recovery this small may end inside its first slice
    missing = {m["name"] for m in spec["per_layer"]} - set(metrics)
    assert "local_repair_roofline" in missing
    assert missing <= {"local_repair_roofline", "recovery_slice_median_MiB_s"}
    assert metrics["recovery_local_repair_share_pct"] == 100
    assert metrics["recovery_read_bytes_per_rebuilt_byte"] == 3.0
    assert metrics["compiles_in_window.recovery"] == 0
    assert metrics["decode_lanes_per_launch"] >= 1
    # the launches and the objects' decodes say what they moved
    launches = [s["tags"] for s in seen["spans"] if s["name"] ==
                "xla_launch" and s["tags"].get("kind") == "local_repair"]
    assert launches and all(t["helper_bytes"] == 3 * t["rebuilt_bytes"]
                            for t in launches)
    reads = [s["tags"] for s in seen["spans"]
             if s["name"] == "recovery_read" and s["tags"].get("helpers")]
    assert reads and {t["helpers"] for t in reads} == {3}
    # under a hand-made device trace, one op of 0.1 ms in every launch:
    # a share of the HBM peak, well under the whole
    ls = [s for s in seen["spans"] if s["name"] == "xla_launch"
          and s["tags"].get("kind") == "local_repair"]
    trace = {"devices": {"/device:TPU:0": [
        ("jit_hand_made/fusion", s["start_mono"], 1e-4) for s in ls]},
        "planes": {}}
    run = {"trace_t0": min(s["start_mono"] for s in ls) - 1,
           "trace_t1": max(s["end_mono"] for s in ls) + 1,
           "peaks": reduce.load_peaks("TPU v5 lite")}
    reader = bench_run.load_layer_metric(spec["metrics_dir"],
                                         "local_repair_roofline")
    share = reader.compute(seen["spans"], {}, trace, run)
    assert share == pytest.approx(100 * sum(
        t["helper_bytes"] + t["rebuilt_bytes"] for t in launches)
        / 819e9 / (1e-4 * len(ls)))
    assert 0 < share < 100


def test_local_repair_roofline_on_a_hand_made_trace():
    spec = bench_run.load_cell(CELL)
    reader = bench_run.load_layer_metric(spec["metrics_dir"],
                                         "local_repair_roofline")
    trace = {"devices": {"/device:TPU:0": [
        ("jit_gf_bitmatmul/fusion", 10.10, 0.0004),
        ("jit_gf_bitmatmul/fusion.1", 10.12, 0.0006),
        ("jit_gf_bitmatmul/fusion", 10.40, 0.0010),     # another kind's
        ("jit_bench_device_probe/add", 10.50, 0.004)]}, "planes": {}}

    def launch(t0, t1, kind, **tags):
        return {"name": "xla_launch", "start_mono": t0, "end_mono": t1,
                "tags": {"kind": kind, **tags}}

    spans = [launch(10.09, 10.20, "local_repair", helper_bytes=3 << 20,
                    rebuilt_bytes=1 << 20, objects=1, lost_node=4),
             launch(10.39, 10.45, "clay_repair", helper_bytes=1 << 22,
                    rebuilt_bytes=1 << 19),
             launch(10.95, 11.05, "local_repair", helper_bytes=3 << 20,
                    rebuilt_bytes=1 << 20)]     # ends after the trace
    run = {"trace_t0": 10.0, "trace_t1": 11.0,
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    want = 100 * (4 << 20) / 819e9 / 0.001
    assert reader.compute(spans, {}, trace, run) == pytest.approx(want)
    assert reader.compute([], {}, trace, run) is None
    assert reader.compute(spans[1:2], {}, trace, run) is None
    assert reader.compute(spans, {}, None, run) is None
    assert reader.compute(spans, {}, trace, {**run, "peaks": None}) is None


def test_the_local_repair_share_counts_shards():
    reader = bench_run.load_layer_metric(
        bench_run.load_cell(CELL)["metrics_dir"],
        "recovery_local_repair_share_pct")
    assert reader.compute([], {"osd.recovery_rebuilt_shards": 8,
                               "osd.recovery_local_repairs": 6},
                          None, {}) == 75.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent commit counts no rebuilt shard and tags no local
    repair."""
    from harness import window

    reader = bench_run.load_layer_metric(
        bench_run.load_cell(CELL)["metrics_dir"], name)
    run = {"acked_ops": 0, "window": window.Window(0.0, 4.0, 1.0),
           "trace_t0": 0.0, "trace_t1": 1.0,
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    spans = [{"name": "xla_launch", "start_mono": 0.3, "end_mono": 0.4,
              "tags": {"kind": "decode_batch", "w": 4096, "b_real": 1}}]
    trace = {"devices": {"/device:TPU:0": [("jit_gf_bitmatmul/f", 0.31,
                                            0.001)]}, "planes": {}}
    counters = {"osd.recovery_decode_bytes": 1 << 20, "osd.recovery_ops": 2,
                "osd.recovery_read_bytes": 7 << 20}
    assert reader.compute(spans, counters, trace, run) is None
