"""The CLAY pool's client cells, ``clay8411_degraded_read`` and
``clay8411_write``: their traffic files rehearsed tiny on the CPU
backend through the harness (the cells' own files cut to k=4 m=2 d=5
over 7 OSDs, ``test_bench_harness._cut``), and the two readers the read
cell brings on hand-made spans and traces.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import json

import pytest
from test_bench_harness import _bench, _run, _tiny  # sets the path first

import run as bench_run
from harness import reduce, window

READ, WRITE = "clay8411_degraded_read", "clay8411_write"
NEW = ("subchunk_decode_roofline", "read_subchunk_decode_share_pct")
PEAKS = {"int8_TOPs": 393, "HBM_GBs": 819}


def _names(cell: str) -> set[str]:
    return {m["name"] for m in bench_run.load_cell(cell)["per_layer"]}


def _hand_made_trace(launches: list[dict], op_s: float = 1e-4) -> dict:
    """One device op of ``op_s`` inside every launch."""
    return {"devices": {"/device:TPU:0": [
        ("jit_hand_made/fusion", s["start_mono"], op_s) for s in launches]},
        "planes": {}}


def test_the_cells_and_their_reader_lists_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert [w["name"] for w in bench["workloads"]][-2:] == [READ, WRITE]
    for cell, traffic, twin, twin_cell in (
            (READ, "rados_bench_rand_degraded_clay",
             "rados_bench_rand_degraded", "ec83_degraded_read"),
            (WRITE, "rados_bench_write_clay", "rados_bench_write",
             "ec83_write")):
        assert (cells[cell]["config"], cells[cell]["traffic"],
                cells[cell]["chips"]) == ("clay8411_13osd_client", traffic, 1)
        p = bench_run.load_cell(cell)["traffic"]
        old = bench_run.load_cell(twin_cell)["traffic"]
        assert cells[twin_cell]["traffic"] == twin
        # the scalar cell's parameters; the harness warms no shape
        assert {k for k in old if old[k] != p[k]} == {
            "source", "warm_matrices"} and p["warm_matrices"] == []
        assert [m["name"] for m in bench_run.load_cell(cell)["end_to_end"]] \
            == ["throughput_MiB_s", "setup_s"]
    # the client configuration is the recovery cell's pool and cluster
    client = bench_run.load_cell(READ)["config"]
    pool = bench_run.load_cell("clay8411_recovery")["config"]
    assert [c["name"] for c in bench["configs"]][-1] == client["name"]
    assert {k for k in pool if pool[k] != client.get(k)} == {
        "name", "source", "deployment", "guarantees", "assumed"}
    assert client["guarantees"]["copies_compared"] == 12
    assert _names(READ) - _names("ec83_degraded_read") == set(NEW)
    assert _names("ec83_degraded_read") - _names(READ) == {
        "gf_bitmatmul_roofline.read_decode"}
    assert _names(WRITE) - _names("ec83_write") == {
        "gf_bitmatmul_roofline.rmw_encode"}
    assert _names("ec83_write") - _names(WRITE) == {
        "gf_bitmatmul_roofline.encode"}
    assert [m["name"] for m in bench["per_layer"]][-2:] == list(NEW)
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [READ] and m["moves"] == "throughput_MiB_s"


def test_degraded_read_tiny_rebuilds_every_lost_chunk_on_the_device(
        tmp_path, capsys, monkeypatch):
    seen, spans_in = {}, reduce.spans_in
    monkeypatch.setattr(reduce, "spans_in", lambda *a: seen.setdefault(
        "spans", spans_in(*a)))     # what the readers are given
    spec = _tiny(READ)
    out = _run(spec, tmp_path, trace=True, seconds=3.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    win = next(ln for ln in lines if ln["line"] == "window")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert win["reads_compared"] == out["attempted"] and \
        win["reads_wrong"] == 0
    got = out["compared"]
    assert got["device_launches"]["value"] > 0
    assert all(x["value"] == 0 for k, x in got.items()
               if k.startswith("must_be_0."))
    assert got["stored_rebuilt"] == {"value": 0, "max": 0}
    c = win["counters"]
    assert c["osd.ec_read_subchunk_decodes"] >= 1
    assert c.get("osd.ec_read_host_decodes", 0) == 0
    metrics = {k: v for k, (v, _unit) in out["metrics"].items()}
    # no peaks for the CPU: the roofline's reader has nothing to read
    assert _names(READ) - set(metrics) == {"subchunk_decode_roofline"}
    assert metrics["read_subchunk_decode_share_pct"] == 100
    assert 0 < metrics["read_decode_share_pct"] < 100
    assert metrics["compiles_in_window.write"] == 0
    assert metrics["ec_decode_ms_per_op"] > 0
    # the op's span says which path rebuilt what
    decodes = [s["tags"] for s in seen["spans"] if s["name"] == "ec_decode"]
    assert {t["path"] for t in decodes} == {"device", "none"}
    obj = spec["traffic"]["object_bytes"] // spec["config"]["pool"]["k"]
    assert all(t["rebuilt_bytes"] == len(t["lost"]) * obj for t in decodes)
    # the chip's share, from the rehearsal's own launches under a
    # hand-made device trace: the fetched rows in, the rebuilt out
    launches = [s for s in seen["spans"] if s["name"] == "xla_launch"
                and s["tags"].get("kind") == "subchunk_decode"]
    assert launches and all(
        s["tags"]["helper_bytes"] == 4 * s["tags"]["rebuilt_bytes"]
        for s in launches)
    run = {"trace_t0": min(s["start_mono"] for s in launches) - 1,
           "trace_t1": max(s["end_mono"] for s in launches) + 1,
           "peaks": reduce.load_peaks("TPU v5 lite")}
    share = bench_run.load_layer_metric(
        spec["metrics_dir"], "subchunk_decode_roofline").compute(
            seen["spans"], c, _hand_made_trace(launches), run)
    assert share == pytest.approx(100 * sum(
        s["tags"]["helper_bytes"] + s["tags"]["rebuilt_bytes"]
        for s in launches) / 819e9 / (1e-4 * len(launches)))


def test_write_tiny_encodes_through_the_service(tmp_path, capsys,
                                                monkeypatch):
    seen, spans_in = {}, reduce.spans_in
    monkeypatch.setattr(reduce, "spans_in", lambda *a: seen.setdefault(
        "spans", spans_in(*a)))
    spec = _tiny(WRITE)
    out = _run(spec, tmp_path, trace=True, seconds=3.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    win = next(ln for ln in lines if ln["line"] == "window")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(x["value"] == 0 for k, x in out["compared"].items()
               if k.startswith("must_be_0."))
    assert win["verify"]["stored"]["differ"] == 0
    metrics = {k: v for k, (v, _unit) in out["metrics"].items()}
    assert _names(WRITE) - set(metrics) == {"gf_bitmatmul_roofline.rmw_encode"}
    assert metrics["encode_ops_per_launch"] >= 1
    assert metrics["compiles_in_window.write"] == 0
    # CLAY's encode launches are of kind encode_single and carry the k
    # data chunks' real bytes: the rmw_encode reader counts them
    launches = [s for s in seen["spans"] if s["name"] == "xla_launch"
                and s["tags"].get("kind") == "encode_single"]
    assert launches and all(
        s["tags"]["real_bytes"] % spec["traffic"]["object_bytes"] == 0
        for s in launches)
    run = {"trace_t0": min(s["start_mono"] for s in launches) - 1,
           "trace_t1": max(s["end_mono"] for s in launches) + 1,
           "config": spec["config"], "traffic": spec["traffic"],
           "peaks": reduce.load_peaks("TPU v5 lite")}
    share = bench_run.load_layer_metric(
        spec["metrics_dir"], "gf_bitmatmul_roofline.rmw_encode").compute(
            seen["spans"], win["counters"], _hand_made_trace(launches), run)
    assert share == pytest.approx(100 * sum(
        s["tags"]["real_bytes"] for s in launches) * 1.5 / 819e9
        / (1e-4 * len(launches)))


def test_decode_roofline_on_a_hand_made_trace():
    reader = bench_run.load_layer_metric(
        bench_run.load_cell(READ)["metrics_dir"], "subchunk_decode_roofline")
    trace = {"devices": {"/device:TPU:0": [
        ("jit_gf_bitmatmul/fusion", 10.10, 0.0004),
        ("jit_gf_bitmatmul/fusion.1", 10.12, 0.0006),
        ("jit_gf_bitmatmul/fusion", 10.40, 0.0010),     # another kind's
        ("jit_gf_bitmatmul/fusion", 10.60, 0.0020),     # an untagged one
        ("jit_bench_device_probe/add", 10.80, 0.004)]}, "planes": {}}

    def launch(t0, t1, kind, **tags):
        return {"name": "xla_launch", "start_mono": t0, "end_mono": t1,
                "tags": {"kind": kind, **tags}}

    spans = [launch(10.09, 10.20, "subchunk_decode", helper_bytes=8 << 19,
                    rebuilt_bytes=1 << 19),
             launch(10.39, 10.45, "encode_single", real_bytes=1 << 22),
             launch(10.59, 10.65, "subchunk_decode"),
             launch(10.95, 11.05, "subchunk_decode", helper_bytes=8 << 19,
                    rebuilt_bytes=1 << 19)]     # ends after the trace
    run = {"trace_t0": 10.0, "trace_t1": 11.0, "peaks": PEAKS}
    want = 100 * (9 << 19) / 819e9 / 0.001
    assert reader.compute(spans, {}, trace, run) == pytest.approx(want)
    assert reader.compute(spans[1:3], {}, trace, run) is None
    assert reader.compute([], {}, trace, run) is None
    assert reader.compute(spans, {}, None, run) is None
    assert reader.compute(spans, {}, trace, {**run, "peaks": None}) is None


@pytest.mark.parametrize("device,host,want", [
    (12, 0, 100.0), (3, 1, 75.0), (0, 4, 0.0), (0, 0, None)])
def test_decode_share_on_hand_made_counters(device, host, want):
    reader = bench_run.load_layer_metric(
        bench_run.load_cell(READ)["metrics_dir"],
        "read_subchunk_decode_share_pct")
    counters = {k: v for k, v in (("osd.ec_read_subchunk_decodes", device),
                                  ("osd.ec_read_host_decodes", host)) if v}
    assert reader.compute([], counters, None, {}) == want


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent commit counts no read decode and tags no such launch:
    its CLAY reads take the host loop, its launches are encodes."""
    reader = bench_run.load_layer_metric(
        bench_run.load_cell(READ)["metrics_dir"], name)
    run = {"acked_ops": 10, "window": window.Window(0.0, 4.0, 1.0),
           "trace_t0": 0.0, "trace_t1": 1.0, "peaks": PEAKS}
    spans = [{"name": "ec_decode", "start_mono": 0.1, "end_mono": 0.5,
              "tags": {"stage": "device", "shards": 8}, "span_id": 1,
              "parent_id": None},
             {"name": "xla_launch", "start_mono": 0.6, "end_mono": 0.7,
              "tags": {"kind": "encode_single", "w": 65536, "b_real": 1,
                       "real_bytes": 1 << 22}}]
    trace = {"devices": {"/device:TPU:0": [("jit_gf_bitmatmul/f", 0.61,
                                            0.001)]}, "planes": {}}
    counters = {"encode.coalesced": 4, "osd.op_r": 10}
    assert reader.compute(spans, counters, trace, run) is None
