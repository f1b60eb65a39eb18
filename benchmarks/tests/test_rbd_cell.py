"""The cell ``ec42_rbd_randwrite_4k`` (PR 37): an RBD image's 4 KiB
random writes on a ``jerasure reed_sol_van`` (4, 2) overwrite pool,
rehearsed tiny on the CPU backend with its own cut (k=4 m=2 kept: the
stripe of 4 x 4 KiB is what puts a lone encode under the encode
service's 32 KiB and two in one window over it), every reader the cell
brings fed by that rehearsal, the three write controls, and the pool
held to another code's reference.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import copy
import json

import pytest
from test_bench_harness import _bench, _cut, _run  # sets the path first

import faults  # noqa: I001
import run as bench_run
from harness import reduce, verify

CELL = "ec42_rbd_randwrite_4k"
NEW = ("store_write_amp_bytes_per_byte", "store_read_disk_bytes_per_op",
       "ec_rmw_read_ms_per_op", "ec_extent_cache_hit_pct",
       "encode_device_share_pct.rmw", "gf_bitmatmul_roofline.rmw_encode")
ROOFLINE = "gf_bitmatmul_roofline.rmw_encode"


def _tiny_rbd() -> dict:
    """``_cut`` makes every EC pool (2, 1); this cell is its (4, 2)
    stripe, so k and m are put back (6 OSDs hold 6 shards), with 8 in
    flight so that some windows gather two stripes."""
    spec = _cut(copy.deepcopy(bench_run.load_cell(CELL)))
    spec["config"]["pool"].update(k=4, m=2)
    spec["traffic"].update(in_flight=8, warmup_ops=8)
    return spec


def test_the_cell_is_the_issues_letter_for_letter():
    bench, spec = _bench(), bench_run.load_cell(CELL)
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 6
    assert spec["cell"] == {**spec["cell"], "config": "ec42_rbd_12osd",
                            "traffic": "rbd_randwrite_4k", "chips": 1}
    cfg = spec["config"]
    assert cfg["pool"] == {
        "type": "erasure", "plugin": "jerasure", "technique": "reed_sol_van",
        "k": 4, "m": 2, "stripe_unit": 4096, "failure_domain": "host",
        "pg_num": 128}
    assert (cfg["reference"], cfg["osds"], cfg["hosts"], cfg["mons"],
            cfg["processes"], cfg["chips"]) == ("rs_van42", 12, 12, 1, 1, 1)
    ec83 = bench_run.load_cell("ec83_write")["config"]
    assert (cfg["store"], cfg["store_free_bytes_min"]) == (
        ec83["store"], ec83["store_free_bytes_min"])
    assert cfg["guarantees"]["copies_compared"] == 6
    assert set(cfg["reduced"]) == {"osds", "hosts", "processes"}
    p = spec["traffic"]
    assert p["loop"] == {"op": "write", "io_bytes": 4096,
                         "offsets": "uniform"}
    assert (p["in_flight"], p["prefill_objects"], p["object_bytes"],
            p["warmup_ops"], p["verify_sample"], p["op_timeout_s"],
            p["fault"], p["trace"], p["warm_matrices"]) == (
        32, 256, 4 << 20, 64, 32, 30, None, {"start_s": 12, "seconds": 6},
        ["encode"])
    assert [m["name"] for m in spec["end_to_end"]] == [
        "throughput_MiB_s", "setup_s"]
    mine = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) <= mine
    assert not mine & {"net_ms_per_op", "queue_wait_ms_per_op",
                       "gf_bitmatmul_roofline.encode"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and \
                m["moves"] == "throughput_MiB_s"
    # what was there is as it was: the cell is the last name of a list
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) > 1


def test_rbd_cell_tiny_and_every_reader_it_brings(tmp_path, capsys,
                                                  monkeypatch):
    seen, spans_in = {}, reduce.spans_in
    monkeypatch.setattr(reduce, "spans_in", lambda *a: seen.setdefault(
        "spans", spans_in(*a)))     # what the readers are given
    spec = _tiny_rbd()
    out = _run(spec, tmp_path, trace=True, seconds=4.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    win = next(ln for ln in lines if ln["line"] == "window")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 20 and out["failed"] == 0
    got = out["compared"]
    assert got["device_launches"]["value"] >= 1
    assert all(x["value"] == 0 for k, x in got.items()
               if k.startswith("must_be_0."))
    patched = got["patched_objects_compared"]["value"]
    assert 12 <= patched <= 24
    assert got["objects_compared"]["value"] == patched + 8
    assert win["verify"]["stored"]["equal"] == 6 * 16   # k+m copies each
    assert win["uncertain"] == 0
    c = win["counters"]
    acked = win["ops_acked"]
    metrics = {k: v for k, (v, _unit) in out["metrics"].items()}
    # every reader of the cell but the chip's share reads the rehearsal
    assert {m["name"] for m in spec["per_layer"]} - set(metrics) == {ROOFLINE}
    # every write of the window is a read-modify-write of one stripe
    # (those the warm-up left in flight make the counts differ a little)
    assert abs(c["osd.ec_rmw_ops"] - acked) <= 8
    assert metrics["ec_extent_cache_hit_pct"] == pytest.approx(
        100 * c.get("osd.ec_extent_cache_hit", 0) / c["osd.ec_rmw_ops"])
    assert 0 < metrics["ec_extent_cache_hit_pct"] < 60
    assert c["osd.ec_rmw_read_bytes"] == 16384 * (
        c["osd.ec_rmw_ops"] - c["osd.ec_extent_cache_hit"])
    # a miss reads one 4 KiB chunk of each of k = 4 shards, from the
    # block file where no earlier write made it a piece: at most 16 KiB
    assert 0 < metrics["store_read_disk_bytes_per_op"] <= 16384
    assert metrics["store_read_disk_bytes_per_op"] == pytest.approx(
        c["osd.store_read_disk_bytes"] / acked)
    # six shard commits an op, none a fold in four seconds, each some
    # KB of kv: far from the parent's thousand
    assert c["osd.store_folds"] == 0 and c["osd.store_block_write_bytes"] == 0
    assert metrics["store_write_amp_bytes_per_byte"] == pytest.approx(
        c["osd.store_kv_write_bytes"] / (acked * 4096))
    assert 6 < metrics["store_write_amp_bytes_per_byte"] < 48
    # the launch decision is the group's: lone stripes on the host, two
    # or more in a window launched, none a fallback
    launched, host = c["encode.coalesced"], c["encode.host_requests"]
    assert launched >= 2 * c["encode.single_dispatches"] > 0 and host > 0
    assert abs(launched + host - acked) <= 8
    assert c.get("encode.fallbacks", 0) == 0
    assert metrics["encode_device_share_pct.rmw"] == pytest.approx(
        100 * launched / (launched + host))
    assert metrics["encode_ops_per_launch"] >= 2
    assert metrics["ec_rmw_read_ms_per_op"] > 0
    assert metrics["ec_sub_read_ms_per_op"] > 0
    assert metrics["ec_rmw_read_ms_per_op"] < metrics["client_op_p95_ms"] * 2
    assert metrics["compiles_in_window.write"] == 0
    # the span: a child of the op, stage net, tagged as the issue says
    rmw = [s for s in seen["spans"] if s["name"] == "ec_rmw_read"]
    assert rmw and all(
        s["tags"]["stage"] == "net" and s["tags"]["stripes"] == 1
        and s["tags"]["bytes"] == (0 if s["tags"]["cache_hit"] else 16384)
        for s in rmw)
    txn = [s["tags"] for s in seen["spans"] if s["name"] == "store_txn"]
    assert txn and all({"block_bytes", "kv_bytes", "folded"} <= set(t)
                       for t in txn)
    reads = [s["tags"] for s in seen["spans"] if s["name"] == "store_read"]
    assert reads and all(t["disk_bytes"] in (0, 4096) for t in reads)
    # the chip's share, from the rehearsal's own launches under a
    # hand-made device trace: real_bytes in, half as much out, at the peak
    launches = [s for s in seen["spans"] if s["name"] == "xla_launch"
                and s["tags"].get("kind") == "encode_single"]
    assert launches and all(s["tags"]["real_bytes"] >= 32768 for s in launches)
    trace = {"devices": {"/device:TPU:0": [
        ("jit_hand_made/fusion", s["start_mono"], 1e-4) for s in launches]},
        "planes": {}}
    run = {"trace_t0": min(s["start_mono"] for s in launches) - 1,
           "trace_t1": max(s["end_mono"] for s in launches) + 1,
           "config": spec["config"], "traffic": spec["traffic"],
           "peaks": reduce.load_peaks("TPU v5 lite")}
    share = bench_run.load_layer_metric(
        spec["metrics_dir"], ROOFLINE).compute(seen["spans"], c, trace, run)
    assert share == pytest.approx(100 * sum(
        s["tags"]["real_bytes"] for s in launches) * 1.5 / 819e9
        / (1e-4 * len(launches)))
    assert 0 < share < 1


@pytest.mark.parametrize("fault,caught_by", [
    ("unsent_write", "read_back_differ"),
    ("shifted_write", "read_back_differ"),
    ("flip_patched_parity", "stored_differ")])
def test_a_write_control_on_the_new_configuration_is_not_correct(
        fault, caught_by, tmp_path):
    """ONE faulty block of the image, or one flipped bit of a parity
    shard, is seen on the (4, 2) ``reed_sol_van`` pool too."""
    with faults.planted(fault):
        out = _run(_tiny_rbd(), tmp_path, trace=False, seconds=2.0)
    assert not out["correct"] and out["failed"] == 0
    outside = {k for k, x in out["compared"].items()
               if not verify.within({k: x})}
    assert {caught_by} <= outside <= {"read_back_differ", "stored_differ"}, \
        out["compared"]
    assert out["compared"][caught_by]["value"] == 1
    if fault == "flip_patched_parity":
        assert outside == {"stored_differ"}


def test_the_pool_held_to_the_cauchy_reference_is_not_correct(tmp_path):
    """``reed_sol_van``'s second parity is not cauchy's, and cauchy's
    first is not the xor: ``harness/reference.py`` reads
    ``stored_differ`` on every object, read-backs stay equal."""
    spec = _tiny_rbd()
    del spec["config"]["reference"]
    out = _run(spec, tmp_path, trace=False, seconds=2.0)
    assert not out["correct"] and out["failed"] == 0
    assert out["compared"]["stored_differ"]["value"] >= \
        out["compared"]["objects_compared"]["value"] >= 16
    assert out["compared"]["read_back_differ"]["value"] == 0


def test_rmw_roofline_on_a_hand_made_trace():
    spec = bench_run.load_cell(CELL)
    reader = bench_run.load_layer_metric(spec["metrics_dir"], ROOFLINE)
    trace = {"devices": {"/device:TPU:0": [
        ("jit_gf_bitmatmul/fusion", 10.10, 0.00004),
        ("jit_gf_bitmatmul/fusion.1", 10.12, 0.00006),
        ("jit_gf_bitmatmul/fusion", 10.40, 0.0010),     # outside a launch
        ("jit_bench_device_probe/add", 10.50, 0.004)]}, "planes": {}}

    def launch(t0, t1, kind, **tags):
        return {"name": "xla_launch", "start_mono": t0, "end_mono": t1,
                "tags": {"kind": kind, **tags}}

    spans = [launch(10.09, 10.20, "encode_single", real_bytes=3 * 16384),
             launch(10.39, 10.45, "decode_batch", real_bytes=1 << 22),
             launch(10.60, 10.65, "encode_single"),     # an untagged one
             launch(10.95, 11.05, "encode_single", real_bytes=16384)]
    run = {"trace_t0": 10.0, "trace_t1": 11.0, "config": spec["config"],
           "traffic": spec["traffic"],
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    want = 100 * (3 * 16384 * 6 / 4) / 819e9 / 0.0001
    assert reader.compute(spans, {}, trace, run) == pytest.approx(want)
    assert reader.compute([], {}, trace, run) is None
    assert reader.compute(spans[1:3], {}, trace, run) is None
    assert reader.compute(spans, {}, None, run) is None
    assert reader.compute(spans, {}, trace, {**run, "peaks": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_from_a_program_without_the_counters(name):
    """The parent commit counts no store byte and no read-modify-write,
    files no request under the service's 32 KiB, and tags no launch with
    what it carried."""
    from harness import window

    spec = bench_run.load_cell(CELL)
    reader = bench_run.load_layer_metric(spec["metrics_dir"], name)
    run = {"acked_ops": 1449, "window": window.Window(0.0, 4.0, 1.0),
           "trace_t0": 0.0, "trace_t1": 1.0, "config": spec["config"],
           "traffic": spec["traffic"],
           "peaks": {"int8_TOPs": 393, "HBM_GBs": 819}}
    spans = [{"name": "ec_sub_read", "start_mono": 0.1, "end_mono": 0.2,
              "tags": {"stage": "net"}, "span_id": 2, "parent_id": 1},
             {"name": "do_op", "start_mono": 0.1, "end_mono": 0.5,
              "tags": {}, "span_id": 1, "parent_id": None},
             {"name": "xla_launch", "start_mono": 0.3, "end_mono": 0.4,
              "tags": {"kind": "encode_single", "w": 4096, "b_real": 1}}]
    trace = {"devices": {"/device:TPU:0": [("jit_gf_bitmatmul/f", 0.31,
                                            0.001)]}, "planes": {}}
    counters = {"osd.op_w": 1449, "osd.ec_extent_cache_hit": 72,
                "osd.store_read_ops": 5796, "encode.coalesced": 0,
                "encode.single_dispatches": 0}
    assert reader.compute(spans, counters, trace, run) is None
