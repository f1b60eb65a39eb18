"""The span-tree readers (PR 25), rehearsed tiny on the CPU backend:
``harness/spantree.py`` on a hand-made span list, every new reader on the
traced rehearsal of its cells, and the three sums that have to close.
Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import pytest
from test_bench_harness import _bench, _run, _tiny

import run as bench_run
from harness import reduce, spantree

FIRST_NEW = "store_exec_wait_ms"


def _new_metrics() -> list[dict]:
    per_layer = _bench()["per_layer"]
    names = [m["name"] for m in per_layer]
    return per_layer[names.index(FIRST_NEW):]


def _span(name, sid, parent, t0, t1, **tags):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "trace_id": 1, "start_mono": t0, "end_mono": t1, "tags": tags}


def test_spantree_on_a_hand_made_span_list():
    spans = [
        _span("store_commit", 1, None, 10.0, 10.100, stage="store"),
        _span("store_exec_wait", 2, 1, 10.010, 10.040),     # overlapping
        _span("store_txn", 3, 1, 10.030, 10.060),           # children
        _span("store_commit", 4, None, 11.0, 11.050, stage="store"),
        _span("store_txn", 5, 4, 11.040, 11.070),   # outlives its parent
        _span("store_txn", 6, 99, 12.0, 12.010),    # an orphan
        _span("msg_send", 7, 1, 10.0, 10.004, lock_wait_ms=1.5),
        _span("msg_send", 8, 1, 10.1, 10.102),      # no tag: left out
    ]
    run = {"acked_ops": 2}
    kids = spantree.children(spans, run)
    assert sorted(kids) == [1, 4, 99] and len(kids[1]) == 4
    # 100 ms less the union [10, 10.004] + [10.010, 10.060]; the send
    # that starts at the parent's end covers nothing
    assert spantree.self_seconds(spans[0], kids[1]) == pytest.approx(0.046)
    assert spantree.self_seconds(spans[3], kids[4]) == pytest.approx(0.040)
    assert spantree.mean_self_ms(spans, run, "store_commit") == \
        pytest.approx(43.0)
    assert spantree.mean_ms(spans, "store_txn") == pytest.approx(70 / 3)
    assert spantree.mean_ms(spans, "store_txn", per="store_commit") == \
        pytest.approx(35.0)             # the orphan's time counts too
    assert spantree.mean_ms(spans, "store_exec_wait", per="store_commit") \
        == pytest.approx(15.0)
    assert spantree.ms_per_op(spans, run, "msg_send") == pytest.approx(3.0)
    assert spantree.ms_per_op(spans, run, "msg_send", tag="lock_wait_ms") \
        == pytest.approx(0.75)
    for absent in (spantree.mean_ms(spans, "pg_reserve"),
                   spantree.mean_ms(spans, "store_txn", per="recover_pg"),
                   spantree.mean_self_ms(spans, run, "recover_object"),
                   spantree.ms_per_op(spans, run, "msg_send", tag="write_ms"),
                   spantree.ms_per_op(spans, {}, "msg_send"),
                   spantree.critical_path_ms(spans, {}, "net")):
        assert absent is None


def test_spantree_in_flight_and_the_critical_path_of_one_op():
    from harness import window

    w = window.Window(100.0, 10.0, 3.0)
    spans = [_span("recover_object", 1, None, 99.0, 102.0),
             _span("recover_object", 2, None, 101.0, 105.0),
             _span("recover_object", 3, None, 104.0, 109.0)]
    assert spantree.in_flight(spans, {"window": w}, "recover_object") == \
        pytest.approx((2 + 4 + 5) / 10)
    w.t_done = 105.0                    # the work ended here
    assert spantree.in_flight(spans, {"window": w}, "recover_object") == \
        pytest.approx((2 + 4 + 1) / 5)

    def op(name, sid, parent, t0, ms, **tags):
        return {**_span(name, sid, parent, t0, t0 + ms / 1e3, **tags),
                "start": t0, "duration_ms": ms, "daemon": "d"}
    trace = [op("client_op", 1, None, 100.0, 100.0),
             op("msg_send", 2, 1, 100.0, 5.0, stage="net"),
             op("do_op", 3, 1, 100.006, 90.0),
             op("store_commit", 4, 3, 100.010, 40.0, stage="store"),
             op("ec_sub_write", 5, 3, 100.010, 80.0, stage="net"),
             op("msg_send", 6, 1, 100.097, 2.0, stage="net")]
    run = {}
    got = {st: spantree.critical_path_ms(trace, run, st) for st in (
        "net", "queue", "device", "store", "other", "client_op")}
    assert got["client_op"] == pytest.approx(100.0)
    assert sum(v for st, v in got.items() if st != "client_op") == \
        pytest.approx(100.0, rel=0.05)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """Every cell once, traced and tiny: its last line's metrics and the
    spans its readers were given."""
    out = {}
    for cell in (w["name"] for w in _bench()["workloads"]):
        seen = {}
        real = reduce.spans_in

        def spans_in(spans, t0, t1, _seen=seen):
            _seen["spans"] = real(spans, t0, t1)
            return _seen["spans"]

        reduce.spans_in = spans_in
        try:
            res = _run(_tiny(cell), tmp_path_factory.mktemp(cell),
                       trace=True, seconds=4.0)
        finally:
            reduce.spans_in = real
        assert res["correct"], cell
        out[cell] = {"metrics": res["metrics"], "spans": seen["spans"]}
    return out


@pytest.mark.parametrize("metric", _new_metrics(), ids=lambda m: m["name"])
def test_new_reader_reads_its_cells_and_nothing_where_spans_are_absent(
        metric, rehearsal):
    for cell in metric["workloads"]:
        value, unit = rehearsal[cell]["metrics"][metric["name"]]
        assert unit == metric["unit"] and value >= 0, (cell, value)
    mod = bench_run.load_layer_metric(
        bench_run.load_cell("ec83_write")["metrics_dir"], metric["name"])
    from harness import window

    empty = {"acked_ops": 5, "window": window.Window(0.0, 4.0, 1.0)}
    assert mod.compute([], {}, None, empty) is None


@pytest.mark.parametrize("cell", ["ec83_write", "rep3_write_4k"])
def test_store_phases_and_critical_path_close_on_the_rehearsal(
        cell, rehearsal):
    got = {k: v for k, (v, _unit) in rehearsal[cell]["metrics"].items()}
    spans = rehearsal[cell]["spans"]
    commit = spantree.mean_ms(spans, "store_commit")
    assert (got["store_exec_wait_ms"] + got["store_txn_ms"]
            + got["store_resume_wait_ms"]) == pytest.approx(commit, rel=0.02)
    op_ms = spantree.critical_path_ms(spans, {}, "client_op")
    assert sum(got[f"op_critical_path_ms.{st}"] for st in (
        "net", "queue", "device", "store", "other")) == \
        pytest.approx(op_ms, rel=0.05)
    assert got["op_critical_path_ms.store"] > 0
    assert got["msg_send_lock_wait_ms_per_op"] <= got["msg_send_ms_per_op"]
    assert got["msg_send_ms_per_op"] < got["net_ms_per_op"]
    if cell == "ec83_write":
        assert 0 < got["encode_batch_wait_ms_per_op"] \
            <= got["ec_path_ms_per_op"]
    else:
        assert "encode_batch_wait_ms_per_op" not in got


def test_recovery_children_close_on_the_rehearsal(rehearsal):
    got = {k: v for k, (v, _unit) in
           rehearsal["ec83_recovery"]["metrics"].items()}
    obj = spantree.mean_ms(rehearsal["ec83_recovery"]["spans"],
                           "recover_object")
    assert 0 < (got["recovery_read_ms"] + got["recovery_decode_ms"]
                + got["recovery_push_ms"]) <= obj
    assert got["recovery_failed_ops"] == 0
    assert got["recovery_ops_in_flight"] > 0
    assert got["recovery_reserve_wait_ms"] >= 0
