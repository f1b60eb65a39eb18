"""``store_read_ms`` (PR 35): the reader on hand-made spans.  It reads the
``read_ms`` tag of the ``store_read`` spans, and nothing where the program
files none (the parent commit).  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import pytest
from test_bench_harness import _bench  # sets the path first

import run as bench_run

NAME = "store_read_ms"


def _reader():
    spec = bench_run.load_cell("ec83_degraded_read")
    return bench_run.load_layer_metric(spec["metrics_dir"], NAME)


def _span(name, start, end, **tags):
    return {"name": name, "span_id": id(tags), "parent_id": 1,
            "trace_id": 7, "start_mono": start, "end_mono": end,
            "tags": tags}


def test_the_entry_is_the_issues_and_the_last():
    entry = _bench()["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "store",
        "moves": "throughput_MiB_s", "workloads": ["ec83_degraded_read"]}
    mod = _reader()
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    spec = bench_run.load_cell("ec83_degraded_read")
    assert NAME in [m["name"] for m in spec["per_layer"]]
    for other in ("ec83_write", "ec83_recovery", "clay8411_recovery"):
        assert NAME not in [m["name"] for m in
                            bench_run.load_cell(other)["per_layer"]]


@pytest.mark.parametrize("spans", [
    [],
    [_span("ec_sub_read", 0.0, 0.08, stage="net"),
     _span("store_commit", 0.0, 0.01, stage="store"),
     _span("store_txn", 0.001, 0.009, bytes=524288)],
])
def test_nothing_to_read_without_the_span(spans):
    assert _reader().compute(spans, {}, None, {"acked_ops": 5}) is None


def test_the_mean_of_read_ms_per_shard_read():
    spans = [
        _span("ec_sub_read", 0.0, 0.08, stage="net"),
        _span("store_read", 0.010, 0.0135, stage="store", bytes=524288,
              read_ms=1.5, copies=0),
        _span("store_read", 0.020, 0.0215, stage="store", bytes=524288,
              read_ms=0.5, copies=0),
        _span("store_read", 0.030, 0.0345, stage="store", bytes=131072,
              read_ms=4.0, copies=2),
    ]
    assert _reader().compute(spans, {}, None, {}) == pytest.approx(2.0)
