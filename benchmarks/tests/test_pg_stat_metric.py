"""``pg_stat_objects_stated_per_s``: the reader on hand-made counters,
as ``test_loop_metrics.py`` gives them.  It reads the growth of
``osd.pg_stat_objects_stated`` over the window's seconds, and nothing
where the program keeps no such counter (a report that scans every
object counts none).  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import pytest
from test_bench_harness import _bench  # sets the path first

import run as bench_run
from harness import window

NAME = "pg_stat_objects_stated_per_s"
CELLS = ["ec83_write", "rep3_write_4k", "ec83_write_4chip",
         "ec83_degraded_read", "ec42_rbd_randwrite_4k"]
RUN = {"window": window.Window(0.0, 5.0, 1.0), "acked_ops": 400}


def _reader():
    spec = bench_run.load_cell(CELLS[0])
    return bench_run.load_layer_metric(spec["metrics_dir"], NAME)


def test_the_entry_is_the_issues():
    entry = next(m for m in _bench()["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "1/s", "better": "lower",
        "source": "program_counter", "layer": "loop",
        "moves": "throughput_MiB_s", "workloads": CELLS}
    mod = _reader()
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    for cell in ("ec83_recovery", "clay8411_recovery"):
        assert NAME not in [m["name"] for m in
                            bench_run.load_cell(cell)["per_layer"]]


@pytest.mark.parametrize("counters", [
    {},
    {"osd.op_w": 400, "msgr.loop_callbacks": 9000},
])
def test_nothing_to_read_without_the_counter(counters):
    assert _reader().compute([], counters, None, RUN) is None


@pytest.mark.parametrize("stated, want", [(750, 150.0), (0, 0.0)])
def test_the_counters_growth_over_the_window(stated, want):
    counters = {"osd.pg_stat_objects_stated": stated,
                "osd.pg_stat_collections_listed": 3, "osd.op_w": 400}
    assert _reader().compute([], counters, None, RUN) == pytest.approx(want)
