"""``pg_mapping_runs_per_op``: the reader on hand-made counters, as
``test_loop_metrics.py`` gives them, and on a tiny traced
``rep3_write_4k`` rehearsal.  It reads the growth of
``osd.pg_mapping_runs`` over the ops acknowledged in the window, and
nothing where the program keeps no such counter.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import pytest
from test_bench_harness import _bench, _run, _tiny  # sets the path first

import run as bench_run
from harness import window

NAME = "pg_mapping_runs_per_op"
CELLS = ["ec83_write", "rep3_write_4k", "ec83_write_4chip",
         "ec83_degraded_read", "ec42_rbd_randwrite_4k",
         "clay8411_degraded_read", "clay8411_write"]
RUN = {"window": window.Window(0.0, 5.0, 1.0), "acked_ops": 400}


def _reader():
    spec = bench_run.load_cell(CELLS[0])
    return bench_run.load_layer_metric(spec["metrics_dir"], NAME)


def test_the_entry_lists_the_client_cells():
    entry = next(m for m in _bench()["per_layer"] if m["name"] == NAME)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == {
        "unit": "1/op", "better": "lower", "source": "program_counter",
        "layer": "loop", "moves": "throughput_MiB_s"}
    assert set(entry["workloads"]) == set(CELLS)
    mod = _reader()
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    for cell in CELLS:
        assert NAME in [m["name"] for m in
                        bench_run.load_cell(cell)["per_layer"]]
    for cell in ("ec83_recovery", "clay8411_recovery"):
        assert NAME not in [m["name"] for m in
                            bench_run.load_cell(cell)["per_layer"]]


@pytest.mark.parametrize("counters", [
    {},
    {"osd.op_w": 400, "osd.pg_stat_objects_stated": 12},
])
def test_nothing_to_read_without_the_counter(counters):
    assert _reader().compute([], counters, None, RUN) is None


@pytest.mark.parametrize("runs, want", [(800, 2.0), (6, 0.015), (0, 0.0)])
def test_the_counters_growth_over_the_ops_acked(runs, want):
    counters = {"osd.pg_mapping_runs": runs,
                "osd.pg_mapping_lookups": 1200, "osd.op_w": 400}
    assert _reader().compute([], counters, None, RUN) == pytest.approx(want)
    assert _reader().compute([], counters, None,
                             {**RUN, "acked_ops": 0}) is None


def test_a_rehearsal_of_new_names_runs_almost_nothing(tmp_path):
    res = _run(_tiny("rep3_write_4k"), tmp_path, trace=True, seconds=3.0)
    assert res["correct"]
    value, unit = res["metrics"][NAME]
    assert unit == "1/op" and 0 <= value <= 0.02, value
