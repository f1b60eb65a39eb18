"""``recovery_reserve_queued_pct`` (PR 39): the reader on hand-made
counters, as ``test_span_metrics.py`` gives them.  It reads the growth of
``osd.backfill_reserve_queued`` over that of
``osd.backfill_reserve_requests``, and nothing where the program counts
no REQUEST (the parent commit).  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import pytest
from test_bench_harness import _bench  # sets the path first

import run as bench_run

NAME = "recovery_reserve_queued_pct"
CELLS = ["ec83_recovery", "clay8411_recovery"]


def _reader():
    spec = bench_run.load_cell(CELLS[0])
    return bench_run.load_layer_metric(spec["metrics_dir"], NAME)


def test_the_entry_is_the_issues():
    entry = next(m for m in _bench()["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "recovery",
        "moves": "recovery_MiB_s", "workloads": CELLS}
    mod = _reader()
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    for cell in CELLS:
        assert NAME in [m["name"] for m in
                        bench_run.load_cell(cell)["per_layer"]]
    for other in ("ec83_write", "ec83_degraded_read"):
        assert NAME not in [m["name"] for m in
                            bench_run.load_cell(other)["per_layer"]]


@pytest.mark.parametrize("counters", [
    {},
    {"osd.backfill_reserve_requests": 0, "osd.backfill_reserve_queued": 0},
    {"osd.recovery_ops": 12, "osd.backfill_reject_toofull": 3},
])
def test_nothing_to_read_without_a_request(counters):
    assert _reader().compute([], counters, None, {}) is None


@pytest.mark.parametrize("requests, queued, want", [
    (40, 30, 75.0),
    (11, 0, 0.0),
    (7, 7, 100.0),
])
def test_the_share_of_requests_that_queued(requests, queued, want):
    counters = {"osd.backfill_reserve_requests": requests,
                "osd.backfill_reserve_queued": queued,
                "osd.backfill_reject_toofull": 2}
    assert _reader().compute([], counters, None, {}) == pytest.approx(want)
