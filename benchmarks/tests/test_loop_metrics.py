"""The ``loop`` layer's readers (the event loop's own clock, which the
program keeps in its messengers' ``stats``): each on hand-made counters,
nothing where the program kept no clock, and every older reader blind to
the ``loop_turn`` spans, on a traced tiny run of every cell.  Run with

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here is a device number.
"""

import pytest
from test_bench_harness import _bench, _run, _tiny
from test_rbd_cell import CELL as RBD_CELL, _tiny_rbd

import run as bench_run
from harness import window

FIRST_LOOP = "loop_busy_pct"
MiB = 1 << 20


def _loop_metrics() -> list[dict]:
    names = [m["name"] for m in _bench()["per_layer"]]
    return _bench()["per_layer"][names.index(FIRST_LOOP):]


def _reader(name: str):
    return bench_run.load_layer_metric(
        bench_run.load_cell("ec83_write")["metrics_dir"], name)


COUNTERS = {    # a window's growth: 4 s of loop time in 5 s, 400 ops
    "msgr.loop_s_msgr": 1.0, "msgr.loop_s_op": 2.0,
    "msgr.loop_s_recovery": 0.25, "msgr.loop_s_background": 0.5,
    "msgr.loop_s_client": 0.25, "msgr.loop_callbacks": 40000,
    "msgr.loop_timer_fires": 50, "msgr.loop_timer_late_s": 1.5,
    "msgr.loop_timer_late_over_1s": 0,
    "osd.recovery_decode_bytes": 64 * MiB}
RUN = {"window": window.Window(0.0, 5.0, 1.0), "acked_ops": 400,
       "traffic": {"counter": "osd.recovery_decode_bytes"}}
EXPECTED = {
    "loop_busy_pct": 80.0, "loop_ms_per_op": 10.0,
    "loop_share_pct.msgr": 25.0, "loop_share_pct.op": 50.0,
    "loop_share_pct.background": 12.5, "loop_timer_late_ms": 30.0,
    "loop_ms_per_MiB.recovery": 250.0 / 64}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_loop_reader_on_hand_made_counters_and_on_none(name):
    assert name in [m["name"] for m in _loop_metrics()]
    mod = _reader(name)
    assert mod.LAYER == "loop" and mod.SOURCE == "program_counter"
    assert mod.compute([], COUNTERS, None, RUN) == pytest.approx(
        EXPECTED[name])
    assert mod.compute([], {}, None, RUN) is None
    # the program before the clock: wire counters, no loop counter
    assert mod.compute([], {"msgr.frames_in": 9}, None, RUN) is None
    # the clock kept but every tracer off: nothing was timed
    assert mod.compute([], {**COUNTERS, "msgr.loop_callbacks": 0},
                       None, RUN) is None


def test_the_loop_layer_is_the_seven_readers_listed_last():
    assert sorted(m["name"] for m in _loop_metrics()) == sorted(EXPECTED)
    assert all(m["layer"] == "loop" for m in _loop_metrics())


def test_shares_and_the_remainders_add_up_to_100():
    shares = sum(_reader(f"loop_share_pct.{r}").compute([], COUNTERS, None,
                                                        RUN)
                 for r in ("msgr", "op", "background"))
    rest = 100 * (COUNTERS["msgr.loop_s_client"]
                  + COUNTERS["msgr.loop_s_recovery"]) / 4.0
    assert shares + rest == pytest.approx(100.0)


@pytest.fixture(scope="module")
def both_ways(tmp_path_factory):
    """Every cell once, traced and tiny; every per-layer reader it lists
    reads the spans it is given, and the same spans less the
    ``loop_turn`` ones: ``{cell: {metric: (with, without, turns)}}``."""
    real = bench_run.load_layer_metric
    out = {}

    class Both:
        def __init__(self, mod, got, name):
            self.mod, self.got, self.name = mod, got, name

        def compute(self, spans, counters, trace, run):
            plain = [s for s in spans if s["name"] != "loop_turn"]
            v = self.mod.compute(spans, counters, trace, run)
            self.got[self.name] = (
                v, self.mod.compute(plain, counters, trace, run),
                len(spans) - len(plain))
            return v

    for cell in (w["name"] for w in _bench()["workloads"]):
        got = out[cell] = {}
        bench_run.load_layer_metric = \
            lambda d, name, _got=got: Both(real(d, name), _got, name)
        try:
            spec = _tiny_rbd() if cell == RBD_CELL else _tiny(cell)
            res = _run(spec, tmp_path_factory.mktemp(cell), trace=True,
                       seconds=4.0)
        finally:
            bench_run.load_layer_metric = real
        assert res["correct"], cell
    return out


def test_older_readers_read_the_same_without_the_loop_turns(both_ways):
    loop = {m["name"] for m in _loop_metrics()}
    compared = 0
    turns = {cell: max(n for _v, _w, n in got.values())
             for cell, got in both_ways.items()}
    # the tiny runs' turns are over 1 ms on the CPU, in most cells
    assert sum(n > 0 for n in turns.values()) >= len(turns) // 2, turns
    for cell, got in both_ways.items():
        for name, (with_, without, _n) in got.items():
            if name not in loop:
                assert with_ == without, (cell, name, with_, without)
                compared += with_ is not None and turns[cell] > 0
    assert compared >= 30, compared
