"""Median over the window's slices (up to clean) of the rebuilt bytes per
slice: the recovery rate with peering's start-up and stalls taken out.
"""

from harness.window import median

LAYER = "recovery"
UNIT = "MiB/s"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    return median(run["window"].counter_slice_rates_MiB_s())
