"""Median over the window's whole slices of the bytes acknowledged in a
slice: the throughput with one stalled flush or one GC pause taken out.
"""

from harness.window import median

LAYER = "client"
UNIT = "MiB/s"
MOVES = "throughput_MiB_s"
SOURCE = "host_clock"


def compute(spans, counters, trace, run):
    rates = run["window"].slice_rates_MiB_s()
    return median(rates) if run["acked_ops"] else None
