"""Median of 200 x (pwrite 512 KiB + fsync) in the data directory, taken
in set-up of a traced run: the medium's flush, before it is blamed.
"""

from harness.window import median

LAYER = "disk"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "host_clock"


def compute(spans, counters, trace, run):
    return 1e3 * median(run["flush_s"]) if run["flush_s"] else None
