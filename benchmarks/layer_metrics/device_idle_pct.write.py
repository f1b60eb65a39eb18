"""Share of the traced window in which no operation ran on the device.
"""

from harness import reduce

LAYER = "device"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def compute(spans, counters, trace, run):
    return reduce.idle_pct(trace, run)
