"""Time in the primaries' ``ec_encode`` spans per acknowledged op: waiting
for the encode service's window, the launch and the copy back.
"""

from harness import reduce

LAYER = "EC op path"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return reduce.ms_per_op(spans, run, name="ec_encode")
