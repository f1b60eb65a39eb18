"""Time in ``op_queue`` spans (mClock admission wait) per acknowledged op.
"""

from harness import reduce

LAYER = "queue"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return reduce.ms_per_op(spans, run, stage="queue")
