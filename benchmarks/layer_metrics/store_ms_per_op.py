"""Time in ``store_commit`` spans of every OSD per acknowledged op (11
commits to an EC write, 3 to a replicated one, two flushes each).
"""

from harness import reduce

LAYER = "store"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return reduce.ms_per_op(spans, run, stage="store")
