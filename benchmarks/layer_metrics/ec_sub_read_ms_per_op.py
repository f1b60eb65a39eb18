"""Time in the primaries' ``ec_sub_read`` spans per acknowledged read: the
round trips to the other shards' OSDs (k - 1 or k of them a read, side
by side), request to reply.
"""

from harness import spantree

LAYER = "net"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.ms_per_op(spans, run, "ec_sub_read")
