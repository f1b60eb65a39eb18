"""Time in ``msg_send`` spans (by name: the messenger alone, request and
reply legs, not the sub-ops' round trips) per acknowledged op.
"""

from harness import spantree

LAYER = "net"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.ms_per_op(spans, run, "msg_send")
