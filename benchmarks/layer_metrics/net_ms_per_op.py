"""Time in ``msg_send``/``msg_recv`` spans (stage net) of every daemon and
the client, per acknowledged op: mostly waiting for the send lock and
the socket behind other frames.
"""

from harness import reduce

LAYER = "net"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return reduce.ms_per_op(spans, run, stage="net")
