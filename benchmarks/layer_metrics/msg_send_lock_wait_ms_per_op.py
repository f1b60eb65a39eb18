"""The part of ``msg_send`` spent waiting for the connection's send lock
(tag ``lock_wait_ms``: behind other frames to the same peer), per
acknowledged op.
"""

from harness import spantree

LAYER = "net"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.ms_per_op(spans, run, "msg_send", tag="lock_wait_ms")
