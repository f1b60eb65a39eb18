"""Time in ``encode_batch_wait`` spans per acknowledged op: from a request's
arrival at the encode service to the start of the launch that serves it
(coalescing window, executor queue, host packing).
"""

from harness import spantree

LAYER = "launch batching"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.ms_per_op(spans, run, "encode_batch_wait")
