"""Time in ``recovery_read`` spans (the gather of source-shard reads) per
``recover_object``.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "ms"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "recovery_read", per="recover_object")
