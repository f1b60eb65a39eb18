"""Time in ``recovery_push`` spans (the gather of pushes, each to the
target's commit and back) per ``recover_object``.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "ms"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "recovery_push", per="recover_object")
