"""Mean number of ``recover_object`` spans open, over all OSDs, from the
window's start to the end of the work.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "ops"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.in_flight(spans, run, "recover_object")
