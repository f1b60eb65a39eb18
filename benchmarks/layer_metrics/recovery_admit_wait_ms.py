"""Mean ``recovery_admit_wait`` span: one object's wait for a slot of
``osd_recovery_max_active`` and for the mClock gate, before
``recover_object`` opens.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "ms"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "recovery_admit_wait")
