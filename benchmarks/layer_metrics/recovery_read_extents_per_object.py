"""Byte runs an object's helper reads ask for: the ``extents`` tags of
the ``recovery_read`` spans, per ``recover_object``.  A sub-chunk repair
of CLAY(8,4,11) reads 11 helpers x 2 stripes x 1, 4 or 16 runs (by the
lost node's row); a whole-chunk read is one run a source.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "extents"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    reads = [s for s in spantree.named(spans, "recovery_read")
             if "extents" in s["tags"]]
    objects = spantree.named(spans, "recover_object")
    if not reads or not objects:
        return None
    return sum(s["tags"]["extents"] for s in reads) / len(objects)
