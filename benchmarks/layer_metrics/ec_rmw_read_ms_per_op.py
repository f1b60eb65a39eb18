"""Time in the primaries' ``ec_rmw_read`` spans per acknowledged write:
the fetch of the old stripes a partial overwrite lands on, before it can
re-encode them: a look into the extent cache or, on a miss, k ranged
sub-reads side by side and the reassembly of what they bring.
"""

from harness import spantree

LAYER = "EC op path"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.ms_per_op(spans, run, "ec_rmw_read")
