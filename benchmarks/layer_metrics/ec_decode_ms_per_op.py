"""Time in the primaries' ``ec_decode`` spans per acknowledged read: from
the k fetched shards to the object's bytes: waiting for the encode
service's window, the launch with a decode matrix and the copy back
where a chunk is rebuilt, and putting the stripes back in order.
"""

from harness import spantree

LAYER = "EC op path"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.ms_per_op(spans, run, "ec_decode")
