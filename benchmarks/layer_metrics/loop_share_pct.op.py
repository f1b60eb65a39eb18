"""The share of the daemons' loop time that went to ``op``: client ops,
sub-ops and their replies, on the primaries and on the shards (a shard
charges a sub-read to the role that asked for it: an op's here).  The
growth of ``msgr.loop_s_op`` over that of every role's, summed.  Nothing
to read where no callback was timed.
"""

from harness.loopclock import owned_s

LAYER = "loop"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    s = owned_s(counters)
    if s is None or not sum(s.values()):
        return None
    return 100.0 * s["op"] / sum(s.values())
