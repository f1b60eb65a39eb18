"""Share of the read-modify-writes of the window whose old stripes came
from the primary's extent cache and not from the shards: the growth of
``osd.ec_extent_cache_hit`` over that of ``osd.ec_rmw_ops``.  Under
uniform offsets into an image larger than the caches only the first
overwrite of an object whose whole-object entry the prefill left can hit.
Nothing to read where the program does not count its read-modify-writes.
"""

LAYER = "EC op path"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    ops = counters.get("osd.ec_rmw_ops", 0)
    if not ops:
        return None
    return 100.0 * counters.get("osd.ec_extent_cache_hit", 0) / ops
