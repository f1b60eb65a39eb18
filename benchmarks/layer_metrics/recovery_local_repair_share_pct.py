"""Shards that recovery rebuilt by one matrix from fewer than k helpers
(``recovery_local_repairs``: an LRC chunk from the 3 others of its local
group), of every shard it rebuilt (``recovery_rebuilt_shards``).
Anything under 100 says the cell measured repairs that read k or more
(two chunks lost in one group, a host fallback).  Nothing to read where
the program does not count them.
"""

LAYER = "recovery"
UNIT = "%"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    local = counters.get("osd.recovery_local_repairs", 0)
    rebuilt = counters.get("osd.recovery_rebuilt_shards", 0)
    return 100.0 * local / rebuilt if rebuilt else None
