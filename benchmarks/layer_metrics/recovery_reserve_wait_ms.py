"""Time in ``pg_reserve`` spans per ``recover_pg``: the wait for the local
backfill slot and a slot on every acting peer, retry sleeps included.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "ms"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "pg_reserve", per="recover_pg")
