"""Time in ``pg_scan`` spans per ``recover_pg``: peer queries, log adoption
and scoping, until the PG's object set is known.
"""

from harness import spantree

LAYER = "recovery"
UNIT = "ms"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "pg_scan", per="recover_pg")
