"""Backfill reservation REQUESTs that a busy replica queued, and granted
when its slot freed, instead of granting at once: the growth of
``osd.backfill_reserve_queued`` over that of
``osd.backfill_reserve_requests``, every OSD's.  Nothing to read where
no REQUEST was counted (the program before PR 39 counts none).
"""

LAYER = "recovery"
UNIT = "%"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    asked = counters.get("osd.backfill_reserve_requests", 0)
    if not asked:
        return None
    return 100.0 * counters.get("osd.backfill_reserve_queued", 0) / asked
