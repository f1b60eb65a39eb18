"""Repairs of a code with sub-chunks that read only the repair sub-chunks
of their helpers (``recovery_subchunk_repairs``), of all its repairs
(those and ``recovery_fullchunk_repairs``, the fallback to whole
chunks).  Anything under 100 says the cell measured the fallback.
"""

LAYER = "recovery"
UNIT = "%"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    sub = counters.get("osd.recovery_subchunk_repairs", 0)
    full = counters.get("osd.recovery_fullchunk_repairs", 0)
    return 100.0 * sub / (sub + full) if sub + full else None
