"""Seconds ``recover_object`` spent awaiting decode (every OSD's
``recovery_decode_seconds``, summed) over the window's seconds.
"""

LAYER = "recovery"
UNIT = "%"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    return 100.0 * counters.get("osd.recovery_decode_seconds", 0) \
        / run["window"].seconds
