"""Payload bytes of the helper reads of recovery per byte rebuilt, every
OSD's summed: ``recovery_read_bytes`` (every read a decode or repair is
given, local or remote) over ``recovery_rebuilt_bytes`` (what the
decodes and repairs made).  Not over ``recovery_decode_bytes``: that
counts the shards that only moved too (marking an OSD out can move a
second position of a PG), which are read whole from their old holder
and passed on, and say nothing of the code.  A regenerating code
promises d / (d - k + 1): 11/4 = 2.75 for CLAY(8,4,11); a scalar code
reads k = 8 or more.  Nothing to read where the program does not count
its helper reads.
"""

LAYER = "recovery"
UNIT = "bytes/byte"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    read = counters.get("osd.recovery_read_bytes", 0)
    rebuilt = counters.get("osd.recovery_rebuilt_bytes", 0)
    return read / rebuilt if read and rebuilt else None
