"""Bytes the stores read from their block files and checksummed, per
acknowledged op: the growth of ``osd.store_read_disk_bytes``, every OSD's
summed.  A read-modify-write that misses the extent cache reads its
stripe from k shards, so the least on a miss is k x the stripe unit
(4 x 4 KiB here); a block that an earlier write turned into a kv piece
costs the block file nothing.  Nothing to read where the program's
stores do not count what they read.
"""

LAYER = "store"
UNIT = "bytes"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    if not run.get("acked_ops") or "osd.store_read_disk_bytes" not in counters:
        return None
    return counters["osd.store_read_disk_bytes"] / run["acked_ops"]
