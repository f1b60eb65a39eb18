"""Bytes the stores wrote to their medium per byte the clients wrote, in
a loop of small writes: the growth of ``osd.store_block_write_bytes``
(blob data into the block files) plus ``osd.store_kv_write_bytes`` (what
the kv engines wrote for the commits: WAL records, and a checkpoint or a
superblock when one fell due), every OSD's summed, over the acknowledged
ops times the loop's ``io_bytes``.  An EC(4,2) pool writes six shards an
op, each behind a rollback clone; what it costs beyond the 4 KiB piece is
extent maps, attributes, the pg log, and the fold of an object's pieces
into one blob when they pass 64.  Nothing to read where the program's
stores do not count what they write.
"""

LAYER = "store"
UNIT = "bytes/byte"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    io_bytes = ((run.get("traffic") or {}).get("loop") or {}).get("io_bytes")
    if not run.get("acked_ops") or not io_bytes \
            or "osd.store_kv_write_bytes" not in counters:
        return None
    wrote = (counters["osd.store_kv_write_bytes"]
             + counters.get("osd.store_block_write_bytes", 0))
    return wrote / (run["acked_ops"] * io_bytes)
