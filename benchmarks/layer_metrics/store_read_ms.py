"""The store's call of a shard read, per read: the mean of the ``read_ms``
tag of the ``store_read`` spans (``OSDDaemon._store_read``: one load of the
meta, one ``pread``, one crc, the object's attrs).
"""

from harness import spantree

LAYER = "store"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    mine = [s["tags"]["read_ms"] for s in spantree.named(spans, "store_read")
            if "read_ms" in s["tags"]]
    return sum(mine) / len(mine) if mine else None
