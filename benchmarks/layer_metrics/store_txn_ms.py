"""Time in ``store_txn`` spans per ``store_commit``: the worker thread's call
of ``queue_transaction`` (its tags split it: ``lock_wait_ms``, ``data_ms``,
``fsync_ms``, ``kv_ms``).
"""

from harness import spantree

LAYER = "store"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "store_txn", per="store_commit")
