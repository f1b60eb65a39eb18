"""Time in ``store_exec_wait`` spans per ``store_commit``: from handing the
transaction to the executor until the worker thread's first instruction
(thread-pool and GIL queueing before the store is touched).
"""

from harness import spantree

LAYER = "store"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_ms(spans, "store_exec_wait", per="store_commit")
