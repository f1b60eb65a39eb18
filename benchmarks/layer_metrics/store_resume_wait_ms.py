"""Mean self time of ``store_commit``: what its two children leave, which is
building the transaction plus the finished commit's wait for the event
loop to resume its coroutine.
"""

from harness import spantree

LAYER = "store"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.mean_self_ms(spans, run, "store_commit")
