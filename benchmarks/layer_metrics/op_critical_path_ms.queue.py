"""Mean exclusive time on one ``client_op``'s blocking path in stage
``queue`` (mClock admission, the wait for an encode launch), by the
program's ``TraceCollector``; the five stages add up to the op's duration.
"""

from harness import spantree

LAYER = "queue"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.critical_path_ms(spans, run, "queue")
