"""Mean exclusive time on one ``client_op``'s blocking path in stage
``net`` (messenger sends, sub-op round trips), by the
program's ``TraceCollector``; the five stages add up to the op's duration.
"""

from harness import spantree

LAYER = "net"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    return spantree.critical_path_ms(spans, run, "net")
