"""Programs JAX lowered inside the window plus launches the engines
counted as cold; ``correct`` is false unless this is 0.
"""

from harness import reduce

LAYER = "launch batching"
UNIT = "count"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    return reduce.compiles_in_window(counters)
