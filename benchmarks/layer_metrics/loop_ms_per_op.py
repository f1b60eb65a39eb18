"""Milliseconds of the one event loop an acknowledged op costs: the
growth of every daemon's ``msgr.loop_s_<role>``, summed over the roles,
over the ops acknowledged in the window.  Nothing to read where no
callback was timed.
"""

from harness.loopclock import owned_s

LAYER = "loop"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    s = owned_s(counters)
    if s is None or not run.get("acked_ops"):
        return None
    return 1e3 * sum(s.values()) / run["acked_ops"]
