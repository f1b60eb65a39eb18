"""The share of the window in which the one event loop ran a callback
that a daemon owns: the growth of every daemon's ``msgr.loop_s_<role>``,
summed over the roles, over the window's seconds.  What the loop ran for
no daemon (the harness's own tasks, a worker thread's completion
handles) is left out.  Nothing to read where no callback was timed.

A saturation check, not the measure of a gain: in a closed-loop cell
the loop stays busy whatever an op costs it, so a change that saves
loop time raises the rate and leaves this share where it was;
``loop_ms_per_op`` is the metric such a change moves.
"""

from harness.loopclock import owned_s

LAYER = "loop"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    s = owned_s(counters)
    return None if s is None else 100.0 * sum(s.values()) \
        / run["window"].seconds
