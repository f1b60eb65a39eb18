"""Milliseconds of the one event loop that recovery took per MiB it
moved: the growth of ``msgr.loop_s_recovery`` (recovery passes, their
reservations, pushes and peering messages, object repairs, and the
helpers' handling of the sub-reads a recovery asks for) over the
growth of the traffic's ``counter`` in MiB.  A per-MiB cost, not a share
of the window: recovery ends inside the window, and after it the loop
runs background work alone.  Nothing to read where no callback was
timed or nothing was moved.
"""

from harness.loopclock import owned_s

LAYER = "loop"
UNIT = "ms/MiB"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    s = owned_s(counters)
    moved = counters.get((run.get("traffic") or {}).get("counter"), 0)
    if s is None or not moved:
        return None
    return 1e3 * s["recovery"] / (moved / float(1 << 20))
