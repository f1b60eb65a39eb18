"""The share of the daemons' loop time that went to ``background``:
heartbeats, beacons and the pg stats they carry, maps, mgr reports,
scrubs, the grant sweep, and all of the mon's work.  The growth of
``msgr.loop_s_background`` over that of every role's, summed.  Nothing
to read where no callback was timed.
"""

from harness.loopclock import owned_s

LAYER = "loop"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    s = owned_s(counters)
    if s is None or not sum(s.values()):
        return None
    return 100.0 * s["background"] / sum(s.values())
