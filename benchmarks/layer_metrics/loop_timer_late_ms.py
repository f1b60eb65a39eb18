"""How late the loop woke a periodic timer, on the mean: the growth of
``msgr.loop_timer_late_s`` over that of ``msgr.loop_timer_fires``, every
daemon's.  The timers are the OSDs' beacons, heartbeats, grant sweeps
and mgr reports and the mon's tick; lateness is the wake time less the
sleep's start and its interval.  Nothing to read where no callback was
timed.
"""

from harness.loopclock import owned_s

LAYER = "loop"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    fires = counters.get("msgr.loop_timer_fires", 0)
    if owned_s(counters) is None or not fires:
        return None
    return 1e3 * counters.get("msgr.loop_timer_late_s", 0.0) / fires
