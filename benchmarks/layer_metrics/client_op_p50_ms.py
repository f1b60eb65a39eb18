"""Median submit-to-ack time of the ops acknowledged in the window.

With 16 in flight in a closed loop this is 16 / ops-per-second by
Little's law: the same fact as the throughput, seen from one op.
"""

LAYER = "client"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "host_clock"


def compute(spans, counters, trace, run):
    return run["window"].latency_ms(50) if run["acked_ops"] else None
