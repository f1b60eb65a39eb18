"""95th percentile of submit-to-ack time over every op acknowledged in
the window (about 700 samples, 35 beyond it).

Not judged end to end: its run-to-run spread (6.4% and 6.5% on
``ec83_write``, chip runs of PR 24) asks for a bound of a third, above
the quarter a bound may be.
"""

LAYER = "client"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "host_clock"


def compute(spans, counters, trace, run):
    return run["window"].latency_ms(95) if run["acked_ops"] else None
