"""How evenly the traced window's device work fell on the chips the
configuration has: the least-busy chip's busy seconds over the
busiest's.  0 when a chip never worked (its plane is empty or absent).
"""

from harness import reduce

LAYER = "device"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def compute(spans, counters, trace, run):
    if not trace or not trace["devices"]:
        return None
    t0, t1 = run["trace_t0"], run["trace_t1"]
    busy = [sum(b - a for a, b in reduce.busy_intervals(ev, t0, t1))
            for ev in trace["devices"].values()]
    if max(busy) <= 0:
        return None
    if len(busy) < run["config"].get("chips", 1):
        return 0.0
    return 100.0 * min(busy) / max(busy)
