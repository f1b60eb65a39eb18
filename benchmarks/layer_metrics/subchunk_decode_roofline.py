"""A vector code's read-decode program's share of its roofline over the
traced window.  The work is counted from what the launches say they
moved, not from how the program does it: a launch of kind
``subchunk_decode`` tags the ``helper_bytes`` of the fetched chunks'
sub-chunk rows it took in and the ``rebuilt_bytes`` of the lost chunks'
rows it gave back, and the least time for them is one pass through HBM.
The time is that of every device op that starts inside such a launch.
Nothing to read where no such launch fell in the trace, where the
launches do not say what they moved, or off the chip (no peaks).
"""

import bisect

LAYER = "kernels"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def least_seconds(launches: list[dict], peaks: dict) -> float:
    """Bytes in and out of the launches at the HBM peak."""
    moved = sum(s["tags"]["helper_bytes"] + s["tags"]["rebuilt_bytes"]
                for s in launches)
    return moved / (peaks["HBM_GBs"] * 1e9)


def device_seconds(trace: dict, launches: list[dict]) -> float:
    """Device time of the ops that start inside one of ``launches``."""
    spans = sorted((s["start_mono"], s["end_mono"]) for s in launches)
    starts = [a for a, _b in spans]
    total = 0.0
    for events in trace["devices"].values():
        for _name, start, dur in events:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < spans[i][1]:
                total += dur
    return total


def compute(spans, counters, trace, run):
    if not trace or not run.get("peaks"):
        return None     # no peaks: not the chip, so no share of them
    launches = [s for s in spans if s["name"] == "xla_launch"
                and s["tags"].get("kind") == "subchunk_decode"
                and "helper_bytes" in s["tags"]
                and "rebuilt_bytes" in s["tags"]
                and run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    t = device_seconds(trace, launches) if launches else 0.0
    return 100.0 * least_seconds(launches, run["peaks"]) / t if t > 0 \
        else None
