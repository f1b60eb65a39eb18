"""The local-repair program's share of its roofline over the traced
window.  The work is counted from what the launches say they moved, not
from how the program does it: a launch of kind ``local_repair`` (an LRC
chunk rebuilt from its local group: a (1, 3) matrix over GF(2^8)) tags
the ``helper_bytes`` it took in and the ``rebuilt_bytes`` it gave back,
and the least time for them is one pass through HBM.  (As a bit matrix
the int8 operations, 2 x 8 x 24 a byte column, take a fifth of that time
at the chip's peak: the bytes bound it.)  The time is that of every
device op that starts inside such a launch.
"""

import bisect

LAYER = "kernels"
UNIT = "%"
MOVES = "recovery_MiB_s"
SOURCE = "device_trace"
KIND = "local_repair"


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
                and s["tags"].get("kind") == KIND
                and "helper_bytes" in s["tags"]
                and run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    t = device_seconds(trace, launches) if launches else 0.0
    return 100.0 * least_seconds(launches, run["peaks"]) / t if t > 0 \
        else None
