"""The encode launches' share of their roofline over the traced window,
in a loop of small overwrites.  The work is counted from what the
launches say they carried, not from how the program does it: a launch of
kind ``encode_single`` tags its ``real_bytes`` (the k data rows of every
request it serves, without the padding to its bucket); the parities are
``m / k`` of that, and the least time for both is one pass through HBM.
The time is that of every device op that starts inside such a launch.
Nothing to read where no such launch fell in the trace, where the
launches do not say what they carried, or off the chip (no peaks).
"""

import bisect

LAYER = "kernels"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def least_seconds(launches: list[dict], k: int, m: int, peaks: dict) -> float:
    """Bytes in and out of the launches at the HBM peak."""
    real = sum(s["tags"]["real_bytes"] for s in launches)
    return real * (k + m) / k / (peaks["HBM_GBs"] * 1e9)


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
    pool = run["config"]["pool"]
    launches = [s for s in spans if s["name"] == "xla_launch"
                and s["tags"].get("kind") == "encode_single"
                and "real_bytes" in s["tags"]
                and run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    t = device_seconds(trace, launches) if launches else 0.0
    return 100.0 * least_seconds(
        launches, pool["k"], pool["m"], run["peaks"]) / t if t > 0 else None
