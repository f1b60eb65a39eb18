"""Share of the reads acknowledged in the window that rebuilt a chunk:
requests the encode service's launches served (a read whose k fetched
shards hold every data chunk asks for none) over reads acknowledged.
"""

LAYER = "EC op path"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    if not run.get("acked_ops") or "encode.coalesced" not in counters:
        return None
    return 100.0 * counters["encode.coalesced"] / run["acked_ops"]
