"""Reads' decode requests served per device launch of the encode service
(reads that lost the same shard share a decode matrix and a launch).
"""

LAYER = "launch batching"
UNIT = "ops/launch"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    launches = sum(counters.get(f"encode.{k}_dispatches", 0)
                   for k in ("single", "dp", "tp"))
    return counters.get("encode.coalesced", 0) / launches if launches else None
