"""Lanes (one request's slab of at most the tile cap) per batched decode
launch of the recovery-decode aggregator; a launch holds up to 8.
"""

LAYER = "launch batching"
UNIT = "lanes/launch"
MOVES = "recovery_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    launches = counters.get("decode.launches", 0)
    return counters.get("decode.batched_requests", 0) / launches \
        if launches else None
