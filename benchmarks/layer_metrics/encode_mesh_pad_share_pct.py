"""Share of the bytes the window's mesh encode launches uploaded that
were padding: the bucket's columns less the requests' real ones.
"""

LAYER = "launch batching"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    padded = counters.get("encode.mesh_padded_bytes", 0)
    if not padded:
        return None
    return 100.0 * (1.0 - counters.get("encode.mesh_occupied_bytes", 0)
                    / padded)
