"""Mean duration of the window's mesh encode launches as the host sees
one: the ``xla_launch`` span of kind ``encode_dp``, from the upload to
the chips through the launch to the parity's copy back.
"""

from harness import reduce

LAYER = "launch batching"
UNIT = "ms"
MOVES = "throughput_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    mesh = reduce.launches(spans, "encode_dp")
    if not mesh:
        return None
    return 1e3 * sum(s["end_mono"] - s["start_mono"] for s in mesh) / len(mesh)
