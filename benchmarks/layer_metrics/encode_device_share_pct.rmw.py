"""Share of the window's encode requests that a device launch served:
``encode.coalesced`` (requests the launches carried) over that plus
``encode.host_requests`` (requests of flushed groups too small for a
launch, answered on the host at the flush).  The launch decision is the
group's: a lone 16 KiB stripe is under the service's 32 KiB, two that
arrive in one window are a launch.  Nothing to read where no request
reached the service.
"""

LAYER = "launch batching"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    launched = counters.get("encode.coalesced", 0)
    filed = launched + counters.get("encode.host_requests", 0)
    return 100.0 * launched / filed if filed else None
