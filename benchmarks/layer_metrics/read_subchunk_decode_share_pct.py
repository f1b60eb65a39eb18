"""Share of a vector code's read decodes in the window that rebuilt the
lost chunks on the device, as one matrix over sub-chunk rows
(``osd.ec_read_subchunk_decodes``), against those the host's layered
decode served on the event loop (``osd.ec_read_host_decodes``).  Nothing
to read where neither counter grew: no read lost a data chunk, or the
program counts neither.
"""

LAYER = "EC op path"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "program_counter"


def compute(spans, counters, trace, run):
    device = counters.get("osd.ec_read_subchunk_decodes", 0)
    host = counters.get("osd.ec_read_host_decodes", 0)
    return 100.0 * device / (device + host) if device + host else None
