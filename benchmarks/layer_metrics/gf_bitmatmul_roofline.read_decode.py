"""The read path's decode launches' share of their roofline over the
traced window.  A whole-object read that lost one shard to the stopped
OSD needs one data chunk rebuilt from k: each request a launch served is
a (k, 1, object_bytes / k) product.  The rows the program computes
beside it (it asks for every chunk it did not fetch, parity too) and the
padding to the width bucket are its own choice and count as none of it.
The window holds no other work for the device, so every op of a
``jit_gf_bitmatmul*`` program is the read path's.
"""

from harness import reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def compute(spans, counters, trace, run):
    if not trace:
        return None
    k, S = run["config"]["pool"]["k"], run["traffic"]["object_bytes"]
    products = [(k, 1, s["tags"]["b_real"] * S // k)
                for s in reduce.launches(spans, "encode")
                if run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    return reduce.roofline_pct(trace, run, products=products,
                               pattern=r"^jit_gf_bitmatmul")
