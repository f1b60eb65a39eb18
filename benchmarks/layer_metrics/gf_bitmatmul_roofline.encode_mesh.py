"""The mesh encode program's share of its roofline over the traced
window: the least time ONE chip could take for the launches' real
(k, m, S) products over the device time of every op of the compiled
column-split program, summed over the planes of all the chips it ran
on.  The work is the same whatever implements it and however it is
split, so a perfect four-way split reads what one chip would, never
more.
"""

from harness import reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def compute(spans, counters, trace, run):
    if not trace:
        return None
    pool, S = run["config"]["pool"], run["traffic"]["object_bytes"]
    products = [(pool["k"], pool["m"],
                 s["tags"]["b_real"] * S // pool["k"])
                for s in reduce.launches(spans, "encode")
                if run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    return reduce.roofline_pct(trace, run, products=products,
                               pattern=r"^jit_encode_mesh_cols/")
