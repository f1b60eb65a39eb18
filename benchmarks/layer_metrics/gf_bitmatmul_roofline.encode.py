"""The encode program's share of its roofline over the traced window:
the least time the chip could take for the launches' real (k, m, S)
products over the device time of every op of the Pallas program.
"""

from harness import reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "throughput_MiB_s"
SOURCE = "device_trace"


def compute(spans, counters, trace, run):
    pool, S = run["config"]["pool"], run["traffic"]["object_bytes"]
    products = [(pool["k"], pool["m"],
                 s["tags"]["b_real"] * S // pool["k"])
                for s in reduce.launches(spans, "encode")
                if run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    return reduce.roofline_pct(trace, run, products=products,
                               pattern=r"^jit_gf_bitmatmul_pallas")
