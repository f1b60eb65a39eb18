"""The batched decode program's share of its roofline over the traced
window: one lost shard is rebuilt from k, so each real lane is a
(k, 1, w) product.
"""

from harness import reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "recovery_MiB_s"
SOURCE = "device_trace"


def compute(spans, counters, trace, run):
    k = run["config"]["pool"]["k"]
    products = [(k, 1, s["tags"]["b_real"] * s["tags"]["w"])
                for s in reduce.launches(spans, "decode_batch")
                if run["trace_t0"] <= s["start_mono"]
                and s["end_mono"] < run["trace_t1"]]
    return reduce.roofline_pct(trace, run, products=products,
                               pattern=r"^jit_gf_bitmatmul/")
