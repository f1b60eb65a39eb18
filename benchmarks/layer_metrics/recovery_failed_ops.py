"""``recover_object`` spans that ended ``result="failed"`` (the function's
boolean) or with an ``error`` tag (an exception)."""

from harness import spantree

LAYER = "recovery"
UNIT = "count"
MOVES = "recovery_MiB_s"
SOURCE = "program_span"


def compute(spans, counters, trace, run):
    ops = spantree.named(spans, "recover_object")
    return float(sum("error" in s["tags"] or s["tags"].get("result")
                     == "failed" for s in ops)) if ops else None
