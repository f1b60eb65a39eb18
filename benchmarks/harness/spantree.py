"""Span trees for the per-layer readers: children by parent, a span's self
time, means that add up, and one op's critical path.

A span is ``Span.dump()`` of ``ceph_tpu/common/tracing.py``; ``spans`` are
the ones that ended in the window (``reduce.spans_in``), from every
daemon.  Means are totals over a count, so that a parent's parts add up
to the parent: ``mean_ms(spans, "store_txn", per="store_commit")`` is the
time in ``store_txn`` per commit, 0 for a commit that has none.  Every
function returns ``None`` where the program recorded no such span (a
parent commit without them), and the metric is then left out.
"""

from __future__ import annotations


def seconds(span: dict) -> float:
    return span["end_mono"] - span["start_mono"]


def named(spans: list[dict], name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name]


def children(spans: list[dict], run: dict) -> dict[int, list[dict]]:
    """``parent_id -> [child, ...]`` over ``spans``, built once a run."""
    memo = run.setdefault("_spantree", {})
    if "children" not in memo:
        kids: dict[int, list[dict]] = {}
        for s in spans:
            if s.get("parent_id") is not None:
                kids.setdefault(s["parent_id"], []).append(s)
        memo["children"] = kids
    return memo["children"]


def self_seconds(span: dict, kids: list[dict]) -> float:
    """The span's duration minus the union of its children's intervals
    (clipped to the span: a child may outlive its parent)."""
    covered, reach = 0.0, span["start_mono"]
    for k in sorted(kids, key=lambda k: k["start_mono"]):
        a = max(k["start_mono"], reach)
        b = min(k["end_mono"], span["end_mono"])
        if b > a:
            covered += b - a
            reach = b
    return seconds(span) - covered


def mean_ms(spans: list[dict], name: str, per: str | None = None,
            ) -> float | None:
    """Time in spans called ``name`` per span called ``per`` (itself by
    default), in ms."""
    mine, base = named(spans, name), named(spans, per or name)
    if not mine or not base:
        return None
    return 1e3 * sum(seconds(s) for s in mine) / len(base)


def mean_self_ms(spans: list[dict], run: dict, name: str) -> float | None:
    mine = named(spans, name)
    if not mine:
        return None
    kids = children(spans, run)
    return 1e3 * sum(self_seconds(s, kids.get(s["span_id"], ()))
                     for s in mine) / len(mine)


def ms_per_op(spans: list[dict], run: dict, name: str,
              tag: str | None = None) -> float | None:
    """Per acknowledged client op: the time in spans called ``name``, or
    the sum of their ``tag`` (a number of ms)."""
    mine = [s for s in named(spans, name)
            if tag is None or tag in s["tags"]]
    if not mine or not run.get("acked_ops"):
        return None
    total = (1e3 * sum(seconds(s) for s in mine) if tag is None
             else sum(s["tags"][tag] for s in mine))
    return total / run["acked_ops"]


def critical_path_ms(spans: list[dict], run: dict, stage: str,
                     ) -> float | None:
    """Mean over the ``client_op`` traces that ended in the window of the
    exclusive ms the program's own collector
    (``ceph_tpu.mgr.tracer.TraceCollector``) finds on the op's blocking
    path in ``stage``; ``stage="client_op"`` gives the mean duration of
    those ops' root spans, which the five stages add up to."""
    memo = run.setdefault("_spantree", {})
    if "critical_path" not in memo:
        from ceph_tpu.mgr.tracer import TraceCollector

        roots = {s["trace_id"] for s in named(spans, "client_op")}
        col = TraceCollector(max_traces=len(roots) + 1)
        col.ingest("bench", [s for s in spans if s["trace_id"] in roots])
        done = [a for a in map(col.assemble, roots)
                if a is not None and a["root"] == "client_op"]
        memo["critical_path"] = None if not done else {
            "client_op": sum(a["duration_ms"] for a in done) / len(done),
            **{st: sum(a["stages_ms"][st] for a in done) / len(done)
               for st in done[0]["stages_ms"]}}
    found = memo["critical_path"]
    return None if found is None else found.get(stage)


def in_flight(spans: list[dict], run: dict, name: str) -> float | None:
    """Mean number of ``name`` spans open: their time inside the window
    over the window's time up to the end of the work (``t_done``)."""
    mine, w = named(spans, name), run["window"]
    if not mine:
        return None
    t_end = w.t_done if w.t_done is not None else w.t_end
    return sum(min(s["end_mono"], t_end) - max(s["start_mono"], w.t0)
               for s in mine if s["start_mono"] < t_end) / (t_end - w.t0)
