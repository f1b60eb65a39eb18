"""From spans and a ``jax.profiler`` trace to numbers: time per op by
stage, device busy and idle, time per kernel, idle gaps named by what
the host was doing, and a kernel's share of its roofline.

Times are seconds on the monotonic clock.  A device event is
``(name, start, duration)``; a span is ``Span.dump()`` of
``ceph_tpu/common/tracing.py`` (``start_mono``/``end_mono``/``tags``).
"""

from __future__ import annotations

import glob
import json
import os
import re

#: idle gaps shorter than this lie inside one launch (between its ops)
GAP_FLOOR_S = 50e-6
#: resolution at which idle time is attributed to host spans
SAMPLE_S = 1e-3
SYNC_NAME = "bench_clock_sync"


# -- spans ---------------------------------------------------------------

def spans_in(spans: list[dict], t0: float, t1: float) -> list[dict]:
    """Finished spans that ended inside ``[t0, t1)``."""
    return [s for s in spans
            if s.get("end_mono") is not None and t0 <= s["end_mono"] < t1]


def span_seconds(spans: list[dict], *, stage: str | None = None,
                 name: str | None = None) -> float:
    return sum(s["end_mono"] - s["start_mono"] for s in spans
               if (stage is None or s["tags"].get("stage") == stage)
               and (name is None or s["name"] == name))


def ms_per_op(spans: list[dict], run: dict, **which) -> float | None:
    """Span time of one stage or name, summed over every daemon, per
    client op acknowledged in the window."""
    if not run.get("acked_ops"):
        return None
    return 1e3 * span_seconds(spans, **which) / run["acked_ops"]


def compiles_in_window(counters: dict) -> float:
    """Programs JAX lowered plus launches the engines counted cold."""
    return float(counters.get("jax.lowerings", 0)
                 + counters.get("encode.cold_launches", 0)
                 + counters.get("decode.cold_launches", 0))


def launches(spans: list[dict], kind_prefix: str) -> list[dict]:
    return [s for s in spans if s["name"] == "xla_launch"
            and str(s["tags"].get("kind", "")).startswith(kind_prefix)]


# -- the profiler's trace --------------------------------------------------

def load_trace(log_dir: str, sync_mono: float) -> dict | None:
    """Read the newest ``.xplane.pb`` under ``log_dir``.  Returns
    ``{"devices": {plane: [(name, start, dur), ...]}, "planes": {...}}``
    with starts moved onto the monotonic clock through the
    ``bench_clock_sync`` annotation the harness wrote at ``sync_mono``.
    """
    import jax

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes, lines, sync_ns = {}, [], None
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            planes[f"{plane.name}|{line.name}"] = len(events)
            lines.append((plane.name, line.name, events))
            for e in events:
                if sync_ns is None and e.name == SYNC_NAME:
                    sync_ns = e.start_ns
    if sync_ns is None:
        return None
    shift = sync_mono - sync_ns * 1e-9
    tpu = any(p.startswith("/device:TPU:") for p, _, _ in lines)
    devices: dict[str, list] = {}
    for plane, line, events in lines:
        if tpu:
            if not (plane.startswith("/device:TPU:") and line == "XLA Ops"):
                continue
            mods = sorted(
                (m.start_ns, m.start_ns + m.duration_ns, _module(m.name))
                for p, ln, evs in lines if p == plane and ln == "XLA Modules"
                for m in evs)
            named = [(_within(mods, e.start_ns) + _op(e.name), e)
                     for e in events]
        else:   # rehearsal on the CPU backend: ops carry their module
            named = []
            for e in events:
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    named.append(
                        (f"{stats.get('hlo_module', '?')}/{e.name}", e))
            plane = "cpu-rehearsal"
        devices.setdefault(plane, []).extend(
            (name, shift + e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for name, e in named)
    return {"devices": devices, "planes": planes}


def _op(hlo: str) -> str:
    """``%fusion.1 = u8[...] fusion(...)`` -> ``fusion.1``."""
    return hlo.split(" = ")[0].lstrip("%")


def _module(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name) + "/"


def _within(mods: list, t_ns: float) -> str:
    for a, b, name in mods:
        if a <= t_ns < b:
            return name
    return ""


def busy_intervals(events: list, t0: float, t1: float) -> list[tuple]:
    """Union of the device events' intervals, clipped to ``[t0, t1]``."""
    out: list[list[float]] = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        a, b = max(start, t0), min(start + dur, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(iv) for iv in out]


def busy_seconds(trace: dict, t0: float, t1: float) -> float:
    """Seconds an operation ran on the device, averaged over devices."""
    per = [sum(b - a for a, b in busy_intervals(ev, t0, t1))
           for ev in trace["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def idle_pct(trace: dict | None, run: dict) -> float | None:
    if not trace or not trace["devices"]:
        return None
    t0, t1 = run["trace_t0"], run["trace_t1"]
    return 100.0 * (1.0 - busy_seconds(trace, t0, t1) / (t1 - t0))


def kernel_seconds(trace: dict, pattern: str, t0: float, t1: float) -> float:
    """Device time of every op whose ``module/op`` name matches."""
    rx = re.compile(pattern)
    return sum(dur for ev in trace["devices"].values()
               for name, start, dur in ev
               if rx.search(name) and t0 <= start < t1)


def device_ops(trace: dict, t0: float, t1: float, top: int = 10) -> list:
    total: dict[str, float] = {}
    for ev in trace["devices"].values():
        for name, start, dur in ev:
            if t0 <= start < t1:
                total[name] = total.get(name, 0.0) + dur
    return [[n, s] for n, s in sorted(
        total.items(), key=lambda kv: -kv[1])[:top]]


def idle_gaps(trace: dict, spans: list[dict], t0: float, t1: float,
              top: int = 10) -> list:
    """Idle time of the first device, by the host span it falls in: the
    window is sampled every millisecond, and an idle sample goes to the
    span running then that started last (the innermost, as a rule)."""
    if not trace["devices"]:
        return []
    events = next(iter(trace["devices"].values()))
    busy = busy_intervals(events, t0, t1)
    n = max(int((t1 - t0) / SAMPLE_S), 1)
    owner = [-1] * n
    order = sorted(range(len(spans)), key=lambda i: spans[i]["start_mono"])
    for i in order:
        a = max(int((spans[i]["start_mono"] - t0) / SAMPLE_S) + 1, 0)
        b = min(int((spans[i]["end_mono"] - t0) / SAMPLE_S), n - 1)
        if b >= a:
            owner[a:b + 1] = [i] * (b - a + 1)
    short = 0.0
    prev = t0
    for a, b in busy + [(t1, t1)]:
        if a - prev < GAP_FLOOR_S:
            short += a - prev
        prev = b
    is_busy = [False] * n
    for a, b in busy:       # a sample is busy if the device ran through it
        for j in range(int((a - t0) / SAMPLE_S) + 1,
                       int((b - t0) / SAMPLE_S)):
            is_busy[j] = True
    total: dict[str, float] = {}
    for j in range(n):
        if not is_busy[j]:
            name = _span_label(spans[owner[j]]) if owner[j] >= 0 \
                else "no_span_open"
            total[name] = total.get(name, 0.0) + SAMPLE_S
    out = sorted(total.items(), key=lambda kv: -kv[1])[:top - 1]
    out.append(("within_a_launch__gaps_under_50_us_", short))
    return [[name, s] for name, s in out]


def _span_label(span: dict) -> str:
    kind = span["tags"].get("kind")
    return f"{span['name']}:{kind}" if kind else span["name"]


# -- rooflines ---------------------------------------------------------------

def load_peaks(device_kind: str) -> dict:
    """This device's published peaks; a device not in the table is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmarks/harness/peaks.json")
    return table[device_kind]


def gf_matmul_cost(k: int, out: int, S: int) -> tuple[float, float]:
    """(int8 operations, HBM bytes) the algorithm needs for one
    ``(out, k) @ (k, S)`` product over GF(2^8) done as a bit-matrix
    product: (8 out) x (8 k) x S multiply-adds on bits, k*S bytes read
    and out*S written.  Padding and the bit expansion are the
    kernel's choice and count as none of it."""
    return 2.0 * (8 * out) * (8 * k) * S, float((k + out) * S)


def roofline_pct(trace: dict | None, run: dict, *, pattern: str,
                 products: list[tuple[int, int, int]]) -> float | None:
    """Least time the chip could take for ``products`` (a list of
    (k, out, S)) over the device time of the ops matching ``pattern``,
    both inside the traced window."""
    if not trace or not products or not run["peaks"]:
        return None     # no peaks: not the chip, so no share of them
    t = kernel_seconds(trace, pattern, run["trace_t0"], run["trace_t1"])
    if t <= 0:
        return None
    peaks = run["peaks"]
    ops = sum(gf_matmul_cost(*p)[0] for p in products)
    nbytes = sum(gf_matmul_cost(*p)[1] for p in products)
    least = max(ops / (peaks["int8_TOPs"] * 1e12),
                nbytes / (peaks["HBM_GBs"] * 1e9))
    return 100.0 * least / t
