"""The measured window: what ended inside it, rates over all of it,
rates per slice, percentiles, and the one last line."""

from __future__ import annotations

import json
import statistics

MiB = float(1 << 20)


def percentile(samples, p: int) -> float:
    """Nearest-rank percentile (copy of ``loadgen/report.percentile``):
    position ceil(p*n/100) - 1 of the sorted samples."""
    if not samples:
        raise ValueError("percentile of no samples")
    srt = sorted(samples)
    n = len(srt)
    return float(srt[max(0, min((p * n + 99) // 100 - 1, n - 1))])


class Window:
    """``[t0, t0 + seconds)`` on the monotonic clock, cut into slices of
    ``slice_s`` (the part-slice at the end belongs to the window, not to
    the slice statistics)."""

    def __init__(self, t0: float, seconds: float, slice_s: float):
        self.t0 = t0
        self.seconds = seconds
        self.t_end = t0 + seconds
        self.slice_s = min(slice_s, seconds)
        self.n_slices = int(seconds // self.slice_s)
        self.ops: list[tuple[float, float, int, bool]] = []
        #: (monotonic time, running total) readings of a byte counter
        self.readings: list[tuple[float, float]] = []
        #: end of the work, if it ended inside the window (recovery done)
        self.t_done: float | None = None

    def contains(self, t: float) -> bool:
        return self.t0 <= t < self.t_end

    def op_ended(self, t: float, latency_s: float, nbytes: int,
                 ok: bool) -> None:
        if self.contains(t):
            self.ops.append((t, latency_s, nbytes, ok))

    # -- client ops ----------------------------------------------------

    @property
    def acked(self) -> list[tuple[float, float, int, bool]]:
        return [o for o in self.ops if o[3]]

    def throughput_MiB_s(self) -> float:
        """All bytes acknowledged in the window over all of its time."""
        return sum(o[2] for o in self.acked) / MiB / self.seconds

    def slice_rates_MiB_s(self) -> list[float]:
        """Bytes acknowledged per whole slice (an op counts in the slice
        its ack arrives in)."""
        per = [0] * self.n_slices
        for t, _lat, nbytes, _ok in self.acked:
            i = int((t - self.t0) // self.slice_s)
            if i < self.n_slices:
                per[i] += nbytes
        return [b / MiB / self.slice_s for b in per]

    def latency_ms(self, p: int) -> float:
        return 1e3 * percentile([o[1] for o in self.acked], p)

    # -- a byte counter read through the window ------------------------

    def counter_rate_MiB_s(self) -> float:
        """The counter's growth from the window's first reading to its
        last (or to ``t_done``) over that time."""
        (ta, a), (tb, b) = self.readings[0], self.readings[-1]
        if self.t_done is not None:
            tb = self.t_done
        return (b - a) / MiB / (tb - ta)

    def counter_slice_rates_MiB_s(self) -> list[float]:
        """Growth per whole slice; slices that end after ``t_done`` are
        left out."""
        out = []
        whole = self.readings[:self.n_slices + 1]
        for (ta, a), (tb, b) in zip(whole, whole[1:]):
            if self.t_done is None or tb <= self.t_done:
                out.append((b - a) / MiB / (tb - ta))
        return out


def median(values) -> float | None:
    values = list(values)
    return statistics.median(values) if values else None


def last_line(*, correct: bool, attempted: int, failed: int,
              metrics: dict, device: dict, breakdown: dict | None,
              compared: dict) -> str:
    """The contract's last stdout line: these keys and no other; last of
    them ``compared``, each number that decided ``correct`` beside its
    limit (``{"value": v, "max": limit}`` or ``"min"``)."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {name: {"value": float(v), "unit": unit}
                       for name, (v, unit) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)
