"""Cluster analytics engine: ONE batched reduction over the whole
time-series store.

The mgr's DaemonServer lands every report in a fixed-shape
``(daemons x metrics x window)`` ring buffer (mgr/daemon.py
``TimeSeriesStore``).  This module computes the cluster-wide view —
p50/p95/p99 per metric, EWMA trend per (daemon, metric) series, and
outlier-OSD detection — as a single jitted XLA program over that whole
array: the same shape every tick, prewarmed at mgr start, so after
warmup **zero** XLA compiles happen on the digest path (the
``cold_launches`` discipline the decode/scrub batchers established;
counters land in ``BucketCounters("mgr_analytics")``).

Bit-identical numpy fallback
----------------------------
The contract is that the numpy host path returns *bit-identical*
arrays to the batched device path (tests/test_mgr.py pins it on random
data).  Floating-point reductions cannot promise that (XLA and numpy
order their sums differently), so the engine is **integer-exact** end
to end:

- samples are int64 (the store quantizes at ingest — latencies ride
  as integer microseconds);
- percentiles are nearest-rank selections on sorted int64 arrays
  (sorting identical integers is order-exact on every backend);
- EWMA runs in fixed point: values are scaled by ``2**SCALE_SHIFT``
  and the recurrence ``e += (x*S - e) >> ALPHA_SHIFT`` (alpha = 1/4)
  uses only int64 adds/shifts — ``lax.scan`` and the numpy loop walk
  the identical sequence;
- per-series means are ``(sum << SCALE_SHIFT) // count`` (int64
  floor division — exact and associative);
- an OSD is an outlier on a metric when its mean exceeds
  ``OUTLIER_FACTOR x`` the median of all daemon means (median =
  lower-median selection on sorted int64).

Everything a float could express is recovered on the way out
(``>> SCALE_SHIFT`` -> µs), but the reduction itself never leaves
int64 — that is what makes "numpy fallback bit-identical" a theorem
rather than a hope.
"""

from __future__ import annotations

import collections
import threading

import numpy as np

from ceph_tpu.common.metrics import BucketCounters

#: fixed-point scale for EWMA/means (values carry 2**8 sub-unit bits)
SCALE_SHIFT = 8
#: EWMA alpha = 1 / 2**ALPHA_SHIFT = 0.25
ALPHA_SHIFT = 2
#: percentiles the digest reports (nearest-rank)
PCTS = (50, 95, 99)
#: a daemon mean > OUTLIER_FACTOR * median(means) flags an outlier
OUTLIER_FACTOR = 2

_I64_MAX = np.int64(np.iinfo(np.int64).max)


def analytics_counters() -> BucketCounters:
    """Process-wide analytics perf collection (launch/cold-compile
    accounting, same shape as the decode/scrub batchers' so the chaos
    engine's cold_launches invariant can watch it)."""
    return BucketCounters("mgr_analytics")


def _ordered(values: np.ndarray, valid: np.ndarray, cursor: np.ndarray,
             xp):
    """Unroll each daemon's ring into time order (oldest first):
    ``cursor[d]`` is the next write position, i.e. the oldest sample.
    Pure gather — identical on both backends."""
    D, M, W = values.shape
    idx = (cursor[:, None].astype(np.int64)
           + xp.arange(W, dtype=np.int64)[None, :]) % W  # (D, W)
    gid = xp.broadcast_to(idx[:, None, :], (D, M, W))
    vals = xp.take_along_axis(values, gid, axis=2)
    mask = xp.take_along_axis(valid, gid, axis=2)
    return vals, mask


def _percentiles(vals, mask, xp):
    """(M, len(PCTS)) nearest-rank percentiles over every valid sample
    of each metric (daemons x window flattened)."""
    D, M, W = vals.shape
    flat = xp.swapaxes(vals, 0, 1).reshape(M, D * W)
    fmask = xp.swapaxes(mask, 0, 1).reshape(M, D * W)
    sent = xp.where(fmask, flat, _I64_MAX)
    srt = xp.sort(sent, axis=1)
    n = xp.sum(fmask.astype(np.int64), axis=1)  # (M,)
    cols = []
    for p in PCTS:
        pos = (np.int64(p) * n + np.int64(99)) // np.int64(100) - np.int64(1)
        pos = xp.clip(pos, 0, D * W - 1)
        v = xp.take_along_axis(srt, pos[:, None], axis=1)[:, 0]
        cols.append(xp.where(n > 0, v, np.int64(0)))
    return xp.stack(cols, axis=1), n


def _means(vals, mask, xp):
    """Scaled per-(daemon, metric) means + counts, exact int64."""
    sums = xp.sum(xp.where(mask, vals, np.int64(0)), axis=2)
    cnt = xp.sum(mask.astype(np.int64), axis=2)
    mean_scaled = (sums << np.int64(SCALE_SHIFT)) // xp.maximum(
        cnt, np.int64(1))
    return xp.where(cnt > 0, mean_scaled, np.int64(0)), cnt


def _outliers(mean_scaled, cnt, xp):
    """(D, M) bool: daemon's mean > OUTLIER_FACTOR x lower-median of
    reporting daemons' means on that metric."""
    col = xp.swapaxes(mean_scaled, 0, 1)  # (M, D)
    have = xp.swapaxes(cnt, 0, 1) > 0
    sent = xp.where(have, col, _I64_MAX)
    srt = xp.sort(sent, axis=1)
    nv = xp.sum(have.astype(np.int64), axis=1)
    med_idx = xp.clip((nv - 1) // 2, 0, col.shape[1] - 1)
    med = xp.take_along_axis(srt, med_idx[:, None], axis=1)[:, 0]
    med = xp.where(nv > 0, med, np.int64(0))
    out = have & (col > np.int64(OUTLIER_FACTOR) * med[:, None]) \
        & (med[:, None] > 0)
    return xp.swapaxes(out, 0, 1)


def _ewma_numpy(vals: np.ndarray, mask: np.ndarray) -> np.ndarray:
    D, M, W = vals.shape
    e = np.zeros((D, M), np.int64)
    seen = np.zeros((D, M), bool)
    for t in range(W):
        x = vals[:, :, t]
        v = mask[:, :, t]
        xs = x << np.int64(SCALE_SHIFT)
        upd = e + ((xs - e) >> np.int64(ALPHA_SHIFT))
        e = np.where(v, np.where(seen, upd, xs), e)
        seen = seen | v
    return e


def analyze_numpy(values: np.ndarray, valid: np.ndarray,
                  cursor: np.ndarray) -> dict[str, np.ndarray]:
    """Host reference path — the semantics the batched path must match
    bit for bit."""
    values = values.astype(np.int64, copy=False)
    valid = valid.astype(bool, copy=False)
    vals, mask = _ordered(values, valid, cursor, np)
    pct, nsamples = _percentiles(vals, mask, np)
    mean_scaled, cnt = _means(vals, mask, np)
    outlier = _outliers(mean_scaled, cnt, np)
    return {
        "percentiles": pct,            # (M, 3) int64, raw units
        "n_samples": nsamples,         # (M,) int64
        "ewma_scaled": _ewma_numpy(vals, mask),  # (D, M) int64 << 8
        "mean_scaled": mean_scaled,    # (D, M) int64 << 8
        "count": cnt,                  # (D, M) int64
        "outlier": outlier,            # (D, M) bool
    }


class AnalyticsEngine:
    """The batched engine: one jitted program per (D, M, W) shape.

    The shape is FIXED at construction (from mgr_stats_* config), so
    :meth:`prewarm` compiles the entire launch set — one program — at
    mgr start; every later :meth:`analyze` is a warm launch.  Any
    device failure answers from :func:`analyze_numpy` (bit-identical,
    so callers cannot tell).
    """

    def __init__(self, n_daemons: int, n_metrics: int, window: int,
                 backend: str = "jax"):
        self.shape = (n_daemons, n_metrics, window)
        self.backend = backend
        self.stats = collections.Counter()
        self.metrics = analytics_counters()
        self._warm: set[tuple] = set()
        self._warm_lock = threading.Lock()
        self._jit = None

    # -- device path ---------------------------------------------------

    def _build_jit(self):
        import jax
        import jax.numpy as jnp

        def _ewma_jax(vals, mask):
            xs_all = jnp.moveaxis(vals, 2, 0)   # (W, D, M)
            v_all = jnp.moveaxis(mask, 2, 0)

            def step(carry, xv):
                e, seen = carry
                x, v = xv
                xs = x << np.int64(SCALE_SHIFT)
                upd = e + ((xs - e) >> np.int64(ALPHA_SHIFT))
                e2 = jnp.where(v, jnp.where(seen, upd, xs), e)
                return (e2, seen | v), None

            D, M, _W = vals.shape
            init = (jnp.zeros((D, M), jnp.int64),
                    jnp.zeros((D, M), bool))
            (e, _seen), _ = jax.lax.scan(step, init, (xs_all, v_all))
            return e

        def run(values, valid, cursor):
            vals, mask = _ordered(values, valid, cursor, jnp)
            pct, nsamples = _percentiles(vals, mask, jnp)
            mean_scaled, cnt = _means(vals, mask, jnp)
            outlier = _outliers(mean_scaled, cnt, jnp)
            ewma = _ewma_jax(vals, mask)
            return pct, nsamples, ewma, mean_scaled, cnt, outlier

        return jax.jit(run)

    def _run_device(self, values, valid, cursor,
                    count_cold: bool = True) -> dict[str, np.ndarray]:
        import jax

        from ceph_tpu.ops.compile_cache import ensure_persistent_cache

        ensure_persistent_cache()
        with jax.enable_x64(True):
            if self._jit is None:
                self._jit = self._build_jit()
            shape_key = ("analytics", self.shape)
            if shape_key not in self._warm:
                with self._warm_lock:
                    if shape_key not in self._warm:
                        self._warm.add(shape_key)
                        if count_cold:
                            # an analyze() winning the compile race IS
                            # a cold launch; prewarm passes False and
                            # never touches the counter (it must not
                            # even transiently read non-zero)
                            self.stats["cold_launches"] += 1
                            self.metrics.inc("cold_launches")
            import contextlib

            from ceph_tpu.common.tracing import device_tracer
            from ceph_tpu.common.transfer_guard import (
                no_implicit_transfers,
            )

            # device-launch profiling span on real digest passes only
            # (prewarm's compile is intentional, not a launch to study)
            span_cm = (
                device_tracer().span(
                    "xla_launch", stage="device", kind="mgr_analytics",
                    shape=str(self.shape))
                if count_cold else contextlib.nullcontext()
            )
            # transfers are explicit: the three store-snapshot arrays
            # ride ONE device_put each (they used to slide into the
            # jitted digest as raw numpy — an implicit h2d per array
            # per tick, flagged by ctlint's transfer rules and
            # disallowed under the runtime guard), and the six digest
            # outputs come back in ONE device_get (the by-design host
            # exit: the digest is consumed host-side by the mon/mgr
            # report plane)
            with span_cm, no_implicit_transfers("mgr_analytics"):
                out = self._jit(
                    jax.device_put(values.astype(np.int64)),
                    jax.device_put(valid.astype(bool)),
                    jax.device_put(cursor.astype(np.int64)))
                out = jax.device_get(jax.block_until_ready(list(out)))
        pct, nsamples, ewma, mean_scaled, cnt, outlier = out
        return {
            "percentiles": pct, "n_samples": nsamples,
            "ewma_scaled": ewma, "mean_scaled": mean_scaled,
            "count": cnt, "outlier": outlier,
        }

    # -- public API ----------------------------------------------------

    def prewarm(self) -> int:
        """Compile the engine's single launch shape with zeros.  Call
        at mgr start (via to_thread) — after this, analyze() never
        compiles (cold_launches stays 0).  Returns programs compiled
        (0 when the backend is numpy or the shape is already warm)."""
        if self.backend != "jax":
            return 0
        shape_key = ("analytics", self.shape)
        if shape_key in self._warm:
            return 0
        D, M, W = self.shape
        try:
            self._run_device(np.zeros((D, M, W), np.int64),
                             np.zeros((D, M, W), bool),
                             np.zeros(D, np.int64),
                             count_cold=False)
        except Exception:
            self.stats["prewarm_failures"] += 1
            return 0
        self.stats["prewarmed_shapes"] += 1
        self.metrics.inc("prewarmed_shapes")
        return 1

    def analyze(self, values: np.ndarray, valid: np.ndarray,
                cursor: np.ndarray) -> dict[str, np.ndarray]:
        """One batched pass over the whole store snapshot.  Shapes must
        match the engine's fixed (D, M, W)."""
        assert values.shape == self.shape, (values.shape, self.shape)
        self.stats["passes"] += 1
        self.metrics.inc("passes")
        if self.backend == "jax":
            try:
                out = self._run_device(values, valid, cursor)
                self.stats["launches"] += 1
                self.metrics.inc("launches")
                return out
            except Exception:
                self.stats["fallbacks"] += 1
                self.metrics.inc("fallbacks")
        return analyze_numpy(values, valid, cursor)
