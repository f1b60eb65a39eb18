"""Transfer guard: the runtime twin of ctlint's transfer rule family.

The static rules (``ceph_tpu/analysis/rules/transfer.py``) prove at
lint time that no device buffer quietly materializes on the host
inside the I/O path; this module proves the same invariant at RUN
time, mirroring how the prewarm registry (static) pairs with the
``cold_launches`` counter (runtime).  Every steady-state launch the
batchers dispatch — recovery decode, deep-scrub crc / re-encode
compare, encode-farm groups, the mgr analytics digest — runs inside
:func:`no_implicit_transfers`, and:

- the window runs under ``jax.transfer_guard("disallow")``: any
  *implicit* host<->device transfer (a raw numpy arg sliding into a
  jitted call, a device scalar forced through ``bool()``) raises, the
  batcher's existing dispatch fallback answers from the host path
  (correctness unaffected), and the violation lands in the
  ``host_transfers`` counter;
- explicit transfers — ``jax.device_put`` in, ``jax.device_get`` out
  — stay allowed: they are the sanctioned, declared boundary ops the
  static ``device-host-sink`` baseline documents one by one.

Counters live in ``BucketCounters("transfer_guard")``
(``guard_windows``, ``host_transfers``, ``host_exits``) and are
watched by the chaos engine's cold-launch snapshot: a chaos sweep
that grows ``host_transfers`` fails the same way a mid-run XLA
compile does.

Arming: the guard only judges the *steady state* — warmup legitimately
moves buffers while compiling the launch ladder.  Daemons arm it
after EC map-install warmup via :func:`arm` (optionally delayed by
``osd_transfer_guard_window`` seconds); ``osd_transfer_guard = off``
keeps it disarmed, ``on`` arms at first use.  Tests arm explicitly.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager

from ceph_tpu.common.metrics import BucketCounters

#: "on" | "off" | "auto" — auto means "armed once arm() is called"
_DEFAULT_MODE = os.environ.get("CEPH_TPU_TRANSFER_GUARD", "auto")

_mode = _DEFAULT_MODE
_armed_at: float | None = None
_state = threading.local()
_counters: BucketCounters | None = None


def guard_counters() -> BucketCounters:
    """Process-wide transfer-guard perf collection (shape shared with
    the batchers' so chaos/bench snapshots read one dict)."""
    global _counters
    if _counters is None:
        _counters = BucketCounters("transfer_guard")
    return _counters


def configure(mode: str | None = None,
              window_s: float | None = None) -> None:
    """Config wiring (osd_transfer_guard / osd_transfer_guard_window):
    sets the mode and — unless off — arms after ``window_s``."""
    global _mode
    if mode is not None:
        _mode = mode
    if _mode != "off":
        arm(window_s or 0.0)


def arm(delay_s: float = 0.0) -> None:
    """Engage the guard ``delay_s`` seconds from now (call after
    warmup: the steady state starts here)."""
    global _armed_at
    _armed_at = time.monotonic() + max(0.0, delay_s)


def disarm() -> None:
    global _armed_at, _mode
    _armed_at = None
    _mode = _DEFAULT_MODE


def active() -> bool:
    if _mode == "off":
        return False
    if _mode == "on":
        return True
    return _armed_at is not None and time.monotonic() >= _armed_at


def in_guard() -> bool:
    return getattr(_state, "depth", 0) > 0


def _is_transfer_error(exc: BaseException) -> bool:
    msg = str(exc)
    return "transfer" in msg and (
        "Disallowed" in msg or "disallow" in msg)


@contextmanager
def no_implicit_transfers(kind: str):
    """Wrap ONE steady-state launch: implicit host<->device transfers
    inside the window raise (and are counted as ``host_transfers``);
    the exception propagates so the caller's dispatch fallback answers
    from the host path.  No-op while the guard is disarmed."""
    if not active():
        yield
        return
    c = guard_counters()
    c.inc("guard_windows", k=kind)
    _state.depth = getattr(_state, "depth", 0) + 1
    import jax

    try:
        with jax.transfer_guard("disallow"):
            yield
    except Exception as exc:
        if _is_transfer_error(exc):
            c.inc("host_transfers", k=kind)
        raise
    finally:
        _state.depth -= 1


@contextmanager
def host_exit(kind: str):
    """A declared by-design host boundary inside a guard window (the
    final shard persist, a digest consumed host-side): implicit
    transfers are allowed again and counted as ``host_exits`` — the
    runtime mirror of a justified ``device-host-sink`` baseline
    entry."""
    if not (active() and in_guard()):
        yield
        return
    guard_counters().inc("host_exits", k=kind)
    import jax

    with jax.transfer_guard("allow"):
        yield


def snapshot() -> dict[str, int]:
    """{counter: value} for chaos/bench snapshots (delta-checked)."""
    d = guard_counters().dump()
    return {
        "guard_windows": int(d.get("guard_windows", 0)),
        "host_transfers": int(d.get("host_transfers", 0)),
        "host_exits": int(d.get("host_exits", 0)),
    }
