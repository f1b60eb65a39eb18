"""Persistent XLA compilation cache for the device programs.

The batched remap (ceph_tpu/osd/remap.py) compiles one XLA program per
(CRUSH topology, rule, size) and the EC batchers one per launch shape;
the in-process program caches only amortize those compiles until the
process exits — a monitor or OSD restart paid them again.  The
reference's analogue never has this problem (ParallelPGMapper is plain
C++, src/osd/OSDMapMapping.h:18), so ours must not either: JAX's
persistent compilation cache serializes compiled executables to disk
keyed by HLO hash and a fresh process warm-starts from it.

Where the cache lives is decided outside the program when
``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that variable itself
and nothing here overrides it.  Otherwise the cache is the fixed
``<checkout>/.jax_cache`` (git-ignored) — fixed because the directory
is part of what makes a later process find the earlier one's entries.

Every program is persisted, not only the slow ones JAX's default
one-second floor would keep: the EC batchers compile some 160 launch
shapes at warm-up that take well under a second each but a minute
together (58.5 s on a v5e, chip run of PR 21), and multi-process
layouts compile the same small programs once per process.  A full
tier-1 run leaves 544 entries / 11 MB in the directory, so keeping
them does not bloat the checkout.
"""

from __future__ import annotations

import logging
import os
import threading

log = logging.getLogger("ceph_tpu.compile_cache")

#: the in-checkout default (ceph_tpu/ops/ -> repo root)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_done = False


def ensure_persistent_cache() -> bool:
    """Idempotently enable the on-disk compile cache.  Returns True if
    it is (now) active.  Called lazily right before the first heavy
    compile so importing ceph_tpu never touches the filesystem."""
    global _done
    if _done:
        return True
    with _lock:
        if _done:
            return True
        try:
            import jax

            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                os.makedirs(DEFAULT_DIR, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        except (OSError, ImportError):
            log.exception(
                "persistent compile cache unavailable: every process "
                "start recompiles its device programs")
            return False
        _done = True
        return True
