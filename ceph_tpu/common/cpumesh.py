"""Which JAX platform a process gets: the virtual CPU mesh, or the chip.

Sharding/collective code is exercised without accelerators on
``--xla_force_host_platform_device_count=n`` CPU devices.  The pin
must happen before any JAX backend is initialized (jax *import* is
fine): XLA reads the flag once, at backend start-up.

Shared by tests/conftest.py and __graft_entry__.py's dryrun child.

An accelerator belongs to one process at a time (a second one that
reaches for it fails or hangs in the runtime), so a launcher that
starts several JAX-using processes lets exactly one inherit the
platform environment and starts the rest with ``JAX_PLATFORMS=cpu``;
each child announces which it is with :func:`claim_jax_backend`.
"""

from __future__ import annotations

import os
import re


def force_host_device_count_flags(flags: str, n: int) -> str:
    """Return ``flags`` with --xla_force_host_platform_device_count=n,
    replacing any existing value of that flag."""
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\S+", "", flags or ""
    ).strip()
    return f"{flags} --xla_force_host_platform_device_count={n}".strip()


def pin_virtual_cpu(n: int) -> None:
    """Force this process onto an n-device virtual CPU platform.

    Raises if a JAX backend was already initialized with another
    platform or device count (too late to pin).
    """
    os.environ["XLA_FLAGS"] = force_host_device_count_flags(
        os.environ.get("XLA_FLAGS", ""), n
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) != n:
        raise RuntimeError(
            f"pin_virtual_cpu({n}) came too late: the JAX backend is "
            f"already {devs[0].platform} x{len(devs)}"
        )


def claim_jax_backend(owner: str) -> str:
    """For a process started by a multi-process launcher: one line, for
    its first log record, saying which JAX backend it has.  A process
    the launcher pinned to the CPU says so without touching JAX
    (``owner`` names the sibling that holds the accelerator).  Any
    other owns the host's accelerator and starts the backend NOW,
    before daemon state exists: a chip that cannot be had (held by
    another process, bad platform env) raises here and ends the
    process, instead of surfacing later as a silent host fallback."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return (f"JAX pinned to the cpu backend (the host's accelerator "
                f"belongs to {owner})")
    import jax

    devs = jax.devices()
    return (f"owns the JAX backend: {devs[0].platform} x{len(devs)} "
            f"({devs[0].device_kind})")
