"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding/collective tests run
against ``--xla_force_host_platform_device_count=8`` as the driver's
``dryrun_multichip`` does.  Set CEPH_TPU_TEST_REAL_DEVICE=1 to target the
real accelerator instead.

The pinning itself (before any backend initializes) lives in
ceph_tpu.common.cpumesh, shared with __graft_entry__.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if not os.environ.get("CEPH_TPU_TEST_REAL_DEVICE"):
    try:
        from ceph_tpu.common.cpumesh import pin_virtual_cpu

        pin_virtual_cpu(8)
    except ImportError:
        pass


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps excluded from tier-1 (-m 'not slow')",
    )


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _clear_fault_injector():
    """FAULTS points are process-global: a test that arms one and
    fails (or forgets) must not leak an armed fault into every later
    test in the session."""
    from ceph_tpu.common.fault_injector import FAULTS

    FAULTS.clear()
    yield
    FAULTS.clear()
