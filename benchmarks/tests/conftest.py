"""What the rehearsal of a four-chip cell needs beside the test files
that were here (PR 27):

- four virtual CPU devices, set before JAX starts its backend, so that
  ``test_mesh_metrics.py`` can run the cell through a real 4-device mesh;
- ``test_span_metrics.py`` rehearses every cell with a *single-device*
  encode service and expects every metric listed after PR 25's first to
  read something there.  Three of the four-chip cell's metrics read what
  only a mesh launch or a chip gives (the mesh program in a TPU trace,
  the mesh byte counters, the ``encode_dp`` launch spans), so those
  three cases cannot pass as written; they are marked as expected
  failures here, strictly, and ``test_mesh_metrics.py`` rehearses the
  same readers on a mesh and on synthetic traces instead;
- ``gf_bitmatmul_roofline.read_decode`` (PR 33) is a share of the chip's
  peaks and the CPU has none (``run["peaks"]`` is ``None`` there), so its
  case is an expected failure too; ``test_read_and_fault.py`` reads it
  from a hand-made trace.
"""

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4"
                               ).strip()

MESH_ONLY = ("gf_bitmatmul_roofline.encode_mesh",
             "encode_mesh_pad_share_pct", "encode_launch_host_ms")
CHIP_ONLY = ("gf_bitmatmul_roofline.read_decode",)
SINGLE_DEVICE_REHEARSAL = \
    "test_new_reader_reads_its_cells_and_nothing_where_spans_are_absent"


def pytest_collection_modifyitems(config, items):
    for item in items:
        if getattr(item, "originalname", None) == SINGLE_DEVICE_REHEARSAL \
                and item.callspec.id in MESH_ONLY + CHIP_ONLY:
            item.add_marker(pytest.mark.xfail(strict=True, reason=(
                "reads a mesh launch or the chip's peaks; "
                "test_span_metrics.py's rehearsal gives every cell a "
                "single-device encode service on the CPU")))
