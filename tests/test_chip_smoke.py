"""Tier-1 rehearsal of ``chip_smoke.py``: its phase functions, tiny, on
the CPU backend — the "run it here first" step before the chip, not a
CPU mode of the smoke (which has none: its ``main`` refuses the CPU).

Asserts what the one-chip run asserts: the encode service is given ONE
device (``EncodeService(device=...)``, the mode ``shared()`` selects on
a single TPU), so writes and degraded reads must show
``single_dispatches``, recovery the decode aggregator's launches, scrub
the verifier's — all with zero fallbacks and zero implicit transfers.
"""

import asyncio
import json

import jax

import chip_smoke as cs
from ceph_tpu.parallel import encode_service as es


def _report() -> cs.Report:
    rep = cs.Report()
    devs = jax.devices()
    rep.device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
    return rep


def test_phases_tiny(tmp_path):
    rep = _report()
    cs.phase_kernels(rep, codes=((2, 1),), widths=(4096,))
    assert rep.phases["kernels"]["byte_exact"] == 2

    svc = es.EncodeService(device=jax.devices()[0])
    asyncio.run(asyncio.wait_for(cs.run_cluster_phases(
        rep, data_dir=str(tmp_path), seed=7, n_osds=6, k=2, m=1,
        pg_num=8, n_objects=6, obj_bytes=64 << 10, in_flight=4,
        phase_timeout=60.0, encode_service=svc), 120))
    ph = rep.phases
    assert ph["setup"]["encode_service_mode"] == "single-device"
    assert ph["write"]["encode_service"]["single_dispatches"] > 0
    assert ph["write"]["encode_service"]["coalesced"] >= 6
    assert ph["write"]["stored_shards_equal_host_reference"] == 6 * 3
    assert ph["degraded"]["encode_service"]["single_dispatches"] > 0
    assert ph["recovery"]["decode_aggregator"]["launches"] > 0
    assert ph["scrub"]["scrub_verifier"]["launches"] > 0
    assert ph["counters"]["transfer_guard"]["guard_windows"] > 0
    assert ph["counters"]["transfer_guard"]["host_transfers"] == 0
    assert svc.stats["fallbacks"] == 0

    # the MSR pool's program alone takes ~10 s to trace on the CPU
    # backend (tests/test_jaxmapper.py owns it): replicated pool only
    cs.phase_remap(
        rep, cs.build_remap_map(
            n_hosts=8, osds_per_host=4, rep_pgs=64, ec_pgs=0, ec_size=0,
            ec_min_size=0),
        sample=16, seed=7)
    assert ph["remap"]["pgs_equal_scalar"] == 16
    assert ph["remap"]["remap"] == {"batched_pools": 2}


def test_main_refuses_the_cpu_backend(capsys):
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "", "no result may be printed without an accelerator"
    assert "needs a TPU" in err


def test_verdict_line_is_exactly_the_contract():
    """The chip check refuses any other key, on either level."""
    verdict = json.loads(cs.verdict_line(_report()))
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    dev = verdict["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert isinstance(dev["platform"], str) and isinstance(dev["kind"], str)
    assert type(dev["count"]) is int
