"""Persistent XLA compile cache (ceph_tpu/ops/compile_cache.py): a
cold process must reuse executables compiled by an earlier one — the
ParallelPGMapper never pays a startup compile (reference
src/osd/OSDMapMapping.h:18), so the batched remap must not either
(r4 weak #2: 193 s first-epoch compile on every mon restart)."""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import sys, time, os
sys.path.insert(0, {repo!r})
import jax.monitoring
events = []
jax.monitoring.register_event_listener(lambda name, **kw: events.append(name))
from ceph_tpu.crush import builder as B
from ceph_tpu.crush.types import CrushMap
from ceph_tpu.osd.osdmap import OSDMap
from ceph_tpu.osd.remap import BatchedClusterMapper
from ceph_tpu.osd.types import PgPool, PoolType
crush = CrushMap()
B.build_hierarchy(crush, osds_per_host=4, n_hosts=8)
om = OSDMap(crush=crush)
for o in range(32):
    om.new_osd(o, weight=0x10000, up=True)
root = om.crush.bucket_names["default"]
fd = om.crush.type_id("host")
rule = B.add_simple_rule(om.crush, root, fd, mode="firstn")
om.pools[1] = PgPool(id=1, type=PoolType.REPLICATED, size=3, min_size=2,
                     crush_rule=rule, pg_num=64, pgp_num=64)
t0 = time.perf_counter()
BatchedClusterMapper(om).map_cluster()
print("ELAPSED", time.perf_counter() - t0)
print("HITS", events.count("/jax/compilation_cache/cache_hits"))
print("MISSES", events.count("/jax/compilation_cache/cache_misses"))
"""


def test_cache_populates_and_speeds_cold_start(tmp_path):
    # the cache is placed from outside: JAX reads the variable itself
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTEST_CURRENT_TEST", None)

    def run() -> dict[str, float]:
        r = subprocess.run(
            [sys.executable, "-c", _PROBE.format(repo=REPO)],
            capture_output=True, text=True, env=env, check=True,
        )
        got = {w[0]: float(w[1]) for w in map(str.split, r.stdout.splitlines())
               if len(w) == 2 and w[0] in ("ELAPSED", "HITS", "MISSES")}
        assert len(got) == 3, r.stdout + r.stderr
        return got

    cold = run()
    entries = sorted(os.listdir(tmp_path))
    assert entries, "persistent cache dir stayed empty"
    assert cold["HITS"] == 0 and cold["MISSES"] >= 1, cold
    # the warm process compiles nothing: every executable comes off
    # the disk and the directory does not grow.  Counted from JAX's own
    # cache events, not inferred from wall time -- tracing still runs
    # in the warm process (~3.5 s of a ~5 s cold start on the CPU CI
    # host), so a time ratio sits too close to scheduler jitter.
    warm = run()
    assert warm["MISSES"] == 0 and warm["HITS"] == cold["MISSES"], (
        cold, warm)
    assert sorted(os.listdir(tmp_path)) == entries
    print(f"cold {cold['ELAPSED']:.2f} s, warm {warm['ELAPSED']:.2f} s")


def _config_updates(monkeypatch) -> list[tuple]:
    """Re-run ensure_persistent_cache() from scratch, recording every
    jax.config.update it makes instead of applying it."""
    import jax

    from ceph_tpu.ops import compile_cache

    calls: list[tuple] = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v)))
    monkeypatch.setattr(compile_cache, "_done", False)
    assert compile_cache.ensure_persistent_cache()
    return calls


def test_env_dir_means_no_dir_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert "jax_compilation_cache_dir" not in dict(
        _config_updates(monkeypatch))


def test_unset_env_uses_fixed_in_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert dict(_config_updates(monkeypatch))[
        "jax_compilation_cache_dir"] == os.path.join(REPO, ".jax_cache")
