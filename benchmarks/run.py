#!/usr/bin/env python3
"""One process, one cell, one run:

  python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
  python benchmarks/run.py --probe        (no cell: the host and its disks)

The cell's configuration, traffic mix and per-layer metrics are found by
the names ``BENCHMARK.json`` gives them; nothing here lists them.  The
last stdout line is the contract's one JSON object.  There is no CPU
mode: without a TPU the command exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from harness import reduce, verify, window  # noqa: E402
from harness.cluster import Cluster  # noqa: E402
from harness.generator import SET_UP_TIMEOUT, Traffic  # noqa: E402

LOWERINGS = {"n": 0, "listening": False}
FLUSH_BYTES, FLUSHES = 512 << 10, 200


def emit(kind: str, **fields) -> None:
    """An earlier line: free-form, one JSON object."""
    print(json.dumps({"line": kind, **fields}), flush=True)


# -- finding a cell's files by name ------------------------------------------

def load_cell(workload: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, bench["paths"][0], "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "metrics_dir": os.path.join(root, bench["paths"][0],
                                        "layer_metrics")}


def load_layer_metric(metrics_dir: str, name: str):
    """A metric's reader is ``layer_metrics/<name>.py``, loaded by path
    because a name may hold a ``.``."""
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_"),
        os.path.join(metrics_dir, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the disk ------------------------------------------------------------------

def flush_latencies(directory: str, n: int = FLUSHES) -> list[float]:
    """Seconds of ``n`` x (pwrite 512 KiB + fsync) on one file there."""
    buf = os.urandom(FLUSH_BYTES)
    fd, path = tempfile.mkstemp(dir=directory, prefix="flush-")
    try:
        out = []
        for i in range(n):
            t0 = time.perf_counter()
            os.pwrite(fd, buf, (i % 16) * FLUSH_BYTES)
            os.fsync(fd)
            out.append(time.perf_counter() - t0)
        return out
    finally:
        os.close(fd)
        os.unlink(path)


def probe() -> int:
    """The host before it is blamed: cores, RAM, and where data could
    live (the temp directory, the checkout) — /dev/shm for comparison."""
    def fstype(path: str) -> str:
        best = ("", "?")
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if os.path.realpath(path).startswith(mnt) \
                        and len(mnt) > len(best[0]):
                    best = (mnt, typ)
        return f"{best[1]} on {best[0]}"

    with open("/proc/meminfo") as f:
        mem = dict(line.split(":") for line in f)
    emit("probe_host", cpus=os.cpu_count(),
         cpus_usable=len(os.sched_getaffinity(0)),
         mem_total=mem["MemTotal"].strip(),
         mem_available=mem["MemAvailable"].strip(),
         tmpdir=tempfile.gettempdir())
    for d in (tempfile.gettempdir(), ROOT, "/dev/shm"):
        try:
            for rep in range(3):
                lat = flush_latencies(d)
                emit("probe_disk", dir=d, fs=fstype(d), rep=rep,
                     free_bytes=shutil.disk_usage(d).free,
                     flush_ms_median=1e3 * window.percentile(lat, 50),
                     flush_ms_p95=1e3 * window.percentile(lat, 95),
                     flush_ms_max=1e3 * max(lat))
        except OSError as exc:
            emit("probe_disk", dir=d, error=repr(exc))
    return 0


# -- one run ---------------------------------------------------------------------

def _listen_for_lowerings() -> None:
    if LOWERINGS["listening"]:
        return
    import jax.monitoring

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            LOWERINGS["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    LOWERINGS["listening"] = True


class Tracing:
    """The profiler's part of a traced run: a few seconds inside the
    window, python tracing off, one annotation that ties the trace's
    clock to the monotonic one, and a few trivial launches so that
    even a pool with no device work shows the device plane."""

    def __init__(self, log_dir: str, start_s: float, seconds: float):
        import jax

        self.log_dir, self.start_s, self.seconds = log_dir, start_s, seconds
        self.t0 = self.t1 = self.sync_mono = None

        def bench_device_probe(x):
            return x + 1

        self._bump = jax.jit(bench_device_probe)
        self._x = jax.device_put(jax.numpy.zeros((8, 128), jax.numpy.int32))
        jax.block_until_ready(self._bump(self._x))     # compiled in set-up
        self.task = None

    def _start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.sync_mono = time.monotonic()
        with jax.profiler.TraceAnnotation(reduce.SYNC_NAME):
            pass
        self.t0 = time.monotonic()
        for _ in range(8):
            jax.block_until_ready(self._bump(self._x))

    def _stop(self) -> None:
        import jax

        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    async def _run(self, w: window.Window) -> None:
        await asyncio.sleep(max(w.t0 + self.start_s - time.monotonic(), 0))
        await asyncio.to_thread(self._start)
        await asyncio.sleep(min(self.seconds,
                                max(w.t_end - time.monotonic(), 0.1)))
        await asyncio.to_thread(self._stop)

    def on_open(self, w: window.Window) -> None:
        self.task = asyncio.ensure_future(self._run(w))


async def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
                   data_dir: str, device: dict, encode_service=None) -> dict:
    """Boot, warm, set up, measure, verify.  Returns the last line's
    fields (``metrics`` as ``{name: (value, unit)}``)."""
    config, p = spec["config"], spec["traffic"]
    _listen_for_lowerings()
    async with Cluster(config, data_dir, op_timeout=p["op_timeout_s"],
                       encode_service=encode_service) as c:
        await c.create_pool()
        compile_s = {}
        if c.erasure:
            await c.wait_warm(SET_UP_TIMEOUT)
            compile_s = await asyncio.wait_for(c.warm_shapes(
                p["warm_matrices"], p["object_bytes"], p["in_flight"]),
                SET_UP_TIMEOUT)
        await c.client.wait_clean(timeout=SET_UP_TIMEOUT)
        if c.up_osds() != c.n_osds:
            raise RuntimeError(f"only {c.up_osds()}/{c.n_osds} OSDs up")
        for tr in c.tracers():
            tr.set_ring_max(1 << 20)
        t = Traffic(c, p, seed)
        await t.touch_every_pg()
        await t.prefill()
        flush_s = (await asyncio.to_thread(flush_latencies, data_dir)
                   if trace else [])
        tracing = Tracing(os.path.join(data_dir, "trace"),
                          **p["trace"]) if trace else None
        acting_before = None
        if p.get("fault"):
            acting_before = {name: c.acting_of(name)[1] for name in t.acked}
        if t.reads:     # no read is in flight to an OSD that stops
            epoch = await t.apply_fault()
            await t.start_loop_and_warm_up()
        else:
            await t.start_loop_and_warm_up()
            epoch = await t.apply_fault()
        before = {**c.counters(), "jax.lowerings": LOWERINGS["n"]}
        emit("setup", compile_s=compile_s, ops_ended=t.ended,
             fault=p.get("fault"), out_epoch=epoch)
        w = await t.run_window(
            seconds, on_open=tracing.on_open if tracing else None)
        after = {**c.counters(), "jax.lowerings": LOWERINGS["n"]}
        setup_s = w.t0 - T_PROCESS
        if tracing:
            await tracing.task
        await t.drain()
        while epoch is not None and c.client.osdmap.epoch < epoch:
            await c.client._wait_new_map(c.client.osdmap.epoch, timeout=1.0)
        verdict = await t.verify(acting_before)
        spans = [s for tr in c.tracers() for s in tr.dump(limit=1 << 20)] \
            if trace else []

    delta = {k: after[k] - before.get(k, 0) for k in after}
    must_be_0 = {k: delta.get(k, 0) for k in (
        "jax.lowerings", "encode.cold_launches", "decode.cold_launches",
        "encode.fallbacks", "decode.fallbacks", "plugin.fallbacks",
        "guard.host_transfers")}
    if verdict["loss"] == "stays_degraded":    # down and in: nothing recovers
        must_be_0["osd.recovery_ops"] = delta.get("osd.recovery_ops", 0)
    acked = w.acked
    if p.get("counter"):
        attempted = int(delta.get(p["attempted_counter"], 0))
        failed = int(sum(v for k, v in must_be_0.items() if "fallbacks" in k))
    else:
        attempted, failed = len(w.ops), len(w.ops) - len(acked)
    device_work = (delta.get("encode.single_dispatches", 0)
                   + delta.get("encode.dp_dispatches", 0)
                   + delta.get("encode.tp_dispatches", 0)
                   + delta.get("decode.launches", 0))
    compared = {
        **verify.limits(verdict),
        "reads_wrong": {"value": t.wrong, "max": 0},
        "attempted": {"value": attempted, "min": 1},
        **{f"must_be_0.{k}": {"value": v, "max": 0}
           for k, v in must_be_0.items()}}
    if c.erasure:
        compared["device_launches"] = {"value": device_work, "min": 1}
    if p.get("counter"):    # the program's bytes, held to the stores'
        compared["counter_bytes"] = {
            "value": delta.get(p["counter"], 0),
            "max": 1.05 * verdict["moved_bytes_present"]}
    emit("window", seconds=seconds, ops_ended=len(w.ops),
         ops_acked=len(acked), slices=w.n_slices, t_done_s=None
         if w.t_done is None else w.t_done - w.t0, must_be_0=must_be_0,
         device_launches=device_work, verify=verdict,
         reads_compared=len(w.ops) if t.reads else 0,
         reads_wrong=t.wrong, counters=delta)

    values: dict[str, float | None] = {"setup_s": setup_s}
    if acked:
        values["throughput_MiB_s"] = w.throughput_MiB_s()
    if p.get("counter"):
        values[p["counter_metric"]] = w.counter_rate_MiB_s()
    out = {"correct": verify.within(compared), "attempted": attempted,
           "failed": failed, "device": dict(device), "breakdown": None,
           "compared": compared}
    if not trace:
        missing = [m["name"] for m in spec["end_to_end"]
                   if values.get(m["name"]) is None]
        if missing:
            raise RuntimeError(f"cell reported no {missing}")
        out["metrics"] = {m["name"]: (values[m["name"]], m["unit"])
                          for m in spec["end_to_end"]}
        return out

    profile = reduce.load_trace(tracing.log_dir, tracing.sync_mono)
    run = {"window": w, "acked_ops": len(acked), "config": config,
           "traffic": p, "flush_s": flush_s,
           "trace_t0": tracing.t0, "trace_t1": tracing.t1,
           "peaks": reduce.load_peaks(device["kind"])
           if device["platform"] == "tpu" else None}
    in_window = reduce.spans_in(spans, w.t0, w.t_end)
    out["metrics"] = {}
    for m in spec["per_layer"]:
        v = load_layer_metric(spec["metrics_dir"], m["name"]).compute(
            in_window, delta, profile, run)
        if v is not None:
            out["metrics"][m["name"]] = (v, m["unit"])
    if profile:
        in_trace = [s for s in spans if s.get("end_mono") is not None
                    and s["end_mono"] > tracing.t0
                    and s["start_mono"] < tracing.t1]
        out["device"]["busy_s"] = reduce.busy_seconds(
            profile, tracing.t0, tracing.t1)
        out["device"]["window_s"] = tracing.t1 - tracing.t0
        out["breakdown"] = {
            "device_ops": reduce.device_ops(profile, tracing.t0, tracing.t1),
            "idle_gaps": reduce.idle_gaps(profile, in_trace, tracing.t0,
                                          tracing.t1)}
        emit("trace", planes=profile["planes"], spans=len(spans))
    return out


def device_identity(chips: int) -> dict | None:
    """The device as JAX reports it, or None when this is no TPU host
    with the chips the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmarks/run.py: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} x {devs[0].platform!r} (there is no CPU mode)",
              file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--probe", action="store_true")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    if args.probe:
        return probe()
    if not args.workload or args.seconds is None:
        ap.error("--workload and --seconds are required")
    spec = load_cell(args.workload)
    device = device_identity(spec["cell"]["chips"])
    if device is None:
        return 1

    from ceph_tpu.ops.compile_cache import ensure_persistent_cache
    from ceph_tpu.parallel import encode_service

    # the backend starts before any daemon exists (beacon grace is 4 s)
    encode_service.shared()
    if not ensure_persistent_cache():
        raise RuntimeError("persistent compile cache is off")

    data_dir = tempfile.mkdtemp(prefix="bench-")
    try:
        free = shutil.disk_usage(data_dir).free
        if free < spec["config"]["store_free_bytes_min"]:
            raise SystemExit(
                f"{data_dir} has {free} bytes free, the configuration "
                f"needs {spec['config']['store_free_bytes_min']} for a "
                "window's writes")
        out = asyncio.run(run_cell(
            spec, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), data_dir=data_dir, device=device))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    out["device"]["memory_peak_bytes"] = memory_peak_bytes()
    for name, x in out["compared"].items():     # stderr's last lines
        print(f"compared {name} {json.dumps(x)}", file=sys.stderr)
    sys.stderr.flush()
    print(window.last_line(**out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
