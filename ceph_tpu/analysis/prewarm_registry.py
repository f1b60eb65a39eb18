"""Declared prewarm registry — the static twin of ``cold_launches == 0``.

Every ``jax.jit`` / ``pmap`` / ``shard_map``-wrapped callable reachable
from the I/O-path modules (``osd/``, ``parallel/``,
``mgr/analytics.py``) must appear here, keyed ``module:qualname``, with
a note saying WHICH warmup path compiles it before the I/O path can
reach it.  The device-discipline rule (``device-prewarm``) fails the
lint when a reachable jit site is missing — so adding a new kernel
forces the author to either wire it into a warmup or consciously
register why it cannot compile mid-I/O.

Keep the runtime invariant in mind when editing: an entry here is a
*claim* that chaos' ``cold_launches`` gate stays green; the claim is
checked by ``tools/chaos_run.py`` and the batcher tests, not by ctlint.
"""

from __future__ import annotations

#: ``module:qualname`` of the jit/shard_map site -> which warmup covers
#: it (or why it is allowed to compile outside the I/O path).
PREWARMED: dict[str, str] = {
    "ceph_tpu.ops.rs_kernels:gf_bitmatmul":
        "decode/scrub batcher prewarm() + encode_service prewarm() "
        "compile every (signature, batch, bucket) shape at EC map-"
        "install warmup (osd/daemon.py _warm_ec_profiles)",
    "ceph_tpu.ops.rs_kernels:gf_encode_compare":
        "scrub_batcher.prewarm() compiles the full bucket ladder at EC "
        "warmup; the scrub I/O path only ever launches warmed shapes",
    "ceph_tpu.ops.rs_kernels:gf_bitmatmul_pallas_grouped":
        "on a TPU backend BitmatrixCodec._apply selects it for 2-D data "
        "whose (k, m) leave room for >1 column group — the client EC "
        "write / degraded-read path (EncodeService._run_group) "
        "and the per-op sync path (MatrixErasureCode._apply_device); "
        "encode_service.prewarm() compiles it at EC map-install warmup "
        "for widths up to 64 x the stripe-unit chunk, wider payloads "
        "(a 4 MiB object is S = 512 KiB per op) launch cold and are "
        "counted in cold_launches (ROADMAP S2); checked byte-exact on "
        "the chip by chip_smoke.py's kernels phase",
    "ceph_tpu.ops.rs_kernels:gf_bitmatmul_pallas":
        "same dispatch as the grouped kernel (BitmatrixCodec._apply on "
        "a TPU backend), taken when the code fills the MXU rows alone "
        "or S has a single tile; same warmup and the same cold-launch "
        "gap above the ladder",
    "ceph_tpu.ops.rs_kernels:gf_bitmatmul_pallas_acc":
        "loop body of bench.py's one-launch harness only; not "
        "dispatched by the I/O path",
    "ceph_tpu.ops.hashing:_crc_kernel_jit.kern":
        "scrub_batcher.prewarm() compiles every (crc_lanes, bucket) "
        "shape at EC warmup; lru_cache(1) keeps one program per process",
    "ceph_tpu.mgr.analytics:AnalyticsEngine._build_jit":
        "AnalyticsEngine.prewarm() compiles the single fixed (D, M, W) "
        "shape at mgr start (mgr/daemon.py), before any digest pass",
    "ceph_tpu.crush.jaxmapper:BatchedRuleMapper._build":
        "compiled once per (map, rule) at mapper construction — remap "
        "builds mappers at map-install/peering, never per-op; the "
        "executable is reused across epochs (osd/remap.py)",
    "ceph_tpu.parallel.encode_farm:_program.encode_mesh_cols":
        "the one mesh program (built and jitted once per mesh, one "
        "executable per width bucket): encode_service.prewarm() drives "
        "it through EncodeService._launch, the call the I/O path makes, "
        "for every warmed width at EC map-install warmup and the "
        "benchmark's warm_shapes",
}

#: host-side entry points that dispatch straight into a jitted program:
#: the device-shape rule (``device-raw-shape``) flags call sites in
#: I/O-path modules that feed these a raw ``len()``/``.shape`` derived
#: dimension instead of a pow2-bucketed one.
JIT_ENTRYPOINTS: frozenset[str] = frozenset({
    "gf_bitmatmul",
    "gf_encode_compare",
    "gf_bitmatmul_pallas",
    "gf_bitmatmul_pallas_acc",
    "gf_bitmatmul_pallas_grouped",
    "batched_crc32c_device",
    "mesh_encode_cols",
})

#: the pow2-bucket helpers whose outputs are legitimate launch
#: dimensions (the shape-discipline allowlist)
BUCKET_HELPERS: frozenset[str] = frozenset({
    "pow2_bucket",
    "bucket_lanes",
})

#: donation declarations: ``module:qualname`` of a jitted callable ->
#: positional-arg indices whose buffers the launch may consume
#: (``donate_argnums`` / ``input_output_aliases``).  The transfer rule
#: ``device-nondonated-inout`` flags an in-place update pattern
#: (``x = kernel(..., x, ...)``) whose arg is NOT declared here: every
#: such launch silently allocates a second output buffer.  An entry is
#: a *claim* that the kernel really aliases the buffer (pallas
#: input_output_aliases or jit donate_argnums) — keep the two in sync.
DONATED: dict[str, tuple[int, ...]] = {
    # carry is aliased to the output (input_output_aliases={3: 0} on
    # the inner pallas_call; python-signature position 2)
    "ceph_tpu.ops.rs_kernels:gf_bitmatmul_pallas_acc": (2,),
}

#: declared analytics columns: the gauge names expected to occupy
#: metric slots of the mgr's fixed-shape (daemons x metrics x window)
#: time-series store.  The mgr RESERVES these slots at start
#: (TimeSeriesStore.reserve), so adding a column here both documents
#: it and guarantees it can never be overflow-dropped by transient
#: metrics racing for slots — the declaration the "fixed shape, never
#: resized" prewarm contract requires before a new column may feed
#: the digest (e.g. the progress module's degraded/misplaced EWMAs).
#: mgr_stats_max_metrics must stay >= len(ANALYTICS_COLUMNS).
ANALYTICS_COLUMNS: tuple[str, ...] = (
    "read_lat_us",
    "write_lat_us",
    "subop_w_lat_us",
    "num_pgs",
    "inflight_ops",
    "slow_ops",
    "slow_ops_inflight",
    # event-plane columns (PR 8): cluster-log/progress ETA inputs —
    # integer-exact EWMA of degraded/misplaced PG counts rides the
    # same ONE-launch digest
    "pgs_degraded",
    "pgs_misplaced",
    # load-harness column (loadgen/driver.py): the driver's interval-
    # mean op latency, ingested from its loadgen.* MgrClient session
    # and served back via `mgr digest` for the client-vs-mgr
    # cross-check — slot-reserved so transient metrics can never
    # overflow-drop the series the check depends on
    "load_lat_us",
)
