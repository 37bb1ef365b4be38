"""Global configuration for the framework.

The reference hard-codes every constant (thresholds, paths, LR params — see
SURVEY.md §5 "Config / flag system"); its only knobs are MLlib's ``setX``
builder pattern, which the estimators here reproduce. This module holds the
few framework-level defaults that Spark keeps in ``SparkConf``.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

#: Conf boolean spellings — THE shared vocabulary for every conf parser
#: (session ``spark.*`` keys, ``spark.serve.*`` keys, env gates). One
#: tuple each, so a new spelling cannot silently diverge between parsers.
CONF_FALSE = ("false", "off", "0", "no")
CONF_TRUE = ("true", "on", "1", "yes")

#: THE ``spark.*`` conf-key registry — every key the engine reads must be
#: declared here (enforced statically by dqlint's ``conf-key`` rule,
#: ``sparkdq4ml_tpu/analysis/rules/conf_keys.py``). The tag records who
#: owns the key's lifecycle:
#:
#: * ``"session"`` — applied by ``session._init_pipeline`` with
#:   save/restore, so one session's setting never leaks process-wide
#:   (the rule verifies the key literal actually appears there);
#: * ``"init"`` — read once during session construction/infrastructure
#:   bring-up (backend probe, compilation cache, observability install,
#:   fault plan, multi-host bootstrap); restored by ``stop()`` where it
#:   mutates process state.
CONF_KEYS = {
    "spark.pipeline.enabled": "session",
    "spark.pipeline.minBucket": "session",
    "spark.pipeline.cacheSize": "session",
    "spark.groupedExec.enabled": "session",
    "spark.explain.memory": "session",
    "spark.explain.caches": "session",
    "spark.serve.enabled": "session",
    "spark.serve.net.enabled": "session",
    "spark.serve.net.port": "session",
    "spark.serve.net.host": "session",
    "spark.serve.net.backlog": "session",
    "spark.serve.net.connTimeoutMs": "session",
    "spark.serve.net.maxFrameBytes": "session",
    "spark.serve.net.streamPageRows": "session",
    "spark.serve.client.retries": "session",
    "spark.serve.client.backoffMs": "session",
    "spark.serve.client.hedging": "session",
    "spark.serve.coalesce.enabled": "session",
    "spark.serve.coalesce.maxDelayMs": "session",
    "spark.serve.coalesce.maxBatch": "session",
    "spark.serve.coalesce.minQueueDepth": "session",
    "spark.audit.enabled": "session",
    "spark.audit.memoryFraction": "session",
    "spark.audit.deviceBudget": "session",
    "spark.audit.constBytes": "session",
    "spark.ingest.streaming": "session",
    "spark.ingest.threads": "session",
    "spark.ingest.chunkBytes": "session",
    "spark.ingest.prefetch": "session",
    "spark.ingest.simd": "session",
    "spark.chaos.seed": "session",
    "spark.chaos.seeds": "session",
    "spark.chaos.soakSeconds": "session",
    "spark.optimizer.enabled": "session",
    "spark.optimizer.level": "session",
    "spark.aqe.enabled": "session",
    "spark.aqe.driftFactor": "session",
    "spark.aqe.broadcastThreshold": "session",
    "spark.aqe.skewFactor": "session",
    "spark.stats.enabled": "session",
    "spark.stats.path": "session",
    "spark.stats.maxEntries": "session",
    "spark.stats.flushOnStop": "session",
    "spark.shard.enabled": "session",
    "spark.shard.minRows": "session",
    "spark.shard.devices": "session",
    "spark.costprof.enabled": "session",
    "spark.costprof.ridge": "session",
    "spark.profiling.maxCaptures": "session",
    "spark.trace.ringSize": "session",
    "spark.trace.retainedSize": "session",
    "spark.trace.exemplars": "session",
    "spark.incident.enabled": "session",
    "spark.incident.dir": "session",
    "spark.incident.maxBundles": "session",
    "spark.incident.cooldownS": "session",
    "spark.incident.sloBurnThreshold": "session",
    "spark.dq.profile.enabled": "session",
    "spark.dq.histogramBins": "session",
    "spark.dq.driftThreshold": "session",
    "spark.dq.baselineMode": "session",
    "spark.observability.enabled": "init",
    "spark.observability.maxSpans": "init",
    "spark.observability.logSpans": "init",
    "spark.faults": "init",
    "spark.faults.seed": "init",
    "spark.recovery.validate": "init",
    "spark.compilation.cache": "init",
    "spark.distributed.coordinator": "init",
    "spark.distributed.numProcesses": "init",
    "spark.distributed.processId": "init",
    "spark.serve.sharedPlanCache": "init",
}

#: Dynamic key families (formatted per site/tenant at runtime): any key
#: starting with one of these prefixes is declared by the family.
CONF_KEY_PREFIXES = (
    "spark.recovery.",   # per-site retry policy (RetryPolicy.from_conf)
    "spark.serve.",      # QueryServer.from_conf tuning family
)


@dataclasses.dataclass
class _Config:
    # Default floating dtype for frame columns and solvers. float32 rides the
    # TPU MXU/VPU natively; tests may select float64 (with jax_enable_x64) for
    # tight golden-number parity on CPU.
    default_float_dtype: jnp.dtype = jnp.float32
    # Default integer dtype (Spark CSV inference yields IntegerType → int32).
    default_int_dtype: jnp.dtype = jnp.int32
    # Rows shown by Frame.show() when no argument is given (Spark default: 20).
    default_show_rows: int = 20
    # Fused expression-pipeline compiler (ops/compiler.py): consecutive
    # compilable Frame.with_column/filter ops coalesce into ONE jitted XLA
    # program per structural plan key (spark.pipeline.enabled conf; False
    # restores the exact per-op eager path).
    pipeline: bool = True
    # Row-slot bucket floor for the pipeline's shape-bucketed padding
    # (rows pad up to the next power of two, never below this).
    pipeline_min_bucket: int = 8
    # Above this row count programs compile at EXACT length instead of a
    # padded bucket: the per-flush pad + unpad copies are O(n) and at
    # this scale cost more than an occasional retrace, while below it
    # bucketing lets frames of different lengths (e.g. two CSV loads)
    # share one compiled program.
    pipeline_exact_threshold: int = 1 << 17
    # Bounded LRU size of the plan-keyed jit cache.
    pipeline_cache_size: int = 256
    # Device-resident grouped execution (ops/segments.py): numeric
    # groupBy/sort/distinct lower to one jitted program (device sort +
    # segment reductions) instead of the host numpy boundary
    # (spark.groupedExec.enabled conf; False restores the legacy path).
    grouped_exec: bool = True
    # EXPLAIN ANALYZE (sql/parser.py): sample device memory at span
    # boundaries during the analyzed query (spark.explain.memory conf) —
    # a live-array census per span; off leaves peak_mem unattributed.
    explain_memory: bool = True
    # Append the jit-cache introspection section (one line per compiled
    # program the query touched) to EXPLAIN ANALYZE output
    # (spark.explain.caches conf).
    explain_caches: bool = True
    # Query-serving layer (serve/): gates session.serve(). False
    # (spark.serve.enabled=false) makes session.serve() refuse to start a
    # server; the layer is otherwise pay-for-use — a process that never
    # starts a QueryServer runs zero serve code (no threads, no metrics).
    serve_enabled: bool = True
    # Network serving front end (serve/net.py): the asyncio socket
    # protocol over the QueryServer — HTTP/1.1 with chunked streaming
    # pages plus the length-prefixed frame protocol. OFF by default
    # (spark.serve.net.enabled): QueryServer.start() reads exactly this
    # one flag when disabled — no socket, no event loop, no thread.
    serve_net_enabled: bool = False
    # Bind point (spark.serve.net.{host,port}): 127.0.0.1 by default —
    # the same unauthenticated-endpoint security posture as the
    # telemetry server; port 0 = ephemeral (tests/soak).
    serve_net_host: str = "127.0.0.1"
    serve_net_port: int = 0
    # Listen backlog (spark.serve.net.backlog).
    serve_net_backlog: int = 64
    # Per-connection read/write timeout in ms
    # (spark.serve.net.connTimeoutMs) — the slow-loris guard: a peer
    # that stalls a request read or a response drain past this is cut
    # with a net.conn_timeout recovery event, never held open.
    serve_net_conn_timeout_ms: int = 10_000
    # Bound on one wire request (frame payload / HTTP head+body) in
    # bytes (spark.serve.net.maxFrameBytes): past it the request is
    # refused with a structured error, bounding per-connection buffers.
    serve_net_max_frame_bytes: int = 4 << 20
    # Rows per streamed result page (spark.serve.net.streamPageRows):
    # a large SELECT leaves the server one page at a time instead of
    # materializing the whole response per client.
    serve_net_stream_page_rows: int = 4096
    # Resilient-client defaults (serve/client.py, RetryPolicy-backed):
    # attempts per call (spark.serve.client.retries), first backoff in
    # ms (spark.serve.client.backoffMs), and opt-in hedging — a second
    # connection racing the first after one backoff interval
    # (spark.serve.client.hedging; idempotency keys keep the hedge
    # exactly-once server-side).
    serve_client_retries: int = 3
    serve_client_backoff_ms: float = 50.0
    serve_client_hedging: bool = False
    # Cross-request plan coalescing (serve/coalesce.py): OFF by default
    # (spark.serve.coalesce.enabled) — QueryServer.start() reads exactly
    # this one flag when disabled, and the per-request dispatch path is
    # byte-for-byte PR-17 behavior (one None check in run_pipeline).
    serve_coalesce_enabled: bool = False
    # Hold window in ms (spark.serve.coalesce.maxDelayMs): how long a
    # batch leader waits for same-plan followers before dispatching; cut
    # short the moment the batch fills.
    serve_coalesce_max_delay_ms: float = 2.0
    # Member cap per batched dispatch (spark.serve.coalesce.maxBatch),
    # clamped further by the admission memory gate pricing the STACKED
    # batch bytes.
    serve_coalesce_max_batch: int = 8
    # Load trigger (spark.serve.coalesce.minQueueDepth): a worker arms
    # the coalescing scope only when the queue depth at pop time is at
    # least this — light load keeps the pure per-request path.
    serve_coalesce_min_queue_depth: int = 2
    # dqaudit — the jaxpr-level program-audit tier (analysis/program/):
    # gates the EXPLAIN `est peak` static-memory column and
    # session.audit_report() (spark.audit.enabled). The auditor is
    # strictly offline/on-demand either way — disabling only removes
    # the EXPLAIN annotation and makes audit_report() refuse.
    audit_enabled: bool = True
    # Static per-program peak-bytes bound must fit this fraction of the
    # device byte budget (spark.audit.memoryFraction).
    audit_memory_fraction: float = 0.9
    # Explicit device byte budget for the static-memory detector
    # (spark.audit.deviceBudget); 0 = use the allocator bytes_limit
    # where the backend exposes one (XLA:CPU exposes none, so the
    # memory gate is advisory-only there unless set).
    audit_device_budget: int = 0
    # Captured-constant size above which the hidden-sync detector flags
    # host-constant capture inside a jitted body
    # (spark.audit.constBytes).
    audit_const_bytes: int = 4096
    # Streaming CSV ingest (frame/native_csv.py): files larger than one
    # chunk parse through the native dq_stream API in bounded chunks cut
    # on structural record boundaries, with a prefetch thread overlapping
    # parse of chunk N+1 with host->device transfer of chunk N
    # (spark.ingest.streaming conf; False restores the exact legacy
    # one-shot native path).
    ingest_streaming: bool = True
    # Parse threads per chunk: 0 = auto (DQCSV_THREADS env, then a
    # size-based heuristic in the native layer), else an explicit cap
    # (spark.ingest.threads).
    ingest_threads: int = 0
    # Chunk size in bytes for the streaming parse — the static per-chunk
    # memory bound; also the streaming threshold: smaller files take one
    # one-shot native call (spark.ingest.chunkBytes).
    ingest_chunk_bytes: int = 8 << 20
    # Bounded prefetch queue depth: how many parsed-but-untransferred
    # chunks the producer thread may run ahead (spark.ingest.prefetch).
    ingest_prefetch: int = 2
    # SIMD tier for the native parse: "auto" (runtime CPU-feature
    # dispatch, overridable by DQCSV_SIMD env), "off" (scalar),
    # "avx2", "avx512" — explicit tiers clamp to what the CPU supports
    # (spark.ingest.simd).
    ingest_simd: str = "auto"
    # Chaos-soak defaults (scripts/chaos_soak.py): base seed of the
    # seeded random fault schedules (spark.chaos.seed), how many seeds
    # the soak sweeps (spark.chaos.seeds), and a minimum per-seed soak
    # duration in seconds — 0 runs each seed's workload exactly once
    # (spark.chaos.soakSeconds). Session-scoped like every other knob;
    # the harness CLI flags override.
    chaos_seed: int = 0
    chaos_seeds: int = 5
    chaos_soak_s: float = 0.0
    # Cost-based plan optimizer (sql/optimizer.py + lowering hooks in
    # ops/compiler.py and ops/segments.py): statstore-driven rewrites
    # over the parsed Query — predicate/projection pushdown, build-side
    # selection, grouped dense-skip, history-informed memory chunking —
    # applied before execution (spark.optimizer.enabled; false runs
    # every query at its literal parse shape, one flag read per query).
    optimizer_enabled: bool = True
    # Rewrite aggressiveness (spark.optimizer.level): 1 = rewrites that
    # preserve physical emission order bit-for-bit (the default); 2 adds
    # join reordering and fused-stage boundary splitting — row MULTISETS
    # stay exact, but physical row order may legally change where SQL
    # imposes none.
    optimizer_level: int = 1
    # Adaptive query execution (sql/adaptive.py + stage-boundary hooks):
    # mid-query re-planning from the rows/bytes THIS execution just
    # observed — build-side flips and broadcast shuffle-skips at the join
    # boundary, downstream re-bucketing after a misestimated filter,
    # skewed-exchange partition splits, and the grouped engine's
    # estimate-informed lowering choice. Every transform is bit-identical
    # by construction (the masked-slot invariant + the partitioned plan's
    # stable order merge); spark.aqe.enabled=false reduces every hook to
    # one flag read and runs the static plan end to end.
    aqe_enabled: bool = True
    # Drift ratio (observed vs estimate, either direction) that triggers
    # a re-plan decision (spark.aqe.driftFactor). Below it the static
    # plan stands — estimates are advisory, re-planning has real cost.
    aqe_drift_factor: float = 4.0
    # Observed build-side byte bound under which a drift-triggered join
    # skips the hash-partition shuffle entirely and runs the single
    # (broadcast-style) plan (spark.aqe.broadcastThreshold).
    aqe_broadcast_threshold: int = 8 << 20
    # Live partition-balance ratio (largest/mean probe rows within one
    # exchange) past which a skewed partition splits into balanced
    # chunks (spark.aqe.skewFactor) — the PR-13 decomposable merge
    # re-sorts the chunk plans back into the exact global order.
    aqe_skew_factor: float = 4.0
    # Plan-statistics observatory (utils/statstore.py): per-plan-key
    # running stats — observed selectivity, wall/compile-ms digests,
    # host syncs, est/measured peak bytes — feeding EXPLAIN's history-
    # informed `est rows` column and (ROADMAP item 4) the cost-based
    # optimizer. spark.stats.enabled=false reduces every producer hook
    # to one flag read (test-pinned no-op).
    stats_enabled: bool = True
    # Snapshot path for cross-session persistence (spark.stats.path);
    # empty = in-memory only. Loaded (merge) at session init, written
    # (merge-don't-clobber, atomic) by stop() when stats_flush_on_stop.
    stats_path: str = ""
    # Bounded per-key entry table (spark.stats.maxEntries): past it the
    # least-recently-updated entry evicts (stats.evict counter).
    stats_max_entries: int = 512
    # Persist on session stop() (spark.stats.flushOnStop).
    stats_flush_on_stop: bool = True
    # Row-sharded frames (parallel/shard.py): Frame._data/_mask lay out
    # row-partitioned across the device mesh, the fused pipeline flush
    # lowers as ONE shard_map program per plan, and grouped execution
    # merges per-shard segment reductions with one cross-shard
    # collective. Off by default (spark.shard.enabled): sharding is a
    # scale feature, activated per session where a multi-device mesh
    # exists; a trivial mesh leaves it inert either way.
    shard_enabled: bool = False
    # Row-count floor below which frames stay single-device
    # (spark.shard.minRows) — placement traffic and the merge collective
    # only pay for themselves at scale; joins/distinct likewise
    # host-fallback below this bound.
    shard_min_rows: int = 1 << 16
    # Cap on the shard device count (spark.shard.devices); 0 = the whole
    # session mesh.
    shard_devices: int = 0
    # Device-cost observatory (utils/costprof.py + analysis/program/
    # costs.py): AOT cost-analysis extraction over every cached program,
    # roofline verdicts in EXPLAIN ANALYZE, shard-skew/exchange-volume
    # accounting, and the /profile telemetry route. Extraction runs
    # lazily on cold surfaces only (report/EXPLAIN/save/scrape);
    # spark.costprof.enabled=false reduces every hook to one flag read
    # and restores byte-identical PR-14 EXPLAIN output.
    costprof_enabled: bool = True
    # Roofline ridge point in FLOPs per byte accessed
    # (spark.costprof.ridge): an operator whose arithmetic intensity is
    # at or above this is verdicted compute-bound, below it
    # memory-bound. The default 8 is a generic accelerator-class ridge;
    # calibrate per chip from a TPU capture (the CPU-sandbox verdicts
    # are structural, not absolute — see README).
    costprof_ridge: float = 8.0
    # Bounded retention of managed jax-profiler captures
    # (spark.profiling.maxCaptures): utils/profiling.start_capture
    # prunes the oldest capture directories past this count.
    profiling_max_captures: int = 4
    # Tail-based request-tree retention (utils/observability.TailSampler):
    # bounded ring of recently completed serving request trees
    # (spark.trace.ringSize) and bounded retained store of keep-policy
    # promoted trees keyed by wire trace id (spark.trace.retainedSize).
    # Only populated while observability is enabled — disabled mode
    # registers nothing.
    trace_ring_size: int = 256
    trace_retained_size: int = 64
    # Emit OpenMetrics exemplars on histogram buckets (the last kept
    # trace id per serve.e2e_ms bucket) in the Prometheus exporter
    # (spark.trace.exemplars) — off by default: exemplar suffixes are an
    # OpenMetrics extension some plain-Prometheus scrapers reject.
    trace_exemplars: bool = False
    # Incident flight recorder (utils/incidents.py): on a trigger
    # (breaker trip, fault-ladder engagement, SLO burn crossing
    # spark.incident.sloBurnThreshold) snapshot a correlated incident
    # bundle — request span tree, metrics deltas, RECOVERY_LOG slice,
    # plan/stats rows. Active only while observability is enabled AND
    # (spark.incident.enabled or spark.incident.dir is set); bundles
    # persist atomically to spark.incident.dir (empty = in-memory only),
    # retention-capped at spark.incident.maxBundles, rate-limited per
    # trigger kind by spark.incident.cooldownS.
    incident_enabled: bool = False
    incident_dir: str = ""
    incident_max_bundles: int = 32
    incident_cooldown_s: float = 5.0
    incident_slo_burn_threshold: float = 8.0
    # Data-quality observatory (utils/dqprof.py): per-column profile
    # sketches + per-rule violation accounting dispatched as deferred
    # device reductions from the flush hook, drained only on cold paths
    # (report / the /dq route / EXPLAIN ANALYZE) — the hot path adds
    # zero counted host syncs. spark.dq.profile.enabled=false reduces
    # every hook to one conf read and pins EXPLAIN byte-identical.
    dq_profile_enabled: bool = True
    # Fixed-bucket histogram resolution over the log-compressed domain
    # (spark.dq.histogramBins) — identical bins values merge
    # bucket-for-bucket across flushes, shards, and sessions.
    dq_histogram_bins: int = 32
    # PSI drift score past this captures an incident bundle and tags
    # the span for tail-keep (spark.dq.driftThreshold).
    dq_drift_threshold: float = 0.25
    # Drift reference policy (spark.dq.baselineMode): "first" adopts a
    # persisted statstore snapshot when present else pins the first
    # drained profile; "persisted" only ever adopts; "off" disables
    # drift scoring.
    dq_baseline_mode: str = "first"


config = _Config()


def float_dtype() -> jnp.dtype:
    return config.default_float_dtype


def int_dtype() -> jnp.dtype:
    return config.default_int_dtype
