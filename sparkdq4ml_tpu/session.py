"""TpuSession — the ``SparkSession`` equivalent.

Covers the session surface the reference exercises
(`DataQuality4MachineLearningApp.java:38-49`): builder with
``appName``/``master``/``getOrCreate``, the UDF registry, the reader, SQL over
temp views, and — the TPU-native part — the device mesh that replaces Spark's
executor pool (SURVEY.md §3.1). There is no session daemon: "starting" a
session is discovering devices and building a ``jax.sharding.Mesh``.

Threading model (session vs server)
-----------------------------------

* The **session is a process singleton** (Spark ``getOrCreate``
  semantics). ``builder().get_or_create()`` is thread-safe — a
  double-checked lock (:data:`_ACTIVE_LOCK`) guarantees racing threads
  get ONE session object, never two half-initialized ones.
* **Frames and queries are safe to share across threads**: frame flushes
  serialize on the pipeline flush lock, the plan/jit caches and metric
  registries are lock-protected, and grouped execution serializes its
  device path. Concurrent ``session.sql`` calls against the SAME catalog
  are safe for reads; concurrent DDL (``CREATE VIEW``) on one catalog
  last-writer-wins like Spark temp views.
* **Multi-tenant concurrency belongs to the serving layer**:
  :meth:`TpuSession.serve` returns the process :class:`~sparkdq4ml_tpu.
  serve.QueryServer`, which gives each tenant its own temp-view catalog,
  admission control, and SLO metrics over the shared engine. Prefer it
  over hand-rolled threads when callers are independent workloads.
* **Conf mutation is session-scoped and lock-protected**: the
  ``_init_pipeline`` save/restore of process config
  (:data:`_CONF_LOCK`) cannot interleave with a concurrent ``stop()``
  restoring it. ``stop()`` drains the serving layer FIRST, so in-flight
  served queries never observe a half-restored config.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

import jax

from .frame.csv import DataFrameReader
from .ops.rules import register_builtin_rules
from .ops.udf import UDFRegistry, default_registry
from .parallel.mesh import make_mesh, parse_master
from .sql.catalog import Catalog, default_catalog
from .sql.parser import execute as _sql_execute

logger = logging.getLogger("sparkdq4ml_tpu.session")

_ACTIVE: Optional["TpuSession"] = None
#: Guards the active-session singleton (builder/get_or_create/stop): the
#: double-checked lock behind Spark's one-session-per-process contract.
_ACTIVE_LOCK = threading.Lock()
#: Guards the session-scoped config save/restore (_init_pipeline/stop):
#: a builder re-init on one thread and a stop() on another must not
#: interleave their read-modify-write of the process config.
_CONF_LOCK = threading.Lock()

#: Conf boolean spellings (session-scoped keys) — the shared vocabulary
#: from config.py, so spark.serve.enabled=no and the serve layer's own
#: parser can never disagree.
from .config import CONF_FALSE as _CONF_FALSE  # noqa: E402
from .config import CONF_TRUE as _CONF_TRUE  # noqa: E402


#: Where the persistent XLA compile cache lives when the environment names
#: no ``JAX_COMPILATION_CACHE_DIR``: one git-ignored directory at the root
#: of this checkout. The path is part of every cache key, so it is the
#: same for tests, examples and ``chip_smoke.py`` and never moves.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure_compilation_cache(enable: bool = True) -> None:
    """Turn JAX's persistent compilation cache on (or off).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so no
    directory is set in code and nothing in it is stamped or deleted.
    Unset: :data:`COMPILE_CACHE_DIR`. On an accelerator every compile is
    persisted (JAX's stock thresholds skip the sub-second compiles this
    engine is made of); XLA:CPU keeps the thresholds it finds, because
    its AOT loader logs a feature-mismatch error on every reload. Off
    flips JAX's own switch and leaves the directory alone. Shared by
    :class:`TpuSession` and ``tests/conftest.py``."""
    from jax.experimental.compilation_cache import compilation_cache as _cc

    if enable and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        except OSError as e:
            logger.warning("compilation cache disabled: %s", e)
            enable = False
        else:
            jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", enable)
    if enable and jax.default_backend() != "cpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches "is the cache in use?" at the first compile; one that ran
    # before this call would have pinned it to the other answer.
    _cc.reset_cache()


class TpuSession:
    """Entry point: device mesh + catalog + UDF registry + reader."""

    def __init__(self, app_name: str = "sparkdq4ml-tpu",
                 master: Optional[str] = None,
                 conf: Optional[dict] = None,
                 register_rules: bool = False):
        self.app_name = app_name
        self.master = master
        self.conf: dict[str, str] = dict(conf or {})
        self._init_faults()
        self._init_distributed()
        self._ensure_backend()
        n = parse_master(master)
        self.mesh = make_mesh(n)
        # Chaos hook: a scheduled ``mesh:device_drop`` spec shrinks the
        # session mesh — the lost-worker scenario, exercised end-to-end by
        # the resilience suite. No-op without an active fault plan.
        from .utils import faults as _faults

        self.mesh = _faults.degrade_mesh("mesh", self.mesh)
        self.catalog: Catalog = default_catalog()
        self.udf: UDFRegistry = default_registry()
        if register_rules:
            register_builtin_rules(self.udf)
        self._init_compilation_cache()
        self._init_observability()
        self._init_pipeline()
        logger.debug("session %r: %d device(s), platform=%s", app_name,
                     self.num_devices, jax.devices()[0].platform)

    def _init_pipeline(self) -> None:
        """Configure the fused expression-pipeline compiler
        (``ops/compiler.py``) from session conf — ON by default:

            .config("spark.pipeline.enabled", "false")   # exact eager path
            .config("spark.pipeline.minBucket", 8)       # padding floor
            .config("spark.pipeline.cacheSize", 256)     # plan-key LRU

        Flipping ``enabled`` also clears the plan-keyed jit cache so a
        disable→enable cycle never serves plans compiled under different
        bucket settings. Settings this session changes are remembered
        and restored by :meth:`stop` — pipeline conf is session-scoped
        like the fault plan, never a process-wide leak."""
        from .config import config as _cfg
        from .ops import compiler as _compiler

        with _CONF_LOCK:
            saved = getattr(self, "_pipeline_saved", None) or {}

            def _set(attr, value):
                saved.setdefault(attr, getattr(_cfg, attr))
                setattr(_cfg, attr, value)

            val = str(self.conf.get("spark.pipeline.enabled", "")).lower()
            if val in _CONF_FALSE:
                _set("pipeline", False)
                _compiler.clear_cache()
            elif val in _CONF_TRUE:
                _set("pipeline", True)
            if "spark.pipeline.minBucket" in self.conf:
                _set("pipeline_min_bucket",
                     int(self.conf["spark.pipeline.minBucket"]))
                _compiler.clear_cache()
            if "spark.pipeline.cacheSize" in self.conf:
                _set("pipeline_cache_size",
                     int(self.conf["spark.pipeline.cacheSize"]))
            # Device-resident grouped execution (ops/segments.py) rides the
            # same session-scoped save/restore:
            #     .config("spark.groupedExec.enabled", "false") # host groupBy
            gval = str(self.conf.get("spark.groupedExec.enabled", "")).lower()
            if gval in _CONF_FALSE:
                from .ops import segments as _segments

                _set("grouped_exec", False)
                _segments.clear_cache()
            elif gval in _CONF_TRUE:
                _set("grouped_exec", True)
            # EXPLAIN ANALYZE knobs (sql/parser.py) and the serving-layer
            # gate (serve/) ride the same session-scoped save/restore:
            #     .config("spark.explain.memory", "false")  # no mem sampling
            #     .config("spark.explain.caches", "false")  # no cache section
            #     .config("spark.serve.enabled", "false")   # serve() refuses
            for conf_key, attr in (
                    ("spark.explain.memory", "explain_memory"),
                    ("spark.explain.caches", "explain_caches"),
                    ("spark.serve.enabled", "serve_enabled"),
                    ("spark.audit.enabled", "audit_enabled"),
                    ("spark.ingest.streaming", "ingest_streaming")):
                v = str(self.conf.get(conf_key, "")).lower()
                if v in _CONF_FALSE:
                    _set(attr, False)
                elif v in _CONF_TRUE:
                    _set(attr, True)
            # Network serving front end (serve/net.py + serve/client.py),
            # session-scoped like everything above:
            #     .config("spark.serve.net.enabled", "true")  # socket on
            #     .config("spark.serve.net.port", 8765)       # 0=ephemeral
            #     .config("spark.serve.net.host", "0.0.0.0")  # widen bind
            #     .config("spark.serve.net.connTimeoutMs", 5000)
            #     .config("spark.serve.net.maxFrameBytes", 1 << 20)
            #     .config("spark.serve.net.streamPageRows", 1024)
            #     .config("spark.serve.client.retries", 5)
            #     .config("spark.serve.client.backoffMs", 25)
            #     .config("spark.serve.client.hedging", "true")
            nval = str(self.conf.get("spark.serve.net.enabled",
                                     "")).lower()
            if nval in _CONF_FALSE:
                _set("serve_net_enabled", False)
            elif nval in _CONF_TRUE:
                _set("serve_net_enabled", True)
            if "spark.serve.net.port" in self.conf:
                _set("serve_net_port",
                     int(self.conf["spark.serve.net.port"]))
            if "spark.serve.net.host" in self.conf:
                _set("serve_net_host",
                     str(self.conf["spark.serve.net.host"]))
            if "spark.serve.net.backlog" in self.conf:
                _set("serve_net_backlog",
                     int(self.conf["spark.serve.net.backlog"]))
            if "spark.serve.net.connTimeoutMs" in self.conf:
                _set("serve_net_conn_timeout_ms",
                     int(self.conf["spark.serve.net.connTimeoutMs"]))
            if "spark.serve.net.maxFrameBytes" in self.conf:
                _set("serve_net_max_frame_bytes",
                     int(self.conf["spark.serve.net.maxFrameBytes"]))
            if "spark.serve.net.streamPageRows" in self.conf:
                _set("serve_net_stream_page_rows",
                     int(self.conf["spark.serve.net.streamPageRows"]))
            if "spark.serve.client.retries" in self.conf:
                _set("serve_client_retries",
                     int(self.conf["spark.serve.client.retries"]))
            if "spark.serve.client.backoffMs" in self.conf:
                _set("serve_client_backoff_ms",
                     float(self.conf["spark.serve.client.backoffMs"]))
            hval = str(self.conf.get("spark.serve.client.hedging",
                                     "")).lower()
            if hval in _CONF_FALSE:
                _set("serve_client_hedging", False)
            elif hval in _CONF_TRUE:
                _set("serve_client_hedging", True)
            # Cross-request plan coalescing (serve/coalesce.py),
            # session-scoped like the net front end above:
            #     .config("spark.serve.coalesce.enabled", "true")
            #     .config("spark.serve.coalesce.maxDelayMs", 2)
            #     .config("spark.serve.coalesce.maxBatch", 8)
            #     .config("spark.serve.coalesce.minQueueDepth", 2)
            coval = str(self.conf.get("spark.serve.coalesce.enabled",
                                      "")).lower()
            if coval in _CONF_FALSE:
                _set("serve_coalesce_enabled", False)
            elif coval in _CONF_TRUE:
                _set("serve_coalesce_enabled", True)
            if "spark.serve.coalesce.maxDelayMs" in self.conf:
                _set("serve_coalesce_max_delay_ms",
                     float(self.conf["spark.serve.coalesce.maxDelayMs"]))
            if "spark.serve.coalesce.maxBatch" in self.conf:
                _set("serve_coalesce_max_batch",
                     int(self.conf["spark.serve.coalesce.maxBatch"]))
            if "spark.serve.coalesce.minQueueDepth" in self.conf:
                _set("serve_coalesce_min_queue_depth",
                     int(self.conf["spark.serve.coalesce.minQueueDepth"]))
            # dqaudit thresholds (analysis/program/), session-scoped like
            # everything above:
            #     .config("spark.audit.enabled", "false")  # no est peak
            #     .config("spark.audit.memoryFraction", 0.8)
            #     .config("spark.audit.deviceBudget", 8 << 30)  # bytes
            #     .config("spark.audit.constBytes", 65536)
            if "spark.audit.memoryFraction" in self.conf:
                _set("audit_memory_fraction",
                     float(self.conf["spark.audit.memoryFraction"]))
            if "spark.audit.deviceBudget" in self.conf:
                _set("audit_device_budget",
                     int(self.conf["spark.audit.deviceBudget"]))
            if "spark.audit.constBytes" in self.conf:
                _set("audit_const_bytes",
                     int(self.conf["spark.audit.constBytes"]))
            # Streaming-ingest tuning (frame/native_csv.py), session-scoped
            # like everything above:
            #     .config("spark.ingest.streaming", "false") # legacy one-shot
            #     .config("spark.ingest.threads", 4)         # parse threads
            #     .config("spark.ingest.chunkBytes", 1 << 20) # chunk bound
            #     .config("spark.ingest.prefetch", 2)        # queue depth
            #     .config("spark.ingest.simd", "off")        # scalar tier
            if "spark.ingest.threads" in self.conf:
                _set("ingest_threads", int(self.conf["spark.ingest.threads"]))
            if "spark.ingest.chunkBytes" in self.conf:
                _set("ingest_chunk_bytes",
                     int(self.conf["spark.ingest.chunkBytes"]))
            if "spark.ingest.prefetch" in self.conf:
                _set("ingest_prefetch",
                     int(self.conf["spark.ingest.prefetch"]))
            if "spark.ingest.simd" in self.conf:
                _set("ingest_simd",
                     str(self.conf["spark.ingest.simd"]).lower())
            # Chaos-soak defaults (scripts/chaos_soak.py), session-scoped
            # like everything above:
            #     .config("spark.chaos.seed", 7)        # schedule base
            #     .config("spark.chaos.seeds", 50)      # seeds to sweep
            #     .config("spark.chaos.soakSeconds", 30) # per-seed floor
            if "spark.chaos.seed" in self.conf:
                _set("chaos_seed", int(self.conf["spark.chaos.seed"]))
            if "spark.chaos.seeds" in self.conf:
                _set("chaos_seeds", int(self.conf["spark.chaos.seeds"]))
            if "spark.chaos.soakSeconds" in self.conf:
                _set("chaos_soak_s",
                     float(self.conf["spark.chaos.soakSeconds"]))
            # Cost-based plan optimizer (sql/optimizer.py), session-scoped
            # like everything above:
            #     .config("spark.optimizer.enabled", "false") # literal plans
            #     .config("spark.optimizer.level", 2)  # + reorder/split
            oval = str(self.conf.get("spark.optimizer.enabled",
                                     "")).lower()
            if oval in _CONF_FALSE:
                _set("optimizer_enabled", False)
            elif oval in _CONF_TRUE:
                _set("optimizer_enabled", True)
            if "spark.optimizer.level" in self.conf:
                _set("optimizer_level",
                     int(self.conf["spark.optimizer.level"]))
            # Adaptive query execution (sql/adaptive.py), session-scoped
            # like everything above:
            #     .config("spark.aqe.enabled", "false")  # static plans
            #     .config("spark.aqe.driftFactor", 8.0)  # replan trigger
            #     .config("spark.aqe.broadcastThreshold", 1 << 20)
            #     .config("spark.aqe.skewFactor", 2.0)   # split trigger
            aval = str(self.conf.get("spark.aqe.enabled", "")).lower()
            if aval in _CONF_FALSE:
                _set("aqe_enabled", False)
            elif aval in _CONF_TRUE:
                _set("aqe_enabled", True)
            if "spark.aqe.driftFactor" in self.conf:
                _set("aqe_drift_factor",
                     float(self.conf["spark.aqe.driftFactor"]))
            if "spark.aqe.broadcastThreshold" in self.conf:
                _set("aqe_broadcast_threshold",
                     int(self.conf["spark.aqe.broadcastThreshold"]))
            if "spark.aqe.skewFactor" in self.conf:
                _set("aqe_skew_factor",
                     float(self.conf["spark.aqe.skewFactor"]))
            # Plan-stats observatory (utils/statstore.py), session-scoped
            # like everything above:
            #     .config("spark.stats.enabled", "false")   # hooks no-op
            #     .config("spark.stats.path", "/x/stats.jsonl")  # persist
            #     .config("spark.stats.maxEntries", 1024)   # entry bound
            #     .config("spark.stats.flushOnStop", "false")
            sval = str(self.conf.get("spark.stats.enabled", "")).lower()
            if sval in _CONF_FALSE:
                _set("stats_enabled", False)
            elif sval in _CONF_TRUE:
                _set("stats_enabled", True)
            if "spark.stats.path" in self.conf:
                _set("stats_path", str(self.conf["spark.stats.path"]))
            if "spark.stats.maxEntries" in self.conf:
                _set("stats_max_entries",
                     int(self.conf["spark.stats.maxEntries"]))
            fval = str(self.conf.get("spark.stats.flushOnStop", "")).lower()
            if fval in _CONF_FALSE:
                _set("stats_flush_on_stop", False)
            elif fval in _CONF_TRUE:
                _set("stats_flush_on_stop", True)
            # Row-sharded frames (parallel/shard.py), session-scoped
            # like everything above:
            #     .config("spark.shard.enabled", "true")  # shard frames
            #     .config("spark.shard.minRows", 65536)   # host fallback
            #     .config("spark.shard.devices", 4)       # mesh cap
            shval = str(self.conf.get("spark.shard.enabled", "")).lower()
            if shval in _CONF_FALSE:
                _set("shard_enabled", False)
            elif shval in _CONF_TRUE:
                _set("shard_enabled", True)
            if "spark.shard.minRows" in self.conf:
                _set("shard_min_rows",
                     int(self.conf["spark.shard.minRows"]))
            if "spark.shard.devices" in self.conf:
                _set("shard_devices",
                     int(self.conf["spark.shard.devices"]))
            # Device-cost observatory (utils/costprof.py), session-scoped
            # like everything above:
            #     .config("spark.costprof.enabled", "false") # no profiles
            #     .config("spark.costprof.ridge", 12.0)  # flops/byte
            #     .config("spark.profiling.maxCaptures", 8)
            cval = str(self.conf.get("spark.costprof.enabled",
                                     "")).lower()
            if cval in _CONF_FALSE:
                _set("costprof_enabled", False)
            elif cval in _CONF_TRUE:
                _set("costprof_enabled", True)
            if "spark.costprof.ridge" in self.conf:
                _set("costprof_ridge",
                     float(self.conf["spark.costprof.ridge"]))
            if "spark.profiling.maxCaptures" in self.conf:
                _set("profiling_max_captures",
                     int(self.conf["spark.profiling.maxCaptures"]))
            # Tail sampler + incident flight recorder (utils/observability
            # .py, utils/incidents.py), session-scoped like everything
            # above:
            #     .config("spark.trace.ringSize", 256)     # recent trees
            #     .config("spark.trace.retainedSize", 64)  # kept trees
            #     .config("spark.trace.exemplars", "true") # /metrics ids
            #     .config("spark.incident.enabled", "true")
            #     .config("spark.incident.dir", "/x/incidents")
            #     .config("spark.incident.maxBundles", 32)
            #     .config("spark.incident.cooldownS", 5.0)
            #     .config("spark.incident.sloBurnThreshold", 8.0)
            if "spark.trace.ringSize" in self.conf:
                _set("trace_ring_size",
                     int(self.conf["spark.trace.ringSize"]))
            if "spark.trace.retainedSize" in self.conf:
                _set("trace_retained_size",
                     int(self.conf["spark.trace.retainedSize"]))
            xval = str(self.conf.get("spark.trace.exemplars",
                                     "")).lower()
            if xval in _CONF_FALSE:
                _set("trace_exemplars", False)
            elif xval in _CONF_TRUE:
                _set("trace_exemplars", True)
            ival = str(self.conf.get("spark.incident.enabled",
                                     "")).lower()
            if ival in _CONF_FALSE:
                _set("incident_enabled", False)
            elif ival in _CONF_TRUE:
                _set("incident_enabled", True)
            if "spark.incident.dir" in self.conf:
                _set("incident_dir",
                     str(self.conf["spark.incident.dir"]))
            if "spark.incident.maxBundles" in self.conf:
                _set("incident_max_bundles",
                     int(self.conf["spark.incident.maxBundles"]))
            if "spark.incident.cooldownS" in self.conf:
                _set("incident_cooldown_s",
                     float(self.conf["spark.incident.cooldownS"]))
            if "spark.incident.sloBurnThreshold" in self.conf:
                _set("incident_slo_burn_threshold",
                     float(self.conf["spark.incident.sloBurnThreshold"]))
            # Data-quality observatory (utils/dqprof.py), session-scoped
            # like everything above:
            #     .config("spark.dq.profile.enabled", "false")
            #     .config("spark.dq.histogramBins", 32)
            #     .config("spark.dq.driftThreshold", 0.25)
            #     .config("spark.dq.baselineMode", "persisted")
            dval = str(self.conf.get("spark.dq.profile.enabled",
                                     "")).lower()
            if dval in _CONF_FALSE:
                _set("dq_profile_enabled", False)
            elif dval in _CONF_TRUE:
                _set("dq_profile_enabled", True)
            if "spark.dq.histogramBins" in self.conf:
                _set("dq_histogram_bins",
                     int(self.conf["spark.dq.histogramBins"]))
            if "spark.dq.driftThreshold" in self.conf:
                _set("dq_drift_threshold",
                     float(self.conf["spark.dq.driftThreshold"]))
            if "spark.dq.baselineMode" in self.conf:
                _set("dq_baseline_mode",
                     str(self.conf["spark.dq.baselineMode"]))
            if saved:
                self._pipeline_saved = saved
        # Install the shard context over THIS session's mesh (outside
        # _CONF_LOCK — mesh construction never holds the conf lock;
        # stop() tears it down via shard.reset()). The enabled flag
        # gates every read, so configuring with sharding off costs
        # nothing.
        from .parallel import shard as _shard_mod

        _shard_mod.configure(self.mesh)
        # Adopt persisted plan-statistics history (outside _CONF_LOCK —
        # file I/O never holds the conf lock). Merge is winner-per-key,
        # so a builder re-init re-loading the same snapshot is a no-op.
        from .config import config as _cfg2

        if _cfg2.stats_enabled and _cfg2.stats_path:
            from .utils import statstore as _statstore

            _statstore.STORE.load(_cfg2.stats_path)
        # Apply the (possibly just-overridden) trace/incident bounds to
        # the process-global tail sampler and flight recorder (outside
        # _CONF_LOCK — both take only their own locks).
        from .utils import incidents as _incidents
        from .utils import observability as _obs3

        _obs3.TAIL.configure(ring_size=_cfg2.trace_ring_size,
                             retained_size=_cfg2.trace_retained_size)
        _incidents.RECORDER.configure(
            enabled=_cfg2.incident_enabled,
            directory=_cfg2.incident_dir,
            max_bundles=_cfg2.incident_max_bundles,
            cooldown_s=_cfg2.incident_cooldown_s,
            slo_burn_threshold=_cfg2.incident_slo_burn_threshold)

    def _init_observability(self) -> None:
        """Install the tracing/metrics subsystem (``utils.observability``)
        from session conf or environment — off by default (the hot fused
        paths keep their zero-host-sync contract):

            .config("spark.observability.enabled", "true")
            .config("spark.observability.maxSpans", 50000)
            .config("spark.observability.logSpans", "true")   # logfmt lines

        or ``SPARKDQ4ML_OBS=1`` in the environment. When enabled, a root
        ``session`` span is opened (ended by ``stop()``); everything the
        session touches — SQL queries, frame ops, fits, solver blocks,
        sharded Gramians — nests under it. Read back via
        :meth:`metrics`, :meth:`trace_report`, :meth:`dump_trace`."""
        from .utils import observability as _obs

        conf_val = str(self.conf.get("spark.observability.enabled",
                                     "")).lower()
        # same truthiness vocabulary as the conf key — "SPARKDQ4ML_OBS=off"
        # must not ENABLE tracing
        env_on = os.environ.get(_obs.ENV_VAR, "").strip().lower() not in (
            ("",) + _CONF_FALSE)
        if conf_val in _CONF_TRUE or (conf_val == "" and env_on):
            _obs.enable(
                max_spans=int(self.conf.get("spark.observability.maxSpans",
                                            10_000)),
                log_spans=str(self.conf.get("spark.observability.logSpans",
                                            "")).lower() in _CONF_TRUE)
            self._obs_enabled_here = True
            if getattr(self, "_session_span", None) is None:
                self._session_span = _obs.TRACER.begin(
                    "session", cat="session", app=self.app_name,
                    devices=self.num_devices,
                    platform=jax.devices()[0].platform)
        elif conf_val in _CONF_FALSE:
            # explicit opt-out wins over a programmatic/env enable — the
            # same session-scoped-override rule as spark.compilation.cache
            _obs.disable()

    # -- observability surface ---------------------------------------------
    def metrics(self) -> dict:
        """One merged metrics snapshot: every monotonic counter (solver
        fits/iterations, jit trace hits/misses, ``recovery.*`` from the
        resilience layer, collective dispatch counts), every gauge
        (``mesh.devices``), and every latency histogram
        (``span_ms.<category>``) — flat by name."""
        from .utils import observability as _obs

        return _obs.metrics_snapshot()

    def metrics_text(self) -> str:
        """Prometheus text-format rendering of :meth:`metrics` (counters,
        gauges, and cumulative-bucket histograms), scrape-ready."""
        from .utils import observability as _obs

        return _obs.prometheus_text()

    def trace_report(self) -> str:
        """Human-readable span tree of everything traced so far (empty
        string when observability was never enabled)."""
        from .utils import observability as _obs

        return _obs.trace_report()

    def dump_trace(self, path: str) -> str:
        """Write the Chrome trace-event JSON (Perfetto /
        ``chrome://tracing`` loadable) to ``path``; returns the path."""
        from .utils import observability as _obs

        return _obs.dump_chrome_trace(path)

    def incident_report(self) -> dict:
        """Flight-recorder view: recorder state (dir, disk-ladder rung,
        bundle counts), the bounded incident index (id, trigger, time,
        joining trace id), and the tail sampler's retention counters.
        Full bundles come from ``utils.incidents.RECORDER.get(id)`` or
        the telemetry server's ``/incidents/<id>`` route."""
        from .utils import incidents as _incidents
        from .utils import observability as _obs

        doc = _incidents.RECORDER.report()
        doc["incidents"] = _incidents.RECORDER.list()
        doc["tail"] = _obs.TAIL.report()
        return doc

    def memory_report(self, top: int = 5) -> dict:
        """Device-memory accounting snapshot (``utils.meminfo``): live/
        peak bytes, live-array census by dtype, the ``top`` largest
        buffers, and per-device allocator stats where the backend exposes
        them. Host-side metadata only — never a device sync."""
        from .utils import meminfo as _meminfo

        return _meminfo.memory_report(top=top)

    def cache_report(self) -> dict:
        """Unified jit-cache introspection (``observability.CACHES``):
        per-cache size/hits/misses/evictions and per-entry detail for the
        pipeline compiler, the grouped-execution engine, the solver jit
        entry points, and the packed-fit factories."""
        from .utils import observability as _obs

        return _obs.cache_report()

    def audit_report(self) -> dict:
        """dqaudit over every cached program of this process
        (``analysis/program``): the four jaxpr-level detectors —
        static-memory bound, hidden-sync (callback/const capture),
        collective-topology, retrace-hazard — run by abstract evaluation
        (zero compiles, zero device execution, zero counted host syncs).
        Returns findings + per-program facts (``est_peak_bytes``,
        structural signature, collective/callback counts). Strictly
        on-demand: the audit package imports only when this is called.
        ``spark.audit.enabled=false`` makes it refuse."""
        from .config import config as _cfg

        if not _cfg.audit_enabled:
            return {"enabled": False, "clean": None, "findings": [],
                    "programs": 0}
        from .analysis.program import audit_report as _audit_report

        doc = _audit_report()
        doc["enabled"] = True
        return doc

    def stats_report(self) -> dict:
        """The plan-statistics observatory view (``utils.statstore``):
        one row per structural plan key — observed selectivity,
        wall/compile-ms digest summaries, host syncs, est/measured peak
        bytes — accumulated across every flush of this process PLUS any
        history loaded from ``spark.stats.path``. This is the memory the
        EXPLAIN ``est rows`` column and (ROADMAP item 4) the cost-based
        optimizer read. Draining the deferred selectivity scalars costs
        one counted batched device pull. ``spark.stats.enabled=false``
        makes it refuse."""
        from .config import config as _cfg

        if not _cfg.stats_enabled:
            return {"enabled": False, "entries": [], "size": 0}
        from .utils import statstore as _statstore

        doc = _statstore.STORE.report()
        doc["enabled"] = True
        doc["path"] = _cfg.stats_path or None
        return doc

    def profile_report(self, top: Optional[int] = None) -> dict:
        """The device-cost observatory's fleet-wide roofline table
        (``utils.costprof``): one row per registry-enumerable cached
        program — AOT-extracted flops/bytes/collective traffic, the
        statstore-joined achieved GFLOP/s / GB/s, and the roofline
        ``bound`` verdict — ranked by device-time share. COLD surface:
        a first call may pay bounded lower+compile extractions (zero
        device execution, zero counted host syncs/compiles) and one
        counted statstore drain. ``spark.costprof.enabled=false`` makes
        it refuse. Achieved numbers are structural on the CPU sandbox
        and meaningful on TPU captures (README "Device-cost
        observatory")."""
        from .config import config as _cfg

        if not _cfg.costprof_enabled:
            return {"enabled": False, "entries": [], "size": 0,
                    "pending": 0}
        from .utils import costprof as _costprof

        return _costprof.report(top=top)

    def dq_report(self, top: Optional[int] = None) -> dict:
        """The data-quality observatory view (``utils.dqprof``): one
        row per profiled column — count/null/min/max/mean/variance
        sketch fields, fixed-bucket histogram, PSI drift vs the pinned
        baseline — plus per-rule violation tallies and rates. COLD
        surface: pays the module's one counted deferred-sketch drain
        (``dq.drain_sync``). ``spark.dq.profile.enabled=false`` makes
        it refuse (README "Data-quality observatory")."""
        from .config import config as _cfg

        if not _cfg.dq_profile_enabled:
            return {"enabled": False, "columns": [], "rules": [],
                    "size": 0, "pending": 0}
        from .utils import dqprof as _dqprof

        return _dqprof.report(top=top)

    def _init_faults(self) -> None:
        """Install the fault-injection plan (``utils.faults``) from session
        conf or environment — chaos-in-production is opt-in and explicit:

            .config("spark.faults", "gram_sharded:device_error:1")
            .config("spark.faults.seed", 7)

        or ``SPARKDQ4ML_FAULTS`` in the environment. The recovery policy
        the injected failures exercise is likewise conf-driven
        (``spark.recovery.maxAttempts``, ``.backoffBase``, ``.backoffMax``,
        ``.backoffFactor``, ``.jitter``, ``.attemptDeadline``,
        ``.totalDeadline``, ``.validate`` — see
        ``utils.recovery.RetryPolicy.from_conf``). With neither conf key
        nor env var set this is a no-op and leaves any programmatically
        installed plan alone."""
        from .utils import faults as _faults

        seed = int(self.conf.get("spark.faults.seed", 0))
        spec = self.conf.get("spark.faults")
        if spec:
            # remembered so stop() can uninstall: chaos configured on one
            # session must never leak into the next one
            self._fault_plan = _faults.install_plan(
                _faults.parse_plan(spec, seed=seed))
        elif os.environ.get(_faults.ENV_VAR):
            self._fault_plan = _faults.install_from_env(seed=seed)

    def _is_multihost(self) -> bool:
        """True when this session bootstraps a multi-host runtime."""
        return (self.master or "").strip().lower() in ("pod", "pod[*]") or \
            bool(self.conf.get("spark.distributed.coordinator"))

    def _ensure_backend(self) -> None:
        """``master("tpu[...]")`` demands the accelerator: raise when the
        backend JAX selected is anything else. JAX's own device selection
        decides the platform (``JAX_PLATFORMS=cpu`` is how tests choose
        the CPU); there is no probe, no watchdog and no CPU fallback, so
        a session never reports a CPU run as an accelerator run."""
        if not (self.master or "").strip().lower().startswith("tpu"):
            return
        backend = jax.default_backend()
        if backend != "tpu":
            raise RuntimeError(
                f"master={self.master!r} requested the TPU backend but the "
                f"default backend here is {backend!r}; use "
                "master='local[*]' to run on the local backend")

    def _init_distributed(self) -> None:
        """Multi-host runtime init — the cluster-master analogue of Spark's
        ``master("spark://host:port")``. After ``jax.distributed.initialize``
        the session mesh spans every host's devices and the fit-path psum
        rides ICI within a slice / DCN across slices (parallel/mesh.py).

        Triggered by ``master("pod")`` (TPU pod auto-bootstrap: coordinator
        and process ranks come from the TPU metadata/env) or explicitly:

            .master("pod")
            .config("spark.distributed.coordinator", "host:1234")
            .config("spark.distributed.numProcesses", 4)
            .config("spark.distributed.processId", 0)

        Idempotent: a no-op when the distributed client already exists.
        """
        if not self._is_multihost():
            return
        coord = self.conf.get("spark.distributed.coordinator")
        try:
            from jax._src import distributed as _dist

            if getattr(_dist.global_state, "client", None) is not None:
                return  # already initialized (e.g. a prior session)
        except Exception:
            pass
        kwargs = {}
        if coord:
            kwargs["coordinator_address"] = coord
        if "spark.distributed.numProcesses" in self.conf:
            kwargs["num_processes"] = int(
                self.conf["spark.distributed.numProcesses"])
        if "spark.distributed.processId" in self.conf:
            kwargs["process_id"] = int(self.conf["spark.distributed.processId"])
        jax.distributed.initialize(**kwargs)

    def _init_compilation_cache(self) -> None:
        """Enable XLA's persistent compilation cache (the TPU analogue of a
        warm JVM: first-run compiles land on disk and later sessions reuse
        them). The directory is ``JAX_COMPILATION_CACHE_DIR`` when set,
        else :data:`COMPILE_CACHE_DIR` — see
        :func:`configure_compilation_cache`. Opt out with
        ``.config("spark.compilation.cache", "off")``."""
        configure_compilation_cache(
            str(self.conf.get("spark.compilation.cache", "on")).lower()
            not in _CONF_FALSE)

    # -- builder (mirrors SparkSession.builder()...getOrCreate()) ----------
    class Builder:
        def __init__(self):
            self._app_name = "sparkdq4ml-tpu"
            self._master: Optional[str] = None
            self._conf: dict[str, str] = {}

        def app_name(self, name: str) -> "TpuSession.Builder":
            self._app_name = name
            return self

        appName = app_name

        def master(self, master: str) -> "TpuSession.Builder":
            self._master = master
            return self

        def config(self, key: str, value) -> "TpuSession.Builder":
            self._conf[key] = str(value)
            return self

        def get_or_create(self) -> "TpuSession":
            # Thread-safe singleton (double-checked): concurrent callers —
            # e.g. serving-layer clients racing at process start — get ONE
            # fully-constructed session; the conf-update path is likewise
            # serialized so two builders cannot interleave re-inits.
            global _ACTIVE
            with _ACTIVE_LOCK:
                if _ACTIVE is None:
                    _ACTIVE = TpuSession(self._app_name, self._master,
                                         self._conf)
                    return _ACTIVE
                _ACTIVE.conf.update(self._conf)  # Spark getOrCreate semantics
                if any(k.startswith("spark.compilation.") for k in self._conf):
                    _ACTIVE._init_compilation_cache()
                if any(k.startswith("spark.faults") for k in self._conf):
                    _ACTIVE._init_faults()   # late chaos conf still installs
                if any(k.startswith("spark.observability.")
                       for k in self._conf):
                    _ACTIVE._init_observability()
                if any(k.startswith(("spark.pipeline.", "spark.groupedExec.",
                                     "spark.explain.", "spark.serve.",
                                     "spark.ingest.", "spark.audit.",
                                     "spark.chaos.", "spark.stats.",
                                     "spark.shard.", "spark.costprof.",
                                     "spark.profiling.", "spark.trace.",
                                     "spark.incident.", "spark.dq."))
                       for k in self._conf):
                    _ACTIVE._init_pipeline()
                return _ACTIVE

        getOrCreate = get_or_create

    @classmethod
    def builder(cls) -> "TpuSession.Builder":
        return cls.Builder()

    @classmethod
    def active(cls) -> Optional["TpuSession"]:
        return _ACTIVE

    getActiveSession = active  # Spark 3.x name

    # -- surface ------------------------------------------------------------
    @property
    def devices(self):
        return list(self.mesh.devices.flat)

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    def sql(self, query: str):
        """Run the SQL subset against this session's temp views
        (`DataQuality4MachineLearningApp.java:77,89`)."""
        return _sql_execute(query, self.catalog)

    def serve(self, **overrides):
        """The session's :class:`~sparkdq4ml_tpu.serve.QueryServer` —
        started on first call from ``spark.serve.*`` conf keys (workers,
        maxQueue, maxInFlight, maxQueuedPerTenant, memoryLimitBytes,
        defaultDeadline, sharedPlanCache, breakerThreshold,
        breakerCooldown), keyword ``overrides`` winning. Subsequent
        calls return the same running server; :meth:`stop` drains and
        stops it. ``spark.serve.enabled=false`` makes this raise — the
        serving layer is otherwise pay-for-use (no server, no threads,
        no metrics). See README § "Serving"."""
        from .config import config as _cfg

        with _ACTIVE_LOCK:
            server = getattr(self, "_server", None)
            if server is not None and server.running:
                return server
            if not _cfg.serve_enabled:
                raise RuntimeError(
                    "query serving is disabled "
                    "(spark.serve.enabled=false on this session)")
            from .serve import QueryServer

            self._server = QueryServer.from_conf(self, self.conf,
                                                 **overrides).start()
            return self._server

    def table(self, name: str):
        """Spark's ``spark.table(name)`` — the registered temp view."""
        return self.catalog.lookup(name)

    def create_data_frame(self, data, names=None):
        from .frame.frame import Frame

        if isinstance(data, dict):
            return Frame(data)
        return Frame.from_rows(data, names)

    createDataFrame = create_data_frame

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: Optional[int] = None) -> "Frame":
        """Spark ``spark.range``: a Frame with one integer ``id`` column.
        ``range(n)`` counts 0..n-1; ``range(start, end, step)`` like
        Python's. ``num_partitions`` is accepted and ignored (this engine
        shards at fit time, like the ``repartition`` no-op shim). ids are
        int64 under ``jax_enable_x64``; without it the device dtype is
        int32, so out-of-int32 bounds raise instead of silently
        wrapping."""
        import numpy as np

        from .frame.frame import Frame

        if step == 0:
            raise ValueError("range step must not be zero")
        if end is None:
            start, end = 0, start
        ids = np.arange(start, end, step, dtype=np.int64)
        import jax as _jax

        if not _jax.config.jax_enable_x64 and ids.size > 0:
            # arange is monotone: the extremes are its endpoints (O(1))
            lo, hi = sorted((int(ids[0]), int(ids[-1])))
            if lo < -(2 ** 31) or hi >= 2 ** 31:
                raise ValueError(
                    f"range ids [{lo}, {hi}] exceed int32 and x64 is "
                    "disabled; enable jax_enable_x64 for 64-bit ids")
        return Frame({"id": ids})

    @property
    def recovery_log(self):
        """The process-global structured recovery-event log (retries,
        backoffs, fallbacks, circuit-breaker trips, preemption resumes)
        — ``utils.recovery.RECOVERY_LOG``. Empty on a clean run; the
        observable side of the resilience layer (README § "Failure model
        & fault injection")."""
        from .utils.recovery import RECOVERY_LOG

        return RECOVERY_LOG

    @property
    def version(self) -> str:
        """Engine version string (Spark ``spark.version`` analogue)."""
        from . import __version__

        return __version__

    def stop(self) -> None:
        global _ACTIVE
        # The server handle is swapped out under the SAME lock serve()
        # creates it under — a serve() racing this stop() either lands
        # before (its server is the one drained below) or after (it
        # starts a fresh server on a stopped-but-usable session object);
        # it can never start one that stop() silently ignores.
        with _ACTIVE_LOCK:
            if _ACTIVE is self:
                _ACTIVE = None
            server = getattr(self, "_server", None)
            self._server = None
        # Drain the serving layer FIRST (outside the lock — draining can
        # take a while): in-flight served queries finish against the
        # session's still-installed config; only then is the
        # session-scoped conf restored below (the stop-vs-query race the
        # threading-model doc pins down).
        if server is not None:
            server.stop(drain=True)
        # Persist the plan-statistics history while the session conf is
        # still installed (the path/enabled flags restore below). The
        # save merges-don't-clobber and degrades to in-memory-only on
        # any I/O failure (stats_persist ladder) — stop() never raises
        # over statistics.
        from .config import config as _cfg

        if (_cfg.stats_enabled and _cfg.stats_path
                and _cfg.stats_flush_on_stop):
            from .utils import statstore as _statstore

            _statstore.STORE.save(_cfg.stats_path, merge=True)
        self.catalog.clear()
        # Close the root session span and stop recording if THIS session
        # turned tracing on (same session-scoped rule as the fault plan).
        # Already-recorded spans stay exportable: dump_trace/trace_report
        # after stop() still work (post-mortem analysis is the point).
        span = getattr(self, "_session_span", None)
        if span is not None:
            from .utils import observability as _obs

            _obs.TRACER.end(span)
            self._session_span = None
        if getattr(self, "_obs_enabled_here", False):
            from .utils import observability as _obs

            _obs.disable()
            self._obs_enabled_here = False
        # Restore pipeline-compiler settings THIS session changed (same
        # session-scoped rule as the fault plan): a session that disabled
        # the pipeline must not leave the process on the eager path.
        # Under _CONF_LOCK so a concurrent builder re-init cannot
        # interleave with (and then clobber) this restore.
        with _CONF_LOCK:
            saved = getattr(self, "_pipeline_saved", None)
            if saved:
                from .config import config as _cfg
                from .ops import compiler as _compiler

                for attr, value in saved.items():
                    setattr(_cfg, attr, value)
                self._pipeline_saved = None
                _compiler.clear_cache()
                from .ops import segments as _segments

                _segments.clear_cache()
        # Tear down the shard context THIS session installed (the mesh
        # belongs to the session; a later session re-configures its own).
        from .parallel import shard as _shard_mod

        _shard_mod.reset()
        # Uninstall the fault plan THIS session installed (conf/env):
        # chaos is session-scoped opt-in; a later chaos-free session (or
        # plain library use) must not keep injecting this one's faults.
        plan = getattr(self, "_fault_plan", None)
        if plan is not None:
            from .utils import faults as _faults

            if _faults.active() is plan:
                _faults.clear()
            self._fault_plan = None
