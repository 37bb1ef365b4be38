"""Structured tracing + metrics — the observability subsystem.

The reference's only observability is stdout banners and a post-hoc
``objectiveHistory`` print (SURVEY.md §5); this module is the production
replacement: a span-based tracer with hierarchical, contextvar-propagated
spans (session → sql query → frame op → fit → solver iteration block) and a
metrics registry that extends :data:`utils.profiling.counters` (monotonic
counters) with gauges and fixed-bucket latency histograms.

Exporters (all host-side, on demand — never on the hot path):

* :func:`chrome_trace` / :func:`dump_chrome_trace` — Chrome trace-event JSON
  loadable in Perfetto / ``chrome://tracing``,
* logfmt event lines through :func:`utils.logging.format_kv` (one DEBUG line
  per finished span when ``log_spans`` is on),
* :func:`prometheus_text` — a Prometheus text-format snapshot of every
  counter, gauge, and histogram in one scrape,
* :func:`trace_report` — a human-readable span tree.

One gate, and it follows the profiler: a site records when the tracer was
switched on explicitly (``spark.observability.enabled`` / ``SPARKDQ4ML_OBS``
/ :func:`enable` / :func:`query_stats` — the ``Tracer.enabled`` flag) **or
while a jax profiler session is active**, whoever started it (a benchmark's
``--trace 1``, ``profiling.start_capture`` behind ``/profile/trace``,
TensorBoard). :attr:`Tracer.recording` is that one predicate. While a
profiler session is active every ``with``-style span also opens
``jax.profiler.TraceAnnotation("dq.<name>", sid=..., parent=...)``, so the
program's spans lie on the capture's host thread lines, on the device's
clock, nested as the spans nest.

Cost contract: **off (no flag, no session) is a near-zero no-op** — every
instrumented site reads :attr:`Tracer.recording` once (the flag plus one
``TraceAnnotation.is_enabled()`` call) and allocates nothing (the shared
:data:`_NOOP` context manager is returned, no Span object exists), so the
fused device paths keep their "no host reads" hygiene. A span recorded
because a profiler is on costs an object, two clock reads and a TraceMe and
**never adds a device wait or a host read**. Only the explicit flag MAY add
host syncs (honest span timing blocks on the traced dispatch where noted,
the live-array census feeds the chrome-trace counter tracks); that is the
explicit price of turning it on.

Wired through the framework (span names are a contract: the benchmark's
``layer_metrics`` and ``benchmarks/tools/scopes.py`` read them):

* ``frame/frame.py`` — op spans (:func:`op_span` decorator; rows in/out),
  ``frame.count`` (the scalar pull, ``host_read_bytes``; its wait is
  the ``host.read`` child),
* ``frame/native_csv.py`` — ``frame.ingest``,
* ``ops/compiler.py`` / ``ops/segments.py`` — ``frame.pipeline.flush`` and
  ``frame.grouped.flush`` around the fused programs,
* ``ops/expressions.py`` / ``ops/compiler.py`` — ``dq.rule``, one per
  evaluation of a registered UDF rule (rule name, rows, and ``lowering``:
  ``"in-flush"`` where the rule runs inside a flush's compiled program —
  a child of ``frame.pipeline.flush`` with nothing dispatched under it,
  counter ``dq.rule_in_flush`` — or ``"eager"`` where its function is not
  row-local and each of its operations is a program, counter
  ``dq.rule_eager``),
* ``sql/parser.py`` — ``sql.query`` with the query text and an
  ``explain()``-style plan summary, and its children ``sql.parse``,
  ``sql.optimize`` (rewrites applied), ``sql.execute``,
* ``models/feature.py`` — ``feature.assemble`` (columns in, output width,
  ``programs=1``: the assembler is one launch),
* ``models/regression.py`` / ``classification.py`` — one root per fit
  (``fit.linear_regression`` / ``fit.logistic_regression`` /
  ``fit.linear_svc``, opened where ``fit`` begins: cold-compile vs steady
  split, iteration counts, retry/fallback annotations pulled from
  ``utils.recovery.RECOVERY_LOG``) holding ``fit.prepare`` (children
  ``fit.extract``, ``fit.validate`` — ``base.label_stats`` and the read
  of its few scalars, with ``host_read_bytes`` — and ``fit.pack``, which
  says how the design reaches the program: ``lowering="in-program"`` on
  one device, where the compiled fit takes the frame's columns and packs
  them itself, counter ``fit.pack_in_program``; ``lowering="eager"``
  where ``pack_design`` writes ``Z`` first — the sharded path — counter
  ``fit.pack_eager``) and ``fit.solve`` (dispatch of the compiled fit to
  its result on the host); ``model.transform`` / ``model.predict`` on
  both model classes,
* ``models/tree.py`` — one root per fit (``fit.gbt_classifier``,
  ``fit.gbt_regressor``, ``fit.decision_tree_classifier`` / ``_regressor``,
  ``fit.random_forest_classifier`` / ``_regressor``) holding
  ``fit.prepare`` (children ``fit.extract``, ``fit.validate`` — the label
  statistics and the finite-label / finite-features flags, with
  ``host_read_bytes`` — and ``fit.tree.bin``: thresholds and bins, with
  ``rows``, ``features``, ``bins``, ``lowering="device"``, ``edges`` =
  ``select`` / ``sort`` (how the thresholds' ranks were found) and
  ``passes`` (the selection's passes over the table, a bit of the dtype
  each; 0 for the sort); the span waits for its program) and
  ``fit.solve`` (``rounds``, ``levels``, ``histogram`` = ``mxu`` /
  ``scatter``: the trees dispatched to the one read of their packed
  arrays); ``model.transform``; counters ``tree.fit_device``,
  ``tree.edges_select`` / ``tree.edges_sort`` (one of the two a fit),
  ``tree.rounds``, ``tree.levels``, ``tree.hist_rows``,
  ``tree.hist_nodes`` / ``tree.hist_derived`` (nodes a fit pushed through
  a level histogram, ``2^(max_depth-1)`` a tree, and nodes it took as
  their parent's histogram less their sibling's, one fewer a tree),
* ``models/clustering.py`` — ``KMeans``'s device entry: ``fit.kmeans``
  holding ``fit.prepare`` (``rows``, ``features``, ``lowering`` =
  ``pallas`` / ``xla``; children ``fit.extract``, ``fit.validate`` — the
  kept rows and the finite-features flag, ``host_read_bytes`` — and
  ``fit.kmeans.init``: ``mode``, ``steps``, ``candidates``, ``overflow``;
  it ends with the candidates on the host and the k centres picked among
  them) and ``fit.solve`` (``iterations``: Lloyd's loop dispatched to the
  one read of its history, sizes and cost); ``model.transform`` and
  ``model.compute_cost`` (its one scalar read); counters
  ``kmeans.fit_device``, ``kmeans.iterations``, ``kmeans.data_passes``,
  ``kmeans.init_candidates``, ``kmeans.init_overflow``,
* ``models/solvers.py`` — ``solver.solve``,
* ``parallel/distributed.py`` / ``mesh.py`` — per-shard Gramian timing
  (blocks under the explicit flag only), collective/shard_map build
  counters, mesh-size gauge,
* ``serve/`` — ``serve.query/admit/queue/stream`` request trees (explicit
  flag only: they feed the tail sampler),
* ``session.py`` — ``spark.observability.*`` conf + ``SPARKDQ4ML_OBS`` env
  gating, ``session.metrics()`` / ``trace_report()`` / ``dump_trace(path)``.

Inside the compiled programs :func:`scope` (``jax.named_scope`` under the
``dq.`` prefix) names the layer a device operation belongs to in its op
metadata: ``dq.flush`` (and inside it ``dq.rule``, a registered rule's
operations), ``dq.sketch``, ``dq.grouped``, ``dq.exchange``,
``dq.feature.assemble`` (the assembler's one program),
``dq.fit.validate``, ``dq.fit.pack`` (what a fit makes of its columns
before its passes: mask, scale, moments, the standardised design),
``dq.fit.gram``,
``dq.fit.newton.margin`` / ``.gradient`` / ``.hessian`` /
``.line_search``, ``dq.fit.fista.loss_grad``, ``dq.fit.solve``; in the
tree programs ``dq.tree.edges`` (the thresholds: the integer image of
the table and the counting passes that select their ranks, or the sorts),
``dq.tree.bin``, ``dq.tree.gradient``, ``dq.tree.hist`` (the Pallas kernel
``tree_level_histogram`` or the scatters over one child of every split —
counter ``tree.hist_nodes`` — and the subtraction that gives the sibling,
``tree.hist_derived``), ``dq.tree.split``,
``dq.tree.descend``, ``dq.tree.score``; in k-means' programs
``dq.kmeans.init.cost`` (a k-means‖ round's pass: the Pallas kernel
``kmeans_pass`` or its ``jax.numpy`` form), ``dq.kmeans.init.sample`` (the
draws and their compaction into the round's bucket),
``dq.kmeans.init.weigh`` (the pass that counts the rows a candidate),
``dq.kmeans.assign`` (a Lloyd pass), ``dq.kmeans.update`` (the new
centres, their shift, the history) and ``dq.kmeans.score`` (the model's
pass: ``transform``, ``compute_cost``). Metadata
only: the operations' HLO names and the compiled code are unchanged. (A
scope opened on the host around eager ``jnp`` calls does not reach their
one-operation programs' metadata — measured on the chip, PERF.md section 3
— so none is opened there.)

Where the host reads from the device, the blocking call runs inside
:func:`host_reading`: the counters ``host.reads`` and ``host.read_bytes``
count it (``profiling.host_read``; beside ``frame.host_sync``, which
keeps its meaning) and, while the tracer records, the span ``host.read``
(``cat="host"``; ``site``, ``bytes``) times it under whatever span is open
— ``frame.count``, ``fit.validate``, ``fit.solve``,
``frame.grouped.flush``, ``frame.join``, ``frame.to_pydict``, ... Sites:
``frame.count`` / ``frame.mask`` / ``frame.to_pydict``, ``agg.verdict``,
``literal.head``, ``grouped.verdict`` / ``sort.keys`` / ``gather.index`` /
``distinct.groups`` / ``distinct.candidates`` / ``distinct.keys``,
``join.order`` / ``join.verdict`` / ``join.count`` / ``join.keys``,
``fit.label_stats`` / ``fit.finite_flags`` / ``fit.result``,
``tree.result`` / ``tree.held_loss`` / ``tree.bin`` (a wait for the
binning program, nothing read: no ``bytes``, not counted),
``kmeans.validate`` / ``kmeans.candidates`` / ``kmeans.result``,
``model.fetch``, ``stat.corr`` / ``stat.cov`` / ``stat.quantile`` /
``stat.strata``, ``window.mask`` / ``window.column``,
``evaluation.pair``. The benchmark's ``host_wait_ms`` is the union of a
job's ``host.read`` spans and ``host_active_ms`` the rest of the job.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import json
import logging
import os
import threading
import time
from typing import Callable, Optional

import jax

from . import profiling
from .logging import format_kv

logger = logging.getLogger("sparkdq4ml_tpu.observability")

ENV_VAR = "SPARKDQ4ML_OBS"

#: Prefix of everything the program writes into a profiler capture: the
#: ``TraceAnnotation`` of a span and the ``named_scope`` of a compiled
#: program's layer. Never ``bench.``: that is the benchmark's own.
TRACE_PREFIX = "dq."

#: True while a jax profiler session is active, whoever started it.
profiler_active = jax.profiler.TraceAnnotation.is_enabled

# ---------------------------------------------------------------------------
# Metrics: gauges + fixed-bucket histograms (counters live in
# utils.profiling.counters so the recovery mirror keeps one home)
# ---------------------------------------------------------------------------

#: Default latency buckets (milliseconds) — fixed at creation so scrapes see
#: a stable schema; spans record their duration into ``span_ms.<category>``.
DEFAULT_BUCKETS_MS = (0.1, 0.5, 1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0)

#: THE metric-name registry — every literal name passed to
#: ``counters.increment`` / ``METRICS.set_gauge`` / ``METRICS.observe``
#: must be declared here (enforced statically by dqlint's
#: ``metric-name`` rule, ``analysis/rules/metric_names.py``): a typo'd
#: counter compiles, runs, and silently creates a ghost series no
#: dashboard reads. name → (type, help); the Prometheus exporter renders
#: the declared help text. Kept a PURE LITERAL so the rule can
#: ``ast.literal_eval`` it without importing the engine (the CONF_KEYS
#: pattern).
METRIC_NAMES = {
    # frame engine
    "frame.host_sync": ("counter", "counted device->host boundary pulls"),
    "frame.cache": ("counter", "Frame.cache()/persist() materializations"),
    # every blocking device->host read on the job paths (host_read)
    "host.reads": ("counter", "blocking device->host reads"),
    "host.read_bytes": ("counter", "bytes pulled by blocking "
                                   "device->host reads"),
    # fused expression pipeline (ops/compiler.py)
    "pipeline.flush": ("counter", "pending-pipeline materializations"),
    "pipeline.compile": ("counter", "fused programs traced+compiled"),
    "pipeline.hit": ("counter", "fused-program plan-cache replays"),
    "pipeline.fallback": ("counter", "flushes degraded to eager replay"),
    "pipeline.fault_fallback": ("counter",
                                "flushes eager-replayed by the fault "
                                "ladder"),
    "pipeline.evict": ("counter", "plan-cache LRU evictions"),
    "pipeline.oom_chunked": ("counter",
                             "over-budget flushes run row-chunked"),
    "pipeline.shard_gather": ("counter",
                              "sharded flushes gathered to single-device "
                              "by the shard_flush ladder"),
    # grouped execution (ops/segments.py)
    "grouped.compile": ("counter", "grouped programs traced+compiled"),
    "grouped.hit": ("counter", "grouped-program plan-cache replays"),
    "grouped.fallback": ("counter", "grouped ops on the host path"),
    "grouped.fault_fallback": ("counter",
                               "grouped ops host-degraded by the fault "
                               "ladder"),
    "grouped.dense_miss": ("counter", "dense lowering misfits rerouted"),
    "grouped.rows": ("counter", "row slots handed to grouped/sort/"
                                "unique programs"),
    "grouped.tile": ("counter", "grouped plans reduced by the dense "
                                "lowering's tile tier"),
    "grouped.evict": ("counter", "grouped plan-cache LRU evictions"),
    "grouped.ordered": ("counter", "grouped plans reduced by the ordered "
                                   "lowering (one integer key stored in "
                                   "order: runs, no sort)"),
    "grouped.order_miss": ("counter", "grouped plans offered the ordered "
                                      "lowering whose keys were out of "
                                      "order"),
    "subquery.semi_join": ("counter", "IN subqueries planned as left-semi "
                                      "joins against their frame"),
    "subquery.literal_in": ("counter", "IN subqueries read to the host as "
                                       "a list of literals"),
    "join.device": ("counter", "joins planned, ordered and gathered by the "
                               "device program (ops/joins.py)"),
    "join.host": ("counter", "joins planned on the host from pulled masks "
                             "and key columns (string keys, right/outer/"
                             "cross, sharded frames)"),
    "join.rows_probed": ("counter", "probe-side row slots handed to "
                                    "device join programs"),
    "join.compile": ("counter", "device join programs traced"),
    "join.hit": ("counter", "device join runs served by a built program"),
    "join.merge": ("counter", "device joins whose build step merged (a "
                              "probe side in key order, sorted in chunks)"),
    "join.merge_miss": ("counter", "ordered build steps (merge, lookup) "
                                   "that found the probe side out of order, "
                                   "or a merge chunk over its room, and "
                                   "ran again as a sort"),
    "join.lookup": ("counter", "device joins whose build step searched a "
                               "few build keys into an ordered probe side"),
    "join.scan_pallas": ("counter", "device joins whose probe scans ran in "
                                    "the Pallas kernel join_probe_scan"),
    "grouped.shard_gather": ("counter",
                             "sharded grouped/distinct programs gathered "
                             "to single-device by the shard_merge "
                             "ladder"),
    # row-sharded frames (parallel/shard.py)
    "shard.place": ("counter", "frames laid out row-sharded"),
    "shard.gather": ("counter", "sharded frames degraded to "
                                "single-device placement"),
    "shard.join_partitioned": ("counter",
                               "joins planned via the hash-partition "
                               "shuffle lowering"),
    "shard.fit_passthrough": ("counter",
                              "fit placements consuming shard partials "
                              "directly (no re-shard)"),
    # streaming ingest (frame/native_csv.py)
    "ingest.files": ("counter", "native CSV files read"),
    "ingest.bytes": ("counter", "native CSV bytes parsed"),
    "ingest.rows": ("counter", "native CSV rows parsed"),
    "ingest.chunks": ("counter", "streamed parse chunks"),
    "ingest.streamed": ("counter", "files read via the streaming path"),
    "ingest.python_fallback": ("counter",
                               "files degraded to the python engine"),
    "ingest.fault_fallback": ("counter",
                              "native reads degraded by the fault "
                              "ladder"),
    # solver / jit layers
    "solver.fits": ("counter", "model fits dispatched"),
    "solver.iterations": ("counter", "solver iterations run"),
    "fit.pack_in_program": ("counter", "fits handed the frame's columns: "
                                       "the design packed inside the "
                                       "compiled fit"),
    "fit.pack_eager": ("counter", "designs packed into Z before a fit "
                                  "(pack_design: the sharded path, "
                                  "callers that hold a Z)"),
    # tree ensembles (models/tree.py)
    "tree.fit_device": ("counter", "tree fits through the device entry: "
                                   "thresholds, bins and growth on the "
                                   "device, nothing n-sized to the host"),
    "tree.edges_select": ("counter", "tree fits whose thresholds were "
                                     "selected by counting passes"),
    "tree.edges_sort": ("counter", "tree fits whose thresholds were read "
                                   "out of a sort a feature"),
    "tree.rounds": ("counter", "trees grown (boosting rounds, forest "
                               "members)"),
    "tree.levels": ("counter", "histogram passes: one a level a tree"),
    "tree.hist_rows": ("counter", "row slots handed to level histograms"),
    "tree.hist_nodes": ("counter", "nodes pushed through a level histogram: "
                                   "the root and one child of every split, "
                                   "2^(max_depth-1) a tree"),
    "tree.hist_derived": ("counter", "nodes whose histogram is their "
                                     "parent's less their sibling's"),
    # k-means (models/clustering.py)
    "kmeans.fit_device": ("counter", "KMeans fits through the device "
                                     "entry: no row leaves the chip"),
    "kmeans.iterations": ("counter", "Lloyd iterations run"),
    "kmeans.data_passes": ("counter", "passes over the feature column a "
                                      "fit dispatched: validation, the "
                                      "seeding's, an iteration each, the "
                                      "final cost's"),
    "kmeans.init_candidates": ("counter", "candidates k-means|| drew"),
    "kmeans.init_overflow": ("counter", "k-means|| rounds that drew more "
                                        "rows than their bucket holds "
                                        "(the rest dropped: degraded)"),
    "jit.trace_miss": ("counter", "jit-factory cache misses (new trace)"),
    "jit.trace_hit": ("counter", "jit-factory cache hits"),
    # parallel / mesh
    "parallel.psum_dispatches": ("counter", "collective dispatches"),
    "parallel.shard_map_builds": ("counter", "shard_map programs built"),
    "mesh.devices": ("gauge", "devices in the session mesh"),
    # device memory (utils/meminfo.py)
    "mem.live_bytes": ("gauge", "live-array census bytes"),
    "mem.peak_bytes": ("gauge", "process-lifetime census peak bytes"),
    # tracer internals
    "trace.dropped_spans": ("counter", "spans evicted by the bounded "
                                       "buffer"),
    # tail-based request-tree retention (TailSampler)
    "trace.kept": ("counter", "request trees promoted to the retained "
                              "store by the tail keep-policy"),
    "trace.dropped": ("counter", "request trees aged out of the tail "
                                 "ring without being kept"),
    # incident flight recorder (utils/incidents.py)
    "incident.written": ("counter", "incident bundles persisted to the "
                                    "incident dir"),
    "incident.failed": ("counter", "incident bundle writes degraded to "
                                   "in-memory retention"),
    # fault injection (utils/faults.py)
    "faults.injected": ("counter", "chaos faults fired"),
    # serving layer (serve/)
    "serve.admit": ("counter", "queries admitted"),
    "serve.reject": ("counter", "queries rejected (all reasons)"),
    "serve.shed": ("counter", "queries shed by an open breaker"),
    "serve.complete": ("counter", "queries completed ok"),
    "serve.error": ("counter", "queries failed in execution"),
    "serve.deadline_exceeded": ("counter", "queries past their deadline"),
    "serve.late_result": ("counter", "executed values discarded late"),
    "serve.requeue": ("counter", "retryable failures requeued"),
    "serve.tenants_reaped": ("counter", "idle stateless tenants reaped"),
    "serve.queue_depth": ("gauge", "queued jobs across tenants"),
    "serve.in_flight": ("gauge", "jobs executing right now"),
    "serve.tenants": ("gauge", "known tenant states"),
    "serve.workers": ("gauge", "live worker threads"),
    "serve.slo_burn": ("gauge", "SLO error-budget burn rate, all "
                                "tenants (1.0 = burning the 1% budget "
                                "exactly)"),
    "serve.queue_ms": ("histogram", "queue wait per executed job"),
    "serve.exec_ms": ("histogram", "execution wall per job"),
    "serve.e2e_ms": ("histogram", "client-experienced end-to-end "
                                  "latency"),
    # cross-request plan coalescing (serve/coalesce.py)
    "serve.coalesce.batched": ("counter", "queries served by a "
                                          "cross-request batched "
                                          "dispatch"),
    "serve.coalesce.dispatches": ("counter", "cross-request batched "
                                             "device dispatches"),
    "serve.coalesce.degraded": ("counter", "batches degraded to "
                                           "per-request replay"),
    "serve.coalesce.batch_size": ("histogram", "members per batched "
                                               "dispatch"),
    "serve.coalesce.window_ms": ("histogram", "hold-window wait per "
                                              "batched dispatch"),
    # network serving front end (serve/net.py + serve/client.py)
    "net.accept": ("counter", "socket connections accepted"),
    "net.requests": ("counter", "wire requests parsed (both framings)"),
    "net.pages": ("counter", "result pages streamed"),
    "net.page_deadline": ("counter", "result streams truncated by the "
                                     "wire deadline between pages"),
    "net.bytes_in": ("counter", "request bytes read off the wire"),
    "net.bytes_out": ("counter", "response bytes written to the wire"),
    "net.conn_reset": ("counter", "connections dropped by a reset "
                                  "(injected or real)"),
    "net.conn_timeout": ("counter", "connections closed by the "
                                    "read/write timeout (slow-loris "
                                    "guard)"),
    "net.partial_write": ("counter", "responses truncated mid-write"),
    "net.frame_overflow": ("counter", "requests refused over "
                                      "maxFrameBytes"),
    "net.client_gone": ("counter", "mid-stream client disconnects "
                                   "(result discarded via "
                                   "serve.late_result)"),
    "net.idem_hit": ("counter", "idempotency-key dedup hits (no "
                                "re-execution)"),
    "net.error_frames": ("counter", "structured error frames/responses "
                                    "sent"),
    "net.active": ("gauge", "open socket connections"),
    "net.client_retry": ("counter", "resilient-client attempt retries"),
    "net.client_hedge": ("counter", "resilient-client hedged attempts"),
    # cost-based plan optimizer (sql/optimizer.py + lowering hooks)
    "optimizer.rewrite": ("counter", "plan rewrites applied"),
    "optimizer.fallback": ("counter",
                           "queries degraded to the unrewritten plan"),
    "optimizer.split": ("counter",
                        "mega-stage flushes split at a warm prefix"),
    "optimizer.mem_chunk": ("counter",
                            "flushes chunked by remembered byte bounds"),
    "optimizer.dense_skip": ("counter",
                             "grouped dense attempts skipped by miss "
                             "history"),
    # adaptive query execution (sql/adaptive.py + boundary hooks)
    "aqe.replans": ("counter", "mid-query re-plan events applied, all "
                               "triggers"),
    "aqe.fallback": ("counter", "re-plan decision points degraded to "
                                "the static plan by the aqe fault "
                                "ladder"),
    # plan-stats observatory (utils/statstore.py)
    "stats.record": ("counter", "flush observations recorded"),
    "stats.evict": ("counter", "stats entries evicted (maxEntries)"),
    "stats.drain_sync": ("counter",
                         "batched deferred-observation device pulls"),
    "stats.pending_dropped": ("counter",
                              "deferred observations dropped at the "
                              "pending bound"),
    "stats.loaded": ("counter", "stats entries adopted from a snapshot"),
    "stats.persisted": ("counter", "stats snapshots written"),
    "stats.persist_failed": ("counter",
                             "snapshot writes degraded to in-memory "
                             "only"),
    "stats.load_failed": ("counter",
                          "corrupt/stale snapshots degraded to empty"),
    # device-cost observatory (utils/costprof.py)
    "costprof.extracted": ("counter",
                           "AOT cost profiles extracted (lower+compile, "
                           "zero device execution)"),
    "costprof.failed": ("counter",
                        "cost extractions degraded to unprofiled "
                        "(surfaces render '-')"),
    "shard.skew": ("gauge", "worst/mean shard row-balance ratio of the "
                            "most recent sharded placement"),
    "shard.exchange_bytes": ("counter",
                             "statically-sized cross-shard exchange "
                             "volume, all kinds"),
    "profiling.captures": ("counter",
                           "managed jax-profiler captures armed"),
    # data-quality observatory (utils/dqprof.py)
    "dq.sketches": ("counter",
                    "column/rule sketch reductions dispatched from "
                    "flush hooks"),
    "dq.drain_sync": ("counter",
                      "batched cold-path drains of deferred dq "
                      "sketches (the only dq host syncs)"),
    "dq.pending_dropped": ("counter",
                           "deferred dq observations dropped at the "
                           "pending bound"),
    "dq.profile_failed": ("counter",
                          "flushes degraded to unprofiled by the "
                          "dq_profile fault ladder"),
    "dq.rule_evals": ("counter",
                      "DQ-rule evaluations tallied by the dq profile, "
                      "in a flush or eager"),
    "dq.rule_in_flush": ("counter",
                         "registered-rule calls run inside a flush's "
                         "compiled program (the function is row-local)"),
    "dq.rule_eager": ("counter",
                      "registered-rule calls evaluated eagerly, one "
                      "program an operation"),
    "dq.baseline_pinned": ("counter",
                           "drift baselines pinned (first drain or "
                           "persisted snapshot adoption)"),
    "dq.drift_breach": ("counter",
                        "column drift scores past "
                        "spark.dq.driftThreshold"),
    "dq.violation_spike": ("counter",
                           "per-drain rule violation-rate spikes"),
    "dq.program_evict": ("counter",
                         "dq sketch programs evicted at the cache "
                         "bound"),
}

#: Dynamic metric-name families (formatted per site/tenant/category at
#: runtime): any name starting with one of these prefixes is declared by
#: the family. prefix → (type, help). Same pure-literal contract as
#: :data:`METRIC_NAMES`.
METRIC_NAME_PREFIXES = {
    "recovery.": ("counter", "resilience-layer event mirror (action and "
                             "per-site action.site keys)"),
    "faults.injected.": ("counter", "per-site injected-fault mirror"),
    "solver.": ("counter", "per-solver dispatch counters"),
    "serve.reject.": ("counter", "per-reason admission rejections"),
    "serve.e2e_ms.": ("histogram", "per-tenant end-to-end latency "
                                   "(series-capped)"),
    "serve.slo_burn.": ("gauge", "per-tenant SLO error-budget burn rate "
                                 "(series-capped)"),
    "span_ms.": ("histogram", "span wall-clock latency by category"),
    "costprof.": ("counter", "device-cost observatory activity"),
    "aqe.replans.": ("counter", "per-trigger mid-query re-plan events "
                                "(build-flip/broadcast/skew-split/"
                                "re-bucket/grouped-lowering)"),
    "shard.exchange_bytes.": ("counter",
                              "per-kind cross-shard exchange volume "
                              "(psum/all_to_all/gather)"),
    "dq.violations.": ("counter", "per-rule DQ violation rows"),
    "dq.violation_rate.": ("gauge", "per-rule cumulative violation "
                                    "fraction"),
    "dq.drift.": ("gauge", "per-column PSI drift vs the pinned "
                           "baseline"),
}


class Histogram:
    """Fixed-bucket histogram (Prometheus convention: cumulative bucket
    counts keyed by upper bound ``le``, plus ``sum`` and ``count``).
    Thread-safe; buckets are fixed at construction."""

    def __init__(self, name: str, buckets=DEFAULT_BUCKETS_MS):
        self.name = name
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # last = +Inf overflow
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        i = 0
        for i, b in enumerate(self.buckets):  # ≤ ~14 buckets: linear is fine
            if v <= b:
                break
        else:
            i = len(self.buckets)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        cumulative, acc = {}, 0
        for b, c in zip(self.buckets, counts):
            acc += c
            cumulative[b] = acc
        cumulative[float("inf")] = total
        return {"buckets": cumulative, "sum": s, "count": total}


class MetricsRegistry:
    """Gauges + histograms, by name. Counters intentionally stay in
    :data:`utils.profiling.counters` (one monotonic registry, one recovery
    mirror); :func:`metrics_snapshot` merges all three views."""

    def __init__(self):
        self._gauges: dict[str, float] = {}
        self._histograms: dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def get_gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def histogram(self, name: str, buckets=None) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = Histogram(name, buckets or DEFAULT_BUCKETS_MS)
                self._histograms[name] = h
            return h

    def observe(self, name: str, value: float, buckets=None) -> None:
        self.histogram(name, buckets).observe(value)

    def snapshot(self) -> dict:
        with self._lock:
            gauges = dict(self._gauges)
            hists = dict(self._histograms)
        out: dict = dict(gauges)
        for name, h in hists.items():
            out[name] = h.snapshot()
        return out

    def clear(self) -> None:
        with self._lock:
            self._gauges.clear()
            self._histograms.clear()


#: Process-global metrics registry (gauges + histograms).
METRICS = MetricsRegistry()


def metrics_snapshot() -> dict:
    """One merged registry view: every monotonic counter (including the
    ``recovery.*`` mirror from PR 1), every gauge, and every histogram
    summary, flat by name."""
    out: dict = dict(profiling.counters.snapshot())
    out.update(METRICS.snapshot())
    return out


# ---------------------------------------------------------------------------
# Tracer: hierarchical spans, contextvar-propagated
# ---------------------------------------------------------------------------


class _NoopSpan:
    """Shared disabled-mode stand-in: reentrant, stateless, allocation-free.
    Every method is a no-op returning self so instrumented sites never
    branch on the enabled flag twice."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()

_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "sparkdq4ml_obs_current_span", default=None)


class Span:
    """One traced operation. Use as a context manager (normal case) or via
    ``Tracer.begin``/``Tracer.end`` for long-lived spans (the session root).
    ``set(**attrs)`` attaches structured attributes at any point.

    ``trace_id`` is the span id of the trace's ROOT span (a root's
    trace_id is its own sid) — emitted by BOTH exporters (logfmt lines and
    Chrome-trace ``args``), so a logfmt line can be cross-referenced into
    the Perfetto view of the same run."""

    __slots__ = ("name", "cat", "attrs", "sid", "parent_id", "trace_id",
                 "tid", "ts_us", "dur_us", "_t0", "_token", "_tracer",
                 "_mem", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.sid = tracer._next_id()
        parent = _CURRENT.get()
        if parent is None:
            # Ambient fallback: a long-lived root opened with ``begin``
            # (the session span) parents spans whose context lost the
            # link — worker threads (fresh contexts) and callers whose
            # enclosing ``with span`` exited after ``begin`` ran inside
            # it (the contextvar reset would otherwise orphan everything
            # that follows). Lock-free read: end()/clear() may empty the
            # list between the check and the index, so tolerate that
            # instead of crashing the instrumented user operation.
            try:
                parent = tracer._ambient[-1]
            except IndexError:
                parent = None
        self.parent_id = parent.sid if parent is not None else None
        self.trace_id = parent.trace_id if parent is not None else self.sid
        self.tid = threading.get_ident()
        self.ts_us = 0
        self.dur_us: Optional[int] = None
        self._t0 = 0.0
        self._token: Optional[contextvars.Token] = None
        self._mem = None              # meminfo.SpanSampler when sampling
        self._annotation = None       # the TraceMe while a profiler is on

    @property
    def start_s(self) -> float:
        """Start on ``time.perf_counter``'s clock (0.0 before entry)."""
        return self._t0

    def set(self, **attrs) -> "Span":
        # Copy-on-write, never in-place: exporters snapshot ``self.attrs``
        # by reference from other threads (open spans export live), and a
        # concurrent in-place mutation would raise "dictionary changed
        # size during iteration" mid-scrape. A reference swap is atomic.
        self.attrs = {**self.attrs, **attrs}
        return self

    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        if self._tracer.mem_sample:
            from . import meminfo

            self._mem = meminfo.span_sampler()
        self.ts_us = self._tracer._now_us()
        self._t0 = time.perf_counter()
        if profiler_active():
            # the same span on the capture's host line, on the device's
            # clock: an event named dq.<name> with the ids as stats
            ids = {"sid": self.sid}
            if self.parent_id is not None:
                ids["parent"] = self.parent_id
            self._annotation = jax.profiler.TraceAnnotation(
                TRACE_PREFIX + self.name, **ids)
            self._annotation.__enter__()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(et, ev, tb)
            self._annotation = None
        self.dur_us = int((time.perf_counter() - self._t0) * 1e6)
        if self._mem is not None:
            self.attrs = {**self.attrs, **self._mem.finish()}
            self._mem = None
        if et is not None:
            self.attrs = {**self.attrs, "error": et.__name__}
        if self._token is not None:
            try:
                _CURRENT.reset(self._token)
            except ValueError:   # crossed contexts (begin/end style misuse)
                _CURRENT.set(None)
            self._token = None
        self._tracer._finish(self)
        return False


class Tracer:
    """Span recorder. :attr:`recording` is THE hot-path gate: every
    instrumented site reads it once and returns :data:`_NOOP` when off.
    It is true under the explicit ``enabled`` flag or while a jax profiler
    session is active; the two stay separate facts, so that
    :func:`query_stats`' save-and-restore and ``session.stop()`` only ever
    touch the flag. Finished spans land in a bounded buffer (oldest
    dropped) and their durations feed the ``span_ms.<category>``
    histograms."""

    #: Minimum spacing of the resource-counter samples the Chrome-trace
    #: exporter renders as ``"ph": "C"`` tracks (microseconds). Sampling
    #: is activity-driven (taken at span completion under the explicit
    #: flag, throttled to this interval) so an idle process records
    #: nothing, and a span recorded for a profiler never pays the census.
    counter_sample_us = 20_000
    #: Bounded counter-sample history (oldest dropped).
    max_counter_samples = 4096

    def __init__(self, max_spans: int = 10_000):
        self.enabled = False
        self.log_spans = False
        self.mem_sample = False       # per-span device-memory sampling
        self.max_spans = max_spans
        self.dropped = 0              # spans evicted by the bounded buffer
        self._spans: list[Span] = []
        self._open: dict[int, Span] = {}
        self._ambient: list[Span] = []   # begun roots (see Span.__init__)
        self._sinks: list = []        # per-query collectors (query_stats)
        self._csamples: list = []     # (ts_us, {metric: value}) track
        self._last_csample_us = 0
        self._lock = threading.Lock()
        self._id = 0
        self._epoch_s = time.time()
        self._pc0 = time.perf_counter()

    @property
    def recording(self) -> bool:
        """The one gate: the explicit flag, or a jax profiler session."""
        return self.enabled or profiler_active()

    # -- internals --------------------------------------------------------
    def _next_id(self) -> int:
        with self._lock:
            self._id += 1
            return self._id

    def _now_us(self) -> int:
        return int((self._epoch_s
                    + (time.perf_counter() - self._pc0)) * 1e6)

    def _finish(self, s: Span) -> None:
        with self._lock:
            self._open.pop(s.sid, None)
            self._spans.append(s)
            excess = len(self._spans) - self.max_spans
            if excess > 0:
                # The bounded buffer wrapping used to be SILENT — a trace
                # that looks complete but starts mid-query. Count it so
                # trace_report()/chrome_trace() can say what's missing.
                del self._spans[:excess]
                self.dropped += excess
            sinks = list(self._sinks)
        if excess > 0:
            profiling.counters.increment("trace.dropped_spans", excess)
        for sink in sinks:
            try:
                sink(s)
            except Exception:   # a broken collector must not break the op
                logger.debug("span sink failed", exc_info=True)
        if self.enabled:
            self._maybe_sample_counters()
        METRICS.observe(f"span_ms.{s.cat or 'other'}",
                        (s.dur_us or 0) / 1e3)
        if self.log_spans:
            logger.debug(
                "span %s",
                format_kv(name=s.name, cat=s.cat,
                          dur_ms=round((s.dur_us or 0) / 1e3, 3),
                          trace_id=s.trace_id, span_id=s.sid,
                          parent_id=s.parent_id, **s.attrs))

    def _maybe_sample_counters(self) -> None:
        """Resource-counter sampling for the Chrome-trace ``"ph": "C"``
        tracks (Perfetto renders them as graphs under the span
        timeline): the live-bytes census, serving queue depth, and the
        pipeline hit/compile counters, taken at span completion and
        throttled to :data:`counter_sample_us`. Runs only under the
        explicit flag: the census walks ``jax.live_arrays()``, which a
        span recorded because a profiler is on must never pay."""
        now = self._now_us()
        with self._lock:
            if now - self._last_csample_us < self.counter_sample_us:
                return
            self._last_csample_us = now
        from . import meminfo
        from . import profiling

        sample = {
            "mem.live_bytes": meminfo.live_bytes(),
            "serve.queue_depth": METRICS.get_gauge("serve.queue_depth"),
            "pipeline.hit": profiling.counters.get("pipeline.hit"),
            "pipeline.compile": profiling.counters.get("pipeline.compile"),
        }
        with self._lock:
            self._csamples.append((now, sample))
            if len(self._csamples) > self.max_counter_samples:
                del self._csamples[: len(self._csamples)
                                   - self.max_counter_samples]

    def counter_samples(self) -> list:
        with self._lock:
            return list(self._csamples)

    # -- recording --------------------------------------------------------
    def span(self, name: str, cat: str = "", **attrs):
        """Context manager for one traced operation. Returns the shared
        no-op when off — one gate read, zero allocation."""
        if not self.recording:
            return _NOOP
        return Span(self, name, cat, attrs)

    def begin(self, name: str, cat: str = "", **attrs):
        """Open a long-lived span (e.g. the session root) that outlives the
        calling frame. Pair with :meth:`end`. Child spans nest under it via
        the context AND the ambient-root fallback (so spans from worker
        threads or sibling contexts still parent correctly). Not written
        into a profiler capture: a TraceMe must close on the thread, and
        inside the session, that opened it."""
        if not self.recording:
            return _NOOP
        s = Span(self, name, cat, attrs)
        s.ts_us = self._now_us()
        s._t0 = time.perf_counter()
        _CURRENT.set(s)
        with self._lock:
            self._open[s.sid] = s
            self._ambient.append(s)
        return s

    def end(self, s) -> None:
        if s is None or s is _NOOP:
            return
        s.dur_us = int((time.perf_counter() - s._t0) * 1e6)
        if s._mem is not None:
            s.attrs = {**s.attrs, **s._mem.finish()}
            s._mem = None
        if _CURRENT.get() is s:
            _CURRENT.set(None)
        with self._lock:
            if s in self._ambient:
                self._ambient.remove(s)
        self._finish(s)

    # -- views ------------------------------------------------------------
    def spans(self) -> list:
        """Finished + still-open spans (open ones report duration so far)."""
        with self._lock:
            done = list(self._spans)
            open_ = list(self._open.values())
        return done + open_

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._open.clear()
            self._ambient.clear()
            self._csamples.clear()
            self._last_csample_us = 0
            self.dropped = 0


#: Process-global tracer. Off by default; ``session`` conf/env turn it on
#: (or call :func:`enable` directly), and so does any jax profiler session.
TRACER = Tracer()


def enabled() -> bool:
    """The explicit flag alone (see :attr:`Tracer.recording`)."""
    return TRACER.enabled


def enable(max_spans: int = 10_000, log_spans: bool = False) -> None:
    """Turn recording on (idempotent). Previously recorded spans are kept;
    call ``TRACER.clear()`` / ``reset()`` for a fresh buffer."""
    TRACER.max_spans = int(max_spans)
    TRACER.log_spans = bool(log_spans)
    TRACER.enabled = True


def disable() -> None:
    """Stop recording. Already-recorded spans stay exportable."""
    TRACER.enabled = False


def reset() -> None:
    """Clear spans, gauges, histograms, the tail sampler's request trees,
    and the device-memory peak tracker (counters have their own
    ``profiling.counters.clear``)."""
    TRACER.clear()
    METRICS.clear()
    TAIL.clear()
    from . import meminfo

    meminfo.reset_peak()


def span(name: str, cat: str = "", **attrs):
    """Module-level convenience: ``with observability.span("x"): ...``."""
    return TRACER.span(name, cat, **attrs)


def current_span():
    """The innermost active span in this context (the :data:`_NOOP`
    singleton when disabled or outside any span) — instrumented sites use
    it to attach attributes computed mid-operation without re-plumbing the
    span object."""
    if not TRACER.recording:
        return _NOOP
    s = _CURRENT.get()
    return s if s is not None else _NOOP


def current_ids() -> tuple:
    """``(trace_id, span_id)`` of the innermost active span — ``(None,
    None)`` when tracing is off or no span is open. Recovery events attach
    these so a retry/fallback line in the structured log can be cross-
    referenced into the logfmt span stream and the Perfetto view."""
    if not TRACER.recording:
        return (None, None)
    s = _CURRENT.get()
    if s is None:
        try:
            s = TRACER._ambient[-1]
        except IndexError:
            return (None, None)
    return (s.trace_id, s.sid)


class _CountedRead(_NoopSpan):
    """:func:`host_reading` while nothing records: shared and stateless,
    so a read allocates nothing; ``done`` counts and that is all."""

    __slots__ = ()

    def done(self, nbytes: int) -> None:
        profiling.host_read(nbytes)


_COUNTED_READ = _CountedRead()


class _HostReadSpan(Span):
    """:func:`host_reading` while the tracer records: the span
    ``host.read``, which ``done`` gives its ``bytes``."""

    __slots__ = ()

    def done(self, nbytes: int) -> None:
        self.attrs = {**self.attrs, "bytes": int(nbytes)}
        profiling.host_read(nbytes)


def host_reading(site: str):
    """THE wrapper of a blocking device->host read: the blocking call
    itself (``np.asarray(x)``, ``int(total)``, ``jax.device_get(tree)``)
    runs inside the ``with``, and ``done(nbytes)`` — the host copy in hand,
    its size from ``.nbytes`` or static shapes — counts the read as
    :func:`profiling.host_read` always has (``host.reads``,
    ``host.read_bytes``). One batched pull is one read, however many
    arrays it brings. A wait that brings nothing to the host (a
    ``jax.block_until_ready`` where a span has to end with its program)
    runs inside the same ``with`` and calls no ``done``: it is timed like
    a read, with its ``site`` and no ``bytes``, and counted nowhere.

    While the tracer records, the read is also the span ``host.read``
    (``cat="host"``), a child of whatever span is open, with ``site`` (a
    fixed short string a call site) and ``bytes``: from before the
    blocking call to the copy in hand, so its length is what the host
    waited — for the transfer and for everything the device still had
    queued before it. A parent's time outside its reads is the host's
    own. In a profiler capture it is ``dq.host.read`` like any span. No
    duration goes into the counters: a job's counters repeat exactly from
    job to job, and a time would not. Off: one gate read, no allocation."""
    t = TRACER
    if not t.recording:
        return _COUNTED_READ
    return _HostReadSpan(t, "host.read", "host", {"site": site})


# ---------------------------------------------------------------------------
# Distributed trace context (W3C traceparent) + tail-based retention
# ---------------------------------------------------------------------------

#: Exact length of a version-00 ``traceparent`` value
#: (``"00-" + 32 hex + "-" + 16 hex + "-" + 2 hex``). The length bound is
#: checked FIRST, so a hostile megabyte header costs one ``len()``.
_TP_LEN = 55
_HEX_DIGITS = frozenset("0123456789abcdef")


def _is_lower_hex(s: str) -> bool:
    return bool(s) and all(c in _HEX_DIGITS for c in s)


class TraceContext:
    """Wire-level trace identity of ONE served request.

    The client mints one per logical query (``trace_id`` constant across
    retries AND hedges; each attempt carries a fresh child span id so the
    server can tell attempts apart) and sends it W3C-``traceparent``-style
    in both framings. The server adopts it — or, on absent/malformed/
    hostile input, degrades to a locally-minted root (NEVER an error) — and
    echoes ``trace_id`` in the end frame so every ``ClientResult`` is
    joinable to the server-side span tree.

    ``root_trace``/``root_sid`` are filled by :func:`request_span` with the
    INTERNAL integer ids of the adopted root span: the tail sampler keys
    its pending request trees by them, and late stream spans (emitted from
    the wire layer after the execute span closed) parent through them.
    """

    __slots__ = ("trace_id", "parent_id", "remote", "defer",
                 "root_trace", "root_sid")

    def __init__(self, trace_id: str, parent_id: Optional[str] = None,
                 remote: bool = False, defer: bool = False):
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.remote = remote
        #: when True the wire layer finalizes the request tree (it still
        #: has stream spans to record after the server-side verdict).
        self.defer = defer
        self.root_trace: Optional[int] = None
        self.root_sid: Optional[int] = None

    @classmethod
    def mint(cls) -> "TraceContext":
        """A fresh locally-minted root context."""
        return cls(os.urandom(16).hex(), None, remote=False)

    @classmethod
    def parse(cls, value) -> Optional["TraceContext"]:
        """Strict parse of a version-00 ``traceparent``; ``None`` on ANY
        deviation (wrong type/length/version, non-hex, all-zero ids)."""
        if not isinstance(value, str) or len(value) != _TP_LEN:
            return None
        parts = value.split("-")
        if len(parts) != 4:
            return None
        version, trace, parent, flags = parts
        if version != "00":
            return None
        if len(trace) != 32 or not _is_lower_hex(trace) \
                or trace == "0" * 32:
            return None
        if len(parent) != 16 or not _is_lower_hex(parent) \
                or parent == "0" * 16:
            return None
        if len(flags) != 2 or not _is_lower_hex(flags):
            return None
        return cls(trace, parent, remote=True)

    @classmethod
    def adopt(cls, value, defer: bool = False) -> "TraceContext":
        """Parse ``value`` or degrade to a locally-minted root. Passing an
        existing context through is idempotent (``defer`` only widens)."""
        if isinstance(value, cls):
            value.defer = value.defer or defer
            return value
        ctx = cls.parse(value)
        if ctx is None:
            ctx = cls.mint()
        ctx.defer = defer
        return ctx

    def child_traceparent(self) -> str:
        """A fresh per-attempt traceparent under this trace — retries and
        hedges stay distinguishable server-side by their span id."""
        return f"00-{self.trace_id}-{os.urandom(8).hex()}-01"


def _span_doc(s) -> dict:
    """JSON-safe dict view of one span (the /trace wire schema)."""
    return {"name": s.name, "cat": s.cat or "other", "span_id": s.sid,
            "parent_id": s.parent_id, "trace_id": s.trace_id,
            "ts_us": s.ts_us,
            "dur_ms": round((s.dur_us or 0) / 1e3, 3),
            "attrs": {k: (v if isinstance(v, (str, int, float, bool,
                                              type(None))) else repr(v))
                      for k, v in s.attrs.items()}}


class TailSampler:
    """Tail-based retention of completed request span trees.

    Every served request registers its root span here; the tracer sink
    buckets the request's finished spans by the root's internal trace id.
    On completion the tree lands in a bounded ring (recent context, kept
    or not) and the keep-policy — error, deadline_exceeded, any
    ``recovery_fault`` annotation, a breaker transition, or e2e latency
    over the serving SLO — promotes it to the retained store keyed by the
    WIRE trace id (what the client holds). Healthy-path cost when
    observability is disabled stays zero: nothing registers, the sink
    sees an empty pending map."""

    #: Pending-bucket bound: a wire layer that dies before finalizing must
    #: not leak request buckets forever (oldest dropped).
    MAX_PENDING = 1024

    def __init__(self, ring_size: int = 256, retained_size: int = 64):
        self.ring_size = int(ring_size)
        self.retained_size = int(retained_size)
        self._pending: dict = {}    # internal root trace id -> bucket
        self._ring: list = []       # completed tree docs, oldest first
        self._retained: dict = {}   # wire trace id -> [tree docs]
        self._exemplars: dict = {}  # histogram name -> {le: (tid, value)}
        self._lock = threading.Lock()

    def configure(self, ring_size: Optional[int] = None,
                  retained_size: Optional[int] = None) -> None:
        with self._lock:
            if ring_size is not None:
                self.ring_size = max(1, int(ring_size))
            if retained_size is not None:
                self.retained_size = max(1, int(retained_size))

    # -- collection -------------------------------------------------------
    def open_request(self, root, ctx: TraceContext) -> None:
        bucket = {"ctx": ctx, "spans": [], "verdict": None}
        prior = getattr(ctx, "root_trace", None)
        with self._lock:
            if prior is not None:
                # a requeued attempt re-roots the same context: carry the
                # earlier attempt's spans into the new bucket so the full
                # retry history stays one tree
                old = self._pending.pop(prior, None)
                if old is not None:
                    bucket["spans"] = old["spans"]
            self._pending[root.trace_id] = bucket
            while len(self._pending) > self.MAX_PENDING:
                self._pending.pop(next(iter(self._pending)))

    def _on_span(self, s) -> None:
        # tracer sink — one dict lookup per finished span; request spans
        # only (everything else misses the pending map).
        b = self._pending.get(s.trace_id)
        if b is not None:
            b["spans"].append(s)

    def finish_request(self, ctx, *, status=None, reason=None,
                       e2e_ms=None, breaker_opened: bool = False,
                       slo_ms=None) -> None:
        """Attach the server-side completion verdict. Finalizes the tree
        immediately unless the context defers to the wire layer (stream
        spans still to come — it calls :meth:`complete` when done)."""
        key = getattr(ctx, "root_trace", None)
        if key is None:
            return
        with self._lock:
            b = self._pending.get(key)
        if b is None:
            return
        if b["verdict"] is None:
            # first verdict wins: the winning resolution is what the
            # client saw — a lost-race worker's later value must not
            # rewrite a deadline verdict as "ok"
            b["verdict"] = {"status": status, "reason": reason,
                            "e2e_ms": e2e_ms,
                            "breaker_opened": bool(breaker_opened),
                            "slo_ms": slo_ms}
        if not getattr(ctx, "defer", False):
            self.complete(ctx)

    def complete(self, ctx) -> Optional[dict]:
        """Finalize one request tree: evaluate the keep-policy, land the
        doc in the ring, promote to the retained store when kept.
        Idempotent — the second call for a context is a no-op."""
        key = getattr(ctx, "root_trace", None)
        if key is None:
            return None
        with self._lock:
            b = self._pending.pop(key, None)
        if b is None:
            return None
        v = b["verdict"] or {}
        spans = b["spans"]
        reasons = []
        if v.get("status") == "error":
            reasons.append("error")
        if v.get("status") == "deadline_exceeded" \
                or v.get("reason") == "deadline":
            reasons.append("deadline_exceeded")
        if any("recovery_fault" in s.attrs for s in spans):
            reasons.append("recovery_fault")
        if any("dq_drift" in s.attrs for s in spans):
            reasons.append("dq_drift")
        if v.get("breaker_opened"):
            reasons.append("breaker_transition")
        slo_ms, e2e_ms = v.get("slo_ms"), v.get("e2e_ms")
        if slo_ms and e2e_ms and e2e_ms > slo_ms:
            reasons.append("slow")
        doc = {"trace_id": ctx.trace_id, "remote": ctx.remote,
               "status": v.get("status"), "reason": v.get("reason"),
               "e2e_ms": e2e_ms, "kept": bool(reasons),
               "keep_reasons": reasons,
               "spans": [_span_doc(s) for s in spans]}
        aged_unkept = 0
        with self._lock:
            self._ring.append(doc)
            while len(self._ring) > self.ring_size:
                if not self._ring.pop(0)["kept"]:
                    aged_unkept += 1
            if reasons:
                self._retained.setdefault(ctx.trace_id, []).append(doc)
                while len(self._retained) > self.retained_size:
                    self._retained.pop(next(iter(self._retained)))
        if reasons:
            profiling.counters.increment("trace.kept")
            if e2e_ms is not None:
                # last kept trace per latency bucket backs the
                # OpenMetrics exemplars on serve.e2e_ms
                self.exemplar("serve.e2e_ms", e2e_ms, ctx.trace_id)
        if aged_unkept:
            profiling.counters.increment("trace.dropped", aged_unkept)
        return doc

    # -- exemplars --------------------------------------------------------
    def exemplar(self, hist_name: str, value: float, trace_id: str,
                 buckets=DEFAULT_BUCKETS_MS) -> None:
        """Remember ``trace_id`` as the last kept trace for the histogram
        bucket ``value`` falls into (OpenMetrics exemplar source)."""
        le = float("inf")
        for b in buckets:
            if value <= b:
                le = float(b)
                break
        with self._lock:
            self._exemplars.setdefault(hist_name, {})[le] = (
                trace_id, float(value))

    def exemplars(self, hist_name: str) -> dict:
        with self._lock:
            return dict(self._exemplars.get(hist_name, ()))

    def pending_tree(self, trace_id: str) -> Optional[dict]:
        """Snapshot an IN-FLIGHT request tree by its wire trace id — the
        flight recorder fires mid-request (breaker trip, requeue
        exhaustion), before the wire layer finalizes the bucket, so the
        completed-tree views come up empty exactly when an incident
        bundle wants the tree most."""
        with self._lock:
            for b in self._pending.values():
                ctx = b["ctx"]
                if getattr(ctx, "trace_id", None) == trace_id:
                    v = b["verdict"] or {}
                    return {"trace_id": trace_id,
                            "remote": getattr(ctx, "remote", False),
                            "status": v.get("status"),
                            "reason": v.get("reason"),
                            "e2e_ms": v.get("e2e_ms"),
                            "partial": True,
                            "spans": [_span_doc(s) for s in b["spans"]]}
        return None

    # -- views ------------------------------------------------------------
    def lookup(self, trace_id: str) -> list:
        """Every completed tree for one WIRE trace id (retries/hedges of
        one logical query share it) — retained store first, then the
        recent ring."""
        with self._lock:
            trees = list(self._retained.get(trace_id, ()))
            if not trees:
                trees = [d for d in self._ring
                         if d["trace_id"] == trace_id]
        return trees

    def recent(self, limit: int = 50, trace_id: Optional[str] = None) \
            -> list:
        with self._lock:
            ring = list(self._ring)
        if trace_id is not None:
            ring = [d for d in ring if d["trace_id"] == trace_id]
        return ring[-max(0, int(limit)):]

    def retained_ids(self) -> list:
        with self._lock:
            return list(self._retained)

    def report(self) -> dict:
        with self._lock:
            return {"pending": len(self._pending),
                    "ring": len(self._ring),
                    "retained": len(self._retained),
                    "ring_size": self.ring_size,
                    "retained_size": self.retained_size}

    def clear(self) -> None:
        with self._lock:
            self._pending.clear()
            self._ring.clear()
            self._retained.clear()
            self._exemplars.clear()


#: Process-global tail sampler; its sink rides the tracer (only called
#: while tracing is enabled — the disabled path never reaches sinks).
TAIL = TailSampler()
TRACER._sinks.append(TAIL._on_span)


def request_span(name: str, ctx: Optional[TraceContext],
                 cat: str = "serve", **attrs):
    """Root span for one served request: detached from any ambient/session
    parent so the request tree owns its internal trace id, stamped with
    the wire trace identity, and registered with the tail sampler.
    Returns the shared no-op when tracing is off or no context given."""
    t = TRACER
    if not t.enabled or ctx is None:
        return _NOOP
    s = Span(t, name, cat, attrs)
    s.parent_id = None
    s.trace_id = s.sid
    wire = {"wire_trace_id": ctx.trace_id}
    if ctx.remote:
        wire["wire_parent_id"] = ctx.parent_id
        wire["remote"] = True
    s.attrs = {**s.attrs, **wire}
    # open BEFORE re-rooting the context: the sampler reads the previous
    # root to merge a requeued attempt's spans into the new bucket
    TAIL.open_request(s, ctx)
    ctx.root_trace = s.sid
    ctx.root_sid = s.sid
    return s


def emit_span(name: str, cat: str = "", dur_ms: float = 0.0,
              ctx: Optional[TraceContext] = None, **attrs) -> None:
    """Record an already-elapsed interval as a finished span, back-dated
    by ``dur_ms``. The serving layer's admission/queue/stream stages run
    outside the execute context (caller thread, asyncio thread) — this is
    how they still land in the request tree: ``ctx`` parents the span
    under the adopted request root. A back-dated span cannot be written
    into a profiler capture (a TraceMe starts when it is opened): it
    exists in the tracer's buffer only."""
    t = TRACER
    if not t.recording:
        return
    s = Span(t, name, cat, attrs)
    if ctx is not None and getattr(ctx, "root_sid", None) is not None:
        s.parent_id = ctx.root_sid
        s.trace_id = ctx.root_trace
    s.dur_us = int(max(float(dur_ms), 0.0) * 1000)
    s.ts_us = t._now_us() - s.dur_us
    t._finish(s)


def op_span(name: str, cat: str = "frame"):
    """Decorator for frame-op style methods: when tracing is enabled, wrap
    the call in a span carrying rows in/out (``num_slots`` — static shape
    info, never a device read) and the number of ``frame.host_sync``
    events the op (and anything nested under it) performed — the per-
    operator sync attribution EXPLAIN ANALYZE reads. Off cost: one gate
    read and a branch."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            t = TRACER
            if not t.recording:
                return fn(self, *args, **kwargs)
            sync0 = profiling.counters.get("frame.host_sync")
            with Span(t, name, cat, {"rows_in": getattr(self, "_n", None)}) \
                    as s:
                out = fn(self, *args, **kwargs)
                n = getattr(out, "_n", None)
                if n is not None:
                    s.set(rows_out=n)
                s.set(host_syncs=profiling.counters.get("frame.host_sync")
                      - sync0)
                return out
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# Recovery + compile-cache annotations
# ---------------------------------------------------------------------------


def recovery_mark() -> int:
    """Cursor into the structured recovery log; pair with
    :func:`recovery_delta` to annotate a span with the retries/fallbacks
    that happened inside it."""
    from .recovery import RECOVERY_LOG

    return len(RECOVERY_LOG)


def recovery_delta(mark: int) -> dict:
    """``{action: count}`` of recovery events recorded since ``mark``
    (empty on a clean run). The log is bounded, so a mark taken more than
    ``maxlen`` events ago degrades to counting the whole window."""
    from .recovery import RECOVERY_LOG

    events = RECOVERY_LOG.events()
    out: dict[str, int] = {}
    for e in events[max(0, min(mark, len(events))):]:
        out[e.action] = out.get(e.action, 0) + 1
    return out


def annotate_recovery(s, mark: int) -> None:
    """Attach ``recovery_<action>=count`` attributes for events since
    ``mark`` (no-op when nothing happened or the span is the no-op)."""
    if s is _NOOP:
        return
    delta = recovery_delta(mark)
    if delta:
        s.set(**{f"recovery_{k}": v for k, v in delta.items()})


def jit_cache_probe(cached_factory) -> Callable[[], str]:
    """Cold-compile vs steady detection for an ``lru_cache``-ed jit-factory
    (``fused_linear_fit_packed`` et al.): snapshot ``cache_info()`` now,
    and the returned thunk reports ``"miss"`` (a new trace+compile was
    built since) or ``"hit"`` (served from cache). Under the explicit flag
    it also mirrors into the ``jit.trace_miss`` / ``jit.trace_hit``
    counters."""
    try:
        before = cached_factory.cache_info().misses
    except AttributeError:        # not an lru_cache — report unknown
        return lambda: "unknown"

    def verdict() -> str:
        try:
            missed = cached_factory.cache_info().misses > before
        except AttributeError:
            return "unknown"
        if TRACER.enabled:
            # the counter mirror belongs to the explicit flag: a profiler
            # session must not change what a job's counters read
            profiling.counters.increment(
                "jit.trace_miss" if missed else "jit.trace_hit")
        return "miss" if missed else "hit"
    return verdict


@contextlib.contextmanager
def fit_span(name: str, *jit_factories, **attrs):
    """The shared fit-instrumentation shape (LinearRegression /
    LogisticRegression both families, LinearSVC): ONE root span per fit,
    opened where ``fit`` begins, carrying the fit attrs, the cold-compile
    vs steady verdict from :func:`jit_cache_probe` on the lru-cached jit
    factories the fit may build from (``miss`` when any of them traced a
    new program), and recovery retry/fallback annotations for anything the
    resilience layer did inside. Yields the span (the no-op when off) —
    the caller opens ``fit.prepare`` / ``fit.solve`` under it and sets
    result attrs (iterations, converged) on it. The gate is read ONCE
    here, so a profiler that starts or stops mid-fit cannot desync the
    probe from the span."""
    t = TRACER
    if not t.recording:
        yield _NOOP
        return
    verdicts = [jit_cache_probe(f) for f in jit_factories]
    mark = recovery_mark()
    with Span(t, name, "fit", attrs) as s:
        yield s
        seen = [v() for v in verdicts]
        s.set(compile="miss" if "miss" in seen
              else (seen[0] if seen else "unknown"))
        annotate_recovery(s, mark)


def scope(name: str):
    """``jax.named_scope`` under :data:`TRACE_PREFIX`, for the bodies of
    compiled programs: every operation traced inside carries
    ``dq.<name>`` in its op metadata, which XProf/Perfetto show beside the
    device operation. Metadata only — HLO instruction names, fusion and
    the compile-cache key are unchanged. Costs nothing at run time (it
    exists while the program is traced)."""
    return jax.named_scope(TRACE_PREFIX + name)


# ---------------------------------------------------------------------------
# Per-query stats collection (EXPLAIN ANALYZE)
# ---------------------------------------------------------------------------


class QueryStatsCollector:
    """Scopes the span and counter streams to ONE query so EXPLAIN ANALYZE
    can attribute them to plan operators: every span finished while the
    collector is installed lands in ``spans`` (in completion order), and
    ``counter_delta()`` reports how every monotonic counter moved.

    Scoped to the INSTALLING thread: a query executes synchronously on
    one thread, and filtering by thread id keeps two concurrent EXPLAIN
    ANALYZE queries (cross-thread frame sharing is supported engine-wide)
    from polluting each other's span streams. Spans an op hands to a
    worker thread would be excluded — no instrumented path does that
    today. Counter deltas remain process-global (counters carry no
    thread identity); concurrent queries share those."""

    def __init__(self):
        self.spans: list = []
        self._tid = threading.get_ident()
        self._counters0 = profiling.counters.snapshot()

    def _on_span(self, s) -> None:
        if s.tid == self._tid:
            self.spans.append(s)

    def counter_delta(self) -> dict:
        """``{name: increment}`` for every counter that moved since the
        collector was installed (recovery/fallback/compile/host-sync
        activity of exactly this query)."""
        now = profiling.counters.snapshot()
        out = {}
        for k, v in now.items():
            d = v - self._counters0.get(k, 0)
            if d:
                out[k] = d
        return out

    def spans_named(self, *names) -> list:
        return [s for s in self.spans if s.name in names]


# query_stats nesting/concurrency state: the enabled/mem_sample restore
# is REFCOUNTED (the outermost/first collector snapshots, the last one
# out restores) so a collector exiting on one thread cannot disable
# tracing while another thread's EXPLAIN ANALYZE is mid-flight.
_QS_LOCK = threading.Lock()
_QS_ACTIVE = 0
_QS_WAS_ENABLED = False
_QS_WAS_MEM = False


@contextlib.contextmanager
def query_stats(sample_memory: bool = True):
    """Install a :class:`QueryStatsCollector` for the duration of one
    query (the EXPLAIN ANALYZE execution window). Activates tracing for
    the window if it is off — per-query activation is the contract that
    keeps the DEFAULT path a no-op — and restores the previous state
    when the LAST active collector exits (refcounted: safe under
    concurrent queries from multiple threads; each collector sees only
    its own thread's spans). ``sample_memory`` additionally turns on
    per-span device-memory sampling (``peak_mem`` attrs; see
    ``utils.meminfo``)."""
    global _QS_ACTIVE, _QS_WAS_ENABLED, _QS_WAS_MEM
    t = TRACER
    with _QS_LOCK:
        if _QS_ACTIVE == 0:
            _QS_WAS_ENABLED = t.enabled
            _QS_WAS_MEM = t.mem_sample
        _QS_ACTIVE += 1
        if not t.enabled:
            enable(max_spans=t.max_spans, log_spans=t.log_spans)
        if sample_memory:
            t.mem_sample = True
    qs = QueryStatsCollector()
    with t._lock:
        t._sinks.append(qs._on_span)
    try:
        yield qs
    finally:
        with t._lock:
            try:
                t._sinks.remove(qs._on_span)
            except ValueError:
                pass
        with _QS_LOCK:
            _QS_ACTIVE -= 1
            if _QS_ACTIVE == 0:
                t.mem_sample = _QS_WAS_MEM
                t.enabled = _QS_WAS_ENABLED


# ---------------------------------------------------------------------------
# Unified jit-cache introspection
# ---------------------------------------------------------------------------


class ProgramHandle:
    """One enumerable cached program: a stable ``program_key`` plus a way
    to RE-TRACE it abstractly (``jax.make_jaxpr`` over the recorded
    abstract argument specs — zero compiles, zero device execution).

    This is the contract between every compiled-program cache and the
    jaxpr-level auditor (``analysis/program``, the dqaudit tier) and the
    future cost-based optimizer: without it, enumerating "every program
    the engine would replay in serving" needs private imports into four
    modules. Producers register a zero-arg enumerator via
    :meth:`CacheRegistry.register_programs`.

    Fields:

    * ``cache`` — the producer's registry name (``pipeline``/``grouped``/
      ``solver``/``fit.factories``);
    * ``program_key`` — stable identity, identical to the
      ``program_key`` field of the matching ``report()`` entry;
    * ``fn`` / ``args`` / ``kwargs`` — the traceable callable and its
      abstract example arguments (``jax.ShapeDtypeStruct`` leaves for
      arrays; concrete host scalars where values are part of the calling
      convention). ``fn`` is the UN-counted trace body where the
      producer tracks replay counters — auditing must not distort stats;
    * ``variants`` — name → ``(args, kwargs)`` (compared against the
      base trace) or a LIST of such pairs (compared among themselves —
      the form real producers use, e.g. bucket x2 vs x4, so both traces
      are fresh under the current config rather than one being jax's
      cached trace of the recorded shape): alternates the producer
      declares structurally equivalent; the retrace-hazard detector
      re-traces each and compares structural jaxpr hashes;
    * ``mesh`` / ``guarded`` — the installed mesh (``None`` off-mesh)
      and whether dispatch routes through the process-wide collective
      guard (``parallel.mesh.serialize_collectives``);
    * ``meta`` — free-form producer facts (``expected_traces`` /
      ``observed_traces`` for the retrace detector, ``expect_no_consts``
      for the literal-hoisting check, …).
    """

    __slots__ = ("cache", "program_key", "fn", "args", "kwargs",
                 "variants", "mesh", "guarded", "meta")

    def __init__(self, cache: str, program_key: str, fn,
                 args: tuple = (), kwargs: Optional[dict] = None,
                 variants: Optional[dict] = None, mesh=None,
                 guarded: Optional[bool] = None,
                 meta: Optional[dict] = None):
        self.cache = cache
        self.program_key = str(program_key)
        self.fn = fn
        self.args = tuple(args)
        self.kwargs = dict(kwargs or {})
        self.variants = dict(variants or {})
        self.mesh = mesh
        self.guarded = guarded
        self.meta = dict(meta or {})

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"ProgramHandle({self.cache!r}, "
                f"{self.program_key[:60]!r}, variants="
                f"{sorted(self.variants)})")


class CacheRegistry:
    """One registry every compiled-program cache reports into: the
    pipeline compiler (``ops/compiler.py``), the grouped-execution engine
    (``ops/segments.py``), the solver jit entry points
    (``models/solvers.py``), and the packed-fit factories
    (``parallel/distributed.py``) each register a zero-arg stats callable
    under a stable name. ``report()`` (surfaced as
    ``session.cache_report()``) returns the merged view; EXPLAIN ANALYZE
    diffs two reports to print one line per cached program the query
    touched. Producers additionally register a program enumerator
    (:meth:`register_programs`) yielding :class:`ProgramHandle` records —
    the re-trace surface the jaxpr auditor (``analysis/program``) and the
    future cost-based optimizer consume."""

    def __init__(self):
        self._providers: dict[str, Callable[[], dict]] = {}
        self._program_providers: dict[str, Callable[[], list]] = {}
        self._lock = threading.Lock()

    def register(self, name: str, stats_fn: Callable[[], dict]) -> None:
        """Idempotent: re-registration under the same name replaces (a
        module reload must not accumulate stale providers)."""
        with self._lock:
            self._providers[name] = stats_fn

    def register_programs(self, name: str,
                          programs_fn: Callable[[], list]) -> None:
        """Register a zero-arg enumerator returning the producer's
        currently-cached programs as :class:`ProgramHandle` records.
        Idempotent like :meth:`register`."""
        with self._lock:
            self._program_providers[name] = programs_fn

    def unregister(self, name: str) -> None:
        with self._lock:
            self._providers.pop(name, None)
            self._program_providers.pop(name, None)

    def programs(self) -> tuple[list, dict]:
        """Every registry-enumerable cached program, merged across
        producers. Returns ``(handles, errors)`` where ``errors`` maps a
        producer name to the exception string its enumerator raised —
        surfaced (never swallowed) so an audit can report partial
        enumeration instead of silently under-covering."""
        with self._lock:
            items = list(self._program_providers.items())
        handles: list = []
        errors: dict[str, str] = {}
        for name, fn in sorted(items):
            try:
                handles.extend(fn())
            except Exception as e:
                errors[name] = f"{type(e).__name__}: {e}"
        return handles, errors

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._providers)

    def report(self) -> dict:
        with self._lock:
            items = list(self._providers.items())
        out: dict = {}
        for name, fn in sorted(items):
            try:
                out[name] = fn()
            except Exception as e:   # introspection must never take
                out[name] = {"error": str(e)}  # a query down
        return out


#: Process-global cache registry (see :class:`CacheRegistry`).
CACHES = CacheRegistry()


def cache_report() -> dict:
    """Merged per-cache introspection: size/capacity, hits/misses/
    evictions, and per-entry detail (plan-key prefix, hit count, bucket
    histogram) where the producer tracks it."""
    return CACHES.report()


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def _tid_map(spans) -> dict:
    """Stable small integer per OS thread id (chrome tids read better)."""
    out: dict[int, int] = {}
    for s in spans:
        if s.tid not in out:
            out[s.tid] = len(out)
    return out


def chrome_trace() -> dict:
    """Chrome trace-event JSON object (``{"traceEvents": [...]}``) —
    complete ("X") events with microsecond timestamps; span/parent ids ride
    in ``args`` so tooling can rebuild the tree exactly. Open spans export
    with their duration so far and ``"open": true``."""
    tracer = TRACER
    spans = tracer.spans()
    tids = _tid_map(spans)
    pid = os.getpid()
    events = []
    for s in spans:
        open_ = s.dur_us is None
        dur = (tracer._now_us() - s.ts_us) if open_ else s.dur_us
        args = {k: v for k, v in s.attrs.items()}
        args["trace_id"] = s.trace_id
        args["span_id"] = s.sid
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        if open_:
            args["open"] = True
        events.append({
            "ph": "X", "name": s.name, "cat": s.cat or "other",
            "ts": s.ts_us, "dur": max(int(dur), 1),
            "pid": pid, "tid": tids[s.tid], "args": args,
        })
    # Counter ("ph": "C") events — Perfetto draws each metric as a
    # resource track under the span timeline (mem.live_bytes, serving
    # queue depth, pipeline hit/compile counts; see
    # Tracer._maybe_sample_counters for the sampling contract).
    for ts, sample in tracer.counter_samples():
        for metric, value in sample.items():
            events.append({
                "ph": "C", "name": metric, "cat": "resource",
                "ts": ts, "pid": pid,
                "args": {"value": value},
            })
    return {"traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"framework": "sparkdq4ml_tpu",
                          "dropped_spans": tracer.dropped}}


def dump_chrome_trace(path: str) -> str:
    """Write :func:`chrome_trace` to ``path`` (atomic rename); returns the
    path. Open in Perfetto / ``chrome://tracing``."""
    doc = chrome_trace()
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def trace_report() -> str:
    """Human-readable span tree (indentation = parentage), oldest first."""
    spans = sorted(TRACER.spans(), key=lambda s: (s.ts_us, s.sid))
    children: dict[Optional[int], list] = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    by_id = {s.sid: s for s in spans}
    lines: list[str] = []

    def emit(s, depth):
        dur = ("open" if s.dur_us is None
               else f"{s.dur_us / 1e3:.3f} ms")
        attrs = format_kv(**s.attrs)
        lines.append("  " * depth + f"{s.name} [{s.cat or 'other'}] {dur}"
                     + (f"  {attrs}" if attrs else ""))
        for c in children.get(s.sid, []):
            emit(c, depth + 1)

    # roots: no parent, or parent already evicted from the bounded buffer
    for s in spans:
        if s.parent_id is None or s.parent_id not in by_id:
            emit(s, 0)
    if TRACER.dropped:
        lines.append(f"dropped={TRACER.dropped} spans (bounded buffer "
                     "wrapped; raise spark.observability.maxSpans)")
    return "\n".join(lines)


def _prom_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() or ch in "_:") else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return "sparkdq4ml_" + s


def _prom_num(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


#: ``# HELP`` text per metric-name prefix (first match wins); the fallback
#: names the original dotted metric so a scrape reader can map the
#: sanitized Prometheus name back to the in-process counter.
_HELP_PREFIXES = (
    ("serve.", "query-serving layer: admission, queueing, per-tenant SLO "
     "(serve/)"),
    ("net.", "network serving front end: socket protocol + resilient "
     "client (serve/net.py, serve/client.py)"),
    ("recovery.", "resilience-layer event count (utils.recovery)"),
    ("pipeline.", "fused expression-pipeline compiler (ops/compiler.py)"),
    ("grouped.", "device-resident grouped execution (ops/segments.py)"),
    ("jit.", "XLA trace/compile cache activity"),
    ("solver.", "linear-solver dispatch (models/solvers.py)"),
    ("frame.", "frame-engine op/boundary activity"),
    ("parallel.", "mesh collective dispatch (parallel/)"),
    ("mesh.", "device-mesh state"),
    ("mem.", "device-memory accounting (utils.meminfo)"),
    ("trace.", "span tracer internals"),
    ("span_ms.", "span wall-clock latency histogram, milliseconds"),
    ("sql.", "SQL layer activity"),
)


def _prom_help(name: str) -> str:
    # declared help first (METRIC_NAMES / the prefix families — the
    # registry the dqlint metric-name rule enforces), then the legacy
    # subsystem prefixes, then the name-mapping fallback
    declared = METRIC_NAMES.get(name)
    if declared is None:
        for prefix in METRIC_NAME_PREFIXES:
            if name.startswith(prefix) and name != prefix:
                declared = METRIC_NAME_PREFIXES[prefix]
                break
    if declared is not None:
        return f"{name} - {declared[1]}"
    for prefix, text in _HELP_PREFIXES:
        if name.startswith(prefix):
            return f"{name} - {text}"
    return f"{name} - sparkdq4ml_tpu metric"


def _exemplars_enabled() -> bool:
    """Render-time read of the ``spark.trace.exemplars`` conf flag (late
    import keeps this module free of a config dependency cycle)."""
    try:
        from ..config import config as _cfg

        return bool(getattr(_cfg, "trace_exemplars", False))
    except Exception:   # pragma: no cover - config always importable
        return False


def prometheus_text() -> str:
    """Prometheus text-format snapshot: every counter (including
    ``recovery.*``), every gauge, and every histogram (cumulative
    ``_bucket{le=...}`` series + ``_sum``/``_count``), one scrape. Each
    series carries ``# HELP`` (mapping the sanitized name back to the
    dotted in-process name) and ``# TYPE`` headers; metric names sanitize
    through :func:`_prom_name` (dots and any other illegal characters
    become underscores, leading digits are prefixed)."""
    lines: list[str] = []
    for name, v in sorted(profiling.counters.snapshot().items()):
        pn = _prom_name(name)
        lines.append(f"# HELP {pn} {_prom_help(name)}")
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {_prom_num(v)}")
    snap = METRICS.snapshot()
    exemplars_on = _exemplars_enabled()
    for name in sorted(snap):
        v = snap[name]
        pn = _prom_name(name)
        lines.append(f"# HELP {pn} {_prom_help(name)}")
        if isinstance(v, dict):      # histogram summary
            lines.append(f"# TYPE {pn} histogram")
            ex = TAIL.exemplars(name) if exemplars_on else {}
            for le, c in v["buckets"].items():
                line = f'{pn}_bucket{{le="{_prom_num(le)}"}} {c}'
                e = ex.get(float(le))
                if e is not None:
                    # OpenMetrics exemplar: the last KEPT trace id that
                    # landed in this bucket — a scrape reader can jump
                    # straight from a latency bucket to /trace/<id>.
                    line += (f' # {{trace_id="{e[0]}"}} '
                             f'{_prom_num(e[1])}')
                lines.append(line)
            lines.append(f"{pn}_sum {_prom_num(v['sum'])}")
            lines.append(f"{pn}_count {v['count']}")
        else:
            lines.append(f"# TYPE {pn} gauge")
            lines.append(f"{pn} {_prom_num(v)}")
    return "\n".join(lines) + "\n"
