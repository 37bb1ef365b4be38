#!/usr/bin/env python
"""Chaos-soak harness — the standing robustness gate (ISSUE 11).

Drives the 32-client concurrent serving workload (the headline DQ+Lasso
query of the reference app) under N seeded RANDOM fault schedules that
span every registered fault site — the fused pipeline flush, the grouped
segment-reduce program, the native streaming ingest, the QueryServer
worker + admission gates, the cross-request coalescer's stacked batch
dispatch (coalescing runs LIVE for the whole soak), the model-fit
ladder, and memory pressure (the ``oom`` budget-shrink fault) — and
asserts the engine's survival contract:

* **zero hangs** — every ``QueryFuture.result()`` returns within a hard
  bound, whatever died underneath;
* **zero result corruption** — every SUCCESSFUL query returns the golden
  numbers (count 24 / RMSE 2.8099 ± 1%); a fault may slow a query or
  refuse it with a structured status, never change its answer;
* **breaker recovery** — a tenant breaker tripped by chaos recovers
  through half-open to closed once the faults stop;
* **coherent counters** — every admitted job resolves exactly once
  (``serve.admit`` == complete + error + deadline_exceeded deltas) and
  every ``recovery.<action>`` counter delta matches the structured
  ``RECOVERY_LOG`` event stream;
* **live telemetry under fire** — the HTTP observability endpoint
  (``serve/http.py``) runs on an ephemeral port with a background
  scraper hitting ``/metrics`` + ``/healthz`` every 100 ms for the whole
  workload: zero scrape failures/hangs, and the admit == complete +
  error + deadline identity is asserted from the SCRAPED Prometheus
  text, not in-process state;
* **tracing under fire** — the soak session runs with distributed
  tracing ON (``spark.trace.*`` sized to hold a full sweep) and the
  incident flight recorder armed (``spark.incident.dir``, cooldown
  off): the scraper hits ``/trace`` + ``/incidents`` alongside
  ``/metrics``, every wire-delivered result's ``trace_id`` must
  resolve through ``/trace/<trace_id>`` (client-synthesized and
  conn_timeout-cut results excluded — no server-side tree exists), and
  every third seed's injected ``serve_admit:breaker_trip`` must leave
  at least one incident bundle behind;
* **stats persistence degrades, never crashes** — each seed writes the
  plan-statistics snapshot (``utils/statstore.py``) with the
  ``stats_persist`` fault site armed: an injected io_error/torn write
  degrades to in-memory-only with coherent ``recovery.*`` counters, and
  the on-disk snapshot stays loadable (a torn temp file never replaces
  it).

Schedules are pure functions of the seed (the ``utils.faults`` crc32
discipline), so a failing seed replays exactly with
``--seeds 1 --base-seed <s>``.

Usage::

    python scripts/chaos_soak.py --seeds 50              # the full gate
    python scripts/chaos_soak.py --seeds 50 --transport socket  # over TCP
    python scripts/chaos_soak.py --seeds 5 --clients 8   # a quick smoke
    python scripts/chaos_soak.py --seeds 1 --base-seed 17  # replay seed 17

``--transport socket`` runs the SAME workload through real sockets
(``serve/net.py``): every client speaks the wire protocol (half frames,
half HTTP) via the resilient client, with the ``net_accept`` /
``net_read`` / ``net_write`` fault sites in candidate rotation — the
gate additionally asserts that every injected net fault resolved
through a ladder rung (a structured recovery event at its site: retry,
timeout cut, counted disconnect — never a silent drop) and that the
``net.*`` counters cohere with the delivered results.

Conf defaults (overridden by flags): ``spark.chaos.seed`` /
``spark.chaos.seeds`` / ``spark.chaos.soakSeconds``. Exit 0 = every seed
held the contract; 1 = a violation (printed per seed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

GOLDEN_COUNT = 24
GOLDEN_RMSE = 2.809940          # SURVEY.md §2.3, dataset-abstract
RESULT_BOUND_S = 300.0          # the zero-hangs bound per result()
BREAKER_COOLDOWN_S = 0.75

#: Candidate fault specs: (site, kind, max Bernoulli p, extra spec args).
#: Each seed includes a deterministic subset with deterministic p values;
#: probabilities stay low enough that most queries succeed (the golden
#: assertion needs successes to bite on).
_CANDIDATES = (
    ("pipeline_flush", "device_error", 0.15, ""),
    ("pipeline_flush", "nan", 0.08, ""),
    ("grouped_flush", "device_error", 0.15, ""),
    ("shard_flush", "device_error", 0.12, ""),
    ("shard_merge", "device_error", 0.12, ""),
    ("ingest_native", "io_error", 0.06, ""),
    ("ingest_native", "torn_chunk", 0.08, ""),
    ("ingest_native", "thread_death", 0.08, ""),
    ("ingest_native", "pool_exhaust", 0.15, ""),
    ("serve_exec", "device_error", 0.10, ""),
    ("serve_admit", "oom", 0.06, ""),
    # n=64: a 64-byte budget — far under any real flush estimate, so a
    # fired oom always forces the row-chunked degrade
    ("oom", "oom", 0.25, ":n=64"),
    ("solver", "device_error", 0.05, ""),
    ("fit_packed", "device_error", 0.05, ""),
    ("stats_persist", "io_error", 0.40, ""),
    ("stats_persist", "torn_chunk", 0.40, ""),
    # the cost-based optimizer's ladder: a planning fault degrades the
    # query to its unrewritten parse shape, never fails or changes it
    ("optimizer", "device_error", 0.25, ""),
    # the device-cost observatory's ladder: an extraction fault leaves
    # that plan unprofiled ("-" on every surface) — /profile keeps
    # answering (the scraper below asserts zero scrape failures)
    ("cost_profile", "device_error", 0.30, ""),
    # the data-quality observatory's ladder (utils/dqprof.py): a sketch
    # fault degrades that flush to unprofiled — the flush itself and
    # the /dq route keep answering (the scraper below asserts it)
    ("dq_profile", "device_error", 0.30, ""),
    # the cross-request coalescer's ladder (serve/coalesce.py): a fault
    # on the STACKED batch dispatch degrades the whole batch to
    # per-request replay of the same cached plans — every member still
    # returns the golden numbers; n=64 under-budgets the stacked bytes
    # so a fired oom always forces the degrade
    ("coalesce", "device_error", 0.12, ""),
    ("coalesce", "stall", 0.08, ""),
    ("coalesce", "oom", 0.12, ":n=64"),
    # the adaptive executor's ladder (sql/adaptive.py): a fault at a
    # re-plan DECISION point degrades that decision to the static plan
    # the query already holds — results stay golden on every rung
    ("aqe", "device_error", 0.20, ""),
    ("aqe", "stall", 0.10, ""),
)


#: Extra candidates for ``--transport socket``: the network fault sites
#: (serve/net.py). Probabilities stay low — most wire exchanges must
#: succeed so the golden assertion and the idempotent-retry path both
#: get exercised on the same run.
_NET_CANDIDATES = (
    ("net_accept", "conn_reset", 0.05, ""),
    ("net_read", "conn_reset", 0.05, ""),
    ("net_read", "stall", 0.04, ""),
    ("net_read", "slow_client", 0.04, ""),
    ("net_write", "conn_reset", 0.05, ""),
    ("net_write", "partial_write", 0.05, ""),
    ("net_write", "stall", 0.04, ""),
)

#: Guaranteed attempt-1 fault per seed (round-robin): even a small smoke
#: run exercises every ladder, instead of leaving low-p Bernoulli draws
#: to the dice at low attempt counts.
_ROTATION = (
    ("pipeline_flush", "device_error", ""),
    ("grouped_flush", "device_error", ""),
    ("shard_flush", "device_error", ""),
    ("shard_merge", "device_error", ""),
    ("serve_exec", "device_error", ""),
    ("oom", "oom", ":n=64"),
    ("ingest_native", "io_error", ""),
    ("ingest_native", "pool_exhaust", ""),
    ("pipeline_flush", "nan", ""),
    ("stats_persist", "io_error", ""),
    ("stats_persist", "torn_chunk", ""),
    ("optimizer", "device_error", ""),
    ("cost_profile", "device_error", ""),
    ("dq_profile", "device_error", ""),
    ("coalesce", "device_error", ""),
    ("coalesce", "oom", ":n=64"),
    ("aqe", "device_error", ""),
)

#: Guaranteed net faults for the socket arm, rotated alongside
#: ``_ROTATION`` (independent index stream, so every (compute, net)
#: pairing eventually occurs across a 50-seed sweep).
_NET_ROTATION = (
    ("net_accept", "conn_reset", ""),
    ("net_read", "conn_reset", ""),
    ("net_read", "stall", ""),
    ("net_read", "slow_client", ""),
    ("net_write", "conn_reset", ""),
    ("net_write", "partial_write", ""),
    ("net_write", "stall", ""),
)


def build_schedule(seed: int, transport: str = "inproc") -> str:
    """Seeded random fault schedule: a deterministic subset of the
    candidate (site, kind) pairs, each with a deterministic probability —
    pure function of ``(seed, transport)`` — plus one guaranteed
    attempt-1 fault from the rotation (and, for ``--transport socket``,
    the net candidates and one guaranteed net fault). Every third seed
    also schedules a ``serve_admit:breaker_trip`` so the trip → shed →
    half-open → closed lifecycle is exercised regularly, not just when
    the dice say so."""
    from sparkdq4ml_tpu.utils.faults import _det_uniform

    candidates = _CANDIDATES
    if transport == "socket":
        candidates = _CANDIDATES + _NET_CANDIDATES
    specs = []
    for site, kind, max_p, extra in candidates:
        pick = _det_uniform(seed, f"sched-pick:{site}:{kind}", 1)
        if pick < 0.5:
            continue
        p = 0.01 + max_p * _det_uniform(seed, f"sched-p:{site}:{kind}", 1)
        specs.append(f"{site}:{kind}:p={p:.4f}{extra}")
    # appended unconditionally: specs are additive (the plan fires the
    # first DUE spec per attempt), so a low-p Bernoulli pick for the
    # same pair must not displace the guaranteed attempt-1 fault
    site, kind, extra = _ROTATION[seed % len(_ROTATION)]
    specs.append(f"{site}:{kind}:1{extra}")
    if transport == "socket":
        site, kind, extra = _NET_ROTATION[seed % len(_NET_ROTATION)]
        specs.append(f"{site}:{kind}:1{extra}")
    if seed % 3 == 0:
        specs.append("serve_admit:breaker_trip:2")
    return ";".join(specs)


def headline_job(data_path: str):
    """The reference app's DQ+Lasso flow as a tenant-scoped server job
    (the test_serve workload): CSV ingest, two DQ rules with SQL
    filters, vector assembly, Lasso fit — touches ingest, the fused
    pipeline, SQL, and the packed-fit ladder in one query."""
    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.models import LinearRegression, VectorAssembler

    def job(ctx):
        dq.register_builtin_rules()
        df = (ctx.read.format("csv").option("inferSchema", "true")
              .option("header", "false").load(data_path))
        df = df.with_column_renamed("_c0", "guest") \
               .with_column_renamed("_c1", "price")
        df = df.with_column("price_no_min",
                            dq.call_udf("minimumPriceRule", dq.col("price")))
        ctx.register_view("price", df)
        df = ctx.sql("SELECT cast(guest as int) guest, price_no_min AS "
                     "price FROM price WHERE price_no_min > 0")
        df = df.with_column(
            "price_correct_correl",
            dq.call_udf("priceCorrelationRule", dq.col("price"),
                        dq.col("guest")))
        ctx.register_view("price", df)
        df = ctx.sql("SELECT guest, price_correct_correl AS price "
                     "FROM price WHERE price_correct_correl > 0")
        # a grouped leg so the segment-reduce ladder (grouped_flush) is
        # on the soak's execution path; its per-group counts must sum to
        # the row count whichever lowering (device or host rung) ran
        ctx.register_view("price_clean", df)
        grouped = ctx.sql("SELECT guest, count(*) c FROM price_clean "
                          "GROUP BY guest")
        group_sum = int(sum(grouped.to_pydict()["c"]))
        df = df.with_column("label", df.col("price"))
        df = VectorAssembler(["guest"], "features").transform(df)
        model = LinearRegression(max_iter=40, reg_param=1.0,
                                 elastic_net_param=1.0).fit(df)
        return {"count": df.count(), "group_sum": group_sum,
                "rmse": float(model.summary.root_mean_squared_error)}

    return job


def _golden(value) -> bool:
    return (isinstance(value, dict) and value.get("count") == GOLDEN_COUNT
            and value.get("group_sum") == GOLDEN_COUNT
            and abs(value.get("rmse", 0.0) - GOLDEN_RMSE)
            / GOLDEN_RMSE < 0.01)


SCRAPE_INTERVAL_S = 0.1


def _parse_scrape(text: str) -> dict:
    """``{metric_name: value}`` from a Prometheus text scrape (samples
    only; HELP/TYPE and labelled series skipped)."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or "{" in line:
            continue
        parts = line.split()
        if len(parts) == 2:
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                pass
    return out


class _Scraper:
    """Background scraper hammering the live telemetry endpoint every
    ``SCRAPE_INTERVAL_S`` for the duration of one seed — the "telemetry
    under fire" arm: scrapes must keep answering (bounded, never a hang)
    while 32 clients and the fault plan do their worst, and the final
    scraped text is what the coherence identity is asserted from."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"
        self.scrapes = 0
        self.failures: list[str] = []
        self.last_metrics: dict = {}
        self.last_health: dict = {}
        self.last_profile: dict = {}
        self.last_dq: dict = {}
        self.last_trace: dict = {}
        self.last_incidents: dict = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="chaos-scraper")

    def scrape_once(self) -> None:
        import urllib.request

        with urllib.request.urlopen(self.base + "/metrics",
                                    timeout=10) as resp:
            self.last_metrics = _parse_scrape(resp.read().decode())
        with urllib.request.urlopen(self.base + "/healthz",
                                    timeout=10) as resp:
            self.last_health = json.loads(resp.read().decode())
        # the device-cost observatory under fire: /profile must keep
        # answering (budgeted extraction; injected cost_profile faults
        # degrade single plans to unprofiled, never the route) — a
        # 30 s timeout bounds the budgeted lower+compile sweep
        with urllib.request.urlopen(self.base + "/profile?top=8",
                                    timeout=30) as resp:
            self.last_profile = json.loads(resp.read().decode())
        # the data-quality observatory under fire: /dq must keep
        # answering its schema (its drain is the module's counted
        # cold-path sync; injected dq_profile faults degrade single
        # flushes to unprofiled, never the route)
        with urllib.request.urlopen(self.base + "/dq?top=8",
                                    timeout=10) as resp:
            self.last_dq = json.loads(resp.read().decode())
        # the tracing tier under fire: the span feed and the incident
        # index must keep answering while the fault plan churns the
        # tail sampler and the flight recorder underneath them
        with urllib.request.urlopen(self.base + "/trace?limit=8",
                                    timeout=10) as resp:
            self.last_trace = json.loads(resp.read().decode())
        with urllib.request.urlopen(self.base + "/incidents",
                                    timeout=10) as resp:
            self.last_incidents = json.loads(resp.read().decode())
        self.scrapes += 1

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.scrape_once()
            except Exception as e:
                # /healthz answers 503 while degraded — that is a VALID
                # scrape (the balancer semantics), not a failure
                import urllib.error

                if isinstance(e, urllib.error.HTTPError) \
                        and e.code == 503:
                    self.last_health = json.loads(e.read().decode())
                    self.scrapes += 1
                else:
                    self.failures.append(f"{type(e).__name__}: {e}")
            self._stop.wait(SCRAPE_INTERVAL_S)

    def start(self) -> "_Scraper":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30)


def run_seed(session, seed: int, clients: int, queries: int, workers: int,
             data_path: str, soak_s: float, transport: str = "inproc",
             log=print) -> dict:
    """One seeded chaos round; returns the per-seed verdict dict with a
    ``violations`` list (empty = the contract held). ``transport=
    "socket"`` drives the same workload through real sockets
    (serve/net.py), clients alternating the frame and HTTP framings via
    :class:`~sparkdq4ml_tpu.serve.ResilientClient`, with the net fault
    sites in rotation."""
    from sparkdq4ml_tpu.serve import QueryServer, TenantQuota
    from sparkdq4ml_tpu.utils import faults, profiling
    from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG, RetryPolicy

    schedule = build_schedule(seed, transport)
    violations: list[str] = []
    RECOVERY_LOG.clear()
    before = profiling.counters.snapshot()
    job = headline_job(data_path)
    # coalesce=True: the soak runs with cross-request coalescing LIVE,
    # so the ``coalesce`` fault site in the rotation actually lands on
    # stacked batches (min_queue_depth=1 — 32 clients over 8 workers
    # keep the queue deep enough without it, but a small --clients
    # smoke must exercise the ladder too)
    server = QueryServer(
        session, workers=workers, max_queue=4 * clients,
        default_quota=TenantQuota(max_in_flight=2, max_queued=queries + 2),
        breaker_threshold=3, breaker_cooldown=BREAKER_COOLDOWN_S,
        metrics_port=0, slo_p99_ms=1000.0, coalesce=True,
        coalesce_max_delay_ms=5.0, coalesce_max_batch=8,
        coalesce_min_queue_depth=1).start()
    net = None
    if transport == "socket":
        from sparkdq4ml_tpu.serve import NetServer

        # a tight connTimeoutMs keeps the injected stall/slow_client
        # ladders (and any real slow peer) cheap per occurrence
        net = NetServer(server, host="127.0.0.1", port=0,
                        conn_timeout_s=2.0).start()
        net.register_job("headline", job)
    scraper = _Scraper(server.telemetry.port).start()
    try:
        scraper.scrape_once()          # baseline from the wire
    except Exception as e:
        violations.append(f"baseline scrape failed: {e}")
    scrape0 = dict(scraper.last_metrics)
    incidents0 = {r.get("id") for r in
                  scraper.last_incidents.get("incidents", ())}
    plan = faults.install_plan(faults.parse_plan(schedule, seed=seed))
    results: list = []
    res_lock = threading.Lock()
    hangs = [0]
    t0 = time.perf_counter()

    def client(i: int) -> None:
        tenant = f"chaos-{i:02d}"
        out = []
        while True:
            done = len(out)
            if done >= queries and time.perf_counter() - t0 >= soak_s:
                break
            fut = server.submit(job, tenant=tenant)
            try:
                out.append(fut.result(timeout=RESULT_BOUND_S))
            except TimeoutError:
                with res_lock:
                    hangs[0] += 1
                break
        with res_lock:
            results.extend(out)

    def socket_client(i: int) -> None:
        # half the clients speak the frame protocol, half HTTP; the
        # zero-hangs contract is asserted on WALL TIME per logical call
        # (the resilient client itself must never wedge)
        from sparkdq4ml_tpu.serve import ResilientClient

        tenant = f"chaos-{i:02d}"
        out = []
        wire = ResilientClient(
            "127.0.0.1", net.port,
            transport="frame" if i % 2 else "http", tenant=tenant,
            policy=RetryPolicy(
                max_attempts=4, backoff_base=0.05,
                attempt_deadline=RESULT_BOUND_S / 3.0,
                total_deadline=RESULT_BOUND_S - 10.0))
        try:
            while True:
                done = len(out)
                if done >= queries and time.perf_counter() - t0 >= soak_s:
                    break
                t_call = time.perf_counter()
                r = wire.call_job("headline", tenant=tenant)
                if time.perf_counter() - t_call > RESULT_BOUND_S:
                    with res_lock:
                        hangs[0] += 1
                    break
                out.append(r)
        finally:
            wire.close()
        with res_lock:
            results.extend(out)

    runner = socket_client if transport == "socket" else client
    threads = [threading.Thread(target=runner, args=(i,),
                                name=f"chaos-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Wire results the SERVER must have read a request for: a result cut
    # at the read rung (conn_timeout before the request parse counted
    # it) or synthesized client-side (retries exhausted, client-side
    # deadline) is real resilience output, but no net.requests tick owes
    # it anything. Captured before the breaker probes append in-process
    # results.
    n_wire = len([r for r in results
                  if getattr(r, "where", None) != "client"
                  and getattr(r, "reason", None) != "conn_timeout"])
    # stats-persistence arm: write the plan-stats snapshot WHILE the
    # fault plan is armed — a due stats_persist io_error/torn write must
    # degrade to in-memory-only (save returns False, recovery event
    # logged), and whatever is on disk must stay a loadable snapshot
    from sparkdq4ml_tpu.utils import statstore

    stats_path = os.path.join(REPO, f".chaos_stats_{os.getpid()}.jsonl")
    try:
        statstore.STORE.save(stats_path, merge=True)
    except Exception as e:
        violations.append(
            f"stats_persist save raised {type(e).__name__}: {e} "
            "(must degrade, never crash)")
    if os.path.exists(stats_path):
        try:
            with open(stats_path) as f:
                header = json.loads(f.readline())
            assert header.get("version") == statstore.SCHEMA_VERSION
        except Exception as e:
            violations.append(
                f"stats snapshot on disk is torn/corrupt after save: {e}")
    fired = list(plan.fired)
    faults.clear()     # chaos off before the recovery probe

    # breaker recovery: every key chaos tripped or failed must admit a
    # half-open trial after the cooldown and CLOSE on one clean probe
    # query (a key whose cooldown already expired mid-workload probes
    # the same way — the half-open → closed transition is the assertion)
    recovered = 0
    tripped = sum(1 for _, k, _ in fired if k == "breaker_trip")
    open_keys = [k for k, st in server.breaker.snapshot().items()
                 if st["open"] or st["consecutive_failures"] > 0]
    for key in open_keys:
        tenant = key.split("/", 1)[1]
        deadline = time.monotonic() + 4 * BREAKER_COOLDOWN_S
        while not server.breaker.allow(key):
            if time.monotonic() > deadline:
                violations.append(
                    f"breaker {key} never reached half-open")
                break
            time.sleep(0.05)
        else:
            try:
                probe = server.submit(job, tenant=tenant).result(
                    timeout=RESULT_BOUND_S)
            except TimeoutError:
                violations.append(
                    f"breaker {key} half-open probe hung past "
                    f"{RESULT_BOUND_S:.0f}s")
                continue
            if not (probe.ok and _golden(probe.value)):
                violations.append(
                    f"breaker {key} half-open probe failed: {probe.status}")
            elif server.breaker.snapshot().get(key, {}).get("open"):
                violations.append(f"breaker {key} did not close on success")
            else:
                recovered += 1
            results.append(probe)
    # Final scrape AFTER every future resolved and BEFORE the server
    # (and its telemetry socket) stops: the admit == complete + error +
    # deadline identity is asserted from the WIRE text. The background
    # scraper stops FIRST — an in-flight background scrape completing
    # late would overwrite last_metrics with staler counters than the
    # foreground read below. A short retry window then absorbs the
    # microseconds between a waiter unblocking and the worker's counter
    # increment landing.
    scraper.stop()
    scrape_deadline = time.monotonic() + 5.0
    keys = ("sparkdq4ml_serve_admit", "sparkdq4ml_serve_complete",
            "sparkdq4ml_serve_error", "sparkdq4ml_serve_deadline_exceeded")
    while True:
        try:
            scraper.scrape_once()
        except Exception as e:
            violations.append(f"final scrape failed: {e}")
            break
        d = {k: scraper.last_metrics.get(k, 0) - scrape0.get(k, 0)
             for k in keys}
        if d[keys[0]] == d[keys[1]] + d[keys[2]] + d[keys[3]]:
            break
        if time.monotonic() > scrape_deadline:
            violations.append(
                "SCRAPED serve counter incoherence: "
                f"admit={d[keys[0]]:.0f} != complete+error+deadline="
                f"{d[keys[1]] + d[keys[2]] + d[keys[3]]:.0f}")
            break
        time.sleep(0.05)
    # tracing arm: every wire result the SERVER delivered must resolve
    # through /trace/<trace_id> on the live endpoint (client-synthesized
    # and conn_timeout-cut results never reached a server-side tree —
    # the same exclusion as n_wire above); new incident bundles are
    # read from the scraped /incidents index, and every third seed's
    # injected breaker_trip must have produced at least one
    from sparkdq4ml_tpu.utils import observability as _obs_soak

    traces_resolved = 0
    new_incidents = 0
    if _obs_soak.TRACER.enabled:
        import urllib.request

        wire_tids = {r.trace_id for r in results
                     if getattr(r, "trace_id", None) is not None
                     and getattr(r, "where", None) != "client"
                     and getattr(r, "reason", None) != "conn_timeout"}
        for tid in wire_tids:
            # the wire layer finalizes a tree AFTER the client sees the
            # end frame — a short poll absorbs that finally-block race
            deadline = time.monotonic() + 5.0
            while True:
                try:
                    with urllib.request.urlopen(
                            f"{scraper.base}/trace/{tid}",
                            timeout=10) as resp:
                        json.loads(resp.read().decode())
                    traces_resolved += 1
                    break
                except Exception as e:
                    if time.monotonic() > deadline:
                        violations.append(
                            f"wire trace_id {tid} never resolved via "
                            f"/trace/<id>: {type(e).__name__}: {e}")
                        break
                    time.sleep(0.05)
        new_incidents = len(
            {r.get("id") for r in
             scraper.last_incidents.get("incidents", ())} - incidents0)
        if seed % 3 == 0 and new_incidents < 1:
            violations.append(
                "injected breaker_trip seed wrote no incident bundle")
    if net is not None:
        net.stop(drain=True)
    if scraper.failures:
        violations.append(
            f"{len(scraper.failures)} scrape failure(s) under fire; "
            f"first: {scraper.failures[0]}")
    if not scraper.last_health.get("status"):
        violations.append("healthz never answered with a status verdict")
    if scraper.last_profile.get("enabled") is None:
        violations.append("/profile never answered with a schema verdict")
    if scraper.last_dq.get("enabled") is None:
        violations.append("/dq never answered with a schema verdict")
    server.stop(drain=True)
    delta = {k: v - before.get(k, 0)
             for k, v in profiling.counters.snapshot().items()
             if v != before.get(k, 0)}

    # -- the contract -------------------------------------------------------
    if hangs[0]:
        violations.append(f"{hangs[0]} result() call(s) hung past "
                          f"{RESULT_BOUND_S:.0f}s")
    ok = [r for r in results if r.ok]
    bad_values = [r for r in ok if not _golden(r.value)]
    if bad_values:
        violations.append(
            f"{len(bad_values)} successful quer(ies) returned corrupted "
            f"results (first: {bad_values[0].value!r})")
    allowed = {"ok", "rejected", "shed", "error", "deadline_exceeded"}
    unstructured = [r for r in results if r.status not in allowed]
    if unstructured:
        violations.append(f"unstructured statuses: "
                          f"{[r.status for r in unstructured]}")
    admitted = delta.get("serve.admit", 0)
    resolved = (delta.get("serve.complete", 0) + delta.get("serve.error", 0)
                + delta.get("serve.deadline_exceeded", 0))
    if admitted != resolved:
        violations.append(
            f"serve counter incoherence: admit={admitted} != "
            f"complete+error+deadline={resolved}")
    by_action: dict[str, int] = {}
    for e in RECOVERY_LOG.events():
        by_action[e.action] = by_action.get(e.action, 0) + 1
    for action, n in by_action.items():
        if delta.get(f"recovery.{action}", 0) != n:
            violations.append(
                f"recovery counter incoherence: recovery.{action}="
                f"{delta.get(f'recovery.{action}', 0)} vs {n} logged "
                "event(s)")
    net_fired: dict[str, int] = {}
    for s, _, _ in fired:
        if s.startswith("net_"):
            net_fired[s] = net_fired.get(s, 0) + 1
    if transport == "socket":
        # ladder-rung proof: every injected net fault left at least one
        # structured recovery event at ITS site — a fault the ladder
        # silently dropped leaves the count short
        for site, n in net_fired.items():
            logged = len(RECOVERY_LOG.events(site=site))
            if logged < n:
                violations.append(
                    f"net fault ladder gap at {site}: {n} fault(s) "
                    f"fired but only {logged} recovery event(s) logged")
        if delta.get("net.accept", 0) <= 0:
            violations.append("socket transport ran but net.accept "
                              "never moved")
        if delta.get("net.requests", 0) < n_wire:
            violations.append(
                f"net.requests={delta.get('net.requests', 0)} below the "
                f"{n_wire} wire results delivered")
    row = {
        "seed": seed, "transport": transport,
        "schedule": schedule, "queries": len(results),
        "completed": len(ok), "refused_or_failed": len(results) - len(ok),
        "faults_fired": len(fired),
        "fault_sites": sorted({s for s, _, _ in fired}),
        "requeues": delta.get("serve.requeue", 0),
        "fault_fallbacks": {
            k: v for k, v in delta.items() if k.endswith("fault_fallback")},
        "oom_chunked": delta.get("pipeline.oom_chunked", 0),
        "breakers_tripped": tripped,
        "breakers_probed": len(open_keys),
        "breakers_recovered": recovered,
        "scrapes": scraper.scrapes,
        "traces_resolved": traces_resolved,
        "incidents_written": new_incidents,
        "net_faults_fired": sum(net_fired.values()),
        "net_client_retries": delta.get("net.client_retry", 0),
        "net_idem_hits": delta.get("net.idem_hit", 0),
        "net_client_gone": delta.get("net.client_gone", 0),
        "stats_persist_degrades": delta.get("stats.persist_failed", 0),
        "wall_s": round(time.perf_counter() - t0, 2),
        "violations": violations,
    }
    log(("OK  " if not violations else "FAIL") + " " + json.dumps(row))
    return row


def run_soak(seeds=None, clients=None, queries=1, workers=8,
             base_seed=None, soak_s=None, data_path=None, session=None,
             transport="inproc", log=print) -> dict:
    """Sweep ``seeds`` seeded chaos rounds; returns the summary dict
    (``ok`` True = every seed held the survival contract). Arguments left
    ``None`` fall back to the session conf (``spark.chaos.*``) defaults.
    """
    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.config import config

    created_here = False
    incident_dir = None
    if session is None:
        import tempfile

        incident_dir = tempfile.mkdtemp(prefix="chaos_incidents_")
        session = (dq.TpuSession.builder().app_name("chaos-soak")
                   .master("local[*]")
                   # tiny chunks: the 320-byte headline CSV streams, so
                   # the mid-stream ingest fault sites are reachable
                   .config("spark.ingest.chunkBytes", "256")
                   # sharding ON (minRows floored so the 40-row headline
                   # frame actually shards): the soak's survival contract
                   # covers the shard_flush/shard_merge ladders and the
                   # sharded serving interplay whenever the backend
                   # exposes a multi-device mesh (inert on one device)
                   .config("spark.shard.enabled", "true")
                   .config("spark.shard.minRows", "8")
                   # the tracing tier rides the whole soak: every wire
                   # result must resolve via /trace/<id>, so the ring
                   # holds a full sweep's worth of healthy trees, and
                   # the flight recorder (cooldown off) must bundle
                   # every third seed's injected breaker trip
                   .config("spark.observability.enabled", "true")
                   .config("spark.trace.ringSize", "8192")
                   .config("spark.trace.retainedSize", "4096")
                   .config("spark.incident.dir", incident_dir)
                   .config("spark.incident.maxBundles", "256")
                   .config("spark.incident.cooldownS", "0")
                   .get_or_create())
        created_here = True
    seeds = int(config.chaos_seeds if seeds is None else seeds)
    base_seed = int(config.chaos_seed if base_seed is None else base_seed)
    soak_s = float(config.chaos_soak_s if soak_s is None else soak_s)
    clients = int(32 if clients is None else clients)
    data_path = data_path or os.path.join(REPO, "data",
                                          "dataset-abstract.csv")
    from sparkdq4ml_tpu.utils import faults

    rows = []
    try:
        for s in range(base_seed, base_seed + seeds):
            rows.append(run_seed(session, s, clients, queries, workers,
                                 data_path, soak_s, transport=transport,
                                 log=log))
    finally:
        faults.clear()
        try:
            os.remove(os.path.join(REPO,
                                   f".chaos_stats_{os.getpid()}.jsonl"))
        except OSError:
            pass
        if created_here:
            session.stop()
            if incident_dir is not None:
                import shutil

                shutil.rmtree(incident_dir, ignore_errors=True)
    bad = [r for r in rows if r["violations"]]
    summary = {
        "seeds": seeds, "clients": clients, "queries_per_client": queries,
        "transport": transport,
        "ok": not bad,
        "net_faults_fired": sum(r["net_faults_fired"] for r in rows),
        "net_client_retries": sum(r["net_client_retries"] for r in rows),
        "net_idem_hits": sum(r["net_idem_hits"] for r in rows),
        "traces_resolved": sum(r["traces_resolved"] for r in rows),
        "incidents_written": sum(r["incidents_written"] for r in rows),
        "failed_seeds": [r["seed"] for r in bad],
        "queries": sum(r["queries"] for r in rows),
        "completed": sum(r["completed"] for r in rows),
        "faults_fired": sum(r["faults_fired"] for r in rows),
        "requeues": sum(r["requeues"] for r in rows),
        "oom_chunked": sum(r["oom_chunked"] for r in rows),
        "breakers_tripped": sum(r["breakers_tripped"] for r in rows),
        "breakers_probed": sum(r["breakers_probed"] for r in rows),
        "breakers_recovered": sum(r["breakers_recovered"] for r in rows),
        "per_seed": rows,
    }
    return summary


def main(argv=None) -> int:
    # Standalone runs shard for real: force a multi-device CPU platform
    # BEFORE the first jax import (a no-op for accelerator backends —
    # the flag only configures the host CPU platform; in-process tier-1
    # smoke inherits the conftest's forced 8 devices instead).
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=None,
                    help="seeded schedules to sweep (spark.chaos.seeds)")
    ap.add_argument("--base-seed", type=int, default=None,
                    help="first seed (spark.chaos.seed); replay one "
                    "failing seed with --seeds 1 --base-seed S")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--queries", type=int, default=1,
                    help="queries per client per seed")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--soak-seconds", type=float, default=None,
                    help="minimum per-seed duration "
                    "(spark.chaos.soakSeconds)")
    ap.add_argument("--transport", choices=("inproc", "socket"),
                    default="inproc",
                    help="inproc: submit() futures (the classic arm); "
                    "socket: real sockets via serve/net.py with the "
                    "net_* fault sites in rotation")
    ap.add_argument("--data", default=None)
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write the summary JSON here")
    args = ap.parse_args(argv)
    summary = run_soak(seeds=args.seeds, clients=args.clients,
                       queries=args.queries, workers=args.workers,
                       base_seed=args.base_seed, soak_s=args.soak_seconds,
                       data_path=args.data, transport=args.transport)
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "per_seed"}, indent=1))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(summary, f, indent=1)
    if not summary["ok"]:
        print(f"CHAOS SOAK FAILED: seeds {summary['failed_seeds']}")
        return 1
    print("chaos soak clean: every seed held the survival contract")
    return 0


if __name__ == "__main__":
    sys.exit(main())
