"""QueryServer — the concurrent query-serving front end.

The reference app is a single-caller batch script; the ROADMAP north star
is serving heavy traffic from many users. This module is the layer in
between: a :class:`QueryServer` multiplexes N concurrent *logical
tenants* over the one process-wide engine (one device, one jit-cache
population), following Snap ML's hierarchical execution framing
(PAPERS.md, arxiv 1803.06333) — many workloads, one shared accelerator
state.

Architecture::

    clients ── submit(sql | fn, tenant=..) ──► AdmissionController
                                                   │ admitted
                                             per-tenant FIFO queues
                                                   │ round-robin, gated on
                                                   │ quota.max_in_flight
                                             worker thread-pool
                                                   │ plan_namespace(tenant)
                                                   │   (isolated mode only)
                                             engine (frame / SQL / fits)

* **Sessions / tenants** — each tenant gets a :class:`TenantContext`
  with its OWN temp-view :class:`~sparkdq4ml_tpu.sql.catalog.Catalog`
  (two tenants can both ``CREATE VIEW price`` without colliding), over
  the SHARED engine and its process-wide plan/jit caches.
* **Shared plan cache** — the structural plan keys from PRs 3/4 contain
  no tenant identity, so tenant B's first query replays tenant A's
  compiled programs with zero new compiles (test-pinned via
  ``cache_report`` diffs). ``shared_plan_cache=False`` partitions the
  pipeline + grouped caches per tenant via
  :func:`ops.compiler.plan_namespace`; only tests turn it off.
  (Solver/fit jit factories key on model params only and stay
  shared in both modes; they carry no per-tenant state.)
* **Admission control** — see :mod:`serve.admission`: breaker shedding,
  global + per-tenant queue bounds, device-memory gate.
* **Deadlines** — ``deadline_s`` bounds a query end-to-end. A job still
  queued past its deadline never executes; a result that lands after the
  deadline is discarded; and ``QueryFuture.result()`` returns a
  structured ``deadline_exceeded`` :class:`QueryResult` at most a grace
  period after the deadline even when the execution is wedged — a
  deadline is never a hang. The in-flight XLA dispatch itself cannot be
  cancelled (same contract as ``utils.recovery.DeadlineExceeded``); the
  worker discards its late result and records ``serve.late_result``.
* **SLO observability** — ``serve.queue_depth`` / ``serve.in_flight`` /
  ``serve.tenants`` gauges, ``serve.queue_ms`` / ``serve.exec_ms`` /
  ``serve.e2e_ms`` latency histograms (plus per-tenant
  ``serve.e2e_ms.<tenant>`` series, capped at
  :data:`MAX_TENANT_SERIES`), and admit/reject/shed/deadline/complete/
  error counters — all through the PR-2 Prometheus surface
  (``session.metrics()`` / ``prometheus_text()`` cover engine + server
  in one scrape). ``submit(collect_stats=True)`` runs the query under
  the PR-5 ``observability.query_stats`` collector and attaches it to
  the result.

Cost contract: a process that never starts a server pays nothing — no
threads, no counters, no gauges (the disabled-mode rule every subsystem
here follows). Threading model: see ``session.py`` § "Threading model".
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Optional

from ..config import CONF_FALSE
from ..config import config as _cfg
from ..utils import faults as _faults
from ..utils import incidents as _incidents
from ..utils import observability as _obs
from ..utils.profiling import counters
from ..utils.recovery import CircuitBreaker
from .admission import AdmissionController, TenantQuota

#: Per-tenant latency-histogram cap: beyond this many distinct tenants the
#: aggregate ``serve.e2e_ms`` histogram still records every query but no
#: new per-tenant series is created (unbounded label cardinality is how
#: scrapes die in production).
MAX_TENANT_SERIES = 64

#: How long past a job's deadline ``QueryFuture.result()`` keeps waiting
#: for the worker's own (more informative) resolution before synthesizing
#: the structured deadline result itself.
RESULT_GRACE_S = 0.25

#: Admitted-tenant sweep threshold: when a NEW tenant's first admitted
#: job would grow the tenant table past this, idle stateless tenants
#: (empty queue, nothing in flight, no registered views, default quota)
#: are reaped first. Without it, one admitted trivial query per unique
#: tenant name grows the round-robin scan and process memory forever —
#: the admitted-flood sibling of the refused-flood hardening in submit().
TENANT_REAP_THRESHOLD = 1024


class ServeError(RuntimeError):
    """Base class for serving-layer errors raised by ``value()``."""


class QueryRefused(ServeError):
    """The query never ran: admission rejected or shed it."""


class QueryDeadlineExceeded(ServeError):
    """The query's end-to-end deadline passed before a result landed."""


class QueryExecutionError(ServeError):
    """The query ran and raised; the original error string is attached."""


@dataclasses.dataclass
class QueryResult:
    """Structured outcome of one submitted query — ALWAYS returned (never
    raised) by ``QueryFuture.result()``; use :meth:`value_or_raise` for
    exception-style consumption."""

    status: str                      # ok | rejected | shed |
    #                                  deadline_exceeded | error
    tenant: str
    value: Any = None
    reason: str = ""                 # machine-readable refusal reason
    detail: str = ""                 # human-readable refusal detail
    error: str = ""                  # exception repr for status="error"
    where: str = ""                  # deadline site: queue | exec | wait
    tag: Optional[str] = None
    queue_ms: Optional[float] = None
    exec_ms: Optional[float] = None
    e2e_ms: Optional[float] = None
    stats: Optional[object] = None   # QueryStatsCollector (collect_stats)

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    def value_or_raise(self):
        if self.status == "ok":
            return self.value
        if self.status in ("rejected", "shed"):
            raise QueryRefused(
                f"query for tenant {self.tenant!r} {self.status} "
                f"({self.reason}): {self.detail}")
        if self.status == "deadline_exceeded":
            raise QueryDeadlineExceeded(
                f"query for tenant {self.tenant!r} exceeded its deadline "
                f"({self.where})")
        raise QueryExecutionError(
            f"query for tenant {self.tenant!r} failed: {self.error}")


class _Job:
    """One admitted unit of work. Resolution is idempotent — the first
    resolver (worker, or a deadline-synthesizing waiter) wins; later
    attempts are reported back so the loser can record ``late_result``."""

    __slots__ = ("work", "tenant", "tag", "deadline_s", "deadline_ts",
                 "t_submit", "est_bytes", "collect_stats", "attempts",
                 "trace", "_event", "_lock", "result")

    def __init__(self, work, tenant, tag, deadline_s, est_bytes,
                 collect_stats, trace=None):
        self.work = work
        self.tenant = tenant
        self.tag = tag
        # wire trace context (observability.TraceContext once adopted by
        # _execute; None with tracing off — the disabled-mode no-op)
        self.trace = trace
        self.deadline_s = deadline_s
        self.t_submit = time.perf_counter()
        self.deadline_ts = (None if deadline_s is None
                            else self.t_submit + float(deadline_s))
        self.est_bytes = est_bytes
        self.collect_stats = collect_stats
        self.attempts = 0      # executions so far (the requeue ladder)
        self._event = threading.Event()
        self._lock = threading.Lock()
        self.result: Optional[QueryResult] = None

    def resolve(self, result: QueryResult) -> bool:
        with self._lock:
            if self.result is not None:
                return False
            self.result = result
        self._event.set()
        return True


class QueryFuture:
    """Handle to one submitted query."""

    def __init__(self, job: _Job, server: "QueryServer"):
        self._job = job
        self._server = server

    def done(self) -> bool:
        return self._job._event.is_set()

    def result(self, timeout: Optional[float] = None) -> QueryResult:
        """Block until the query resolves and return its
        :class:`QueryResult`. Deadline queries NEVER hang: at most
        ``deadline + grace`` after submission this returns a structured
        ``deadline_exceeded`` result even if the execution is wedged
        (the worker's late result is then discarded). Without a
        deadline, ``timeout`` bounds the wait (``TimeoutError`` on
        expiry, matching ``concurrent.futures`` semantics)."""
        job = self._job
        while True:
            wait = timeout
            if job.deadline_ts is not None:
                bound = max(0.0, job.deadline_ts - time.perf_counter()) \
                    + RESULT_GRACE_S
                wait = bound if timeout is None else min(timeout, bound)
            if job._event.wait(wait):
                return job.result
            if (job.deadline_ts is not None
                    and time.perf_counter() >= job.deadline_ts):
                self._server._resolve_deadline(job, where="wait")
                return job.result
            if timeout is not None:
                raise TimeoutError(
                    f"query for tenant {job.tenant!r} not done within "
                    f"{timeout:.3g} s")
            # no deadline, no timeout: keep waiting

    def value(self, timeout: Optional[float] = None):
        """``result().value_or_raise()`` — exception-style consumption."""
        return self.result(timeout).value_or_raise()


class TenantContext:
    """What a tenant's job sees: tenant-scoped SQL/temp views over the
    shared engine. The catalog is PER TENANT (two tenants can both
    register a ``price`` view); UDF registry, jit caches, and the device
    are shared process state."""

    def __init__(self, server: "QueryServer", tenant: str):
        from ..sql.catalog import Catalog

        self._server = server
        self.tenant = tenant
        self.catalog = Catalog()

    def sql(self, query: str):
        """Run SQL against THIS tenant's temp views."""
        from ..sql.parser import execute as _sql_execute

        return _sql_execute(query, self.catalog)

    def register_view(self, name: str, frame) -> None:
        """Tenant-scoped ``createOrReplaceTempView`` (the Frame method of
        the same name registers in the process-default catalog and is
        NOT tenant-isolated — server jobs should register here)."""
        self.catalog.register(name, frame)

    create_or_replace_temp_view = register_view

    def table(self, name: str):
        return self.catalog.lookup(name)

    @property
    def session(self):
        s = self._server.session
        if s is None:
            raise RuntimeError("this QueryServer was built without a "
                               "TpuSession; ctx.session is unavailable")
        return s

    @property
    def read(self):
        from ..frame.csv import DataFrameReader

        return DataFrameReader(self.session)


class _TenantState:
    __slots__ = ("name", "quota", "queue", "in_flight", "context",
                 "exposed")

    def __init__(self, server, name: str, quota: TenantQuota):
        self.name = name
        self.quota = quota
        self.queue: collections.deque[_Job] = collections.deque()
        self.in_flight = 0
        self.context = TenantContext(server, name)
        # True once server.context(tenant) handed this context out: a
        # client may be holding it to register views later, so the reap
        # sweep must not orphan it (jobs see the context only transiently
        # during _execute and are not "exposed" in this sense).
        self.exposed = False


class QueryServer:
    """Multi-tenant query server over one engine (module docstring).

    Usable directly or as a context manager::

        with QueryServer(session, workers=8) as srv:
            fut = srv.submit("SELECT count(*) c FROM t", tenant="a")
            print(fut.result().value.to_pydict())

    or built from session conf via ``session.serve()`` (``spark.serve.*``
    keys — see :meth:`from_conf`).
    """

    def __init__(self, session=None, *, workers: int = 4,
                 max_queue: int = 64,
                 default_quota: Optional[TenantQuota] = None,
                 memory_limit_bytes: Optional[int] = None,
                 shared_plan_cache: bool = True,
                 default_deadline_s: Optional[float] = None,
                 breaker_threshold: int = 5,
                 breaker_cooldown: float = 5.0,
                 breaker: Optional[CircuitBreaker] = None,
                 metrics_port: Optional[int] = None,
                 metrics_host: str = "127.0.0.1",
                 slo_p99_ms: Optional[float] = None,
                 coalesce: Optional[bool] = None,
                 coalesce_max_delay_ms: Optional[float] = None,
                 coalesce_max_batch: Optional[int] = None,
                 coalesce_min_queue_depth: Optional[int] = None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        self.session = session
        self.workers = int(workers)
        self.default_quota = default_quota or TenantQuota()
        self.shared_plan_cache = bool(shared_plan_cache)
        self.default_deadline_s = default_deadline_s
        self.breaker = breaker if breaker is not None else CircuitBreaker(
            failure_threshold=int(breaker_threshold),
            cooldown=float(breaker_cooldown))
        self.admission = AdmissionController(
            max_queue=max_queue, memory_limit_bytes=memory_limit_bytes,
            breaker=self.breaker)
        # Live HTTP telemetry (serve/http.py): OFF unless a port is
        # given (spark.serve.metricsPort) — no socket, no thread, no
        # cost. 127.0.0.1 by default; port 0 = ephemeral (tests/soak).
        self.metrics_port = (None if metrics_port is None
                             else int(metrics_port))
        self.metrics_host = str(metrics_host)
        self.telemetry = None          # TelemetryServer once started
        # Per-tenant SLO burn-rate tracking (spark.serve.sloP99Ms): the
        # p99 target in ms; None = zero-cost off. Budget = 1% of
        # requests may exceed the target (a p99 promise); burn rate =
        # observed over-target fraction / 1%, published as the
        # serve.slo_burn[.<tenant>] gauges so a scrape shows budget
        # exhaustion BEFORE the breaker trips.
        self.slo_p99_ms = None if slo_p99_ms is None else float(slo_p99_ms)
        self._slo: dict[str, list] = {}    # tenant -> [total, over]
        self._slo_all = [0, 0]
        self._cond = threading.Condition()
        self._tenants: dict[str, _TenantState] = {}
        self._rr: list[str] = []       # round-robin tenant order
        self._rr_idx = 0
        self._queued_total = 0
        self._accepting = False
        self._draining = False         # stop()/begin_drain() in progress
        self._threads: list[threading.Thread] = []
        self.net = None                # NetServer once started (net.py)
        # Cross-request coalescing (serve/coalesce.py): explicit kwargs
        # win; None defers to the spark.serve.coalesce.* conf at start()
        # (the same deferred one-flag read as the net front end).
        self._coalesce_conf = (coalesce, coalesce_max_delay_ms,
                               coalesce_max_batch,
                               coalesce_min_queue_depth)
        self.coalescer = None          # Coalescer once started
        # tenants granted a per-tenant latency series (MAX_TENANT_SERIES
        # cap); own lock — _finish runs while stop() may hold self._cond
        self._series_lock = threading.Lock()
        self._series: set[str] = set()

    # -- conf ---------------------------------------------------------------
    @classmethod
    def from_conf(cls, session=None, conf=None, **overrides) -> "QueryServer":
        """Build from ``spark.serve.*`` conf keys (defaults in
        parentheses): ``workers`` (4), ``maxQueue`` (64), ``maxInFlight``
        (4) / ``maxQueuedPerTenant`` (16) for the default tenant quota,
        ``memoryLimitBytes`` (unset), ``defaultDeadline`` seconds
        (unset), ``sharedPlanCache`` (true), ``breakerThreshold`` (5) /
        ``breakerCooldown`` (5.0 s) for the shedding breaker. Keyword
        ``overrides`` win over conf."""
        conf = dict(conf if conf is not None
                    else (session.conf if session is not None else {}))

        def num(key, default, cast):
            v = conf.get(f"spark.serve.{key}")
            return default if v is None else cast(v)

        kw: dict = {
            "workers": num("workers", 4, int),
            "max_queue": num("maxQueue", 64, int),
            "default_quota": TenantQuota(
                max_in_flight=num("maxInFlight", 4, int),
                max_queued=num("maxQueuedPerTenant", 16, int)),
            "memory_limit_bytes": num("memoryLimitBytes", None, int),
            "default_deadline_s": num("defaultDeadline", None, float),
            "shared_plan_cache": str(
                conf.get("spark.serve.sharedPlanCache", "true")
            ).lower() not in CONF_FALSE,
            "breaker_threshold": num("breakerThreshold", 5, int),
            "breaker_cooldown": num("breakerCooldown", 5.0, float),
            "metrics_port": num("metricsPort", None, int),
            "metrics_host": str(conf.get("spark.serve.metricsHost",
                                         "127.0.0.1")),
            "slo_p99_ms": num("sloP99Ms", None, float),
            "coalesce": (
                None if "spark.serve.coalesce.enabled" not in conf
                else str(conf["spark.serve.coalesce.enabled"]).lower()
                not in CONF_FALSE),
            "coalesce_max_delay_ms": num("coalesce.maxDelayMs", None,
                                         float),
            "coalesce_max_batch": num("coalesce.maxBatch", None, int),
            "coalesce_min_queue_depth": num("coalesce.minQueueDepth",
                                            None, int),
        }
        kw.update(overrides)
        return cls(session, **kw)

    # -- lifecycle ----------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._accepting

    @property
    def draining(self) -> bool:
        """True from drain start (``begin_drain``/``stop``) until a
        stop completes — the window where /healthz answers 503 while
        in-flight work still finishes."""
        return self._draining

    def start(self) -> "QueryServer":
        """Spin up the worker pool (idempotent)."""
        with self._cond:
            if self._accepting:
                return self
            self._accepting = True
            self._draining = False
            # Stragglers a timed-out stop() left wedged in a device call
            # rejoin the pool the moment accepting flips back on (their
            # loop re-enters _next_job) — spawn only the difference, or
            # the pool runs oversized with threads no future stop() ever
            # joins and the workers gauge lies.
            self._threads = [t for t in self._threads if t.is_alive()]
            new = [
                threading.Thread(target=self._worker_loop, daemon=True,
                                 name=f"sparkdq4ml-serve-{i}")
                for i in range(len(self._threads), self.workers)]
            self._threads.extend(new)
            for t in new:
                t.start()
            _obs.METRICS.set_gauge("serve.workers", len(self._threads))
        if self.metrics_port is not None and self.telemetry is None:
            from .http import TelemetryServer

            self.telemetry = TelemetryServer(
                self, host=self.metrics_host,
                port=self.metrics_port).start()
        # Network front end (serve/net.py): exactly ONE flag read when
        # disabled — no import, no socket, no event loop, no thread
        # (the same zero-cost-off contract as telemetry above).
        if _cfg.serve_net_enabled and self.net is None:
            from .net import NetServer

            self.net = NetServer(self).start()
        # Cross-request coalescer (serve/coalesce.py): the same
        # zero-cost-off contract — disabled mode reads exactly one flag,
        # builds nothing, and every dispatch stays per-request.
        co_on = self._coalesce_conf[0]
        if co_on is None:
            co_on = _cfg.serve_coalesce_enabled
        if co_on and self.coalescer is None:
            from .coalesce import Coalescer

            _, delay, batch, depth = self._coalesce_conf
            self.coalescer = Coalescer(
                admission=self.admission,
                max_delay_ms=(_cfg.serve_coalesce_max_delay_ms
                              if delay is None else float(delay)),
                max_batch=(_cfg.serve_coalesce_max_batch
                           if batch is None else int(batch)),
                min_queue_depth=(_cfg.serve_coalesce_min_queue_depth
                                 if depth is None else int(depth)))
        return self

    def begin_drain(self) -> None:
        """Enter the drain window WITHOUT stopping: new submissions are
        refused (structured shutdown rejection), /healthz flips to 503
        so balancers stop routing here, but workers keep finishing
        queued + in-flight jobs and the sockets stay up to deliver
        their results. ``stop()`` completes the shutdown."""
        with self._cond:
            self._draining = True
            self._accepting = False
            self._cond.notify_all()

    def stop(self, drain: bool = True,
             timeout: Optional[float] = 30.0) -> None:
        """Stop accepting work and shut the pool down. ``drain=True``
        (default) lets queued + in-flight jobs finish; ``drain=False``
        resolves every queued job with a structured ``shutdown``
        rejection (in-flight jobs still finish — XLA dispatches are not
        cancellable). ``timeout`` bounds the join per worker; a wedged
        device call past it leaves that daemon worker behind rather than
        hanging the caller."""
        with self._cond:
            if not self._accepting and not self._threads:
                return
            self._accepting = False
            self._draining = True
        # The network front end drains FIRST, while the worker pool is
        # still alive: its in-flight connections hold futures whose jobs
        # the workers must still execute — stopping the pool first would
        # strand every connected client on a dead queue.
        net, self.net = self.net, None
        if net is not None:
            net.stop(drain=drain, timeout=timeout)
        with self._cond:
            if not drain:
                for state in self._tenants.values():
                    while state.queue:
                        job = state.queue.popleft()
                        self._queued_total -= 1
                        # refusals are observable, never silent (the
                        # admission contract) — shutdown rejections count
                        # like any other reject reason
                        counters.increment("serve.reject")
                        counters.increment("serve.reject.shutdown")
                        self._finish(job, QueryResult(
                            status="rejected", tenant=job.tenant,
                            reason="shutdown", tag=job.tag,
                            detail="server stopping (drain=False)"),
                            executed=False)
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout)
        self._threads = [t for t in self._threads if t.is_alive()]
        # a stopped server has no worker pool — scrapes must not keep
        # reporting the pre-stop count (stragglers past the join timeout
        # are the honest residue)
        _obs.METRICS.set_gauge("serve.workers", len(self._threads))
        self._update_gauges()
        # telemetry goes down LAST: the final gauge values above are
        # scrape-able until the socket closes
        telemetry, self.telemetry = self.telemetry, None
        if telemetry is not None:
            telemetry.stop()
        self._draining = False         # drain window over: fully stopped

    def __enter__(self) -> "QueryServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # -- tenant surface -----------------------------------------------------
    def context(self, tenant: str = "default") -> TenantContext:
        """The tenant's :class:`TenantContext` (created on first use) —
        register views here before submitting SQL-string jobs."""
        with self._cond:
            state = self._state(tenant)
            state.exposed = True
            return state.context

    def set_quota(self, tenant: str, quota: TenantQuota) -> None:
        with self._cond:
            self._state(tenant).quota = quota

    def _state(self, tenant: str) -> _TenantState:
        # callers hold self._cond
        state = self._tenants.get(tenant)
        if state is None:
            if len(self._tenants) >= TENANT_REAP_THRESHOLD:
                self._reap_idle_tenants_locked()
            state = _TenantState(self, tenant, self.default_quota)
            self._tenants[tenant] = state
            self._rr.append(tenant)
            _obs.METRICS.set_gauge("serve.tenants", len(self._tenants))
        return state

    def _reap_idle_tenants_locked(self) -> None:
        """Drop tenants with no live work and no durable state (no
        registered views, default quota, context never handed out via
        :meth:`context`): their state is pure bookkeeping and is rebuilt
        for free if the name ever returns. Tenants holding temp views, an
        operator-set quota, or an exposed context are NEVER reaped —
        that's real state a client may come back for.

        The breaker entry is part of the tenant's bookkeeping and is
        reaped with it: ``CircuitBreaker._state`` grows one key per
        tenant that ever failed, so leaving it behind would re-open the
        unbounded-memory hole this sweep closes (a returning name starts
        with a clean failure count, same as its rebuilt state)."""
        dead = [name for name, s in self._tenants.items()
                if not s.queue and s.in_flight == 0
                and not s.exposed
                and s.quota is self.default_quota
                and not s.context.catalog.list_views()]
        if not dead:
            return
        for name in dead:
            del self._tenants[name]
            self.breaker.reset(self.admission.breaker_key(name))
        self._rr = [n for n in self._rr if n in self._tenants]
        self._rr_idx = 0
        counters.increment("serve.tenants_reaped", len(dead))
        _obs.METRICS.set_gauge("serve.tenants", len(self._tenants))

    # -- submission ---------------------------------------------------------
    def submit(self, work, tenant: str = "default", *,
               deadline_s: Optional[float] = None,
               est_bytes: Optional[int] = None,
               collect_stats: bool = False,
               tag: Optional[str] = None,
               trace=None) -> QueryFuture:
        """Submit one query for ``tenant``.

        ``work`` is either a SQL string (run against the tenant's
        catalog) or a callable taking the :class:`TenantContext`.
        Admission happens synchronously — a refused query resolves
        immediately with its structured rejection. ``est_bytes``
        declares the job's estimated device footprint for the memory
        gate; ``deadline_s`` (default ``default_deadline_s``) bounds the
        query end-to-end; ``collect_stats`` attaches a per-query
        ``QueryStatsCollector`` to the result; ``trace`` carries the
        wire trace context (a ``TraceContext`` or raw ``traceparent``
        string) the executing span tree adopts as its root."""
        if isinstance(work, str):
            sql_text = work
            work = lambda ctx: ctx.sql(sql_text)   # noqa: E731
        elif not callable(work):
            raise TypeError(f"work must be a SQL string or a callable "
                            f"taking a TenantContext, got {type(work)}")
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        job = _Job(work, tenant, tag, deadline_s, est_bytes, collect_stats,
                   trace=trace)
        # Take the memory-gate census BEFORE the scheduler lock: it walks
        # every live jax array, and holding self._cond through that scan
        # would stall every worker and submitter. Advisory gate — the
        # slightly stale figure is within its documented precision.
        live = None
        if (self.admission.memory_limit_bytes is not None
                and est_bytes is not None and est_bytes > 0):
            from ..utils import meminfo

            live = meminfo.live_bytes()
        # serve_admit chaos hooks (one None check without a plan), run
        # BEFORE the scheduler lock — a firing hook logs and annotates,
        # and log I/O under self._cond would serialize every submitter
        # and worker (the same lock-hygiene rule that keeps the
        # live-array census above outside it). A due breaker_trip forces
        # the tenant's breaker open — THIS submission sheds through the
        # normal gate and recovery follows the normal half-open path; a
        # due oom injects an allocator-census-OOM memory rejection
        # (works without a configured memory limit, so the gate's
        # refusal path is soak-testable everywhere).
        injected = None
        if _faults.active() is not None:
            if _faults.fired("serve_admit", "breaker_trip"):
                self.breaker.trip(self.admission.breaker_key(tenant))
                # a breaker transition is a flight-recorder trigger
                # whether the trip was organic or injected
                if _obs.TRACER.enabled:
                    _incidents.RECORDER.record(
                        "breaker_trip",
                        detail=f"injected trip, tenant {tenant!r}",
                        extra={"breaker": self.breaker.snapshot()})
            if _faults.fired("serve_admit", "oom"):
                injected = AdmissionController._reject(
                    "memory", "injected allocator-census OOM "
                    "(serve_admit chaos)")
        with self._cond:
            if not self._accepting:
                raise RuntimeError("QueryServer is not running "
                                   "(start() it, or session.serve())")
            # Admission runs against the EXISTING tenant state (or the
            # default quota for a first-time name): tenant state is only
            # allocated for ADMITTED work, so a flood of refused
            # submissions under unique tenant names cannot grow
            # _tenants/_rr (and the scheduler scan) without bound.
            existing = self._tenants.get(tenant)
            verdict = injected if injected is not None \
                else self.admission.admit(
                    tenant,
                    existing.quota if existing is not None
                    else self.default_quota,
                    self._queued_total,
                    len(existing.queue) if existing is not None else 0,
                    est_bytes=est_bytes, live_bytes=live)
            if verdict is not None:
                if _obs.TRACER.enabled and trace is not None:
                    # a refused wire request still gets a (one-span)
                    # tree: its echoed trace_id must resolve via
                    # /trace/<id> like any admitted request's — opened
                    # BEFORE resolve() so the wire layer's completion
                    # hook cannot race an unregistered context
                    ctx = _obs.TraceContext.adopt(trace)
                    job.trace = ctx
                    with _obs.request_span("serve.query", ctx,
                                           tenant=tenant,
                                           rejected=verdict.status):
                        pass
                    _obs.TAIL.finish_request(
                        ctx, status=verdict.status,
                        reason=verdict.reason, e2e_ms=None,
                        breaker_opened=False, slo_ms=self.slo_p99_ms)
                job.resolve(QueryResult(
                    status=verdict.status, tenant=tenant, tag=tag,
                    reason=verdict.reason, detail=verdict.detail))
                return QueryFuture(job, self)
            state = self._state(tenant)
            counters.increment("serve.admit")
            state.queue.append(job)
            self._queued_total += 1
            self._update_gauges_locked()
            self._cond.notify()
        return QueryFuture(job, self)

    # -- scheduler ----------------------------------------------------------
    def _next_job(self):
        """Round-robin over tenants with queued work AND a free in-flight
        slot; None when the server is stopping and nothing is left."""
        with self._cond:
            while True:
                n = len(self._rr)
                for off in range(n):
                    name = self._rr[(self._rr_idx + off) % n]
                    state = self._tenants[name]
                    if (state.queue
                            and state.in_flight < state.quota.max_in_flight):
                        self._rr_idx = (self._rr_idx + off + 1) % n
                        job = state.queue.popleft()
                        self._queued_total -= 1
                        state.in_flight += 1
                        self._update_gauges_locked()
                        return job, state
                if not self._accepting and self._queued_total == 0:
                    return None, None
                self._cond.wait()

    def _worker_loop(self) -> None:
        while True:
            job, state = self._next_job()
            if job is None:
                return
            try:
                self._execute(job, state)
            finally:
                with self._cond:
                    state.in_flight -= 1
                    self._update_gauges_locked()
                    self._cond.notify()

    # -- execution ----------------------------------------------------------
    def _execute(self, job: _Job, state: _TenantState) -> None:
        t_start = time.perf_counter()
        queue_ms = (t_start - job.t_submit) * 1e3
        # ONE flag read adopts (or locally mints) the request's wire
        # trace context; disabled mode allocates nothing and the span
        # below is the shared no-op.
        trace = (_obs.TraceContext.adopt(job.trace)
                 if _obs.TRACER.enabled else None)
        job.trace = trace
        if job.deadline_ts is not None and t_start >= job.deadline_ts:
            # queue-expired jobs still register a (minimal) request tree
            # so the client-held trace id resolves server-side
            with _obs.request_span("serve.query", trace,
                                   tenant=job.tenant, tag=job.tag,
                                   expired="queue"):
                pass
            self._finish(job, QueryResult(
                status="deadline_exceeded", tenant=job.tenant, tag=job.tag,
                where="queue", queue_ms=queue_ms,
                e2e_ms=queue_ms), executed=False, queue_ms=queue_ms,
                e2e_ms=queue_ms)
            return
        ns_cm = (contextlib.nullcontext() if self.shared_plan_cache
                 else _plan_namespace(job.tenant))
        # Adaptive coalescing arm (ONE None check when the coalescer is
        # off): the queue depth REMAINING at pop time is the load
        # signal — below minQueueDepth, or without deadline headroom for
        # a hold window, the scope is the shared nullcontext and every
        # dispatch below is byte-for-byte the per-request path.
        co = self.coalescer
        co_cm = (contextlib.nullcontext() if co is None
                 else co.scope(job, self._queued_total))
        stats = None
        status, value, error = "ok", None, ""
        job.attempts += 1
        try:
            with ns_cm, co_cm, _shard_guard(), _obs.request_span(
                    "serve.query", trace,
                    tenant=job.tenant, tag=job.tag,
                    attempt=job.attempts):
                if trace is not None:
                    # admission and queueing happened before this span
                    # opened (caller thread / queue wait) — record them
                    # as back-dated children of the request root
                    _obs.emit_span("serve.admit", cat="serve",
                                   ctx=trace, tenant=job.tenant)
                    _obs.emit_span("serve.queue", cat="serve",
                                   dur_ms=queue_ms, ctx=trace)
                # serve_exec chaos hook (one None check without a plan):
                # a due device_error raises the same XlaRuntimeError
                # class a real worker device fault would
                _faults.inject("serve_exec")
                if job.collect_stats:
                    with _obs.query_stats() as stats:
                        value = _materialize(job.work(state.context))
                else:
                    value = _materialize(job.work(state.context))
        except Exception as e:    # noqa: BLE001 - a tenant's bad query
            if self._maybe_requeue(job, state, e):
                return             # re-enters the tenant queue; no finish
            status, error = "error", f"{type(e).__name__}: {e}"
        t_end = time.perf_counter()
        exec_ms = (t_end - t_start) * 1e3
        e2e_ms = (t_end - job.t_submit) * 1e3
        if (job.deadline_ts is not None and t_end >= job.deadline_ts
                and status == "ok"):
            # honest semantics: a deadline is a promise about END-TO-END
            # latency; a value that arrives late is discarded, not handed
            # back as if the SLO held
            status, value = "deadline_exceeded", None
        result = QueryResult(
            status=status, tenant=job.tenant, tag=job.tag, value=value,
            error=error, where="exec" if status == "deadline_exceeded"
            else "", queue_ms=queue_ms, exec_ms=exec_ms, e2e_ms=e2e_ms,
            stats=stats)
        self._finish(job, result, executed=True, queue_ms=queue_ms,
                     exec_ms=exec_ms, e2e_ms=e2e_ms)

    def _maybe_requeue(self, job: _Job, state: _TenantState,
                       err: BaseException) -> bool:
        """Deadline-aware requeue — the serve rung of the degradation
        ladder (ISSUE 11). A worker exception of the RETRYABLE class
        (``XlaRuntimeError`` / recovery ``DeadlineExceeded`` — never a
        tenant's bad SQL, which is deterministic and must fail fast)
        re-enters the tenant's queue while the per-tenant
        :class:`~..utils.recovery.RetryPolicy` grants attempts AND the
        job's deadline has headroom for the policy backoff, which is
        slept in this worker before the requeue (see below). Every
        requeued attempt counts against the tenant's breaker, so a
        persistently faulting tenant still trips to shed. Returns True
        when the job was requeued (the caller must not resolve it)."""
        import jax

        from ..utils import recovery as _rec

        if not isinstance(err, (jax.errors.JaxRuntimeError,
                                _rec.DeadlineExceeded)):
            return False
        cause = f"{type(err).__name__}: {err}"
        policy = self._retry_policy(job.tenant)
        if job.attempts >= policy.max_attempts:
            _rec.RECOVERY_LOG.record(
                "serve_exec", "exhausted", attempt=job.attempts,
                rung="requeue", cause=cause)
            if _obs.TRACER.enabled:
                # fault-ladder engagement exhausted its rung — capture
                # the evidence while the recovery log still has it
                _incidents.RECORDER.record(
                    "fault_ladder",
                    trace=job.trace if isinstance(
                        job.trace, _obs.TraceContext) else None,
                    detail=f"serve_exec requeue exhausted after "
                           f"{job.attempts} attempts: {cause}")
            return False
        wait = policy.backoff(job.attempts, "serve_exec")
        if job.deadline_ts is not None \
                and time.perf_counter() + wait >= job.deadline_ts:
            _rec.RECOVERY_LOG.record(
                "serve_exec", "deadline", attempt=job.attempts,
                rung="requeue", cause=cause,
                detail="no deadline headroom; failing instead of requeue")
            return False
        if wait > 0.0:
            # The backoff is served HERE, in the failing worker, before
            # the job re-enters the queue: with an idle worker slot an
            # appendleft'ed job would otherwise re-execute within
            # microseconds and exhaust every attempt while a transient
            # fault is still present. The job is not yet queued, so no
            # other worker can grab it early; the cost is one worker
            # slot for the (policy-bounded, deterministic-jitter) wait —
            # the same in-place sleep resilient_call makes.
            policy.sleep(wait)
        with self._cond:
            if not self._accepting:
                return False       # stopping: resolve as the error it is
            state.queue.appendleft(job)
            self._queued_total += 1
            self._update_gauges_locked()
            self._cond.notify()
        # Count the failed attempt against the tenant's breaker ONLY for
        # attempts that actually requeue: a non-requeued failure resolves
        # as an error result and _finish records it there — counting in
        # both places charged the final attempt twice and tripped the
        # breaker ~2x faster than its configured threshold.
        self.breaker.record_failure(self.admission.breaker_key(job.tenant))
        counters.increment("serve.requeue")
        _rec.RECOVERY_LOG.record(
            "serve_exec", "retry", attempt=job.attempts, rung="requeue",
            cause=cause, backoff_s=wait)
        return True

    def _retry_policy(self, tenant: str):
        """Per-tenant retry policy for the requeue ladder: global
        ``spark.recovery.*`` keys, overlaid by ``spark.recovery.
        serve_exec.*``, overlaid by ``spark.recovery.serve_exec.
        <tenant>.*`` — one misbehaving tenant can be tuned (or starved of
        retries) without touching the others."""
        from ..utils.recovery import RetryPolicy

        conf = self.session.conf if self.session is not None else {}
        kw = RetryPolicy._conf_kwargs(conf, "spark.recovery.")
        kw.update(RetryPolicy._conf_kwargs(
            conf, "spark.recovery.serve_exec."))
        kw.update(RetryPolicy._conf_kwargs(
            conf, f"spark.recovery.serve_exec.{tenant}."))
        return RetryPolicy(**kw)

    def _finish(self, job: _Job, result: QueryResult, *, executed: bool,
                queue_ms: Optional[float] = None,
                exec_ms: Optional[float] = None,
                e2e_ms: Optional[float] = None) -> None:
        won = job.resolve(result)
        breaker_opened = False
        if won:
            key = self.admission.breaker_key(job.tenant)
            if result.status == "ok":
                counters.increment("serve.complete")
                self.breaker.record_success(key)
            elif result.status == "error":
                counters.increment("serve.error")
                breaker_opened = self.breaker.record_failure(key)
            elif result.status == "deadline_exceeded":
                counters.increment("serve.deadline_exceeded")
                breaker_opened = self.breaker.record_failure(key)
            # rejected/shed counters were recorded at admission (or at
            # the drain=False shutdown site)
        elif executed:
            # a real execution value landed after someone else (the
            # deadline waiter) resolved the job — discarded, counted.
            # Lost races that never ran work (a queued-past-deadline job
            # the worker pops after the waiter gave up) are NOT late
            # results: nothing was computed, nothing was discarded.
            counters.increment("serve.late_result")
        if queue_ms is not None:
            _obs.METRICS.observe("serve.queue_ms", queue_ms)
        if exec_ms is not None:
            _obs.METRICS.observe("serve.exec_ms", exec_ms)
        # e2e is the CLIENT-experienced latency: exactly one observation
        # per job, made by the resolution the client actually received.
        # A deadline overrun resolved from the queue pop or the waiter
        # must land in the histogram — under queue saturation those are
        # the worst latencies, and skipping them (while recording the
        # exec-path ones) made a scrape-derived p99 read healthy in the
        # exact regime deadlines exist for. A losing worker's later
        # value is resource accounting (queue/exec above), not latency.
        if not won:
            e2e_ms = None
        if e2e_ms is not None:
            _obs.METRICS.observe("serve.e2e_ms", e2e_ms)
            with self._series_lock:
                granted = (job.tenant in self._series
                           or len(self._series) < MAX_TENANT_SERIES)
                if granted:
                    self._series.add(job.tenant)
            if granted:
                _obs.METRICS.observe(f"serve.e2e_ms.{job.tenant}", e2e_ms)
            if self.slo_p99_ms is not None:
                self._record_slo(job.tenant, e2e_ms, granted)
        if _obs.TRACER.enabled \
                and isinstance(job.trace, _obs.TraceContext):
            if won:
                # hand the completion verdict to the tail sampler; the
                # tree finalizes here unless the wire layer deferred
                # (stream spans still to come — it completes after the
                # page write-out)
                _obs.TAIL.finish_request(
                    job.trace, status=result.status,
                    reason=result.reason, e2e_ms=e2e_ms,
                    breaker_opened=breaker_opened,
                    slo_ms=self.slo_p99_ms)
                if breaker_opened:
                    _incidents.RECORDER.record(
                        "breaker_trip", trace=job.trace,
                        detail=f"tenant {job.tenant!r}, "
                               f"status {result.status}",
                        extra={"breaker": self.breaker.snapshot()})
            else:
                # lost race: the winning resolution carried the client-
                # visible verdict, but it may have landed BEFORE this
                # execution opened the tree — record this resolution as
                # the verdict only if none is stored yet, then finalize
                # so a late execution cannot leak a pending tree
                # (idempotent once the bucket is gone)
                _obs.TAIL.finish_request(
                    job.trace, status=result.status,
                    reason=result.reason, e2e_ms=None,
                    breaker_opened=False, slo_ms=self.slo_p99_ms)
                _obs.TAIL.complete(job.trace)

    def _record_slo(self, tenant: str, e2e_ms: float,
                    granted: bool) -> None:
        """Incremental SLO burn-rate update, fed by the SAME
        client-experienced latencies the ``serve.e2e_ms`` histograms
        record (exactly one observation per job). SLO semantics: p99 ≤
        ``slo_p99_ms``, i.e. an error budget of 1% of requests over
        target; burn rate = over-target fraction / 1% — 1.0 burns the
        budget exactly, >1 exhausts it early, and a scrape of
        ``serve.slo_burn.<tenant>`` shows that long before the tenant's
        failure-driven breaker trips. ``granted`` reuses the per-tenant
        series cap so gauge cardinality is bounded with the histograms."""
        over = e2e_ms > self.slo_p99_ms
        with self._series_lock:
            self._slo_all[0] += 1
            self._slo_all[1] += over
            burn_all = (self._slo_all[1] / self._slo_all[0]) / 0.01
            cell = None
            if granted:
                cell = self._slo.setdefault(tenant, [0, 0])
                cell[0] += 1
                cell[1] += over
                burn = (cell[1] / cell[0]) / 0.01
        _obs.METRICS.set_gauge("serve.slo_burn", round(burn_all, 4))
        if cell is not None:
            _obs.METRICS.set_gauge(f"serve.slo_burn.{tenant}",
                                   round(burn, 4))
        # flight-recorder trigger: sustained burn over the configured
        # threshold (min 100 samples so a cold start can't fire it);
        # the recorder's per-trigger cooldown bounds repeat captures
        if _obs.TRACER.enabled and self._slo_all[0] >= 100 \
                and burn_all >= _incidents.RECORDER.slo_burn_threshold:
            _incidents.RECORDER.record(
                "slo_burn",
                detail=f"burn {burn_all:.2f} over "
                       f"{self._slo_all[0]} samples",
                extra={"slo_p99_ms": self.slo_p99_ms})

    def _resolve_deadline(self, job: _Job, where: str) -> None:
        """Waiter-side deadline resolution (``QueryFuture.result``):
        synthesize the structured result; idempotent vs the worker."""
        now = time.perf_counter()
        e2e_ms = (now - job.t_submit) * 1e3
        self._finish(job, QueryResult(
            status="deadline_exceeded", tenant=job.tenant, tag=job.tag,
            where=where, e2e_ms=e2e_ms),
            executed=False, e2e_ms=e2e_ms)

    # -- introspection ------------------------------------------------------
    def _update_gauges_locked(self) -> None:
        _obs.METRICS.set_gauge("serve.queue_depth", self._queued_total)
        _obs.METRICS.set_gauge(
            "serve.in_flight",
            sum(s.in_flight for s in self._tenants.values()))

    def _update_gauges(self) -> None:
        with self._cond:
            self._update_gauges_locked()

    def stats(self) -> dict:
        """One structured snapshot: queue/in-flight state per tenant, the
        shedding breaker, and every ``serve.*`` counter."""
        with self._cond:
            tenants = {
                name: {"queued": len(s.queue), "in_flight": s.in_flight,
                       "max_in_flight": s.quota.max_in_flight,
                       "max_queued": s.quota.max_queued}
                for name, s in self._tenants.items()}
            queued_total = self._queued_total
        return {
            "running": self.running,
            "draining": self.draining,
            "workers": self.workers,
            "queue_depth": queued_total,
            "shared_plan_cache": self.shared_plan_cache,
            "tenants": tenants,
            "breaker": self.breaker.snapshot(),
            "counters": counters.snapshot("serve."),
            "coalesce": (None if self.coalescer is None
                         else self.coalescer.stats()),
        }

    def cache_report(self) -> dict:
        """The unified jit-cache introspection view (PR 5) — the shared
        plan/jit cache this server multiplexes tenants over."""
        return _obs.cache_report()


def _plan_namespace(tenant: str):
    from ..ops.compiler import plan_namespace

    return plan_namespace(tenant)


def _shard_guard():
    """Serialize served-query EXECUTION while row-sharding is active
    (``spark.shard.enabled`` on a multi-device mesh): a sharded query's
    eager host-boundary reductions (``count``'s mask sum, ``limit``'s
    cumsum) dispatch multi-device programs outside any jit factory's
    ``serialize_collectives`` wrapper, and overlapping multi-device
    executions are the XLA:CPU rendezvous-deadlock class PR 6 closed.
    With sharding active every query already spans the whole mesh, so
    whole-query serialization is the correct dispatch semantics (the
    mesh is the unit of concurrency), not a throughput concession. The
    plan caches stay namespace-partitioned exactly as before — the
    shard layout tag composes with the tenant namespace prefix inside
    the plan key. One flag/None check when sharding is off."""
    from ..parallel.mesh import collective_guard
    from ..parallel.shard import active_mesh

    return collective_guard(active_mesh())


def _materialize(value):
    """Flush any lazy Frame state in a job's return value INSIDE the
    serve scope. A callable job may return a Frame with pending fused-
    pipeline steps; left lazy, the client's first read would flush on the
    client thread — OUTSIDE the tenant's ``plan_namespace`` (silently
    un-partitioning the isolated-cache mode), the ``serve.query`` span,
    and the exec/deadline accounting. Walks one container level (dict /
    list / tuple), matching the shapes jobs actually return."""
    if hasattr(value, "_flush") and getattr(value, "_pending", None):
        value._flush()
    elif isinstance(value, dict):
        for v in value.values():
            if hasattr(v, "_flush") and getattr(v, "_pending", None):
                v._flush()
    elif isinstance(value, (list, tuple)):
        for v in value:
            if hasattr(v, "_flush") and getattr(v, "_pending", None):
                v._flush()
    return value
