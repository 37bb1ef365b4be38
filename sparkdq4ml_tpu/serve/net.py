"""Network serving front end — the socket protocol over the QueryServer.

ROADMAP item 2 asks for a real network protocol in front of the
thread-pool serving layer (PR 6); this module is its robustness half:
an asyncio front end layered over the existing
:class:`~.server.QueryServer` admission/tenant/breaker machinery, built
to the same fault-site + degradation-ladder discipline as every other
subsystem (PR 10).

Two framings over one listening socket, sniffed per connection from the
first four bytes:

* **HTTP/1.1** (``POST /query``) — the interoperable framing. The
  request body is a JSON document (``sql`` or a registered ``job``
  name, ``tenant``, ``deadline_ms``, ``idem``, ``tag``,
  ``est_bytes`` — the declared device footprint the admission memory
  gate and the coalescer's batch sizing price); the ``X-DQ-Tenant`` /
  ``X-DQ-Deadline-Ms`` / ``X-DQ-Idempotency-Key`` / ``X-DQ-Tag`` /
  ``X-DQ-Est-Bytes`` headers override. Responses stream as
  ``Transfer-Encoding: chunked`` ndjson — one JSON line per result
  page, then one terminal line with the structured status — so a large
  SELECT never materializes per client. ``GET /healthz`` answers the
  drain state (503 while draining/stopped — balancer semantics).
* **Length-prefixed frames** (magic ``DQW1``) — the low-overhead
  framing: 4-byte magic once, then per message a 4-byte big-endian
  length + JSON payload. Requests use the same document; responses are
  a sequence of page frames then one ``{"end": true, "status": ...}``
  frame. Connections are keep-alive: the client sends the next request
  after the previous end frame.

**Wire deadline propagation** is RELATIVE, never absolute: the client
sends its remaining budget in milliseconds (``X-DQ-Deadline-Ms`` /
``deadline_ms``) and the server re-anchors it on its own monotonic
clock at receipt — two hosts whose wall clocks disagree by minutes
still agree on the budget (clock-skew tolerance by construction). The
budget becomes the job's server-side ``deadline_s``: a queued-past-
deadline job never executes, and the waiter-synthesized
``deadline_exceeded`` result reaches the client as a structured frame,
never a hang or reset.

**Fault sites** (``utils.faults.FAULT_SITES``): ``net_accept``
(``conn_reset``), ``net_read`` (``conn_reset``/``stall``/
``slow_client``), ``net_write`` (``conn_reset``/``partial_write``/
``stall``). Ladders: a reset aborts the connection with a
``net.conn_reset`` count + recovery event (the resilient client
retries, idempotency-key dedup keeping the query exactly-once); a
stall/slow client is the read/write-timeout ladder — the connection is
cut after ``connTimeoutMs`` with a structured ``conn_timeout`` error
where the protocol still permits one (``net.conn_timeout`` + recovery
event); a partial write truncates the response mid-stream
(``net.partial_write``), which the client detects as a torn frame and
retries. A peer that vanishes while its query is still pending is
abandoned through the server's own accounting
(:meth:`~.server.QueryServer._finish` with a structured
``client_gone`` error), so the worker's late value is discarded via
the existing ``serve.late_result`` path — counted, never silent.

Slow-loris protection: the whole request read shares ONE
``connTimeoutMs`` bound (a byte-trickling peer cannot extend it),
reader buffers are bounded by ``maxFrameBytes``, and the writer's
high-water mark forces backpressure so a slow-draining client hits the
write timeout instead of growing the server's buffers.

Security: binds ``127.0.0.1`` by default (``spark.serve.net.host`` to
widen) — the endpoint is unauthenticated, same posture as the
telemetry server; fronting with a real proxy is the operator's job.
OFF by default: with ``spark.serve.net.enabled=false`` the
``QueryServer`` reads exactly one flag and starts nothing — no socket,
no event loop, no thread.
"""

from __future__ import annotations

import asyncio
import collections
import json
import logging
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

from ..config import config as _cfg
from ..utils import faults as _faults
from ..utils import incidents as _incidents
from ..utils import observability as _obs
from ..utils.profiling import counters
from ..utils.recovery import RECOVERY_LOG
from .server import QueryFuture, QueryResult

logger = logging.getLogger("sparkdq4ml_tpu.serve.net")

#: Frame-protocol magic: the client's first four bytes. Anything else is
#: parsed as HTTP (requests start with the method token).
MAGIC = b"DQW1"

#: Bound on idempotency-key dedup entries (LRU): a retried query re-
#: attaches to its original job instead of re-executing; past the bound
#: the oldest key evicts and a very late retry re-executes (documented
#: best-effort window, bounded memory).
IDEM_CACHE = 512

#: Hard bound on waiting for one query's result on behalf of a
#: connection: queries without a wire deadline cannot wedge a waiter
#: thread (and its connection) forever — past it the client gets a
#: structured error, same zero-hangs contract as ``QueryFuture``.
RESULT_BOUND_S = 600.0

#: Writer high-water mark: past this many unflushed bytes the page loop
#: blocks in ``drain()`` (backpressure), so a slow-draining client runs
#: into the write timeout instead of ballooning server-side buffers.
WRITE_HIGH_WATER = 1 << 16

#: An injected ``stall``/``slow_client`` sleeps this long for real (a
#: token, deterministic pause) and then takes the SAME timeout ladder a
#: full ``connTimeoutMs`` expiry would — the ladder is exercised without
#: the soak paying the full wall-clock timeout per injection.
STALL_EMULATION_S = 0.05

_HTTP_REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
                 408: "Request Timeout", 413: "Payload Too Large",
                 429: "Too Many Requests", 500: "Internal Server Error",
                 503: "Service Unavailable", 504: "Gateway Timeout"}

#: Structured status → HTTP response code (pre-stream errors; once the
#: chunked stream started the terminal ndjson line carries the status).
_STATUS_HTTP = {"ok": 200, "rejected": 429, "shed": 503,
                "deadline_exceeded": 504, "error": 500}


class _Abort(Exception):
    """Tear the connection down now (reset semantics) — raised by the
    fault ladders and the disconnect paths; the handler's finally block
    owns the cleanup."""


def _json_default(v):
    tolist = getattr(v, "tolist", None)
    if callable(tolist):
        return tolist()
    return str(v)


class _Conn:
    """One accepted connection: the stream pair plus a pushback buffer
    (the protocol sniff and the disconnect watch both read ahead)."""

    __slots__ = ("reader", "writer", "buf", "peer", "streaming", "proto")

    def __init__(self, reader, writer):
        self.reader = reader
        self.writer = writer
        self.buf = b""
        try:
            self.peer = writer.get_extra_info("peername")
        except Exception:
            self.peer = None
        self.streaming = False     # a chunked/page stream has started
        self.proto = None          # "frame" | "http" once sniffed

    async def read_exactly(self, n: int) -> bytes:
        while len(self.buf) < n:
            chunk = await self.reader.read(n - len(self.buf))
            if not chunk:
                raise asyncio.IncompleteReadError(self.buf, n)
            self.buf += chunk
        out, self.buf = self.buf[:n], self.buf[n:]
        return out

    async def read_line(self, limit: int) -> bytes:
        while b"\n" not in self.buf:
            if len(self.buf) > limit:
                raise _FrameOverflow(f"header line over {limit} bytes")
            chunk = await self.reader.read(2048)
            if not chunk:
                raise asyncio.IncompleteReadError(self.buf, limit)
            self.buf += chunk
        line, _, self.buf = self.buf.partition(b"\n")
        return line + b"\n"

    def pushback(self, data: bytes) -> None:
        self.buf = data + self.buf


class _FrameOverflow(Exception):
    """A request exceeded ``maxFrameBytes`` — refused with a structured
    413, bounding per-connection buffers."""


class NetServer:
    """The asyncio socket front end over one :class:`QueryServer`.

    Runs its own event loop on a dedicated thread (the engine is
    threaded, not async); connection handlers bridge to the blocking
    ``QueryFuture`` API through a bounded waiter thread pool. Normally
    started by ``QueryServer.start()`` when ``spark.serve.net.enabled``
    is set, but directly constructible for tests and the chaos soak
    (every constructor default reads the session-scoped config)."""

    def __init__(self, server, *, host: Optional[str] = None,
                 port: Optional[int] = None,
                 backlog: Optional[int] = None,
                 conn_timeout_s: Optional[float] = None,
                 max_frame_bytes: Optional[int] = None,
                 page_rows: Optional[int] = None,
                 waiters: int = 64):
        self.server = server
        self.host = _cfg.serve_net_host if host is None else str(host)
        self._requested_port = (_cfg.serve_net_port if port is None
                                else int(port))
        self.backlog = (_cfg.serve_net_backlog if backlog is None
                        else int(backlog))
        self.conn_timeout_s = (
            _cfg.serve_net_conn_timeout_ms / 1e3
            if conn_timeout_s is None else float(conn_timeout_s))
        self.max_frame_bytes = (
            _cfg.serve_net_max_frame_bytes
            if max_frame_bytes is None else int(max_frame_bytes))
        self.page_rows = (_cfg.serve_net_stream_page_rows
                          if page_rows is None else int(page_rows))
        self._waiters = int(waiters)
        self._jobs: dict[str, Callable] = {}
        self._idem: collections.OrderedDict[str, QueryFuture] = \
            collections.OrderedDict()
        self._idem_lock = threading.Lock()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self._listener = None
        self._conns: set = set()
        self._draining = False
        self._port: Optional[int] = None
        self._started = threading.Event()

    # -- lifecycle ----------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._loop is not None and not self._draining

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def port(self) -> Optional[int]:
        """The BOUND port (resolves a requested port of 0)."""
        return self._port

    def register_job(self, name: str, work: Callable) -> None:
        """Expose ``work`` (a callable taking a ``TenantContext``) as a
        named server-side job wire clients can invoke by name — the
        stored-procedure shape for work that is not a SQL string (the
        soak's headline DQ+Lasso flow)."""
        self._jobs[name] = work

    def start(self) -> "NetServer":
        if self._loop is not None:
            return self
        self._draining = False
        self._pool = ThreadPoolExecutor(
            max_workers=self._waiters,
            thread_name_prefix="sparkdq4ml-net-wait")
        self._thread = threading.Thread(
            target=self._run_loop, daemon=True, name="sparkdq4ml-net")
        self._thread.start()
        if not self._started.wait(timeout=10.0) or self._port is None:
            raise RuntimeError("NetServer failed to bind "
                               f"{self.host}:{self._requested_port}")
        logger.info("network serving on %s:%d (HTTP/1.1 + DQW1 frames)",
                    self.host, self._port)
        return self

    def _run_loop(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop

        async def _bind():
            self._listener = await asyncio.start_server(
                self._accept, host=self.host, port=self._requested_port,
                backlog=self.backlog, limit=self.max_frame_bytes)
            self._port = self._listener.sockets[0].getsockname()[1]
            self._started.set()

        try:
            loop.run_until_complete(_bind())
        except Exception:
            logger.exception("NetServer bind failed")
            self._loop = None
            self._started.set()
            loop.close()
            return
        try:
            loop.run_forever()
        finally:
            # drain callbacks scheduled during shutdown, then close
            loop.run_until_complete(loop.shutdown_asyncgens())
            loop.close()

    def stop(self, drain: bool = True,
             timeout: Optional[float] = 30.0) -> None:
        """Graceful drain: flip to draining (healthz → 503), close the
        listener (stop accepting), let in-flight requests finish —
        their queries still run on the QueryServer workers, which the
        caller must not stop first — then close the loop. ``drain=
        False`` (or the timeout) aborts the stragglers instead."""
        loop, self._loop = self._loop, None
        if loop is None:
            return
        self._draining = True

        async def _close_listener():
            if self._listener is not None:
                self._listener.close()
                await self._listener.wait_closed()
                self._listener = None

        try:
            asyncio.run_coroutine_threadsafe(
                _close_listener(), loop).result(timeout=10.0)
            deadline = (None if timeout is None
                        else time.monotonic() + float(timeout))
            while drain and self._conns:
                if deadline is not None and time.monotonic() > deadline:
                    break
                time.sleep(0.02)

            async def _abort_rest():
                for task in list(self._conns):
                    task.cancel()

            asyncio.run_coroutine_threadsafe(
                _abort_rest(), loop).result(timeout=10.0)
        except Exception:
            logger.debug("NetServer drain cleanup failed", exc_info=True)
        loop.call_soon_threadsafe(loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        self._port = None
        self._started.clear()
        _obs.METRICS.set_gauge("net.active", 0)

    def __enter__(self) -> "NetServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # -- fault hooks ---------------------------------------------------------
    def _read_fault(self) -> None:
        """net_read chaos switchpoint, once per request read. A due
        ``conn_reset`` aborts like a peer RST; ``stall``/``slow_client``
        take the read-timeout ladder (the injection stands in for the
        peer trickling/stalling past ``connTimeoutMs``)."""
        if _faults.active() is None:
            return
        if _faults.fired("net_read", "conn_reset"):
            self._ladder_reset("net_read")
        for kind in ("stall", "slow_client"):
            if _faults.fired("net_read", kind):
                RECOVERY_LOG.record("net_read", "timeout", rung="cut",
                                    cause=f"injected {kind}")
                counters.increment("net.conn_timeout")
                raise _InjectedStall()

    def _write_fault(self, payload: bytes, writer) -> Optional[bytes]:
        """net_write chaos switchpoint, once per payload write. Returns
        a TRUNCATED payload for a due ``partial_write`` (the caller
        writes it then aborts); raises for reset/stall."""
        if _faults.active() is None:
            return None
        if _faults.fired("net_write", "conn_reset"):
            self._ladder_reset("net_write")
        if _faults.fired("net_write", "partial_write"):
            RECOVERY_LOG.record("net_write", "partial_write", rung="cut",
                                cause="injected partial_write")
            counters.increment("net.partial_write")
            return payload[:max(1, len(payload) // 2)]
        if _faults.fired("net_write", "stall"):
            RECOVERY_LOG.record("net_write", "timeout", rung="cut",
                                cause="injected stall")
            counters.increment("net.conn_timeout")
            raise _InjectedStall()
        return None

    @staticmethod
    def _ladder_reset(site: str) -> None:
        RECOVERY_LOG.record(site, "conn_reset", rung="abort",
                            cause="injected conn_reset")
        counters.increment("net.conn_reset")
        raise _Abort()

    # -- connection handling -------------------------------------------------
    async def _accept(self, reader, writer) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        counters.increment("net.accept")
        _obs.METRICS.set_gauge("net.active", len(self._conns))
        conn = _Conn(reader, writer)
        try:
            writer.transport.set_write_buffer_limits(
                high=WRITE_HIGH_WATER)
        except Exception:
            pass
        try:
            if _faults.active() is not None \
                    and _faults.fired("net_accept", "conn_reset"):
                self._ladder_reset("net_accept")
            head = await asyncio.wait_for(conn.read_exactly(4),
                                          self.conn_timeout_s)
            counters.increment("net.bytes_in", 4)
            if head == MAGIC:
                conn.proto = "frame"
                await self._frame_loop(conn)
            else:
                conn.proto = "http"
                conn.pushback(head)
                await self._http_request(conn)
        except (_Abort, asyncio.IncompleteReadError, ConnectionError):
            self._abort(conn)
        except asyncio.TimeoutError:
            # a REAL slow peer ran past connTimeoutMs (slow loris, dead
            # drain): the timeout ladder, counted here
            RECOVERY_LOG.record("net_read", "timeout", rung="cut",
                                cause="connTimeoutMs expired")
            counters.increment("net.conn_timeout")
            await self._timeout_cut(conn)
        except _InjectedStall:
            # injected stall/slow_client: counted at its switchpoint,
            # same ladder tail as the real expiry above
            await self._timeout_cut(conn)
        except asyncio.CancelledError:
            self._abort(conn)
            raise
        except Exception:
            logger.debug("connection handler failed", exc_info=True)
            self._abort(conn)
        finally:
            self._conns.discard(task)
            _obs.METRICS.set_gauge("net.active", len(self._conns))
            try:
                conn.writer.close()
            except Exception:
                pass

    @staticmethod
    def _abort(conn: _Conn) -> None:
        try:
            conn.writer.transport.abort()
        except Exception:
            pass

    async def _timeout_cut(self, conn: _Conn) -> None:
        """The read/write-timeout ladder tail: one structured
        ``conn_timeout`` error if the response stream has not started,
        then the connection closes. Real ``wait_for`` expiries count
        here; the injected rungs counted at their switchpoint."""
        if not conn.streaming:
            doc = {"status": "error", "reason": "conn_timeout",
                   "error": "connection read/write timed out "
                            f"({self.conn_timeout_s:.3g}s)"}
            try:
                if conn.proto == "frame":
                    doc["end"] = True
                    payload = json.dumps(doc).encode()
                    conn.writer.write(
                        struct.pack(">I", len(payload)) + payload)
                    await asyncio.wait_for(conn.writer.drain(), 2.0)
                else:
                    await asyncio.wait_for(
                        self._send_http_doc(conn, 408, doc, raw=True),
                        timeout=2.0)
                counters.increment("net.error_frames")
            except Exception:
                pass
        if _obs.TRACER.enabled:
            # the timeout ladder cut a connection — flight-recorder
            # trigger (per-trigger cooldown bounds repeat captures)
            _incidents.RECORDER.record(
                "fault_ladder",
                detail=f"net conn_timeout cut "
                       f"({self.conn_timeout_s:.3g}s, "
                       f"proto {conn.proto})")
        self._abort(conn)

    # -- frame protocol ------------------------------------------------------
    async def _frame_loop(self, conn: _Conn) -> None:
        while True:
            try:
                head = await asyncio.wait_for(conn.read_exactly(4),
                                              self.conn_timeout_s * 4)
            except asyncio.IncompleteReadError:
                return                      # clean keep-alive close
            self._read_fault()
            (length,) = struct.unpack(">I", head)
            if length > self.max_frame_bytes:
                counters.increment("net.frame_overflow")
                await self._send_frame(conn, {
                    "end": True, "status": "error",
                    "reason": "frame_overflow",
                    "error": f"frame of {length} bytes over "
                             f"maxFrameBytes={self.max_frame_bytes}"})
                counters.increment("net.error_frames")
                return
            body = await asyncio.wait_for(conn.read_exactly(length),
                                          self.conn_timeout_s)
            counters.increment("net.bytes_in", 4 + length)
            counters.increment("net.requests")
            try:
                req = json.loads(body.decode())
            except (ValueError, UnicodeDecodeError) as e:
                await self._send_end(conn, QueryResult(
                    status="error", tenant="", reason="bad_request",
                    error=f"unparseable frame: {e}"), pages=0)
                return
            result, fut = await self._submit_and_wait(conn, req)
            ctx = self._trace_ctx(fut)
            t_stream = time.perf_counter()
            pages = 0
            try:
                if result.status == "ok":
                    dts = self._stream_deadline(fut)
                    for page in self._pages(result.value):
                        if dts is not None \
                                and time.perf_counter() > dts:
                            result = self._page_deadline(result, pages)
                            break
                        page["page"] = pages
                        await self._send_frame(conn, page)
                        pages += 1
                        counters.increment("net.pages")
                await self._send_end(
                    conn, result, pages=pages,
                    trace_id=ctx.trace_id if ctx is not None else None)
            finally:
                self._finish_trace(
                    ctx, pages=pages, proto="frame",
                    stream_ms=(time.perf_counter() - t_stream) * 1e3)

    async def _send_frame(self, conn: _Conn, doc: dict) -> None:
        payload = json.dumps(doc, default=_json_default).encode()
        data = struct.pack(">I", len(payload)) + payload
        await self._write(conn, data)

    async def _send_end(self, conn: _Conn, result: QueryResult,
                        pages: int,
                        trace_id: Optional[str] = None) -> None:
        doc = self._end_doc(result)
        doc["end"] = True
        doc["pages"] = pages
        if trace_id is not None:
            # echo the wire trace id so every client-held result is
            # joinable with the server-side tree; with tracing disabled
            # the frame stays byte-identical (no trace_id key at all)
            doc["trace_id"] = trace_id
        if result.status != "ok":
            counters.increment("net.error_frames")
        await self._send_frame(conn, doc)

    # -- HTTP protocol -------------------------------------------------------
    async def _http_request(self, conn: _Conn) -> None:
        # ONE timeout bound spans the whole head+body read: a trickling
        # peer (slow loris) cannot stretch it byte by byte
        try:
            method, path, headers, body = await asyncio.wait_for(
                self._read_http(conn), self.conn_timeout_s)
        except _FrameOverflow as e:
            counters.increment("net.frame_overflow")
            await self._send_http_doc(conn, 413, {
                "status": "error", "reason": "frame_overflow",
                "error": str(e)})
            return
        counters.increment("net.requests")
        if method == "GET" and path == "/healthz":
            draining = self._draining or getattr(
                self.server, "draining", False)
            ok = not draining and self.server.running
            await self._send_http_doc(
                conn, 200 if ok else 503,
                {"status": "ok" if ok else
                 ("draining" if draining else "stopped")})
            return
        if method != "POST" or path != "/query":
            await self._send_http_doc(conn, 404, {
                "status": "error", "reason": "unknown_route",
                "routes": ["POST /query", "GET /healthz"]})
            return
        req = {}
        if body:
            try:
                req = json.loads(body.decode())
            except (ValueError, UnicodeDecodeError) as e:
                await self._send_http_doc(conn, 400, {
                    "status": "error", "reason": "bad_request",
                    "error": f"unparseable body: {e}"})
                return
        for header, field in (("x-dq-tenant", "tenant"),
                              ("x-dq-deadline-ms", "deadline_ms"),
                              ("x-dq-idempotency-key", "idem"),
                              ("x-dq-tag", "tag"),
                              ("x-dq-est-bytes", "est_bytes"),
                              ("traceparent", "traceparent")):
            if header in headers:
                req[field] = headers[header]
        result, fut = await self._submit_and_wait(conn, req)
        ctx = self._trace_ctx(fut)
        trace_id = ctx.trace_id if ctx is not None else None
        if result.status != "ok":
            counters.increment("net.error_frames")
            doc = self._end_doc(result)
            if trace_id is not None:
                doc["trace_id"] = trace_id
            try:
                await self._send_http_doc(
                    conn, _STATUS_HTTP.get(result.status, 500), doc)
            finally:
                self._finish_trace(ctx, pages=0, stream_ms=0.0,
                                   proto="http")
            return
        t_stream = time.perf_counter()
        pages = 0
        try:
            pages = await self._stream_http(conn, result,
                                            trace_id=trace_id, fut=fut)
        finally:
            self._finish_trace(
                ctx, pages=pages, proto="http",
                stream_ms=(time.perf_counter() - t_stream) * 1e3)

    async def _read_http(self, conn: _Conn):
        request_line = (await conn.read_line(self.max_frame_bytes)) \
            .decode("latin-1").strip()
        self._read_fault()
        parts = request_line.split()
        if len(parts) < 2:
            raise _FrameOverflow(f"bad request line {request_line!r}")
        method, path = parts[0].upper(), parts[1].split("?", 1)[0]
        headers: dict[str, str] = {}
        total = len(request_line)
        while True:
            line = (await conn.read_line(self.max_frame_bytes)) \
                .decode("latin-1")
            total += len(line)
            if total > self.max_frame_bytes:
                raise _FrameOverflow(
                    f"HTTP head over maxFrameBytes="
                    f"{self.max_frame_bytes}")
            line = line.strip()
            if not line:
                break
            name, _, value = line.partition(":")
            headers[name.strip().lower()] = value.strip()
        length = int(headers.get("content-length", "0") or "0")
        if length > self.max_frame_bytes:
            raise _FrameOverflow(
                f"body of {length} bytes over maxFrameBytes="
                f"{self.max_frame_bytes}")
        body = await conn.read_exactly(length) if length else b""
        counters.increment("net.bytes_in", total + length)
        return method, path, headers, body

    async def _send_http_doc(self, conn: _Conn, code: int, doc: dict,
                             raw: bool = False) -> None:
        payload = json.dumps(doc, default=_json_default).encode()
        head = (f"HTTP/1.1 {code} {_HTTP_REASONS.get(code, 'OK')}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(payload)}\r\n"
                "Connection: close\r\n\r\n").encode("latin-1")
        if raw:
            # timeout-ladder tail: best-effort, no nested fault hooks
            conn.writer.write(head + payload)
            await conn.writer.drain()
            return
        await self._write(conn, head + payload)

    async def _stream_http(self, conn: _Conn, result: QueryResult,
                           trace_id: Optional[str] = None,
                           fut=None) -> int:
        head = ("HTTP/1.1 200 OK\r\n"
                "Content-Type: application/x-ndjson\r\n"
                "Transfer-Encoding: chunked\r\n"
                "Connection: close\r\n\r\n").encode("latin-1")
        await self._write(conn, head)
        conn.streaming = True
        pages = 0
        dts = self._stream_deadline(fut)
        for page in self._pages(result.value):
            if dts is not None and time.perf_counter() > dts:
                result = self._page_deadline(result, pages)
                break
            page["page"] = pages
            await self._write_chunk(conn, page)
            pages += 1
            counters.increment("net.pages")
        end = self._end_doc(result)
        end["end"] = True        # same self-describing marker as frames
        end["pages"] = pages
        if trace_id is not None:
            end["trace_id"] = trace_id
        await self._write_chunk(conn, end)
        await self._write(conn, b"0\r\n\r\n")
        return pages

    async def _write_chunk(self, conn: _Conn, doc: dict) -> None:
        line = json.dumps(doc, default=_json_default).encode() + b"\n"
        await self._write(
            conn, f"{len(line):x}\r\n".encode("latin-1") + line + b"\r\n")

    async def _write(self, conn: _Conn, data: bytes) -> None:
        truncated = self._write_fault(data, conn.writer)
        if truncated is not None:
            conn.writer.write(truncated)
            try:
                await asyncio.wait_for(conn.writer.drain(), 2.0)
            except Exception:
                pass
            raise _Abort()
        conn.writer.write(data)
        await asyncio.wait_for(conn.writer.drain(), self.conn_timeout_s)
        counters.increment("net.bytes_out", len(data))

    # -- submission bridge ---------------------------------------------------
    async def _submit_and_wait(self, conn: _Conn, req: dict):
        """Admit the wire request into the QueryServer (idempotency-key
        dedup first) and await its result without blocking the event
        loop; a peer that disconnects mid-wait abandons the job through
        the server's accounting. Always returns a structured
        ``QueryResult`` — never raises for tenant-visible failures."""
        try:
            fut = self._resolve_future(req)
        except _BadRequest as e:
            return QueryResult(status="error",
                               tenant=str(req.get("tenant", "")),
                               reason=e.reason, error=str(e)), None
        except RuntimeError as e:
            # submit() while the server drains/stops — the shutdown gate
            return QueryResult(status="rejected",
                               tenant=str(req.get("tenant", "")),
                               reason="shutdown", detail=str(e)), None
        loop = asyncio.get_running_loop()
        bound = RESULT_BOUND_S
        job = fut._job
        if job.deadline_ts is not None:
            bound = max(0.1, job.deadline_ts - time.perf_counter()) + 2.0
        res_task = loop.run_in_executor(self._pool, self._wait_result,
                                        fut, bound)
        watch = None
        if not conn.buf:
            watch = asyncio.ensure_future(conn.reader.read(1))
        try:
            if watch is None:
                return await res_task, fut
            done, _ = await asyncio.wait(
                {res_task, watch}, return_when=asyncio.FIRST_COMPLETED)
            if res_task in done:
                return res_task.result(), fut
            data = watch.result()
            if data:
                # pipelined bytes from a keep-alive client: not a
                # disconnect — push back and keep waiting
                conn.pushback(data)
                return await res_task, fut
            # peer vanished mid-wait: abandon through the server's own
            # accounting — serve.error now, the worker's late value is
            # discarded via the existing serve.late_result path
            counters.increment("net.client_gone")
            self._abandon(fut)
            await res_task
            # the abandon verdict is in; nobody will stream, so the
            # deferred tree finalizes here (no-op if never opened)
            self._finish_trace(self._trace_ctx(fut), pages=0,
                               stream_ms=0.0, proto=conn.proto or "")
            raise _Abort()
        finally:
            if watch is not None and not watch.done():
                # The cancelled read must have RUN its cancellation (it
                # owns the StreamReader's one waiter slot) before the
                # keep-alive loop reads the next request: a reply small
                # enough to drain without suspending would otherwise
                # reach that read first, fail it with "read() called
                # while another coroutine is already waiting" and drop
                # the connection after every response. wait() does not
                # re-raise the watch's own CancelledError.
                watch.cancel()
                await asyncio.wait({watch})

    def _wait_result(self, fut: QueryFuture, bound: float) -> QueryResult:
        try:
            return fut.result(timeout=bound)
        except TimeoutError:
            job = fut._job
            return QueryResult(
                status="error", tenant=job.tenant, tag=job.tag,
                reason="result_bound",
                error=f"no result within the {bound:.0f}s wire bound")

    # -- tracing bridge ------------------------------------------------------
    @staticmethod
    def _trace_ctx(fut) -> Optional["_obs.TraceContext"]:
        """The request's adopted trace context (None for pre-admission
        refusals, which never reached ``submit``)."""
        if fut is None:
            return None
        trace = getattr(getattr(fut, "_job", None), "trace", None)
        return trace if isinstance(trace, _obs.TraceContext) else None

    @staticmethod
    def _finish_trace(ctx, *, pages: int, stream_ms: float,
                      proto: str) -> None:
        """Wire-side finalization of a deferred request tree: a
        back-dated ``serve.stream`` span for the page write-out, then
        the tail sampler's keep-policy completion. Idempotent."""
        if ctx is None or not _obs.TRACER.enabled:
            return
        if ctx.root_sid is not None and pages:
            _obs.emit_span("serve.stream", cat="serve",
                           dur_ms=stream_ms, ctx=ctx, pages=pages,
                           proto=proto)
        _obs.TAIL.complete(ctx)

    def _abandon(self, fut: QueryFuture) -> None:
        job = fut._job
        e2e_ms = (time.perf_counter() - job.t_submit) * 1e3
        self.server._finish(job, QueryResult(
            status="error", tenant=job.tenant, tag=job.tag,
            reason="client_gone", error="peer disconnected mid-request",
            e2e_ms=e2e_ms), executed=False, e2e_ms=e2e_ms)

    def _resolve_future(self, req: dict) -> QueryFuture:
        tenant = str(req.get("tenant") or "default")
        idem = req.get("idem")
        if idem:
            with self._idem_lock:
                fut = self._idem.get(idem)
                if fut is not None:
                    self._idem.move_to_end(idem)
                    counters.increment("net.idem_hit")
                    return fut
        work = req.get("sql")
        if work is None:
            name = req.get("job")
            work = self._jobs.get(name) if name else None
            if work is None:
                raise _BadRequest(
                    "bad_request", f"no 'sql' and no registered job "
                    f"{name!r}")
        deadline_s = None
        if req.get("deadline_ms") is not None:
            try:
                deadline_s = max(1e-3, float(req["deadline_ms"]) / 1e3)
            except (TypeError, ValueError):
                raise _BadRequest(
                    "bad_request",
                    f"bad deadline_ms {req['deadline_ms']!r}")
        est_bytes = None
        if req.get("est_bytes") is not None:
            try:
                est_bytes = max(0, int(req["est_bytes"]))
            except (TypeError, ValueError):
                raise _BadRequest(
                    "bad_request",
                    f"bad est_bytes {req['est_bytes']!r}")
        # ONE flag read: with tracing on, the wire traceparent (frame doc
        # field / HTTP header) becomes the request's context — malformed
        # or absent degrades to a locally-minted root, NEVER an error.
        # defer=True: this wire layer finalizes the tree after streaming.
        trace = (_obs.TraceContext.adopt(req.get("traceparent"),
                                         defer=True)
                 if _obs.TRACER.enabled else None)
        fut = self.server.submit(
            work, tenant=tenant, deadline_s=deadline_s,
            tag=str(req["tag"]) if req.get("tag") is not None else None,
            est_bytes=est_bytes, trace=trace)
        if idem:
            with self._idem_lock:
                self._idem[idem] = fut
                while len(self._idem) > IDEM_CACHE:
                    self._idem.popitem(last=False)
        return fut

    # -- result paging -------------------------------------------------------
    def _pages(self, value):
        """Result pages: a Frame streams ``page_rows`` rows at a time as
        column slices; anything else is one ``value`` page. The column
        pull is one host materialization per query (the same boundary a
        direct ``to_pydict`` consumer pays); paging bounds the PER-
        CLIENT serialized bytes in flight."""
        if hasattr(value, "to_pydict"):
            cols = value.to_pydict()
            n = max((len(v) for v in cols.values()), default=0)
            step = max(1, self.page_rows)
            for lo in range(0, n, step):
                yield {"rows": {k: v[lo:lo + step]
                                for k, v in cols.items()}}
            if n == 0:
                yield {"rows": {k: [] for k in cols}}
            return
        yield {"value": value}

    @staticmethod
    def _stream_deadline(fut) -> Optional[float]:
        """The job's wire deadline carried INTO streaming: ``deadline_s``
        bounds queueing and execution, but a large SELECT's result could
        page out past it indefinitely — each page send re-checks this
        ``perf_counter`` bound, so the deadline covers the stream end to
        end. None (no wire deadline, or a dedup/reject path without a
        job) streams unbounded as before."""
        job = getattr(fut, "_job", None)
        return getattr(job, "deadline_ts", None)

    @staticmethod
    def _page_deadline(result: QueryResult, pages: int) -> QueryResult:
        """Truncate a result stream at the wire deadline: the pages
        already sent stand, the rest are dropped, and the terminal frame
        carries a structured ``deadline_exceeded`` (site ``stream``) —
        the client sees a clean refusal, never a wedged socket."""
        counters.increment("net.page_deadline")
        return QueryResult(
            status="deadline_exceeded", tenant=result.tenant,
            reason="deadline", where="stream", tag=result.tag,
            queue_ms=result.queue_ms, exec_ms=result.exec_ms,
            e2e_ms=result.e2e_ms,
            detail=f"wire deadline expired mid-stream after {pages} "
                   "page(s); remaining pages dropped")

    @staticmethod
    def _end_doc(result: QueryResult) -> dict:
        doc = {"status": result.status, "tenant": result.tenant}
        for field in ("reason", "detail", "error", "where", "tag",
                      "queue_ms", "exec_ms", "e2e_ms"):
            v = getattr(result, field, None)
            if v not in (None, ""):
                doc[field] = v
        if result.status == "ok" and not hasattr(result.value,
                                                 "to_pydict") \
                and not isinstance(result.value, (dict, list)):
            doc["value"] = result.value
        return doc


class _BadRequest(Exception):
    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


class _InjectedStall(Exception):
    """An injected ``stall``/``slow_client`` standing in for a peer
    exceeding ``connTimeoutMs`` — handled by the same ladder as a real
    ``asyncio.TimeoutError``."""
