"""Network serving front end (serve/net.py + serve/client.py, ISSUE 16).

Pins the wire contract end-to-end over REAL sockets: both framings
(DQW1 length-prefixed frames and HTTP/1.1 chunked ndjson streaming),
wire-propagated relative deadlines (header → server-side QueryResult
deadline; a queued-past-wire-deadline job provably never executes; the
waiter-synthesized ``deadline_exceeded`` reaches the socket client as a
structured frame, never a hang or reset), streaming result pages,
graceful drain (/healthz → 503 from drain start, both on the telemetry
endpoint and the net endpoint), slow-loris read-timeout cuts
(``net.conn_timeout``), the idempotency-key no-double-execute contract,
the resilient client's retry ladder over injected net faults, the
session-conf vocabulary (``spark.serve.net.*`` / ``spark.serve.
client.*`` with session-scoped restore), the disabled-mode one-flag
no-op, and the ≥5-seed ``--transport socket`` chaos-soak smoke.
"""

import json
import os
import socket
import struct
import threading
import time
import urllib.error
import urllib.request

import pytest

import sparkdq4ml_tpu as dq
from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.serve import (NetServer, QueryServer, ResilientClient,
                                  TenantQuota)
from sparkdq4ml_tpu.serve.net import MAGIC
from sparkdq4ml_tpu.utils import faults, profiling, recovery
from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG, RetryPolicy

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_net_state():
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    yield
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()


@pytest.fixture
def served():
    """A running QueryServer (no engine session — jobs return plain
    values) + NetServer on an ephemeral localhost port."""
    srv = QueryServer(workers=2).start()
    net = NetServer(srv, host="127.0.0.1", port=0,
                    conn_timeout_s=2.0).start()
    srv.net = net       # stop() then drains the front end first
    yield srv, net
    srv.stop()


def _frame_exchange(port: int, docs, read_until_end=True):
    """Raw frame-protocol exchange: send each request doc, collect the
    response frames up to (and including) the end frame per request."""
    out = []
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        s.sendall(MAGIC)
        for doc in docs:
            payload = json.dumps(doc).encode()
            s.sendall(struct.pack(">I", len(payload)) + payload)
            frames = []
            while True:
                head = _recv_exactly(s, 4)
                (length,) = struct.unpack(">I", head)
                frames.append(json.loads(_recv_exactly(s, length).decode()))
                if frames[-1].get("end"):
                    break
            out.append(frames)
    return out


def _recv_exactly(s: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = s.recv(n - len(buf))
        assert chunk, f"peer closed mid-frame ({len(buf)}/{n})"
        buf += chunk
    return buf


# ---------------------------------------------------------------------------
# Wire protocol: both framings, streaming pages, keep-alive
# ---------------------------------------------------------------------------

class TestWireProtocol:
    def test_frame_and_http_roundtrip_scalar_job(self, served):
        srv, net = served
        net.register_job("answer", lambda ctx: {"n": 7, "ok": True})
        for transport in ("frame", "http"):
            with ResilientClient("127.0.0.1", net.port,
                                 transport=transport) as c:
                r = c.call_job("answer", tenant="t1")
                assert r.ok and r.status == "ok"
                assert r.value == {"n": 7, "ok": True}
                assert r.tenant == "t1"
                assert r.attempts == 1

    def test_frame_connection_is_keepalive(self, served):
        srv, net = served
        net.register_job("n", lambda ctx: 1)
        accepted = profiling.counters.get("net.accept")
        with ResilientClient("127.0.0.1", net.port,
                             transport="frame") as c:
            for _ in range(3):
                r = c.call_job("n")
                # a retry would silently reconnect: the server dropping
                # the connection after each response must not pass
                assert r.value == 1 and r.attempts == 1
            assert c._sock is not None    # one persistent connection
        assert profiling.counters.get("net.accept") == accepted + 1
        assert len(RECOVERY_LOG) == 0

    def test_sql_streams_frame_pages(self, session, served):
        """A Frame-valued SELECT streams as row pages (page_rows rows
        each), and the merged pages reproduce the full column data —
        the never-materialize-per-client contract's visible half."""
        srv, net = served
        net.page_rows = 16
        ctx = srv.context("sqltenant")
        from sparkdq4ml_tpu import Frame
        import numpy as np

        ctx.register_view("t", Frame({"x": np.arange(100.0)}))
        for transport in ("frame", "http"):
            with ResilientClient("127.0.0.1", net.port,
                                 transport=transport,
                                 tenant="sqltenant") as c:
                r = c.query("SELECT x FROM t WHERE x < 50")
                assert r.ok, (r.status, r.error)
                assert r.pages >= 4                # 50 rows / 16 per page
                assert r.value["x"] == list(range(50))

    def test_http_error_statuses_are_structured(self, served):
        srv, net = served
        # unknown route → 404 with a structured doc
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{net.port}/nope", timeout=10)
        assert ei.value.code == 404
        doc = json.loads(ei.value.read().decode())
        assert doc["reason"] == "unknown_route"
        # unparseable body → 400, still structured
        req = urllib.request.Request(
            f"http://127.0.0.1:{net.port}/query", data=b"{not json",
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        assert json.loads(ei.value.read().decode())["reason"] \
            == "bad_request"

    def test_frame_overflow_is_refused_structured(self, served):
        srv, net = served
        net.max_frame_bytes = 128
        before = profiling.counters.get("net.frame_overflow")
        [frames] = _frame_exchange(
            net.port, [{"job": "x", "pad": "y" * 4096}])
        assert frames[-1]["status"] == "error"
        assert frames[-1]["reason"] == "frame_overflow"
        assert profiling.counters.get("net.frame_overflow") == before + 1

    def test_unknown_job_is_bad_request(self, served):
        srv, net = served
        with ResilientClient("127.0.0.1", net.port,
                             transport="frame") as c:
            r = c.call_job("never-registered")
            assert r.status == "error" and r.reason == "bad_request"


# ---------------------------------------------------------------------------
# Wire deadline propagation
# ---------------------------------------------------------------------------

class TestWireDeadline:
    def test_deadline_survives_header_roundtrip(self, served):
        """The client's RELATIVE ms budget becomes the server-side job
        deadline within tolerance — clock-skew tolerant because no wall
        clock ever crosses the wire."""
        srv, net = served
        net.register_job("quick", lambda ctx: 1)
        captured = {}
        orig = srv.submit

        def spy(work, *a, **kw):
            captured.update(kw)
            return orig(work, *a, **kw)

        srv.submit = spy
        try:
            for transport in ("frame", "http"):
                with ResilientClient("127.0.0.1", net.port,
                                     transport=transport) as c:
                    assert c.call_job("quick", deadline_s=7.5).ok
                assert abs(captured["deadline_s"] - 7.5) < 0.05, transport
        finally:
            srv.submit = orig

    def test_queued_past_wire_deadline_never_executes(self, session):
        """A job still queued when its wire deadline passes is skipped
        by the worker — provably never executed (its side-effect flag
        stays unset) — and the client sees a structured
        ``deadline_exceeded``."""
        srv = QueryServer(workers=1,
                          default_quota=TenantQuota(max_in_flight=1,
                                                    max_queued=8)).start()
        net = NetServer(srv, host="127.0.0.1", port=0).start()
        srv.net = net
        executed = threading.Event()
        release = threading.Event()
        net.register_job("blocker",
                         lambda ctx: (release.wait(30), "done")[1])
        net.register_job("flagged",
                         lambda ctx: (executed.set(), "ran")[1])
        try:
            with ResilientClient("127.0.0.1", net.port,
                                 transport="frame") as c_block, \
                    ResilientClient("127.0.0.1", net.port,
                                    transport="frame") as c_dead:
                blocked = threading.Thread(
                    target=lambda: c_block.call_job("blocker",
                                                    deadline_s=30.0))
                blocked.start()
                deadline = time.monotonic() + 5.0
                while not srv.stats()["tenants"].get(
                        "default", {}).get("in_flight"):
                    assert time.monotonic() < deadline, "blocker not taken"
                    time.sleep(0.01)
                r = c_dead.call_job("flagged", deadline_s=0.3)
                assert r.status == "deadline_exceeded", (r.status, r.error)
                release.set()
                blocked.join(timeout=30)
            # drain: the skipped job is popped and dropped, not run
            srv.stop()
            assert not executed.is_set()
        finally:
            release.set()
            srv.stop()

    def test_waiter_deadline_is_structured_frame_not_hang(self, served):
        """The waiter-synthesized deadline result crosses the socket as
        a structured error frame within deadline + small grace — not a
        hang, not a reset."""
        srv, net = served
        net.register_job("slow", lambda ctx: (time.sleep(5.0), 1)[1])
        t0 = time.monotonic()
        [frames] = _frame_exchange(net.port,
                                   [{"job": "slow", "deadline_ms": 300}])
        took = time.monotonic() - t0
        assert frames[-1]["end"] is True
        assert frames[-1]["status"] == "deadline_exceeded"
        assert frames[-1]["where"] in ("wait", "queue", "exec")
        assert took < 4.0, f"deadline frame took {took:.1f}s"


# ---------------------------------------------------------------------------
# Drain / healthz
# ---------------------------------------------------------------------------

class TestDrainHealthz:
    def test_healthz_503_while_draining_and_when_stopped(self):
        """/healthz (telemetry AND net endpoints): 200 running → 503
        "draining" from drain start → 503 "stopped" after stop — the
        balancer stops routing the moment the drain begins, not only
        once the server is gone."""
        srv = QueryServer(workers=1, metrics_port=0).start()
        net = NetServer(srv, host="127.0.0.1", port=0).start()
        srv.net = net
        tport = srv.telemetry.port

        def telemetry_health():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{tport}/healthz",
                        timeout=10) as resp:
                    return resp.status, json.loads(resp.read().decode())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read().decode())

        c = ResilientClient("127.0.0.1", net.port, transport="http")
        try:
            code, doc = telemetry_health()
            assert (code, doc["status"]) == (200, "ok")
            assert c.healthz()["http_code"] == 200
            srv.begin_drain()
            code, doc = telemetry_health()
            assert (code, doc["status"]) == (503, "draining")
            h = c.healthz()
            assert (h["http_code"], h["status"]) == (503, "draining")
            srv.stop()
            # net socket is gone; the telemetry endpoint died with stop
            # — the stopped pin runs against a fresh telemetry server
        finally:
            c.close()
            srv.stop()
        srv2 = QueryServer(workers=1, metrics_port=0).start()
        tport = srv2.telemetry.port
        telemetry = srv2.telemetry
        with srv2._cond:
            srv2._accepting = False          # stopped-shaped stats
        try:
            code, doc = telemetry_health()
            assert (code, doc["status"]) == (503, "stopped")
        finally:
            srv2._accepting = True
            srv2.stop()

    def test_submit_during_drain_is_structured_rejection(self, served):
        srv, net = served
        net.register_job("n", lambda ctx: 1)
        srv.begin_drain()
        with ResilientClient("127.0.0.1", net.port,
                             transport="frame") as c:
            r = c.call_job("n")
            assert r.status == "rejected" and r.reason == "shutdown"


# ---------------------------------------------------------------------------
# Slow-loris / read timeout ladder
# ---------------------------------------------------------------------------

class TestConnTimeout:
    def test_slow_loris_is_cut_with_structured_408(self):
        """A peer trickling its request past connTimeoutMs is cut —
        bounded wait, ``net.conn_timeout`` counted, a structured 408
        where the protocol still allows one."""
        srv = QueryServer(workers=1).start()
        net = NetServer(srv, host="127.0.0.1", port=0,
                        conn_timeout_s=0.4).start()
        srv.net = net
        before = profiling.counters.get("net.conn_timeout")
        try:
            t0 = time.monotonic()
            with socket.create_connection(("127.0.0.1", net.port),
                                          timeout=10) as s:
                s.sendall(b"POST")          # sniffed as HTTP, then stall
                data = b""
                while True:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    data += chunk
            took = time.monotonic() - t0
            assert took < 5.0, f"loris connection lived {took:.1f}s"
            assert b"408" in data and b"conn_timeout" in data
            assert profiling.counters.get("net.conn_timeout") \
                == before + 1
            assert RECOVERY_LOG.count(site="net_read",
                                      action="timeout") == 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Idempotency & the resilient client
# ---------------------------------------------------------------------------

class TestIdempotency:
    def test_same_idem_key_never_double_executes(self, served):
        srv, net = served
        runs = []
        net.register_job("counted",
                         lambda ctx: (runs.append(1), len(runs))[1])
        doc = {"job": "counted", "idem": "fixed-key-1"}
        before = profiling.counters.get("net.idem_hit")
        [first] = _frame_exchange(net.port, [doc])
        [replay] = _frame_exchange(net.port, [doc])     # retried query
        assert first[-1]["status"] == replay[-1]["status"] == "ok"
        # the replay streamed the ORIGINAL result, no second execution
        assert first[0]["value"] == replay[0]["value"] == 1
        assert len(runs) == 1
        assert profiling.counters.get("net.idem_hit") == before + 1

    def test_client_retries_injected_reset_exactly_once_serverside(
            self, served):
        """An injected net_read conn_reset kills the first attempt; the
        resilient client retries (same idempotency key) and lands the
        golden value with exactly one server-side execution."""
        srv, net = served
        runs = []
        net.register_job("counted",
                         lambda ctx: (runs.append(1), 42)[1])
        faults.install_plan(faults.parse_plan("net_read:conn_reset:1",
                                              seed=0))
        before = profiling.counters.get("net.client_retry")
        with ResilientClient(
                "127.0.0.1", net.port, transport="frame",
                policy=RetryPolicy(max_attempts=3,
                                   backoff_base=0.01)) as c:
            r = c.call_job("counted")
        assert r.ok and r.value == 42
        assert r.attempts == 2
        assert len(runs) == 1
        assert profiling.counters.get("net.client_retry") == before + 1
        assert RECOVERY_LOG.count(site="net_read",
                                  action="conn_reset") == 1
        assert RECOVERY_LOG.count(site="net_client", action="retry") == 1
        assert RECOVERY_LOG.count(site="net_client",
                                  action="recovered") == 1

    def test_exhausted_wire_is_structured_never_raises(self):
        """Every attempt failing (nothing listening) exhausts into a
        structured ClientResult — never an exception, never a hang."""
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            dead_port = probe.getsockname()[1]
        c = ResilientClient("127.0.0.1", dead_port, transport="frame",
                            policy=RetryPolicy(max_attempts=2,
                                               backoff_base=0.01),
                            connect_timeout=0.5)
        r = c.call_job("anything")
        assert r.status == "error" and r.reason == "net_exhausted"
        assert r.attempts == 2
        c.close()

    def test_client_deadline_budget_is_clientside_bound(self):
        """The wire deadline also bounds the CLIENT's total spend: a
        dead endpoint + tiny deadline returns deadline_exceeded with
        where="client" well inside the hang bound."""
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            dead_port = probe.getsockname()[1]
        c = ResilientClient(
            "127.0.0.1", dead_port, transport="frame",
            policy=RetryPolicy(max_attempts=50, backoff_base=0.2,
                               total_deadline=0.5),
            connect_timeout=0.3)
        t0 = time.monotonic()
        r = c.call_job("anything", deadline_s=0.2)
        assert time.monotonic() - t0 < 10.0
        assert r.status in ("deadline_exceeded", "error")
        if r.status == "deadline_exceeded":
            assert r.where == "client"
        c.close()

    def test_client_gone_midwait_discards_via_late_result(self, served):
        """A peer that vanishes while its query runs is abandoned
        through the server's accounting: serve.admit stays coherent
        (the job resolves as a structured error) and the worker's
        eventual value is discarded via serve.late_result — counted,
        never silent."""
        srv, net = served
        release = threading.Event()
        net.register_job("slow",
                         lambda ctx: (release.wait(15), "late")[1])
        gone0 = profiling.counters.get("net.client_gone")
        late0 = profiling.counters.get("serve.late_result")
        s = socket.create_connection(("127.0.0.1", net.port), timeout=10)
        s.sendall(MAGIC)
        payload = json.dumps({"job": "slow"}).encode()
        s.sendall(struct.pack(">I", len(payload)) + payload)
        deadline = time.monotonic() + 5.0
        while not srv.stats()["tenants"].get("default",
                                             {}).get("in_flight"):
            assert time.monotonic() < deadline, "job never started"
            time.sleep(0.01)
        s.close()                        # vanish mid-execution
        deadline = time.monotonic() + 5.0
        while profiling.counters.get("net.client_gone") == gone0:
            assert time.monotonic() < deadline, "disconnect not seen"
            time.sleep(0.01)
        release.set()
        deadline = time.monotonic() + 5.0
        while profiling.counters.get("serve.late_result") == late0:
            assert time.monotonic() < deadline, "late result not counted"
            time.sleep(0.01)
        assert profiling.counters.get("net.client_gone") == gone0 + 1


# ---------------------------------------------------------------------------
# Conf vocabulary & disabled mode
# ---------------------------------------------------------------------------

class TestNetConf:
    def test_disabled_mode_one_flag_noop(self, session):
        """spark.serve.net.enabled defaults false: start() reads ONE
        flag and starts nothing — no NetServer, no net thread."""
        assert config.serve_net_enabled is False
        srv = QueryServer(session, workers=1).start()
        try:
            assert srv.net is None
            assert not any("sparkdq4ml-net" in t.name
                           for t in threading.enumerate())
        finally:
            srv.stop()

    def test_conf_enables_and_session_restore(self):
        s = dq.TpuSession.builder().app_name("netconf") \
            .config("spark.serve.net.enabled", "true") \
            .config("spark.serve.net.port", "0") \
            .config("spark.serve.net.connTimeoutMs", "1234") \
            .config("spark.serve.net.maxFrameBytes", "65536") \
            .config("spark.serve.net.streamPageRows", "128") \
            .config("spark.serve.client.retries", "5") \
            .config("spark.serve.client.backoffMs", "10") \
            .config("spark.serve.client.hedging", "true") \
            .get_or_create()
        try:
            assert config.serve_net_enabled is True
            assert config.serve_net_conn_timeout_ms == 1234
            assert config.serve_net_max_frame_bytes == 65536
            assert config.serve_net_stream_page_rows == 128
            assert config.serve_client_retries == 5
            assert config.serve_client_backoff_ms == 10.0
            assert config.serve_client_hedging is True
            srv = QueryServer(s, workers=1).start()
            try:
                # the conf flag started the front end; its knobs flowed
                # through the NetServer's conf-default constructor
                assert srv.net is not None and srv.net.port
                assert srv.net.conn_timeout_s == pytest.approx(1.234)
                assert srv.net.max_frame_bytes == 65536
                assert srv.net.page_rows == 128
                net = srv.net
                c = ResilientClient("127.0.0.1", net.port,
                                    transport="frame")
                assert c.policy.max_attempts == 5
                assert c.policy.backoff_base == pytest.approx(0.01)
                assert c.hedging is True
                c.close()
            finally:
                srv.stop()
                assert srv.net is None       # stop() tore the net down
        finally:
            s.stop()
        # session-scoped restore-on-stop: every knob back to defaults
        assert config.serve_net_enabled is False
        assert config.serve_net_conn_timeout_ms == 10_000
        assert config.serve_net_max_frame_bytes == 4 << 20
        assert config.serve_net_stream_page_rows == 4096
        assert config.serve_client_retries == 3
        assert config.serve_client_backoff_ms == 50.0
        assert config.serve_client_hedging is False

    def test_hedged_call_uses_one_idem_key(self, served):
        """Hedging races a second connection with the SAME idempotency
        key: the query still executes exactly once server-side."""
        srv, net = served
        runs = []
        release = threading.Event()
        net.register_job(
            "slowish",
            lambda ctx: (runs.append(1), release.wait(5), "v")[2])
        hedge0 = profiling.counters.get("net.client_hedge")
        with ResilientClient(
                "127.0.0.1", net.port, transport="frame", hedging=True,
                policy=RetryPolicy(max_attempts=2,
                                   backoff_base=0.05)) as c:
            t = threading.Thread(target=lambda: time.sleep(0.4)
                                 or release.set())
            t.start()
            r = c.call_job("slowish")
            t.join()
        assert r.ok and r.value == "v"
        assert profiling.counters.get("net.client_hedge") == hedge0 + 1
        assert len(runs) == 1            # idem dedup ate the hedge


# ---------------------------------------------------------------------------
# The socket chaos-soak smoke (tier-1 CI arm)
# ---------------------------------------------------------------------------

def _load_soak():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_soak_net", os.path.join(REPO, "scripts", "chaos_soak.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSocketSoak:
    def test_socket_schedule_extends_inproc(self):
        soak = _load_soak()
        for s in range(7):
            inproc = soak.build_schedule(s)
            sock = soak.build_schedule(s, "socket")
            assert sock != inproc
            assert "net_" in sock and "net_" not in inproc
            faults.parse_plan(sock, seed=s)          # parses clean
            assert sock == soak.build_schedule(s, "socket")   # pure

    def test_socket_soak_smoke_five_seeds(self):
        """≥5-seed ``--transport socket`` soak: the full workload over
        real sockets with net faults in rotation — zero hangs, golden
        results, every injected net fault resolved through a ladder
        rung, coherent scraped counters."""
        soak = _load_soak()
        summary = soak.run_soak(seeds=5, clients=3, queries=1, workers=4,
                                transport="socket")
        assert summary["ok"], summary["per_seed"]
        assert summary["transport"] == "socket"
        assert summary["completed"] > 0
        assert summary["net_faults_fired"] > 0
        assert summary["breakers_recovered"] == summary["breakers_probed"]
