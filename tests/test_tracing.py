"""Distributed request tracing + incident flight recorder (ISSUE 17).

Pins the tentpole contracts end-to-end:

* strict W3C-style ``traceparent`` parsing — every malformed shape
  (wrong type/length/version, non-hex, all-zero ids, a hostile 1 MB
  header) degrades to a locally-minted root, NEVER an error, over both
  wire framings against a real socket;
* client-side propagation — one trace id per logical query, a fresh
  child span id per attempt and per hedge, the id echoed back on every
  ``ClientResult`` (including client-synthesized ones);
* tail-based sampling — healthy trees age out of the bounded ring,
  error/deadline/fault/breaker/slow trees promote to the retained
  store and resolve via ``TAIL.lookup`` and ``/trace/<id>``;
* the incident flight recorder — atomic on-disk bundles, retention
  pruning, the ``incident`` fault site's degrade-to-memory ladder, and
  the breaker-trip trigger through a real serving stack;
* the disabled-mode contract — byte-identical wire frames and a
  one-flag-read no-op, pinned by monkeypatching every tracing hook to
  raise.
"""

import json
import os
import socket
import struct
import threading
import time

import pytest

from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.serve import NetServer, QueryServer, ResilientClient
from sparkdq4ml_tpu.serve.net import MAGIC
from sparkdq4ml_tpu.utils import faults, incidents, profiling, recovery
from sparkdq4ml_tpu.utils import observability as obs
from sparkdq4ml_tpu.utils.recovery import RECOVERY_LOG

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _tracing_clean():
    """Every test starts and ends with tracing off, buffers empty, and
    the incident recorder back at factory state."""
    obs.disable()
    obs.reset()
    profiling.counters.clear()
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    incidents.RECORDER.reset()
    incidents.RECORDER.configure(enabled=False, directory="",
                                 max_bundles=32, cooldown_s=5.0,
                                 slo_burn_threshold=8.0)
    yield
    obs.disable()
    obs.reset()
    profiling.counters.clear()
    faults.clear()
    RECOVERY_LOG.clear()
    recovery.DEVICE_BREAKER.reset()
    incidents.RECORDER.reset()
    incidents.RECORDER.configure(enabled=False, directory="",
                                 max_bundles=32, cooldown_s=5.0,
                                 slo_burn_threshold=8.0)


@pytest.fixture
def served():
    """A running QueryServer + NetServer on an ephemeral port."""
    srv = QueryServer(workers=2).start()
    net = NetServer(srv, host="127.0.0.1", port=0,
                    conn_timeout_s=2.0).start()
    srv.net = net
    net.register_job("answer", lambda ctx: 7)
    net.register_job("boom", _raise_value_error)
    yield srv, net
    srv.stop()


def _raise_value_error(ctx):
    raise ValueError("deliberate test failure")


VALID_TP = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


def _frame_exchange(port, docs):
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
                frames.append(
                    json.loads(_recv_exactly(s, length).decode()))
                if frames[-1].get("end"):
                    break
            out.append(frames)
    return out


def _lookup_soon(trace_id, timeout_s=2.0):
    """Poll ``TAIL.lookup``: the end frame is sent BEFORE the server's
    finally-block finalizes the tree, so a fresh wire result may race
    the sampler by a few scheduler ticks."""
    deadline = time.monotonic() + timeout_s
    while True:
        docs = obs.TAIL.lookup(trace_id)
        if docs or time.monotonic() >= deadline:
            return docs
        time.sleep(0.01)


def _recv_exactly(s, n):
    buf = b""
    while len(buf) < n:
        chunk = s.recv(n - len(buf))
        assert chunk, f"peer closed mid-frame ({len(buf)}/{n})"
        buf += chunk
    return buf


# ---------------------------------------------------------------------------
# traceparent parsing: strict in, degrade on everything else
# ---------------------------------------------------------------------------

class TestTraceparentParse:
    def test_valid_traceparent_parses_remote(self):
        ctx = obs.TraceContext.parse(VALID_TP)
        assert ctx is not None and ctx.remote
        assert ctx.trace_id == "ab" * 16
        assert ctx.parent_id == "cd" * 8

    @pytest.mark.parametrize("bad", [
        None,                                       # absent
        1234,                                       # non-string
        b"00-" + b"ab" * 16 + b"-" + b"cd" * 8 + b"-01",  # bytes
        "",                                         # empty
        "garbage",                                  # short junk
        VALID_TP[:-1],                              # truncated by one
        VALID_TP + "0",                             # one char long
        "01-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # wrong version
        "zz-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # non-hex version
        "00-" + "00" * 16 + "-" + "cd" * 8 + "-01",  # all-zero trace id
        "00-" + "ab" * 16 + "-" + "00" * 8 + "-01",  # all-zero span id
        "00-" + "AB" * 16 + "-" + "cd" * 8 + "-01",  # uppercase hex
        "00-" + "gg" * 16 + "-" + "cd" * 8 + "-01",  # non-hex trace id
        "00" + "-" * 53,                            # right length, dashes
        "00-" + "ab" * 16 + "-" + "cd" * 8 + "-0g",  # non-hex flags
        "x" * (1 << 20),                            # hostile 1 MB value
    ])
    def test_every_malformed_shape_is_rejected(self, bad):
        assert obs.TraceContext.parse(bad) is None

    def test_adopt_degrades_to_local_mint_and_is_idempotent(self):
        local = obs.TraceContext.adopt("not a traceparent")
        assert not local.remote and len(local.trace_id) == 32
        again = obs.TraceContext.adopt(local, defer=True)
        assert again is local and again.defer
        # defer only widens: re-adopting without defer keeps it set
        assert obs.TraceContext.adopt(local).defer

    def test_child_traceparent_fresh_span_id_same_trace(self):
        ctx = obs.TraceContext.mint()
        a, b = ctx.child_traceparent(), ctx.child_traceparent()
        assert a != b
        pa, pb = obs.TraceContext.parse(a), obs.TraceContext.parse(b)
        assert pa.trace_id == pb.trace_id == ctx.trace_id
        assert pa.parent_id != pb.parent_id


# ---------------------------------------------------------------------------
# wire-level degradation: hostile headers never 500, never hang
# ---------------------------------------------------------------------------

class TestWireDegradation:
    @pytest.mark.parametrize("hostile", [
        "garbage", VALID_TP[:-1],
        "01-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
        "00-" + "00" * 16 + "-" + "cd" * 8 + "-01",
    ])
    def test_frame_garbage_traceparent_degrades_to_local_root(
            self, served, hostile):
        srv, net = served
        obs.enable()
        (frames,) = _frame_exchange(net.port, [
            {"job": "answer", "tenant": "t", "traceparent": hostile}])
        end = frames[-1]
        assert end["status"] == "ok"
        # degraded = locally-minted root: an echoed trace id that is NOT
        # the hostile value's id, and resolvable server-side
        assert len(end["trace_id"]) == 32
        assert end["trace_id"] != "ab" * 16
        assert _lookup_soon(end["trace_id"])

    def test_http_garbage_traceparent_degrades_not_500(self, served):
        srv, net = served
        obs.enable()
        body = json.dumps({"job": "answer", "tenant": "t"}).encode()
        req = (b"POST /query HTTP/1.1\r\nHost: dq\r\n"
               b"traceparent: total nonsense value here\r\n"
               b"Content-Type: application/json\r\n"
               b"Content-Length: " + str(len(body)).encode() +
               b"\r\nConnection: close\r\n\r\n" + body)
        with socket.create_connection(("127.0.0.1", net.port),
                                      timeout=10) as s:
            s.sendall(req)
            raw = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                raw += chunk
        status = int(raw.split(b" ", 2)[1])
        assert status == 200
        assert b'"trace_id"' in raw

    def test_http_hostile_1mb_header_is_bounded_never_hangs(self):
        """A 1 MB traceparent header against a small maxFrameBytes is
        refused with a structured 413 inside the connection timeout —
        the length bound fires before any parse work."""
        srv = QueryServer(workers=1).start()
        net = NetServer(srv, host="127.0.0.1", port=0,
                        conn_timeout_s=5.0,
                        max_frame_bytes=64 * 1024).start()
        srv.net = net
        obs.enable()
        try:
            req = (b"POST /query HTTP/1.1\r\nHost: dq\r\n"
                   b"traceparent: " + b"x" * (1 << 20) + b"\r\n"
                   b"Content-Length: 2\r\n\r\n{}")
            t0 = time.monotonic()
            raw = b""
            reset = False
            try:
                with socket.create_connection(
                        ("127.0.0.1", net.port), timeout=15) as s:
                    s.sendall(req)
                    while True:
                        chunk = s.recv(65536)
                        if not chunk:
                            break
                        raw += chunk
            except ConnectionResetError:
                # the server 413s and closes with ~1 MB unread in its
                # receive buffer; that close is a TCP RST which may
                # clobber the response in flight — a prompt reset is
                # still a bounded refusal, not a hang
                reset = True
            took = time.monotonic() - t0
            if not reset:
                assert int(raw.split(b" ", 2)[1]) == 413
            assert took < 10.0, f"hostile header stalled {took:.1f}s"
        finally:
            srv.stop()

    def test_absent_traceparent_still_minted_and_echoed(self, served):
        srv, net = served
        obs.enable()
        (frames,) = _frame_exchange(net.port,
                                    [{"job": "answer", "tenant": "t"}])
        assert len(frames[-1]["trace_id"]) == 32

    def test_valid_traceparent_adopted_verbatim(self, served):
        srv, net = served
        obs.enable()
        (frames,) = _frame_exchange(net.port, [
            {"job": "answer", "tenant": "t", "traceparent": VALID_TP}])
        assert frames[-1]["trace_id"] == "ab" * 16
        (tree,) = _lookup_soon("ab" * 16)
        root = [s for s in tree["spans"]
                if s["name"] == "serve.query"][0]
        assert root["attrs"]["wire_trace_id"] == "ab" * 16
        assert root["attrs"]["wire_parent_id"] == "cd" * 8
        assert root["attrs"]["remote"] is True


# ---------------------------------------------------------------------------
# client propagation: one trace id per logical query, joinable results
# ---------------------------------------------------------------------------

class TestClientPropagation:
    def test_client_result_joins_server_tree(self, served):
        srv, net = served
        obs.enable()
        with ResilientClient("127.0.0.1", net.port,
                             transport="frame") as c:
            r = c.call_job("answer")
        assert r.ok and len(r.trace_id) == 32
        (tree,) = _lookup_soon(r.trace_id)
        names = {s["name"] for s in tree["spans"]}
        assert {"serve.query", "serve.admit",
                "serve.queue"} <= names

    def test_both_transports_carry_the_same_contract(self, served):
        srv, net = served
        obs.enable()
        for transport in ("frame", "http"):
            with ResilientClient("127.0.0.1", net.port,
                                 transport=transport) as c:
                r = c.call_job("answer")
            assert r.ok and r.trace_id, transport
            assert _lookup_soon(r.trace_id), transport

    def test_retries_share_trace_id_with_fresh_attempt_span(self):
        """Each wire attempt re-stamps a fresh child span id under the
        SAME trace id — observed through the per-attempt doc."""
        obs.enable()
        from sparkdq4ml_tpu.serve import client as client_mod

        c = ResilientClient("127.0.0.1", 1, transport="frame")
        seen = []

        def fake_attempt(doc, attempt, remaining):
            seen.append(doc.get("traceparent"))
            if len(seen) < 3:
                raise client_mod.WireError("induced")
            from sparkdq4ml_tpu.serve.client import ClientResult
            return ClientResult(status="ok", tenant="t")

        c._hedged_attempt = fake_attempt
        r = c._run({"job": "x"}, tenant="t", deadline_s=None, tag=None)
        assert r.ok and r.trace_id
        assert len(seen) == 3 and all(seen)
        parsed = [obs.TraceContext.parse(tp) for tp in seen]
        assert len({p.trace_id for p in parsed}) == 1
        assert len({p.parent_id for p in parsed}) == 3
        assert parsed[0].trace_id == r.trace_id

    def test_client_synthesized_results_carry_trace_id(self):
        obs.enable()
        from sparkdq4ml_tpu.utils.recovery import RetryPolicy

        c = ResilientClient(
            "127.0.0.1", 1, transport="frame",
            policy=RetryPolicy(max_attempts=1, backoff_base=0.001))
        r = c.query("SELECT 1")     # nothing listens on port 1
        assert r.status == "error" and r.reason == "net_exhausted"
        assert r.trace_id and len(r.trace_id) == 32

    def test_hedge_doc_restamps_span_id_only(self):
        obs.enable()
        ctx = obs.TraceContext.mint()
        doc = {"job": "x", "traceparent": ctx.child_traceparent()}
        hedged = ResilientClient._hedge_doc(doc)
        p0 = obs.TraceContext.parse(doc["traceparent"])
        p1 = obs.TraceContext.parse(hedged["traceparent"])
        assert p1.trace_id == p0.trace_id == ctx.trace_id
        assert p1.parent_id != p0.parent_id
        # without a traceparent the doc passes through untouched
        assert ResilientClient._hedge_doc({"job": "x"}) == {"job": "x"}


# ---------------------------------------------------------------------------
# tail-based sampling: keep-policy, ring bounds, lookup
# ---------------------------------------------------------------------------

class TestTailSampling:
    def test_healthy_tree_rings_but_is_not_retained(self, served):
        srv, net = served
        obs.enable()
        with ResilientClient("127.0.0.1", net.port,
                             transport="frame") as c:
            r = c.call_job("answer")
        (doc,) = _lookup_soon(r.trace_id)
        assert doc["kept"] is False and doc["keep_reasons"] == []
        assert r.trace_id not in obs.TAIL.retained_ids()

    def test_error_tree_is_kept_and_counted(self, served):
        srv, net = served
        obs.enable()
        with ResilientClient("127.0.0.1", net.port,
                             transport="frame") as c:
            r = c.call_job("boom")
        assert r.status == "error"
        (doc,) = _lookup_soon(r.trace_id)
        assert doc["kept"] and "error" in doc["keep_reasons"]
        assert r.trace_id in obs.TAIL.retained_ids()
        assert profiling.counters.snapshot().get("trace.kept", 0) >= 1

    def test_deadline_tree_is_kept(self, served):
        srv, net = served
        obs.enable()
        slow = threading.Event()
        net.register_job("slow", lambda ctx: slow.wait(2.0))
        from sparkdq4ml_tpu.utils.recovery import RetryPolicy

        with ResilientClient(
                "127.0.0.1", net.port, transport="frame",
                policy=RetryPolicy(max_attempts=1)) as c:
            r = c.call_job("slow", deadline_s=0.15)
        slow.set()
        assert r.status == "deadline_exceeded"
        assert r.trace_id
        deadline_kept = [
            d for d in _lookup_soon(r.trace_id) if d["kept"]]
        assert deadline_kept, "deadline verdict must promote the tree"
        assert any("deadline_exceeded" in d["keep_reasons"]
                   for d in deadline_kept)

    def test_slow_tree_kept_when_over_slo(self):
        obs.enable()
        obs.TAIL.configure(ring_size=8, retained_size=8)
        ctx = obs.TraceContext.mint()
        with obs.request_span("serve.query", ctx, tenant="t"):
            pass
        obs.TAIL.finish_request(ctx, status="ok", reason="",
                                e2e_ms=500.0, breaker_opened=False,
                                slo_ms=100.0)
        (doc,) = obs.TAIL.lookup(ctx.trace_id)
        assert doc["kept"] and doc["keep_reasons"] == ["slow"]

    def test_recovery_fault_annotation_keeps_tree(self):
        obs.enable()
        ctx = obs.TraceContext.mint()
        with obs.request_span("serve.query", ctx, tenant="t") as root:
            root.attrs["recovery_fault"] = "serve_exec:device_error"
        obs.TAIL.finish_request(ctx, status="ok", reason="",
                                e2e_ms=1.0, breaker_opened=False,
                                slo_ms=None)
        (doc,) = obs.TAIL.lookup(ctx.trace_id)
        assert doc["kept"] and doc["keep_reasons"] == ["recovery_fault"]

    def test_ring_is_bounded_and_drops_are_counted(self):
        obs.enable()
        obs.TAIL.configure(ring_size=4, retained_size=4)
        for _ in range(10):
            ctx = obs.TraceContext.mint()
            with obs.request_span("serve.query", ctx):
                pass
            obs.TAIL.finish_request(ctx, status="ok", reason="",
                                    e2e_ms=1.0, breaker_opened=False,
                                    slo_ms=None)
        assert len(obs.TAIL.recent(limit=100)) == 4
        assert profiling.counters.snapshot().get("trace.dropped", 0) == 6

    def test_requeued_attempt_merges_into_one_tree(self):
        """Re-rooting the same context (the serve requeue ladder) carries
        the earlier attempt's spans into the new bucket."""
        obs.enable()
        ctx = obs.TraceContext.mint()
        with obs.request_span("serve.query", ctx, attempt=1):
            pass
        with obs.request_span("serve.query", ctx, attempt=2):
            pass
        obs.TAIL.finish_request(ctx, status="error", reason="",
                                e2e_ms=1.0, breaker_opened=False,
                                slo_ms=None)
        (doc,) = obs.TAIL.lookup(ctx.trace_id)
        roots = [s for s in doc["spans"] if s["name"] == "serve.query"]
        assert len(roots) == 2
        assert {r["attrs"]["attempt"] for r in roots} == {1, 2}

    def test_lookup_unknown_id_is_empty(self):
        obs.enable()
        assert obs.TAIL.lookup("ff" * 16) == []


# ---------------------------------------------------------------------------
# incident flight recorder
# ---------------------------------------------------------------------------

class TestIncidentRecorder:
    def test_bundle_written_atomically_and_loadable(self, tmp_path):
        obs.enable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     cooldown_s=0.0)
        ctx = obs.TraceContext.mint()
        with obs.request_span("serve.query", ctx):
            pass
        obs.TAIL.finish_request(ctx, status="error", reason="",
                                e2e_ms=1.0, breaker_opened=True,
                                slo_ms=None)
        iid = incidents.RECORDER.record("breaker_trip", trace=ctx,
                                        detail="test")
        assert iid is not None
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".json")]
        assert files == [f"{iid}.json"]
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".tmp")]
        with open(tmp_path / files[0]) as f:
            bundle = json.load(f)
        assert bundle["trigger"] == "breaker_trip"
        assert bundle["trace_id"] == ctx.trace_id
        assert bundle["trace_trees"], "joined span tree must ride along"
        assert "recovery" in bundle and "metrics_delta" in bundle
        assert incidents.RECORDER.get(iid) == bundle
        assert profiling.counters.snapshot().get("incident.written", 0) == 1

    def test_retention_prunes_oldest(self, tmp_path):
        obs.enable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     max_bundles=3, cooldown_s=0.0)
        ids = [incidents.RECORDER.record("slo_burn", detail=str(i))
               for i in range(6)]
        assert all(ids)
        files = sorted(f for f in os.listdir(tmp_path)
                       if f.endswith(".json"))
        assert len(files) == 3
        assert f"{ids[-1]}.json" in files

    def test_cooldown_suppresses_repeat_triggers(self, tmp_path):
        obs.enable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     cooldown_s=60.0)
        assert incidents.RECORDER.record("slo_burn") is not None
        assert incidents.RECORDER.record("slo_burn") is None
        # a DIFFERENT trigger is not suppressed
        assert incidents.RECORDER.record("breaker_trip") is not None

    def test_io_fault_degrades_to_memory_then_disables_disk(
            self, tmp_path):
        obs.enable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     cooldown_s=0.0)
        faults.install_plan(faults.parse_plan("incident:io_error:p=1"))
        ids = [incidents.RECORDER.record("slo_burn", detail=str(i))
               for i in range(4)]
        assert all(ids)
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".json")]
        assert profiling.counters.snapshot().get("incident.failed", 0) >= 3
        rep = incidents.RECORDER.report()
        assert rep["disk_disabled"] and rep["in_memory"] == 4
        # bundles are still retrievable from the memory rung
        assert incidents.RECORDER.get(ids[0])["trigger"] == "slo_burn"
        events = [e for e in RECOVERY_LOG.events()
                  if e.site == "incident"]
        assert events and events[-1].rung == "disabled"
        faults.clear()
        # the ladder is terminal for the recorder's lifetime until
        # reconfigured with a directory (which resets the rung)
        incidents.RECORDER.record("slo_burn", detail="post")
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".json")]

    def test_inactive_recorder_records_nothing(self, tmp_path):
        # tracing on but recorder not opted in
        obs.enable()
        assert incidents.RECORDER.record("breaker_trip") is None
        # recorder opted in but tracing off
        obs.disable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     cooldown_s=0.0)
        assert incidents.RECORDER.record("breaker_trip") is None
        assert not os.listdir(tmp_path)

    def test_breaker_trip_through_serving_stack(self, tmp_path):
        """Consecutive failures past the breaker threshold fire ONE
        breaker_trip incident with the tripping request's trace id."""
        obs.enable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     cooldown_s=0.0)
        srv = QueryServer(workers=1, breaker_threshold=3,
                          breaker_cooldown=30.0).start()
        net = NetServer(srv, host="127.0.0.1", port=0,
                        conn_timeout_s=2.0).start()
        srv.net = net
        net.register_job("boom", _raise_value_error)
        try:
            with ResilientClient("127.0.0.1", net.port,
                                 transport="frame") as c:
                for _ in range(3):
                    r = c.call_job("boom")
                    assert r.status == "error"
        finally:
            srv.stop()
        rows = [r for r in incidents.RECORDER.list()
                if r.get("trigger") == "breaker_trip"]
        assert len(rows) == 1
        bundle = incidents.RECORDER.get(rows[0]["id"])
        assert bundle["trace_id"] and bundle["trace_trees"]
        assert bundle["breaker"], "breaker snapshot rides along"


# ---------------------------------------------------------------------------
# telemetry surfaces: /trace filter, /trace/<id>, /incidents, exemplars
# ---------------------------------------------------------------------------

class TestTelemetrySurfaces:
    @pytest.fixture
    def telemetry(self):
        from sparkdq4ml_tpu.serve.http import TelemetryServer

        t = TelemetryServer(None, port=0).start()
        yield t
        t.stop()

    @staticmethod
    def _get(port, path):
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    def _one_tree(self, status="error"):
        ctx = obs.TraceContext.mint()
        with obs.request_span("serve.query", ctx, tenant="t"):
            pass
        obs.TAIL.finish_request(ctx, status=status, reason="",
                                e2e_ms=3.0, breaker_opened=False,
                                slo_ms=None)
        return ctx

    def test_trace_route_filters_by_trace_id_and_limit(self, telemetry):
        obs.enable()
        ctx = self._one_tree()
        self._one_tree()
        code, doc = self._get(telemetry.port,
                              f"/trace?trace_id={ctx.trace_id}")
        assert code == 200
        assert doc["spans"], "filter must match the wire trace id"
        assert all(s["attrs"].get("wire_trace_id") == ctx.trace_id
                   for s in doc["spans"])
        code, doc = self._get(telemetry.port, "/trace?limit=1")
        assert code == 200 and len(doc["spans"]) == 1
        # a bogus limit falls back to the default bound, not a 500
        code, _ = self._get(telemetry.port, "/trace?limit=bogus")
        assert code == 200

    def test_trace_tree_route_and_404(self, telemetry):
        obs.enable()
        ctx = self._one_tree()
        code, doc = self._get(telemetry.port, f"/trace/{ctx.trace_id}")
        assert code == 200
        assert doc["trace_id"] == ctx.trace_id
        assert doc["trees"][0]["kept"]
        code, _ = self._get(telemetry.port, "/trace/" + "ee" * 16)
        assert code == 404

    def test_incidents_routes(self, telemetry, tmp_path):
        obs.enable()
        incidents.RECORDER.configure(directory=str(tmp_path),
                                     cooldown_s=0.0)
        iid = incidents.RECORDER.record("fault_ladder", detail="t")
        code, doc = self._get(telemetry.port, "/incidents")
        assert code == 200
        assert [r["id"] for r in doc["incidents"]] == [iid]
        code, bundle = self._get(telemetry.port, f"/incidents/{iid}")
        assert code == 200 and bundle["id"] == iid
        code, _ = self._get(telemetry.port, "/incidents/inc-nope")
        assert code == 404

    def test_exemplars_only_behind_conf_flag(self):
        obs.enable()
        ctx = self._one_tree()        # kept → exemplar registered
        assert obs.TAIL.exemplars("serve.e2e_ms")
        obs.METRICS.observe("serve.e2e_ms", 3.0)
        saved = config.trace_exemplars
        try:
            config.trace_exemplars = False
            assert "# {trace_id=" not in obs.prometheus_text()
            config.trace_exemplars = True
            text = obs.prometheus_text()
            assert f'# {{trace_id="{ctx.trace_id}"}}' in text
        finally:
            config.trace_exemplars = saved


# ---------------------------------------------------------------------------
# disabled mode: byte-identical wire + one-flag-read no-op
# ---------------------------------------------------------------------------

class TestDisabledMode:
    def test_wire_frames_byte_identical_and_hooks_never_run(
            self, served, monkeypatch):
        """With observability off, NO tracing hook may execute (pinned
        by raising from all of them) and the wire docs must not grow a
        traceparent/trace_id key."""
        srv, net = served
        assert not obs.TRACER.enabled

        def boom(*a, **k):
            raise AssertionError("tracing hook ran while disabled")

        monkeypatch.setattr(obs.TraceContext, "mint",
                            classmethod(boom))
        monkeypatch.setattr(obs.TraceContext, "adopt",
                            classmethod(boom))
        monkeypatch.setattr(obs.TAIL, "open_request", boom)
        monkeypatch.setattr(obs.TAIL, "finish_request", boom)
        monkeypatch.setattr(obs.TAIL, "complete", boom)
        monkeypatch.setattr(incidents.RECORDER, "record", boom)
        (frames,) = _frame_exchange(net.port,
                                    [{"job": "answer", "tenant": "t"}])
        end = frames[-1]
        assert end["status"] == "ok"
        assert "trace_id" not in end
        with ResilientClient("127.0.0.1", net.port,
                             transport="http") as c:
            r = c.call_job("answer")
        assert r.ok and r.trace_id is None

    def test_request_span_is_shared_noop_when_disabled(self):
        assert obs.request_span("x", obs.TraceContext("a" * 32)) \
            is obs._NOOP
        obs.enable()
        assert obs.request_span("x", None) is obs._NOOP

    def test_emit_span_noop_when_disabled(self):
        obs.emit_span("x", dur_ms=5.0)      # must not raise or record
        assert obs.TRACER.spans() == []


# ---------------------------------------------------------------------------
# conf vocabulary: session-scoped save/restore
# ---------------------------------------------------------------------------

class TestTracingConf:
    def test_trace_and_incident_conf_applied_and_restored(
            self, tmp_path):
        import sparkdq4ml_tpu as dq

        before = (config.trace_ring_size, config.trace_retained_size,
                  config.trace_exemplars, config.incident_enabled,
                  config.incident_dir, config.incident_max_bundles,
                  config.incident_cooldown_s,
                  config.incident_slo_burn_threshold)
        s = (dq.TpuSession.builder()
             .config("spark.trace.ringSize", 99)
             .config("spark.trace.retainedSize", 11)
             .config("spark.trace.exemplars", "true")
             .config("spark.incident.enabled", "true")
             .config("spark.incident.dir", str(tmp_path))
             .config("spark.incident.maxBundles", 5)
             .config("spark.incident.cooldownS", 0.5)
             .config("spark.incident.sloBurnThreshold", 3.0)
             .get_or_create())
        try:
            assert config.trace_ring_size == 99
            assert config.trace_retained_size == 11
            assert config.trace_exemplars is True
            assert config.incident_enabled is True
            assert config.incident_dir == str(tmp_path)
            assert config.incident_max_bundles == 5
            assert config.incident_cooldown_s == 0.5
            assert config.incident_slo_burn_threshold == 3.0
            # and the process-global instances picked the bounds up
            assert obs.TAIL.ring_size == 99
            assert obs.TAIL.retained_size == 11
            assert incidents.RECORDER.directory == str(tmp_path)
            assert incidents.RECORDER.max_bundles == 5
        finally:
            s.stop()
        after = (config.trace_ring_size, config.trace_retained_size,
                 config.trace_exemplars, config.incident_enabled,
                 config.incident_dir, config.incident_max_bundles,
                 config.incident_cooldown_s,
                 config.incident_slo_burn_threshold)
        assert after == before

    def test_incident_report_shape(self, tmp_path):
        import sparkdq4ml_tpu as dq

        s = (dq.TpuSession.builder()
             .config("spark.observability.enabled", "true")
             .config("spark.incident.dir", str(tmp_path))
             .config("spark.incident.cooldownS", 0)
             .get_or_create())
        try:
            iid = incidents.RECORDER.record("slo_burn", detail="rpt")
            rep = s.incident_report()
            assert rep["active"] and rep["dir"] == str(tmp_path)
            assert [r["id"] for r in rep["incidents"]] == [iid]
            assert "tail" in rep and "ring_size" in rep["tail"]
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# Grouped execution's spans, scopes and counters (ISSUE 28)
# ---------------------------------------------------------------------------

class TestGroupedExecutionNames:
    """``frame.grouped.flush`` names the tier that ran, the grouped program
    carries its three inner scopes, and the grouped verdict and the sort
    pull are counted host reads."""

    @pytest.fixture
    def grouped_view(self, session):
        import jax.numpy as jnp
        import numpy as np

        from sparkdq4ml_tpu.ops import segments

        segments.clear_cache()
        frame = session.create_data_frame(
            {"k": jnp.asarray(np.arange(4_000) % 5, jnp.int32),
             "wide": jnp.asarray(np.arange(4_000) % 900, jnp.int32),
             "v": jnp.arange(4_000.0)})
        frame.create_or_replace_temp_view("g")
        yield session
        segments.clear_cache()

    def _flushes(self, session, query):
        obs.enable()
        before = profiling.counters.snapshot()
        session.sql(query).to_pydict()
        moved = {k: v - before.get(k, 0)
                 for k, v in profiling.counters.snapshot().items()
                 if v != before.get(k, 0)}
        spans = [s for s in obs.TRACER.spans()
                 if s.name == "frame.grouped.flush"]
        obs.disable()
        return spans, moved

    def test_flush_span_names_the_tile_tier(self, grouped_view):
        spans, moved = self._flushes(
            grouped_view, "SELECT k, sum(v) AS s, count(*) AS n FROM g "
                          "GROUP BY k")
        (span,) = [s for s in spans if s.attrs["op"] == "group_by"]
        assert span.attrs["lowering"] == "dense-tile"
        assert span.attrs["groups"] == 5 and span.attrs["rows"] == 4_000
        assert span.attrs["blocks"] == 1
        assert moved["grouped.tile"] == 1
        assert moved["grouped.rows"] == 4_096           # the plan's bucket
        assert "grouped.fallback" not in moved

    def test_flush_span_names_the_scatter_tier_above_the_tile(
            self, grouped_view):
        spans, moved = self._flushes(
            grouped_view, "SELECT wide, sum(v) AS s FROM g GROUP BY wide")
        (span,) = [s for s in spans if s.attrs["op"] == "group_by"]
        assert span.attrs["lowering"] == "dense"
        assert span.attrs["groups"] == 900 and "blocks" not in span.attrs
        assert "grouped.tile" not in moved
        assert moved["grouped.rows"] == 4_096

    def test_grouped_program_carries_its_three_scopes(self, grouped_view):
        import jax

        from sparkdq4ml_tpu.ops import segments

        grouped_view.sql("SELECT k, avg(v) AS m FROM g GROUP BY k") \
            .to_pydict()
        (h,) = [h for h in segments.program_handles()]
        text = jax.jit(h.fn).lower(*h.args, **h.kwargs).as_text(
            debug_info=True)
        assert "dq.grouped/dq.grouped.slots/" in text
        # the tier is chosen inside the program: each branch of the cond
        # reduces and compacts under its own scopes
        for branch in ("branch_0_fun", "branch_1_fun"):
            for scope in ("grouped.reduce", "grouped.compact"):
                assert f"dq.grouped/cond/{branch}/dq.{scope}/" in text, \
                    (branch, scope)

    def test_new_counters_are_declared(self):
        assert obs.METRIC_NAMES["grouped.rows"][0] == "counter"
        assert obs.METRIC_NAMES["grouped.tile"][0] == "counter"

    def test_grouped_verdict_and_sort_pull_are_counted_reads(
            self, grouped_view):
        import jax.numpy as jnp

        frame = grouped_view.table("g")
        profiling.counters.clear()
        out = frame.group_by("k").agg({"v": "sum"})
        # the verdict: fit, group count and tier, three scalars in one pull
        assert profiling.counters.get("host.reads") == 1
        assert profiling.counters.get("frame.host_sync") == 1
        assert 0 < profiling.counters.get("host.read_bytes") <= 3 * 8
        verdict = profiling.counters.get("host.read_bytes")
        out = out.sort("k")
        assert profiling.counters.get("host.reads") == 2
        assert profiling.counters.get("frame.host_sync") == 2
        # on the CPU backend the sort plans on the host from the key and
        # the mask: 5 keys and 5 flags
        pulled = profiling.counters.get("host.read_bytes") - verdict
        key_bytes = jnp.asarray(out.to_pydict()["k"]).dtype.itemsize
        assert pulled == 5 * key_bytes + 5
