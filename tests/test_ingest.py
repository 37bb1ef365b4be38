"""Streaming ingest (native/csvparse.cpp + frame/native_csv.py) — ISSUE 7.

Covers the acceptance surface of the streaming-ingest tentpole:

* streaming-vs-whole-file BIT parity across thread counts × chunk sizes
  × SIMD tiers × prefetch depths (same dtypes, same bytes — chunked
  conversion uses the same elementwise astype as the one-shot read),
* chunk-split correctness hardening: quoted fields containing newlines
  are never torn by the chunk splitter — a mid-quote boundary resyncs on
  a structural newline, so the file falls back to the python engine as a
  WHOLE (clean `None`) instead of parsing torn half-records as data,
* ragged rows, blank lines, trailing separators/EOF shapes,
* golden DQ counts (24 abstract / 1024 full) + RMSE 2.810/1.805 driven
  through the streaming reader with chunks small enough to truly stream,
* the 64 KiB header sniff surviving a probe boundary that splits a
  multibyte UTF-8 character (cut at the last record separator),
* host-sync pinning (ingest is host→device only: zero `frame.host_sync`),
* `spark.ingest.streaming=false` = the exact legacy one-shot path (v1
  ABI, no ingest telemetry), session-scoped conf save/restore,
* `ingest.*` counters + the `frame.ingest` span contract,
* the native-build gate (scripts/check_native_build.py — rebuild, smoke,
  runtime-dispatch clamp; SKIPs cleanly without a C++ toolchain).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import dataset_path, prepare_features, run_dq_pipeline

pytestmark = pytest.mark.ingest

from sparkdq4ml_tpu.config import config
from sparkdq4ml_tpu.frame import native_csv
from sparkdq4ml_tpu.frame.csv import read_csv
from sparkdq4ml_tpu.utils.profiling import counters

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

needs_native = pytest.mark.skipif(
    not native_csv.available(), reason="native/libdqcsv.so not built")
needs_streaming = needs_native

_INGEST_DEFAULTS = ("ingest_streaming", "ingest_threads",
                    "ingest_chunk_bytes", "ingest_prefetch", "ingest_simd")


@pytest.fixture(autouse=True)
def _fresh_ingest_conf():
    saved = {k: getattr(config, k) for k in _INGEST_DEFAULTS}
    counters.clear("ingest")
    counters.clear("frame.")
    yield
    for k, v in saved.items():
        setattr(config, k, v)


def _set(streaming=True, threads=0, chunk_bytes=8 << 20, prefetch=2,
         simd="auto"):
    config.ingest_streaming = streaming
    config.ingest_threads = threads
    config.ingest_chunk_bytes = chunk_bytes
    config.ingest_prefetch = prefetch
    config.ingest_simd = simd


def _assert_bit_equal(a, b):
    assert a.columns == b.columns
    for c in a.columns:
        x, y = np.asarray(a._data[c]), np.asarray(b._data[c])
        assert x.dtype == y.dtype, (c, x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y, err_msg=c)


def _mixed_text(n, seed=7):
    """All-numeric CSV exercising every conversion path: short bare
    digits (the SIMD word kernel), fractions, signs, exponents, > 7-digit
    mantissas (scalar fallback), empty fields, padded fields."""
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        a = rng.integers(0, 10_000)
        b = round(rng.uniform(-120.0, 120.0), rng.integers(0, 5))
        c = f"{rng.uniform(1e-8, 1e8):.10g}" if i % 7 else ""
        d = ("-12345678901.25", " 42 ", "+7.5", "9e2",
             "0.00003")[i % 5]
        lines.append(f"{a},{b},{c},{d}")
    return "\n".join(lines) + "\n"


@pytest.fixture(scope="module")
def mixed_csv(tmp_path_factory):
    p = tmp_path_factory.mktemp("ingest") / "mixed.csv"
    p.write_text(_mixed_text(4000))
    return str(p)


@pytest.fixture(scope="module")
def mixed_reference(mixed_csv):
    """One-shot scalar single-thread parse — the parity reference."""
    saved = {k: getattr(config, k) for k in _INGEST_DEFAULTS}
    _set(streaming=True, threads=1,
         chunk_bytes=os.path.getsize(mixed_csv) + 1, simd="off")
    try:
        return read_csv(mixed_csv, engine="native")
    finally:
        for k, v in saved.items():
            setattr(config, k, v)


# ---------------------------------------------------------------------------
# Streaming-vs-whole-file bit parity across the conf grid
# ---------------------------------------------------------------------------

@needs_streaming
@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("chunk_bytes", [1024, 16384])
@pytest.mark.parametrize("simd", ["off", "auto"])
def test_stream_parity_grid(mixed_csv, mixed_reference, threads,
                            chunk_bytes, simd):
    _set(streaming=True, threads=threads, chunk_bytes=chunk_bytes,
         simd=simd)
    streamed = read_csv(mixed_csv, engine="native")
    assert counters.get("ingest.chunks") > 1  # genuinely streamed
    _assert_bit_equal(streamed, mixed_reference)


@needs_streaming
@pytest.mark.parametrize("prefetch", [0, 1, 4])
def test_prefetch_depth_parity(mixed_csv, mixed_reference, prefetch):
    # depth 0 = synchronous (no producer thread); >0 = bounded queue
    _set(chunk_bytes=4096, prefetch=prefetch)
    _assert_bit_equal(read_csv(mixed_csv, engine="native"),
                      mixed_reference)


@needs_streaming
def test_oneshot_v2_matches_stream(mixed_csv, mixed_reference):
    # a file smaller than one chunk takes the one-shot v2 call under the
    # same conf surface — still bit-identical
    _set(chunk_bytes=os.path.getsize(mixed_csv) + 1)
    whole = read_csv(mixed_csv, engine="native")
    assert counters.get("ingest.streamed") == 0
    _assert_bit_equal(whole, mixed_reference)


@needs_streaming
@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("breaker", ["2.5", ""])
@pytest.mark.parametrize("break_at", ["first", "mid", "late"])
def test_late_integrality_break_backfill(tmp_path, threads, breaker,
                                         break_at):
    # The bind-mode sink writes an integral column i32-only and backfills
    # the float lane when integrality breaks (native SinkTyped /
    # bind_chunk_lane). Exercise every backfill site: break on the first
    # record (prologue), deep inside one parallel piece (inline prefix
    # backfill), and chunks after the column ran integral for whole PRIOR
    # chunks (cross-chunk [0, row0) repair + alive sibling pieces) — for
    # both a fractional breaker and an empty field (NaN). Results must be
    # bit-identical to the one-shot scalar parse, float dtype included.
    n = 6000
    k = {"first": 0, "mid": n // 2, "late": n - 3}[break_at]
    lines = [f"{i % 97},{breaker if i == k else 3}" for i in range(n)]
    p = tmp_path / f"break_{break_at}.csv"
    p.write_text("\n".join(lines) + "\n")
    _set(streaming=True, threads=1, chunk_bytes=os.path.getsize(p) + 1,
         simd="off")
    ref = read_csv(str(p), engine="native")
    for chunk_bytes in (1024, os.path.getsize(p) // 3):
        _set(streaming=True, threads=threads, chunk_bytes=chunk_bytes,
             simd="auto")
        streamed = read_csv(str(p), engine="native")
        assert counters.get("ingest.chunks") > 1
        counters.clear("ingest")
        _assert_bit_equal(streamed, ref)
        assert np.asarray(streamed._data["_c0"]).dtype.kind == "i"
        assert np.asarray(streamed._data["_c1"]).dtype.kind == "f"


@needs_streaming
@pytest.mark.parametrize("break_at", ["first", "mid", "late"])
def test_accelerator_chunk_ship_path(tmp_path, monkeypatch, break_at):
    # The non-CPU branch of _stream_pinned ships a column's float rows
    # per chunk ONLY once its integral flag is dead (while alive, the
    # single-lane native protocol leaves the float lane unwritten — a
    # naive per-chunk snapshot would capture garbage). Simulate the
    # accelerator branch on the CPU device by patching the backend probe
    # and assert bit parity incl. the cross-chunk late-break repair.
    import jax

    n = 6000
    k = {"first": 0, "mid": n // 2, "late": n - 3}[break_at]
    lines = [f"{i % 97},{2.5 if i == k else 3}" for i in range(n)]
    p = tmp_path / "accel.csv"
    p.write_text("\n".join(lines) + "\n")
    _set(streaming=True, threads=1, chunk_bytes=os.path.getsize(p) + 1,
         simd="off")
    ref = read_csv(str(p), engine="native")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _set(streaming=True, threads=2, chunk_bytes=1024, simd="auto")
    streamed = read_csv(str(p), engine="native")
    assert counters.get("ingest.chunks") > 1
    _assert_bit_equal(streamed, ref)


@needs_streaming
def test_explicit_simd_tiers_clamp(mixed_csv, mixed_reference):
    # explicit avx2/avx512 requests clamp to the CPU ceiling and parse
    # bit-identically; nothing SIGILLs on lesser hardware
    for tier in ("avx2", "avx512"):
        _set(chunk_bytes=4096, simd=tier)
        _assert_bit_equal(read_csv(mixed_csv, engine="native"),
                          mixed_reference)
    assert native_csv.simd_level("off") in ("scalar", "unavailable")
    assert native_csv.simd_level("avx512") in (
        "scalar", "avx2", "avx512", "unavailable")


# ---------------------------------------------------------------------------
# Edge shapes: ragged rows, blank lines, trailing EOF forms
# ---------------------------------------------------------------------------

def _parity_all_paths(tmp_path, text, name="edge.csv"):
    """python engine vs native one-shot vs native streamed (tiny chunks):
    all three must agree on values (NaN == NaN) and row count."""
    p = tmp_path / name
    p.write_text(text)
    py = read_csv(str(p), engine="python")
    _set(streaming=False)
    legacy = read_csv(str(p), engine="native")
    _set(streaming=True, chunk_bytes=16)
    streamed = read_csv(str(p), engine="native")
    assert streamed.columns == legacy.columns == py.columns
    for c in py.columns:
        a = np.asarray(py._data[c], np.float64)
        b = np.asarray(legacy._data[c], np.float64)
        d = np.asarray(streamed._data[c], np.float64)
        np.testing.assert_array_equal(b, d, err_msg=c)  # native bit parity
        np.testing.assert_allclose(a, d, rtol=1e-12, equal_nan=True,
                                   err_msg=c)
    return streamed


@needs_streaming
def test_ragged_short_rows_nan_pad(tmp_path):
    f = _parity_all_paths(tmp_path,
                          "1,2,3\n4,5\n6\n7,8,9\n")
    assert f.count() == 4
    col = np.asarray(f._data["_c2"], np.float64)
    assert np.isnan(col[1]) and np.isnan(col[2])


@needs_streaming
def test_blank_lines_and_empty_trailing(tmp_path):
    f = _parity_all_paths(
        tmp_path, "1,2\n\n3,4\n   \n5,6\n\n\n")
    assert f.count() == 3


@needs_streaming
def test_unterminated_final_record(tmp_path):
    f = _parity_all_paths(tmp_path, "1,2\n3,4")
    assert f.count() == 2


@needs_streaming
def test_trailing_delimiter_at_eof(tmp_path):
    # "…3," with no newline: the implicit final empty field is a null
    f = _parity_all_paths(tmp_path, "1,2\n3,")
    assert f.count() == 2
    assert np.isnan(np.asarray(f._data["_c1"], np.float64)[1])


@needs_streaming
def test_crlf_and_bare_cr(tmp_path):
    f = _parity_all_paths(tmp_path, "1,2\r\n3,4\r5,6\r\n")
    assert f.count() == 3


# ---------------------------------------------------------------------------
# Chunk-split hardening: quoted fields containing newlines never tear
# ---------------------------------------------------------------------------

@needs_streaming
def test_quoted_numeric_fields_stream(tmp_path):
    # quoted NUMERIC fields (no embedded separators) stay on the native
    # path through the quoted serial chunk parser, bit-equal to one-shot
    text = "".join(f'"{i}",{i}.5\n' for i in range(500))
    f = _parity_all_paths(tmp_path, text, "quoted.csv")
    assert f.count() == 500
    assert counters.get("ingest.chunks") > 1


@needs_streaming
def test_quoted_newline_not_torn_by_chunk_split(tmp_path):
    # A quoted field with an embedded newline is non-numeric, so the
    # native engine must decline the WHOLE file (python fallback). The
    # regression this pins: a naive splitter that cuts at the embedded
    # newline hands the parser two torn half-records — '7,"88' parses as
    # a valid (7, 88) row — and the stream would return WRONG DATA
    # instead of falling back. The quote-parity resync makes every chunk
    # boundary structural, so the bad record stays whole and rejects.
    rows = [f"{i},{i * 2}" for i in range(50)]
    rows.insert(25, '7,"88\n99"')        # embedded newline inside quotes
    p = tmp_path / "qnl.csv"
    p.write_text("\n".join(rows) + "\n")
    for chunk in (16, 64, 256):          # boundaries land mid-quote
        _set(chunk_bytes=chunk)
        assert native_csv.try_read_csv(str(p), header=False,
                                       infer_schema=True,
                                       delimiter=",") is None
    # engine=auto lands on the python engine, the quoted record intact
    _set(chunk_bytes=16)
    f = read_csv(str(p), engine="auto")
    assert f.count() == 51
    d = f.to_pydict()
    assert d["_c0"][25] == 7
    assert d["_c1"][25] == "88\n99"      # one field, newline preserved


@needs_streaming
def test_quoted_newline_oneshot_also_declines(tmp_path):
    p = tmp_path / "qnl1.csv"
    p.write_text('1,"2\n3"\n4,5\n')
    _set(chunk_bytes=8 << 20)
    assert native_csv.try_read_csv(str(p), header=False,
                                   infer_schema=True,
                                   delimiter=",") is None


# ---------------------------------------------------------------------------
# Header sniff: 64 KiB probe boundary inside a multibyte character
# ---------------------------------------------------------------------------

def _multibyte_boundary_file(tmp_path):
    """File whose 64 KiB probe (bytes [0, 65536)) ends mid-character:
    a 2-byte UTF-8 é starts at byte 65535, so a whole-probe decode
    raises UnicodeDecodeError."""
    p = tmp_path / "mb.csv"
    header = b"a,b\n"
    filler = b"1,2\n" * 16382            # 4 + 65528 bytes
    prefix = header + filler + b"5,9"    # exactly 65535 bytes
    assert len(prefix) == 65535
    body = prefix + b"\xc3\xa9" * 4 + b"\n" + b"4,5\n" * 100
    assert body[65535] == 0xC3           # probe cuts between C3 and A9
    p.write_bytes(body)
    return str(p)


@needs_native
def test_sniff_multibyte_boundary_reads_header(tmp_path):
    path = _multibyte_boundary_file(tmp_path)
    # the old whole-probe decode raised UnicodeDecodeError here; the
    # cut-at-last-separator sniff reads the header cleanly
    names = native_csv._read_header_names(path, ",", '"')
    assert names == ["a", "b"]


@needs_native
def test_sniff_multibyte_boundary_end_to_end(tmp_path):
    # the é-row is non-numeric -> native declines -> python engine; no
    # UnicodeDecodeError anywhere on the way
    path = _multibyte_boundary_file(tmp_path)
    f = read_csv(path, header=True, engine="auto")
    assert f.columns == ["a", "b"]
    assert counters.get("ingest.python_fallback") == 1


@needs_native
def test_sniff_no_newline_in_probe_punts(tmp_path):
    # > 64 KiB single record: no separator inside the probe -> fail
    # closed (python engine), never a mis-sniffed header
    p = tmp_path / "long.csv"
    p.write_text("9" * 70000 + ",1\n2,3\n")
    assert native_csv._read_header_names(str(p), ",", '"') is None


# ---------------------------------------------------------------------------
# Goldens through the streaming reader
# ---------------------------------------------------------------------------

@needs_streaming
def test_golden_abstract_through_streaming(session):
    from sparkdq4ml_tpu.models import LinearRegression

    _set(chunk_bytes=64)                  # 320-byte file: ~5 chunks
    df = run_dq_pipeline(session, dataset_path("abstract"))
    assert counters.get("ingest.streamed") >= 1
    assert df.count() == 24
    model = (LinearRegression().setMaxIter(40).setRegParam(1)
             .setElasticNetParam(1)).fit(prepare_features(df))
    assert model.summary.root_mean_squared_error == pytest.approx(
        2.809940, abs=1e-4)


@needs_streaming
def test_golden_full_through_streaming(session):
    from sparkdq4ml_tpu.models import LinearRegression

    _set(chunk_bytes=512)                 # 9.4 KB file: ~19 chunks
    df = run_dq_pipeline(session, dataset_path("full"))
    assert counters.get("ingest.streamed") >= 1
    assert df.count() == 1024
    model = (LinearRegression().setMaxIter(40).setRegParam(1)
             .setElasticNetParam(1)).fit(prepare_features(df))
    assert model.summary.root_mean_squared_error == pytest.approx(
        1.805140, rel=1e-3)


# ---------------------------------------------------------------------------
# Telemetry contracts: counters, span, host-sync pinning, disabled mode
# ---------------------------------------------------------------------------

@needs_streaming
def test_host_sync_pinned_to_zero(mixed_csv):
    # ingest is host→device only; the streaming path must add ZERO
    # device→host syncs (the engine's standing frame.host_sync contract)
    _set(chunk_bytes=4096)
    before = counters.get("frame.host_sync")
    read_csv(mixed_csv, engine="native")
    assert counters.get("frame.host_sync") == before


@needs_streaming
def test_ingest_counters_stream(mixed_csv):
    _set(chunk_bytes=4096)
    read_csv(mixed_csv, engine="native")
    snap = counters.snapshot("ingest.")
    assert snap["ingest.files"] == 1
    assert snap["ingest.streamed"] == 1
    assert snap["ingest.bytes"] == os.path.getsize(mixed_csv)
    assert snap["ingest.rows"] == 4000
    assert snap["ingest.chunks"] > 1


@needs_streaming
def test_frame_ingest_span(mixed_csv):
    from sparkdq4ml_tpu.utils import observability as obs

    _set(chunk_bytes=4096)
    obs.enable()
    try:
        read_csv(mixed_csv, engine="native")
        spans = [s for s in obs.TRACER.spans()
                 if s.name == "frame.ingest"]
        assert spans
        sp = spans[-1]
        assert sp.attrs["mode"] == "stream"
        assert sp.attrs["bytes"] == os.path.getsize(mixed_csv)
        assert sp.attrs["rows"] == 4000
        assert sp.attrs["chunks"] > 1
        assert sp.attrs["simd"] in ("scalar", "avx2", "avx512")
        assert sp.attrs["gb_s"] > 0
    finally:
        obs.disable()
        obs.TRACER.clear()


@needs_streaming
def test_oneshot_span_mode(mixed_csv):
    from sparkdq4ml_tpu.utils import observability as obs

    _set(chunk_bytes=os.path.getsize(mixed_csv) + 1)
    obs.enable()
    try:
        read_csv(mixed_csv, engine="native")
        sp = [s for s in obs.TRACER.spans()
              if s.name == "frame.ingest"][-1]
        assert sp.attrs["mode"] == "oneshot"
        assert sp.attrs["chunks"] == 1
    finally:
        obs.disable()
        obs.TRACER.clear()


@needs_streaming
def test_disabled_mode_is_exact_legacy(mixed_csv, mixed_reference):
    # spark.ingest.streaming=false: the v1 ABI path — bit-identical
    # results, and NO ingest telemetry (the pre-streaming contract)
    _set(streaming=False)
    legacy = read_csv(mixed_csv, engine="native")
    _assert_bit_equal(legacy, mixed_reference)
    assert counters.snapshot("ingest.") == {}


def test_python_fallback_counter(tmp_path):
    if not native_csv.available():
        pytest.skip("native library not built")
    p = tmp_path / "strings.csv"
    p.write_text("x,hello\ny,world\n")
    read_csv(str(p), engine="auto")
    assert counters.get("ingest.python_fallback") == 1


# ---------------------------------------------------------------------------
# Session conf: spark.ingest.* save/restore scoping
# ---------------------------------------------------------------------------

@needs_streaming
def test_session_conf_scoping():
    from sparkdq4ml_tpu import TpuSession

    defaults = {k: getattr(config, k) for k in _INGEST_DEFAULTS}
    s = (TpuSession.builder().app_name("ingest-conf")
         .config("spark.ingest.streaming", "false")
         .config("spark.ingest.threads", "3")
         .config("spark.ingest.chunkBytes", str(1 << 20))
         .config("spark.ingest.prefetch", "5")
         .config("spark.ingest.simd", "off")
         .get_or_create())
    try:
        assert config.ingest_streaming is False
        assert config.ingest_threads == 3
        assert config.ingest_chunk_bytes == 1 << 20
        assert config.ingest_prefetch == 5
        assert config.ingest_simd == "off"
    finally:
        s.stop()
    for k, v in defaults.items():
        assert getattr(config, k) == v, k


@needs_streaming
def test_conf_boolean_vocabulary():
    from sparkdq4ml_tpu import TpuSession

    s = (TpuSession.builder().app_name("ingest-no")
         .config("spark.ingest.streaming", "no").get_or_create())
    try:
        assert config.ingest_streaming is False
    finally:
        s.stop()
    assert config.ingest_streaming is True


# ---------------------------------------------------------------------------
# The library is tied to this checkout's csvparse.cpp (build on first use)
# ---------------------------------------------------------------------------

class TestBuildOnFirstUse:
    @pytest.fixture
    def fake_native(self, tmp_path, monkeypatch):
        """A native/ directory with a source and an OLDER library, and a
        ``make`` that records its command line and touches the target."""
        src = tmp_path / "csvparse.cpp"
        so = tmp_path / "libdqcsv.so"
        src.write_text("// source")
        so.write_bytes(b"stale")
        os.utime(so, (1_000, 1_000))
        os.utime(src, (2_000, 2_000))
        monkeypatch.setattr(native_csv, "_NATIVE_DIR", str(tmp_path))
        monkeypatch.setattr(native_csv, "_SRC_PATH", str(src))
        monkeypatch.setattr(native_csv, "_SO_PATH", str(so))
        calls = []

        def fake_make(cmd, **kw):
            calls.append(cmd)
            if fake_make.rc == 0:
                so.write_bytes(b"rebuilt")
            return subprocess.CompletedProcess(cmd, fake_make.rc, "",
                                               "g++: boom")

        fake_make.rc = 0
        monkeypatch.setattr(subprocess, "run", fake_make)
        return so, calls, fake_make

    def test_rebuilt_when_source_is_newer_then_left_alone(self, fake_native):
        so, calls, _ = fake_native
        assert native_csv._build_if_stale() is True
        assert calls == [["make", "-C", os.path.dirname(str(so)),
                          "libdqcsv.so"]]
        assert so.read_bytes() == b"rebuilt"
        assert native_csv._build_if_stale() is True      # now current
        assert len(calls) == 1

    def test_missing_library_is_built(self, fake_native):
        so, calls, _ = fake_native
        so.unlink()
        assert native_csv._build_if_stale() is True
        assert len(calls) == 1 and so.exists()

    def test_toolchain_failure_raises_for_auto_and_native(self, fake_native,
                                                          monkeypatch):
        _, _, fake_make = fake_native
        fake_make.rc = 2
        monkeypatch.setattr(native_csv, "_LIB", None)
        monkeypatch.setattr(native_csv, "_LIB_TRIED", False)
        for engine in ("auto", "native"):
            with pytest.raises(native_csv.NativeBuildError, match="boom"):
                read_csv(dataset_path("small"), engine=engine,
                         infer_schema=True)

    def test_absent_library_under_auto_is_counted(self, monkeypatch):
        monkeypatch.setattr(native_csv, "_LIB", None)
        monkeypatch.setattr(native_csv, "_LIB_TRIED", True)
        frame = read_csv(dataset_path("small"), engine="auto",
                         infer_schema=True)
        assert frame.count() == 27
        assert counters.get("ingest.python_fallback") == 1


@needs_native
def test_streamed_frame_survives_the_next_read(tmp_path):
    # On the CPU backend device_put ADOPTS the 64-byte-aligned bind
    # buffers, so a finished read's columns alias them: handing such a
    # buffer back to the pool let the NEXT streamed read overwrite the
    # previous frame's data.
    def write(name, offset):
        p = tmp_path / name
        p.write_text("".join(f"{i % 97},{i + offset}.5\n"
                             for i in range(5000)))
        return str(p)

    a, b = write("a.csv", 0), write("b.csv", 100_000)
    _set(chunk_bytes=1024)
    with native_csv._POOL_LOCK:
        native_csv._POOL.clear()
    fa = read_csv(a, engine="native")
    fb = read_csv(b, engine="native")
    assert counters.get("ingest.streamed") == 2
    np.testing.assert_array_equal(np.asarray(fa._data["_c1"][:3]),
                                  [0.5, 1.5, 2.5])
    np.testing.assert_array_equal(np.asarray(fb._data["_c1"][:3]),
                                  [100000.5, 100001.5, 100002.5])


# ---------------------------------------------------------------------------
# CI gate: native rebuild + dispatch
# ---------------------------------------------------------------------------

def test_check_native_build_gate():
    # rebuilds libdqcsv.so from source in a temp dir, runs the C++ smoke
    # cross-check, and verifies runtime SIMD dispatch clamps; SKIPs
    # inside the script (exit 0) when no C++ toolchain exists
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_native_build.py")],
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert ("PASS" in p.stdout) or ("SKIP" in p.stdout)
