"""ctypes binding to the native C++ CSV tokenizer (``native/csvparse.cpp``).

Role: the data-loader fast path — the analogue of the Univocity parser inside
Spark's CSV source (SURVEY.md §2.2 "CSV reader"). The native tokenizer handles
the common all-numeric case (which is what feature matrices are), with or
without a header record (names are read host-side, the body is skipped
C-side); anything else returns ``None`` here and the pure-Python reader takes
over. The shared library is built on first use from the ``csvparse.cpp`` of
this checkout (see :func:`_load`), so the engine a read goes through never
depends on who built what beforehand.

Two native paths, selected by ``spark.ingest.*`` conf (see ``config``):

* **one-shot** — the whole file parses into column-major float64 in one
  call (the legacy contract; ``spark.ingest.streaming=false`` pins exactly
  this with the v1 ABI and auto tiers);
* **streaming** — files larger than one chunk (``spark.ingest.chunkBytes``)
  parse through the ``dq_stream`` API in bounded chunks cut on STRUCTURAL
  record boundaries (quote-parity aware, so a quoted field containing
  newlines is never torn). A producer thread runs the native parse (the
  ctypes call releases the GIL) up to ``spark.ingest.prefetch`` chunks
  ahead of the consumer, which converts each chunk's columns and hands
  them to JAX — parse of chunk N+1 overlaps the dtype convert + (async)
  device transfer of chunk N, and per-process memory stays bounded by
  ``chunk_bytes * (prefetch + 2)`` instead of the whole file. Column
  dtype finalizes at EOF from the tokenizer's cumulative integral flags:
  float columns concatenate ON DEVICE from the streamed chunks; integral
  columns re-use per-chunk int32 host staging so results are bit-identical
  to the one-shot read (both are the same elementwise ``astype``).

Both native paths emit ``ingest.*`` counters and a ``frame.ingest`` span
(bytes, rows, chunks, threads, GB/s, simd verdict); the python-engine
fallback is counted by the caller (``frame/csv.py``).
"""

from __future__ import annotations

import ctypes
import os
import queue
import threading
from typing import Optional

import numpy as np

from ..config import config, float_dtype, int_dtype
from ..utils import faults as _faults
from ..utils.observability import span
from ..utils.profiling import counters


class NativeIngestError(RuntimeError):
    """The native streaming layer failed mid-read — a prefetch producer
    thread died (its exception rides as ``__cause__``), or an injected
    ``ingest_native`` chaos fault. ``frame/csv.py`` catches this (with
    ``OSError``/``MemoryError``) and degrades the read to the python
    engine, which re-reads the file from scratch — the native → python
    rung of the ingest degradation ladder."""


class NativeBuildError(RuntimeError):
    """``native/libdqcsv.so`` could not be built from this checkout's
    ``csvparse.cpp`` (no ``make``/``g++``, or a compile error). Raised to
    the caller for ``engine="native"`` and ``engine="auto"`` alike: a
    broken toolchain must not silently turn every read into a
    python-engine read."""


_LIB = None
_LIB_TRIED = False
_LOAD_LOCK = threading.Lock()

_NATIVE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "native"))
_SO_PATH = os.path.join(_NATIVE_DIR, "libdqcsv.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "csvparse.cpp")

_SIMD_CONF = {"auto": -1, "off": 0, "scalar": 0, "avx2": 1, "avx512": 2}
_SIMD_NAMES = {0: "scalar", 1: "avx2", 2: "avx512"}


def _build_if_stale() -> bool:
    """Make ``_SO_PATH`` current with ``_SRC_PATH``: build it when it is
    missing or older than its source. Returns False only when this
    install carries no native source at all (then there is nothing to
    tie a library to, and none is loaded)."""
    try:
        src_mtime = os.path.getmtime(_SRC_PATH)
    except OSError:
        return False
    try:
        if os.path.getmtime(_SO_PATH) >= src_mtime:
            return True
    except OSError:
        pass
    import subprocess

    try:
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR, "libdqcsv.so"],
            capture_output=True, text=True, timeout=600)
    except (OSError, subprocess.SubprocessError) as e:
        raise NativeBuildError(
            f"cannot build {_SO_PATH}: {type(e).__name__}: {e}") from e
    if proc.returncode != 0:
        raise NativeBuildError(
            f"building {_SO_PATH} failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")
    return True


def _load():
    """The bound library, built first when stale (:func:`_build_if_stale`).
    ``None`` when this install has no native source; raises
    :class:`NativeBuildError` when the build fails."""
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    with _LOAD_LOCK:
        if _LIB_TRIED:
            return _LIB
        if _build_if_stale():
            try:
                _LIB = _bind(ctypes.CDLL(_SO_PATH))
            except OSError as e:
                raise NativeBuildError(
                    f"cannot load {_SO_PATH}: {e}") from e
        _LIB_TRIED = True
    return _LIB


def _bind(lib):
    """Declare ``argtypes``/``restype`` for every entry point."""
    pd = ctypes.POINTER(ctypes.c_double)
    lib.dq_parse_numeric_csv.restype = ctypes.c_longlong
    lib.dq_parse_numeric_csv.argtypes = [
        ctypes.c_char_p,                      # path
        ctypes.c_char,                        # delimiter
        ctypes.c_char,                        # quote
        ctypes.c_int,                         # skip_header
        ctypes.POINTER(pd),                   # out data
        ctypes.POINTER(ctypes.c_longlong),    # out ncols
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),  # out int_flags
    ]
    lib.dq_free.restype = None
    lib.dq_free.argtypes = [ctypes.c_void_p]
    lib.dq_parse_numeric_csv_v2.restype = ctypes.c_longlong
    lib.dq_parse_numeric_csv_v2.argtypes = (
        lib.dq_parse_numeric_csv.argtypes[:4]
        + [ctypes.c_int, ctypes.c_int]        # simd, threads
        + lib.dq_parse_numeric_csv.argtypes[4:])
    lib.dq_effective_simd.restype = ctypes.c_int
    lib.dq_effective_simd.argtypes = [ctypes.c_int]
    lib.dq_stream_open.restype = ctypes.c_void_p
    lib.dq_stream_open.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_char,
        ctypes.c_int,                     # skip_header
        ctypes.c_longlong,                # chunk_bytes
        ctypes.c_int, ctypes.c_int,       # threads, simd
    ]
    lib.dq_stream_ncols.restype = ctypes.c_longlong
    lib.dq_stream_ncols.argtypes = [ctypes.c_void_p]
    lib.dq_stream_simd.restype = ctypes.c_int
    lib.dq_stream_simd.argtypes = [ctypes.c_void_p]
    lib.dq_stream_next.restype = ctypes.c_longlong
    lib.dq_stream_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(pd)]
    lib.dq_stream_int_flags.restype = None
    lib.dq_stream_int_flags.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.dq_stream_close.restype = None
    lib.dq_stream_close.argtypes = [ctypes.c_void_p]
    lib.dq_stream_total_rows.restype = ctypes.c_longlong
    lib.dq_stream_total_rows.argtypes = [ctypes.c_void_p]
    lib.dq_stream_bind.restype = ctypes.c_int
    lib.dq_stream_bind.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_longlong, ctypes.c_int,
    ]
    lib.dq_stream_next_into.restype = ctypes.c_longlong
    lib.dq_stream_next_into.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong)]
    return lib


def available() -> bool:
    return _load() is not None




def simd_level(requested: Optional[str] = None) -> str:
    """Effective SIMD tier name for a conf request (default: the session
    conf) — the simd-vs-scalar verdict the ``frame.ingest`` span reports."""
    lib = _load()
    if lib is None:
        return "unavailable"
    req = _SIMD_CONF.get((requested or config.ingest_simd).lower(), -1)
    return _SIMD_NAMES.get(int(lib.dq_effective_simd(req)), "scalar")


def try_read_csv(path: str, header: bool, infer_schema: bool, delimiter: str,
                 quote: str = '"', required: bool = False):
    """Native read; returns a Frame or None (fallback to python engine)."""
    lib = _load()
    if lib is None:
        if required:
            raise RuntimeError(
                "native CSV engine requested but this install carries no "
                "native/csvparse.cpp to build it from")
        return None
    if len(delimiter.encode("utf-8")) != 1 or len(quote.encode("utf-8")) != 1:
        return None  # ctypes c_char needs exactly one BYTE → python engine
    if not infer_schema:
        # Native fast path only covers the inferred all-numeric shape (the
        # reference's shape); explicit schemas stay on the python engine.
        if required:
            raise RuntimeError("native CSV engine only supports "
                               "infer_schema=True")
        return None
    names = None
    if header:
        # Column names come from the header record host-side; the C
        # tokenizer skips that record (skip_header) and parses the numeric
        # body. Anything irregular — unreadable text, a header wider or
        # narrower than the data — falls back to the python engine.
        names = _read_header_names(path, delimiter, quote)
        if names is None:
            return None

    # chaos hook (one None check without a plan): a due io_error raises
    # InjectedIOError here — the flaky-disk model — and frame/csv.py
    # degrades the read to the python engine.
    _faults.inject("ingest_native")
    if config.ingest_streaming:
        try:
            size = os.path.getsize(path)
        except OSError:
            raise FileNotFoundError(path)
        if size > config.ingest_chunk_bytes:
            return _stream_read(lib, path, size, names, header, delimiter,
                                quote)
        return _oneshot_read(lib, path, size, names, header, delimiter,
                             quote, v2=True)
    # spark.ingest.streaming=false: the EXACT legacy one-shot path (v1 ABI,
    # env-driven auto tiers, no span/counters) — byte-for-byte the pre-
    # streaming behavior.
    return _oneshot_read(lib, path, None, names, header, delimiter, quote,
                         v2=False)


def _oneshot_read(lib, path, size, names, header, delimiter, quote, v2):
    """Whole-file native parse (v2: conf-driven simd/threads + ingest
    telemetry; v1: the untouched legacy contract)."""
    data_p = ctypes.POINTER(ctypes.c_double)()
    ncols = ctypes.c_longlong(0)
    intf_p = ctypes.POINTER(ctypes.c_char)()
    if v2:
        simd = _SIMD_CONF.get(config.ingest_simd.lower(), -1)
        with span("frame.ingest", cat="frame", path=os.path.basename(path),
                  mode="oneshot") as sp:
            import time

            t0 = time.perf_counter()
            nrows = lib.dq_parse_numeric_csv_v2(
                path.encode(), delimiter.encode(), quote.encode(),
                1 if header else 0, simd, config.ingest_threads,
                ctypes.byref(data_p), ctypes.byref(ncols),
                ctypes.byref(intf_p))
            frame = _finish_oneshot(lib, path, nrows, data_p, ncols, intf_p,
                                    names)
            if nrows > 0 and size:
                el = time.perf_counter() - t0
                counters.increment("ingest.files")
                counters.increment("ingest.bytes", size)
                counters.increment("ingest.rows", nrows)
                sp.set(bytes=size, rows=int(nrows), chunks=1,
                       threads=config.ingest_threads or 0,
                       simd=simd_level(),
                       gb_s=round(size / el / 1e9, 4) if el > 0 else 0.0)
        return frame
    nrows = lib.dq_parse_numeric_csv(
        path.encode(), delimiter.encode(), quote.encode(),
        1 if header else 0,
        ctypes.byref(data_p), ctypes.byref(ncols), ctypes.byref(intf_p))
    return _finish_oneshot(lib, path, nrows, data_p, ncols, intf_p, names)


def _finish_oneshot(lib, path, nrows, data_p, ncols, intf_p, names):
    if nrows < 0:
        if nrows == -2:
            raise FileNotFoundError(path)
        return None  # non-numeric content → python engine
    data = {}
    try:
        nc = ncols.value
        if names is not None and len(names) != nc:
            return None  # ragged header vs body → python semantics
        if nc == 0 or nrows == 0:
            if names:
                return None  # header-only file: python's typing is exact
            from .frame import Frame
            return Frame({})
        # No intermediate .copy(): astype below always copies out of the
        # C buffer (dtype conversion or copy=True default), so an extra
        # staging copy would just add a full-matrix memory pass.
        flat = np.ctypeslib.as_array(data_p, shape=(nc * nrows,))
        cols = flat.reshape(nc, nrows)  # column-major from C
        int_flags = bytes(ctypes.cast(intf_p, ctypes.POINTER(ctypes.c_char * nc)).contents)
        for j in range(nc):
            col = cols[j]
            name = names[j] if names is not None else f"_c{j}"
            if int_flags[j]:
                data[name] = col.astype(np.dtype(int_dtype()))
            else:
                data[name] = col.astype(np.dtype(float_dtype()))
    finally:
        lib.dq_free(data_p)
        lib.dq_free(intf_p)

    from .frame import Frame

    return Frame(data)


def _aligned_empty(n: int, dtype, align: int = 64) -> np.ndarray:
    """Uninitialized 1-D array whose data pointer is ``align``-byte
    aligned — the alignment at which XLA:CPU adopts a host buffer
    zero-copy on ``device_put``, and a cache-line-aligned store target
    for the native column writes either way."""
    dt = np.dtype(dtype)
    raw = np.empty(n * dt.itemsize + align, dtype=np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off:off + n * dt.itemsize].view(dt)


# ---- bind-buffer pool ------------------------------------------------------
# Reused bind buffers have warm (already-faulted) pages; on fault-throttled
# hosts first-touch faults on a couple hundred MB of fresh columns otherwise
# cost more than the parse itself. A buffer may come back here only when the
# engine holds no reference into it: after an accelerator read (device_put
# COPIED the columns into device memory) or after a read that handed no
# column over. On the CPU backend device_put ADOPTS these 64-byte-aligned
# buffers zero-copy — the columns ARE the buffers, so a successful CPU read
# never returns them.
_POOL_LOCK = threading.Lock()
_POOL: list = []  # (fbuf, ibuf) pairs no live column refers to
_POOL_MAX_ENTRIES = 2
_POOL_CAP_BYTES = 1 << 30


def _pool_checkout(nf: int, fdtype, ni: int):
    with _POOL_LOCK:
        for k, (f, i) in enumerate(_POOL):
            if f.dtype == np.dtype(fdtype) and f.size >= nf and i.size >= ni:
                del _POOL[k]
                return f, i
    return _aligned_empty(nf, fdtype), _aligned_empty(ni, np.int32)


def _pool_checkin(fbuf: np.ndarray, ibuf: np.ndarray) -> None:
    if fbuf.nbytes + ibuf.nbytes > _POOL_CAP_BYTES:
        return
    with _POOL_LOCK:
        if len(_POOL) < _POOL_MAX_ENTRIES:
            _POOL.append((fbuf, ibuf))


def _stream_read(lib, path, size, names, header, delimiter, quote):
    """Streaming native read: bounded-chunk parse → device columns.

    Two modes behind one ``read_csv`` surface:

    * **pinned** (unquoted files, the overwhelming case): one classify
      sweep bounds the row count, the final engine-dtype column buffers
      (float32/float64 + int32 staging) come 64-byte aligned from a
      process-level pool (warm pages — see the pool note above
      ``_pool_checkout``), and every chunk parses STRAIGHT into its
      final rows inside ``dq_stream_next_into`` (typed stores in the
      native walk — no per-chunk malloc, no astype, no concatenate). On
      the CPU backend each column hands to JAX at EOF with one
      ``device_put`` (which adopts the aligned buffer); on an
      accelerator float rows ship per chunk and concatenate on device.
      Bit parity: the native (float)/(int32) casts are the same IEEE
      elementwise conversions as the one-shot path's numpy ``astype``.
    * **chunked** (quoted files): the original per-chunk f64 blocks +
      host-side ``astype`` staging.

    In both modes a producer thread blocks in the native parse (GIL
    released) up to ``spark.ingest.prefetch`` chunks ahead of the
    consumer, so parse, conversion/transfer, and downstream compute
    overlap.
    """
    simd = _SIMD_CONF.get(config.ingest_simd.lower(), -1)
    h = lib.dq_stream_open(path.encode(), delimiter.encode(), quote.encode(),
                           1 if header else 0, config.ingest_chunk_bytes,
                           config.ingest_threads, simd)
    if not h:
        raise FileNotFoundError(path)
    import time

    t0 = time.perf_counter()
    try:
        nc = int(lib.dq_stream_ncols(h))
        if nc < 0:
            return None  # non-numeric prologue → python engine
        if names is not None and len(names) != nc:
            return None  # ragged header vs body → python semantics
        if nc == 0:
            if names:
                return None  # header-only file: python's typing is exact
            from .frame import Frame
            return Frame({})
        verdict = _SIMD_NAMES.get(int(lib.dq_stream_simd(h)), "scalar")
        with span("frame.ingest", cat="frame", path=os.path.basename(path),
                  mode="stream") as sp:
            # _stream_pinned falls back to the chunked body itself if the
            # bind is refused; a None from either body is DEFINITIVE
            # (non-numeric content) — never retried.
            out = _stream_pinned(lib, h, nc, names, size)
            if out is None:
                return None  # non-numeric mid-file → python engine
            data, total_rows, nchunks = out
            el = time.perf_counter() - t0
            counters.increment("ingest.files")
            counters.increment("ingest.streamed")
            counters.increment("ingest.bytes", size)
            counters.increment("ingest.rows", total_rows)
            counters.increment("ingest.chunks", nchunks)
            sp.set(bytes=size, rows=total_rows, chunks=nchunks,
                   threads=config.ingest_threads or 0, simd=verdict,
                   prefetch=config.ingest_prefetch,
                   gb_s=round(size / el / 1e9, 4) if el > 0 else 0.0)
    finally:
        lib.dq_stream_close(h)

    from .frame import Frame

    # Sharded ingest hand-off: streamed chunks assembled into the pooled
    # engine-dtype buffers place straight into the row-sharded layout
    # (contiguous ranges — chunk order, and with it row order, is
    # preserved exactly); the prefetch thread keeps overlapping parse
    # with this device transfer. One flag check when sharding is off.
    from ..parallel.shard import maybe_shard_frame

    return maybe_shard_frame(Frame(data))


def _stream_pinned(lib, h, nc, names, size):
    """Bind-mode body: parse chunks into preallocated aligned typed
    buffers; returns ``(data, rows, chunks)``, or None for the python
    fallback (non-numeric content; the caller must not retry chunked —
    None here is definitive because native already scanned the file)."""
    import jax

    fdt = np.dtype(float_dtype())
    idt = np.dtype(int_dtype())
    want_f64 = fdt == np.dtype(np.float64)
    # Exact row bound from the native structural count (one read-only SIMD
    # sweep) — exact sizing is what lets the buffer pool actually hit: a
    # bytes-derived bound overallocates ~the field width, which balloons
    # the pooled footprint past the cap. Quoted files have no structural
    # count (-1): bound by bytes — every EMITTED record consumes at least
    # 2 input bytes (blank lines are skipped, so ≥ 1 content byte + a
    # separator; ragged short rows make nc-based bounds unsafe), +2 for
    # an unterminated tail — where the overallocation stays VIRTUAL
    # (untouched pages are never faulted in) and such buffers simply
    # exceed the pool cap.
    # chaos hook: a due pool_exhaust fault models an allocation-starved
    # bind pool — degrade one level to the chunked body (per-chunk
    # malloc'd blocks, no pooled buffers) instead of dying.
    if _faults.fired("ingest_native", "pool_exhaust"):
        from ..utils.recovery import RECOVERY_LOG

        RECOVERY_LOG.record(
            "ingest_native", "fallback", rung="chunked",
            cause="pool exhausted",
            detail="bind-buffer pool exhausted; chunked stream body")
        counters.increment("ingest.fault_fallback")
        return _stream_chunked(lib, h, nc, names)
    total_cap = int(lib.dq_stream_total_rows(h))
    if total_cap < 0:
        total_cap = size // 2 + 2
    # Column stride padded to 16 elements: with a 64-byte-aligned base,
    # every column of both blocks starts 64-byte aligned too (16 * 4-byte
    # lanes = one cache line; 16 * 8-byte lanes = two).
    stride = ((max(total_cap, 1) + 15) // 16) * 16
    fbuf, ibuf = _pool_checkout(
        nc * stride, np.float64 if want_f64 else np.float32, nc * stride)
    # Release-ONCE discipline: the buffers return to the pool on every
    # exit that leaves no column referring to them — an accelerator
    # success (after the engine finished copying them), the
    # definitive-None parse failure, the alloc-failure raise, a dead
    # prefetch producer — via the finally below. The flag stops a double
    # checkin (two pool entries aliasing one buffer would hand the same
    # memory to two concurrent readers); ``adopted`` keeps buffers the
    # CPU backend's columns alias out of the pool for good.
    released = False
    adopted = False

    def _release():
        nonlocal released
        if not released:
            released = True
            if not adopted:
                _pool_checkin(fbuf, ibuf)

    rc = int(lib.dq_stream_bind(
        h, fbuf.ctypes.data_as(ctypes.c_void_p),
        ibuf.ctypes.data_as(ctypes.c_void_p), stride, 1 if want_f64 else 0))
    if rc != 0:
        _release()
        return _stream_chunked(lib, h, nc, names)
    # On a real accelerator a column's float rows are device_put as soon
    # as they are KNOWN-float, so host->device DMA overlaps the parse of
    # the next chunk and the final concat runs on device. "Known-float"
    # follows the native single-lane store protocol (SinkTyped): while a
    # column's integral flag is alive only its i32 lane is written, so
    # the float lane must not be snapshot yet — when the flag dies, the
    # native backfill has (synchronously, before the chunk call returns)
    # completed the float lane for every row so far, and the whole
    # [0, row_end) range ships at once; thereafter per-chunk. Columns
    # integral at EOF never ship float rows — they hand over as int32.
    # No transferred region is ever rewritten: backfill only targets
    # columns transitioning alive->dead, which by construction have no
    # prior float transfers. On the CPU backend there is no DMA to
    # overlap — columns hand over whole at EOF, and device_put adopts the
    # aligned buffer instead of copying it.
    cpu_backend = jax.default_backend() == "cpu"
    chunks = _bind_chunk_iter(lib, h, nc)
    try:
        dev_chunks: list[list] = [[] for _ in range(nc)]
        dev_rows = [0] * nc  # float rows already transferred per column
        total_rows = 0
        nchunks = 0
        for rows, (off, chunk_flags) in chunks:
            if rows == -2:
                raise MemoryError("native CSV stream allocation failure")
            if rows < 0:
                return None  # non-numeric mid-file → python engine
            nchunks += 1
            total_rows += rows
            if not cpu_backend:
                for j in range(nc):
                    if chunk_flags[j]:
                        continue  # i32 lane live: float lane unwritten
                    base = j * stride + dev_rows[j]
                    dev_chunks[j].append(
                        jax.device_put(fbuf[base:base + total_rows -
                                            dev_rows[j]]))
                    dev_rows[j] = total_rows
        flags = _stream_flags(lib, h, nc)
        adopted = cpu_backend
        data = {}
        for j in range(nc):
            name = names[j] if names is not None else f"_c{j}"
            base = j * stride
            if flags[j]:
                col = ibuf[base:base + total_rows]
                col = col if idt == np.dtype(np.int32) else col.astype(idt)
                data[name] = jax.device_put(col)
            elif cpu_backend:
                data[name] = jax.device_put(fbuf[base:base + total_rows])
            else:
                import jax.numpy as jnp

                data[name] = (dev_chunks[j][0] if len(dev_chunks[j]) == 1
                              else jnp.concatenate(dev_chunks[j]))
        # The engine must be done copying out of the bind buffers before
        # they can be pooled for the next read.
        jax.block_until_ready(list(data.values()))
        return data, total_rows, nchunks
    finally:
        # Quiesce the prefetch producer BEFORE pooling the buffers: on a
        # consumer-side exception the producer may still be parsing a
        # chunk INTO fbuf/ibuf, and a checkin at that moment would hand
        # live-written memory to the next reader. Closing the iterator
        # runs its finally (stop + drain + join); only then is the
        # checkin safe.
        chunks.close()
        _release()


def _stream_chunked(lib, h, nc, names):
    """Per-chunk f64 blocks + host astype staging — quoted files (the
    bind is refused) and the ``pool_exhaust`` rung. Returns
    ``(data, rows, chunks)`` or None."""
    import jax

    fdt = np.dtype(float_dtype())
    idt = np.dtype(int_dtype())
    # One host-side np.concatenate + a single device_put per column
    # measures ~5x cheaper on XLA:CPU than per-chunk puts + an XLA
    # concatenate, so staging stays host-side there; accelerators stream
    # each converted chunk to the device immediately. Results are
    # bit-identical either way (same astype, same concatenation).
    cpu_backend = jax.default_backend() == "cpu"

    dev_chunks: list[list] = [[] for _ in range(nc)]  # float col chunks
    int_chunks: list[Optional[list]] = [[] for _ in range(nc)]  # host i32
    total_rows = 0
    nchunks = 0
    for rows, data_p in _chunk_iter(lib, h):
        if rows == -2:
            raise MemoryError("native CSV stream allocation failure")
        if rows < 0:
            return None  # non-numeric mid-file → python engine
        nchunks += 1
        flat = np.ctypeslib.as_array(data_p, shape=(nc * rows,))
        cols = flat.reshape(nc, rows)
        flags = _stream_flags(lib, h, nc)
        for j in range(nc):
            # Float path streams to the device now (accelerators) or
            # stages host-side (CPU backend); integral candidates also
            # stage the EXACT int32 the one-shot read would produce
            # (astype is elementwise, so per-chunk == whole-file
            # bit-wise).
            fcol = cols[j].astype(fdt)
            dev_chunks[j].append(
                fcol if cpu_backend else jax.device_put(fcol))
            ij = int_chunks[j]
            if ij is not None:
                if flags[j]:
                    ij.append(cols[j].astype(idt))
                else:
                    int_chunks[j] = None  # integrality broke
        lib.dq_free(data_p)
        total_rows += rows

    flags = _stream_flags(lib, h, nc)
    data = {}
    for j in range(nc):
        name = names[j] if names is not None else f"_c{j}"
        if flags[j] and int_chunks[j] is not None:
            data[name] = (int_chunks[j][0] if len(int_chunks[j]) == 1
                          else np.concatenate(int_chunks[j]))
        elif cpu_backend:
            host = (dev_chunks[j][0] if len(dev_chunks[j]) == 1
                    else np.concatenate(dev_chunks[j]))
            data[name] = jax.device_put(host)
        else:
            import jax.numpy as jnp

            data[name] = (dev_chunks[j][0] if len(dev_chunks[j]) == 1
                          else jnp.concatenate(dev_chunks[j]))
    return data, total_rows, nchunks


def _stream_flags(lib, h, nc) -> bytes:
    buf = ctypes.create_string_buffer(nc)
    lib.dq_stream_int_flags(h, buf)
    return buf.raw[:nc]


#: Reserved queue code: the producer thread died and the payload is its
#: exception (never emitted by the native layer, whose codes stop at -2).
_PRODUCER_ERROR = -3


def _prefetch_iter(next_chunk, release=None):
    """Yield ``(rows, payload)`` chunks from a ``next_chunk()`` callable.

    With ``spark.ingest.prefetch`` > 0, a producer thread runs the native
    parse up to that many chunks ahead (bounded queue = bounded memory);
    the terminal code (0 EOF / -1 fallback / -2 alloc) is yielded too so
    the consumer owns all error handling. The producer never outlives the
    iterator: closing/failing the consumer sets ``stop`` and any chunk
    that cannot be handed over is released via ``release(payload)``
    (malloc'd blocks in chunked mode; bind mode has no ownership to
    reclaim and passes no release).

    A DYING producer must never strand the consumer on the bounded
    queue: any exception it raises (a ctypes failure, an injected
    ``ingest_native:thread_death``) is handed through the queue as a
    ``_PRODUCER_ERROR`` item and re-raised here as
    :class:`NativeIngestError` (original as ``__cause__``); as
    belt-and-braces, the consumer's waits are timed and probe
    ``t.is_alive()``, so even a producer killed without a handoff
    surfaces as an error, never a hang.
    """
    depth = config.ingest_prefetch
    if depth <= 0:  # synchronous mode: no thread, parse inline
        while True:
            rows, payload = next_chunk()
            if rows <= 0:
                if rows < 0:
                    yield rows, payload
                return
            yield rows, payload
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def produce():
        while True:
            try:
                if _faults.fired("ingest_native", "thread_death"):
                    raise RuntimeError("injected prefetch-producer death")
                item = next_chunk()
            except BaseException as e:  # surface, never silently die
                item = (_PRODUCER_ERROR, e)
            rows, payload = item
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    break
                except queue.Full:
                    continue
            else:  # consumer gone: release the orphaned chunk
                if rows > 0 and release is not None:
                    release(payload)
                return
            if rows <= 0:
                return

    t = threading.Thread(target=produce, name="dqcsv-prefetch", daemon=True)
    t.start()
    try:
        while True:
            while True:
                try:
                    rows, payload = q.get(timeout=0.5)
                    break
                except queue.Empty:
                    if not t.is_alive():
                        # the producer may have put its final item and
                        # exited between the Empty and the liveness
                        # probe: drain once more before declaring death
                        try:
                            rows, payload = q.get_nowait()
                            break
                        except queue.Empty:
                            raise NativeIngestError(
                                "prefetch producer thread died without "
                                "handing off a chunk") from None
            if rows == _PRODUCER_ERROR:
                raise NativeIngestError(
                    f"prefetch producer thread died: {payload!r}"
                ) from payload
            if rows <= 0:
                if rows < 0:
                    yield rows, payload
                return
            yield rows, payload
    finally:
        stop.set()
        while True:  # drain queued chunks / unblock a waiting producer
            try:
                rows, payload = q.get_nowait()
                if rows > 0 and release is not None:
                    release(payload)
            except queue.Empty:
                break
        t.join()


def _chunk_iter(lib, h):
    """``(rows, data_ptr)`` chunks — per-chunk malloc'd blocks the
    consumer (or the iterator, on teardown) must ``dq_free``."""
    def next_chunk():
        data_p = ctypes.POINTER(ctypes.c_double)()
        rows = int(lib.dq_stream_next(h, ctypes.byref(data_p)))
        if rows > 0 and _faults.fired("ingest_native", "torn_chunk"):
            # chaos: a short read / torn chunk — the real parse result
            # is discarded and the failure raised as the native-layer
            # error class, so engine=auto degrades to the python engine
            # while an explicit engine="native" request still raises
            # (the same contract as io_error/thread_death)
            lib.dq_free(data_p)
            raise NativeIngestError("injected short-read/torn chunk")
        return rows, (data_p if rows > 0 else None)

    return _prefetch_iter(next_chunk, release=lib.dq_free)


def _bind_chunk_iter(lib, h, nc):
    """``(rows, (row_off, flags))`` for the bind-mode stream — values land
    directly in the bound buffers, so there is no chunk ownership to
    reclaim. ``flags`` is the integral-flag state AS OF THE END OF THIS
    CHUNK, captured in the producer (the thread that ran the parse) and
    handed through the queue: with prefetch the producer may already be
    parsing — and BACKFILLING — later chunks while the consumer processes
    this one, so a live ``dq_stream_int_flags`` read from the consumer
    would race those writes. The snapshot is what makes acting on a dead
    flag safe: once a column's flag is dead in the post-chunk-k snapshot,
    its float rows [0, rows_k) are final (backfill fires only on the
    alive->dead transition, and later chunks write only later rows)."""
    def next_chunk():
        off = ctypes.c_longlong(0)
        rows = int(lib.dq_stream_next_into(h, ctypes.byref(off)))
        if rows > 0 and _faults.fired("ingest_native", "torn_chunk"):
            # chaos: torn chunk in bind mode — values already written to
            # the bound buffers are abandoned (the pool checkin in
            # _stream_pinned's finally reclaims them after the producer
            # quiesces); raised as the native-layer class so the
            # engine=auto/"native" degrade contract matches io_error
            raise NativeIngestError("injected short-read/torn chunk")
        flags = _stream_flags(lib, h, nc) if rows > 0 else b""
        return rows, (off.value if rows > 0 else 0, flags)

    return _prefetch_iter(next_chunk)


def _read_header_names(path: str, delimiter: str, quote: str):
    """First non-blank record's fields, via the same record/field scanner
    the python engine uses (one quoting state machine to maintain) — or
    None when the header can't be confidently read, sending the read to
    the python engine. Fail-closed cases:

    - undecodable bytes, or no complete first record inside the probe
      window (an unquoted record terminator proves completeness even when
      the file is larger than the probe);
    - the python engine and the C prologue would pick DIFFERENT header
      records: python's blank-record skip is ``str.strip()`` (any unicode
      whitespace), the C side's is space/tab only, so a ``\\x0b``-only
      first line would make C skip the REAL header as its header record
      and parse it as data — a silent extra row. Detected by replicating
      the C pick host-side and comparing.

    The probe reads 64 KiB; when the file continues past it, the sniff is
    cut at the LAST record separator before decoding (separators are
    ASCII, so the cut can never split a multibyte UTF-8 character — the
    old whole-probe decode raised ``UnicodeDecodeError`` whenever the
    read truncated mid-character, spuriously demoting native-eligible
    files to the python engine).
    """
    try:
        with open(path, "rb") as f:
            chunk = f.read(1 << 16)
            more = f.read(1) != b""
    except OSError:
        return None
    if more:
        cut = max(chunk.rfind(b"\n"), chunk.rfind(b"\r"))
        if cut < 0:
            return None  # no complete record inside the probe: punt
        chunk = chunk[:cut + 1]
    try:
        text = chunk.decode("utf-8")
    except UnicodeDecodeError:
        return None
    if more and not _has_unquoted_record_end(text, quote):
        return None  # first record may be truncated by the probe: punt
    from .csv import parse_csv_text, split_fields

    rows = parse_csv_text(text, delimiter, quote)
    if not rows:
        return None
    # The record the C prologue will treat as the header: first record
    # (plain \r\n|\r|\n split, no quote awareness — the C side's skip
    # happens in the same byte-level terms) whose content is not
    # space/tab-only. If its fields differ from python's first record,
    # the engines would disagree on where data starts: fall back.
    c_first = None
    for rec in _plain_records(text):
        if rec.strip(" \t") != "":
            c_first = rec
            break
    if c_first is None or split_fields(c_first, delimiter, quote) != rows[0]:
        return None
    return list(rows[0])


def _plain_records(text: str):
    """Byte-level record split (\\r\\n, \\r, \\n), quote-unaware — the C
    prologue's view of the file."""
    rec = []
    i = 0
    n = len(text)
    while i < n:
        ch = text[i]
        if ch == "\n" or ch == "\r":
            yield "".join(rec)
            rec = []
            if ch == "\r" and i + 1 < n and text[i + 1] == "\n":
                i += 1
        else:
            rec.append(ch)
        i += 1
    if rec:
        yield "".join(rec)


def _has_unquoted_record_end(text: str, quote: str) -> bool:
    """True when an unquoted record terminator exists in ``text`` — proof
    the first record is complete inside the probe window even for quoted
    files (RFC-4180: terminators inside quotes don't end a record)."""
    in_quotes = False
    for ch in text:
        if ch == quote:
            in_quotes = not in_quotes
        elif (ch == "\n" or ch == "\r") and not in_quotes:
            return True
    return False
