"""CSV reader with schema inference — the data-loader capability.

Replaces the engine's CSV source the reference invokes with
``inferSchema=true, header=false`` (`DataQuality4MachineLearningApp.java:53-55`).
Must-have behavior (SURVEY.md §2.2):

* **universal newline handling including bare CR** — all three reference
  datasets are CR-terminated (``\\r`` only, no LF); a naive ``\\n`` split reads
  one giant record,
* default column names ``_c0, _c1, …`` when ``header=False``,
* type inference producing integer/long/double/boolean/string in that order of
  preference; empty fields are nulls (NaN in float columns — int columns with
  nulls promote to double, a documented deviation from Spark's boxed nulls).

Parsing happens on host (strings never touch the TPU); inferred numeric
columns are uploaded once as device arrays. A native C++ tokenizer (the
Univocity-parser analogue in the data-loader role) is used for large files
when available — see ``sparkdq4ml_tpu/frame/native_csv.py``.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

from ..config import float_dtype, int_dtype
from .frame import Frame

_NULL_STRINGS = {""}
_TRUE = {"true", "TRUE", "True"}
_FALSE = {"false", "FALSE", "False"}


def split_records(text: str) -> list[str]:
    r"""Split on \r\n, \r, or \n; drop blank records (Spark skips blank lines).

    Quote-UNaware — only safe when the text contains no quote character;
    :func:`parse_csv_text` routes quoted input through the stateful scanner
    so record separators inside quoted fields stay literal.
    """
    text = text.replace("\r\n", "\n").replace("\r", "\n")
    return [line for line in text.split("\n") if line.strip() != ""]


def _parse_quoted_text(text: str, delimiter: str, quote: str) -> list[list[str]]:
    r"""Single-pass stateful tokenizer for text containing quotes: record
    separators (\r\n, \r, \n) and delimiters inside quoted fields are
    literal content; ``""`` inside quotes is an escaped quote (RFC 4180 —
    the Univocity behavior behind the reference's CSV options,
    `DataQuality4MachineLearningApp.java:53-55`)."""
    rows: list[list[str]] = []
    row: list[str] = []
    buf: list[str] = []
    quoted_field = False   # current field had quotes (never blank-skipped)
    in_q = False
    i, n = 0, len(text)
    while i < n:
        c = text[i]
        if in_q:
            if c == quote:
                if i + 1 < n and text[i + 1] == quote:
                    buf.append(quote)
                    i += 1
                else:
                    in_q = False
            else:
                buf.append(c)
        elif c == quote:
            in_q = True
            quoted_field = True
        elif c == delimiter:
            row.append("".join(buf))
            buf = []
        elif c in ("\r", "\n"):
            if c == "\r" and i + 1 < n and text[i + 1] == "\n":
                i += 1
            row.append("".join(buf))
            buf = []
            if len(row) > 1 or row[0].strip() != "" or quoted_field:
                rows.append(row)      # blank lines are skipped (Spark)
            row = []
            quoted_field = False
        else:
            buf.append(c)
        i += 1
    if buf or row or quoted_field:   # a lone quoted "" is still a record
        row.append("".join(buf))
        if len(row) > 1 or row[0].strip() != "" or quoted_field:
            rows.append(row)
    return rows


def parse_csv_text(text: str, delimiter: str = ",",
                   quote: str = '"') -> list[list[str]]:
    """Tokenize a whole CSV text into rows of fields.

    Quote-free text (the reference datasets) takes the allocation-light
    split path; any quote routes through the stateful scanner so embedded
    record separators parse correctly.
    """
    if quote and quote in text:
        return _parse_quoted_text(text, delimiter, quote)
    return [r.split(delimiter) for r in split_records(text)]


def split_fields(record: str, delimiter: str = ",", quote: str = '"') -> list[str]:
    """Tokenize one record with RFC-4180 quoting — a thin wrapper over the
    same scanner :func:`parse_csv_text` uses (one quote state machine to
    maintain, not two)."""
    if quote not in record:
        return record.split(delimiter)
    rows = _parse_quoted_text(record, delimiter, quote)
    return rows[0] if rows else [""]


def _try_int(s: str) -> Optional[int]:
    try:
        return int(s)
    except ValueError:
        return None


def _try_float(s: str) -> Optional[float]:
    try:
        return float(s)
    except ValueError:
        return None


def _is_null_field(v: str) -> bool:
    """Whitespace-only (incl. empty) fields are nulls for numeric/boolean
    typing — Spark's univocity parser trims unquoted fields by default
    (ignoreLeading/TrailingWhiteSpace), so "  " reads as empty -> null.
    The native tokenizer (parse_span) agrees. String columns keep the
    narrower exact-"" rule so "  " survives as a value there."""
    return v in _NULL_STRINGS or not v.strip()


def infer_column(values: Sequence[str]):
    """Infer one column's type and parse it.

    Preference order integer → long → double → boolean → string, matching the
    Spark CSV inferrer's ladder. Returns a numpy array (object dtype for
    strings).
    """
    non_null = [v for v in values if not _is_null_field(v)]
    has_null = len(non_null) != len(values)

    if non_null and all(_try_int(v) is not None for v in non_null):
        ints = [int(v) for v in non_null]
        if not has_null:
            lo, hi = min(ints), max(ints)
            dt = np.dtype(int_dtype()) if -(2**31) <= lo and hi < 2**31 else np.int64
            return np.asarray([int(v) for v in values], dtype=dt)
        # int column with nulls promotes to double + NaN
        return np.asarray([np.nan if _is_null_field(v) else float(v)
                           for v in values], dtype=np.dtype(float_dtype()))
    if non_null and all(_try_float(v) is not None for v in non_null):
        return np.asarray([np.nan if _is_null_field(v) else float(v)
                           for v in values], dtype=np.dtype(float_dtype()))
    if non_null and all(v in _TRUE or v in _FALSE for v in non_null) and not has_null:
        return np.asarray([v in _TRUE for v in values], dtype=np.bool_)
    return np.asarray([v if v not in _NULL_STRINGS else None for v in values],
                      dtype=object)


_MODES = ("PERMISSIVE", "DROPMALFORMED", "FAILFAST")


def parse_ddl_schema(ddl: str) -> list:
    """Parse a Spark DDL schema string (``"a INT, b DOUBLE, s STRING"``)
    into [(name, type_name)]; type names are validated against the
    engine's Spark type-name table."""
    from ..ops.expressions import resolve_type_name

    fields = []
    for part in ddl.split(","):
        toks = part.split()
        if len(toks) != 2:
            raise ValueError(
                f"bad DDL field {part.strip()!r} (expected 'name TYPE')")
        name, type_name = toks
        resolve_type_name(type_name)          # raises on unknown types
        fields.append((name, type_name.lower()))
    return fields


def _cast_column(values: list, type_name: str):
    """Cast raw CSV strings to a declared Spark type; unparseable or null
    cells become null (Spark PERMISSIVE), which for integral columns
    promotes the column to float (the engine's nullable-numeric form)."""
    if type_name == "string":
        return np.asarray([v if v not in _NULL_STRINGS else None
                           for v in values], dtype=object)
    if type_name == "boolean":
        out = [None if _is_null_field(v)
               else v.strip().lower() == "true" for v in values]
        if any(v is None for v in out):
            return np.asarray([np.nan if v is None else float(v)
                               for v in out])
        return np.asarray(out, bool)
    floats = np.empty(len(values), np.float64)
    any_null = False
    for i, v in enumerate(values):
        try:
            floats[i] = float(v)
        except (TypeError, ValueError):
            floats[i] = np.nan
            any_null = True
    if type_name in ("int", "integer", "long"):
        if not any_null and np.all(floats == np.floor(floats)):
            dt = np.int64 if type_name == "long" else np.int32
            return floats.astype(dt)
        return floats          # nullable integral → float column
    from ..config import float_dtype

    return floats.astype(np.float32 if type_name == "float"
                         else np.dtype(float_dtype()))


def read_csv(path: str, header: bool = False, infer_schema: bool = True,
             delimiter: str = ",", engine: str = "auto",
             quote: str = '"', mode: str = "PERMISSIVE",
             schema=None) -> Frame:
    """Load a CSV file into a Frame.

    ``engine``: "python" (pure host parser), "native" (C++ tokenizer), or
    "auto" (native when the column set is numeric-friendly, else python —
    counted as ``ingest.python_fallback``). The native library builds
    itself from ``native/csvparse.cpp`` on first use; a toolchain failure
    raises for "native" and "auto" alike.

    ``mode`` (Spark's malformed-record policy): ``PERMISSIVE`` (default —
    short rows null-fill, long rows truncate), ``DROPMALFORMED`` (rows with
    the wrong field count are dropped), ``FAILFAST`` (raise on the first
    malformed row).

    ``schema``: explicit [(name, type)] (from a DDL string) — skips
    inference, names the columns, and casts each to its declared type.
    """
    mode = mode.upper()
    if mode not in _MODES:
        raise ValueError(f"mode={mode!r}; expected one of {_MODES}")
    if schema is not None:
        engine = "python"      # explicit-schema cast path is host-side
    if engine in ("auto", "native"):
        from . import native_csv

        if mode != "PERMISSIVE":
            # native pads short rows NaN (permissive); exact drop/failfast
            # field-count semantics live in the python engine
            if engine == "native":
                raise RuntimeError("native CSV engine supports "
                                   "mode=PERMISSIVE only")
        else:
            degraded = False
            try:
                frame = native_csv.try_read_csv(
                    path, header=header, infer_schema=infer_schema,
                    delimiter=delimiter, quote=quote,
                    required=(engine == "native"))
            except FileNotFoundError:
                raise          # permanent: the python engine can't help
            except (OSError, MemoryError,
                    native_csv.NativeIngestError) as e:
                if engine == "native":
                    raise      # explicit native request: never degrade
                # The native → python rung of the ingest degradation
                # ladder (ISSUE 11): a mid-read I/O error, an allocation
                # failure, or a dead prefetch producer (real or injected
                # via utils.faults site "ingest_native") re-reads the
                # file through the python engine — correctness over
                # speed, observable via the recovery event + counters.
                from ..utils.profiling import counters
                from ..utils.recovery import RECOVERY_LOG

                RECOVERY_LOG.record(
                    "ingest_native", "fallback", rung="python",
                    cause=f"{type(e).__name__}: {e}")
                counters.increment("ingest.fault_fallback")
                counters.increment("ingest.python_fallback")
                frame, degraded = None, True
            if frame is not None:
                return frame
            if not degraded:
                # native declined (non-numeric content, ragged header,
                # multibyte delimiter...) or this install carries no
                # native source: the ingest telemetry counts the demotion
                # so a fleet-wide scrape can see what share of reads
                # misses the fast path
                from ..utils.profiling import counters

                counters.increment("ingest.python_fallback")

    with open(path, "rb") as f:
        text = f.read().decode("utf-8")
    rows = parse_csv_text(text, delimiter, quote)
    if not rows:
        return Frame({})

    if header:
        names = rows[0]
        rows = rows[1:]
    else:
        names = [f"_c{i}" for i in range(len(rows[0]))]
    if schema is not None:
        if len(schema) != len(names):
            raise ValueError(
                f"schema has {len(schema)} fields but the file has "
                f"{len(names)} columns")
        names = [n for n, _ in schema]

    ncols = len(names)
    if mode != "PERMISSIVE":
        bad = [r for r in rows if len(r) != ncols]
        if bad and mode == "FAILFAST":
            raise ValueError(
                f"FAILFAST: malformed CSV record (expected {ncols} fields, "
                f"got {len(bad[0])}): {bad[0]!r}")
        if bad:  # DROPMALFORMED
            rows = [r for r in rows if len(r) == ncols]
    cols: list[list[str]] = [[] for _ in range(ncols)]
    for r in rows:
        for i in range(ncols):
            cols[i].append(r[i] if i < len(r) else "")

    data = {}
    if schema is not None:
        for (name, type_name), values in zip(schema, cols):
            data[name] = _cast_column(values, type_name)
        return Frame(data)
    for name, values in zip(names, cols):
        if infer_schema:
            data[name] = infer_column(values)
        else:
            data[name] = np.asarray([v if v not in _NULL_STRINGS else None
                                     for v in values], dtype=object)
    return Frame(data)


class DataFrameReader:
    """Builder-style reader mirroring ``spark.read().format("csv")
    .option(...).load(path)`` (`DataQuality4MachineLearningApp.java:53-55`)."""

    def __init__(self, session=None):
        self._session = session
        self._format = "csv"
        self._options: dict[str, str] = {}
        self._schema = None

    def schema(self, ddl: str) -> "DataFrameReader":
        """Explicit schema as a Spark DDL string (``"a INT, b DOUBLE"``) —
        skips inference and casts columns to the declared types."""
        self._schema = parse_ddl_schema(ddl)
        return self

    def format(self, fmt: str) -> "DataFrameReader":
        self._format = fmt.lower()
        return self

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key.lower()] = str(value)
        return self

    def options(self, **kwargs) -> "DataFrameReader":
        for k, v in kwargs.items():
            self.option(k, v)
        return self

    def _bool_opt(self, key: str, default: bool) -> bool:
        v = self._options.get(key.lower())
        return default if v is None else v.strip().lower() in ("true", "1", "yes")

    def load(self, path: str) -> Frame:
        if self._format not in ("csv", "json", "parquet"):
            raise ValueError(
                f"unsupported format {self._format!r} (csv, json, "
                "or parquet)")
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        if self._format == "parquet":
            from .parquet import read_parquet

            out = read_parquet(path)
        elif self._format == "json":
            from .jsonl import read_json

            out = read_json(path,
                            multi_line=self._bool_opt("multiline", False))
        else:
            out = read_csv(
                path,
                header=self._bool_opt("header", False),
                infer_schema=self._bool_opt("inferschema", False),
                delimiter=self._options.get(
                    "sep", self._options.get("delimiter", ",")),
                engine=self._options.get("engine", "auto"),
                quote=self._options.get("quote", '"'),
                mode=self._options.get("mode", "PERMISSIVE"),
                schema=self._schema,
            )
        # Sharded-frames ingest hand-off (spark.shard.enabled): loaded
        # frames above the minRows bound land row-sharded, so the whole
        # downstream pipeline — DQ filters, SQL, fit packing — runs the
        # sharded lowerings without re-placement. One flag check when
        # sharding is off.
        from ..parallel.shard import maybe_shard_frame

        return maybe_shard_frame(out)

    def csv(self, path: str, header: bool = False, inferSchema: bool = False) -> Frame:
        return self.option("header", header).option("inferSchema", inferSchema).load(path)

    def json(self, path: str, multiLine: bool = False) -> Frame:
        return self.format("json").option("multiLine", multiLine).load(path)

    def parquet(self, path: str) -> Frame:
        return self.format("parquet").load(path)
