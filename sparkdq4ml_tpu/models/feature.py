"""Feature-layer transformers.

``VectorAssembler`` packs input columns into one ``(n, d)`` feature-matrix
column (`DataQuality4MachineLearningApp.java:110-113`). TPU-first: the
"vector column" is literally the feature matrix in HBM, laid out densely so
the fit's Gramian is a single MXU matmul — there is no per-row vector object.
``transform`` is one compiled program (``_assemble``): every input column
in, the matrix out, one launch. The matrix is materialised — the fitted
model's ``transform`` reads it again in the score stage.

``StandardScaler`` / ``MinMaxScaler`` / ``MaxAbsScaler`` are the adjacent
MLlib feature estimators (same ``spark.ml.feature`` package the reference's
VectorAssembler comes from, pom.xml:29-32 mllib dependency). Statistics are
mask-weighted one-pass device reductions — filtered rows never leak into the
moments (SURVEY.md §7 "Masked-filter semantics") — and MLlib conventions are
kept: StandardScaler uses the *sample* (n−1) std, defaults
``with_mean=False, with_std=True``, and maps zero-variance features to 0;
MinMaxScaler maps constant features to ``(min+max)/2``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import float_dtype, int_dtype
from ..utils import observability as _obs
from .base import Estimator, Model, Transformer, host_fetch, persistable


#: 1-D columns gathered by one chain of selects; a longer run is cut into
#: blocks of this many (a chain does ``k`` selects an element).
_CHAIN = 32


def _scalar_block(columns, dtype):
    """``k`` 1-D columns as one ``(n, k)`` block: column ``i`` broadcast
    along the row and selected where the column index is ``i``. On the TPU
    that is ONE loop fusion that reads the columns and writes the block;
    ``concatenate([c[:, None] ...])`` relays every column out from its 1-D
    tiling into an ``(n, 1)`` array first — a ``while`` loop a column —
    and took 14.2 ms for 28 columns of 1.1e7 rows where this takes 8.6
    (PERF.md section 6, PR 29)."""
    n, k = columns[0].shape[0], len(columns)
    wide = [jax.lax.broadcast_in_dim(c.astype(dtype), (n, k), (0,))
            for c in columns]
    index = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1)
    out = wide[-1]
    for i in reversed(range(k - 1)):
        out = jax.lax.select(index == i, wide[i], out)
    return out


@functools.partial(jax.jit, static_argnums=1)
def _assemble(columns, dtype):
    """Every input column in, the ``(n, d)`` matrix out: ONE program a
    tuple of column shapes and dtypes (each eager convert, ``[:, None]``
    and the concatenate retraced per shape too, and was a launch and a
    full pass of its own). A 1-D column is one feature, a 2-D vector
    column its width; runs of 1-D columns are gathered by
    :func:`_scalar_block`, and blocks meet in one concatenate."""
    with _obs.scope("feature.assemble"):
        blocks, run = [], []

        def close_run():
            if run:
                blocks.append(_scalar_block(tuple(run), dtype))
                run.clear()

        for c in columns:
            if c.ndim == 1:
                run.append(c)
                if len(run) == _CHAIN:
                    close_run()
            else:
                close_run()
                blocks.append(c.astype(dtype))
        close_run()
        if len(blocks) == 1:
            return blocks[0]
        return jax.lax.concatenate(blocks, 1)


@persistable
class VectorAssembler(Transformer):
    _persist_attrs = ('input_cols', 'output_col')
    def __init__(self, input_cols: Optional[Sequence[str]] = None,
                 output_col: str = "features"):
        self.input_cols = list(input_cols) if input_cols else []
        self.output_col = output_col

    def set_input_cols(self, cols: Sequence[str]) -> "VectorAssembler":
        self.input_cols = list(cols)
        return self

    setInputCols = set_input_cols

    def set_output_col(self, name: str) -> "VectorAssembler":
        self.output_col = name
        return self

    setOutputCol = set_output_col

    def get_input_cols(self):
        return list(self.input_cols)

    getInputCols = get_input_cols

    def get_output_col(self):
        return self.output_col

    getOutputCol = get_output_col

    def transform(self, frame):
        if not self.input_cols:
            raise ValueError("VectorAssembler: input_cols not set")
        dt = float_dtype()
        with _obs.span("feature.assemble", cat="feature",
                       columns=len(self.input_cols),
                       rows=frame.num_slots, programs=1) as s:
            # a host column is converted on the host, as jnp.asarray(a, dt)
            # converted it (a 64-bit host column must not pass through
            # jit's 32-bit canonical form on its way to dt)
            parts = tuple(
                a if isinstance(a, jax.Array) else np.asarray(a, dt)
                for a in map(frame._column_values, self.input_cols))
            out = _assemble(parts, dt)
            s.set(width=int(out.shape[1]))      # static shape, no read
            return frame.with_column(self.output_col, out)


@persistable
class VectorSizeHint(Transformer):
    """MLlib ``VectorSizeHint``: declare (and validate) the size of a vector
    column so downstream stages (VectorAssembler in a streaming/persisted
    pipeline) know their output width without seeing data.

    Columnar-engine semantics: vector columns are dense ``(n, d)`` device
    arrays, so the size is uniform and checked once against the declared
    ``size`` — there are no per-row ragged vectors. Spark's
    ``handle_invalid`` modes map accordingly: ``error`` raises on a
    mismatch (including a scalar column when ``size != 1``);
    ``skip`` drops mismatching rows — a uniform column mismatching the
    hint means every row, so the frame comes back fully masked (empty);
    ``optimistic`` is Spark's no-validation mode and passes through.
    """

    _persist_attrs = ('input_col', 'size', 'handle_invalid')

    def __init__(self, input_col: str = None, size: int = None,
                 handle_invalid: str = "error"):
        if handle_invalid not in ("error", "skip", "optimistic"):
            raise ValueError(
                f"handle_invalid must be error/skip/optimistic, "
                f"got {handle_invalid!r}")
        self.input_col = input_col
        self.size = None if size is None else int(size)
        self.handle_invalid = handle_invalid

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_size(self, v):
        self.size = int(v)
        return self

    setSize = set_size

    def set_handle_invalid(self, v):
        if v not in ("error", "skip", "optimistic"):
            raise ValueError(
                f"handle_invalid must be error/skip/optimistic, got {v!r}")
        self.handle_invalid = v
        return self

    setHandleInvalid = set_handle_invalid

    def transform(self, frame):
        if self.input_col is None or self.size is None:
            raise ValueError("VectorSizeHint: input_col and size must be set")
        if self.size < 1:
            raise ValueError(f"VectorSizeHint: invalid size {self.size}")
        arr = frame._column_values(self.input_col)
        width = 1 if arr.ndim == 1 else arr.shape[1]
        if width != self.size:
            if self.handle_invalid == "error":
                raise ValueError(
                    f"VectorSizeHint: column {self.input_col!r} has size "
                    f"{width}, expected {self.size}")
            if self.handle_invalid == "skip":
                return frame.filter(
                    jnp.zeros((frame.num_slots,), bool))
        return frame


@persistable
class StringIndexer(Estimator):
    """MLlib ``StringIndexer``: map string categories to double indices,
    most-frequent-first (``frequencyDesc``; ties broken alphabetically, as
    Spark does). ``handle_invalid``: ``"error"`` (default) | ``"keep"``
    (unseen → numLabels) | ``"skip"`` (unseen → masked out on transform).

    The index *fit* is host-side (categories are host strings); the
    transformed column is a device array ready for VectorAssembler.
    """

    _persist_attrs = ('input_col', 'output_col', 'handle_invalid')

    def __init__(self, input_col: str = None, output_col: str = None,
                 handle_invalid: str = "error"):
        self.input_col = input_col
        self.output_col = output_col
        if handle_invalid not in ("error", "keep", "skip"):
            raise ValueError(f"handle_invalid={handle_invalid!r}")
        self.handle_invalid = handle_invalid

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def set_handle_invalid(self, v):
        self.handle_invalid = v
        return self

    setHandleInvalid = set_handle_invalid

    def fit(self, frame) -> "StringIndexerModel":
        col = frame._column_values(self.input_col)
        mask = np.asarray(frame.mask)
        values = [str(v) for v, m in zip(np.asarray(col, object), mask)
                  if m and v is not None]
        from collections import Counter

        counts = Counter(values)
        labels = sorted(counts, key=lambda k: (-counts[k], k))
        return StringIndexerModel(labels, self.input_col, self.output_col,
                                  self.handle_invalid)


@persistable
class StringIndexerModel(Model):
    _persist_attrs = ('labels', 'input_col', 'output_col', 'handle_invalid')

    def __init__(self, labels, input_col, output_col, handle_invalid="error"):
        self.labels = list(labels)
        self.input_col = input_col
        self.output_col = output_col
        self.handle_invalid = handle_invalid
        self._index = {l: i for i, l in enumerate(self.labels)}

    def _post_load(self):
        self.labels = list(self.labels)
        self._index = {l: i for i, l in enumerate(self.labels)}

    labelsArray = property(lambda self: [list(self.labels)])

    def transform(self, frame):
        col = np.asarray(frame._column_values(self.input_col), object)
        n_labels = len(self.labels)
        idx = np.empty(len(col), dtype=np.dtype(float_dtype()))
        invalid = np.zeros(len(col), bool)
        host_mask = np.asarray(frame.mask)
        for i, v in enumerate(col):
            j = self._index.get(str(v)) if v is not None else None
            if j is None:
                invalid[i] = True
                idx[i] = n_labels
            else:
                idx[i] = j
        if self.handle_invalid == "error" and bool((invalid & host_mask).any()):
            bad = sorted({str(col[i]) for i in np.nonzero(invalid & host_mask)[0]})
            raise ValueError(f"StringIndexer: unseen labels {bad}; set "
                             f"handle_invalid='keep' or 'skip'")
        out = frame.with_column(self.output_col, jnp.asarray(idx))
        if self.handle_invalid == "skip":
            out = out.filter(jnp.asarray(~invalid))
        return out


@persistable
class IndexToString(Transformer):
    """Inverse of StringIndexer: indices → label strings (host column)."""

    _persist_attrs = ('input_col', 'output_col', 'labels')

    def __init__(self, input_col: str = None, output_col: str = None,
                 labels=None):
        self.input_col = input_col
        self.output_col = output_col
        self.labels = list(labels) if labels is not None else None

    def transform(self, frame):
        idx = np.asarray(frame._column_values(self.input_col))
        labels = self.labels
        out = np.asarray([labels[int(i)] if 0 <= int(i) < len(labels) else None
                          for i in idx], dtype=object)
        return frame.with_column(self.output_col, out)


@persistable
class OneHotEncoder(Estimator):
    """MLlib ``OneHotEncoder``: index column → one-hot vector column.

    ``drop_last=True`` (Spark default) omits the last category so the
    encoding stays linearly independent with an intercept. The encode is a
    device comparison against an iota — one fused op, no host loop.
    """

    _persist_attrs = ('input_col', 'output_col', 'drop_last',
                      'input_cols', 'output_cols')
    input_cols = None     # back-compat default for pre-plural saves
    output_cols = None

    def __init__(self, input_col: str = None, output_col: str = None,
                 drop_last: bool = True, input_cols=None, output_cols=None):
        if input_col is not None and input_cols is not None:
            raise ValueError("set input_col OR input_cols, not both")
        self.input_col = input_col
        self.output_col = output_col
        self.input_cols = list(input_cols) if input_cols is not None else None
        self.output_cols = (list(output_cols) if output_cols is not None
                            else None)
        self.drop_last = drop_last

    def set_drop_last(self, v: bool):
        self.drop_last = v
        return self

    setDropLast = set_drop_last

    def _col_pairs(self):
        """Normalized [(in, out)] across the single- and plural-column
        forms (Spark 2.4's OneHotEncoderEstimator / 3.x OneHotEncoder
        take inputCols/outputCols lists)."""
        if self.input_cols is not None:
            if not self.input_cols:
                raise ValueError("input_cols must not be empty")
            outs = self.output_cols
            if outs is None or len(outs) != len(self.input_cols):
                raise ValueError("output_cols must match input_cols")
            return list(zip(self.input_cols, outs))
        if self.input_col is None:
            raise ValueError("OneHotEncoder needs input_col or input_cols")
        return [(self.input_col, self.output_col)]

    def fit(self, frame) -> "OneHotEncoderModel":
        w = frame.mask
        # stack the per-column maxes and cross device->host ONCE (a sync
        # per column would scale fit latency with the column count)
        maxes = jnp.stack([
            jnp.max(jnp.where(w, jnp.asarray(frame._column_values(cin)), -1))
            for cin, _ in self._col_pairs()])
        sizes = (np.asarray(maxes).astype(np.int64) + 1).tolist()
        if self.input_cols is not None:
            return OneHotEncoderModel(sizes[0], None, None, self.drop_last,
                                      category_sizes=sizes,
                                      input_cols=self.input_cols,
                                      output_cols=self.output_cols)
        return OneHotEncoderModel(sizes[0], self.input_col, self.output_col,
                                  self.drop_last)


# Spark 2.4 ships this estimator under the name OneHotEncoderEstimator
# (the old OneHotEncoder transformer was deprecated); 3.0 renamed it back.
# Both names resolve here.
OneHotEncoderEstimator = OneHotEncoder


@persistable
class OneHotEncoderModel(Model):
    _persist_attrs = ('category_size', 'input_col', 'output_col',
                      'drop_last', 'category_sizes', 'input_cols',
                      'output_cols')
    category_sizes = None  # back-compat defaults for pre-plural saves
    input_cols = None
    output_cols = None

    def __init__(self, category_size, input_col, output_col, drop_last=True,
                 category_sizes=None, input_cols=None, output_cols=None):
        self.category_size = int(category_size)
        self.input_col = input_col
        self.output_col = output_col
        self.drop_last = drop_last
        self.category_sizes = (list(map(int, category_sizes))
                               if category_sizes is not None else None)
        self.input_cols = list(input_cols) if input_cols is not None else None
        self.output_cols = (list(output_cols) if output_cols is not None
                            else None)
        self._check_plural_invariant()

    def _check_plural_invariant(self):
        """zip in _triples would silently truncate on mismatched lists."""
        if self.input_cols is not None:
            if (self.output_cols is None or self.category_sizes is None
                    or len(self.output_cols) != len(self.input_cols)
                    or len(self.category_sizes) != len(self.input_cols)):
                raise ValueError(
                    "input_cols / output_cols / category_sizes lengths "
                    "must match")

    def _post_load(self):
        # load_stage constructs via __new__ + setattr, bypassing __init__:
        # re-establish the invariant for saved (possibly hand-edited or
        # truncated) stage files too
        self._check_plural_invariant()

    @property
    def categorySizes(self):
        if self.category_sizes is not None:
            return list(self.category_sizes)
        return [self.category_size]

    def _triples(self):
        if self.input_cols is not None:
            return list(zip(self.input_cols, self.output_cols,
                            self.category_sizes))
        return [(self.input_col, self.output_col, self.category_size)]

    def transform(self, frame):
        out = frame
        for cin, cout, size in self._triples():
            # read indices from the ORIGINAL frame: an earlier output
            # name colliding with a later input name must not feed a
            # one-hot matrix back in as indices
            idx = jnp.asarray(frame._column_values(cin), int_dtype())
            width = size - (1 if self.drop_last else 0)
            eye = jnp.arange(width, dtype=int_dtype())
            onehot = (idx[:, None] == eye[None, :]).astype(float_dtype())
            out = out.with_column(cout, onehot)
        return out


@persistable
class Bucketizer(Transformer):
    """MLlib ``Bucketizer``: continuous column → bucket index by split
    points (``splits`` of length b+1, monotonic; use ±inf for open ends).
    One device ``searchsorted``; values outside the splits raise unless
    ``handle_invalid='keep'`` (→ NaN) or ``'skip'`` (→ masked)."""

    _persist_attrs = ('splits', 'input_col', 'output_col', 'handle_invalid')

    def __init__(self, splits=None, input_col: str = None,
                 output_col: str = None, handle_invalid: str = "error"):
        self.splits = list(splits) if splits is not None else None
        self.input_col = input_col
        self.output_col = output_col
        self.handle_invalid = handle_invalid

    def set_splits(self, v):
        self.splits = list(v)
        return self

    setSplits = set_splits

    def transform(self, frame):
        s = np.asarray(self.splits, np.dtype(float_dtype()))
        if s.ndim != 1 or len(s) < 3 or not np.all(np.diff(s) > 0):
            raise ValueError("splits must be >=3 strictly increasing values")
        x = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        # right-closed last bucket, Spark semantics: x == splits[-1] falls in
        # the last bucket; outside [splits[0], splits[-1]] is invalid.
        idx = jnp.clip(jnp.searchsorted(jnp.asarray(s), x, side="right") - 1,
                       0, len(s) - 2).astype(float_dtype())
        # NaN is invalid too (it compares false to both bounds, and Spark
        # routes it through handleInvalid rather than into a bucket)
        invalid = jnp.logical_or(jnp.logical_or(x < s[0], x > s[-1]),
                                 jnp.isnan(x))
        if self.handle_invalid == "error":
            if bool(host_fetch(jnp.logical_and(invalid, frame.mask)).any()):
                raise ValueError("Bucketizer: values outside splits; set "
                                 "handle_invalid='keep' or 'skip'")
        elif self.handle_invalid == "keep":
            # Spark's 'keep': invalid values land in a special extra bucket
            # with index numBuckets (= len(splits) - 1)
            idx = jnp.where(invalid,
                            jnp.asarray(float(len(s) - 1), float_dtype()),
                            idx)
        out = frame.with_column(self.output_col, idx)
        if self.handle_invalid == "skip":
            out = out.filter(jnp.logical_not(invalid))
        return out


class _ScalerBase(Estimator):
    """Shared input/output-col builder surface for the feature scalers."""

    def __init__(self, input_col: str = "features",
                 output_col: str = "scaled_features"):
        self.input_col = input_col
        self.output_col = output_col

    def set_input_col(self, name: str):
        self.input_col = name
        return self

    setInputCol = set_input_col

    def set_output_col(self, name: str):
        self.output_col = name
        return self

    setOutputCol = set_output_col

    def _masked_feature_matrix(self, frame):
        """(n, d) feature matrix + (n,) mask weights on device."""
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        w = frame.mask.astype(X.dtype)
        return X, w


@jax.jit
def _masked_moments(X, w):
    """Mask-weighted count, mean, and sample variance — one fused pass."""
    n = jnp.sum(w)
    wc = w[:, None]
    mean = jnp.sum(X * wc, axis=0) / n
    centered = (X - mean) * wc
    var = jnp.sum(centered * centered, axis=0) / jnp.maximum(n - 1.0, 1.0)
    return n, mean, var


@jax.jit
def _masked_min_max(X, w):
    big = jnp.asarray(jnp.finfo(X.dtype).max, X.dtype)
    wc = w[:, None] > 0
    lo = jnp.min(jnp.where(wc, X, big), axis=0)
    hi = jnp.max(jnp.where(wc, X, -big), axis=0)
    return lo, hi


@persistable
class StandardScaler(_ScalerBase):
    """MLlib ``StandardScaler``: defaults ``with_mean=False, with_std=True``;
    sample (n−1) std; zero-variance features scale to 0.0."""

    _persist_attrs = ('input_col', 'output_col', 'with_mean', 'with_std')

    def __init__(self, input_col: str = "features",
                 output_col: str = "scaled_features",
                 with_mean: bool = False, with_std: bool = True):
        super().__init__(input_col, output_col)
        self.with_mean = with_mean
        self.with_std = with_std

    def set_with_mean(self, v: bool):
        self.with_mean = v
        return self

    setWithMean = set_with_mean

    def set_with_std(self, v: bool):
        self.with_std = v
        return self

    setWithStd = set_with_std

    def fit(self, frame) -> "StandardScalerModel":
        X, w = self._masked_feature_matrix(frame)
        _, mean, var = _masked_moments(X, w)
        return StandardScalerModel(np.asarray(mean), host_fetch(jnp.sqrt(var)),
                                   self.with_mean, self.with_std,
                                   self.input_col, self.output_col)


@persistable
class StandardScalerModel(Model):
    _persist_attrs = ('mean', 'std', 'with_mean', 'with_std', 'input_col', 'output_col')
    def __init__(self, mean, std, with_mean, with_std, input_col, output_col):
        self.mean = np.asarray(mean)
        self.std = np.asarray(std)
        self.with_mean = with_mean
        self.with_std = with_std
        self.input_col = input_col
        self.output_col = output_col

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        if self.with_mean:
            X = X - jnp.asarray(self.mean, X.dtype)
        if self.with_std:
            # MLlib: features with std == 0 map to 0.0 (scale factor 0).
            inv = np.where(self.std > 0, 1.0 / np.where(self.std > 0,
                                                        self.std, 1.0), 0.0)
            X = X * jnp.asarray(inv, X.dtype)
        return frame.with_column(self.output_col,
                                 X[:, 0] if squeeze else X)


@persistable
class MinMaxScaler(_ScalerBase):
    """MLlib ``MinMaxScaler``: rescale to [min, max] per feature; constant
    features map to ``(min+max)/2``."""

    _persist_attrs = ('input_col', 'output_col', 'min', 'max')

    def __init__(self, input_col: str = "features",
                 output_col: str = "scaled_features",
                 min: float = 0.0, max: float = 1.0):
        super().__init__(input_col, output_col)
        self.min = float(min)
        self.max = float(max)

    def set_min(self, v: float):
        self.min = float(v)
        return self

    setMin = set_min

    def set_max(self, v: float):
        self.max = float(v)
        return self

    setMax = set_max

    def fit(self, frame) -> "MinMaxScalerModel":
        X, w = self._masked_feature_matrix(frame)
        lo, hi = _masked_min_max(X, w)
        return MinMaxScalerModel(np.asarray(lo), np.asarray(hi),
                                 self.min, self.max,
                                 self.input_col, self.output_col)


@persistable
class MinMaxScalerModel(Model):
    _persist_attrs = ('original_min', 'original_max', 'min', 'max', 'input_col', 'output_col')
    def __init__(self, original_min, original_max, min, max,
                 input_col, output_col):
        self.original_min = np.asarray(original_min)
        self.original_max = np.asarray(original_max)
        self.min = min
        self.max = max
        self.input_col = input_col
        self.output_col = output_col

    originalMin = property(lambda self: self.original_min)
    originalMax = property(lambda self: self.original_max)

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        rng = self.original_max - self.original_min
        constant = rng == 0
        inv = np.where(constant, 0.0, 1.0 / np.where(constant, 1.0, rng))
        scaled = (X - jnp.asarray(self.original_min, X.dtype)) \
            * jnp.asarray(inv, X.dtype) * (self.max - self.min) + self.min
        half = 0.5 * (self.max + self.min)
        scaled = jnp.where(jnp.asarray(constant), jnp.asarray(half, X.dtype),
                           scaled)
        return frame.with_column(self.output_col,
                                 scaled[:, 0] if squeeze else scaled)


@persistable
class MaxAbsScaler(_ScalerBase):
    """MLlib ``MaxAbsScaler``: divide by per-feature max |x| (sparsity
    preserving); all-zero features stay 0."""

    _persist_attrs = ('input_col', 'output_col')

    def fit(self, frame) -> "MaxAbsScalerModel":
        X, w = self._masked_feature_matrix(frame)
        lo, hi = _masked_min_max(X, w)
        max_abs = np.maximum(np.abs(np.asarray(lo)), np.abs(np.asarray(hi)))
        return MaxAbsScalerModel(max_abs, self.input_col, self.output_col)


@persistable
class MaxAbsScalerModel(Model):
    _persist_attrs = ('max_abs', 'input_col', 'output_col')
    def __init__(self, max_abs, input_col, output_col):
        self.max_abs = np.asarray(max_abs)
        self.input_col = input_col
        self.output_col = output_col

    maxAbs = property(lambda self: self.max_abs)

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        inv = np.where(self.max_abs > 0,
                       1.0 / np.where(self.max_abs > 0, self.max_abs, 1.0), 0.0)
        X = X * jnp.asarray(inv, X.dtype)
        return frame.with_column(self.output_col, X[:, 0] if squeeze else X)


@persistable
class Imputer(Estimator):
    """MLlib ``Imputer``: replace missing values (NaN by default, or a
    configured ``missing_value`` sentinel) in numeric columns with the
    column's mean / median / mode, learned over valid rows only.

    Statistics are computed at the host boundary (median/mode are sort- and
    histogram-shaped, not device hot loops); the transform itself is a device
    ``jnp.where`` per column, fused by XLA with downstream ops.
    """

    _persist_attrs = ('input_cols', 'output_cols', 'strategy',
                      'missing_value')

    def __init__(self, input_cols: Optional[Sequence[str]] = None,
                 output_cols: Optional[Sequence[str]] = None,
                 strategy: str = "mean", missing_value: float = float("nan")):
        self.input_cols = list(input_cols) if input_cols else []
        self.output_cols = list(output_cols) if output_cols else []
        if strategy not in ("mean", "median", "mode"):
            raise ValueError(f"strategy={strategy!r} (mean|median|mode)")
        self.strategy = strategy
        self.missing_value = float(missing_value)

    def set_input_cols(self, v):
        self.input_cols = list(v)
        return self

    setInputCols = set_input_cols

    def set_output_cols(self, v):
        self.output_cols = list(v)
        return self

    setOutputCols = set_output_cols

    def set_strategy(self, v):
        if v not in ("mean", "median", "mode"):
            raise ValueError(f"strategy={v!r}")
        self.strategy = v
        return self

    setStrategy = set_strategy

    def set_missing_value(self, v):
        self.missing_value = float(v)
        return self

    setMissingValue = set_missing_value

    def _out_cols(self):
        return self.output_cols or self.input_cols

    def fit(self, frame) -> "ImputerModel":
        if not self.input_cols:
            raise ValueError("Imputer: input_cols not set")
        if self.output_cols and len(self.output_cols) != len(self.input_cols):
            raise ValueError("output_cols length must match input_cols")
        mask = np.asarray(frame.mask)
        surrogates = []
        for name in self.input_cols:
            x = np.asarray(frame._column_values(name), np.float64)[mask]
            miss = np.isnan(x) if np.isnan(self.missing_value) \
                else (x == self.missing_value)
            vals = x[~miss & ~np.isnan(x)]
            if len(vals) == 0:
                raise ValueError(f"Imputer: column {name!r} has no valid "
                                 "values to learn a surrogate from")
            if self.strategy == "mean":
                s = float(vals.mean())
            elif self.strategy == "median":
                s = float(np.median(vals))
            else:  # mode: most frequent, smallest on ties (Spark)
                uniq, cnt = np.unique(vals, return_counts=True)
                s = float(uniq[np.argmax(cnt)])
            surrogates.append(s)
        return ImputerModel(self.input_cols, self._out_cols(),
                            surrogates, self.missing_value)


@persistable
class ImputerModel(Model):
    _persist_attrs = ('input_cols', 'output_cols', 'surrogates',
                      'missing_value')

    def __init__(self, input_cols, output_cols, surrogates, missing_value):
        self.input_cols = list(input_cols)
        self.output_cols = list(output_cols)
        self.surrogates = [float(s) for s in surrogates]
        self.missing_value = float(missing_value)

    @property
    def surrogate_df(self):
        """The learned surrogates as a 1-row Frame (MLlib surrogateDF)."""
        from ..frame import Frame

        return Frame({c: [s] for c, s in zip(self.input_cols,
                                             self.surrogates)})

    surrogateDF = surrogate_df

    def transform(self, frame):
        for name, out, s in zip(self.input_cols, self.output_cols,
                                self.surrogates):
            x = jnp.asarray(frame._column_values(name), float_dtype())
            # NaN (the engine's null) is always missing — Spark imputes
            # nulls regardless of the configured missingValue sentinel
            miss = jnp.isnan(x)
            if not np.isnan(self.missing_value):
                miss = jnp.logical_or(miss, x == self.missing_value)
            frame = frame.with_column(out,
                                      jnp.where(miss, jnp.asarray(s, x.dtype),
                                                x))
        return frame


@persistable
class Normalizer(Transformer):
    """MLlib ``Normalizer``: scale each row of a vector column to unit
    p-norm (default p=2). Zero rows stay zero. Pure device elementwise —
    XLA fuses the norm and the divide into one kernel."""

    _persist_attrs = ('input_col', 'output_col', 'p')

    def __init__(self, input_col: str = "features",
                 output_col: str = "normalized_features", p: float = 2.0):
        self.input_col = input_col
        self.output_col = output_col
        if not p >= 1.0:
            raise ValueError("p must be >= 1")
        self.p = float(p)

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def set_p(self, v):
        if not v >= 1.0:
            raise ValueError("p must be >= 1")
        self.p = float(v)
        return self

    setP = set_p

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(X), axis=1, keepdims=True)
        elif self.p == 2.0:
            norm = jnp.sqrt(jnp.sum(X * X, axis=1, keepdims=True))
        elif self.p == 1.0:
            norm = jnp.sum(jnp.abs(X), axis=1, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(X) ** self.p, axis=1,
                           keepdims=True) ** (1.0 / self.p)
        out = jnp.where(norm > 0, X / jnp.where(norm > 0, norm, 1.0), X)
        return frame.with_column(self.output_col,
                                 out[:, 0] if squeeze else out)


@persistable
class Binarizer(Transformer):
    """MLlib ``Binarizer``: 1.0 where x > threshold else 0.0, on a scalar
    or vector column (NaN compares false → 0.0, as Spark's codegen does)."""

    _persist_attrs = ('threshold', 'input_col', 'output_col')

    def __init__(self, threshold: float = 0.0, input_col: str = None,
                 output_col: str = None):
        self.threshold = float(threshold)
        self.input_col = input_col
        self.output_col = output_col

    def set_threshold(self, v):
        self.threshold = float(v)
        return self

    setThreshold = set_threshold

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def transform(self, frame):
        x = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        out = jnp.where(x > self.threshold,
                        jnp.asarray(1.0, x.dtype), jnp.asarray(0.0, x.dtype))
        return frame.with_column(self.output_col, out)


@persistable
class PolynomialExpansion(Transformer):
    """MLlib ``PolynomialExpansion``: expand an (n, d) vector column into
    all monomials of total degree 1..``degree`` over the d features.

    The monomial *plan* (which feature-index multisets to multiply) is a
    tiny host-side enumeration; the expansion itself is one stacked device
    product per monomial, fused by XLA — the MXU-friendly dense layout is
    preserved (output is a single (n, D) matrix). Ordering: grouped by
    degree, lexicographic within a degree (MLlib interleaves; the *set* of
    monomials is identical, only column order differs — documented because
    downstream fits are order-insensitive)."""

    _persist_attrs = ('degree', 'input_col', 'output_col')

    def __init__(self, degree: int = 2, input_col: str = "features",
                 output_col: str = "poly_features"):
        if degree < 1:
            raise ValueError("degree must be >= 1")
        self.degree = int(degree)
        self.input_col = input_col
        self.output_col = output_col

    def set_degree(self, v):
        if v < 1:
            raise ValueError("degree must be >= 1")
        self.degree = int(v)
        return self

    setDegree = set_degree

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def transform(self, frame):
        from itertools import combinations_with_replacement

        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        d = X.shape[1]
        cols = []
        for deg in range(1, self.degree + 1):
            for combo in combinations_with_replacement(range(d), deg):
                term = X[:, combo[0]]
                for j in combo[1:]:
                    term = term * X[:, j]
                cols.append(term)
        return frame.with_column(self.output_col, jnp.stack(cols, axis=1))


@persistable
class QuantileDiscretizer(Estimator):
    """MLlib ``QuantileDiscretizer``: learn ``num_buckets`` quantile split
    points over the valid rows and return a :class:`Bucketizer` with open
    (±inf) outer splits. Exact quantiles (the reference engine's
    approxQuantile relative-error knob is unnecessary at this scale);
    duplicate quantiles collapse, so the fitted bucketizer may have fewer
    buckets, exactly like Spark."""

    _persist_attrs = ('num_buckets', 'input_col', 'output_col',
                      'handle_invalid')

    def __init__(self, num_buckets: int = 2, input_col: str = None,
                 output_col: str = None, handle_invalid: str = "error"):
        if num_buckets < 2:
            raise ValueError("num_buckets must be >= 2")
        self.num_buckets = int(num_buckets)
        self.input_col = input_col
        self.output_col = output_col
        self.handle_invalid = handle_invalid

    def set_num_buckets(self, v):
        if v < 2:
            raise ValueError("num_buckets must be >= 2")
        self.num_buckets = int(v)
        return self

    setNumBuckets = set_num_buckets

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def set_handle_invalid(self, v):
        self.handle_invalid = v
        return self

    setHandleInvalid = set_handle_invalid

    def fit(self, frame) -> "Bucketizer":
        mask = np.asarray(frame.mask)
        x = np.asarray(frame._column_values(self.input_col),
                       np.float64)[mask]
        x = x[~np.isnan(x)]
        if len(x) == 0:
            raise ValueError("QuantileDiscretizer: no valid rows to fit on")
        qs = np.quantile(x, np.linspace(0, 1, self.num_buckets + 1)[1:-1])
        inner = np.unique(qs)  # duplicate quantiles collapse (Spark)
        splits = [-float("inf"), *inner.tolist(), float("inf")]
        return Bucketizer(splits, self.input_col, self.output_col,
                          self.handle_invalid)


@persistable
class PCA(Estimator):
    """MLlib ``PCA``: learn the top-k principal components of a vector
    column. Fit is one masked covariance (a single MXU matmul over the
    row-sharded data, psum-reduced under a mesh) + a device ``eigh`` on the
    tiny (d, d) matrix. Transform follows MLlib exactly: rows are projected
    onto the components **without** mean subtraction (Spark's documented
    behavior — the components themselves come from the centered covariance,
    but ``transform`` multiplies raw rows)."""

    _persist_attrs = ('k', 'input_col', 'output_col')

    def __init__(self, k: int = None, input_col: str = "features",
                 output_col: str = "pca_features"):
        self.k = k
        self.input_col = input_col
        self.output_col = output_col

    def set_k(self, v):
        self.k = int(v)
        return self

    setK = set_k

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def fit(self, frame) -> "PCAModel":
        if not self.k or self.k < 1:
            raise ValueError("PCA: k must be a positive integer")
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        d = X.shape[1]
        if self.k > d:
            raise ValueError(f"k={self.k} exceeds the {d} input features")
        if int(np.asarray(frame.mask).sum()) == 0:
            raise ValueError("PCA: no valid rows to fit on")
        w = frame.mask.astype(X.dtype)
        n = jnp.sum(w)
        mean = jnp.sum(X * w[:, None], axis=0) / n
        C = (X - mean) * w[:, None]
        cov = (C.T @ C) / jnp.maximum(n - 1.0, 1.0)      # sample covariance
        vals, vecs = jnp.linalg.eigh(cov)                # ascending order
        vals = vals[::-1][: self.k]
        vecs = vecs[:, ::-1][:, : self.k]                # (d, k) columns
        # deterministic sign: largest-|.| element of each component positive
        vecs_np = np.asarray(vecs)
        signs = np.sign(vecs_np[np.argmax(np.abs(vecs_np), axis=0),
                                np.arange(self.k)])
        signs[signs == 0] = 1.0
        total = float(host_fetch(jnp.sum(jnp.clip(jnp.diagonal(cov),
                                                  0.0, None))))
        ev = np.clip(np.asarray(vals), 0.0, None)
        ratios = ev / total if total > 0 else np.zeros_like(ev)
        return PCAModel(vecs_np * signs, ratios, self.k,
                        self.input_col, self.output_col)


@persistable
class PCAModel(Model):
    _persist_attrs = ('pc', 'explained_variance', 'k', 'input_col',
                      'output_col')

    def __init__(self, pc, explained_variance, k, input_col, output_col):
        self.pc = np.asarray(pc)                         # (d, k)
        self.explained_variance = np.asarray(explained_variance)
        self.k = int(k)
        self.input_col = input_col
        self.output_col = output_col

    explainedVariance = property(lambda self: self.explained_variance)

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        return frame.with_column(self.output_col,
                                 X @ jnp.asarray(self.pc, X.dtype))


@persistable
class Interaction(Transformer):
    """MLlib ``Interaction``: the per-row tensor (Kronecker) product of the
    input columns — scalars or vectors — as one output vector of dimension
    ∏ dᵢ. TPU-first: built as a chain of broadcasted outer products
    reshaped flat, one fused elementwise kernel, no per-row work.
    (spark.ml.feature surface, `/root/reference/pom.xml:29-32`.)"""

    _persist_attrs = ('input_cols', 'output_col')

    def __init__(self, input_cols: Optional[Sequence[str]] = None,
                 output_col: str = "interacted"):
        self.input_cols = list(input_cols) if input_cols else []
        self.output_col = output_col

    def set_input_cols(self, v):
        self.input_cols = list(v)
        return self

    setInputCols = set_input_cols

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def transform(self, frame):
        if len(self.input_cols) < 2:
            raise ValueError("Interaction needs at least two input columns")
        dt = float_dtype()
        out = None
        for name in self.input_cols:
            arr = jnp.asarray(frame._column_values(name), dt)
            if arr.ndim == 1:
                arr = arr[:, None]
            if out is None:
                out = arr
            else:
                n = out.shape[0]
                out = (out[:, :, None] * arr[:, None, :]).reshape(n, -1)
        return frame.with_column(self.output_col, out)


@persistable
class SQLTransformer(Transformer):
    """MLlib ``SQLTransformer``: a SQL statement over the placeholder view
    ``__THIS__`` — wired straight into the framework's own SQL engine
    (sql/parser.py), so the full supported SELECT surface (CAST, WHERE,
    CASE, window functions, ...) is available in pipelines."""

    _persist_attrs = ('statement',)

    def __init__(self, statement: Optional[str] = None):
        self.statement = statement

    def set_statement(self, v):
        self.statement = v
        return self

    setStatement = set_statement

    def get_statement(self):
        return self.statement

    getStatement = get_statement

    def transform(self, frame):
        if not self.statement:
            raise ValueError("SQLTransformer: statement not set")
        import uuid

        from ..sql.catalog import default_catalog
        from ..sql.parser import execute

        # run against the session catalog (so joins against registered
        # temp views work, like Spark), registering the placeholder under
        # a collision-free name and always dropping it afterwards
        view = f"sql_transformer_{uuid.uuid4().hex[:12]}"
        cat = default_catalog()
        cat.register(view, frame)
        try:
            return execute(self.statement.replace("__THIS__", view), cat)
        finally:
            cat.drop(view)


@persistable
class VectorIndexer(Estimator):
    """MLlib ``VectorIndexer``: scan a vector column; every feature with
    ≤ ``max_categories`` distinct values becomes categorical and is
    re-encoded to 0..k−1 category indices (by value order); the rest pass
    through. The scan is one host pass over the fitted column; transform
    is a vectorized ``searchsorted`` per categorical feature."""

    _persist_attrs = ('input_col', 'output_col', 'max_categories',
                      'handle_invalid')

    def __init__(self, max_categories: int = 20,
                 input_col: str = "features",
                 output_col: str = "indexed",
                 handle_invalid: str = "error"):
        if max_categories < 2:
            raise ValueError("max_categories must be >= 2")
        if handle_invalid not in ("error", "keep"):
            raise ValueError(f"handle_invalid={handle_invalid!r}")
        self.max_categories = int(max_categories)
        self.input_col = input_col
        self.output_col = output_col
        self.handle_invalid = handle_invalid

    def set_max_categories(self, v):
        if v < 2:
            raise ValueError("max_categories must be >= 2")
        self.max_categories = int(v)
        return self

    setMaxCategories = set_max_categories

    def set_input_col(self, v):
        self.input_col = v
        return self

    setInputCol = set_input_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def fit(self, frame) -> "VectorIndexerModel":
        X = np.asarray(frame._column_values(self.input_col), np.float64)
        if X.ndim == 1:
            X = X[:, None]
        mask = np.asarray(frame.mask)
        Xv = X[mask]
        category_maps = {}
        for j in range(X.shape[1]):
            uniq = np.unique(Xv[:, j])
            uniq = uniq[~np.isnan(uniq)]
            # 0 observed values (all-NaN/all-masked) ⇒ treat as continuous
            # passthrough rather than an empty, untransformable map
            if 0 < len(uniq) <= self.max_categories:
                category_maps[j] = uniq.tolist()
        return VectorIndexerModel(X.shape[1], category_maps,
                                  self.input_col, self.output_col,
                                  self.handle_invalid)


@persistable
class VectorIndexerModel(Model):
    _persist_attrs = ('num_features', '_category_maps_json', 'input_col',
                      'output_col', 'handle_invalid')

    def __init__(self, num_features, category_maps, input_col="features",
                 output_col="indexed", handle_invalid="error"):
        self.num_features = int(num_features)
        self.category_maps = {int(k): list(v)
                              for k, v in category_maps.items()}
        # JSON keys are strings; persist through a string-keyed mirror
        self._category_maps_json = {str(k): list(v)
                                    for k, v in self.category_maps.items()}
        self.input_col = input_col
        self.output_col = output_col
        self.handle_invalid = handle_invalid

    def _post_load(self):
        self.category_maps = {int(k): list(v)
                              for k, v in self._category_maps_json.items()}

    @property
    def category_maps_(self):
        return dict(self.category_maps)

    categoryMaps = category_maps_

    def transform(self, frame):
        X = np.asarray(frame._column_values(self.input_col), np.float64)
        if X.ndim == 1:
            X = X[:, None]
        mask = np.asarray(frame.mask)
        out = X.copy()
        for j, cats in self.category_maps.items():
            cats_arr = np.asarray(cats, np.float64)
            idx = np.searchsorted(cats_arr, X[:, j])
            idx_c = np.clip(idx, 0, len(cats_arr) - 1)
            known = cats_arr[idx_c] == X[:, j]
            is_nan = np.isnan(X[:, j])
            if self.handle_invalid == "error":
                bad = mask & ~known & ~is_nan
                if bad.any():
                    raise ValueError(
                        f"VectorIndexer: unseen category "
                        f"{X[bad, j][0]!r} in feature {j}")
                # NaN stays NaN (it is not a category), never index k−1
                out[:, j] = np.where(is_nan, np.nan, idx_c)
            else:   # keep → unseen (incl. NaN) gets index k
                out[:, j] = np.where(known & ~is_nan, idx_c, len(cats_arr))
        return frame.with_column(self.output_col,
                                 jnp.asarray(out, float_dtype()))


@persistable
class ChiSqSelector(Estimator):
    """MLlib ``ChiSqSelector``: pick features by the χ² independence test
    against a categorical label. ``selector_type``: ``numTopFeatures``
    (default, smallest p-values first), ``percentile``, or ``fpr``.
    The per-feature contingency tables are one-hot matmuls (see
    ``stat.ChiSquareTest``)."""

    _persist_attrs = ('num_top_features', 'selector_type', 'percentile',
                      'fpr', 'features_col', 'label_col', 'output_col')

    def __init__(self, num_top_features: int = 50,
                 selector_type: str = "numTopFeatures",
                 percentile: float = 0.1, fpr: float = 0.05,
                 features_col: str = "features", label_col: str = "label",
                 output_col: str = "selected"):
        if selector_type not in ("numTopFeatures", "percentile", "fpr"):
            raise ValueError(f"selector_type={selector_type!r}")
        self.num_top_features = int(num_top_features)
        self.selector_type = selector_type
        self.percentile = float(percentile)
        self.fpr = float(fpr)
        self.features_col = features_col
        self.label_col = label_col
        self.output_col = output_col

    def set_num_top_features(self, v):
        self.num_top_features = int(v)
        return self

    setNumTopFeatures = set_num_top_features

    def set_selector_type(self, v):
        if v not in ("numTopFeatures", "percentile", "fpr"):
            raise ValueError(f"selector_type={v!r}")
        self.selector_type = v
        return self

    setSelectorType = set_selector_type

    def set_percentile(self, v):
        self.percentile = float(v)
        return self

    setPercentile = set_percentile

    def set_fpr(self, v):
        self.fpr = float(v)
        return self

    setFpr = set_fpr

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def set_label_col(self, v):
        self.label_col = v
        return self

    setLabelCol = set_label_col

    def set_output_col(self, v):
        self.output_col = v
        return self

    setOutputCol = set_output_col

    def fit(self, frame) -> "ChiSqSelectorModel":
        from .stat import ChiSquareTest

        res = ChiSquareTest.test(frame, self.features_col,
                                 self.label_col).to_pydict()
        p_values = np.asarray(res["pValues"][0], np.float64)
        d = len(p_values)
        order = np.argsort(p_values, kind="stable")
        if self.selector_type == "numTopFeatures":
            chosen = order[: self.num_top_features]
        elif self.selector_type == "percentile":
            chosen = order[: max(1, int(d * self.percentile))]
        else:   # fpr
            chosen = np.flatnonzero(p_values < self.fpr)
        return ChiSqSelectorModel(sorted(int(i) for i in chosen),
                                  self.features_col, self.output_col)


class _SelectorModelBase(Model):
    """Shared surface of the feature selectors: a list of selected indices
    + a gather transform (an empty selection yields an (n, 0) column, the
    MLlib behavior)."""

    _persist_attrs = ('selected_features', 'features_col', 'output_col')

    def __init__(self, selected_features, features_col="features",
                 output_col="selected"):
        self.selected_features = [int(i) for i in selected_features]
        self.features_col = features_col
        self.output_col = output_col

    selectedFeatures = property(lambda self: list(self.selected_features))

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.features_col),
                        float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        sel = jnp.asarray(np.asarray(self.selected_features, np.int32))
        return frame.with_column(self.output_col, X[:, sel])


@persistable
class ChiSqSelectorModel(_SelectorModelBase):
    pass


def _is_string_col(arr) -> bool:
    """The frame's canonical string-column test, tolerant of raw lists and
    numpy 'U'/'S' arrays that have not passed through Frame normalization."""
    from ..frame.frame import _is_string_col as _frame_is_string

    a = np.asarray(arr) if not isinstance(arr, np.ndarray) else arr
    if getattr(a, "dtype", None) is not None and a.dtype.kind in ("U", "S"):
        return True
    try:
        return _frame_is_string(a)
    except TypeError:
        return a.dtype == object


def _parse_r_formula(formula: str):
    """``label ~ term + term - term`` → (label, include_terms,
    exclude_terms); a term is a tuple of column names (len > 1 ⇒ ``:``
    interaction). ``.`` means "all other columns"."""
    if "~" not in formula:
        raise ValueError(f"RFormula: missing '~' in {formula!r}")
    lhs, rhs = formula.split("~", 1)
    label = lhs.strip()
    include, exclude = [], []
    # split on +/- at top level, tracking sign
    sign, token = 1, ""
    tokens = []
    for ch in rhs + "+":
        if ch in "+-":
            if token.strip():
                tokens.append((sign, token.strip()))
            sign = 1 if ch == "+" else -1
            token = ""
        else:
            token += ch
    for sg, tok in tokens:
        term = tuple(t.strip() for t in tok.split(":"))
        if any(not t for t in term):
            raise ValueError(f"RFormula: empty term in {formula!r}")
        (include if sg > 0 else exclude).append(term)
    return label, include, exclude


@persistable
class RFormula(Estimator):
    """MLlib ``RFormula``: R-style model formulas — ``label ~ col1 + col2``,
    ``.`` (all other columns), ``-`` (exclusion), ``:`` (interaction).
    Numeric terms pass through; string terms are StringIndexed
    (frequencyDesc) and dummy-coded with the last category dropped, exactly
    Spark's encoding. Produces ``features`` + ``label`` columns.
    (spark.ml.feature surface, `/root/reference/pom.xml:29-32`.)"""

    _persist_attrs = ('formula', 'features_col', 'label_col',
                      'force_index_label')

    def __init__(self, formula: Optional[str] = None,
                 features_col: str = "features", label_col: str = "label",
                 force_index_label: bool = False):
        self.formula = formula
        self.features_col = features_col
        self.label_col = label_col
        self.force_index_label = bool(force_index_label)

    def set_formula(self, v):
        self.formula = v
        return self

    setFormula = set_formula

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def set_label_col(self, v):
        self.label_col = v
        return self

    setLabelCol = set_label_col

    def set_force_index_label(self, v):
        self.force_index_label = bool(v)
        return self

    setForceIndexLabel = set_force_index_label

    def _encode_col(self, frame, col):
        """One column → encoder spec: ("num", col) or ("cat", col, labels)."""
        values = frame._column_values(col)
        if not _is_string_col(values):
            return ("num", col)
        model = StringIndexer(input_col=col, output_col="_idx").fit(frame)
        return ("cat", col, model.labels)

    def fit(self, frame) -> "RFormulaModel":
        if not self.formula:
            raise ValueError("RFormula: formula not set")
        label, include, exclude = _parse_r_formula(self.formula)
        excluded = {t[0] for t in exclude if len(t) == 1}
        terms = []
        for term in include:
            if term == (".",):
                for c in frame.columns:
                    if c != label and c not in excluded and \
                            (c,) not in terms:
                        terms.append((c,))
            elif term not in terms:
                terms.append(term)
        terms = [t for t in terms if t not in exclude]

        encoders = [[self._encode_col(frame, c) for c in t] for t in terms]
        label_labels = None
        if label:
            lv = frame._column_values(label)
            if _is_string_col(lv) or self.force_index_label:
                label_labels = StringIndexer(
                    input_col=label, output_col="_l").fit(frame).labels
        return RFormulaModel(encoders, label, label_labels,
                             self.features_col, self.label_col)


@persistable
class RFormulaModel(Model):
    _persist_attrs = ('_encoders_json', 'label_source', 'label_labels',
                      'features_col', 'label_col')

    def __init__(self, encoders=None, label_source="", label_labels=None,
                 features_col="features", label_col="label"):
        self.encoders = encoders or []
        self._encoders_json = [[list(e) for e in term]
                               for term in self.encoders]
        self.label_source = label_source
        self.label_labels = (None if label_labels is None
                             else list(label_labels))
        self.features_col = features_col
        self.label_col = label_col

    def _post_load(self):
        self.encoders = [[tuple(e) for e in term]
                         for term in self._encoders_json]

    def _encode_one(self, frame, enc):
        """Encoder spec → (n, k) float matrix."""
        kind = enc[0]
        if kind == "num":
            arr = np.asarray(frame._column_values(enc[1]), np.float64)
            return arr[:, None] if arr.ndim == 1 else arr
        _, col, labels = enc
        values = np.asarray(frame._column_values(col), object)
        lut = {l: i for i, l in enumerate(labels)}
        k = len(labels)
        idx = np.asarray([lut.get(str(v) if v is not None else None, k)
                          for v in values])
        mask = np.asarray(frame.mask)
        unseen = mask & (idx == k)
        if unseen.any():
            # an unseen category would otherwise dummy-code identically to
            # the dropped reference level; Spark's RFormula errors too
            bad = sorted({str(values[i])
                          for i in np.flatnonzero(unseen)})[:5]
            raise ValueError(f"RFormula: unseen categories {bad} in "
                             f"column {col!r}")
        onehot = np.zeros((len(values), max(k - 1, 1)), np.float64)
        known = idx < k - 1   # last category → all-zero row (dropLast)
        onehot[np.arange(len(values))[known], idx[known]] = 1.0
        if k == 1:            # single category: dropLast leaves zero width
            return onehot[:, :0]
        return onehot

    def transform(self, frame):
        mats = []
        for term in self.encoders:
            mat = None
            for enc in term:
                m = self._encode_one(frame, enc)
                if mat is None:
                    mat = m
                else:   # ':' interaction = per-row outer product, flattened
                    n = mat.shape[0]
                    mat = (mat[:, :, None] * m[:, None, :]).reshape(n, -1)
            if mat is not None and mat.shape[1] > 0:
                mats.append(mat)
        if not mats:
            raise ValueError("RFormula produced no feature columns")
        X = np.concatenate(mats, axis=1)
        out = frame.with_column(self.features_col,
                                jnp.asarray(X, float_dtype()))
        if self.label_source:
            lv = frame._column_values(self.label_source)
            if self.label_labels is not None:
                lut = {l: i for i, l in enumerate(self.label_labels)}
                y = np.asarray([float(lut.get(str(v), np.nan))
                                for v in np.asarray(lv, object)])
            else:
                y = np.asarray(lv, np.float64)
            out = out.with_column(self.label_col,
                                  jnp.asarray(y, float_dtype()))
        return out


@persistable
class ElementwiseProduct(Transformer):
    """MLlib ``ElementwiseProduct``: Hadamard product of each row with a
    fixed ``scaling_vec`` — one fused VPU multiply."""

    _persist_attrs = ('scaling_vec', 'input_col', 'output_col')

    def __init__(self, scaling_vec=None, input_col: str = "features",
                 output_col: str = "scaled_features"):
        self.scaling_vec = None if scaling_vec is None \
            else np.asarray(scaling_vec, np.float64)
        self.input_col = input_col
        self.output_col = output_col

    def set_scaling_vec(self, v):
        self.scaling_vec = np.asarray(v, np.float64)
        return self

    def set_input_col(self, v):
        self.input_col = v
        return self

    def set_output_col(self, v):
        self.output_col = v
        return self

    setScalingVec = set_scaling_vec
    setInputCol = set_input_col
    setOutputCol = set_output_col

    def transform(self, frame):
        if self.scaling_vec is None:
            raise ValueError("ElementwiseProduct: scaling_vec not set")
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        v = jnp.asarray(self.scaling_vec, X.dtype)
        if v.shape[0] != X.shape[1]:
            raise ValueError(f"scaling_vec length {v.shape[0]} != "
                             f"vector size {X.shape[1]}")
        return frame.with_column(self.output_col, X * v[None, :])


@persistable
class VectorSlicer(Transformer):
    """MLlib ``VectorSlicer``: select a subset of vector indices — one
    device gather."""

    _persist_attrs = ('indices', 'input_col', 'output_col')

    def __init__(self, indices=(), input_col: str = "features",
                 output_col: str = "sliced_features"):
        self.indices = [int(i) for i in indices]
        self.input_col = input_col
        self.output_col = output_col

    def set_indices(self, v):
        self.indices = [int(i) for i in v]
        return self

    def set_input_col(self, v):
        self.input_col = v
        return self

    def set_output_col(self, v):
        self.output_col = v
        return self

    setIndices = set_indices
    setInputCol = set_input_col
    setOutputCol = set_output_col

    def transform(self, frame):
        if not self.indices:
            raise ValueError("VectorSlicer: indices not set")
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        if X.ndim == 1:
            X = X[:, None]
        d = X.shape[1]
        if any(i < 0 or i >= d for i in self.indices):
            raise ValueError(f"indices out of range for vector size {d}")
        return frame.with_column(
            self.output_col, X[:, jnp.asarray(self.indices, jnp.int32)])


@persistable
class DCT(Transformer):
    """MLlib ``DCT``: orthonormal 1-D DCT-II (or its inverse, DCT-III) of
    each row. TPU-first: the transform is ONE ``(n,d)×(d,d)`` MXU matmul
    against a precomputed orthonormal cosine basis — the scaled output
    matches MLlib's jTransforms ``forward(..., true)`` convention."""

    _persist_attrs = ('inverse', 'input_col', 'output_col')

    def __init__(self, inverse: bool = False, input_col: str = "features",
                 output_col: str = "dct_features"):
        self.inverse = bool(inverse)
        self.input_col = input_col
        self.output_col = output_col

    def set_inverse(self, v):
        self.inverse = bool(v)
        return self

    def set_input_col(self, v):
        self.input_col = v
        return self

    def set_output_col(self, v):
        self.output_col = v
        return self

    setInverse = set_inverse
    setInputCol = set_input_col
    setOutputCol = set_output_col

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _basis(d: int, dtype_name: str):
        """Orthonormal DCT-II matrix B (d, d): y = B @ x."""
        k = np.arange(d)[:, None]
        i = np.arange(d)[None, :]
        B = np.cos(np.pi * k * (2 * i + 1) / (2 * d))
        B *= np.sqrt(2.0 / d)
        B[0] *= 1.0 / np.sqrt(2.0)
        return jnp.asarray(B, dtype_name)

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        B = self._basis(X.shape[1], str(X.dtype))
        out = X @ (B if self.inverse else B.T)  # inverse: Bᵀ orthonormality
        return frame.with_column(self.output_col,
                                 out[:, 0] if squeeze else out)


@persistable
class FeatureHasher(Transformer):
    """MLlib ``FeatureHasher``: hash any mix of numeric and string columns
    into one fixed-dimension vector. Numeric column → bucket(hash(name)),
    value added; string column → bucket(hash(name=value)), +1. Hashing is
    per unique (column, value) pair on host; the scatter is one
    ``np.add.at`` (same vectorized shape as HashingTF)."""

    _persist_attrs = ('num_features', 'input_cols', 'output_col')

    def __init__(self, num_features: int = 1024, input_cols=(),
                 output_col: str = "features"):
        if num_features < 1:
            raise ValueError("num_features must be >= 1")
        self.num_features = int(num_features)
        self.input_cols = list(input_cols)
        self.output_col = output_col

    def set_num_features(self, v):
        if v < 1:
            raise ValueError("num_features must be >= 1")
        self.num_features = int(v)
        return self

    def set_input_cols(self, v):
        self.input_cols = list(v)
        return self

    def set_output_col(self, v):
        self.output_col = v
        return self

    setNumFeatures = set_num_features
    setInputCols = set_input_cols
    setOutputCol = set_output_col

    def transform(self, frame):
        from .text import _stable_hash

        if not self.input_cols:
            raise ValueError("FeatureHasher: input_cols not set")
        first = frame._column_values(self.input_cols[0])
        n = int(np.asarray(first).shape[0])
        M = np.zeros((n, self.num_features), np.dtype(float_dtype()))
        rows = np.arange(n)
        for name in self.input_cols:
            arr = frame._column_values(name)
            if _is_string_col(arr):
                vals = np.asarray(
                    ["" if v is None else str(v) for v in arr])
                uniq, inv = np.unique(vals, return_inverse=True)
                buckets = np.fromiter(
                    (_stable_hash(f"{name}={u}", self.num_features)
                     for u in uniq), np.int64, count=uniq.size)
                present = np.asarray([v is not None for v in arr])
                np.add.at(M, (rows[present], buckets[inv][present]), 1.0)
            else:
                j = _stable_hash(name, self.num_features)
                col = np.asarray(arr, np.float64)
                M[:, j] += np.where(np.isfinite(col), col, 0.0)
        return frame.with_column(self.output_col, jnp.asarray(M))


@persistable
class RobustScaler(_ScalerBase):
    """MLlib ``RobustScaler``: center by median, scale by IQR (quantile
    range). Quantiles are a host pass over valid rows (data-dependent
    order statistics — same boundary as QuantileDiscretizer); the
    transform is one fused device subtract/divide."""

    _persist_attrs = ('with_centering', 'with_scaling', 'lower', 'upper',
                      'input_col', 'output_col')

    def __init__(self, with_centering: bool = False,
                 with_scaling: bool = True, lower: float = 0.25,
                 upper: float = 0.75, input_col: str = "features",
                 output_col: str = "scaled_features"):
        super().__init__(input_col, output_col)
        self.with_centering = bool(with_centering)
        self.with_scaling = bool(with_scaling)
        self.lower = float(lower)
        self.upper = float(upper)
        self._check_bounds()

    def set_with_centering(self, v):
        self.with_centering = bool(v)
        return self

    def set_with_scaling(self, v):
        self.with_scaling = bool(v)
        return self

    def set_lower(self, v):
        self.lower = float(v)
        self._check_bounds()
        return self

    def set_upper(self, v):
        self.upper = float(v)
        self._check_bounds()
        return self

    def _check_bounds(self):
        if not 0.0 <= self.lower < self.upper <= 1.0:
            raise ValueError("need 0 <= lower < upper <= 1")

    setWithCentering = set_with_centering
    setWithScaling = set_with_scaling
    setLower = set_lower
    setUpper = set_upper

    def fit(self, frame) -> "RobustScalerModel":
        self._check_bounds()
        X = np.asarray(frame._column_values(self.input_col),
                       np.dtype(float_dtype()))
        if X.ndim == 1:
            X = X[:, None]
        mask = np.asarray(frame.mask)
        if mask.sum() == 0:
            raise ValueError("RobustScaler: no valid rows")
        Xv = X[mask]
        d = Xv.shape[1]
        # NaN values are ignored in the statistics (MLlib convention); each
        # pass is skipped entirely when its statistic is unused
        with np.errstate(all="ignore"):
            med = np.nanmedian(Xv, axis=0) if self.with_centering \
                else np.zeros(d)
            if self.with_scaling:
                rng = (np.nanquantile(Xv, self.upper, axis=0)
                       - np.nanquantile(Xv, self.lower, axis=0))
                # MLlib: zero-range (constant) features map to 0.0
                scale = np.where(np.nan_to_num(rng) > 0, 1.0 / rng, 0.0)
            else:
                scale = np.ones(d)
        med = np.nan_to_num(med)     # all-NaN column: center 0, scale 0
        return RobustScalerModel(med, scale, self.input_col,
                                 self.output_col)


@persistable
class RobustScalerModel(Model):
    """``scale`` is the multiplicative factor (0 for zero-range features —
    the MLlib convention StandardScalerModel also follows)."""

    _persist_attrs = ('median', 'scale', 'input_col', 'output_col')

    def __init__(self, median, scale, input_col="features",
                 output_col="scaled_features"):
        self.median = np.asarray(median, np.float64)
        self.scale = np.asarray(scale, np.float64)
        self.input_col = input_col
        self.output_col = output_col

    def transform(self, frame):
        X = jnp.asarray(frame._column_values(self.input_col), float_dtype())
        squeeze = X.ndim == 1
        if squeeze:
            X = X[:, None]
        out = (X - jnp.asarray(self.median, X.dtype)) \
            * jnp.asarray(self.scale, X.dtype)
        return frame.with_column(self.output_col,
                                 out[:, 0] if squeeze else out)


@persistable
class VarianceThresholdSelector(Estimator):
    """MLlib ``VarianceThresholdSelector``: keep features whose (sample)
    variance exceeds ``variance_threshold`` — ONE masked moment pass on
    device (the Summarizer statistic), selection is a gather."""

    _persist_attrs = ('variance_threshold', 'features_col', 'output_col')

    def __init__(self, variance_threshold: float = 0.0,
                 features_col: str = "features",
                 output_col: str = "selected_features"):
        self.variance_threshold = float(variance_threshold)
        self.features_col = features_col
        self.output_col = output_col

    def set_variance_threshold(self, v):
        self.variance_threshold = float(v)
        return self

    def set_features_col(self, v):
        self.features_col = v
        return self

    def set_output_col(self, v):
        self.output_col = v
        return self

    setVarianceThreshold = set_variance_threshold
    setFeaturesCol = set_features_col
    setOutputCol = set_output_col

    def fit(self, frame) -> "VarianceThresholdSelectorModel":
        from .stat import _extract, _moment_pass

        if not np.asarray(frame.mask).any():
            raise ValueError("VarianceThresholdSelector: no valid rows")
        X, w = _extract(frame, self.features_col)
        n, _, C, *_ = _moment_pass(X, w)
        var = np.diag(np.asarray(C)) / max(float(n) - 1.0, 1.0)
        keep = np.nonzero(var > self.variance_threshold)[0]
        # empty selection is a valid model (MLlib; ChiSqSelector's fpr
        # path behaves the same) — transform yields an (n, 0) column
        return VarianceThresholdSelectorModel(
            keep.astype(np.int64).tolist(), self.features_col,
            self.output_col)


@persistable
class VarianceThresholdSelectorModel(_SelectorModelBase):
    def __init__(self, selected_features, features_col="features",
                 output_col="selected_features"):
        super().__init__(selected_features, features_col, output_col)


@persistable
class UnivariateFeatureSelector(Estimator):
    """MLlib ``UnivariateFeatureSelector``: score every feature against the
    label with the test implied by (featureType, labelType) — χ² for
    categorical/categorical, ANOVA F for continuous features vs categorical
    label, F-regression for continuous/continuous — then select by mode
    (numTopFeatures | percentile | fpr | fdr | fwe).

    TPU-first: all three statistics come from one-hot / moment matmuls over
    masked rows (the ChiSquareTest & Summarizer passes); only the final
    p-value tail probabilities use scipy on the tiny (d,) statistics.
    """

    _persist_attrs = ('feature_type', 'label_type', 'selection_mode',
                      'selection_threshold', 'features_col', 'label_col',
                      'output_col')

    _MODES = ("numTopFeatures", "percentile", "fpr", "fdr", "fwe")

    def __init__(self, feature_type: str = "continuous",
                 label_type: str = "categorical",
                 selection_mode: str = "numTopFeatures",
                 selection_threshold: Optional[float] = None,
                 features_col: str = "features", label_col: str = "label",
                 output_col: str = "selected_features"):
        if feature_type not in ("categorical", "continuous"):
            raise ValueError(f"feature_type={feature_type!r}")
        if label_type not in ("categorical", "continuous"):
            raise ValueError(f"label_type={label_type!r}")
        if selection_mode not in self._MODES:
            raise ValueError(f"selection_mode={selection_mode!r}; "
                             f"expected one of {self._MODES}")
        self.feature_type = feature_type
        self.label_type = label_type
        self.selection_mode = selection_mode
        self.selection_threshold = selection_threshold
        self.features_col = features_col
        self.label_col = label_col
        self.output_col = output_col

    def set_feature_type(self, v):
        if v not in ("categorical", "continuous"):
            raise ValueError(f"feature_type={v!r}")
        self.feature_type = v
        return self

    def set_label_type(self, v):
        if v not in ("categorical", "continuous"):
            raise ValueError(f"label_type={v!r}")
        self.label_type = v
        return self

    def set_selection_mode(self, v):
        if v not in self._MODES:
            raise ValueError(f"selection_mode={v!r}")
        self.selection_mode = v
        return self

    def set_selection_threshold(self, v):
        self.selection_threshold = float(v)
        return self

    def set_features_col(self, v):
        self.features_col = v
        return self

    def set_label_col(self, v):
        self.label_col = v
        return self

    def set_output_col(self, v):
        self.output_col = v
        return self

    setFeatureType = set_feature_type
    setLabelType = set_label_type
    setSelectionMode = set_selection_mode
    setSelectionThreshold = set_selection_threshold
    setFeaturesCol = set_features_col
    setLabelCol = set_label_col
    setOutputCol = set_output_col

    def _p_values(self, X, y):
        """(d,) p-values for the CONTINUOUS-feature tests (the chi2 path
        reuses ChiSquareTest in :meth:`fit` — device matmuls + its input
        validation, no duplicate table logic)."""
        from scipy import stats as sstats

        n, d = X.shape
        if self.label_type == "categorical":   # ANOVA F (f_classif)
            classes = np.unique(y)
            grand = X.mean(axis=0)
            ss_between = np.zeros(d)
            ss_within = np.zeros(d)
            for c in classes:
                Xi = X[y == c]
                ss_between += len(Xi) * (Xi.mean(axis=0) - grand) ** 2
                ss_within += ((Xi - Xi.mean(axis=0)) ** 2).sum(axis=0)
            df_b = len(classes) - 1
            df_w = n - len(classes)
            with np.errstate(divide="ignore", invalid="ignore"):
                F = (ss_between / df_b) / (ss_within / df_w)
            F = np.nan_to_num(F)
            return sstats.f.sf(F, df_b, df_w)
        # continuous/continuous: F-regression on the Pearson correlation
        Xc = X - X.mean(axis=0)
        yc = y - y.mean()
        denom = np.sqrt((Xc ** 2).sum(axis=0) * (yc ** 2).sum())
        with np.errstate(divide="ignore", invalid="ignore"):
            r = np.where(denom > 0, Xc.T @ yc / denom, 0.0)
            F = r * r / np.maximum(1.0 - r * r, 1e-300) * (n - 2)
        return sstats.f.sf(F, 1, n - 2)

    def fit(self, frame) -> "UnivariateFeatureSelectorModel":
        X = np.asarray(frame._column_values(self.features_col),
                       np.dtype(float_dtype()))
        if X.ndim == 1:
            X = X[:, None]
        y = np.asarray(frame._column_values(self.label_col), np.float64)
        mask = np.asarray(frame.mask)
        if not mask.any():
            raise ValueError("UnivariateFeatureSelector: no valid rows")
        Xv, yv = X[mask].astype(np.float64), y[mask]
        d = Xv.shape[1]
        if self.feature_type == "categorical":
            if self.label_type != "categorical":
                raise ValueError("categorical features require a "
                                 "categorical label (chi2)")
            from .stat import ChiSquareTest

            res = ChiSquareTest.test(frame, self.features_col,
                                     self.label_col).to_pydict()
            pvals = np.asarray(res["pValues"][0], np.float64)
        else:
            pvals = self._p_values(Xv, yv)

        mode = self.selection_mode
        # Spark's defaults per mode
        thr = self.selection_threshold
        if thr is None:
            thr = {"numTopFeatures": 50, "percentile": 0.1,
                   "fpr": 0.05, "fdr": 0.05, "fwe": 0.05}[mode]
        order = np.argsort(pvals, kind="stable")
        if mode == "numTopFeatures":
            keep = np.sort(order[: int(thr)])
        elif mode == "percentile":
            # Spark floors (and keeps at least one), like ChiSqSelector
            keep = np.sort(order[: max(1, int(thr * d))])
        elif mode == "fpr":
            keep = np.nonzero(pvals < thr)[0]
        elif mode == "fwe":
            keep = np.nonzero(pvals < thr / d)[0]
        else:  # fdr: Benjamini–Hochberg
            ranked = pvals[order]
            below = ranked <= thr * (np.arange(1, d + 1) / d)
            k = int(np.nonzero(below)[0].max()) + 1 if below.any() else 0
            keep = np.sort(order[:k])
        return UnivariateFeatureSelectorModel(
            keep.astype(np.int64).tolist(), self.features_col,
            self.output_col)


@persistable
class UnivariateFeatureSelectorModel(_SelectorModelBase):
    def __init__(self, selected_features, features_col="features",
                 output_col="selected_features"):
        super().__init__(selected_features, features_col, output_col)
