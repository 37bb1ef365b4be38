"""Evaluators — the MLlib ``ml.evaluation`` surface CrossValidator needs
(BASELINE.json config: "CrossValidator grid (regParam × elasticNetParam)")."""

from __future__ import annotations

import numpy as np

from ..frame.frame import Frame


def _host_pair(labels, scores):
    """Device inputs pull to host in ONE batched, COUNTED transfer
    (``frame.host_sync``); numpy inputs pass through free. The curve
    helpers are public library surface — a caller handing them device
    arrays used to trigger an implicit, uncounted device→host transfer
    per numpy op, invisible to the sync audits the fused paths pin."""
    pair = (labels, scores)
    if any(hasattr(x, "devices") for x in pair):
        import jax

        from ..utils.observability import host_reading
        from ..utils.profiling import counters

        counters.increment("frame.host_sync")
        with host_reading("evaluation.pair") as rd:
            labels, scores = jax.device_get(pair)
            rd.done(sum(h.nbytes for h, x in zip((labels, scores), pair)
                        if hasattr(x, "devices")))
    return np.asarray(labels), np.asarray(scores)


def threshold_sweep(labels: np.ndarray, scores: np.ndarray):
    """Cumulative (thresholds desc, tp, fp) at each DISTINCT score —
    the single O(n log n) sweep behind every ROC/PR curve and
    by-threshold metric (at threshold t, every row scoring ≥ t is
    predicted positive, so the last index of each tied run counts)."""
    labels, scores = _host_pair(labels, scores)
    order = np.argsort(-scores, kind="mergesort")
    y = (labels[order] == 1.0).astype(np.float64)
    s = scores[order]
    tp = np.cumsum(y)
    fp = np.cumsum(1.0 - y)
    boundary = np.r_[s[1:] != s[:-1], True]
    return s[boundary], tp[boundary], fp[boundary]


def pr_points(labels: np.ndarray, scores: np.ndarray):
    """(thresholds desc, precision, recall) at each distinct score."""
    labels, scores = _host_pair(labels, scores)
    thr, tp, fp = threshold_sweep(labels, scores)
    npos = max(float((labels == 1.0).sum()), 1.0)
    precision = tp / np.maximum(tp + fp, 1.0)
    recall = tp / npos
    return thr, precision, recall


def roc_points(labels: np.ndarray, scores: np.ndarray):
    """(FPR, TPR) arrays over descending score thresholds, O(n log n).

    Shared by the evaluators and the classifier summaries."""
    _, tps, fps = threshold_sweep(labels, scores)
    npos = max(tps[-1], 1.0) if len(tps) else 1.0
    nneg = max(fps[-1], 1.0) if len(fps) else 1.0
    tpr = np.r_[0.0, tps / npos]
    fpr = np.r_[0.0, fps / nneg]
    return fpr, tpr


def area_under_roc(labels: np.ndarray, scores: np.ndarray) -> float:
    """Exact AUC (rank statistic with tie handling) via the trapezoid over
    the ROC boundary points — O(n log n)."""
    labels, scores = _host_pair(labels, scores)
    pos = labels == 1.0
    if pos.sum() == 0 or (~pos).sum() == 0:
        return float("nan")
    fpr, tpr = roc_points(labels, scores)
    return float(np.trapezoid(tpr, fpr))


def area_under_pr(labels: np.ndarray, scores: np.ndarray) -> float:
    """Precision-recall AUC over threshold boundaries, O(n log n)."""
    labels, scores = _host_pair(labels, scores)
    pos = labels == 1.0
    if pos.sum() == 0 or (~pos).sum() == 0:
        return float("nan")
    _, precision, recall = pr_points(labels, scores)
    return float(np.trapezoid(np.r_[1.0, precision], np.r_[0.0, recall]))


class Evaluator:
    def evaluate(self, frame: Frame) -> float:
        raise NotImplementedError

    def is_larger_better(self) -> bool:
        return True

    isLargerBetter = is_larger_better


class RegressionEvaluator(Evaluator):
    """Metrics: rmse (default), mse, mae, r2."""

    def __init__(self, metric_name: str = "rmse", label_col: str = "label",
                 prediction_col: str = "prediction"):
        if metric_name not in ("rmse", "mse", "mae", "r2", "var"):
            raise ValueError(f"unknown metric {metric_name!r}")
        self.metric_name = metric_name
        self.label_col = label_col
        self.prediction_col = prediction_col

    def set_metric_name(self, v: str):
        self.metric_name = v
        return self

    setMetricName = set_metric_name

    def is_larger_better(self) -> bool:
        return self.metric_name in ("r2", "var")

    isLargerBetter = is_larger_better

    def evaluate(self, frame: Frame) -> float:
        d = frame.to_pydict()
        y = d[self.label_col].astype(np.float64)
        p = d[self.prediction_col].astype(np.float64)
        return self.compute(y, p)

    def compute(self, y: np.ndarray, p: np.ndarray) -> float:
        if self.metric_name == "rmse":
            return float(np.sqrt(np.mean((y - p) ** 2)))
        if self.metric_name == "mse":
            return float(np.mean((y - p) ** 2))
        if self.metric_name == "mae":
            return float(np.mean(np.abs(y - p)))
        if self.metric_name == "var":
            # Spark RegressionMetrics.explainedVariance:
            # mean((p_i - mean(y))^2)
            return float(np.mean((p - y.mean()) ** 2))
        ss_res = float(np.sum((y - p) ** 2))
        ss_tot = float(np.sum((y - y.mean()) ** 2))
        return float("nan") if ss_tot == 0 else 1.0 - ss_res / ss_tot


class BinaryClassificationEvaluator(Evaluator):
    """Metrics: areaUnderROC (default), areaUnderPR. Reads the probability
    column when present (falls back to rawPrediction)."""

    def __init__(self, metric_name: str = "areaUnderROC",
                 label_col: str = "label",
                 raw_prediction_col: str = "rawPrediction"):
        if metric_name not in ("areaUnderROC", "areaUnderPR"):
            raise ValueError(f"unknown metric {metric_name!r}")
        self.metric_name = metric_name
        self.label_col = label_col
        self.raw_prediction_col = raw_prediction_col

    def set_metric_name(self, v: str):
        self.metric_name = v
        return self

    setMetricName = set_metric_name

    def evaluate(self, frame: Frame) -> float:
        d = frame.to_pydict()
        y = d[self.label_col].astype(np.float64)
        score_col = self.raw_prediction_col
        if score_col not in d and "probability" in d:
            score_col = "probability"
        s = d[score_col].astype(np.float64)
        return self.compute(y, s)

    def compute(self, y: np.ndarray, s: np.ndarray) -> float:
        if self.metric_name == "areaUnderROC":
            return area_under_roc(y, s)
        return area_under_pr(y, s)


class MulticlassClassificationEvaluator(Evaluator):
    """MLlib metrics: ``f1`` (the Spark default), ``accuracy``,
    ``weightedPrecision``, ``weightedRecall`` — per-class one-vs-rest
    scores weighted by true-class frequency."""

    _METRICS = ("f1", "accuracy", "weightedPrecision", "weightedRecall",
                "hammingLoss")

    def __init__(self, metric_name: str = "f1", label_col: str = "label",
                 prediction_col: str = "prediction"):
        if metric_name not in self._METRICS:
            raise ValueError(f"unknown metric {metric_name!r} "
                             f"(supported: {self._METRICS})")
        self.metric_name = metric_name
        self.label_col = label_col
        self.prediction_col = prediction_col

    def is_larger_better(self) -> bool:
        return self.metric_name != "hammingLoss"

    isLargerBetter = is_larger_better

    def evaluate(self, frame: Frame) -> float:
        d = frame.to_pydict()
        y = d[self.label_col].astype(np.float64)
        p = d[self.prediction_col].astype(np.float64)
        if self.metric_name == "accuracy":
            return float(np.mean(y == p))
        if self.metric_name == "hammingLoss":
            return float(np.mean(y != p))
        classes = np.unique(y)
        scores, weights = [], []
        for c in classes:
            tp = float(((p == c) & (y == c)).sum())
            fp = float(((p == c) & (y != c)).sum())
            fn = float(((p != c) & (y == c)).sum())
            prec = tp / max(tp + fp, 1.0)
            rec = tp / max(tp + fn, 1.0)
            if self.metric_name == "weightedPrecision":
                scores.append(prec)
            elif self.metric_name == "weightedRecall":
                scores.append(rec)
            else:
                scores.append(0.0 if prec + rec == 0
                              else 2 * prec * rec / (prec + rec))
            weights.append((y == c).mean())
        return float(np.average(scores, weights=weights))


class ClusteringEvaluator(Evaluator):
    """MLlib ``ClusteringEvaluator``: mean silhouette coefficient with
    squared-Euclidean distance (Spark's default and only 2.4-era metric).

    Device path: per-cluster means and squared norms make the per-point
    cluster distances one (n, k) matmul — the same ‖x−c‖² expansion the
    KMeans fit uses — instead of the naive O(n²) pairwise matrix, which is
    exactly Spark's optimization for this metric."""

    def __init__(self, features_col: str = "features",
                 prediction_col: str = "prediction",
                 metric_name: str = "silhouette"):
        if metric_name != "silhouette":
            raise ValueError(f"unknown metric {metric_name!r}")
        self.features_col = features_col
        self.prediction_col = prediction_col
        self.metric_name = metric_name

    def set_features_col(self, v):
        self.features_col = v
        return self

    setFeaturesCol = set_features_col

    def set_prediction_col(self, v):
        self.prediction_col = v
        return self

    setPredictionCol = set_prediction_col

    def evaluate(self, frame: Frame) -> float:
        d = frame.to_pydict()
        X = np.asarray(d[self.features_col], np.float64)
        if X.ndim == 1:
            X = X[:, None]
        labels = np.asarray(d[self.prediction_col], np.float64).astype(int)
        uniq = np.unique(labels)
        k = len(uniq)
        if k < 2:
            return float("nan")
        remap = {c: i for i, c in enumerate(uniq)}
        lab = np.asarray([remap[c] for c in labels])
        n = len(lab)
        counts = np.bincount(lab, minlength=k).astype(np.float64)
        onehot = np.zeros((n, k))
        onehot[np.arange(n), lab] = 1.0
        sums = onehot.T @ X                              # (k, d)
        means = sums / counts[:, None]
        sq_sums = onehot.T @ np.sum(X * X, axis=1)       # (k,)
        # mean squared distance from point i to all of cluster c:
        #   E_c‖x_i − y‖² = ‖x_i‖² − 2·x_i·mean_c + E_c‖y‖²
        x_sq = np.sum(X * X, axis=1, keepdims=True)
        msd = x_sq - 2.0 * (X @ means.T) + (sq_sums / counts)[None, :]
        own = lab
        # a(i): mean distance to own cluster EXCLUDING self
        c_own = counts[own]
        a = np.where(c_own > 1,
                     (msd[np.arange(n), own] * c_own) / np.maximum(c_own - 1,
                                                                   1),
                     0.0)
        msd[np.arange(n), own] = np.inf
        b = msd.min(axis=1)                              # nearest other cluster
        s = np.where(c_own > 1,
                     (b - a) / np.maximum(np.maximum(a, b), 1e-300), 0.0)
        return float(s.mean())
