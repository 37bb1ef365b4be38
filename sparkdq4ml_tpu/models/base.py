"""Estimator/Transformer/Model/Pipeline base classes (the MLlib ``ml``
pipeline contracts that ``VectorAssembler`` and ``LinearRegression``
implement — `DataQuality4MachineLearningApp.java:110-126` uses exactly the
Transformer and Estimator halves), plus the generic stage-persistence layer
(MLlib's MLWritable/MLReadable analogue; SURVEY.md §5 "Checkpoint / resume"
— a capability upgrade over the reference, which never saves models).

Persistence model: every stage class declares ``_persist_attrs`` (the
attributes that fully determine it) and registers itself with
``@persistable``; ``save_stage``/``load_stage`` write/read one JSON file per
stage (numpy arrays embedded with a dtype tag). ``Pipeline`` and
``PipelineModel`` save stages into numbered subdirectories.
"""

from __future__ import annotations

import json
import os
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import observability as _obs

_STAGE_REGISTRY: dict[str, type] = {}


def host_fetch(x) -> np.ndarray:
    """THE sanctioned device→host pull for model accessor APIs
    (``predict(features)`` single points, ``compute_cost``, summary
    statistics): one counted ``frame.host_sync`` per call, host numpy
    out. Every such accessor is host-returning by contract, so the
    transfer is inherent — what the standing ROADMAP constraint requires
    is that it be *counted*, so EXPLAIN ANALYZE and the span layer's
    per-op sync deltas see it (dqlint's ``host-sync`` rule pins the
    discipline statically)."""
    from ..utils.profiling import counters

    counters.increment("frame.host_sync")
    with _obs.host_reading("model.fetch") as rd:
        out = np.asarray(x)
        rd.done(out.nbytes)
    return out


@jax.jit
def label_stats(y, mask, w=None):
    """What a fit has to know of its labels and weights before it may
    start, reduced ON THE DEVICE to one small vector (decode with
    :func:`read_label_stats`): ``[rows, label_min, label_max, label_bad,
    weight_bad]`` over the rows ``mask`` keeps. ``label_bad``: some valid
    label is not a finite integer (``y != floor(y)`` holds for NaN);
    ``weight_bad``: some valid weight is not ``>= 0`` (NaN fails ``>=``).
    With a NaN among the valid labels ``label_bad`` is the verdict; min and
    max are then whatever the backend's reduction makes of it. ``y`` or
    ``w`` may be ``None``; its entries then read 0.

    Masked rows take neutral values through ``jnp.where`` BEFORE any
    reduction, so a NaN or negative payload in a filtered slot cannot
    reach the result — the contract indexing host copies with the mask
    had. Keyed by shape and dtype; sharded operands need no path of their
    own."""
    with _obs.scope("fit.validate"):
        valid = jnp.asarray(mask, jnp.bool_)
        dtype = jnp.result_type(*(a for a in (y, w) if a is not None),
                                jnp.float32)
        # exact below 2**24 rows in float32; read for "== 0" only
        rows = jnp.sum(valid, dtype=jnp.int32)
        lo = hi = label_bad = weight_bad = 0
        if y is not None:
            lo = jnp.min(jnp.where(valid, y, jnp.inf))
            hi = jnp.max(jnp.where(valid, y, -jnp.inf))
            label_bad = jnp.any(jnp.where(
                valid, (y != jnp.floor(y)) | jnp.isinf(y), False))
        if w is not None:
            weight_bad = jnp.any(jnp.where(valid, ~(w >= 0), False))
        return jnp.stack([jnp.asarray(v, dtype) for v in
                          (rows, lo, hi, label_bad, weight_bad)])


class LabelStats(NamedTuple):
    rows: float
    label_min: float
    label_max: float
    label_bad: bool
    weight_bad: bool
    nbytes: int


def read_label_stats(stats) -> LabelStats:
    """The one blocking read of a :func:`label_stats` vector, counted as a
    host read (a few scalars, whatever the row count)."""
    with _obs.host_reading("fit.label_stats") as rd:
        out = np.asarray(stats)
        rd.done(out.nbytes)
    rows, lo, hi, label_bad, weight_bad = out.tolist()
    return LabelStats(rows, lo, hi, bool(label_bad), bool(weight_bad),
                      out.nbytes)


def persistable(cls):
    """Class decorator: register for name-based load_stage resolution."""
    _STAGE_REGISTRY[cls.__name__] = cls
    return cls


def _to_jsonable(v):
    if isinstance(v, np.ndarray):
        dt = "object" if v.dtype == object else str(v.dtype)
        # dqlint: ok(host-sync): isinstance-narrowed to host numpy —
        # persistence serializes the host copies stored on the stage
        return {"__ndarray__": v.tolist(), "dtype": dt}
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, dict):
        return {k: _to_jsonable(x) for k, x in v.items()}
    return v


def _from_jsonable(v):
    if isinstance(v, dict) and "__ndarray__" in v:
        dt = v["dtype"]
        return np.asarray(v["__ndarray__"],
                          object if dt == "object" else np.dtype(dt))
    if isinstance(v, dict):
        return {k: _from_jsonable(x) for k, x in v.items()}
    return v


def save_stage(stage, path: str) -> None:
    """Persist one stage (transformer/estimator/model) to ``path/``."""
    if hasattr(stage, "_save_to_dir"):  # composite stages (Pipeline, ...)
        stage._save_to_dir(path)
        return
    attrs = getattr(stage, "_persist_attrs", None)
    if attrs is None:
        raise TypeError(f"{type(stage).__name__} is not persistable "
                        f"(no _persist_attrs)")
    payload = {"class": type(stage).__name__,
               "data": {k: _to_jsonable(getattr(stage, k)) for k in attrs}}
    write_json(os.path.join(path, "stage.json"), payload)


def load_stage(path: str):
    """Load any persisted stage; dispatches on the recorded class name."""
    meta_path = os.path.join(path, "stage.json")
    if not os.path.exists(meta_path):  # composite stage directory
        comp = read_json(os.path.join(path, "metadata.json"))
        cls = _STAGE_REGISTRY.get(comp["class"])
        if cls is None or not hasattr(cls, "_load_from_dir"):
            raise ValueError(f"unknown composite stage {comp['class']!r}")
        return cls._load_from_dir(path, comp)
    meta = read_json(meta_path)
    cls = _STAGE_REGISTRY.get(meta["class"])
    if cls is None:
        raise ValueError(f"unknown stage class {meta['class']!r}; known: "
                         f"{sorted(_STAGE_REGISTRY)}")
    obj = cls.__new__(cls)
    for k, v in meta["data"].items():
        setattr(obj, k, _from_jsonable(v))
    post = getattr(obj, "_post_load", None)
    if post is not None:
        post()
    return obj


class _Persist:
    """save()/load() surface shared by all stage kinds."""

    def save(self, path: str) -> None:
        save_stage(self, path)

    def write(self):  # MLlib: model.write().overwrite().save(path)
        return _Writer(self)

    @classmethod
    def load(cls, path: str):
        obj = load_stage(path)
        if not isinstance(obj, cls):
            raise TypeError(f"{path} holds a {type(obj).__name__}, "
                            f"not a {cls.__name__}")
        return obj

    read = load


class _Writer:
    def __init__(self, stage):
        self._stage = stage

    def overwrite(self) -> "_Writer":
        return self

    def save(self, path: str) -> None:
        save_stage(self._stage, path)


class Transformer(_Persist):
    def transform(self, frame):
        raise NotImplementedError

    def __call__(self, frame):
        return self.transform(frame)


class Estimator(_Persist):
    def fit(self, frame):
        raise NotImplementedError


class Model(Transformer):
    pass


@persistable
class Pipeline(Estimator):
    """Chain of stages; each Estimator stage is fit on the running frame and
    replaced by its Model."""

    def __init__(self, stages: Sequence = ()):
        self._stages = list(stages)

    def _save_to_dir(self, path: str) -> None:
        write_json(os.path.join(path, "metadata.json"),
                   {"class": type(self).__name__,
                    "n_stages": len(self._stages)})
        for i, st in enumerate(self._stages):
            save_stage(st, os.path.join(path, f"stage_{i:02d}"))

    @classmethod
    def _load_from_dir(cls, path: str, meta: dict):
        stages = [load_stage(os.path.join(path, f"stage_{i:02d}"))
                  for i in range(meta["n_stages"])]
        return cls(stages)

    def set_stages(self, stages: Sequence) -> "Pipeline":
        self._stages = list(stages)
        return self

    setStages = set_stages

    def get_stages(self):
        return list(self._stages)

    getStages = get_stages

    def fit(self, frame) -> "PipelineModel":
        fitted = []
        cur = frame
        for stage in self._stages:
            if isinstance(stage, Estimator):
                model = stage.fit(cur)
                fitted.append(model)
                cur = model.transform(cur)
            else:
                fitted.append(stage)
                cur = stage.transform(cur)
        return PipelineModel(fitted)


@persistable
class PipelineModel(Model):
    def __init__(self, stages: Sequence):
        self.stages = list(stages)

    def transform(self, frame):
        cur = frame
        for stage in self.stages:
            cur = stage.transform(cur)
        return cur

    def _save_to_dir(self, path: str) -> None:
        write_json(os.path.join(path, "metadata.json"),
                   {"class": type(self).__name__,
                    "n_stages": len(self.stages)})
        for i, st in enumerate(self.stages):
            save_stage(st, os.path.join(path, f"stage_{i:02d}"))

    @classmethod
    def _load_from_dir(cls, path: str, meta: dict):
        return cls([load_stage(os.path.join(path, f"stage_{i:02d}"))
                    for i in range(meta["n_stages"])])


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, default=float)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)
