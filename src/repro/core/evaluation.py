"""Executor-side fused validation plane (DESIGN.md §3.4).

The paper's pipeline ends with ``multiModel.validateAll(validateDF, ...)`` —
and pre-§3.4 our reproduction ran that stage exactly as naively as the name
suggests: a serial, driver-side, pure-numpy loop (``GBDTModel.
predict_margin`` Python-looping over every round and tree level, one model
at a time) whose time was invisible to the WAL, the CostModel and the
scheduler. This module owns the driver-side pieces of the fix; the three
halves mirror the §3.2 fusion / §3.3 prepared-data architecture:

* **Jitted batched inference** — every tabular family grows a device
  predictor (``TrainedModel.predict_proba_jax`` /
  ``predict_proba_batched``): GBDT/forest route ALL rounds' heap-layout
  trees in one vectorized gather program, logreg/mlp are single matmul
  programs, and a stacked model batch (a fused unit's models share padded
  shapes by construction) scores through ONE compile. Compiled predictors
  live in :func:`predict_compile_cache` — a dedicated process-wide
  :class:`~repro.core.fusion.CompileCache`, separate from the training
  cache so ``SearchStats.predict_compile_cache_*`` can report the
  validation plane's own traffic.

* **Executor-side scoring** — both pools call :func:`evaluate_models`
  right after training, where the model already lives: validation data is
  resolved ONCE per (fingerprint, eval format, placement) through the
  :class:`~repro.core.data_format.PreparedDataCache` (the ``eval_dense``
  entries; mesh slices each hold their own resident copy), and results
  stream back with ``TaskResult.score``/``eval_seconds`` attached — the
  Session never re-predicts on the driver.

* **Eval as a scheduled cost** — ``eval_seconds`` feeds the CostModel's
  per-family eval law (``observe_eval``/``predict_eval``) and
  ``scheduler.charge_units`` adds the estimate to every unit's planned
  cost, so LPT, ``split_for_balance`` and the drift window all see the
  validation work the old driver loop hid.

:func:`stable_sigmoid` is the shared numerically-stable numpy sigmoid every
family's ``predict_proba`` uses — the naive ``1/(1+exp(-z))`` overflows
(RuntimeWarning, precision loss) for large negative margins;
:func:`stable_softmax` is its K-class counterpart.
"""
from __future__ import annotations

import dataclasses
from typing import Hashable, Sequence

import numpy as np

from repro.core.data_format import DenseMatrix, is_sharded_payload, prepare_cached
from repro.core.fusion import CompileCache
from repro.core.results import METRICS, sharded_metric
from repro.core.tracing import span

__all__ = [
    "EvalPlan",
    "evaluate_models",
    "predict_compile_cache",
    "stable_sigmoid",
    "stable_softmax",
]


def stable_sigmoid(z: np.ndarray) -> np.ndarray:
    """Numerically stable ``1/(1+exp(-z))``: never exponentiates a positive
    argument, so extreme margins (|z| ~ 1000) neither overflow (the naive
    form raises RuntimeWarning and rounds to exactly 0/1 via ``inf``) nor
    lose the tiny-probability tail representable in the output dtype.
    Computes in the input's floating dtype — float32 margins yield float32
    probabilities (the hot batched-scoring path must not silently double
    its output memory), float64 keeps the full tail."""
    z = np.asarray(z)
    if z.dtype not in (np.float32, np.float64):
        z = z.astype(np.float64)
    out = np.empty_like(z)
    pos = z >= 0
    out[pos] = 1.0 / (1.0 + np.exp(-z[pos]))
    ez = np.exp(z[~pos])
    out[~pos] = ez / (1.0 + ez)
    return out


def stable_softmax(z: np.ndarray) -> np.ndarray:
    """Numerically stable softmax over the last axis (class margins to
    class probabilities): shifts each row by its largest margin before
    exponentiating, so no argument is positive and extreme margins neither
    overflow nor turn into NaN. Computes in the input's floating dtype,
    like :func:`stable_sigmoid`."""
    z = np.asarray(z)
    if z.dtype not in (np.float32, np.float64):
        z = z.astype(np.float64)
    e = np.exp(z - z.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


#: process-wide cache of compiled PREDICT programs — deliberately separate
#: from fusion.compile_cache() (training programs) so the validation plane's
#: hit/miss traffic is observable on its own (SearchStats.predict_compile_*)
_PREDICT_CACHE = CompileCache(name="predict")


def predict_compile_cache() -> CompileCache:
    """The process-wide cache shared by every family's jitted predictors."""
    return _PREDICT_CACHE


@dataclasses.dataclass(frozen=True)
class EvalPlan:
    """What the executors score against: validation split + metric.

    Passed to ``ExecutorBackend.submit(assignment, data, validate=plan)`` by
    the Session whenever the backend supports executor-side scoring (both
    shipped pools do); backends without the keyword keep the pre-§3.4
    driver-side fallback.
    """

    data: DenseMatrix
    metric: str = "auc"

    def __post_init__(self):
        if self.metric not in METRICS:
            raise ValueError(
                f"unknown metric {self.metric!r}; known: {sorted(METRICS)}")


def evaluate_models(
    est,
    models: Sequence,
    plan: EvalPlan,
    *,
    prepared_cache=None,
    placement: Hashable = None,
    cache: CompileCache | None = None,
) -> tuple[list[float | None], float]:
    """Score ``models`` (one task's model, or a fused unit's whole stack)
    executor-side; returns ``(scores, per_model_eval_seconds)``.

    The eval split converts once per (fingerprint, ``est.eval_format``,
    placement) through the PreparedDataCache — the build time is folded
    into this call's eval seconds for the caller that built it (hits pay
    ~0), exactly like training-side conversion accounting. A model batch
    scores through ``predict_proba_batched`` (one vmapped program via the
    predict compile cache); the metric itself is a cheap O(R log R) numpy
    reduction on the executor thread.

    Scoring failures degrade to ``None`` scores — a trained model must
    never be lost because its evaluation raised; the Session's driver-side
    fallback (``score_of``) can still rank it lazily.
    """
    from repro.core.interface import TrainedModel

    models = list(models)
    if not models or not all(isinstance(m, TrainedModel) for m in models):
        return [None] * len(models), 0.0
    cache = cache if cache is not None else _PREDICT_CACHE
    with span("repro.eval", family=est.name, size=len(models)) as sp:
        try:
            entry, _conv_s, _built = prepare_cached(
                plan.data, getattr(est, "eval_format", "eval_dense"),
                cache=prepared_cache, placement=placement)
            x = entry["x"]
            sharded = is_sharded_payload(entry)
            if sharded:
                # prediction is row-local: score the flattened (S·Rs, F) block
                # view, then reduce per-shard metric PARTIALS (§3.9) — no
                # gathered prediction vector for decomposable metrics
                n_shards, rows_per_shard = int(entry["_n_shards"]), x.shape[1]
                x = x.reshape(n_shards * rows_per_shard, *x.shape[2:])
            if len(models) > 1:
                probs = type(models[0]).predict_proba_batched(models, x, cache=cache)
            else:
                probs = [models[0].predict_proba_jax(x, cache=cache)]
            # outputs a row: 1 for P(y=1), K for a K-class model's (R, K)
            sp.set(outputs=int(np.shape(probs[0])[1])
                   if np.ndim(probs[0]) == 2 else 1)
            y = plan.data.y
            if sharded:
                n_rows = int(entry["_n_rows"])
                valid = np.asarray(entry["_shard_valid"])
                y_blocks = np.zeros(valid.shape, np.asarray(y).dtype)
                y_blocks.reshape(-1)[:n_rows] = np.asarray(y).reshape(-1)

                def score(p):
                    p = np.asarray(p)
                    return sharded_metric(plan.metric, y_blocks,
                                          p.reshape(valid.shape + p.shape[1:]),
                                          valid, n_rows)
            else:
                metric_fn = METRICS[plan.metric]

                def score(p):
                    return float(metric_fn(y, np.asarray(p)))
            # the host reduction alone: the probabilities are on the host
            with span("repro.eval.metric", metric=plan.metric,
                      size=len(models)):
                scores: list[float | None] = [score(p) for p in probs]
        except Exception:
            return [None] * len(models), 0.0
    return scores, sp.seconds / len(models)
