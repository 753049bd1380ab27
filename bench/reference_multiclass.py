"""Plain reference of K-class gradient-boosted trees and their score: K
outputs per row, the softmax loss. It fulfils the contract at the top of
``reference.py`` and takes that file's binning, histograms, gains, tree
growth and routing as they are, adding only the multi-class objective:

- labels are the class ids 0..K-1, K the number of distinct training
  labels;
- base margins: the log class priors of the training labels,
  ``log(count_k / R)``, each prior floored at 1e-6, stored in float32;
- each round: p = softmax of the (K, R) margins over the classes,
  g_k = p_k - [y = k] and h_k = max(2·p_k·(1 - p_k), 1e-16) (XGBoost's
  ``multi:softprob``); one complete tree per class is grown from its own g_k
  and h_k by ``reference.grow_tree``, with the split rule, stopping rule
  and leaves ``-eta·G/(H+λ)`` of the binary reference; the K margins are
  updated after all K trees of the round are grown;
- predictions: per class, the base plus the class's trees' leaves summed in
  round order, then the softmax over classes, shifted by the row's largest
  margin;
- score: ``neg_mlogloss``, the negated mean of -log p_y over the rows with
  p_y clipped to [1e-7, 1], as ``core/results.py`` defines it.

``dtype`` is the precision every stored value is rounded to: float32 for the
reference, bfloat16 for the control; histogram sums of the float32
reference are taken at the highest matmul precision (``reference.py``).

Only GBDT has a multi-class objective in the program, so the cells that use
this reference train GBDT alone: the forest and the dense families raise
``NotImplementedError`` here.
"""
from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as binary
from bench.counts import DEFAULTS

F32 = jnp.float32
_rnd = binary._rnd


def softmax(z: np.ndarray) -> np.ndarray:
    """Softmax over the last axis of float32 margins."""
    z = np.asarray(z, np.float32)
    e = np.exp(z - z.max(axis=-1, keepdims=True))
    return (e / e.sum(axis=-1, keepdims=True)).astype(np.float32)


def mlogloss(y: np.ndarray, p: np.ndarray) -> float:
    p = np.asarray(p, np.float64)
    y = np.asarray(y).astype(np.int64)
    return float(-np.log(np.clip(p[np.arange(y.size), y], 1e-7, 1.0)).mean())


#: the metrics a multi-class search may be scored by, under the program's names
SCORES = {"neg_mlogloss": lambda y, p: -mlogloss(y, p)}


def score(metric: str, y: np.ndarray, probs: np.ndarray) -> float:
    """The configuration's metric of predicted class probabilities."""
    if metric not in SCORES:
        raise ValueError(f"the multi-class reference has no metric "
                         f"{metric!r}; known: {sorted(SCORES)}")
    return float(SCORES[metric](y, np.asarray(probs)))


class Prepared(binary.Prepared):
    """The training rows and their class ids, quantised once per bin count."""

    def __init__(self, x: np.ndarray, y: np.ndarray):
        super().__init__(x, y)
        self.n_classes = int(np.unique(y).size)
        self.onehot = (self.y_dev[None, :]
                       == jnp.arange(self.n_classes, dtype=F32)[:, None]
                       ).astype(F32)


def _base_margins(y: np.ndarray, k: int) -> np.ndarray:
    counts = np.bincount(np.asarray(y).astype(np.int64), minlength=k)[:k]
    return np.log(np.clip(counts / counts.sum(), 1e-6, 1.0)).astype(np.float32)


def _gradients(margin, onehot, dtype):
    p = jax.nn.softmax(margin, axis=0)
    return (_rnd(p - onehot, dtype),
            _rnd(jnp.maximum(2.0 * p * (1.0 - p), 1e-16), dtype))


def _only_gbdt(family: str) -> None:
    if family != "gbdt":
        raise NotImplementedError(
            f"the multi-class reference has no {family} objective")


def fit_trees(prep: Prepared, family: str, params: Mapping, dtype) -> dict:
    """A K-class GBDT model trained by the reference: ``feat``, ``thresh``
    (rounds, K, 2^D - 1), ``leaves`` (rounds, K, 2^D), ``base`` (K,) and
    ``depth``."""
    _only_gbdt(family)
    p = {**DEFAULTS["gbdt"], **params}
    bins, edges, n_bins = prep.bins(int(p["max_bin"]))
    r, f = bins.shape
    k, depth = prep.n_classes, int(p["max_depth"])
    base = _base_margins(prep.y, k)
    margin = jnp.broadcast_to(jnp.asarray(base)[:, None], (k, r))
    mask = jnp.ones((f,), bool)
    eta, lam = jnp.float32(p["eta"]), jnp.float32(p["lambda"])
    feats, threshs, leaves = [], [], []
    for _ in range(int(p["round"])):
        g, h = _gradients(margin, prep.onehot, dtype)
        rf, rt, rl, rows = [], [], [], []
        for c in range(k):
            feat, split, lg, lh, node = binary.grow_tree(
                bins, g[c], h[c], lam, jnp.float32(p["gamma"]),
                jnp.float32(p["min_child_weight"]), mask, depth=depth,
                n_bins=n_bins, dtype=dtype)
            leaf = _rnd(-eta * lg / (lh + lam), dtype)
            rf.append(np.asarray(feat))
            rt.append(binary._thresholds(np.asarray(feat), np.asarray(split),
                                         edges, n_bins))
            rl.append(np.asarray(leaf))
            rows.append(leaf[node])
        margin = _rnd(margin + jnp.stack(rows), dtype)
        feats.append(np.stack(rf))
        threshs.append(np.stack(rt))
        leaves.append(np.stack(rl))
    return {"feat": np.stack(feats), "thresh": np.stack(threshs),
            "leaves": np.stack(leaves), "base": base, "depth": depth}


def fit(prep: Prepared, family: str, params: Mapping, dtype) -> dict:
    return fit_trees(prep, family, params, dtype)


def probs(family: str, model: dict, x: np.ndarray, dtype=F32) -> np.ndarray:
    """(rows, K) class probabilities: each class's margins summed over its
    trees in round order, stored in ``dtype``, then the softmax."""
    _only_gbdt(family)
    k = model["feat"].shape[1]
    margins = np.stack(
        [binary.route(x, model["feat"][:, c], model["thresh"][:, c],
                      model["leaves"][:, c], int(model["depth"]),
                      np.dtype(dtype), base=float(model["base"][c]))
         for c in range(k)], axis=1)
    return softmax(margins)


def _cut_depth(model: dict, depth: int):
    """``reference._cut_depth`` over the (rounds · K) trees, as (rounds, K)."""
    t, k = model["feat"].shape[:2]
    flat = {key: model[key].reshape((t * k,) + model[key].shape[2:])
            for key in ("feat", "thresh", "leaves")}
    feat, thresh, leaves, extra_stop = binary._cut_depth(
        {**flat, "depth": model["depth"]}, depth)
    return (feat.reshape(t, k, -1), thresh.reshape(t, k, -1),
            leaves.reshape(t, k, -1), extra_stop)


def replay_trees(prep: Prepared, family: str, params: Mapping,
                 model: dict) -> dict:
    """``split_gap`` and ``leaf_gap`` of a given K-class GBDT model.

    Round by round, each class's tree is replayed on the training rows with
    that class's g and h, which the reference derives from the model's own
    earlier rounds; the numbers are those of ``reference.replay_trees``,
    the worst over every class's tree. A model whose base margins are not
    the log class priors of the training labels (to float32 rounding) reads
    the worst numbers: its trees would be replayed on the wrong g and h."""
    _only_gbdt(family)
    p = {**DEFAULTS["gbdt"], **params}
    bins, edges, n_bins = prep.bins(int(p["max_bin"]))
    r, f = bins.shape
    k, depth = prep.n_classes, int(p["max_depth"])
    bad = {"split_gap": float("inf"), "leaf_gap": float("inf")}
    if model["feat"].ndim != 3 or model["feat"].shape[1] != k:
        return bad
    base = np.asarray(model["base"], np.float32)
    if base.shape != (k,) or not np.allclose(
            base, _base_margins(prep.y, k), rtol=1e-6, atol=1e-7):
        return bad
    feat, thresh, leaves, extra_stop = _cut_depth(model, depth)
    if len(feat) != int(p["round"]) or not extra_stop:
        return bad
    lam = float(p["lambda"])
    mask = jnp.ones((f,), bool)
    split_gap = leaf_gap = 0.0
    margin = jnp.broadcast_to(jnp.asarray(base)[:, None], (k, r))
    for t in range(len(feat)):
        g, h = _gradients(margin, prep.onehot, F32)
        rows = []
        for c in range(k):
            splits = binary._split_bins(feat[t, c], thresh[t, c], edges, n_bins)
            if np.any(splits < 0):
                return bad
            worst, lg, lh, node = binary._replay_tree(
                bins, g[c], h[c], jnp.asarray(feat[t, c], jnp.int32),
                jnp.asarray(splits), jnp.float32(lam),
                jnp.float32(p["gamma"]), jnp.float32(p["min_child_weight"]),
                mask, depth=depth, n_bins=n_bins)
            want = np.asarray(-float(p["eta"]) * lg / (lh + lam), np.float64)
            got = np.asarray(leaves[t, c], np.float64)
            scale = max(float(np.abs(want).max()), 1e-30)
            split_gap = max(split_gap, float(worst))
            leaf_gap = max(leaf_gap, float(np.abs(got - want).max()) / scale)
            rows.append(jnp.asarray(leaves[t, c], F32)[node])
        margin = margin + jnp.stack(rows)
    return {"split_gap": split_gap, "leaf_gap": leaf_gap}


def program_model(family: str, model) -> dict | None:
    """The program's K-class GBDT model in the reference's plain form: trees
    (rounds, K, ·) and (K,) base margins."""
    if model is None:
        return None
    _only_gbdt(family)
    return {"feat": np.asarray(model.feat), "thresh": np.asarray(model.thresh),
            "leaves": np.asarray(model.leaves),
            "base": np.asarray(model.base, np.float32),
            "depth": int(model.max_depth)}


def fit_dense(prep, family, params, dtype, half=False):
    raise NotImplementedError("the multi-class reference has no dense family")


def dense_init(prep, family, params):
    raise NotImplementedError("the multi-class reference has no dense family")


def dense_loss(prep, family, params, model):
    raise NotImplementedError("the multi-class reference has no dense family")


def change_gap(model, want, init):
    raise NotImplementedError("the multi-class reference has no dense family")
