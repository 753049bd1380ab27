"""Plain reference of the four families the search trains, and the score.

Written from the algorithms' definitions, in straightforward numpy and
``jax.numpy``, importing nothing of the program:

- quantile binning: per feature, edges at the ``linspace(0, 1, B + 1)[1:-1]``
  quantiles of the training rows, a row's bin the number of edges below it;
- GBDT (logistic loss): base margin ``log(p / (1 - p))`` of the label mean;
  each round grows a complete depth-D tree level by level from (node,
  feature, bin) sums of g = sigmoid(margin) - y and h = sigmoid' (floored at
  1e-16); a split's gain is ``GL²/(HL+λ) + GR²/(HR+λ) - G²/(H+λ)``, admissible
  where both children weigh at least ``min_child_weight`` and the bin is not
  the last; the first best (feature, bin) wins; a node whose best gain is at
  most ``gamma`` sends every row left; leaves are ``-eta·G/(H+λ)``;
- random forest: per tree t a Poisson(1) bootstrap and a random sqrt(F)
  feature subset drawn from ``fold_in(key(seed), t)``; g = -y·w, h = w,
  λ = 1e-6, leaves the weighted mean label, predictions the tree mean;
- MLP: He-normal weights, zero biases, minibatch Adam on the logistic loss,
  minibatch rows drawn with ``randint`` from a key split once per step;
- logistic regression: full-batch Adam on the mean logistic loss plus
  ``|w|² / (2·c·n)``;
- AUC: the Mann-Whitney statistic with average ranks for ties.

The random draws (initial weights, minibatches, bootstraps, feature subsets)
are part of each algorithm's definition, so they follow the same
``jax.random`` calls from the same seeds.

``dtype`` is the precision every stored value is rounded to: float32 for the
reference, bfloat16 for the control. Matrix products keep JAX's default
precision in both, except the histogram sums of the float32 reference and
the dense objective a fitted model is judged by, which are taken at the
highest.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import DEFAULTS

#: bins a forest's data is quantised to
FOREST_BINS = 256
F32 = jnp.float32


def _rnd(x, dtype):
    """``x`` rounded as if stored in ``dtype``, as float32. The rounding is
    explicit: XLA may drop a float32 -> bfloat16 -> float32 round trip."""
    if dtype == F32:
        return x.astype(F32)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


# ---------------------------------------------------------------------------
# data and score
# ---------------------------------------------------------------------------

def quantize(x: np.ndarray, max_bins: int):
    """(bins (R, F) int32, edges (F, B-1) float32, B)."""
    n_rows, n_feat = x.shape
    n_bins = min(max_bins, max(2, n_rows))
    edges = np.quantile(x, np.linspace(0.0, 1.0, n_bins + 1)[1:-1], axis=0)
    bins = np.empty((n_rows, n_feat), np.int32)
    for f in range(n_feat):
        bins[:, f] = np.searchsorted(edges[:, f], x[:, f], side="left")
    return bins, np.ascontiguousarray(edges.T).astype(np.float32), n_bins


def sigmoid(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.float32)
    e = np.exp(-np.abs(z))
    return np.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e)).astype(np.float32)


def auc(y: np.ndarray, s: np.ndarray) -> float:
    y = np.asarray(y) > 0.5
    n_pos = int(y.sum())
    n_neg = y.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return 0.5
    _, inv, counts = np.unique(np.asarray(s, np.float64), return_inverse=True,
                               return_counts=True)
    ends = np.cumsum(counts)
    avg_rank = ends - (counts - 1) / 2.0
    r_pos = avg_rank[inv][y].sum()
    return float((r_pos - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


# ---------------------------------------------------------------------------
# trees
# ---------------------------------------------------------------------------

def _chunk_rows(n_rows: int, width: int) -> int:
    """Rows per block of the one-hot histogram sum (about 128 MB a block)."""
    return int(max(8, min(n_rows, (1 << 25) // max(1, width))))


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "dtype"))
def histograms(bins, g, h, node, *, n_nodes: int, n_bins: int, dtype):
    """(2, nodes, F, B) sums of g and h, in row blocks of one-hot products."""
    r, f = bins.shape
    c = _chunk_rows(r, f * n_bins)
    pad = (-r) % c
    bins = jnp.pad(bins, ((0, pad), (0, 0))).reshape(-1, c, f)
    gh = jnp.stack([jnp.pad(g, (0, pad)), jnp.pad(h, (0, pad))], 1)
    gh = gh.reshape(-1, c, 2)
    node = jnp.pad(node, (0, pad), constant_values=n_nodes).reshape(-1, c)
    f32 = dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if f32 else jax.lax.Precision.DEFAULT

    def block(acc, xs):
        b, w, nd = xs
        onehot = jax.nn.one_hot(b, n_bins, dtype=F32)              # (c, F, B)
        at = jax.nn.one_hot(nd, n_nodes, dtype=F32)                # (c, N)
        wn = (at[:, None, :] * w[:, :, None]).reshape(c, 2 * n_nodes)
        acc = acc + jnp.einsum("cn,cfb->nfb", _rnd(wn, dtype), onehot,
                               precision=prec)
        return acc, None

    acc0 = jnp.zeros((2 * n_nodes, f, n_bins), F32)
    acc, _ = jax.lax.scan(block, acc0, (bins, gh, node))
    return acc.reshape(2, n_nodes, f, n_bins)


def _gains(hist, lam, mcw, n_bins: int, feat_mask):
    """(gain of every (feature, bin) split per node, -inf where it is not
    admissible; the node's G and H)."""
    gl = jnp.cumsum(hist[0], axis=-1)
    hl = jnp.cumsum(hist[1], axis=-1)
    gt = gl[:, :1, -1:]
    ht = hl[:, :1, -1:]
    gr, hr = gt - gl, ht - hl
    gain = gl**2 / (hl + lam) + gr**2 / (hr + lam) - gt**2 / (ht + lam)
    ok = (hl >= mcw) & (hr >= mcw)
    ok &= feat_mask[None, :, None]
    ok &= jnp.arange(n_bins)[None, None, :] < n_bins - 1
    gain = jnp.where(ok, gain, -jnp.inf).reshape(gain.shape[0], -1)
    return gain, gt[:, 0, 0], ht[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("depth", "n_bins", "dtype"))
def grow_tree(bins, g, h, lam, gamma, mcw, feat_mask, *, depth: int,
              n_bins: int, dtype):
    """One complete tree: heap-ordered (feature, bin) splits, the leaf sums
    of g and h, and each row's leaf."""
    r = bins.shape[0]
    node = jnp.zeros((r,), jnp.int32)
    feats, splits = [], []
    for level in range(depth):
        n = 1 << level
        hist = histograms(bins, g, h, node, n_nodes=n, n_bins=n_bins,
                          dtype=dtype)
        gains, _, _ = _gains(hist, lam, mcw, n_bins, feat_mask)
        best = jnp.argmax(gains, axis=-1)
        feat, split = best // n_bins, best % n_bins
        stop = gains[jnp.arange(n), best] <= gamma
        feat = jnp.where(stop, 0, feat).astype(jnp.int32)
        split = jnp.where(stop, n_bins - 1, split).astype(jnp.int32)
        feats.append(feat)
        splits.append(split)
        row_bin = jnp.take_along_axis(bins, feat[node][:, None], axis=1)[:, 0]
        node = 2 * node + (row_bin > split[node]).astype(jnp.int32)
    leaf_g = jnp.zeros((1 << depth,), F32).at[node].add(g)
    leaf_h = jnp.zeros((1 << depth,), F32).at[node].add(h)
    return jnp.concatenate(feats), jnp.concatenate(splits), leaf_g, leaf_h, node


@functools.partial(jax.jit, static_argnames=("depth", "n_bins", "dtype"))
def _gbdt_round(bins, y, margin, eta, lam, gamma, mcw, *, depth: int,
                n_bins: int, dtype):
    p = jax.nn.sigmoid(margin)
    g = _rnd(p - y, dtype)
    h = _rnd(jnp.maximum(p * (1.0 - p), 1e-16), dtype)
    mask = jnp.ones((bins.shape[1],), bool)
    feat, split, lg, lh, node = grow_tree(bins, g, h, lam, gamma, mcw, mask,
                                          depth=depth, n_bins=n_bins,
                                          dtype=dtype)
    leaf = _rnd(-eta * lg / (lh + lam), dtype)
    return feat, split, leaf, _rnd(margin + leaf[node], dtype)


def _thresholds(feat, split, edges, n_bins):
    """Split bins as raw-feature thresholds: ``x > edges[f, s]`` goes right;
    a node that sends every row left gets +inf."""
    real = split < n_bins - 1
    return np.where(real, edges[feat, np.minimum(split, n_bins - 2)],
                    np.float32(np.inf)).astype(np.float32)


def route(x: np.ndarray, feat, thresh, leaves, depth: int,
          dtype=np.float32, base: float = 0.0) -> np.ndarray:
    """Sum over trees of each row's leaf value, in tree order, the sum
    stored in ``dtype``."""
    rows = np.arange(x.shape[0])
    out = np.full((x.shape[0],), base, np.float32)
    for tf, tt, tl in zip(feat, thresh, leaves):
        local = np.zeros(x.shape[0], np.int64)
        for level in range(depth):
            i = (1 << level) - 1 + local
            local = 2 * local + (x[rows, tf[i]] > tt[i])
        out = (out + tl[local]).astype(dtype).astype(np.float32)
    return out


class Prepared:
    """The training rows, quantised once per bin count, on the device."""

    def __init__(self, x: np.ndarray, y: np.ndarray):
        self.x, self.y = x, y
        self.y_dev = jnp.asarray(y, F32)
        self._bins: dict[int, tuple] = {}

    def bins(self, max_bins: int):
        if max_bins not in self._bins:
            b, edges, n = quantize(self.x, max_bins)
            self._bins[max_bins] = (jnp.asarray(b), edges, n)
        return self._bins[max_bins]

    def bins_for(self, family: str, params: Mapping):
        if family == "gbdt":
            return self.bins(int({**DEFAULTS["gbdt"], **params}["max_bin"]))
        return self.bins(FOREST_BINS)


def _base_margin(y) -> float:
    prior = float(np.clip(np.asarray(y).mean(), 1e-6, 1 - 1e-6))
    return float(np.log(prior / (1 - prior)))


def _forest_draws(key, t: int, r: int, f: int):
    """Tree ``t``'s bootstrap weights and feature mask."""
    kb, kf = jax.random.split(jax.random.fold_in(key, t))
    w = jax.random.poisson(kb, 1.0, (r,)).astype(F32)
    perm = jax.random.permutation(kf, f)
    mask = jnp.zeros((f,), bool).at[perm[:max(1, int(np.sqrt(f)))]].set(True)
    return w, mask


def fit_trees(prep: Prepared, family: str, params: Mapping, dtype) -> dict:
    """A GBDT or forest model trained by the reference: ``{"feat",
    "thresh", "leaves"}`` per tree, ``base`` and ``depth``."""
    p = {**DEFAULTS[family], **params}
    bins, edges, n_bins = prep.bins_for(family, p)
    r, f = bins.shape
    depth = int(p["max_depth"])
    feats, splits, leaves = [], [], []
    if family == "gbdt":
        base = _base_margin(prep.y)
        margin = jnp.full((r,), base, F32)
        for _ in range(int(p["round"])):
            feat, split, leaf, margin = _gbdt_round(
                bins, prep.y_dev, margin, jnp.float32(p["eta"]),
                jnp.float32(p["lambda"]), jnp.float32(p["gamma"]),
                jnp.float32(p["min_child_weight"]), depth=depth,
                n_bins=n_bins, dtype=dtype)
            feats.append(feat)
            splits.append(split)
            leaves.append(leaf)
    else:
        base = 0.0
        key = jax.random.key(int(p["seed"]))
        for t in range(int(p["n_estimators"])):
            w, mask = _forest_draws(key, t, r, f)
            feat, split, lg, lh, _ = grow_tree(
                bins, _rnd(-prep.y_dev * w, dtype), _rnd(w, dtype),
                jnp.float32(1e-6), jnp.float32(0.0),
                jnp.float32(p["min_samples_leaf"]), mask, depth=depth,
                n_bins=n_bins, dtype=dtype)
            feats.append(feat)
            splits.append(split)
            leaves.append(_rnd(-lg / jnp.maximum(lh, 1e-6), dtype))
    feats = [np.asarray(a) for a in feats]
    thresh = [_thresholds(a, np.asarray(s), edges, n_bins)
              for a, s in zip(feats, splits)]
    return {"feat": np.stack(feats), "thresh": np.stack(thresh),
            "leaves": np.stack([np.asarray(a) for a in leaves]),
            "base": base, "depth": depth}


def tree_probs(family: str, model: dict, x: np.ndarray,
               dtype=np.float32) -> np.ndarray:
    total = route(x, model["feat"], model["thresh"], model["leaves"],
                  int(model["depth"]), np.dtype(dtype), base=model["base"])
    if family == "gbdt":
        return sigmoid(total)
    return np.clip(total / len(model["feat"]), 0.0, 1.0).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("depth", "n_bins"))
def _replay_tree(bins, g, h, feat, split, lam, gamma, mcw, feat_mask, *,
                 depth: int, n_bins: int):
    """Check one given tree against the reference's split rule.

    The tree's rows are partitioned by its own splits; at every node the
    reference's histograms give the best admissible gain and the gain of
    the split the tree took. Returns the worst shortfall of the taken split
    below the best, relative to the node's scale (the larger of the best
    gain and ``G²/(H+λ)``) and at most 1, and the leaf sums of g and h."""
    r = bins.shape[0]
    node = jnp.zeros((r,), jnp.int32)
    worst = jnp.float32(0.0)
    for level in range(depth):
        n = 1 << level
        lo = n - 1
        hist = histograms(bins, g, h, node, n_nodes=n, n_bins=n_bins,
                          dtype=F32)
        gain, gt, ht = _gains(hist, lam, mcw, n_bins, feat_mask)
        best = gain.max(axis=-1)
        tf, ts = feat[lo:lo + n], split[lo:lo + n]
        stops = ts >= n_bins - 1
        taken = gain[jnp.arange(n), tf * n_bins + jnp.minimum(ts, n_bins - 1)]
        scale = jnp.maximum(jnp.where(jnp.isfinite(best), jnp.abs(best), 0.0),
                            gt**2 / (ht + lam)) + 1e-30
        # a node that stops while the reference splits, or splits where the
        # reference stops or below the reference's best, falls short
        short_stop = jnp.where(best > gamma, best - gamma, 0.0)
        short_split = jnp.maximum(best - taken, gamma - taken)
        short = jnp.where(stops, short_stop, short_split) / scale
        # a split the reference would not take at all reads 1
        short = jnp.where(jnp.isnan(short), 1.0, jnp.minimum(short, 1.0))
        worst = jnp.maximum(worst, short.max())
        row_bin = jnp.take_along_axis(bins, tf[node][:, None], axis=1)[:, 0]
        node = 2 * node + (row_bin > ts[node]).astype(jnp.int32)
    leaf_g = jnp.zeros((1 << depth,), F32).at[node].add(g)
    leaf_h = jnp.zeros((1 << depth,), F32).at[node].add(h)
    return worst, leaf_g, leaf_h, node


def _split_bins(feat, thresh, edges, n_bins):
    """The bin each threshold splits at (its edge's index; +inf is the stop
    split ``n_bins - 1``). A threshold that is no edge of its feature maps
    to -1, which the replay reads as an inadmissible split."""
    out = np.full(feat.shape, n_bins - 1, np.int32)
    for i, (a, t) in enumerate(zip(feat, thresh)):
        if np.isinf(t):
            continue
        row = edges[a]
        s = int(np.searchsorted(row, t, side="left"))
        out[i] = s if s < row.size and row[s] == t else -1
    return out


def _cut_depth(model: dict, depth: int):
    """The model's trees at the configuration's own depth: levels past it
    must stop, and the leaf a row reaches there is its first descendant."""
    d = int(model["depth"])
    feat, thresh, leaves = model["feat"], model["thresh"], model["leaves"]
    if d == depth:
        return feat, thresh, leaves, True
    keep = (1 << depth) - 1
    extra_stop = bool(np.all(np.isinf(thresh[:, keep:])))
    return (feat[:, :keep], thresh[:, :keep],
            leaves[:, ::1 << (d - depth)], extra_stop)


def replay_trees(prep: Prepared, family: str, params: Mapping,
                 model: dict) -> dict:
    """``split_gap`` and ``leaf_gap`` of a given GBDT or forest model.

    Each tree is replayed on the training rows in the order it was grown,
    with the g and h that the reference derives from the model's own earlier
    trees. ``split_gap`` is the worst relative shortfall of a taken split
    below the reference's best at that node; ``leaf_gap`` the worst gap of a
    leaf value from the reference's value for the rows that reach it,
    relative to the largest reference leaf of the tree."""
    p = {**DEFAULTS[family], **params}
    bins, edges, n_bins = prep.bins_for(family, p)
    r, f = bins.shape
    depth = int(p["max_depth"])
    feat, thresh, leaves, extra_stop = _cut_depth(model, depth)
    n_trees = int(p["round"] if family == "gbdt" else p["n_estimators"])
    if len(feat) != n_trees or not extra_stop:
        return {"split_gap": float("inf"), "leaf_gap": float("inf")}
    split_gap = leaf_gap = 0.0
    margin = jnp.full((r,), float(model["base"]), F32)
    key = jax.random.key(int(p.get("seed", 0)))
    for t in range(n_trees):
        splits = _split_bins(feat[t], thresh[t], edges, n_bins)
        if np.any(splits < 0):
            return {"split_gap": float("inf"), "leaf_gap": float("inf")}
        if family == "gbdt":
            prob = jax.nn.sigmoid(margin)
            g = prob - prep.y_dev
            h = jnp.maximum(prob * (1.0 - prob), 1e-16)
            mask = jnp.ones((f,), bool)
            lam, gamma, mcw = p["lambda"], p["gamma"], p["min_child_weight"]
        else:
            w, mask = _forest_draws(key, t, r, f)
            g, h = -prep.y_dev * w, w
            lam, gamma, mcw = 1e-6, 0.0, p["min_samples_leaf"]
        worst, lg, lh, node = _replay_tree(
            bins, g, h, jnp.asarray(feat[t], jnp.int32), jnp.asarray(splits),
            jnp.float32(lam), jnp.float32(gamma), jnp.float32(mcw), mask,
            depth=depth, n_bins=n_bins)
        if family == "gbdt":
            want = -float(p["eta"]) * lg / (lh + float(lam))
        else:
            want = -lg / jnp.maximum(lh, 1e-6)
        want = np.asarray(want, np.float64)
        got = np.asarray(leaves[t], np.float64)
        scale = max(float(np.abs(want).max()), 1e-30)
        split_gap = max(split_gap, float(worst))
        leaf_gap = max(leaf_gap, float(np.abs(got - want).max()) / scale)
        if family == "gbdt":
            margin = margin + jnp.asarray(leaves[t], F32)[node]
    return {"split_gap": split_gap, "leaf_gap": leaf_gap}


# ---------------------------------------------------------------------------
# dense families
# ---------------------------------------------------------------------------

def _logistic(logits, y):
    return (jnp.maximum(logits, 0) - logits * y
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _adam(params, grads, m, v, t, lr, dtype):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda a, g: (b1 * a + (1 - b1) * g).astype(dtype), m, grads)
    v = jax.tree.map(lambda a, g: (b2 * a + (1 - b2) * g * g).astype(dtype), v, grads)
    new = jax.tree.map(
        lambda p_, a, b: (p_ - lr * (a / (1 - b1**t))
                          / (jnp.sqrt(b / (1 - b2**t)) + eps)).astype(dtype),
        params, m, v)
    return new, m, v


def _mlp_forward(params, x):
    h = x
    for i, (w, b) in enumerate(params):
        h = h @ w + b
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h[:, 0]


def _mlp_init(key, dims):
    """He-normal weights from successive splits of the seed's key, zero
    biases."""
    params, k = [], key
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        k, sub = jax.random.split(k)
        w = jax.random.normal(sub, (d_in, d_out), F32) * jnp.sqrt(2.0 / d_in)
        params.append((w, jnp.zeros((d_out,), F32)))
    return params


@functools.partial(jax.jit,
                   static_argnames=("dims", "steps", "batch", "keep", "dtype"))
def _fit_mlp(x, y, key, lr, *, dims, steps: int, batch: int, keep: int, dtype):
    """Minibatch Adam; the gradient is the mean over the first ``keep`` rows
    of each drawn minibatch (all of them but where a fault is planted)."""
    params = [(w.astype(dtype), b.astype(dtype)) for w, b in _mlp_init(key, dims)]
    zeros = jax.tree.map(jnp.zeros_like, params)
    x, y = x.astype(dtype), y.astype(dtype)

    def loss(ps, xb, yb):
        return jnp.mean(_logistic(_mlp_forward(ps, xb), yb))

    def step(carry, i):
        ps, m, v, kk = carry
        kk, sub = jax.random.split(kk)
        idx = jax.random.randint(sub, (batch,), 0, x.shape[0])[:keep]
        grads = jax.grad(loss)(ps, x[idx], y[idx])
        ps, m, v = _adam(ps, grads, m, v, i + 1.0, lr, dtype)
        return (ps, m, v, kk), None

    (params, _, _, _), _ = jax.lax.scan(
        step, (params, zeros, zeros, key), jnp.arange(steps, dtype=F32))
    return params


def _logreg_objective(w, b, x, y, c, n):
    return (jnp.mean(_logistic(x @ w + b, y)) + 0.5 / (c * n) * jnp.sum(w * w))


@functools.partial(jax.jit, static_argnames=("steps", "dtype"))
def _fit_logreg(x, y, c, lr, *, steps: int, dtype):
    n, d = x.shape
    x, y = x.astype(dtype), y.astype(dtype)
    params = (jnp.zeros((d,), dtype), jnp.zeros((), dtype))

    def step(carry, i):
        ps, m, v = carry
        grads = jax.grad(lambda q: _logreg_objective(*q, x, y, c, n))(ps)
        ps, m, v = _adam(ps, grads, m, v, i + 1.0, lr, dtype)
        return (ps, m, v), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros),
                                     jnp.arange(steps, dtype=F32))
    return params


def _dims(prep: Prepared, p: Mapping) -> tuple[int, ...]:
    return ((prep.x.shape[1],)
            + tuple(int(h) for h in str(p["network"]).split("_")) + (1,))


def _as_layers(params) -> list:
    return [(np.asarray(w, np.float32), np.asarray(b, np.float32))
            for w, b in params]


def fit_dense(prep: Prepared, family: str, params: Mapping, dtype,
              half: bool = False) -> dict:
    """An MLP's ``layers`` [(w, b), ...] or logistic regression's single
    layer (w as a column, b), trained by the reference. ``half`` plants a
    fault: half of each batch left out, the mean taken over the rest."""
    p = {**DEFAULTS[family], **params}
    x = jnp.asarray(prep.x)
    if family == "mlp":
        batch = int(min(p["batch_size"], prep.x.shape[0]))
        net = _fit_mlp(x, prep.y_dev, jax.random.key(int(p["seed"])),
                       jnp.float32(p["learning_rate"]), dims=_dims(prep, p),
                       steps=int(p["steps"]), batch=batch,
                       keep=batch // 2 if half else batch, dtype=dtype)
        return {"layers": _as_layers(net)}
    rows = prep.x.shape[0] // 2 if half else prep.x.shape[0]
    w, b = _fit_logreg(x[:rows], prep.y_dev[:rows], jnp.float32(p["c"]),
                       jnp.float32(p["lr"]), steps=int(p["steps"]), dtype=dtype)
    return {"layers": [(np.asarray(w, np.float32)[:, None],
                        np.asarray(b, np.float32).reshape(1))]}


def dense_init(prep: Prepared, family: str, params: Mapping) -> dict:
    """The state a dense fit starts from, in the form of :func:`fit_dense`."""
    p = {**DEFAULTS[family], **params}
    if family == "mlp":
        return {"layers": _as_layers(_mlp_init(jax.random.key(int(p["seed"])),
                                               _dims(prep, p)))}
    return {"layers": [(np.zeros((prep.x.shape[1], 1), np.float32),
                        np.zeros((1,), np.float32))]}


@functools.partial(jax.jit, static_argnames=("family",))
def _dense_objective(layers, x, y, c, *, family: str):
    with jax.default_matmul_precision("highest"):
        if family == "mlp":
            return jnp.mean(_logistic(_mlp_forward(layers, x), y))
        (w, b), = layers
        return _logreg_objective(w[:, 0], b[0], x, y, c, x.shape[0])


def dense_loss(prep: Prepared, family: str, params: Mapping,
               model: dict) -> float:
    """The objective the family minimises, on every training row, in
    float32 at the highest matmul precision: the mean logistic loss, plus
    for logistic regression its ``|w|² / (2·c·n)``."""
    p = {**DEFAULTS[family], **params}
    layers = [(jnp.asarray(w, F32), jnp.asarray(b, F32))
              for w, b in model["layers"]]
    return float(_dense_objective(layers, jnp.asarray(prep.x), prep.y_dev,
                                  jnp.float32(p.get("c", 1.0)), family=family))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dense_margin(layers, x, *, dtype):
    h = x.astype(dtype)
    for i, (w, b) in enumerate(layers):
        h = h @ w.astype(dtype) + b.astype(dtype)
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h[:, 0].astype(F32)


def dense_probs(model: dict, x: np.ndarray, dtype=jnp.float32) -> np.ndarray:
    layers = [(jnp.asarray(w), jnp.asarray(b)) for w, b in model["layers"]]
    return sigmoid(np.asarray(_dense_margin(layers, jnp.asarray(x),
                                            dtype=dtype)))


def change_gap(model: dict, want: dict, init: dict) -> float:
    """How far the norm of each weight or bias array's change from ``init``
    lies from the reference's: the worst gap of the two norms (not the norm
    of their difference), relative to the larger of the reference's norm of
    that change and of the median array's. A state left unchanged reads 1."""
    got = [a for wb in model["layers"] for a in wb]
    ref = [a for wb in want["layers"] for a in wb]
    start = [a for wb in init["layers"] for a in wb]
    if [a.shape for a in got] != [a.shape for a in ref]:
        return float("inf")
    moved = [float(np.linalg.norm(np.asarray(a, np.float64) - s))
             for a, s in zip(got, start)]
    norms = [float(np.linalg.norm(np.asarray(a, np.float64) - s))
             for a, s in zip(ref, start)]
    floor = float(np.median(norms))
    return max(abs(m - n) / max(n, floor, 1e-30) for m, n in zip(moved, norms))


TREES = ("gbdt", "forest")


def fit(prep: Prepared, family: str, params: Mapping, dtype) -> dict:
    if family in TREES:
        return fit_trees(prep, family, params, dtype)
    return fit_dense(prep, family, params, dtype)


def probs(family: str, model: dict, x: np.ndarray, dtype=jnp.float32):
    if family in TREES:
        return tree_probs(family, model, x, dtype)
    return dense_probs(model, x, dtype)
