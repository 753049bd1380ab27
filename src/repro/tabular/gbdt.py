"""Histogram-based gradient-boosted trees in JAX — stands in for XGBoost.

The paper runs XGBoost for 864 of its 1,211 search tasks; this is the
framework's dominant workload. We implement the ``hist`` algorithm: features
are quantile-binned once (the ``quantized_bins`` uniform-format conversion,
executor-side), then each boosting round grows one depth-``max_depth`` tree
level-by-level from per-(node, feature, bin) grad/hess histograms
(``ops.level_split`` — fused Pallas histogram+split-scan kernel on TPU,
scatter + XLA scan on CPU — with histogram subtraction across levels,
DESIGN.md §3.8).

Trees are COMPLETE binary trees in heap layout: a node that stops splitting
gets a sentinel split (bin B−1 → every row routes left), so row→leaf routing
stays a fixed-shape gather chain and the whole training loop is one
``lax.scan`` over rounds under jit. Hyperparameters follow XGBoost naming
(eta, round, max_depth, max_bin, lambda, gamma, min_child_weight).

Two objectives: the logistic loss (one tree a round, scalar margins), and
for a K-class task (prepared data with ``n_classes = K``) XGBoost's
``multi:softprob`` — softmax margins, K trees a round grown by
``build_tree`` mapped over a class axis, so each level of all K trees is
one ``ops.level_split`` launch (DESIGN.md §3.10).
"""
from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.data_format import is_sharded_payload
from repro.core.evaluation import (
    predict_compile_cache,
    stable_sigmoid,
    stable_softmax,
)
from repro.core.interface import (
    Estimator,
    ResumeState,
    TrainedModel,
    register_estimator,
)
from repro.kernels import ops

__all__ = [
    "GBDTEstimator",
    "GBDTModel",
    "build_tree",
    "predict_margin",
    "predict_raw_margin",
    "batched_tree_margins",
]


def build_tree(
    bins: jax.Array,            # (R, F) int32 in [0, B)
    g: jax.Array,               # (R,) f32 gradients
    h: jax.Array,               # (R,) f32 hessians
    *,
    n_bins: int,
    max_depth: int,
    lam,
    gamma,
    min_child_weight,
    feat_mask: jax.Array | None = None,   # (F,) bool — forest feature subsets
    depth_limit=None,            # traced int: levels >= this force sentinels
    bin_limit=None,              # traced int: valid splits are < bin_limit - 1
    subtract: bool = True,       # histogram subtraction (DESIGN.md §3.8)
    force=None,                  # ops dispatch override, threaded to the kernel
    axis_name=None,              # SPMD shard axis (row-sharded data, §3.9)
    row_valid=None,              # (R,) bool — False on sharded pad rows
):
    """Grow one level-wise tree; returns (feat, split_bin, leaf_g, leaf_h).

    feat/split_bin: (2^D − 1,) heap-ordered internal nodes; sentinel split is
    ``split_bin == n_bins - 1`` (no row has bin > B−1, so all go left).
    leaf_g/leaf_h: (2^D,) per-leaf grad/hess sums for the caller's leaf-value
    formula (GBDT: −η·G/(H+λ); forest: −G/H = mean target).

    ``lam``/``gamma``/``min_child_weight`` may be traced 0-d arrays, and
    ``depth_limit``/``bin_limit`` traced ints — this is how the fused-batch
    path (``train_batched``) vmaps heterogeneous configs through ONE compile:
    a config with a shallower tree forces sentinel splits past its depth, and
    a config with coarser quantisation masks bins past its own bin count.

    Each level is one ``ops.level_split`` (fused Pallas kernel on TPU, the
    historical scatter + scan ops on CPU). With ``subtract`` (the default)
    the level's histograms are cached and the NEXT level builds only the
    smaller child of each sibling pair, deriving the sibling as
    ``parent − small`` — about half the histogram work per level below the
    root. ``subtract=False`` is the pre-subtraction path, kept as the
    bit-exactness reference (tests) and the bench comparison point.
    """
    r, f = bins.shape
    node = jnp.zeros((r,), jnp.int32)        # level-local node of each row
    feats, splits = [], []
    parent = None                            # previous level's histograms
    for level in range(max_depth):
        n_nodes = 1 << level
        keep_hist = subtract and level + 1 < max_depth
        with jax.named_scope("level_split"):
            parent, best_gain, feat, split = ops.level_split(
                bins, g, h, node, n_nodes=n_nodes, n_bins=n_bins,
                lam=lam, min_child_weight=min_child_weight,
                bin_limit=bin_limit, feat_mask=feat_mask,
                parent_hist=parent if subtract else None,
                return_hist=keep_hist, force=force,
                axis_name=axis_name, row_valid=row_valid)
        is_leaf = best_gain <= gamma
        if depth_limit is not None:
            is_leaf = is_leaf | (level >= depth_limit)
        feat = jnp.where(is_leaf, 0, feat)
        split = jnp.where(is_leaf, n_bins - 1, split)    # sentinel: all left
        feats.append(feat)
        splits.append(split)
        with jax.named_scope("row_routing"):
            row_bin = jnp.take_along_axis(bins, feat[node][:, None], axis=1)[:, 0]
            node = 2 * node + (row_bin > split[node]).astype(jnp.int32)
    n_leaves = 1 << max_depth
    with jax.named_scope("leaf_sums"):
        if row_valid is not None:
            g = jnp.where(row_valid, g, 0.0)
            h = jnp.where(row_valid, h, 0.0)
        leaf_g = jnp.zeros((n_leaves,), jnp.float32).at[node].add(g)
        leaf_h = jnp.zeros((n_leaves,), jnp.float32).at[node].add(h)
        if axis_name is not None:
            # per-shard leaf sums → global: leaf values become shard-invariant
            leaf_g = jax.lax.psum(leaf_g, axis_name)
            leaf_h = jax.lax.psum(leaf_h, axis_name)
    return jnp.concatenate(feats), jnp.concatenate(splits), leaf_g, leaf_h


def predict_margin(bins, feat, split, leaf_value, max_depth: int):
    """Route binned rows through one heap-layout tree; returns (R,) margins.

    Compile time on the TPU shapes this: each level looks (feat, split) up
    in ONE packed gather, and materializes its node vector
    (``optimization_barrier``). Two small-table gathers per level, fused
    across levels, took the v5e compiler over 20 s for a depth-6 tree at
    600k rows; this form compiles in under 2 s at every depth."""
    r = bins.shape[0]
    local = jnp.zeros((r,), jnp.int32)
    table = jnp.stack([feat, split], axis=1)
    for level in range(max_depth):
        fs = table[(1 << level) - 1 + local]
        row_bin = jnp.take_along_axis(bins, fs[:, :1], axis=1)[:, 0]
        local = jax.lax.optimization_barrier(
            2 * local + (row_bin > fs[:, 1]).astype(jnp.int32))
    return leaf_value[local]


# --------------------------------------------------------------------------
# Jitted validation plane (DESIGN.md §3.4): raw-feature tree routing.
# --------------------------------------------------------------------------

def predict_raw_margin(x, feat, thresh, leaves, base, *, max_depth: int):
    """Margins of RAW rows through a whole heap-layout tree stack, one
    program: ``lax.scan`` over the (rounds, ·) tree arrays, each level a
    vectorized gather+compare — this replaces the driver's per-round
    per-level numpy loop (``GBDTModel.predict_margin``). Sentinel splits
    carry ``thresh = +inf`` (``x > inf`` is False → every row routes left),
    so depth-padded and round-padded trees route exactly like the numpy
    predictor; a fully-sentinel PADDING tree lands every row in leaf 0,
    whose value is 0, adding nothing to the margin.

    A K-class stack has (rounds, K, ·) trees and a (K,) base: each round
    routes its K trees at once (mapped over the class axis) and the
    margins come back (R, K)."""
    r = x.shape[0]

    def route(tf, tt, tl):
        local = jnp.zeros((r,), jnp.int32)
        for level in range(max_depth):
            g = (1 << level) - 1 + local
            xv = jnp.take_along_axis(x, tf[g][:, None], axis=1)[:, 0]
            local = 2 * local + (xv > tt[g]).astype(jnp.int32)
        return tl[local]

    classes = feat.ndim == 3
    if classes:
        route = jax.vmap(route)

    def one_tree(margin, tree):
        return margin + route(*tree), 0.0

    with jax.named_scope("tree_routing"):
        if classes:
            margin0 = jnp.zeros((feat.shape[1], r), jnp.float32) + base[:, None]
        else:
            margin0 = jnp.full((r,), jnp.float32(0.0), jnp.float32) + base
        margin, _ = jax.lax.scan(one_tree, margin0, (feat, thresh, leaves))
    return margin.T if classes else margin


def _build_predict_batched(max_depth: int):
    """Predict-compile-cache builder: vmap the tree-stack router over a
    model batch (shared rows, per-model trees + base)."""
    core = functools.partial(predict_raw_margin, max_depth=max_depth)
    return jax.jit(jax.vmap(core, in_axes=(None, 0, 0, 0, 0)))


def _stack_tree_models(models) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack per-model tree arrays into one (B, T, ·) batch, padding each
    model's tree count to the shared pow-2 maximum with sentinel trees —
    the batch analogue of ``pad_configs``: a fused unit's models share
    padded DEPTH by construction (``train_batched``), rounds pad here, so
    one compile serves any batch whose padded shape matches."""
    from repro.core.fusion import pad_pow2

    pad_t = pad_pow2(max(m.feat.shape[0] for m in models))
    b, nodes = len(models), models[0].feat.shape[1:]
    leaf_shape = models[0].leaves.shape[1:]
    feat = np.zeros((b, pad_t) + nodes, np.int32)
    thresh = np.full((b, pad_t) + nodes, np.inf, np.float32)
    leaves = np.zeros((b, pad_t) + leaf_shape, np.float32)
    for i, m in enumerate(models):
        t = m.feat.shape[0]
        feat[i, :t] = m.feat
        thresh[i, :t] = m.thresh
        leaves[i, :t] = m.leaves
    return feat, thresh, leaves


def batched_tree_margins(models, x, *, cache=None) -> np.ndarray:
    """(B, rows) margins for a stack of heap-layout tree models (GBDT with
    its base margin, forest with base 0) — shared by both families' jitted
    paths; (B, rows, K) for K-class GBDT models. Models are grouped by
    depth (a fused unit is a single group by construction; mixed stacks
    still score correctly), each group one vmapped program through the
    predict compile cache."""
    cache = cache if cache is not None else predict_compile_cache()
    x = jnp.asarray(x, jnp.float32)
    classes = models[0].feat.shape[1:-1]          # () or (K,)
    out = np.empty((len(models), x.shape[0]) + classes, np.float32)
    groups: dict[int, list[int]] = {}
    for i, m in enumerate(models):
        groups.setdefault(int(m.max_depth), []).append(i)
    for depth, idxs in groups.items():
        feat, thresh, leaves = _stack_tree_models([models[i] for i in idxs])
        fn = cache.get(
            ("tree_predict", depth, feat.shape[1], len(idxs), tuple(x.shape))
            + classes,
            lambda: _build_predict_batched(depth),
        )
        base = jnp.asarray(np.stack(
            [np.broadcast_to(np.float32(getattr(models[i], "base", 0.0)),
                             classes) for i in idxs]), jnp.float32)
        margins = fn(x, jnp.asarray(feat), jnp.asarray(thresh),
                     jnp.asarray(leaves), base)
        out[idxs] = np.asarray(margins)
    return out


def note_level_rows(data, n_bins: int, **meta) -> None:
    """Name on the open ``repro.train`` span which rows the fit's levels
    below the root read: ``ops.level_rows`` of its width, or ``"all"`` for
    row-sharded data, whose levels mask rows in place (DESIGN.md §3.9).
    ``meta`` adds what else the fit knows (GBDT: ``outputs``, ``trees``)."""
    rows = ("all" if is_sharded_payload(data)
            else ops.level_rows(int(data["bins"].shape[-1]), n_bins))
    tracing.annotate(level_rows=rows, **meta)


def n_classes_of(data) -> int:
    """K of prepared data for a K-class task (``DenseMatrix.n_classes``),
    0 for a binary one. Row-sharded placements have no multi-class fit."""
    k = int(data.get("n_classes", 0) or 0)
    if k and is_sharded_payload(data):
        raise ValueError("multi-class GBDT has no row-sharded placement")
    return k


def _coarse_bins(bins, factor):
    """``bins // factor`` for a traced ``factor``, in-graph. Integer division
    by a traced divisor costs the TPU compiler tens of seconds per program at
    a million rows, so the quotient is taken in f32 and then corrected with
    integer products: f32 division need not be correctly rounded on every
    backend, but it lands within one of the true quotient for ids and
    factors far below 2^24, and one step each way makes it exact."""
    factor = jnp.asarray(factor, bins.dtype)
    q = jnp.floor(bins.astype(jnp.float32) / factor.astype(jnp.float32))
    q = q.astype(bins.dtype)
    q = q - (q * factor > bins).astype(bins.dtype)
    return q + ((q + 1) * factor <= bins).astype(bins.dtype)


def _boosting_round(cbins, y, n_rounds, depth_limit, bin_limit, eta, lam,
                    gamma, min_child_weight, *, n_classes: int, n_bins: int,
                    max_depth: int, subtract: bool, force, axis_name,
                    row_valid):
    """The round both cores scan: ``margin -> (margin', tree)``.

    ``n_classes = 0``: the logistic loss, one tree a round on (R,) margins.
    ``n_classes = K``: softmax over (K, R) margins (XGBoost's
    ``multi:softprob``: g_k = p_k − [y = k], h_k = 2·p_k·(1 − p_k)), and
    ``build_tree`` and the margin routing mapped over the class axis, so a
    level of all K trees is one ``ops.level_split`` launch and the tree
    arrays gain a K axis after the round axis."""
    grow = functools.partial(
        build_tree, cbins, n_bins=n_bins, max_depth=max_depth,
        lam=lam, gamma=gamma, min_child_weight=min_child_weight,
        depth_limit=depth_limit, bin_limit=bin_limit,
        subtract=subtract, force=force,
        axis_name=axis_name, row_valid=row_valid)
    route = functools.partial(predict_margin, cbins, max_depth=max_depth)
    if n_classes:
        onehot = (y[None, :] == jnp.arange(n_classes, dtype=y.dtype)[:, None]
                  ).astype(jnp.float32)
        grow, route = jax.vmap(grow), jax.vmap(route)

    def one_round(margin, r_idx):
        with jax.named_scope("gradients"):
            if n_classes:
                p = jax.nn.softmax(margin, axis=0)
                g = p - onehot
                h = jnp.maximum(2.0 * p * (1.0 - p), 1e-16)
            else:
                p = jax.nn.sigmoid(margin)
                g = p - y
                h = jnp.maximum(p * (1.0 - p), 1e-16)
        feat, split, leaf_g, leaf_h = grow(g, h)
        with jax.named_scope("margin_update"):
            # where (not multiply): an empty padded leaf is 0/(0+λ), which for
            # λ=0 is NaN and would poison the margin through a plain mask
            leaf_value = jnp.where(
                r_idx < n_rounds, -eta * leaf_g / (leaf_h + lam), 0.0)
            margin = margin + route(feat, split, leaf_value)
        return margin, (feat, split, leaf_value)

    return one_round


def _initial_margin(base, n_rows: int, n_classes: int):
    """Every row's starting margin: (R,) of the scalar base, or (K, R) of
    the (K,) base margins."""
    if n_classes:
        return jnp.broadcast_to(
            jnp.asarray(base, jnp.float32)[:, None], (n_classes, n_rows))
    return jnp.full((n_rows,), base, jnp.float32)


def _fit_gbdt_core(
    bins, y, base, factor, bin_limit, n_rounds, depth_limit,
    eta, lam, gamma, min_child_weight, *, n_bins: int, rounds: int, max_depth: int,
    subtract: bool = True, force=None, axis_name=None, row_valid=None,
    n_classes: int = 0,
):
    """One GBDT fit over PADDED maxima (rounds/max_depth/n_bins static).

    Scalar hyperparameters (eta, lambda, gamma, min_child_weight) and the
    per-config structural LIMITS (factor, bin_limit, n_rounds, depth_limit)
    are traced — so one compile serves every config sharing the maxima, and
    ``jax.vmap`` over the traced args turns a whole config stack into one
    fused program (``train_batched``; the class axis of a K-class fit then
    sits inside the config axis). Masking keeps padded work inert:
    rounds past ``n_rounds`` add zero-valued trees, levels past
    ``depth_limit`` force sentinel splits, bins past ``bin_limit`` never win.
    """
    r = bins.shape[0]
    cbins = _coarse_bins(bins, factor)
    one_round = _boosting_round(
        cbins, y, n_rounds, depth_limit, bin_limit, eta, lam, gamma,
        min_child_weight, n_classes=n_classes, n_bins=n_bins,
        max_depth=max_depth, subtract=subtract, force=force,
        axis_name=axis_name, row_valid=row_valid)
    margin0 = _initial_margin(base, r, n_classes)
    _, trees = jax.lax.scan(one_round, margin0, jnp.arange(rounds))
    return trees  # (rounds, [K,] 2^D−1) ×2, (rounds, [K,] 2^D)


_fit_gbdt = functools.partial(
    jax.jit, static_argnames=("n_bins", "rounds", "max_depth", "subtract",
                              "force", "n_classes")
)(_fit_gbdt_core)


def _resume_gbdt_core(
    bins, y, margin0, factor, bin_limit, n_rounds, depth_limit,
    eta, lam, gamma, min_child_weight, start,
    *, n_bins: int, rounds: int, max_depth: int,
    subtract: bool = True, force=None, axis_name=None, row_valid=None,
    n_classes: int = 0,
):
    """Boost ``rounds`` MORE trees on top of a carried margin — the rung
    machinery (DESIGN.md §3.6). Round indices continue from ``start`` and the
    final margin is returned alongside the trees (it IS the resume state:
    boosting's only carry is the ensemble margin), so rung-k-then-resume
    appends the exact trees a straight run would have grown. ``rounds`` is
    the UNPADDED increment — no masked tail whose ``+0.0`` margin adds could
    flip -0.0 bits between the chained and the straight run."""
    cbins = _coarse_bins(bins, factor)
    one_round = _boosting_round(
        cbins, y, n_rounds, depth_limit, bin_limit, eta, lam, gamma,
        min_child_weight, n_classes=n_classes, n_bins=n_bins,
        max_depth=max_depth, subtract=subtract, force=force,
        axis_name=axis_name, row_valid=row_valid)
    margin, trees = jax.lax.scan(one_round, margin0, start + jnp.arange(rounds))
    return trees, margin


_resume_gbdt = functools.partial(
    jax.jit, static_argnames=("n_bins", "rounds", "max_depth", "subtract",
                              "force", "n_classes")
)(_resume_gbdt_core)


# --------------------------------------------------------------------------
# Sharded data plane (DESIGN.md §3.9): row-sharded fits.
#
# Inputs arrive block-stacked — bins (S, Rs, F), y (S, Rs), valid (S, Rs) —
# from ``core.data_format.shard_payload``. Each shard runs the SAME per-round
# program as the unsharded core over its own rows; the only cross-shard
# communication is inside ``ops.level_split`` (one histogram psum per level,
# plus one count psum for the global smaller-child plan) and the leaf-sum
# psums in ``build_tree``. Tree outputs are shard-invariant; the resume
# margin stays per-shard (S, Rs) — it IS row-local state.
# --------------------------------------------------------------------------

_SHARD_AXIS = "shards"


def _fit_gbdt_sharded_core(
    bins, y, valid, base, factor, bin_limit, n_rounds, depth_limit,
    eta, lam, gamma, min_child_weight,
    *, n_bins: int, rounds: int, max_depth: int, n_shards: int,
    subtract: bool = True, force=None,
):
    from repro import compat

    def per_shard(b, yy, vv):
        return _fit_gbdt_core(
            b, yy, base, factor, bin_limit, n_rounds, depth_limit,
            eta, lam, gamma, min_child_weight,
            n_bins=n_bins, rounds=rounds, max_depth=max_depth,
            subtract=subtract, force=force,
            axis_name=_SHARD_AXIS, row_valid=vv)

    return compat.sharded_call(per_shard, n_shards=n_shards,
                               axis=_SHARD_AXIS)(bins, y, valid)


_fit_gbdt_sharded = functools.partial(
    jax.jit, static_argnames=(
        "n_bins", "rounds", "max_depth", "n_shards", "subtract", "force")
)(_fit_gbdt_sharded_core)


def _resume_gbdt_sharded_core(
    bins, y, valid, margin0, factor, bin_limit, n_rounds, depth_limit,
    eta, lam, gamma, min_child_weight, start,
    *, n_bins: int, rounds: int, max_depth: int, n_shards: int,
    subtract: bool = True, force=None,
):
    """Sharded resume: the margin carry is PER-SHARD (S, Rs) — unlike the
    tree outputs it is row-local, so it rides the virtual vmap lowering
    directly (tree outputs take shard 0's copy, margins stay stacked)."""

    def per_shard(b, yy, vv, m0):
        return _resume_gbdt_core(
            b, yy, m0, factor, bin_limit, n_rounds, depth_limit,
            eta, lam, gamma, min_child_weight, start,
            n_bins=n_bins, rounds=rounds, max_depth=max_depth,
            subtract=subtract, force=force,
            axis_name=_SHARD_AXIS, row_valid=vv)

    trees, margin = jax.vmap(per_shard, axis_name=_SHARD_AXIS)(
        bins, y, valid, margin0)
    return jax.tree.map(lambda t: t[0], trees), margin


_resume_gbdt_sharded = functools.partial(
    jax.jit, static_argnames=(
        "n_bins", "rounds", "max_depth", "n_shards", "subtract", "force")
)(_resume_gbdt_sharded_core)


def _build_batched_sharded_fit(n_bins: int, rounds: int, max_depth: int,
                               n_shards: int, subtract: bool = True,
                               force=None):
    """Fused batches over sharded data: vmap-over-configs of the sharded
    core — the shard axis nests INSIDE the config axis, so one compile still
    serves the whole bucket."""
    core = functools.partial(
        _fit_gbdt_sharded_core, n_bins=n_bins, rounds=rounds,
        max_depth=max_depth, n_shards=n_shards, subtract=subtract, force=force)
    return jax.jit(jax.vmap(core, in_axes=(None, None, None, None) + (0,) * 8))


def _build_batched_fit(n_bins: int, rounds: int, max_depth: int,
                       subtract: bool = True, force=None, n_classes: int = 0):
    """Compile-cache builder: vmap the core over the per-config args (data,
    labels and base margin are shared across the batch)."""
    core = functools.partial(
        _fit_gbdt_core, n_bins=n_bins, rounds=rounds, max_depth=max_depth,
        subtract=subtract, force=force, n_classes=n_classes)
    return jax.jit(jax.vmap(core, in_axes=(None, None, None) + (0,) * 8))


class GBDTModel(TrainedModel):
    """Raw-feature predictor: thresholds are bin edges mapped back to floats.

    Binary: trees (rounds, ·) and a scalar ``base``; ``predict_proba`` is
    P(y=1). K classes: trees (rounds, K, ·) — round r's tree for class k at
    ``[r, k]`` — and ``base`` (K,); ``predict_proba`` is the (rows, K)
    softmax of the class margins."""

    def __init__(self, feat, thresh, leaves, base, max_depth: int,
                 trees: int | None = None):
        self.feat = np.asarray(feat)       # (rounds, [K,] 2^D − 1) int32
        self.thresh = np.asarray(thresh)   # (rounds, [K,] 2^D − 1) f32 (+inf = left)
        self.leaves = np.asarray(leaves)   # (rounds, [K,] 2^D) f32
        self.base = (np.asarray(base, np.float32) if self.feat.ndim == 3
                     else float(base))
        self.max_depth = max_depth
        #: trees grown by the call that made this model (a resumed rung
        #: grows only its increment); every tree it holds by default
        self.trees = (int(np.prod(self.feat.shape[:-1])) if trees is None
                      else int(trees))

    @property
    def n_classes(self) -> int:
        """K of a K-class model, 0 for a binary one."""
        return int(self.feat.shape[1]) if self.feat.ndim == 3 else 0

    def predict_margin(self, x: np.ndarray) -> np.ndarray:
        """(rows,) margins, or (rows, K) for a K-class model: each class's
        trees summed in round order onto its base margin."""
        x = np.asarray(x, np.float32)
        if self.n_classes:
            return np.stack([
                self._margin(x, self.feat[:, k], self.thresh[:, k],
                             self.leaves[:, k], self.base[k])
                for k in range(self.n_classes)], axis=1)
        return self._margin(x, self.feat, self.thresh, self.leaves, self.base)

    def _margin(self, x, feats, threshs, leaves_stack, base) -> np.ndarray:
        out = np.full((x.shape[0],), base, np.float32)
        for feat, thresh, leaves in zip(feats, threshs, leaves_stack):
            local = np.zeros(x.shape[0], np.int64)
            for level in range(self.max_depth):
                g = (1 << level) - 1 + local
                local = 2 * local + (x[np.arange(x.shape[0]), feat[g]] > thresh[g])
            out += leaves[local]
        return out

    def _proba(self, margins: np.ndarray) -> np.ndarray:
        return (stable_softmax(margins) if self.n_classes
                else stable_sigmoid(margins))

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        return self._proba(self.predict_margin(x))

    # ---- jitted validation plane (DESIGN.md §3.4) -----------------------
    def predict_margin_jax(self, x, *, cache=None) -> np.ndarray:
        """One-program device margins (scan over trees, gather per level);
        bit-identical to :meth:`predict_margin` — same float32 adds in the
        same tree order, sentinel thresholds route identically."""
        return batched_tree_margins([self], x, cache=cache)[0]

    def predict_proba_jax(self, x, *, cache=None) -> np.ndarray:
        # same stable link as predict_proba over bit-identical margins,
        # so the jitted path scores EXACTLY what the numpy path would
        return self._proba(self.predict_margin_jax(x, cache=cache))

    @classmethod
    def predict_margin_batched(cls, models, x, *, cache=None) -> np.ndarray:
        return batched_tree_margins(models, x, cache=cache)

    @classmethod
    def predict_proba_batched(cls, models, x, *, cache=None) -> np.ndarray:
        margins = batched_tree_margins(models, x, cache=cache)
        return models[0]._proba(margins)


@register_estimator
class GBDTEstimator(Estimator):
    name = "gbdt"
    data_format = "quantized_bins"
    budget_param = "round"
    objectives = ("binary", "multiclass")

    def default_params(self) -> dict[str, Any]:
        return {
            "eta": 0.3, "round": 30, "max_depth": 6, "max_bin": 64,
            "lambda": 1.0, "gamma": 0.0, "min_child_weight": 1.0,
        }

    def format_params(self, params: Mapping[str, Any]) -> dict[str, Any]:
        """``max_bin`` is a CONVERTER parameter (§3.3): quantization happens
        at the config's own granularity, so each (dataset, max_bin) pair is
        one prepared-data cache entry shared by every config using it —
        instead of the old fixed-256 conversion re-run per task and
        re-coarsened in-graph. ``_coarsen`` still handles data prepared at
        any finer granularity (factor > 1), e.g. the uniform 256-bin default
        used when callers convert without format params."""
        p = {**self.default_params(), **params}
        return {"max_bins": int(p["max_bin"])}

    @staticmethod
    def _coarsen(n_bins: int, max_bin: int) -> tuple[int, int]:
        # Coarsen an n_bins-level quantisation to max_bin levels (identity
        # when the data was prepared at max_bin already, the §3.3 default):
        # coarse bin = fine bin // factor; coarse edge s = fine edge
        # (s+1)·factor − 1 (same "x > edge ⇔ bin > s" identity).
        factor = max(1, -(-n_bins // max_bin))
        return factor, -(-n_bins // factor)

    @staticmethod
    def _base_margin(y, n_classes: int = 0):
        """The logit of the label mean, or for K classes the (K,) log class
        priors (float32), each prior floored at 1e-6 (a row sample may miss
        a class)."""
        if n_classes:
            counts = np.bincount(np.asarray(y).astype(np.int64),
                                 minlength=n_classes)[:n_classes]
            prior = np.clip(counts / max(1, counts.sum()), 1e-6, 1.0)
            return np.log(prior).astype(np.float32)
        prior = float(np.clip(np.asarray(y).mean(), 1e-6, 1 - 1e-6))
        return float(np.log(prior / (1 - prior)))

    @staticmethod
    def _sharded_base_margin(data) -> float:
        # flatten the (S, Rs) blocks and drop the zero tail pad: same values
        # in the same row order as the unsharded label vector, so the prior
        # (and hence the base margin) is bit-identical
        y = np.asarray(data["y"]).reshape(-1)[: int(data["_n_rows"])]
        return GBDTEstimator._base_margin(y)

    @staticmethod
    def _thresholds(feat_np, split_np, edges_np, factor: int, n_cbins: int):
        # Map split bins to float thresholds: coarse split s → fine edge index
        # (s+1)·factor − 1; sentinel (s ≥ n_cbins−1) or out-of-range → +inf.
        fine = (split_np + 1) * factor - 1
        in_range = (split_np < n_cbins - 1) & (fine < edges_np.shape[1])
        return np.where(
            in_range,
            edges_np[feat_np, np.minimum(fine, edges_np.shape[1] - 1)],
            np.float32(np.inf),
        ).astype(np.float32)

    def train(self, data, params: Mapping[str, Any]) -> GBDTModel:
        p = {**self.default_params(), **params}
        bins, edges, y = data["bins"], data["edges"], data["y"]
        factor, n_cbins = self._coarsen(int(data["n_bins"]), int(p["max_bin"]))
        max_depth, rounds = int(p["max_depth"]), int(p["round"])
        k = n_classes_of(data)
        note_level_rows(data, n_cbins, outputs=max(1, k),
                        trees=rounds * max(1, k))
        if is_sharded_payload(data):
            base = self._sharded_base_margin(data)
            feat, split, leaves = _fit_gbdt_sharded(
                bins, y, data["_shard_valid"], jnp.float32(base),
                jnp.int32(factor), jnp.int32(n_cbins),
                jnp.int32(rounds), jnp.int32(max_depth),
                jnp.float32(p["eta"]), jnp.float32(p["lambda"]),
                jnp.float32(p["gamma"]), jnp.float32(p["min_child_weight"]),
                n_bins=n_cbins, rounds=rounds, max_depth=max_depth,
                n_shards=int(data["_n_shards"]),
            )
        else:
            base = self._base_margin(y, k)
            feat, split, leaves = _fit_gbdt(
                bins, y, jnp.asarray(base, jnp.float32) if k else jnp.float32(base),
                jnp.int32(factor), jnp.int32(n_cbins),
                jnp.int32(rounds), jnp.int32(max_depth),
                jnp.float32(p["eta"]), jnp.float32(p["lambda"]),
                jnp.float32(p["gamma"]), jnp.float32(p["min_child_weight"]),
                n_bins=n_cbins, rounds=rounds, max_depth=max_depth, n_classes=k,
            )
        feat_np, split_np = np.asarray(feat), np.asarray(split)
        thresh = self._thresholds(feat_np, split_np, np.asarray(edges), factor, n_cbins)
        return GBDTModel(feat_np, thresh, leaves, base, max_depth)

    # ---- adaptive search (DESIGN.md §3.6) -------------------------------
    def train_resumable(self, data, params: Mapping[str, Any], *,
                        budget: int, state: ResumeState | None = None):
        p = {**self.default_params(), **params}
        bins, edges, y = data["bins"], data["edges"], data["y"]
        factor, n_cbins = self._coarsen(int(data["n_bins"]), int(p["max_bin"]))
        max_depth = int(p["max_depth"])
        sharded = is_sharded_payload(data)
        k = n_classes_of(data)
        base = (self._sharded_base_margin(data) if sharded
                else self._base_margin(y, k))
        target = int(budget)
        if state is None:
            start = 0
            # sharded margins carry per-shard blocks: same (S, Rs) layout
            # as the labels, so rung-resume keeps rows on their home shard
            margin0 = (_initial_margin(base, int(np.shape(y)[0]), k) if k
                       else jnp.full(np.shape(y), base, jnp.float32))
            classes = (k,) if k else ()
            n_nodes, n_leaves = (1 << max_depth) - 1, 1 << max_depth
            prev_feat = np.zeros((0,) + classes + (n_nodes,), np.int32)
            prev_thresh = np.zeros((0,) + classes + (n_nodes,), np.float32)
            prev_leaves = np.zeros((0,) + classes + (n_leaves,), np.float32)
        else:
            start = int(state.budget)
            pl = state.payload
            margin0 = jnp.asarray(pl["margin"], jnp.float32)
            prev_feat, prev_thresh, prev_leaves = pl["feat"], pl["thresh"], pl["leaves"]
        grown = max(0, target - start) * max(1, k)
        note_level_rows(data, n_cbins, outputs=max(1, k), trees=grown)
        if target > start:
            common = (
                jnp.int32(factor), jnp.int32(n_cbins),
                jnp.int32(target), jnp.int32(max_depth),
                jnp.float32(p["eta"]), jnp.float32(p["lambda"]),
                jnp.float32(p["gamma"]), jnp.float32(p["min_child_weight"]),
                jnp.int32(start),
            )
            if sharded:
                (feat, split, leaves), margin = _resume_gbdt_sharded(
                    bins, y, data["_shard_valid"], margin0, *common,
                    n_bins=n_cbins, rounds=target - start, max_depth=max_depth,
                    n_shards=int(data["_n_shards"]),
                )
            else:
                (feat, split, leaves), margin = _resume_gbdt(
                    bins, y, margin0, *common,
                    n_bins=n_cbins, rounds=target - start, max_depth=max_depth,
                    n_classes=k,
                )
            feat_np, split_np = np.asarray(feat), np.asarray(split)
            thresh = self._thresholds(feat_np, split_np, np.asarray(edges),
                                      factor, n_cbins)
            prev_feat = np.concatenate([prev_feat, feat_np])
            prev_thresh = np.concatenate([prev_thresh, thresh])
            prev_leaves = np.concatenate([prev_leaves, np.asarray(leaves)])
            margin0 = margin
        model = GBDTModel(prev_feat, prev_thresh, prev_leaves, base, max_depth,
                          trees=grown)
        new_state = ResumeState(self.name, max(target, start),
                                {"feat": prev_feat, "thresh": prev_thresh,
                                 "leaves": prev_leaves,
                                 "margin": np.asarray(margin0)})
        return model, new_state

    # ---- fused batches (core/fusion.py, DESIGN.md §3.2) -----------------
    def fuse_signature(self, params: Mapping[str, Any]):
        # max_bin is in the signature because it is a FORMAT parameter
        # (format_params): a fused batch converts once, so members must
        # share a prepared-data variant; rounds/depth still pad and mask.
        p = {**self.default_params(), **params}
        return ("gbdt", int(p["max_bin"]))

    def fuse_bucket(self, params: Mapping[str, Any]) -> tuple:
        from repro.core.fusion import pad_pow2

        # pad_pow2 (round UP), matching train_batched's padding: every
        # member of a bucket pads to the same shape, so same-bucket chunks
        # share one compile signature and bucket-boundary splits are safe
        # (max_bin lives in fuse_signature now, so it is constant per group)
        p = {**self.default_params(), **params}
        return (pad_pow2(int(p["round"])), int(p["max_depth"]))

    def train_batched(self, data, configs, *, cache=None) -> list[GBDTModel]:
        from repro.core import fusion

        ps = [{**self.default_params(), **c} for c in configs]
        ps, n_real = fusion.pad_configs(ps)   # pow-2 batch axis, see fusion
        bins, edges, y = data["bins"], data["edges"], data["y"]
        n_bins = int(data["n_bins"])
        coarse = [self._coarsen(n_bins, int(p["max_bin"])) for p in ps]
        pad_bins = max(nc for _, nc in coarse)
        pad_rounds = fusion.pad_pow2(max(int(p["round"]) for p in ps))
        pad_depth = max(int(p["max_depth"]) for p in ps)
        k = n_classes_of(data)
        note_level_rows(data, pad_bins, outputs=max(1, k),
                        trees=sum(int(p["round"]) for p in ps[:n_real])
                        * max(1, k))
        cc = cache if cache is not None else fusion.compile_cache()
        if is_sharded_payload(data):
            n_shards = int(data["_n_shards"])
            base = self._sharded_base_margin(data)
            fit = cc.get(
                ("gbdt", pad_bins, pad_rounds, pad_depth, len(ps),
                 tuple(bins.shape), n_shards),
                lambda: _build_batched_sharded_fit(
                    pad_bins, pad_rounds, pad_depth, n_shards),
            )
            shared = (bins, y, data["_shard_valid"], jnp.float32(base))
        else:
            base = self._base_margin(y, k)
            fit = cc.get(
                ("gbdt", pad_bins, pad_rounds, pad_depth, len(ps),
                 tuple(bins.shape), k),
                lambda: _build_batched_fit(pad_bins, pad_rounds, pad_depth,
                                           n_classes=k),
            )
            shared = (bins, y,
                      jnp.asarray(base, jnp.float32) if k else jnp.float32(base))
        col = lambda vals, dt: jnp.asarray(np.asarray(vals, dtype=dt))  # noqa: E731
        feat, split, leaves = fit(
            *shared,
            col([f for f, _ in coarse], np.int32),
            col([nc for _, nc in coarse], np.int32),
            col([int(p["round"]) for p in ps], np.int32),
            col([int(p["max_depth"]) for p in ps], np.int32),
            col([float(p["eta"]) for p in ps], np.float32),
            col([float(p["lambda"]) for p in ps], np.float32),
            col([float(p["gamma"]) for p in ps], np.float32),
            col([float(p["min_child_weight"]) for p in ps], np.float32),
        )
        edges_np = np.asarray(edges)
        feat_np, split_np = np.asarray(feat), np.asarray(split)
        leaves_np = np.asarray(leaves)
        models = []
        for i, p in enumerate(ps[:n_real]):
            rounds, (factor, n_cbins) = int(p["round"]), coarse[i]
            fi, si = feat_np[i, :rounds], split_np[i, :rounds]
            thresh = self._thresholds(fi, si, edges_np, factor, n_cbins)
            # padded levels carry sentinel splits (+inf thresholds), so the
            # depth-padded model routes identically to the unpadded one
            models.append(GBDTModel(fi, thresh, leaves_np[i, :rounds], base, pad_depth))
        return models

    @staticmethod
    def estimate_cost(params: Mapping[str, Any], n_rows: int, n_features: int,
                      n_classes: int = 0) -> float:
        """Analytic-profiler hook: histogram work dominates — R·F adds at
        the root, then histogram subtraction (DESIGN.md §3.8) builds only
        the smaller child per level, so every level below the root costs
        ~half: effective histogram levels = 1 + (D−1)/2 (plus split scans).
        A K-class fit grows K trees a round, K times the work."""
        p = {"round": 30, "max_depth": 6, "max_bin": 64, **dict(params)}
        depth = int(p["max_depth"])
        hist_levels = 1 + 0.5 * (depth - 1)
        per_tree = n_rows * n_features * hist_levels
        split_scan = (1 << depth) * n_features * int(p["max_bin"])
        return (int(p["round"]) * max(1, n_classes) * (per_tree + split_scan)
                / 2e8)
