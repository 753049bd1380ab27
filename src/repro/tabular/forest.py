"""Random forest in JAX — stands in for scikit-learn's RandomForestClassifier.

Reuses the GBDT histogram tree builder (tabular/gbdt.py) with squared-error
statistics: with g = −y and h = 1 the split gain reduces to variance
reduction and the leaf value −G/H is the leaf's mean label, i.e. a
probability estimate. Per tree: a Poisson(1) bootstrap (as row weights
scaling g and h) and a random √F feature subset (as a gain mask). Tree
predictions are averaged.
"""
from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.data_format import is_sharded_payload
from repro.core.interface import (
    Estimator,
    ResumeState,
    TrainedModel,
    register_estimator,
)
from repro.tabular.gbdt import batched_tree_margins, build_tree, note_level_rows

__all__ = ["ForestEstimator", "ForestModel"]


def _fit_forest_core(
    bins, y, key, min_samples_leaf, depth_limit,
    *, n_bins: int, n_trees: int, max_depth: int, max_features: int,
    subtract: bool = True, force=None,
):
    """Forest fit with traced ``min_samples_leaf``/``depth_limit`` so one
    compile serves all configs sharing the padded maxima, and vmap over the
    traced args fuses a config stack (``train_batched``). Per-tree keys are
    ``fold_in(key, t)`` — unlike ``split(key, n)``, the first k keys do not
    depend on the total count, so a tree-count-padded batch grows the SAME
    trees the sequential run would."""
    r, f = bins.shape

    def one_tree(_, tree_key):
        kb, kf = jax.random.split(tree_key)
        w = jax.random.poisson(kb, 1.0, (r,)).astype(jnp.float32)  # bootstrap
        perm = jax.random.permutation(kf, f)
        feat_mask = jnp.zeros((f,), bool).at[perm[:max_features]].set(True)
        g = -y * w
        h = w
        feat, split, leaf_g, leaf_h = build_tree(
            bins, g, h, n_bins=n_bins, max_depth=max_depth,
            lam=1e-6, gamma=0.0, min_child_weight=min_samples_leaf,
            feat_mask=feat_mask, depth_limit=depth_limit,
            subtract=subtract, force=force,
        )
        leaf_value = -leaf_g / jnp.maximum(leaf_h, 1e-6)   # = weighted mean(y)
        return None, (feat, split, leaf_value)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_trees))
    _, trees = jax.lax.scan(one_tree, None, keys)
    return trees


def _resume_forest_core(
    bins, y, key, min_samples_leaf, depth_limit, start,
    *, n_bins: int, n_trees: int, max_depth: int, max_features: int,
    subtract: bool = True, force=None,
):
    """Grow trees ``start .. start + n_trees`` — the rung machinery
    (DESIGN.md §3.6). Trees are mutually independent (the scan carries
    nothing) and tree t's key is ``fold_in(key, t)`` regardless of how many
    trees ran before, so appending a rung's trees to the previous stack is
    bit-exact against growing the whole forest in one go."""
    r, f = bins.shape

    def one_tree(_, tree_key):
        kb, kf = jax.random.split(tree_key)
        w = jax.random.poisson(kb, 1.0, (r,)).astype(jnp.float32)  # bootstrap
        perm = jax.random.permutation(kf, f)
        feat_mask = jnp.zeros((f,), bool).at[perm[:max_features]].set(True)
        g = -y * w
        h = w
        feat, split, leaf_g, leaf_h = build_tree(
            bins, g, h, n_bins=n_bins, max_depth=max_depth,
            lam=1e-6, gamma=0.0, min_child_weight=min_samples_leaf,
            feat_mask=feat_mask, depth_limit=depth_limit,
            subtract=subtract, force=force,
        )
        leaf_value = -leaf_g / jnp.maximum(leaf_h, 1e-6)   # = weighted mean(y)
        return None, (feat, split, leaf_value)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, start + i))(
        jnp.arange(n_trees))
    _, trees = jax.lax.scan(one_tree, None, keys)
    return trees


_fit_forest = functools.partial(
    jax.jit, static_argnames=("n_bins", "n_trees", "max_depth", "max_features",
                              "subtract", "force")
)(_fit_forest_core)
_resume_forest = functools.partial(
    jax.jit, static_argnames=("n_bins", "n_trees", "max_depth", "max_features",
                              "subtract", "force")
)(_resume_forest_core)


# --------------------------------------------------------------------------
# Sharded data plane (DESIGN.md §3.9): row-sharded forest fits.
#
# Bit-exactness note: every shard draws the bootstrap weights over the FULL
# unsharded (n_rows,) shape from the same per-tree key — the jax PRNG gives
# no prefix-stability guarantee across shapes, so drawing (rows_per_shard,)
# locally would sample DIFFERENT weights than the single-device run. Each
# shard then slices its own block by ``axis_index``. With integer-valued
# g = −y·w and h = w the per-level histogram psums are exact integer sums in
# f32, so sharded split decisions AND leaf values are bit-identical to the
# single-device forest (unlike gbdt, where leaf sums can differ in ulps).
# --------------------------------------------------------------------------

_SHARD_AXIS = "shards"


def _sharded_forest_trees(
    b, yy, vv, keys, min_samples_leaf, depth_limit,
    *, n_bins: int, max_depth: int, max_features: int, n_rows: int,
    n_shards: int, subtract: bool, force,
):
    """Per-shard tree scan shared by the sharded fit and resume cores; runs
    under ``sharded_call`` (vmap-with-axis-name or shard_map)."""
    r_local, f = b.shape

    def one_tree(_, tree_key):
        kb, kf = jax.random.split(tree_key)
        w_full = jax.random.poisson(kb, 1.0, (n_rows,)).astype(jnp.float32)
        w_pad = jnp.pad(w_full, (0, n_shards * r_local - n_rows))
        s = jax.lax.axis_index(_SHARD_AXIS)
        w = jax.lax.dynamic_slice(w_pad, (s * r_local,), (r_local,))
        perm = jax.random.permutation(kf, f)
        feat_mask = jnp.zeros((f,), bool).at[perm[:max_features]].set(True)
        g = -yy * w
        h = w
        feat, split, leaf_g, leaf_h = build_tree(
            b, g, h, n_bins=n_bins, max_depth=max_depth,
            lam=1e-6, gamma=0.0, min_child_weight=min_samples_leaf,
            feat_mask=feat_mask, depth_limit=depth_limit,
            subtract=subtract, force=force,
            axis_name=_SHARD_AXIS, row_valid=vv,
        )
        leaf_value = -leaf_g / jnp.maximum(leaf_h, 1e-6)   # = weighted mean(y)
        return None, (feat, split, leaf_value)

    _, trees = jax.lax.scan(one_tree, None, keys)
    return trees


def _fit_forest_sharded_core(
    bins, y, valid, key, min_samples_leaf, depth_limit,
    *, n_bins: int, n_trees: int, max_depth: int, max_features: int,
    n_rows: int, n_shards: int, subtract: bool = True, force=None,
):
    from repro import compat

    def per_shard(b, yy, vv):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_trees))
        return _sharded_forest_trees(
            b, yy, vv, keys, min_samples_leaf, depth_limit,
            n_bins=n_bins, max_depth=max_depth, max_features=max_features,
            n_rows=n_rows, n_shards=n_shards, subtract=subtract, force=force)

    return compat.sharded_call(per_shard, n_shards=n_shards,
                               axis=_SHARD_AXIS)(bins, y, valid)


def _resume_forest_sharded_core(
    bins, y, valid, key, min_samples_leaf, depth_limit, start,
    *, n_bins: int, n_trees: int, max_depth: int, max_features: int,
    n_rows: int, n_shards: int, subtract: bool = True, force=None,
):
    from repro import compat

    def per_shard(b, yy, vv):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, start + i))(
            jnp.arange(n_trees))
        return _sharded_forest_trees(
            b, yy, vv, keys, min_samples_leaf, depth_limit,
            n_bins=n_bins, max_depth=max_depth, max_features=max_features,
            n_rows=n_rows, n_shards=n_shards, subtract=subtract, force=force)

    return compat.sharded_call(per_shard, n_shards=n_shards,
                               axis=_SHARD_AXIS)(bins, y, valid)


_fit_forest_sharded = functools.partial(
    jax.jit, static_argnames=("n_bins", "n_trees", "max_depth", "max_features",
                              "n_rows", "n_shards", "subtract", "force")
)(_fit_forest_sharded_core)
_resume_forest_sharded = functools.partial(
    jax.jit, static_argnames=("n_bins", "n_trees", "max_depth", "max_features",
                              "n_rows", "n_shards", "subtract", "force")
)(_resume_forest_sharded_core)


def _build_batched_sharded_fit(n_bins: int, n_trees: int, max_depth: int,
                               max_features: int, n_rows: int, n_shards: int,
                               subtract: bool = True, force=None):
    core = functools.partial(
        _fit_forest_sharded_core, n_bins=n_bins, n_trees=n_trees,
        max_depth=max_depth, max_features=max_features,
        n_rows=n_rows, n_shards=n_shards, subtract=subtract, force=force)
    return jax.jit(jax.vmap(core, in_axes=(None, None, None, 0, 0, 0)))


def _build_batched_fit(n_bins: int, n_trees: int, max_depth: int, max_features: int,
                       subtract: bool = True, force=None):
    core = functools.partial(
        _fit_forest_core, n_bins=n_bins, n_trees=n_trees,
        max_depth=max_depth, max_features=max_features,
        subtract=subtract, force=force)
    return jax.jit(jax.vmap(core, in_axes=(None, None, 0, 0, 0)))


class ForestModel(TrainedModel):
    def __init__(self, feat, thresh, leaves, max_depth: int):
        self.feat = np.asarray(feat)
        self.thresh = np.asarray(thresh)
        self.leaves = np.asarray(leaves)
        self.max_depth = max_depth

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, np.float32)
        out = np.zeros((x.shape[0],), np.float32)
        for feat, thresh, leaves in zip(self.feat, self.thresh, self.leaves):
            local = np.zeros(x.shape[0], np.int64)
            for level in range(self.max_depth):
                g = (1 << level) - 1 + local
                local = 2 * local + (x[np.arange(x.shape[0]), feat[g]] > thresh[g])
            out += leaves[local]
        return np.clip(out / len(self.feat), 0.0, 1.0)

    # ---- jitted validation plane (DESIGN.md §3.4) -----------------------
    # A forest "margin" is the SUM of per-tree leaf values (base 0); the
    # probability is the tree-mean, clipped. The tree router is shared with
    # gbdt (batched_tree_margins), so both families reuse one compiled
    # predictor per (depth, padded trees, batch, rows) shape — round-padded
    # sentinel trees contribute leaf 0 = 0 to the sum, and the divisor is
    # each model's REAL tree count, so padding never skews the mean.
    def predict_margin_jax(self, x, *, cache=None) -> np.ndarray:
        return batched_tree_margins([self], x, cache=cache)[0]

    def predict_proba_jax(self, x, *, cache=None) -> np.ndarray:
        margin = self.predict_margin_jax(x, cache=cache)
        return np.clip(margin / len(self.feat), 0.0, 1.0)

    @classmethod
    def predict_margin_batched(cls, models, x, *, cache=None) -> np.ndarray:
        return batched_tree_margins(models, x, cache=cache)

    @classmethod
    def predict_proba_batched(cls, models, x, *, cache=None) -> np.ndarray:
        margins = batched_tree_margins(models, x, cache=cache)
        counts = np.asarray([len(m.feat) for m in models], np.float32)
        return np.clip(margins / counts[:, None], 0.0, 1.0)


@register_estimator
class ForestEstimator(Estimator):
    name = "forest"
    data_format = "quantized_bins"
    budget_param = "n_estimators"

    def default_params(self) -> dict[str, Any]:
        return {"n_estimators": 100, "max_depth": 8, "min_samples_leaf": 1.0, "seed": 0}

    @staticmethod
    def _thresholds(feat_np, split_np, edges_np):
        in_range = split_np < edges_np.shape[1]
        return np.where(
            in_range,
            edges_np[feat_np, np.minimum(split_np, edges_np.shape[1] - 1)],
            np.float32(np.inf),
        ).astype(np.float32)

    def train(self, data, params: Mapping[str, Any]) -> ForestModel:
        p = {**self.default_params(), **params}
        bins, edges = data["bins"], data["edges"]
        n_bins = int(data["n_bins"])
        f = bins.shape[-1]
        max_depth = int(p["max_depth"])
        note_level_rows(data, n_bins)
        if is_sharded_payload(data):
            feat, split, leaves = _fit_forest_sharded(
                bins, data["y"], data["_shard_valid"],
                jax.random.key(int(p["seed"])),
                jnp.float32(p["min_samples_leaf"]), jnp.int32(max_depth),
                n_bins=n_bins, n_trees=int(p["n_estimators"]),
                max_depth=max_depth, max_features=max(1, int(np.sqrt(f))),
                n_rows=int(data["_n_rows"]), n_shards=int(data["_n_shards"]),
            )
        else:
            feat, split, leaves = _fit_forest(
                bins, data["y"], jax.random.key(int(p["seed"])),
                jnp.float32(p["min_samples_leaf"]), jnp.int32(max_depth),
                n_bins=n_bins, n_trees=int(p["n_estimators"]), max_depth=max_depth,
                max_features=max(1, int(np.sqrt(f))),
            )
        feat_np, split_np = np.asarray(feat), np.asarray(split)
        thresh = self._thresholds(feat_np, split_np, np.asarray(edges))
        return ForestModel(feat_np, thresh, leaves, max_depth)

    # ---- adaptive search (DESIGN.md §3.6) -------------------------------
    def train_resumable(self, data, params: Mapping[str, Any], *,
                        budget: int, state: ResumeState | None = None):
        p = {**self.default_params(), **params}
        bins, edges = data["bins"], data["edges"]
        f = bins.shape[-1]
        max_depth = int(p["max_depth"])
        note_level_rows(data, int(data["n_bins"]))
        target = int(budget)
        if state is None:
            start = 0
            n_nodes, n_leaves = (1 << max_depth) - 1, 1 << max_depth
            prev_feat = np.zeros((0, n_nodes), np.int32)
            prev_thresh = np.zeros((0, n_nodes), np.float32)
            prev_leaves = np.zeros((0, n_leaves), np.float32)
        else:
            start = int(state.budget)
            pl = state.payload
            prev_feat, prev_thresh, prev_leaves = pl["feat"], pl["thresh"], pl["leaves"]
        if target > start:
            if is_sharded_payload(data):
                feat, split, leaves = _resume_forest_sharded(
                    bins, data["y"], data["_shard_valid"],
                    jax.random.key(int(p["seed"])),
                    jnp.float32(p["min_samples_leaf"]), jnp.int32(max_depth),
                    jnp.int32(start),
                    n_bins=int(data["n_bins"]), n_trees=target - start,
                    max_depth=max_depth, max_features=max(1, int(np.sqrt(f))),
                    n_rows=int(data["_n_rows"]), n_shards=int(data["_n_shards"]),
                )
            else:
                feat, split, leaves = _resume_forest(
                    bins, data["y"], jax.random.key(int(p["seed"])),
                    jnp.float32(p["min_samples_leaf"]), jnp.int32(max_depth),
                    jnp.int32(start),
                    n_bins=int(data["n_bins"]), n_trees=target - start,
                    max_depth=max_depth, max_features=max(1, int(np.sqrt(f))),
                )
            feat_np, split_np = np.asarray(feat), np.asarray(split)
            thresh = self._thresholds(feat_np, split_np, np.asarray(edges))
            prev_feat = np.concatenate([prev_feat, feat_np])
            prev_thresh = np.concatenate([prev_thresh, thresh])
            prev_leaves = np.concatenate([prev_leaves, np.asarray(leaves)])
        model = ForestModel(prev_feat, prev_thresh, prev_leaves, max_depth)
        new_state = ResumeState(self.name, max(target, start),
                                {"feat": prev_feat, "thresh": prev_thresh,
                                 "leaves": prev_leaves})
        return model, new_state

    # ---- fused batches (core/fusion.py, DESIGN.md §3.2) -----------------
    def fuse_signature(self, params: Mapping[str, Any]):
        return ("forest",)

    def fuse_bucket(self, params: Mapping[str, Any]) -> tuple:
        from repro.core.fusion import pad_pow2

        # round UP like train_batched's padding (see gbdt.fuse_bucket)
        p = {**self.default_params(), **params}
        return (pad_pow2(int(p["n_estimators"])), int(p["max_depth"]))

    def train_batched(self, data, configs, *, cache=None) -> list[ForestModel]:
        from repro.core import fusion

        ps = [{**self.default_params(), **c} for c in configs]
        ps, n_real = fusion.pad_configs(ps)   # pow-2 batch axis, see fusion
        bins, edges = data["bins"], data["edges"]
        n_bins = int(data["n_bins"])
        f = bins.shape[-1]
        max_features = max(1, int(np.sqrt(f)))
        pad_trees = fusion.pad_pow2(max(int(p["n_estimators"]) for p in ps))
        pad_depth = max(int(p["max_depth"]) for p in ps)
        note_level_rows(data, n_bins)
        cc = cache if cache is not None else fusion.compile_cache()
        if is_sharded_payload(data):
            n_rows, n_shards = int(data["_n_rows"]), int(data["_n_shards"])
            fit = cc.get(
                ("forest", n_bins, pad_trees, pad_depth, max_features,
                 len(ps), tuple(bins.shape), n_shards),
                lambda: _build_batched_sharded_fit(
                    n_bins, pad_trees, pad_depth, max_features, n_rows, n_shards),
            )
            shared = (bins, data["y"], data["_shard_valid"])
        else:
            fit = cc.get(
                ("forest", n_bins, pad_trees, pad_depth, max_features,
                 len(ps), tuple(bins.shape)),
                lambda: _build_batched_fit(n_bins, pad_trees, pad_depth, max_features),
            )
            shared = (bins, data["y"])
        keys = jax.vmap(jax.random.key)(
            jnp.asarray([int(p["seed"]) for p in ps], jnp.uint32))
        feat, split, leaves = fit(
            *shared, keys,
            jnp.asarray([float(p["min_samples_leaf"]) for p in ps], jnp.float32),
            jnp.asarray([int(p["max_depth"]) for p in ps], jnp.int32),
        )
        edges_np = np.asarray(edges)
        feat_np, split_np = np.asarray(feat), np.asarray(split)
        leaves_np = np.asarray(leaves)
        models = []
        for i, p in enumerate(ps[:n_real]):
            n_i = int(p["n_estimators"])
            thresh = self._thresholds(feat_np[i, :n_i], split_np[i, :n_i], edges_np)
            # trees past n_estimators are dropped here; depth-padded levels
            # keep sentinel splits, so routing matches the unpadded model
            models.append(ForestModel(feat_np[i, :n_i], thresh,
                                      leaves_np[i, :n_i], pad_depth))
        return models

    @staticmethod
    def estimate_cost(params: Mapping[str, Any], n_rows: int, n_features: int) -> float:
        # histogram subtraction (DESIGN.md §3.8): root level full, deeper
        # levels build only the smaller child — same halving as gbdt's
        p = {"n_estimators": 100, "max_depth": 8, **dict(params)}
        hist_levels = 1 + 0.5 * (int(p["max_depth"]) - 1)
        per_tree = n_rows * max(1, int(np.sqrt(n_features))) * hist_levels
        return int(p["n_estimators"]) * per_tree / 2e8
