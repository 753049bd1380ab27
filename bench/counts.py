"""Operations and bytes the search's algorithms require, from shapes and
configurations alone, whatever implements them.

These counts are what ``search_mfu`` and ``level_kernel_roofline`` divide by
the chip's peaks (``peaks.json``). They count the algorithm's work, never an
implementation's: the level kernel's one-hot MXU formulation, histogram
padding and recomputation are not counted, so both shares stay lower bounds
of the true utilisation and can never pass 100 %.

A configuration's parameters are the traffic file's, filled in with the
defaults each family trains with when the traffic leaves them out
(``DEFAULTS``).
"""
from __future__ import annotations

import math
from typing import Mapping

#: the values each family trains with where a configuration leaves them out
#: (the reference, ``reference.py``, trains with them too)
DEFAULTS = {
    "gbdt": {"eta": 0.3, "round": 30, "max_depth": 6, "max_bin": 64,
             "lambda": 1.0, "gamma": 0.0, "min_child_weight": 1.0},
    "forest": {"n_estimators": 100, "max_depth": 8, "min_samples_leaf": 1.0,
               "seed": 0},
    "mlp": {"network": "64_64", "learning_rate": 0.003, "steps": 300,
            "batch_size": 128, "seed": 0},
    "logreg": {"c": 1.0, "lr": 0.05, "steps": 200},
}

#: bytes of one (row, feature) bin id (bins <= 256) and of g and h per row
BIN_ID_BYTES = 1
GH_BYTES_PER_ROW = 8


def params_of(family: str, params: Mapping) -> dict:
    if family not in DEFAULTS:
        raise ValueError(f"no operation count for family {family!r}")
    return {**DEFAULTS[family], **dict(params)}


def forest_features(n_features: int) -> int:
    """Features a forest tree may split on: a random sqrt(F) subset."""
    return max(1, int(math.sqrt(n_features)))


def trees(family: str, params: Mapping) -> tuple[int, int]:
    """(trees grown, levels per tree) of a tree family's configuration."""
    p = params_of(family, params)
    if family == "gbdt":
        return int(p["round"]), int(p["max_depth"])
    if family == "forest":
        return int(p["n_estimators"]), int(p["max_depth"])
    return 0, 0


def histogram_ops(rows: int, features: int, levels: int) -> int:
    """Histogram adds of one tree: every row adds its g and h into one bin
    of every feature at every level, 2·R·F per level."""
    return 2 * rows * features * levels


def mlp_parameters(network: str, n_features: int) -> int:
    dims = [n_features] + [int(h) for h in str(network).split("_")] + [1]
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def config_ops(family: str, params: Mapping, rows: int, features: int) -> int:
    """Operations one configuration's training requires.

    GBDT and forest: 2·R·F histogram adds per tree level (a forest tree
    sees sqrt(F) features). MLP: 6 × parameters × batch rows per step
    (forward and backward). Logistic regression: 4·R·F per full-batch step.
    """
    p = params_of(family, params)
    if family == "gbdt":
        n, depth = trees(family, p)
        return n * histogram_ops(rows, features, depth)
    if family == "forest":
        n, depth = trees(family, p)
        return n * histogram_ops(rows, forest_features(features), depth)
    if family == "mlp":
        batch = min(int(p["batch_size"]), rows)
        return 6 * mlp_parameters(p["network"], features) * batch * int(p["steps"])
    return 4 * rows * features * int(p["steps"])


def level_kernel_bytes(family: str, params: Mapping, rows: int,
                       features: int) -> int:
    """Least bytes the level work of a configuration's trees must move: per
    tree one read of every (row, feature) bin id and of g and h. Deeper
    levels are not counted, because histogram subtraction and row
    compaction change which rows a kernel touches there, so this is a
    floor for any implementation."""
    n, _ = trees(family, params)
    return n * rows * (features * BIN_ID_BYTES + GH_BYTES_PER_ROW)


def level_kernel_ops(family: str, params: Mapping, rows: int,
                     features: int) -> int:
    """Histogram adds of a configuration's trees (0 for dense families)."""
    if family not in ("gbdt", "forest"):
        return 0
    return config_ops(family, params, rows, features)


def least_seconds(ops: float, nbytes: float, peak: Mapping) -> tuple[float, str]:
    """The least time the chip could take: the larger of ops over peak
    FLOP/s and bytes over peak HBM bytes/s, and which of the two bounds it."""
    t_ops = ops / float(peak["bf16_flops_per_s"])
    t_bytes = nbytes / float(peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
