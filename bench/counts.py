"""Operations and bytes the search's algorithms require, from shapes and
configurations alone, whatever implements them.

These counts are what ``search_mfu`` and ``level_kernel_roofline`` divide by
the chip's peaks (``peaks.json``). They count the algorithm's work, never an
implementation's: the level kernel's one-hot MXU formulation, histogram
padding and recomputation are not counted, so both shares stay lower bounds
of the true utilisation and can never pass 100 %.

A configuration's parameters are the traffic file's, filled in with the
defaults each family trains with when the traffic leaves them out
(``DEFAULTS``). ``outputs`` is the model's outputs per row, as the
configuration file states it: 1 for binary and regression, K for K classes.
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

#: bytes of one (row, feature) bin id (bins <= 256) and of one float32
#: statistic of a row (a g, an h or a class count)
BIN_ID_BYTES = 1
STAT_BYTES = 4


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


def row_stats(family: str, outputs: int) -> int:
    """Statistics a row adds into a histogram bin, per GBDT round or forest
    tree: g and h of each output for GBDT (K trees a round), (g, h) of one
    output or K class counts for the forest."""
    if family == "gbdt":
        return 2 * outputs
    return max(2, outputs)


def histogram_ops(rows: int, features: int, levels: int, stats: int) -> int:
    """Histogram adds of one GBDT round or forest tree: every row adds its
    ``stats`` into one bin of every feature at every level, R·F·stats per
    level."""
    return stats * rows * features * levels


def mlp_parameters(network: str, n_features: int, outputs: int) -> int:
    dims = [n_features] + [int(h) for h in str(network).split("_")] + [outputs]
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def config_ops(family: str, params: Mapping, rows: int, features: int,
               outputs: int) -> int:
    """Operations one configuration's training requires.

    GBDT: 2·R·F histogram adds per tree level, K trees a round. Forest:
    R·√F·max(2, K) per tree level (a tree sees sqrt(F) features). MLP:
    6 × parameters × batch rows per step (forward and backward), the last
    layer K units wide. Logistic regression: 4·R·F·K per full-batch step.
    """
    p = params_of(family, params)
    if family in ("gbdt", "forest"):
        n, depth = trees(family, p)
        width = features if family == "gbdt" else forest_features(features)
        return n * histogram_ops(rows, width, depth, row_stats(family, outputs))
    if family == "mlp":
        batch = min(int(p["batch_size"]), rows)
        return (6 * mlp_parameters(p["network"], features, outputs) * batch
                * int(p["steps"]))
    return 4 * rows * features * outputs * int(p["steps"])


def level_kernel_bytes(family: str, params: Mapping, rows: int,
                       features: int, outputs: int) -> int:
    """Least bytes the level work of a configuration's trees must move: per
    GBDT round or forest tree one read of every (row, feature) bin id and of
    the row's statistics (``row_stats``: 8·K bytes of g and h for GBDT).
    Deeper levels are not counted, because histogram subtraction and row
    compaction change which rows a kernel touches there, so this is a
    floor for any implementation."""
    n, _ = trees(family, params)
    return n * rows * (features * BIN_ID_BYTES
                       + STAT_BYTES * row_stats(family, outputs))


def level_kernel_ops(family: str, params: Mapping, rows: int,
                     features: int, outputs: int) -> int:
    """Histogram adds of a configuration's trees (0 for dense families)."""
    if family not in ("gbdt", "forest"):
        return 0
    return config_ops(family, params, rows, features, outputs)


def least_seconds(ops: float, nbytes: float, peak: Mapping) -> tuple[float, str]:
    """The least time the chip could take: the larger of ops over peak
    FLOP/s and bytes over peak HBM bytes/s, and which of the two bounds it."""
    t_ops = ops / float(peak["bf16_flops_per_s"])
    t_bytes = nbytes / float(peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
