"""SECOM-like rows: sensors loaded on 30 correlated latent groups plus noise,
a tenth of the columns dead (constant), and a rare positive class (about
6.6 %) from a sparse function of the latents. A copy of the generator the
program ships, kept here so that the benchmark's data cannot move with it."""
from __future__ import annotations

import numpy as np


def generate(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    n_rows, n_features = int(config["rows"]), int(config["features"])
    pos_rate = float(config["pos_rate"])
    rng = np.random.default_rng(seed)
    n_groups = 30
    latent = rng.normal(size=(n_rows, n_groups)).astype(np.float32)
    loadings = rng.normal(size=(n_groups, n_features)).astype(np.float32) * (
        rng.random((n_groups, n_features)) < 0.15)
    x = latent @ loadings + 0.6 * rng.normal(
        size=(n_rows, n_features)).astype(np.float32)
    dead = rng.choice(n_features, size=n_features // 10, replace=False)
    x[:, dead] = rng.normal(size=n_features // 10).astype(np.float32)[None, :]
    score = (latent[:, 0] - 0.8 * latent[:, 1] * latent[:, 2]
             + 0.5 * rng.normal(size=n_rows))
    y = (score > np.quantile(score, 1.0 - pos_rate)).astype(np.float32)
    return x.astype(np.float32), y
