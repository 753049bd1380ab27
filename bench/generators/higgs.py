"""HIGGS-like rows: 21 "low-level" normal features, 7 "high-level" features
derived from them (products, trig, squared differences), and balanced binary
labels from a smooth nonlinear score plus noise. A copy of the generator the
program ships, kept here so that the benchmark's data cannot move with it."""
from __future__ import annotations

import numpy as np


def generate(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    n_rows = int(config["rows"])
    rng = np.random.default_rng(seed)
    x_low = rng.normal(size=(n_rows, 21)).astype(np.float32)
    x_high = np.stack(
        [
            x_low[:, 0] * x_low[:, 1],
            x_low[:, 2] * x_low[:, 3],
            np.sin(x_low[:, 4]) * x_low[:, 5],
            x_low[:, 6] ** 2 - x_low[:, 7] ** 2,
            np.cos(x_low[:, 8]) + x_low[:, 9],
            x_low[:, 10] * x_low[:, 11] * np.sign(x_low[:, 12]),
            np.abs(x_low[:, 13]) - np.abs(x_low[:, 14]),
        ],
        axis=1,
    ).astype(np.float32)
    x = np.concatenate([x_low, x_high], axis=1)
    logits = (
        1.8 * x_high[:, 0]
        - 1.2 * x_high[:, 3]
        + 0.9 * np.tanh(x_high[:, 2])
        + 0.6 * x_low[:, 15]
        - 0.4 * x_low[:, 16] * x_low[:, 17]
        + 0.5 * rng.normal(size=n_rows)
    )
    y = (logits > np.median(logits)).astype(np.float32)
    return x, y
