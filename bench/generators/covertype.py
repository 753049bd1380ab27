"""Covertype-like rows at the published shape of UCI Covertype (Blackard and
Dean, 1999): 10 quantitative columns over their published ranges, then 4
wilderness-area and 40 soil-type one-hot columns with exactly one of each
set per row, and 7 cover-type classes at the published counts.

The rows are synthetic from the seed. The labels are drawn first, at the
published class counts in a seeded order; each row's columns are then drawn
from a class-conditional model (``CLASS_MODEL``): elevation, the dominant
feature of the real data, from a normal around the class's mean, the
distances to roadways and fire points from exponentials with class means,
and the wilderness area and soil type from class-conditional categoricals,
so that trees make real splits on the quantitative and the one-hot columns
alike. Every value is rounded to an integer, as in the published set."""
from __future__ import annotations

import numpy as np

#: rows of each cover type in the published set (581,012 in all)
CLASS_COUNTS = (211_840, 283_301, 35_754, 2_747, 9_493, 17_367, 20_510)

#: per class: (elevation mean, sd) in m, mean horizontal distance to
#: roadways and to fire points in m, wilderness-area weights (4), and the
#: soil types (0-based, of 40) that carry most of the class's rows
CLASS_MODEL = (
    ((3129, 155), 2600, 2000, (0.50, 0.05, 0.42, 0.03), (28, 22, 30, 32, 21)),
    ((2920, 190), 2430, 2170, (0.45, 0.03, 0.44, 0.08), (28, 22, 31, 11, 9)),
    ((2394, 195), 940, 910, (0.00, 0.00, 0.34, 0.66), (9, 1, 5, 3, 16)),
    ((2223, 100), 910, 860, (0.00, 0.00, 0.00, 1.00), (9, 2, 5, 16, 1)),
    ((2787, 96), 1350, 1580, (0.40, 0.00, 0.60, 0.00), (28, 22, 19, 11, 17)),
    ((2419, 190), 1040, 1060, (0.00, 0.00, 0.46, 0.54), (9, 1, 5, 3, 12)),
    ((3362, 105), 2720, 2070, (0.38, 0.19, 0.43, 0.00), (37, 38, 39, 34, 28)),
)

#: published (low, high) of the quantitative columns, in column order
RANGES = (
    (1859, 3858),    # elevation
    (0, 360),        # aspect
    (0, 66),         # slope
    (0, 1397),       # horizontal distance to hydrology
    (-173, 601),     # vertical distance to hydrology
    (0, 7117),       # horizontal distance to roadways
    (0, 254),        # hillshade 9am
    (0, 254),        # hillshade noon
    (0, 254),        # hillshade 3pm
    (0, 7173),       # horizontal distance to fire points
)
N_WILDERNESS, N_SOIL = 4, 40


def _soil_weights(dominant) -> np.ndarray:
    """Half a class's rows on its dominant soil types, in falling shares,
    the rest spread over all 40."""
    w = np.full(N_SOIL, 0.5 / N_SOIL)
    share = np.array([0.4, 0.25, 0.15, 0.12, 0.08]) * 0.5
    w[list(dominant)] += share
    return w / w.sum()


def class_counts(n_rows: int) -> np.ndarray:
    """The published class counts scaled to ``n_rows`` (the largest
    remainders round up), each class keeping at least one row; at 581,012
    rows, the published counts themselves."""
    pub = np.asarray(CLASS_COUNTS, np.int64)
    share = pub * n_rows / pub.sum()
    counts = np.maximum(np.floor(share).astype(np.int64), 1)
    order = np.argsort(-(share - np.floor(share)), kind="stable")
    for k in order[:max(0, n_rows - int(counts.sum()))]:
        counts[k] += 1
    if counts.sum() != n_rows:
        raise ValueError(f"{n_rows} rows cannot hold {len(pub)} classes")
    return counts


def generate(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    n_rows = int(config["rows"])
    counts = class_counts(n_rows)
    rng = np.random.default_rng(seed)
    y = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    elev_mu = np.array([m[0][0] for m in CLASS_MODEL], np.float64)[y]
    elev_sd = np.array([m[0][1] for m in CLASS_MODEL], np.float64)[y]
    road_mu = np.array([m[1] for m in CLASS_MODEL], np.float64)[y]
    fire_mu = np.array([m[2] for m in CLASS_MODEL], np.float64)[y]
    q = np.empty((n_rows, len(RANGES)), np.float64)
    q[:, 0] = rng.normal(elev_mu, elev_sd)
    q[:, 1] = rng.uniform(0, 360, n_rows)
    q[:, 2] = rng.gamma(3.0, 4.7, n_rows)
    q[:, 3] = rng.exponential(270.0, n_rows)
    q[:, 4] = 0.17 * q[:, 3] + rng.normal(0.0, 50.0, n_rows)
    q[:, 5] = rng.exponential(road_mu)
    aspect = np.deg2rad(q[:, 1])
    q[:, 6] = 212 + 25 * np.cos(aspect - np.pi / 2) + rng.normal(0, 12, n_rows)
    q[:, 7] = 223 - 0.6 * q[:, 2] + rng.normal(0, 15, n_rows)
    q[:, 8] = 142 - 40 * np.cos(aspect - np.pi / 2) + rng.normal(0, 20, n_rows)
    q[:, 9] = rng.exponential(fire_mu)
    lo, hi = np.asarray(RANGES, np.float64).T
    q = np.rint(np.clip(q, lo, hi))
    x = np.zeros((n_rows, len(RANGES) + N_WILDERNESS + N_SOIL), np.float32)
    x[:, :len(RANGES)] = q
    rows = np.arange(n_rows)
    for k, model in enumerate(CLASS_MODEL):
        idx = rows[y == k]
        wild = np.asarray(model[3], np.float64)
        area = rng.choice(N_WILDERNESS, size=idx.size, p=wild / wild.sum())
        soil = rng.choice(N_SOIL, size=idx.size, p=_soil_weights(model[4]))
        x[idx, len(RANGES) + area] = 1.0
        x[idx, len(RANGES) + N_WILDERNESS + soil] = 1.0
    return x, y.astype(np.float32)
