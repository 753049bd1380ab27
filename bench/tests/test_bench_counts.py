"""The operation and byte counts behind search_mfu and level_kernel_roofline."""
import math

import pytest

from bench import cells, counts

ROWS, FEATURES = 600_000, 28


def test_gbdt_counts_two_adds_per_row_feature_and_level():
    p = {"eta": 0.3, "round": 9, "max_bin": 128, "max_depth": 6}
    assert counts.config_ops("gbdt", p, ROWS, FEATURES) == 2 * ROWS * FEATURES * 6 * 9
    assert counts.level_kernel_ops("gbdt", p, ROWS, FEATURES) == \
        counts.config_ops("gbdt", p, ROWS, FEATURES)


def test_forest_counts_only_its_feature_subset():
    p = {"n_estimators": 5, "max_depth": 6}
    assert counts.forest_features(FEATURES) == 5
    assert counts.forest_features(590) == 24
    assert counts.config_ops("forest", p, ROWS, FEATURES) == 2 * ROWS * 5 * 6 * 5


def test_dense_counts():
    assert counts.mlp_parameters("64_64", 28) == 28 * 64 + 64 + 64 * 64 + 64 + 64 + 1
    p = {"network": "64_64", "learning_rate": 0.3, "steps": 20}
    assert counts.config_ops("mlp", p, ROWS, FEATURES) == \
        6 * counts.mlp_parameters("64_64", 28) * 128 * 20
    # the batch is capped at the rows there are
    assert counts.config_ops("mlp", p, 100, FEATURES) == \
        6 * counts.mlp_parameters("64_64", 28) * 100 * 20
    assert counts.config_ops("logreg", {"c": 0.1}, ROWS, FEATURES) == \
        4 * ROWS * FEATURES * 200
    assert counts.level_kernel_ops("mlp", p, ROWS, FEATURES) == 0


def test_level_bytes_are_one_read_of_bins_and_gh_per_tree():
    p = {"round": 3, "max_depth": 4, "max_bin": 64}
    assert counts.level_kernel_bytes("gbdt", p, ROWS, FEATURES) == \
        3 * ROWS * (FEATURES + 8)
    assert counts.level_kernel_bytes("logreg", {"c": 1.0}, ROWS, FEATURES) == 0


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        counts.config_ops("svm", {}, ROWS, FEATURES)


def test_least_time_names_its_bound():
    peak = cells.peaks_for("TPU v5 lite")
    p = {"round": 9, "max_depth": 6}
    t, bound = counts.least_seconds(
        counts.level_kernel_ops("gbdt", p, ROWS, FEATURES),
        counts.level_kernel_bytes("gbdt", p, ROWS, FEATURES), peak)
    assert bound == "bytes"
    assert math.isclose(t, 9 * ROWS * 36 / 819e9)
    assert counts.least_seconds(1e15, 1.0, peak)[1] == "ops"


def test_peak_table_refuses_an_unknown_device():
    assert cells.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks_for("cpu")
