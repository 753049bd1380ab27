"""The operation and byte counts behind search_mfu and level_kernel_roofline."""
import math

import pytest

from bench import cells, counts

ROWS, FEATURES = 600_000, 28


def test_gbdt_counts_two_adds_per_row_feature_and_level():
    p = {"eta": 0.3, "round": 9, "max_bin": 128, "max_depth": 6}
    assert counts.config_ops("gbdt", p, ROWS, FEATURES, 1) == 2 * ROWS * FEATURES * 6 * 9
    assert counts.level_kernel_ops("gbdt", p, ROWS, FEATURES, 1) == \
        counts.config_ops("gbdt", p, ROWS, FEATURES, 1)


def test_forest_counts_only_its_feature_subset():
    p = {"n_estimators": 5, "max_depth": 6}
    assert counts.forest_features(FEATURES) == 5
    assert counts.forest_features(590) == 24
    assert counts.config_ops("forest", p, ROWS, FEATURES, 1) == 2 * ROWS * 5 * 6 * 5


def test_dense_counts():
    assert counts.mlp_parameters("64_64", 28, 1) == 28 * 64 + 64 + 64 * 64 + 64 + 64 + 1
    p = {"network": "64_64", "learning_rate": 0.3, "steps": 20}
    assert counts.config_ops("mlp", p, ROWS, FEATURES, 1) == \
        6 * counts.mlp_parameters("64_64", 28, 1) * 128 * 20
    # the batch is capped at the rows there are
    assert counts.config_ops("mlp", p, 100, FEATURES, 1) == \
        6 * counts.mlp_parameters("64_64", 28, 1) * 100 * 20
    assert counts.config_ops("logreg", {"c": 0.1}, ROWS, FEATURES, 1) == \
        4 * ROWS * FEATURES * 200
    assert counts.level_kernel_ops("mlp", p, ROWS, FEATURES, 1) == 0


def test_level_bytes_are_one_read_of_bins_and_gh_per_tree():
    p = {"round": 3, "max_depth": 4, "max_bin": 64}
    assert counts.level_kernel_bytes("gbdt", p, ROWS, FEATURES, 1) == \
        3 * ROWS * (FEATURES + 8)
    assert counts.level_kernel_bytes("logreg", {"c": 1.0}, ROWS, FEATURES, 1) == 0


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        counts.config_ops("svm", {}, ROWS, FEATURES, 1)


def test_least_time_names_its_bound():
    peak = cells.peaks_for("TPU v5 lite")
    p = {"round": 9, "max_depth": 6}
    t, bound = counts.least_seconds(
        counts.level_kernel_ops("gbdt", p, ROWS, FEATURES, 1),
        counts.level_kernel_bytes("gbdt", p, ROWS, FEATURES, 1), peak)
    assert bound == "bytes"
    assert math.isclose(t, 9 * ROWS * 36 / 819e9)
    assert counts.least_seconds(1e15, 1.0, peak)[1] == "ops"


def test_peak_table_refuses_an_unknown_device():
    assert cells.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks_for("cpu")


#: one configuration of each family, and shapes (rows, features) from a
#: tiny table to HIGGS and SECOM widths
CONFIGS = {
    "gbdt": {"round": 9, "max_depth": 6, "max_bin": 128},
    "forest": {"n_estimators": 5, "max_depth": 8},
    "mlp": {"network": "64_32", "steps": 20, "batch_size": 128},
    "logreg": {"c": 0.1, "steps": 50},
}
SHAPES = [(600_000, 28), (940, 590), (100, 3)]
CASES = [(fam, r, f) for fam in CONFIGS for r, f in SHAPES]


def _counts(family, rows, features, outputs):
    p = CONFIGS[family]
    return (counts.config_ops(family, p, rows, features, outputs),
            counts.level_kernel_ops(family, p, rows, features, outputs),
            counts.level_kernel_bytes(family, p, rows, features, outputs))


@pytest.mark.parametrize("family,rows,features", CASES)
def test_one_output_counts_are_the_binary_formulas(family, rows, features):
    # the formulas the counts had before a configuration stated its outputs
    sqrt_f = max(1, int(math.sqrt(features)))
    mlp_params = (features * 64 + 64) + (64 * 32 + 32) + (32 * 1 + 1)
    want = {
        "gbdt": (2 * rows * features * 6 * 9, 2 * rows * features * 6 * 9,
                 9 * rows * (features + 8)),
        "forest": (2 * rows * sqrt_f * 8 * 5, 2 * rows * sqrt_f * 8 * 5,
                   5 * rows * (features + 8)),
        "mlp": (6 * mlp_params * min(128, rows) * 20, 0, 0),
        "logreg": (4 * rows * features * 50, 0, 0),
    }[family]
    assert _counts(family, rows, features, 1) == want


@pytest.mark.parametrize("family,rows,features", CASES)
def test_seven_outputs_count_the_work_of_seven(family, rows, features):
    ops1, level1, bytes1 = _counts(family, rows, features, 1)
    ops7, level7, bytes7 = _counts(family, rows, features, 7)
    if family == "gbdt":
        # seven trees a round, each 2·R·F adds per level; 8 bytes of g and
        # h per output and row
        assert (ops7, level7) == (7 * ops1, 7 * level1)
        assert bytes7 == 9 * rows * (features + 8 * 7)
    elif family == "forest":
        # seven class counts a row in place of (g, h)
        assert 2 * ops7 == 7 * ops1 and level7 == ops7
        assert bytes7 == 5 * rows * (features + 4 * 7)
    elif family == "mlp":
        # the last layer has seven units: 6 more weights of its 32 inputs
        # and 6 more biases
        extra = 6 * (32 + 1)
        assert counts.mlp_parameters("64_32", features, 7) == \
            counts.mlp_parameters("64_32", features, 1) + extra
        assert ops7 == ops1 + 6 * extra * min(128, rows) * 20
        assert (level7, bytes7) == (0, 0)
    else:
        assert ops7 == 7 * ops1
        assert (level7, bytes7) == (0, 0)
