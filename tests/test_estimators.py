"""Tabular estimator quality + property tests (the paper's 4 algorithms)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: deterministic stub, same surface
    from _hypothesis_stub import given, settings, st

import repro.tabular  # noqa: F401
from repro.core import DenseMatrix, auc, convert, get_estimator, estimator_names
from repro.data.synthetic import make_secom_like


def test_all_four_registered():
    assert set(estimator_names()) >= {"gbdt", "mlp", "forest", "logreg"}


@pytest.mark.parametrize("name,params,min_auc", [
    ("gbdt", {"round": 20, "max_depth": 5, "max_bin": 64}, 0.90),
    ("mlp", {"network": "32_32", "steps": 400}, 0.90),
    ("forest", {"n_estimators": 30, "max_depth": 8}, 0.84),
    ("logreg", {"c": 0.3}, 0.80),
])
def test_estimator_beats_chance_on_higgs(higgs_small, name, params, min_auc):
    train, valid = higgs_small
    est = get_estimator(name)
    model, secs = est.run(train, params)
    score = auc(valid.y, model.predict_proba(valid.x))
    assert score >= min_auc, f"{name} auc={score:.3f} < {min_auc}"
    assert secs > 0


def test_gbdt_on_imbalanced_secom_like():
    data = make_secom_like(n_rows=800, n_features=120, seed=3)
    train, valid = data.split((0.8, 0.2), seed=0)
    train, mu, sd = train.standardize()
    valid, _, _ = valid.standardize(mu, sd)
    est = get_estimator("gbdt")
    model, _ = est.run(train, {"round": 30, "max_depth": 4, "max_bin": 64})
    score = auc(valid.y, model.predict_proba(valid.x))
    assert score > 0.6                          # imbalanced + noisy: modest bar


def test_gbdt_more_rounds_fits_train_better(higgs_small):
    train, _ = higgs_small
    est = get_estimator("gbdt")
    m_small, _ = est.run(train, {"round": 3, "max_depth": 4})
    m_big, _ = est.run(train, {"round": 40, "max_depth": 4})
    auc_small = auc(train.y, m_small.predict_proba(train.x))
    auc_big = auc(train.y, m_big.predict_proba(train.x))
    assert auc_big > auc_small


def test_gbdt_predictions_are_probabilities(higgs_small):
    train, valid = higgs_small
    model, _ = get_estimator("gbdt").run(train, {"round": 5, "max_depth": 3})
    p = model.predict_proba(valid.x)
    assert p.shape == (valid.n_rows,)
    assert np.all((p >= 0) & (p <= 1))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_forest_prob_range_property(seed):
    """Forest output is a mean of leaf means of {0,1} labels → always [0,1]."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(120, 6)).astype(np.float32)
    y = (x[:, 0] * x[:, 1] > 0).astype(np.float32)
    d = DenseMatrix(x, y)
    model, _ = get_estimator("forest").run(d, {"n_estimators": 4, "max_depth": 4})
    p = model.predict_proba(x)
    assert np.all((p >= 0.0) & (p <= 1.0))


def test_quantized_bins_roundtrip_consistency(higgs_small):
    """bin > s  ⇔  x > edges[s] — the split-threshold identity GBDT's
    float-space predictor relies on."""
    train, _ = higgs_small
    q = convert(train, "quantized_bins")
    bins = np.asarray(q["bins"])
    edges = np.asarray(q["edges"])             # (F, B−1)
    x = train.x
    f = 3
    for s in (5, 100, 200):
        if s >= edges.shape[1]:
            continue
        lhs = bins[:, f] > s
        rhs = x[:, f] > edges[f, s]
        np.testing.assert_array_equal(lhs, rhs)


def test_coarse_bins_equals_integer_division():
    """The in-graph coarsening matches ``//`` for every 8-bit bin id and
    every factor the 256-bin format can ask for, with a traced factor."""
    from repro.tabular.gbdt import _coarse_bins

    ids = jnp.arange(256, dtype=jnp.int32)
    factors = jnp.arange(1, 257, dtype=jnp.int32)
    got = jax.jit(jax.vmap(_coarse_bins, in_axes=(None, 0)))(ids, factors)
    want = np.arange(256)[None, :] // np.arange(1, 257)[:, None]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_mlp_cost_model_monotonic():
    est = get_estimator("mlp")
    small = est.estimate_cost({"network": "32", "steps": 100}, 1000, 28)
    big = est.estimate_cost({"network": "256_256", "steps": 100}, 1000, 28)
    assert big > small


# ---------------------------------------------------------------------------
# histogram-subtraction / fused-kernel bit-identity pins (DESIGN.md §3.8)
#
# ``subtract=False`` replays the pre-subtraction training path op for op, so
# these pins say: the models this PR trains are byte-identical to the models
# the repo trained before it — on the solo fit, the resumable-rung fit, and
# the vmap-fused batch fit, for both tree families.
# ---------------------------------------------------------------------------

def _gbdt_fit_inputs(higgs_small, max_bin=64):
    from repro.tabular.gbdt import GBDTEstimator

    train, _ = higgs_small
    est = get_estimator("gbdt")
    q = convert(train, "quantized_bins")
    factor, n_cbins = GBDTEstimator._coarsen(int(q["n_bins"]), max_bin)
    base = est._base_margin(q["y"])
    return est, q, factor, n_cbins, base


def _assert_trees_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_gbdt_fit_subtraction_bit_identity(higgs_small):
    from repro.tabular.gbdt import _fit_gbdt

    est, q, factor, n_cbins, base = _gbdt_fit_inputs(higgs_small)
    rounds, depth = 8, 4
    args = (q["bins"], q["y"], jnp.float32(base),
            jnp.int32(factor), jnp.int32(n_cbins),
            jnp.int32(rounds), jnp.int32(depth),
            jnp.float32(0.3), jnp.float32(1.0), jnp.float32(0.0),
            jnp.float32(1.0))
    kw = dict(n_bins=n_cbins, rounds=rounds, max_depth=depth)
    sub = _fit_gbdt(*args, subtract=True, **kw)
    direct = _fit_gbdt(*args, subtract=False, **kw)
    _assert_trees_equal(sub, direct)
    # the public estimator entry point routes through the same default path
    train, _ = higgs_small
    model, _ = est.run(train, {"round": rounds, "max_depth": depth,
                               "max_bin": 64})
    np.testing.assert_array_equal(model.feat, np.asarray(direct[0]))


def test_gbdt_fused_kernel_model_bit_identity(higgs_small):
    """ISSUE 9 acceptance pin: a model trained through the fused Pallas
    kernel (interpret mode on CPU) carries bit-identical feat/split/leaves
    to the XLA path — the split DECISIONS agree, and leaf sums are computed
    by the same scatter given identical routing."""
    from repro.tabular.gbdt import _fit_gbdt

    _, q, factor, n_cbins, base = _gbdt_fit_inputs(higgs_small)
    bins, y = q["bins"][:400], q["y"][:400]
    rounds, depth = 3, 3
    args = (bins, y, jnp.float32(base),
            jnp.int32(factor), jnp.int32(n_cbins),
            jnp.int32(rounds), jnp.int32(depth),
            jnp.float32(0.3), jnp.float32(1.0), jnp.float32(0.0),
            jnp.float32(1.0))
    kw = dict(n_bins=n_cbins, rounds=rounds, max_depth=depth)
    kernel = _fit_gbdt(*args, subtract=True, force="kernel", **kw)
    xla = _fit_gbdt(*args, subtract=False, **kw)
    _assert_trees_equal(kernel, xla)


def test_gbdt_resume_subtraction_bit_identity(higgs_small):
    from repro.tabular.gbdt import _resume_gbdt

    _, q, factor, n_cbins, base = _gbdt_fit_inputs(higgs_small)
    rounds, depth = 6, 4
    margin0 = jnp.full((q["bins"].shape[0],), base, jnp.float32)
    args = (q["bins"], q["y"], margin0,
            jnp.int32(factor), jnp.int32(n_cbins),
            jnp.int32(rounds), jnp.int32(depth),
            jnp.float32(0.3), jnp.float32(1.0), jnp.float32(0.0),
            jnp.float32(1.0), jnp.int32(0))
    kw = dict(n_bins=n_cbins, rounds=rounds, max_depth=depth)
    trees_s, margin_s = _resume_gbdt(*args, subtract=True, **kw)
    trees_d, margin_d = _resume_gbdt(*args, subtract=False, **kw)
    _assert_trees_equal(trees_s, trees_d)
    np.testing.assert_array_equal(np.asarray(margin_s), np.asarray(margin_d))


def test_gbdt_batched_fit_subtraction_bit_identity(higgs_small):
    """The vmap-fused plane (train_batched's compile-cache unit)."""
    from repro.tabular.gbdt import _build_batched_fit

    _, q, factor, n_cbins, base = _gbdt_fit_inputs(higgs_small)
    rounds, depth = 4, 4
    col = lambda v, dt: jnp.asarray(np.asarray(v, dt))  # noqa: E731
    args = (q["bins"], q["y"], jnp.float32(base),
            col([factor, factor], np.int32), col([n_cbins, 32], np.int32),
            col([rounds, 2], np.int32), col([depth, 2], np.int32),
            col([0.3, 0.1], np.float32), col([1.0, 2.0], np.float32),
            col([0.0, 0.5], np.float32), col([1.0, 3.0], np.float32))
    sub = _build_batched_fit(n_cbins, rounds, depth, subtract=True)(*args)
    direct = _build_batched_fit(n_cbins, rounds, depth, subtract=False)(*args)
    _assert_trees_equal(sub, direct)


def test_forest_fit_subtraction_bit_identity(higgs_small):
    from repro.tabular.forest import _fit_forest

    train, _ = higgs_small
    q = convert(train, "quantized_bins")
    bins = q["bins"] // 4                       # 256 → 64 levels
    key = jax.random.PRNGKey(11)
    kw = dict(n_bins=64, n_trees=5, max_depth=4, max_features=5)
    sub = _fit_forest(bins, q["y"], key, jnp.float32(1.0), jnp.int32(4),
                      subtract=True, **kw)
    direct = _fit_forest(bins, q["y"], key, jnp.float32(1.0), jnp.int32(4),
                         subtract=False, **kw)
    _assert_trees_equal(sub, direct)
