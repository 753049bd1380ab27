"""Multi-class boosting (DESIGN.md §3.10): the metric's task selects the
softmax objective, GBDT grows K trees a round through the shared level
kernel, and every binary search stays what it was, bit for bit."""
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.tabular  # noqa: F401  (registers the four estimators)
from repro.core import (
    AnalyticProfiler,
    DenseMatrix,
    SearchSpec,
    Session,
    auc,
    convert,
    get_estimator,
)
from repro.core.cost_model import CostModel
from repro.core.evaluation import stable_softmax
from repro.core.grid import SearchSpace
from repro.core.interface import TrainTask
from repro.core.results import METRICS, metric_task, sharded_metric
from repro.data.synthetic import make_higgs_like, make_secom_like
from repro.kernels import ops
from repro.tabular import gbdt

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import drive  # noqa: E402
from bench import reference_multiclass as ref  # noqa: E402
from bench.generators import covertype  # noqa: E402

K = 7
PARAMS = ({"eta": 0.3, "round": 3, "max_bin": 32, "max_depth": 4},
          {"eta": 0.9, "round": 2, "max_bin": 32, "max_depth": 3,
           "lambda": 0.5})


BINARY_PARAMS = ({"round": 3, "max_depth": 3, "max_bin": 32, "eta": 0.3},
                 {"round": 2, "max_depth": 2, "max_bin": 32, "eta": 0.9,
                  "lambda": 0.5})


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode() + str(a.shape).encode() + a.tobytes())
    return h.hexdigest()[:16]


def _binary_rows(kind: str):
    d = (make_higgs_like(1200, seed=5) if kind == "higgs"
         else make_secom_like(400, 60, seed=2))
    tr, va = d.split((0.75, 0.25), seed=0)
    tr, mu, sd = tr.standardize()
    va, _, _ = va.standardize(mu, sd)
    return tr, va


# digests of the trees, base margins, validation margins and AUCs the
# parent commit of the multi-class change produced here (the CPU XLA path);
# a binary search must reproduce them bit for bit
_BINARY_DIGESTS = {
    ("higgs", "solo"): "fa0d23fe7216314b",
    ("higgs", "fused"): "5848c968b0b85503",
    ("higgs", "resume"): "478ce5a6f4576c62",
    ("secom", "solo"): "d5b68c3e257850f9",
    ("secom", "fused"): "194e7dbf1468f737",
    ("secom", "resume"): "d69d2d61b981168d",
}


@pytest.mark.parametrize("kind,path", sorted(_BINARY_DIGESTS))
def test_binary_gbdt_bit_identical_to_parent(kind, path):
    tr, va = _binary_rows(kind)
    est = get_estimator("gbdt")
    q = convert(tr, "quantized_bins", max_bins=32)
    cfgs = [dict(p) for p in BINARY_PARAMS]
    if path == "resume":
        m, st = est.train_resumable(q, cfgs[0], budget=2)
        m2, _ = est.train_resumable(q, cfgs[0], budget=3, state=st)
        got = _digest(m2.feat, m2.thresh, m2.leaves, m2.predict_margin(va.x))
    else:
        ms = ([est.train(q, c) for c in cfgs] if path == "solo"
              else est.train_batched(q, cfgs))
        assert all(m.n_classes == 0 and isinstance(m.base, float) for m in ms)
        got = _digest(
            *[a for m in ms
              for a in (m.feat, m.thresh, m.leaves, np.float64(m.base))],
            type(ms[0]).predict_margin_batched(ms, va.x),
            np.asarray([auc(va.y, m.predict_proba_jax(va.x)) for m in ms]))
    assert got == _BINARY_DIGESTS[(kind, path)]


def test_binary_programs_unchanged():
    """The binary fit, fused fit, resume and predict programs lower to the
    parent commit's text (debug locations aside): one compiled program,
    one compile-cache entry, as before."""
    bins = jnp.zeros((256, 5), jnp.int32)
    y = jnp.zeros((256,), jnp.float32)
    f32, i32 = jnp.float32, jnp.int32
    args = (bins, y, f32(0.1), i32(1), i32(16), i32(3), i32(3), f32(0.3),
            f32(1.0), f32(0.0), f32(1.0))
    cols = [jnp.asarray(np.full(2, v, dt)) for v, dt in [
        (1, np.int32), (16, np.int32), (3, np.int32), (3, np.int32),
        (0.3, np.float32), (1.0, np.float32), (0.0, np.float32),
        (1.0, np.float32)]]
    x = jnp.zeros((64, 5), f32)
    texts = [
        gbdt._fit_gbdt.lower(*args, n_bins=16, rounds=3, max_depth=3),
        gbdt._build_batched_fit(16, 4, 3).lower(bins, y, f32(0.1), *cols),
        gbdt._resume_gbdt.lower(bins, y, jnp.zeros((256,), f32), *args[3:],
                                i32(0), n_bins=16, rounds=2, max_depth=3),
        gbdt._build_predict_batched(3).lower(
            x, jnp.zeros((2, 4, 7), i32), jnp.zeros((2, 4, 7), f32),
            jnp.zeros((2, 4, 8), f32), jnp.zeros((2,), f32)),
    ]
    got = [hashlib.sha256(t.as_text(debug_info=False).encode()).hexdigest()[:16]
           for t in texts]
    assert got == ["ec5cd621b56b16e0", "e6ad8f485fd7414b", "82f863fbb8f19592",
                   "ee68eefa1e06de9a"]


# --------------------------------------------------------------------------
# 7-class Covertype rows
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cover():
    """Covertype generator rows cut to 2,000, split and standardised as the
    benchmark does: (train, valid) as DenseMatrix, train a 7-class task."""
    x, y = covertype.generate({"rows": 2000}, 2**31 + 11)
    (xt, yt), (xv, yv), _ = drive.split_standardize(x, y, [0.6, 0.2, 0.2], 7)
    return DenseMatrix(xt, yt).as_classes(), DenseMatrix(xv, yv)


@pytest.fixture(scope="module")
def cover_prep(cover):
    train, _ = cover
    return ref.Prepared(train.x, train.y)


@pytest.mark.parametrize("i", range(len(PARAMS)))
def test_multiclass_gbdt_matches_reference(cover, cover_prep, i):
    """Every node's (feature, bin) is the reference's best split there, the
    leaves are the reference's, the score is the reference's score.

    The program and the reference sum the same g and h in other orders
    (scatter-adds against one-hot products at the highest precision), so
    splits whose exact gains tie — common here: a round's g and h take one
    value per (class, leaf), and integer columns repeat values — are broken
    by rounding, and node-for-node equality with the reference's OWN trees
    is not asked. Instead each tree is replayed with the reference's split
    rule on the program's own g and h: the split taken at every node must
    reach the reference's best gain within 1e-3 relative — a gain is the
    difference of three terms each rounded at about 1e-7, which nearly
    cancel at weak nodes (read here up to 7e-5; the bfloat16 control reads
    up to 1), the limit the HIGGS configuration holds ``split_gap`` to —
    and each leaf must lie within 1e-5 of the reference's -eta·G/(H+λ),
    relative to the tree's largest leaf (float32 sums of 1,200 rows round at
    about 1e-6; read up to 2e-6). The roots of the first round are chosen
    from g and h the reference computes alike, and match it exactly."""
    train, valid = cover
    params = PARAMS[i]
    est = get_estimator("gbdt")
    q = convert(train, "quantized_bins", max_bins=params["max_bin"])
    model = est.train(q, params)
    assert model.feat.shape == (params["round"], K,
                                (1 << params["max_depth"]) - 1)
    assert model.base.shape == (K,) and model.trees == params["round"] * K
    got = ref.program_model("gbdt", model)
    nums = ref.replay_trees(cover_prep, "gbdt", params, got)
    assert nums["split_gap"] <= 1e-3 and nums["leaf_gap"] <= 1e-5, nums
    want = ref.fit(cover_prep, "gbdt", params, jnp.float32)
    np.testing.assert_array_equal(model.base, want["base"])
    assert np.array_equal(model.feat[0, :, 0], want["feat"][0, :, 0])
    assert np.array_equal(model.thresh[0, :, 0], want["thresh"][0, :, 0])
    # the reported score is the reference's score of the same model
    score = METRICS["neg_mlogloss"](valid.y, model.predict_proba_jax(valid.x))
    assert score == ref.score("neg_mlogloss", valid.y,
                              ref.probs("gbdt", got, valid.x))
    # the jitted and the numpy predictors agree bit for bit
    np.testing.assert_array_equal(model.predict_margin_jax(valid.x),
                                  model.predict_margin(valid.x))


def test_multiclass_fused_equals_solo(cover):
    train, valid = cover
    est = get_estimator("gbdt")
    q = convert(train, "quantized_bins", max_bins=32)
    solo = [est.train(q, p) for p in PARAMS]
    fused = est.train_batched(q, list(PARAMS))
    depth = max(p["max_depth"] for p in PARAMS)
    for s, f in zip(solo, fused):
        assert f.feat.shape[1] == K and f.trees == s.trees
        np.testing.assert_array_equal(s.base, f.base)
        # the fused model is padded to the batch's depth with stop splits
        np.testing.assert_array_equal(s.predict_margin(valid.x),
                                      f.predict_margin(valid.x))
        assert f.max_depth == depth
    stacked = type(fused[0]).predict_proba_batched(fused, valid.x)
    assert stacked.shape == (len(PARAMS), valid.n_rows, K)
    np.testing.assert_allclose(stacked.sum(axis=-1), 1.0, rtol=1e-5)


def test_multiclass_resume_equals_straight(cover):
    train, valid = cover
    est = get_estimator("gbdt")
    q = convert(train, "quantized_bins", max_bins=32)
    params = dict(PARAMS[0], round=5)
    straight = est.train(q, params)
    m2, s2 = est.train_resumable(q, params, budget=2)
    m5, s5 = est.train_resumable(q, params, budget=5, state=s2)
    assert s2.payload["margin"].shape == (K, train.n_rows)
    assert m2.trees == 2 * K and m5.trees == 3 * K
    for name in ("feat", "thresh", "leaves"):
        assert np.array_equal(getattr(straight, name), getattr(m5, name)), name
    assert np.array_equal(straight.predict_proba(valid.x),
                          m5.predict_proba(valid.x))


@pytest.mark.parametrize("force", [None, "kernel"])
def test_level_split_under_class_axis_equals_separate_calls(rng, force):
    """One launch for all K trees of a level (the class axis mapped over
    ``ops.level_split``, bins shared) equals K separate calls: the XLA path
    and the level kernel in interpret mode, direct and subtraction mode."""
    r, f, nb, nn, k = 200, 5, 16, 4, 3
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(k, r)), jnp.float32)
    h = jnp.asarray(rng.random((k, r)) + 0.1, jnp.float32)
    node = jnp.asarray(rng.integers(0, nn, size=(k, r)), jnp.int32)
    scan = dict(n_nodes=nn, n_bins=nb, lam=1.0, min_child_weight=1.0,
                force=force)

    def one(gg, hh, nd):
        return ops.level_split(bins, gg, hh, nd, **scan)

    mapped = jax.vmap(one)(g, h, node)
    for c in range(k):
        sep = one(g[c], h[c], node[c])
        for a, b in zip(mapped, sep):
            np.testing.assert_array_equal(np.asarray(a[c]), np.asarray(b))
    parent = jax.vmap(lambda gg, hh, nd: ops.level_split(
        bins, gg, hh, nd // 2, **{**scan, "n_nodes": nn // 2})[0])(g, h, node)
    mapped = jax.vmap(lambda gg, hh, nd, pa: ops.level_split(
        bins, gg, hh, nd, parent_hist=pa, **scan))(g, h, node, parent)
    for c in range(k):
        sep = ops.level_split(bins, g[c], h[c], node[c], parent_hist=parent[c],
                              **scan)
        for a, b in zip(mapped, sep):
            np.testing.assert_array_equal(np.asarray(a[c]), np.asarray(b))


# --------------------------------------------------------------------------
# the normal path: SearchSpec -> Session -> LocalExecutorPool -> GBDT
# --------------------------------------------------------------------------

def _space(*configs):
    return SearchSpace("gbdt", tuple(dict(c) for c in configs))


@pytest.mark.parametrize("fuse", [False, True])
def test_multiclass_search_end_to_end(cover, fuse):
    train, valid = cover
    spec = SearchSpec(spaces=[_space(*PARAMS)], metric="neg_mlogloss",
                      fuse=fuse, profiler=AnalyticProfiler())
    # plain labels: the metric's task makes them a 7-class task
    results = list(Session(spec).results(DenseMatrix(train.x, train.y), valid))
    assert len(results) == len(PARAMS)
    for r in results:
        assert r.ok and np.isfinite(r.score) and r.score < 0
        assert r.trees == r.task.params["round"] * K
        assert r.model.predict_proba(valid.x).shape == (valid.n_rows, K)
        assert r.score == pytest.approx(
            METRICS["neg_mlogloss"](valid.y, r.model.predict_proba(valid.x)),
            abs=1e-12)


def test_multiclass_search_resumable(cover):
    train, valid = cover
    spec = SearchSpec(spaces=[_space(*[dict(PARAMS[0], eta=e)
                                       for e in (0.1, 0.3, 0.9, 0.5)])],
                      metric="neg_mlogloss", tuner="asha",
                      tuner_args={"budget_param": "round", "base_budget": 1,
                                  "max_budget": 4, "eta": 2},
                      profiler=AnalyticProfiler())
    results = list(Session(spec).results(train, valid))
    assert results and all(r.ok and np.isfinite(r.score) for r in results)
    for r in results:
        assert r.trees == (r.task.budget - r.task.prev_budget) * K
        assert r.resume_state.payload["margin"].shape == (K, train.n_rows)
    assert max(r.task.budget for r in results) == 4


@pytest.mark.parametrize("family", ["forest", "mlp", "logreg"])
def test_families_without_a_multiclass_objective_are_refused(family):
    assert "multiclass" not in get_estimator(family).objectives
    with pytest.raises(ValueError, match=family):
        SearchSpec(spaces=[SearchSpace(family, ({},))], metric="neg_mlogloss")
    SearchSpec(spaces=[SearchSpace(family, ({},))], metric="auc")


def test_multiclass_refuses_row_sharding_and_bad_labels(cover):
    assert get_estimator("gbdt").objectives == ("binary", "multiclass")
    with pytest.raises(ValueError, match="row-sharded"):
        SearchSpec(spaces=[_space(PARAMS[0])], metric="neg_mlogloss",
                   n_shards=2)
    train, valid = cover
    spec = SearchSpec(spaces=[_space(PARAMS[0])], metric="neg_mlogloss")
    for y in (train.y + 0.5, train.y + 1, np.where(train.y == 3, 2, train.y)):
        with pytest.raises(ValueError, match="class ids"):
            list(Session(spec).results(DenseMatrix(train.x, y), valid))
    with pytest.raises(ValueError, match="class ids"):
        list(Session(spec).results(train, DenseMatrix(valid.x, valid.y + K)))


def test_as_classes_is_checked_once_and_keys_caches_apart(cover):
    train, _ = cover
    plain = DenseMatrix(train.x, train.y)
    view = plain.as_classes()
    assert view.n_classes == K and plain.as_classes() is view
    assert view.fingerprint() != plain.fingerprint()
    assert view.fingerprint() == DenseMatrix(train.x, train.y,
                                             n_classes=K).fingerprint()
    assert view.sample(0.5).n_classes == K
    assert convert(view, "quantized_bins", max_bins=16)["n_classes"] == K
    assert convert(plain, "quantized_bins", max_bins=16)["n_classes"] == 0
    with pytest.raises(ValueError, match="integer labels"):
        DenseMatrix(train.x, train.y, n_classes=3)


def test_neg_mlogloss_and_its_sharded_partial():
    assert metric_task("neg_mlogloss") == "multiclass"
    assert {metric_task(m) for m in ("auc", "accuracy", "neg_logloss")} == {
        "binary"}
    y = np.array([0, 2, 1, 2], np.float32)
    p = stable_softmax(np.array([[2.0, 0.0, -1.0], [0.0, 0.0, 900.0],
                                 [1.0, 1.0, 1.0], [5.0, -900.0, 0.0]]))
    assert np.all(np.isfinite(p)) and np.allclose(p.sum(axis=1), 1.0)
    want = -np.mean(-np.log(np.clip(p[np.arange(4), y.astype(int)], 1e-7, 1)))
    assert METRICS["neg_mlogloss"](y, p) == pytest.approx(want)
    valid = np.array([[True, True, True], [True, False, False]])
    yb = np.array([[0, 2, 1], [2, 0, 0]], np.float32)
    pb = np.zeros((2, 3, 3), np.float64)
    pb[0], pb[1, 0] = p[:3], p[3]
    assert sharded_metric("neg_mlogloss", yb, pb, valid, 4) == pytest.approx(want)


def test_cost_laws_know_k():
    est = get_estimator("gbdt")
    p = {"round": 3, "max_depth": 4, "max_bin": 32}
    assert est.estimate_cost(p, 1000, 54, n_classes=K) == pytest.approx(
        K * est.estimate_cost(p, 1000, 54))
    x = np.zeros((10, 3), np.float32)
    y = np.arange(10, dtype=np.float32) % 3
    task = TrainTask(0, "gbdt", p)
    binary = AnalyticProfiler().profile([task], DenseMatrix(x, y % 2))
    classes = AnalyticProfiler().profile([task], DenseMatrix(x, y).as_classes())
    assert classes.costs[0] == pytest.approx(3 * binary.costs[0])
    cm = CostModel()
    cm.observe(task, 7.0, 1000, n_classes=K)
    assert cm.predict(task, 1000) is None          # the binary law is cold
    assert cm.predict(task, 1000, n_classes=K) == pytest.approx(7.0)
    cm.observe(task, 1.0, 1000)
    assert cm.predict(task, 1000) == pytest.approx(1.0)
    assert cm.predict(task, 1000, n_classes=K) == pytest.approx(7.0)
    assert "gbdt#k7" in cm.to_dict()["families"]


def test_spans_and_results_count_outputs_and_trees(tmp_path):
    from jax.profiler import ProfileData

    rng = np.random.default_rng(16)
    x = rng.normal(size=(300, 6)).astype(np.float32)
    y = np.digitize(x[:, 0] + 0.3 * rng.normal(size=300), [-0.5, 0.5])
    train, valid = DenseMatrix(x, y).split((0.7, 0.3), seed=5)
    cfgs = ({"round": 2, "max_depth": 2, "max_bin": 16},
            {"round": 3, "max_depth": 2, "max_bin": 16})
    spec = SearchSpec(spaces=[_space(*cfgs)], metric="neg_mlogloss",
                      fuse=True, max_fuse=4)
    with jax.profiler.trace(str(tmp_path)):
        results = list(Session(spec).results(train, valid))
    assert sorted(r.trees for r in results) == [6, 9]
    for r in results:
        assert set(np.unique(r.model.predict(valid.x))) <= {0.0, 1.0, 2.0}
    pd = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    stats = {name: [] for name in ("repro.train", "repro.eval")}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in stats:
                    stats[e.name].append(dict(e.stats))
    (train_span,), (eval_span,) = stats["repro.train"], stats["repro.eval"]
    assert train_span["outputs"] == 3 and train_span["trees"] == 15
    assert eval_span["outputs"] == 3
