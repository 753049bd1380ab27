"""Sharded data plane (DESIGN.md §3.9): row-sharded prepared data with
cross-shard GBDT histograms and partial-sum eval.

The acceptance grid is exercised here on the single-device vmap lowering
(the path every tier-1 session takes): sharded GBDT/forest split decisions
must be IDENTICAL to single-device across depths {1,3,6} × bins
{16,64,256} × shards {2,4,8}; logreg/mlp margins within 1e-6; an 8-shard
placement's per-device residency bounded by full-copy/8 plus pad slack.

Multi-device shard_map parity (the other lowering of the same program)
runs in subprocesses under ``--xla_force_host_platform_device_count`` and
is gated on ``REPRO_SHARDED_TESTS=1`` (the ci.yml ``sharded`` lane), same
contract as the heavy lane in test_distributed.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.tabular  # noqa: F401  (registers the four estimators)
from repro.core import (
    CostModel,
    DenseMatrix,
    GridBuilder,
    SearchSpec,
    Session,
    TrainTask,
    convert,
    get_estimator,
    prepared_data_cache,
    schedule,
)
from repro.core.data_format import (
    PreparedDataCache,
    ShardedPlacement,
    is_sharded_payload,
    payload_nbytes,
    prepare_cached,
    shard_payload,
    shard_pspecs,
)
from repro.core.executor import MeshSliceExecutorPool, ShardGroup
from repro.distributed.collectives import compressed_psum, psum_tree
from repro.distributed.sharding import bytes_per_device

# Multi-device SPMD compiles are minutes of XLA CPU work; they run in the
# ci.yml `sharded` lane rather than every tier-1 invocation.
sharded_lane = pytest.mark.skipif(
    os.environ.get("REPRO_SHARDED_TESTS") != "1",
    reason="multi-device sharded-lane subprocess test; "
           "set REPRO_SHARDED_TESTS=1 to run",
)

SHARDS = (2, 4, 8)
DEPTHS = (1, 3, 6)
BINS = (16, 64, 256)


def run_subprocess(code: str, devices: int = 8) -> str:
    """Run a python snippet with N fake host devices; returns stdout."""
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
            # the child must never reach for a chip its parent may hold
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin",
            "HOME": "/root",
        },
    )
    assert res.returncode == 0, f"subprocess failed:\n{res.stderr[-3000:]}"
    return res.stdout


@pytest.fixture(autouse=True)
def _clean_global_cache():
    prepared_data_cache().clear()
    yield
    prepared_data_cache().clear()


def _toy(rows: int = 120, features: int = 5, seed: int = 11) -> DenseMatrix:
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, features)).astype(np.float32)
    margin = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] - 0.25 * x[:, 3]
    y = (margin + 0.3 * rng.standard_normal(rows) > 0).astype(np.float32)
    return DenseMatrix(x, y)


@pytest.fixture(scope="module")
def tiny():
    return _toy()


# ---------------------------------------------------------------------------
# sharded payload layout
# ---------------------------------------------------------------------------

def test_shard_payload_roundtrip_and_global_stats(tiny):
    """Row order survives flatten-then-slice; global quantile edges are the
    FULL dataset's (sharding happens after conversion, §3.9)."""
    prep = convert(tiny, "quantized_bins", max_bins=64)
    for n in SHARDS:
        sh = shard_payload(prep, n)
        assert is_sharded_payload(sh) and not is_sharded_payload(prep)
        assert sh["_n_shards"] == n and sh["_n_rows"] == tiny.x.shape[0]
        # stacked leaves: (n, ceil(R/n), ...); flatten-then-slice restores rows
        rs = -(-tiny.x.shape[0] // n)
        assert sh["bins"].shape[:2] == (n, rs)
        flat = np.asarray(sh["bins"]).reshape(n * rs, -1)[: tiny.x.shape[0]]
        np.testing.assert_array_equal(flat, np.asarray(prep["bins"]))
        # validity mask counts exactly the real rows; tail pad is zeroed
        assert int(np.asarray(sh["_shard_valid"]).sum()) == tiny.x.shape[0]
        # shard-invariant leaves (edges/format scalars) are NOT stacked
        np.testing.assert_array_equal(np.asarray(sh["edges"]),
                                      np.asarray(prep["edges"]))
        assert int(sh["n_bins"]) == int(prep["n_bins"])


def test_eight_shard_residency_bound(tiny):
    """Acceptance bar: per-device resident bytes for an 8-shard placement
    <= full-copy/8 + pad slack (one padded row per row-leading leaf, plus
    the validity mask)."""
    prep = convert(tiny, "quantized_bins", max_bins=64)
    full = payload_nbytes(prep)
    n_rows = tiny.x.shape[0]
    for n in SHARDS:
        per_shard = payload_nbytes(shard_payload(prep, n))
        rs = -(-n_rows // n)
        pad_rows = n * rs - n_rows
        # pad slack: padded rows at the full per-row rate + mask + replicated
        # non-row leaves (edges etc.) which do not shrink with n
        slack = (full // n_rows) * (pad_rows + 1) + n * rs + 4096
        assert per_shard <= full // n + slack, (n, per_shard, full)
    # sharding strictly shrinks residency vs the replicated copy
    assert payload_nbytes(shard_payload(prep, 8)) < full


def test_bytes_per_device_accepts_prepared_payload_trees(tiny):
    """Satellite 2: distributed.sharding.bytes_per_device takes the payload
    + shard_pspecs tree directly (array leaves via .nbytes, scalars ~0, a
    plain {axis: size} virtual mesh) and agrees with the cache's per-shard
    accounting to within padding."""
    prep = convert(tiny, "quantized_bins", max_bins=64)
    sh = shard_payload(prep, 8)
    specs = shard_pspecs(sh)
    # the pspec-tree report IS the cache's per-shard accounting
    per8 = bytes_per_device(sh, specs, {"shards": 8})
    assert per8 == payload_nbytes(sh)
    assert per8 < payload_nbytes(prep)
    # a degenerate {axis: 1} mesh reports the host-side stack (full + pad)
    stacked = bytes_per_device(sh, specs, {"shards": 1})
    assert stacked >= payload_nbytes(prep)
    # leaf-count mismatch is a loud error, not a silent misestimate
    with pytest.raises(ValueError):
        bytes_per_device(sh, {"bins": P("shards")}, {"shards": 8})


# ---------------------------------------------------------------------------
# acceptance grid: split-decision / margin parity on the vmap lowering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("bins", BINS)
def test_gbdt_split_parity_grid(tiny, depth, bins):
    """Per-shard histograms + one psum before the split scan choose the SAME
    (feature, threshold) at every node as the single-device build."""
    est = get_estimator("gbdt")
    params = {"round": 2, "max_depth": depth, "max_bin": bins, "eta": 0.3}
    prep = est.prepare(tiny, params)
    base = est.train(prep, params)
    for n in SHARDS:
        model = est.train(shard_payload(prep, n), params)
        np.testing.assert_array_equal(model.feat, base.feat,
                                      err_msg=f"shards={n}")
        np.testing.assert_array_equal(model.thresh, base.thresh,
                                      err_msg=f"shards={n}")
        np.testing.assert_allclose(model.leaves, base.leaves,
                                   rtol=0, atol=1e-5, err_msg=f"shards={n}")
        assert float(model.base) == float(base.base)


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("bins", BINS)
def test_forest_split_parity_grid(tiny, depth, bins):
    """Forest rides the same cross-shard histogram path; per-tree feature
    subsets and bootstrap draws are taken over the FULL row range before
    slicing, so the trees match node-for-node."""
    est = get_estimator("forest")
    params = {"n_estimators": 3, "max_depth": depth, "seed": 0}
    prep = convert(tiny, "quantized_bins", max_bins=bins)
    base = est.train(prep, params)
    for n in SHARDS:
        model = est.train(shard_payload(prep, n), params)
        np.testing.assert_array_equal(model.feat, base.feat,
                                      err_msg=f"shards={n}")
        np.testing.assert_array_equal(model.thresh, base.thresh,
                                      err_msg=f"shards={n}")
        np.testing.assert_allclose(model.leaves, base.leaves,
                                   rtol=0, atol=1e-5, err_msg=f"shards={n}")


@pytest.mark.parametrize("family,params", [
    ("logreg", {"c": 1.0, "lr": 0.05, "steps": 80}),
    ("mlp", {"network": "16_16", "learning_rate": 0.01, "steps": 60,
             "batch_size": 32, "seed": 0}),
])
def test_dp_families_margin_parity(tiny, family, params):
    """logreg/mlp do plain data-parallel grad psum (collectives.psum_tree
    semantics): margins within 1e-6 of single-device for every shard count."""
    est = get_estimator(family)
    prep = est.prepare(tiny, params)
    base = est.train(prep, params).predict_proba(tiny.x)
    for n in SHARDS:
        got = est.train(shard_payload(prep, n), params).predict_proba(tiny.x)
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-6,
                                   err_msg=f"{family} shards={n}")


# ---------------------------------------------------------------------------
# cache: placement-keyed entries, exactly-once builds, coexistence
# ---------------------------------------------------------------------------

def test_sharded_cache_exactly_once_and_coexistence(tiny):
    cache = PreparedDataCache()
    placement = ShardedPlacement(4)
    rep, _, built_rep = prepare_cached(tiny, "quantized_bins",
                                       {"max_bins": 64}, cache=cache)
    sh1, _, built1 = prepare_cached(tiny, "quantized_bins", {"max_bins": 64},
                                    cache=cache, placement=placement)
    sh2, _, built2 = prepare_cached(tiny, "quantized_bins", {"max_bins": 64},
                                    cache=cache, placement=ShardedPlacement(4))
    assert built_rep and built1 and not built2  # identity = (n, axis, tag)
    assert sh2 is sh1 and is_sharded_payload(sh1) and not is_sharded_payload(rep)
    assert cache.n_entries == 2  # replicated + sharded coexist
    # residency gauge counts ONLY the ShardedPlacement entries, per-shard
    resident = cache.sharded_resident_bytes()
    assert 0 < resident < payload_nbytes(rep)
    assert resident == payload_nbytes(sh1)
    assert cache.bytes_cached == payload_nbytes(rep) + resident
    # a different shard count is a different entry (its own partition)
    _, _, built8 = prepare_cached(tiny, "quantized_bins", {"max_bins": 64},
                                  cache=cache, placement=ShardedPlacement(8))
    assert built8 and cache.n_entries == 3


def test_sharded_placement_identity():
    a, b = ShardedPlacement(4), ShardedPlacement(4)
    assert a == b and hash(a) == hash(b)
    assert ShardedPlacement(4) != ShardedPlacement(8)
    assert ShardedPlacement(4, tag=("slice-group", 1, 0)) != a
    with pytest.raises(ValueError):
        ShardedPlacement(1)


# ---------------------------------------------------------------------------
# collectives under the vmap lowering (satellite 1, tier-1 runnable)
# ---------------------------------------------------------------------------

def _grad_tree(rng, n):
    return {
        "w": rng.standard_normal((n, 6, 3)).astype(np.float32),
        "b": (10.0 * rng.standard_normal((n, 3))).astype(np.float32),
    }


def test_compressed_psum_int8_roundtrip_with_residual_carry():
    """int8 round-trip: one-step error bounded by the shared quantisation
    scale; carrying the residual into the next step keeps the CUMULATIVE
    mean unbiased (error feedback) instead of compounding."""
    rng = np.random.default_rng(5)
    grads = _grad_tree(rng, 8)
    true = {k: v.mean(axis=0) for k, v in grads.items()}

    step0 = jax.vmap(lambda g: compressed_psum(g, "dp"), axis_name="dp")
    mean1, res1 = step0(grads)
    # outputs are shard-invariant; residuals stay per-shard
    for k in grads:
        np.testing.assert_allclose(np.asarray(mean1[k][0]),
                                   np.asarray(mean1[k][7]), rtol=0, atol=0)
        assert np.asarray(res1[k]).shape == grads[k].shape
        scale = np.abs(grads[k]).max() / 127.0
        assert np.abs(np.asarray(mean1[k][0]) - true[k]).max() <= 2 * scale

    step = jax.vmap(lambda g, r: compressed_psum(g, "dp", r), axis_name="dp")
    mean2, _ = step(grads, res1)
    for k in grads:
        # telescoping: err(mean1 + mean2 vs 2·true) = step-2's own
        # quantisation error only — no worse than a single step's bound
        cum = np.asarray(mean1[k][0]) + np.asarray(mean2[k][0])
        scale = 2 * np.abs(grads[k]).max() / 127.0  # residual can ~double |g|
        assert np.abs(cum - 2 * true[k]).max() <= 2 * scale


def test_psum_tree_is_mean_under_vmap():
    rng = np.random.default_rng(6)
    grads = _grad_tree(rng, 8)
    out = jax.vmap(lambda g: psum_tree(g, "dp"), axis_name="dp")(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out[k][0]),
                                   grads[k].mean(axis=0), rtol=0, atol=1e-6)


def test_sharded_call_vmap_psum_matches_numpy():
    from repro.compat import sharded_call
    x = np.arange(24, dtype=np.float32).reshape(8, 3)

    def per_shard(block):
        return jax.lax.psum(block.sum(), "shards"), block * 2.0

    total, doubled = sharded_call(per_shard, n_shards=8)(x)
    assert float(total) == float(x.sum())
    np.testing.assert_array_equal(np.asarray(doubled), x[0] * 2.0)


# ---------------------------------------------------------------------------
# scheduler / pool: a sharded placement is ONE unit spanning its shard group
# ---------------------------------------------------------------------------

def test_mesh_pool_shard_groups(tiny):
    pool = MeshSliceExecutorPool(slices=["s0", "s1", "s2", "s3"], n_shards=2,
                                 prepared_cache=PreparedDataCache())
    assert pool.n_executors == 2
    assert all(isinstance(g, ShardGroup) and len(g.slices) == 2
               for g in pool.slices)
    tokens = pool.prepare_placements()
    assert all(isinstance(t, ShardedPlacement) and t.n_shards == 2
               for t in tokens)
    assert len(set(tokens)) == 2  # each group keys its own partition


def test_mesh_pool_rejects_ragged_shard_groups():
    with pytest.raises(ValueError):
        MeshSliceExecutorPool(slices=["s0", "s1", "s2"], n_shards=2)


def test_mesh_pool_sharded_training_matches_replicated(tiny):
    est = get_estimator("logreg")
    params = {"c": 1.0, "lr": 0.05, "steps": 60}
    task = TrainTask(task_id=0, estimator="logreg", params=params, cost=1.0)
    base = est.train(est.prepare(tiny, params), params).predict_proba(tiny.x)
    pool = MeshSliceExecutorPool(slices=["s0", "s1"], n_shards=2,
                                 prepared_cache=PreparedDataCache())
    results = pool.run(schedule([task], pool.n_executors), tiny)
    assert len(results) == 1 and results[0].ok
    got = results[0].model.predict_proba(tiny.x)
    np.testing.assert_allclose(got, base, rtol=0, atol=1e-6)
    assert pool.prepared_cache.sharded_resident_bytes() > 0


# ---------------------------------------------------------------------------
# cost model: shard-count-aware laws (rows-per-shard is the bucketed size)
# ---------------------------------------------------------------------------

def _task(family="gbdt", cost=1.0):
    return TrainTask(task_id=0, estimator=family, params={}, cost=cost)


def test_cost_model_shard_laws_and_fallback():
    cm = CostModel()
    t = _task()
    # cold sharded law → the unsharded estimate answers (conservative)
    for n_rows, secs in ((1000, 1.0), (4000, 4.0), (16000, 16.0)):
        cm.observe(t, secs, n_rows)
    cold = cm.estimate(t, 8000, n_shards=4)
    assert cold == pytest.approx(cm.estimate(t, 8000), rel=1e-6)
    # sharded observations land under their own family law, keyed on
    # rows-per-shard: 8000 rows over 4 shards regress at x = log(2000)
    for n_rows, secs in ((4000, 0.4), (16000, 1.6)):
        cm.observe(t, secs, n_rows, n_shards=4)
    warm = cm.estimate(t, 8000, n_shards=4)
    assert warm is not None and warm < cold
    # the unsharded law is untouched by sharded observations
    assert cm.estimate(t, 8000) == pytest.approx(cold, rel=1e-6)


def test_cost_model_shard_laws_persist_roundtrip(tmp_path):
    cm = CostModel(path=str(tmp_path / "cost.json"))
    t = _task()
    for n_rows, secs in ((4000, 0.4), (16000, 1.6)):
        cm.observe(t, secs, n_rows, n_shards=4)
    cm.observe_eval(t, 0.05, 4000, n_shards=4)
    d = cm.to_dict()
    assert "gbdt#s4" in d["families"]  # plain string key → no format change
    cm2 = CostModel.from_dict(d)
    assert cm2.estimate(t, 8000, n_shards=4) == pytest.approx(
        cm.estimate(t, 8000, n_shards=4), rel=1e-9)
    assert cm2.predict_eval(t, 8000, n_shards=4) == pytest.approx(
        cm.predict_eval(t, 8000, n_shards=4), rel=1e-9)


def test_cost_model_predict_eval_shard_fallback():
    cm = CostModel()
    t = _task()
    for n_rows, secs in ((1000, 0.01), (4000, 0.04)):
        cm.observe_eval(t, secs, n_rows)
    # cold sharded eval law falls back to the unsharded local one
    assert cm.predict_eval(t, 2000, n_shards=4) == pytest.approx(
        cm.predict_eval(t, 2000), rel=1e-6)


# ---------------------------------------------------------------------------
# spec + session plumbing
# ---------------------------------------------------------------------------

def test_spec_n_shards_validation():
    space = GridBuilder("logreg").add_grid("c", [1.0]).build()
    assert SearchSpec(spaces=[space]).n_shards == 1
    assert SearchSpec(spaces=[space], n_shards=4).n_shards == 4
    with pytest.raises(ValueError):
        SearchSpec(spaces=[space], n_shards=0)


def test_session_sharded_parity_and_residency(tiny):
    """End-to-end: a 2-sharded Session scores every config within 1e-6 of
    the replicated run and reports nonzero shard residency, strictly below
    a full copy's bytes."""
    valid = _toy(rows=80, seed=12)
    space = GridBuilder("logreg").add_grid("c", [0.1, 1.0]).build()

    def run(n_shards):
        spec = SearchSpec(spaces=[space], n_executors=2, n_shards=n_shards,
                          seed=0)
        session = Session(spec)
        results = {tuple(sorted(r.task.params.items())): r.score
                   for r in session.results(tiny, valid)}
        return results, session.stats

    base, st1 = run(1)
    got, st2 = run(2)
    assert set(got) == set(base) and len(base) == 2
    for key, score in got.items():
        assert score == pytest.approx(base[key], abs=1e-6)
    assert st1.shard_residency_bytes == 0
    prep = get_estimator("logreg").prepare(tiny, {})
    assert 0 < st2.shard_residency_bytes < payload_nbytes(prep)


# ---------------------------------------------------------------------------
# multi-device lowering (ci.yml `sharded` lane)
# ---------------------------------------------------------------------------

@sharded_lane
def test_psum_tree_on_8_device_host_mesh():
    """Satellite 1: psum_tree under shard_map over a real (virtual-host)
    8-device mesh equals the numpy mean."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map
        from repro.launch.mesh import compat_make_mesh
        from repro.distributed.collectives import psum_tree
        assert jax.device_count() == 8
        mesh = compat_make_mesh((8,), ("dp",))
        g = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
        f = shard_map(lambda x: psum_tree(x, "dp"), mesh=mesh,
                      in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
        got = np.asarray(f(g))[0]
        rel = float(np.abs(got - g.mean(0)).max())
        print("REL", rel)
    """)
    assert float(out.split("REL ")[1].split()[0]) < 1e-6


@sharded_lane
def test_sharded_call_shard_map_matches_vmap_lowering():
    """The two lowerings of sharded_call — shard_map over a real 8-device
    mesh vs single-device vmap — are the same program: identical psums."""
    out = run_subprocess("""
        import jax, numpy as np
        from repro.compat import sharded_call
        from repro.launch.mesh import compat_make_mesh
        assert jax.device_count() == 8
        mesh = compat_make_mesh((8,), ("shards",))
        x = np.random.default_rng(1).standard_normal((8, 5, 3)).astype(np.float32)

        def per_shard(block):
            return jax.lax.psum(block.sum(axis=0), "shards")

        spmd = np.asarray(sharded_call(per_shard, n_shards=8, mesh=mesh)(x))
        vmap = np.asarray(sharded_call(per_shard, n_shards=8)(x))
        rel = float(np.abs(spmd - vmap).max())
        print("REL", rel)
    """)
    assert float(out.split("REL ")[1].split()[0]) < 1e-6


@sharded_lane
def test_gbdt_sharded_split_parity_on_real_mesh():
    """Cross-shard histogram psum under a REAL 8-device mesh picks the same
    splits as the single-device build (the §3.9 bit-exactness argument is
    lowering-independent)."""
    out = run_subprocess("""
        import numpy as np
        import repro.tabular  # noqa: F401
        from repro.core import DenseMatrix, convert, get_estimator
        from repro.core.data_format import shard_payload
        rng = np.random.default_rng(11)
        x = rng.standard_normal((120, 5)).astype(np.float32)
        y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] > 0).astype(np.float32)
        data = DenseMatrix(x, y)
        est = get_estimator("gbdt")
        params = {"round": 2, "max_depth": 3, "max_bin": 64}
        prep = est.prepare(data, params)
        base = est.train(prep, params)
        model = est.train(shard_payload(prep, 8), params)
        ok = (np.array_equal(model.feat, base.feat)
              and np.array_equal(model.thresh, base.thresh))
        print("SPLITS", "match" if ok else "MISMATCH")
    """)
    assert "SPLITS match" in out
