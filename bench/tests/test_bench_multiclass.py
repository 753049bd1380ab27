"""The 7-class configuration as data: the Covertype generator's published
shape, the multi-class reference's contract, a tiny Covertype cell run off
the chip, its bfloat16 control failing the limits the program meets, and the
two per-layer metrics the configuration's cell adds."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import cells, check, trace_reduce as tr
from bench import reference_multiclass as ref
from bench.generators import covertype
from bench.record import RunRecord
from bench.tests import tiny

MS = 1_000_000
SEEDS = (3, 2**31 + 5)

#: a 2,000-row Covertype cut. On the CPU over these seeds and six more the
#: program reads at most 2.2e-5 (split_gap), 3.7e-6 (leaf_gap) and 0
#: (score_gap); the bfloat16 control at least 2.0e-4, 4.9e-3 and 2.5e-4,
#: so the control fails by leaf_gap and score_gap on every seed
TINY_COVER = {"name": "tiny-cover", "generator": "covertype",
              "metric": "neg_mlogloss", "outputs": 7,
              "reference": "reference_multiclass", "rows": 2000,
              "features": 54, "split": [0.6, 0.2, 0.2],
              "correct_limits": {"split_gap": 1e-3, "leaf_gap": 2e-4,
                                 "score_gap": 1e-6}}
TINY_COVER_TRAFFIC = {
    "backend": "local", "n_executors": 1, "policy": "lpt", "fuse": False,
    "max_fuse": 16, "warmup": [0], "check_sample": {"gbdt": 2},
    "searches": [[
        {"estimator": "gbdt", "params": {"eta": 0.3, "round": 3,
                                         "max_bin": 32, "max_depth": 4}},
        {"estimator": "gbdt", "params": {"eta": 0.9, "round": 2,
                                         "max_bin": 16, "max_depth": 3}},
    ]],
}


def test_generator_has_the_published_shape():
    assert covertype.class_counts(581_012).tolist() == list(
        covertype.CLASS_COUNTS)
    counts = covertype.class_counts(5000)
    assert counts.sum() == 5000 and counts.min() >= 1
    x, y = covertype.generate({"rows": 5000}, 2**31 + 9)
    assert x.shape == (5000, 54) and x.dtype == np.float32
    assert np.bincount(y.astype(int), minlength=7).tolist() == counts.tolist()
    assert np.array_equal(x, np.rint(x))
    lo, hi = np.asarray(covertype.RANGES).T
    assert np.all(x[:, :10] >= lo) and np.all(x[:, :10] <= hi)
    onehot = x[:, 10:]
    assert set(np.unique(onehot)) == {0.0, 1.0}
    assert np.all(onehot[:, :4].sum(axis=1) == 1)
    assert np.all(onehot[:, 4:].sum(axis=1) == 1)
    again, y2 = covertype.generate({"rows": 5000}, 2**31 + 9)
    assert np.array_equal(x, again) and np.array_equal(y, y2)


def test_the_configuration_states_its_task():
    cell = cells.load_cell("covertype-gbdt")
    assert cell.chips == 1
    assert (cell.config["metric"], cell.config["outputs"],
            cell.config["reference"]) == ("neg_mlogloss", 7,
                                          "reference_multiclass")
    names = [m["name"] for m in cell.per_layer]
    assert {"train_s_per_tree", "class_level_kernel_roofline",
            "class_level_kernel_time_share"} <= set(names)
    assert "level_kernel_roofline" not in names
    assert "level_kernel_time_share" not in names
    other = cells.load_cell("higgs-gbdt")
    assert "train_s_per_tree" not in [m["name"] for m in other.per_layer]
    traffic = cell.traffic
    warm = [tuple(c["params"][k] for k in ("round", "max_bin", "max_depth"))
            for i in traffic["warmup"] for c in traffic["searches"][i]]
    assert len(warm) == len(set(warm)) == 18
    assert sum(len(s) for s in traffic["searches"]) == 54


def test_reference_has_no_dense_family():
    for fn, args in ((ref.fit_dense, (None, "mlp", {}, None)),
                     (ref.dense_init, (None, "mlp", {})),
                     (ref.dense_loss, (None, "mlp", {}, None)),
                     (ref.change_gap, (None, None, None)),
                     (ref.probs, ("forest", {}, None))):
        with pytest.raises(NotImplementedError):
            fn(*args)
    with pytest.raises(ValueError, match="auc"):
        ref.score("auc", np.zeros(2), np.full((2, 7), 1 / 7))


def test_tiny_cover_cell_runs_correct_off_the_chip(tmp_path):
    root = tiny.make_root(tmp_path)
    tiny.add_cell(root, "tiny-cover", TINY_COVER, "tiny-cover-traffic",
                  TINY_COVER_TRAFFIC)
    proc = tiny.run_off_chip(root, ["--workload", "tiny-cover", "--seed",
                                    str(SEEDS[1]), "--seconds", "2",
                                    "--trace", "0"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"split_gap", "leaf_gap", "score_gap"}
    assert out["metrics"]["configs_per_s"]["value"] > 0


def test_control_fails_where_the_program_passes(tmp_path):
    root = tiny.make_root(tmp_path)
    tiny.add_cell(root, "tiny-cover", TINY_COVER, "tiny-cover-traffic",
                  TINY_COVER_TRAFFIC)
    out = tmp_path / "cal.jsonl"
    code = (f"import sys; sys.path.insert(0, {str(root)!r})\n"
            "from bench import calibrate, run\n"
            "run.chips_or_none = lambda jax, n: jax.devices()[:n]\n"
            f"sys.exit(calibrate.main(['--workload', 'tiny-cover', '--seeds', "
            f"{','.join(map(str, SEEDS))!r}, '--out', {str(out)!r}]))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(root / "src")}
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [ln["seed"] for ln in lines] == list(SEEDS)
    limits = TINY_COVER["correct_limits"]
    for ln in lines:
        assert ln["failed"] == 0
        assert check.judge(ln["program"], limits)[0], ln
        assert not check.judge(ln["control"], limits)[0], ln


@pytest.mark.parametrize("fault", ["as_trained", "permuted", "zeros",
                                   "binary_prior"])
def test_replay_catches_a_wrong_base(fault):
    """The trees of a model are optimal for whatever base they were grown
    on, so only the base margins themselves show a wrong prior: a model whose
    base is not the log class priors of the training labels reads the worst
    gaps, whatever its trees."""
    x, y = covertype.generate({"rows": 700}, SEEDS[1])
    prep = ref.Prepared(x, y)
    params = {"eta": 0.3, "round": 2, "max_bin": 16, "max_depth": 3}
    model = ref.fit_trees(prep, "gbdt", params, np.float32)
    base = np.asarray(model["base"], np.float32)
    model["base"] = {"as_trained": base, "permuted": np.roll(base, 1),
                     "zeros": np.zeros_like(base),
                     "binary_prior": np.float32(base[0])}[fault]
    nums = ref.replay_trees(prep, "gbdt", params, model)
    if fault == "as_trained":
        assert nums["split_gap"] <= 1e-3 and nums["leaf_gap"] <= 1e-5, nums
    else:
        assert nums == {"split_gap": float("inf"), "leaf_gap": float("inf")}
        assert not check.judge(nums, TINY_COVER["correct_limits"])[0]


def _ev(a, b, name):
    return tr.Event(int(a * MS), int(b * MS), name)


def _record(trees, outputs):
    res = types.SimpleNamespace(
        ok=True, score=-0.7, train_seconds=2.1, eval_seconds=0.5,
        convert_seconds=0.0,
        task=types.SimpleNamespace(estimator="gbdt",
                                   params={"round": 3, "max_depth": 4}))
    if trees is not None:
        res.trees = trees
    landed = [types.SimpleNamespace(t=1.0, result=res, search=0)]
    rec = RunRecord(cell=types.SimpleNamespace(name="t"), landed=landed,
                    t0=0.0, ends=[1.5], n_executors=1, chips=1,
                    setup={"convert_s": 1.0, "compile_s": 2.0},
                    train_rows=348_607, features=54, outputs=outputs,
                    peak=cells.peaks_for("TPU v5 lite"))
    trace = tr.Trace(
        devices={"/device:TPU:0": [_ev(0, 8, "fused_level_split_tpu.3")]},
        host=[_ev(0, 10, "bench.window"), _ev(9, 9.1, "bench.result"),
              _ev(9.1, 9.1, "bench.search_end")])
    rec.attach_trace(trace, [types.SimpleNamespace(id=0)])
    return rec


def test_train_s_per_tree_reads_the_program_counter():
    per_tree = cells.load_module(cells.BENCH_DIR / "metrics/train_s_per_tree.py")
    assert per_tree.read(_record(21, 7)) == pytest.approx(2.1 / 21)
    # a program that counts no trees (the parent of this metric) reads none
    assert per_tree.read(_record(None, 7)) is None
    assert per_tree.read(_record(0, 7)) is None


def test_class_level_kernel_roofline_is_the_level_roofline_at_k():
    cls = cells.load_module(
        cells.BENCH_DIR / "metrics/class_level_kernel_roofline.py")
    level = cells.load_module(cells.BENCH_DIR / "metrics/level_kernel_roofline.py")
    rec = _record(21, 7)
    # 3 rounds of 348,607 rows x (54 bin ids + 7 x 8 bytes of g and h) at
    # 819 GB/s, over the kernel's 8 ms
    want = 100 * 3 * 348_607 * (54 + 56) / 819e9 / 0.008
    assert cls.read(rec) == pytest.approx(want) == level.read(rec)
    assert 0 < cls.read(rec) < 100
    assert cls.read(_record(3, 1)) is None


def test_class_level_kernel_time_share_is_the_level_share_at_k():
    cls = cells.load_module(
        cells.BENCH_DIR / "metrics/class_level_kernel_time_share.py")
    level = cells.load_module(
        cells.BENCH_DIR / "metrics/level_kernel_time_share.py")
    rec = _record(21, 7)
    # the kernel's 8 ms of the 9.1 ms from the window's start to the end of
    # its last whole search
    assert cls.read(rec) == pytest.approx(100 * 8 / 9.1) == level.read(rec)
    assert cls.read(_record(3, 1)) is None
