"""A run whose timed path is broken underneath comes out not correct: the
harness's look for a chip is skipped and the rest of a run is driven, at a
size a test run can hold, with one fault planted in the program each time."""
import pytest

from bench.tests import tiny

#: a wrapper that trains on the first half of the rows, the mean taken
#: over them
HALF = """
def half(train):
    def wrapped(self, data, params):
        n = data["y"].shape[0] // 2
        cut = {k: (v[:n] if getattr(v, "shape", ())[:1] == (2 * n,) else v)
               for k, v in data.items()}
        return train(self, cut, params)
    return wrapped
"""

#: each fault, and the numbers of which at least one has to go over its limit
FAULTS = {
    # a scored answer altered where it is produced
    "score_altered": ("""
import repro.core.executor as ex
_orig = ex.evaluate_models
def _bad(*a, **k):
    scores, secs = _orig(*a, **k)
    return [None if s is None else s + 0.01 for s in scores], secs
ex.evaluate_models = _bad
""", {"score_gap"}),
    # tree training that returns its state unchanged
    "state_unchanged": ("""
from repro.tabular.gbdt import GBDTEstimator
_g = GBDTEstimator.train
def _gbdt(self, data, params):
    model = _g(self, data, params)
    model.leaves = model.leaves * 0.0
    return model
GBDTEstimator.train = _gbdt
""", {"leaf_gap", "split_gap"}),
    # dense training steps that return their state unchanged, trees sound
    "dense_state_unchanged": ("""
from repro.tabular.logreg import LogRegEstimator
from repro.tabular.mlp import MLPEstimator
for _cls in (MLPEstimator, LogRegEstimator):
    _cls.train = (lambda t: lambda self, data, params:
                  t(self, data, {**params, "steps": 0}))(_cls.train)
""", {"change_gap", "loss_gap"}),
    # half of the rows left out of every tree fit
    "half_the_rows": (HALF + """
from repro.tabular.gbdt import GBDTEstimator
GBDTEstimator.train = half(GBDTEstimator.train)
""", {"leaf_gap", "split_gap"}),
    # half of the rows left out of every dense fit, trees sound
    "dense_half_the_rows": (HALF + """
from repro.tabular.logreg import LogRegEstimator
from repro.tabular.mlp import MLPEstimator
MLPEstimator.train = half(MLPEstimator.train)
LogRegEstimator.train = half(LogRegEstimator.train)
""", {"change_gap", "loss_gap"}),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("faults"))
    tiny.add_cell(root, "tiny", tiny.TINY_HIGGS, "tiny-traffic",
                  tiny.TINY_TRAFFIC)
    return root


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(root, fault):
    prelude, caught_by = FAULTS[fault]
    proc = tiny.run_off_chip(root, ["--workload", "tiny", "--seed",
                                    str(2**31 + 7), "--seconds", "1",
                                    "--trace", "0"], prelude=prelude)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = tiny.result_line(proc)
    assert out["correct"] is False, out["checks"]
    over = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert over & caught_by, out["checks"]


def test_sound_run_is_correct(root):
    proc = tiny.run_off_chip(root, ["--workload", "tiny", "--seed",
                                    str(2**31 + 7), "--seconds", "1",
                                    "--trace", "0"])
    out = tiny.result_line(proc)
    assert out["correct"] is True, out["checks"]
