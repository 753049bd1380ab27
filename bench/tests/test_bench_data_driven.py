"""A new cell, configuration, traffic mix and per-layer metric are taken as
data: files and entries added beside the others, found by name, with no
edit to a file that is there. So is a configuration's task: the metric its
search is scored by and the plain reference it is checked against."""
import json

import pytest

from bench import cells
from bench.tests import tiny

#: a copy of ``reference.py`` that says on standard error that it scored
OWN_REFERENCE_TAIL = '''

import sys as _sys

_score = score


def score(metric, y, probs):
    print(f"own_reference scored by {metric}", file=_sys.stderr)
    return _score(metric, y, probs)
'''

#: a run in which ``bench/reference.py`` cannot be loaded, by path or import
BLOCK_REFERENCE = '''
import sys
sys.modules["bench.reference"] = None
from bench import cells
_load = cells.load_module
def _guarded(path):
    if path.name == "reference.py":
        raise AssertionError(f"{path} was loaded")
    return _load(path)
cells.load_module = _guarded
'''

#: each case: what its configuration changes of the tiny one's, whether it
#: brings its own reference, and the range its best reported score lies in
CASES = {
    "auc": ({}, False, (0.5, 1.0)),
    # the program's negated log loss, compared with the reference's
    "neg_logloss": ({"name": "tiny-logloss", "metric": "neg_logloss"}, False,
                    (-1.0, 0.0)),
    # checked through a reference file of its own, not bench/reference.py
    "own_reference": ({"name": "tiny-own", "reference": "own_reference"}, True,
                      (0.5, 1.0)),
}


def _add_metric(root, name, body, doc):
    (root / "bench/metrics" / f"{name}.py").write_text(
        f'"""{doc}"""\n\n\ndef read(run):\n    return {body}\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": name, "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "configs_per_s",
        "workloads": ["tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("case", sorted(CASES))
def test_new_files_are_found_by_name_and_run(tmp_path, case):
    changes, own_reference, (lo, hi) = CASES[case]
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    config = {**tiny.TINY_HIGGS, **changes}
    tiny.add_cell(root, "tiny", config, "tiny-traffic", tiny.TINY_TRAFFIC)
    if own_reference:
        (root / "bench/own_reference.py").write_text(
            (root / "bench/reference.py").read_text() + OWN_REFERENCE_TAIL)
    _add_metric(root, "scored_count", "float(len(run.scored()))",
                "Configurations the window scored.")
    _add_metric(root, "best_score", "max(r.score for r in run.scored())",
                "The best score the search reported.")
    assert all(p.read_bytes() == b for p, b in before.items())

    cell = cells.load_cell("tiny", root)
    assert cell.config["rows"] == tiny.TINY_HIGGS["rows"]
    assert cell.traffic == tiny.TINY_TRAFFIC
    assert "scored_count" in [m["name"] for m in cell.per_layer]
    assert "level_kernel_roofline" not in [m["name"] for m in cell.per_layer]

    proc = tiny.run_off_chip(root, ["--workload", "tiny", "--seed",
                                    str(2**31 + 11), "--seconds", "2",
                                    "--trace", "1"],
                             prelude=BLOCK_REFERENCE if own_reference else "")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = tiny.result_line(proc)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["scored_count"]["value"] > 0
    assert lo < out["metrics"]["best_score"]["value"] <= hi
    assert "configs_per_s" not in out["metrics"]
    assert list(out)[-1] == "checks"
    # the trees and the dense fits the window produced are both compared
    assert set(out["checks"]) == {"split_gap", "leaf_gap", "score_gap",
                                  "change_gap", "loss_gap"}
    scored_by_own = f"own_reference scored by {config['metric']}"
    assert (scored_by_own in proc.stderr) == own_reference


@pytest.mark.parametrize("key", cells.TASK_KEYS)
def test_a_configuration_that_does_not_state_its_task_is_refused(tmp_path,
                                                                 key):
    root = tiny.make_root(tmp_path)
    config = {k: v for k, v in tiny.TINY_HIGGS.items() if k != key}
    tiny.add_cell(root, "tiny", config, "tiny-traffic", tiny.TINY_TRAFFIC)
    with pytest.raises(ValueError, match=r"tiny-higgs\.json.*" + key):
        cells.load_cell("tiny", root)
