"""A new cell, configuration, traffic mix and per-layer metric are taken as
data: files and entries added beside the others, found by name, with no
edit to a file that is there."""
import json

from bench import cells
from bench.tests import tiny


def test_new_files_are_found_by_name_and_run(tmp_path):
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    tiny.add_cell(root, "tiny", tiny.TINY_HIGGS, "tiny-traffic",
                  tiny.TINY_TRAFFIC)
    (root / "bench/metrics/scored_count.py").write_text(
        '"""Configurations the window scored."""\n\n\n'
        "def read(run):\n    return float(len(run.scored()))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "scored_count", "unit": "configs", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "configs_per_s",
        "workloads": ["tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())

    cell = cells.load_cell("tiny", root)
    assert cell.config["rows"] == tiny.TINY_HIGGS["rows"]
    assert cell.traffic == tiny.TINY_TRAFFIC
    assert "scored_count" in [m["name"] for m in cell.per_layer]
    assert "level_kernel_roofline" not in [m["name"] for m in cell.per_layer]

    proc = tiny.run_off_chip(root, ["--workload", "tiny", "--seed",
                                    str(2**31 + 11), "--seconds", "2",
                                    "--trace", "1"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = tiny.result_line(proc)
    assert out["correct"] is True
    assert out["metrics"]["scored_count"]["value"] > 0
    assert "configs_per_s" not in out["metrics"]
    assert list(out)[-1] == "checks"
    # the trees and the dense fits the window produced are both compared
    assert set(out["checks"]) == {"split_gap", "leaf_gap", "score_gap",
                                  "change_gap", "loss_gap"}
