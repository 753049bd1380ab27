"""A benchmark checkout at a size a test run can hold: a copy of ``bench/``
and ``BENCHMARK.json`` under a temporary root, with the program's sources
linked in, and tiny configurations, traffic and cells added as data."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_HIGGS = {"name": "tiny-higgs", "generator": "higgs", "rows": 1500,
              "features": 28, "split": [0.6, 0.2, 0.2],
              # on the CPU over six seeds the program reads at most 0, 2.2e-7,
              # 0, 6.6e-8 and 0 here; the control at least 0, 2.1e-3, 0,
              # 5.8e-3 and 1.6e-3; half of each batch left out 7.2e-2 and
              # 3.3e-2, a state left unchanged 1 and 0.71
              "correct_limits": {"split_gap": 1e-3, "leaf_gap": 2e-4,
                                 "score_gap": 5e-5, "change_gap": 1e-4,
                                 "loss_gap": 1e-4}}
TINY_TRAFFIC = {
    "backend": "local", "n_executors": 1, "policy": "lpt", "fuse": False,
    "max_fuse": 16, "warmup": [0],
    "check_sample": {"gbdt": 2, "mlp": 1, "logreg": 1},
    "searches": [[
        {"estimator": "gbdt", "params": {"eta": 0.3, "round": 2,
                                         "max_bin": 16, "max_depth": 3}},
        {"estimator": "mlp", "params": {"network": "8", "learning_rate": 0.03,
                                        "steps": 4}},
        {"estimator": "logreg", "params": {"c": 0.1, "steps": 20}},
    ]],
}


def make_root(tmp: Path) -> Path:
    """A checkout under ``tmp`` holding the committed benchmark files and a
    link to the program's sources."""
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    os.symlink(REPO / "src", root / "src")
    return root


def add_cell(root: Path, cell: str, config: dict, traffic_name: str,
             traffic: dict) -> None:
    """Add a configuration, a traffic mix and a cell as new files and
    entries; no file that is there is edited but ``BENCHMARK.json``."""
    cfg = dict(config)
    (root / "bench/configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (root / "bench/traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if cfg["name"] not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append({"name": cfg["name"], "source": "test",
                                 "file": f"bench/configs/{cfg['name']}.json",
                                 "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": cfg["name"],
                               "traffic": traffic_name, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def run_off_chip(root: Path, args: list[str], prelude: str = "",
                 timeout: float = 600) -> subprocess.CompletedProcess:
    """``bench/run.py``'s ``main`` in a child process on the CPU, past the
    look for a chip. ``prelude`` runs first (a planted fault)."""
    code = (f"import sys; sys.path.insert(0, {str(root)!r})\n{prelude}\n"
            "from bench import run\n"
            f"sys.exit(run.main({args!r}, require_chip=False))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(root / "src")}
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def result_line(proc: subprocess.CompletedProcess) -> dict:
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1])
