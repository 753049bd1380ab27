"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic; everything
else sits in a file of its own under this directory:

- ``configs/<config>.json``: the dataset deployment, with the generator it
  names (``generators/<generator>.py``, a ``generate(sizes, seed)``);
- ``traffic/<traffic>.json``: the searches, backend, executors and policy;
- ``metrics/<metric>.py``: one per-layer metric, a ``read(run)`` that
  returns a number or None where it finds nothing to read;
- ``peaks.json``: the chip's peaks, keyed by JAX's ``device_kind``.

A later change adds a cell, a configuration, a traffic mix or a metric by
adding files and entries, without editing any file that is here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    bench_dir: Path

    def generator(self) -> ModuleType:
        return load_module(self.bench_dir / "generators"
                           / f"{self.config['generator']}.py")

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{name}.py")


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration,
    traffic and the metrics it reports."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / Path(configs[w["config"]]["file"]).parent.parent
    config = read_json(root / configs[w["config"]]["file"])
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        bench_dir=bench_dir)


def peaks_for(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The peak table's row for this device; a device not in it is an error."""
    table = read_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
