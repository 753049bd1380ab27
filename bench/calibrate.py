"""Readings that the limits of ``correct`` are set from, on the chip at a
cell's own size:

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --out <file>

For each seed, in one process: the cell's rows from that seed, the first
``--searches`` searches of its traffic through the program, the same sample
of scored configurations that a run checks, and for those configurations
the numbers of the program's answers (the lower readings), of the
control's (the reference computed in bfloat16 in the program's place) and,
for the dense families, of two faults planted in the reference put in the
program's place: half of each batch left out with the mean taken over the
rest (``half_batch``), and the state left as it started
(``state_unchanged``). One JSON line per seed goes to ``--out``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cells, check, drive, run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--searches", type=int, default=1)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    cell = cells.load_cell(args.workload)
    import jax

    devices = run.chips_or_none(jax, cell.chips)
    if devices is None:
        print("calibrate: no TPU with the cell's chips", file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    sys.path.insert(0, drive.src_path())
    from bench import reference

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        train, valid = drive.make_data(cell, seed)
        driver = drive.Driver(cell, seed, train, valid, devices)
        landed = []
        for i in range(args.searches):
            landed += driver.run_search(i)[0]
        t_search = time.perf_counter() - t0
        failed = [lr for lr in landed if not lr.result.ok]
        answers = run.answers_of(landed)
        picked = check.sample(answers, cell.traffic["check_sample"], seed,
                              int(cell.traffic["n_executors"]))
        driver.free()
        prep = reference.Prepared(*train)
        chosen = [answers[i] for i in picked]
        t1 = time.perf_counter()
        prog = [check.numbers(prep, valid[0], valid[1], a, reference)
                for a in chosen]
        t_ref = time.perf_counter() - t1
        ctrl_answers = check.control_answers(prep, valid[0], valid[1], chosen,
                                             reference)
        ctrl = [check.numbers(prep, valid[0], valid[1], a, reference)
                for a in ctrl_answers]
        planted = {name: [None if a.family in check.TREES else
                          check.numbers(prep, valid[0], valid[1], plant(a),
                                        reference) for a in chosen]
                   for name, plant in _dense_faults(prep, reference).items()}
        line = {"seed": seed, "failed": len(failed), "scored": len(answers),
                "search_s": t_search, "reference_s": t_ref,
                "program": check.worst(prog), "control": check.worst(ctrl),
                "faults": {name: check.worst([n for n in per if n is not None])
                           for name, per in planted.items()},
                "configs": [{"family": a.family, "params": a.params,
                             "score": a.score, "program": prog[j],
                             "control": ctrl[j],
                             "faults": {name: per[j]
                                        for name, per in planted.items()}}
                            for j, a in enumerate(chosen)]}
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line[k] for k in ("seed", "failed", "scored",
                                                "search_s", "reference_s",
                                                "program", "control",
                                                "faults")}),
              flush=True)
    return 0


def _dense_faults(prep, reference):
    """Each planted fault as a map from a dense answer to its faulty twin."""
    import jax.numpy as jnp

    def half_batch(a):
        model = reference.fit_dense(prep, a.family, a.params, jnp.float32,
                                    half=True)
        return dataclasses.replace(a, model=model)

    def state_unchanged(a):
        return dataclasses.replace(
            a, model=reference.dense_init(prep, a.family, a.params))

    return {"half_batch": half_batch, "state_unchanged": state_unchanged}


if __name__ == "__main__":
    sys.exit(main())
