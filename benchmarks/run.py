"""Benchmark harness: one function per paper table/figure + LM substrate.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,fig7] [--out FILE]
    PYTHONPATH=src python -m benchmarks.run --smoke --json BENCH_abc.json \
        --baseline benchmarks/baseline.json

Prints ``name,value,derived`` CSV rows; exits non-zero if any benchmark
raises. Figures map to the paper as documented in paper_figs.py.

CI gating (DESIGN.md §3.1): ``--smoke`` runs only the deterministic,
device-free benches (fixed seeds, simulated makespans — no wall-clock in any
gated value); ``--json`` writes the rows as ``{"rows": {name: value}}``;
``--baseline`` compares every ``*makespan*`` row against a checked-in
baseline JSON and FAILS when one regresses more than ``--regress-tolerance``
(makespans are lower-is-better). Regenerate the baseline with
``scripts/bench_baseline.py`` after an intentional scheduling change.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (
    asha_bench,
    chaos_bench,
    cost_model_bench,
    eval_bench,
    fusion_bench,
    gbdt_kernel_bench,
    lm_bench,
    paper_figs,
    prepared_data_bench,
    serve_bench,
    sharded_bench,
)

#: bump when row names/semantics change incompatibly, so BENCH_<sha>.json
#: artifacts from different PRs are only ever compared within one schema
SCHEMA_VERSION = 1

BENCHES = {
    "fig3": paper_figs.fig3_profiling_ratio,
    "fig4": paper_figs.fig4_loc,
    "fig5": paper_figs.fig5_scheduling,
    "fig6": paper_figs.fig6_frameworks,
    "fig7": paper_figs.fig7_auc_parity,
    "session_stream": paper_figs.session_streaming,
    "cost_model": cost_model_bench.mis_estimate_recovery,
    "fusion": fusion_bench.full,
    "prepared_data": prepared_data_bench.full,
    "eval_plane": eval_bench.full,
    "asha": asha_bench.full,
    "gbdt_kernel": gbdt_kernel_bench.full,
    "lm_steps": lm_bench.arch_step_times,
    "kernels": lm_bench.kernel_parity,
    "serve": serve_bench.full,
    "chaos": chaos_bench.full,
    "sharded": sharded_bench.full,
}

#: the --smoke table: deterministic (except the *.wallclock.* rows, which
#: are excluded from the exact-compared baseline) + fast, safe to gate CI on
SMOKE_BENCHES = {
    "cost_model": cost_model_bench.smoke,
    "fusion": fusion_bench.smoke,
    "prepared_data": prepared_data_bench.smoke,
    "eval_plane": eval_bench.smoke,
    "asha": asha_bench.smoke,
    "histogram": fusion_bench.histogram_smoke,
    "gbdt_kernel": gbdt_kernel_bench.smoke,
    "serve": serve_bench.smoke,
    "chaos": chaos_bench.smoke,
    "sharded": sharded_bench.smoke,
}


def compare_to_baseline(rows: dict[str, float], baseline_rows: dict[str, float],
                        tolerance: float, *, full_run: bool = True) -> list[str]:
    """Regression messages for every gated (makespan) row; empty == pass.

    With ``full_run`` (no ``--only`` filter) a baseline makespan row that
    vanished from the produced set is itself flagged — silently dropping a
    gated metric is how regressions sneak in. A partial ``--only`` run gates
    only the rows it actually produced.
    """
    problems = []
    for name, base in sorted(baseline_rows.items()):
        if "makespan" not in name:
            continue
        if name not in rows:
            if full_run:
                problems.append(f"{name}: in baseline but not produced by this run")
            continue
        value = rows[name]
        if base > 0 and value > base * (1.0 + tolerance):
            problems.append(
                f"{name}: {value:.6g} vs baseline {base:.6g} "
                f"(+{100 * (value / base - 1):.1f}% > {100 * tolerance:.0f}% allowed)")
    return problems


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default=None, help="comma-separated bench names")
    p.add_argument("--out", default=None, help="also write CSV to this path")
    p.add_argument("--smoke", action="store_true",
                   help="deterministic device-free subset (the CI gate)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help='write {"rows": {name: value}} JSON (CI artifact)')
    p.add_argument("--baseline", default=None, metavar="PATH",
                   help="fail if any *makespan* row regresses vs this JSON")
    p.add_argument("--regress-tolerance", type=float, default=0.20,
                   help="allowed relative makespan regression (default 20%%)")
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    table = SMOKE_BENCHES if args.smoke else BENCHES
    names = args.only.split(",") if args.only else list(table)
    lines = ["name,value,derived"]
    results: dict[str, float] = {}
    failed = []
    for name in names:
        t0 = time.perf_counter()
        try:
            rows = table[name]()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            continue
        for row_name, value, derived in rows:
            line = f'{row_name},{value:.6g},"{derived}"'
            print(line, flush=True)
            lines.append(line)
            results[row_name] = float(value)
        print(f"# {name}: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION, "smoke": args.smoke,
                       "benches": names, "rows": results},
                      f, indent=1, sort_keys=True)
            f.write("\n")
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        return 1
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        base_schema = baseline.get("schema_version", SCHEMA_VERSION)
        if base_schema != SCHEMA_VERSION:
            print(f"BASELINE SCHEMA MISMATCH: baseline v{base_schema} vs "
                  f"this run v{SCHEMA_VERSION} — regenerate with "
                  "scripts/bench_baseline.py", file=sys.stderr)
            return 1
        baseline_rows = baseline["rows"]
        problems = compare_to_baseline(results, baseline_rows,
                                       args.regress_tolerance,
                                       full_run=args.only is None)
        if problems:
            print("BENCHMARK REGRESSION vs " + args.baseline, file=sys.stderr)
            for msg in problems:
                print("  " + msg, file=sys.stderr)
            return 1
        gated = sum(1 for n in baseline_rows if "makespan" in n)
        print(f"# baseline gate passed ({gated} makespan rows within "
              f"{100 * args.regress_tolerance:.0f}%)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
