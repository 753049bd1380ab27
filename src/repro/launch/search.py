"""Model-search launcher — the paper's workload, end to end.

Two workloads:

  * ``--workload tabular`` (the paper's evaluation): grid over the paper's
    four algorithms (GBDT / MLP / RF / LogReg, all pure-JAX) on a synthetic
    HIGGS- or SECOM-like dataset, with profile-based (or baseline)
    scheduling over N thread executors. Prints per-policy makespans and the
    best model under the chosen metric. Built as a declarative
    ``SearchSpec`` run by a ``Session`` (DESIGN.md §2) — results stream as
    tasks finish, ``--wal`` makes the run resumable, and ``--max-seconds`` /
    ``--max-tasks`` / ``--target-metric`` early-stop it mid-stream.

  * ``--workload lm`` (the TPU-native adaptation): the search space is LM
    architectures × hyperparameters; executors are MESH SLICES — each task
    trains its config for a few steps on its slice (DP×TP inside the slice).
    Profiling uses the ANALYTIC roofline profiler (cost ≈ one eval_shape,
    the paper's sampling profiler made free — DESIGN.md §2).
"""
from __future__ import annotations

import argparse
import sys
import time

import repro.tabular  # noqa: F401  (registers the four estimators)
from repro import configs
from repro.core import (
    AnalyticProfiler,
    GridBuilder,
    MeshSliceExecutorPool,
    SamplingProfiler,
    SearchSpec,
    Session,
    TrainTask,
    schedule,
)
from repro.data.pipeline import make_lm_stream
from repro.data.synthetic import make_higgs_like, make_secom_like
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import count_params
from repro.train import Trainer, make_optimizer


def paper_search_space(scale: float = 1.0):
    """The paper's §V-A grid, structurally faithful (scaled for CPU time)."""
    r = lambda n: max(1, int(round(n * scale)))  # noqa: E731
    gbdt = (GridBuilder("gbdt")
            .add_grid("eta", [0.1, 0.3, 0.9])
            .add_grid("round", [r(30), r(60), r(90)])
            .add_grid("max_bin", [32, 64, 128])
            .add_grid("max_depth", [4, 6])
            .build())
    mlp = (GridBuilder("mlp")
           .add_grid("network", ["128_128", "64_64", "128_64", "64_64_64"])
           .add_grid("learning_rate", [0.003, 0.03, 0.3])
           .add_grid("steps", [r(200), r(400)])
           .build())
    forest = (GridBuilder("forest")
              .add_grid("n_estimators", [r(50), r(100)])
              .add_grid("max_depth", [6, 8, 10])
              .build())
    logreg = (GridBuilder("logreg")
              .add_grid("c", [0.011, 0.033, 0.1, 0.3, 0.9])
              .build())
    return [gbdt, mlp, forest, logreg]


def _parse_tuner_args(pairs) -> dict:
    """``--tuner-arg k=v`` values: int, then float, then bare string."""
    out = {}
    for pair in pairs or ():
        if "=" not in pair:
            raise SystemExit(f"--tuner-arg wants k=v, got {pair!r}")
        k, v = pair.split("=", 1)
        for conv in (int, float):
            try:
                v = conv(v)
                break
            except ValueError:
                continue
        out[k] = v
    return out


def run_tabular(args) -> int:
    data = (make_higgs_like(args.rows, seed=0) if args.dataset == "higgs"
            else make_secom_like(seed=0))
    train, valid, test = data.split((0.6, 0.2, 0.2), seed=0)
    train, mu, sd = train.standardize()
    valid, _, _ = valid.standardize(mu, sd)
    test, _, _ = test.standardize(mu, sd)

    spec = SearchSpec(
        spaces=paper_search_space(args.scale),
        n_executors=args.executors,
        policy=args.policy,
        profiler=(SamplingProfiler(args.sample_rate) if args.profiler == "sampling"
                  else AnalyticProfiler()),
        tuner=args.tuner,
        tuner_args=(_parse_tuner_args(args.tuner_arg)
                    if args.tuner is not None else None),
        metric=args.metric,
        seed=0,
        wal_path=args.wal,
        max_seconds=args.max_seconds,
        max_tasks=args.max_tasks,
        target_metric=args.target_metric,
        cost_model_path=args.cost_model,
        replan_threshold=args.replan_threshold,
        fuse=args.fuse,
        max_fuse=args.max_fuse,
        max_task_retries=args.max_task_retries,
        deadline_factor=args.deadline_factor,
        n_shards=args.shards,
    )
    print(f"search space: {spec.n_grid_tasks} configurations over "
          f"{[s.estimator for s in spec.spaces]}")
    if args.resume:
        # budgets passed alongside --resume apply to THIS invocation too
        keep = any(v is not None for v in
                   (args.max_seconds, args.max_tasks, args.target_metric))
        session = Session.resume(args.wal, spec, keep_budgets=keep)
    else:
        session = Session(spec)
    t0 = time.perf_counter()
    done = 0
    for r in session.results(train, valid):
        done += 1
        if args.verbose and r.ok:
            # full per-task cost breakdown (§3.3/§3.4): train + convert +
            # executor-side eval, the fused batch it rode in, and the score
            # it streamed back with — no driver-side re-predicting
            extras = f"{r.train_seconds:.2f}s train"
            if r.convert_seconds:
                extras += f" +{r.convert_seconds:.2f}s conv"
            if r.eval_seconds:
                extras += f" +{r.eval_seconds:.3f}s eval"
            if r.batch_size > 1:
                extras += f", batch={r.batch_size}"
            if r.score is not None:
                extras += f", {args.metric}={r.score:.4f}"
            print(f"  [{done}/{spec.n_grid_tasks}] exec {r.executor_id}: "
                  f"{r.task.key()} ({extras})")
    multi = session.multi_model()
    # a task that raised is a failed search, not a smaller one
    for r in multi.failures:
        print(f"FAILED {r.task.key()}: {r.error}", file=sys.stderr)
    if not len(multi):
        if multi.failures:
            return 1
        print("nothing left to search (WAL already complete?)")
        return 0
    best = multi.best(valid, metric=args.metric)
    test_score = None
    for r in multi.results:
        if r.task.task_id == best.task.task_id:
            from repro.core import METRICS
            test_score = METRICS[args.metric](test.y, r.model.predict_proba(test.x))
    stopped = f" stop={session.stop_reason}" if session.stop_reason else ""
    feedback = ""
    if session.cost_model is not None:
        feedback = (f" replans={session.stats.n_replans} "
                    f"model_estimates={session.stats.n_model_estimates} "
                    f"profiled={session.stats.n_profiled} "
                    f"cost_model={session.cost_model.path or '<memory>'}")
    st = session.stats
    fused = ""
    if spec.fuse:
        fused = (f" fused_batches={st.n_fused_batches}"
                 f" fused_tasks={st.n_fused_tasks}"
                 f" compile_cache={st.compile_cache_hits}h/"
                 f"{st.compile_cache_misses}m")
    prepared = (f" prepared_cache={st.prepared_cache_hits}h/"
                f"{st.prepared_cache_misses}m"
                f" convert={st.convert_seconds_total:.2f}s")
    evald = (f" eval={st.eval_seconds_total:.2f}s"
             f" predict_cache={st.predict_compile_cache_hits}h/"
             f"{st.predict_compile_cache_misses}m")
    sharded = ""
    if spec.n_shards > 1:
        sharded = (f" shards={spec.n_shards}"
                   f" shard_residency={st.shard_residency_bytes}B")
    print(f"policy={args.policy} total={time.perf_counter() - t0:.1f}s "
          f"profiling_ratio={st.profiling_ratio:.1%} "
          f"failures={st.n_failures}{stopped}{feedback}{fused}{prepared}"
          f"{evald}{sharded}")
    print(f"best: {best.task.key()}  valid {args.metric}={best.score:.4f} "
          f"test {args.metric}={test_score:.4f} "
          f"(train {best.train_seconds:.2f}s + conv {best.convert_seconds:.2f}s "
          f"+ eval {best.eval_seconds:.3f}s, batch={best.batch_size})")
    return 1 if multi.failures else 0


def run_lm(args) -> int:
    """LM search on mesh-slice executors (smoke scale on CPU)."""
    mesh = make_test_mesh(data=args.slices, model=args.model_par)
    spaces = []
    for arch in (args.archs.split(",") if args.archs else
                 ["qwen2_1_5b", "tinyllama_1_1b", "gemma_2b"]):
        spaces.append(
            GridBuilder(arch).add_grid("lr", [1e-3, 3e-3]).build()
        )
    tasks = []
    tid = 0
    for s in spaces:
        for cfg_params in s.configs:
            tasks.append(TrainTask(task_id=tid, estimator=s.estimator,
                                   params=dict(cfg_params)))
            tid += 1
    # analytic profile: modelled step cost ∝ active params (roofline §2)
    costs = {}
    for t in tasks:
        cfg = configs.get_smoke_config(t.estimator)
        costs[t.task_id] = count_params(cfg) * args.steps
    tasks = [t.with_cost(costs[t.task_id]) for t in tasks]
    assignment = schedule(tasks, args.slices, policy=args.policy)
    print(f"{len(tasks)} LM tasks over {args.slices} mesh slices "
          f"(estimated makespan {assignment.estimated_makespan:.2e} units)")

    def task_runner(task: TrainTask, slice_mesh, _data):
        cfg = configs.get_smoke_config(task.estimator)
        stream = make_lm_stream(slice_mesh, batch=4, seq_len=32, vocab=cfg.vocab)
        tr = Trainer(cfg, make_optimizer("adamw", lr=task.params["lr"]),
                     slice_mesh, stream)
        t0 = time.perf_counter()
        m = tr.run(args.steps)
        stream.close()
        return m.history[-1]["loss"], time.perf_counter() - t0

    pool = MeshSliceExecutorPool(mesh, args.slices, task_runner)
    results = []
    for r in pool.submit(assignment, None):     # streams slice by slice
        status = f"loss={r.model:.4f}" if r.ok else f"ERROR {r.error}"
        print(f"  slice {r.executor_id}: {r.task.key():40s} {status}")
        results.append(r)
    best = min((r for r in results if r.ok), default=None,
               key=lambda r: r.model)
    if best is not None:
        print(f"best after {args.steps} steps: {best.task.key()} "
              f"loss={best.model:.4f}")
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", default="tabular", choices=("tabular", "lm"))
    p.add_argument("--dataset", default="higgs", choices=("higgs", "secom"))
    p.add_argument("--rows", type=int, default=8000)
    p.add_argument("--executors", type=int, default=4)
    p.add_argument("--policy", default="lpt",
                   choices=("lpt", "random", "round_robin", "dynamic", "lpt_dynamic"))
    p.add_argument("--profiler", default="sampling", choices=("sampling", "analytic"))
    p.add_argument("--sample-rate", type=float, default=0.03)
    p.add_argument("--tuner", default=None,
                   choices=("grid", "random", "asha", "surrogate"),
                   help="search strategy over the declared spaces "
                        "(default: exhaustive grid). 'asha' runs adaptive "
                        "successive halving on the streaming eval plane "
                        "(DESIGN.md §3.6)")
    p.add_argument("--tuner-arg", action="append", metavar="K=V",
                   help="tuner kwarg, repeatable — e.g. --tuner asha "
                        "--tuner-arg base_budget=10 --tuner-arg "
                        "max_budget=270 --tuner-arg eta=3")
    p.add_argument("--metric", default="auc")
    p.add_argument("--scale", type=float, default=0.3,
                   help="search-space budget scale (1.0 = paper-sized)")
    p.add_argument("--wal", default=None, help="WAL path for restartable search")
    p.add_argument("--resume", action="store_true",
                   help="resume a search whose WAL is at --wal")
    p.add_argument("--cost-model", default=None, metavar="PATH",
                   help="persistent CostModel JSON: observed runtimes feed a "
                        "learned profiler that replaces sampling once warm "
                        "(defaults to <wal>.cost.json when --replan-threshold "
                        "is set alongside --wal)")
    p.add_argument("--replan-threshold", type=float, default=None, metavar="DRIFT",
                   help="re-run rebalance mid-round when mean |log(observed/"
                        "estimated)| exceeds this (0.69 ≈ runtimes 2x off)")
    p.add_argument("--fuse", action="store_true",
                   help="pack same-family configs into vmap-fused batches "
                        "that train as one device program (DESIGN.md §3.2)")
    p.add_argument("--max-fuse", type=int, default=16, metavar="N",
                   help="largest fused batch (configs per program, default 16)")
    p.add_argument("--shards", type=int, default=1, metavar="N",
                   help="row-shard the prepared data N ways (DESIGN.md "
                        "§3.9): per-shard GBDT histograms combined with "
                        "one psum, data-parallel grads for logreg/mlp, "
                        "partial-sum eval — per-device residency drops to "
                        "~1/N of a full copy (default 1 = replicated)")
    p.add_argument("--max-task-retries", type=int, default=0, metavar="N",
                   help="re-run a task whose train raises up to N times "
                        "(capped exponential backoff) before it surfaces "
                        "as a terminal error (DESIGN.md \u00a73.7)")
    p.add_argument("--deadline-factor", type=float, default=None, metavar="F",
                   help="soft deadline: a task in flight longer than F \u00d7 "
                        "its CostModel-predicted cost is speculatively "
                        "duplicated on an idle executor; first completion "
                        "wins (DESIGN.md \u00a73.7)")
    p.add_argument("--max-seconds", type=float, default=None,
                   help="early-stop budget: wall-clock seconds")
    p.add_argument("--max-tasks", type=int, default=None,
                   help="early-stop budget: trained-task count")
    p.add_argument("--target-metric", type=float, default=None,
                   help="early-stop as soon as a model reaches this score")
    p.add_argument("--verbose", action="store_true",
                   help="print each task result as it streams in")
    # lm workload
    p.add_argument("--slices", type=int, default=2)
    p.add_argument("--model-par", type=int, default=1)
    p.add_argument("--archs", default=None)
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()
    if args.resume and not args.wal:
        p.error("--resume requires --wal")
    if args.tuner_arg and not args.tuner:
        p.error("--tuner-arg requires --tuner")
    enable_compile_cache()
    return run_tabular(args) if args.workload == "tabular" else run_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
