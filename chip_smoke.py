"""Smoke run of the tabular model search on a TPU chip.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # a four-chip host

On one chip it runs three phases, each through the entry points a user
calls, and fails if any of them fails:

1. kernel parity — ``ops.level_split`` as training calls it (the compiled
   Pallas level kernel) against the ``force="ref"`` oracle at HIGGS width
   (2^20 rows x 28 features) and SECOM width (1,567 x 590), bins {64, 256},
   nodes {1, 32}, direct and histogram-subtraction modes; the compiled level
   program must hold the kernel (``tpu_custom_call``); and the in-graph bin
   coarsening equals ``//`` for every 8-bit bin id and factor;
2. HIGGS search — the paper's search space over ``make_higgs_like`` at
   1,000,000 rows x 28 features, all four families, through
   ``SearchSpec`` -> ``Session`` -> ``LocalExecutorPool``;
3. SECOM search — the same space over ``make_secom_like`` at its published
   1,567 x 590, with fused (vmapped) batches.

Every task must finish and be scored; a task that raised fails the run.

With ``--chips 4`` it runs only a HIGGS search twice: on four one-chip
slices (``MeshSliceExecutorPool``), and with one executor on one chip. It
checks that every chip held its own slice's prepared data and ran tasks,
and that both runs score every configuration alike. That search keeps the
full 1,000,000 x 28 rows and every family but one compiled program per
family (``layout_space``): each slice compiles what it runs for its own
chip, so the full grid would compile every program five times.

It exits non-zero with no result line when JAX finds no TPU. Its last line
on success is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``. Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FAMILIES = {"gbdt", "mlp", "forest", "logreg"}
HIGGS_ROWS = 1_000_000
#: budget scale of the paper's search space (1.0 = paper-sized); chosen so
#: the cold one-chip run ends in about ten minutes
SEARCH_SCALE = 0.1
PARITY_ROWS = 1 << 20
#: best validation AUC a HIGGS search must clear (0.5 is chance)
HIGGS_MIN_AUC = 0.75
#: the SECOM-like set is small and 6.6% positive; chance is still 0.5
SECOM_MIN_AUC = 0.6
#: two chips running the same programs on the same data score alike
SCORE_TOL = 1e-5


#: seconds per JAX monitoring duration event (compile time among them)
_DURATIONS: collections.Counter = collections.Counter()


def log(msg: str) -> None:
    print(msg, flush=True)


def phase(name: str, t0: float) -> None:
    spent = _DURATIONS["/jax/core/compile/backend_compile_duration"]
    log(f"phase {name}: {time.perf_counter() - t0:.1f}s "
        f"(backend compile so far {spent:.1f}s)")


# ---------------------------------------------------------------------------
# phase 1: kernel parity
# ---------------------------------------------------------------------------

def _oracle_hist(bins, g, h, node, n_nodes, n_bins):
    """``force="ref"`` histograms, applied in row blocks (histograms add
    over rows) so the oracle's (rows, F, B, 2) one-hot fits the chip, at
    full f32 matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    r, f = bins.shape
    chunk = max(128, min(r, (1 << 29) // (f * n_bins * 8)))
    pad = (-r) % chunk
    pb = jnp.pad(bins, ((0, pad), (0, 0))).reshape(-1, chunk, f)
    pg = jnp.pad(g, (0, pad)).reshape(-1, chunk)
    ph = jnp.pad(h, (0, pad)).reshape(-1, chunk)
    # pad rows sit at node n_nodes: their one-hot row is all zero
    pn = jnp.pad(node, (0, pad), constant_values=n_nodes).reshape(-1, chunk)

    @jax.jit
    def run(pb, pg, ph, pn):
        def step(acc, xs):
            return acc + ref.histogram_ref(*xs, n_nodes, n_bins), None
        with jax.default_matmul_precision("highest"):
            acc0 = jnp.zeros((n_nodes, f, n_bins, 2), jnp.float32)
            return jax.lax.scan(step, acc0, (pb, pg, ph, pn))[0]

    return run(pb, pg, ph, pn)


def holds_kernel(compiled) -> bool:
    """Whether a compiled program runs a Pallas kernel on the chip (and not
    the kernel's interpreter or an XLA fallback)."""
    return "tpu_custom_call" in compiled.as_text()


def kernel_parity(rows: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    scan = dict(lam=1.0, min_child_weight=1.0)
    worst, n_cases = 0.0, 0
    for r, f in ((rows, 28), (1_567, 590)):
        ids = rng.integers(0, 256, (r, f))
        g = jnp.asarray(rng.normal(size=r), jnp.float32)
        h = jnp.asarray(rng.random(r) + 0.05, jnp.float32)
        for b in (64, 256):
            bins = jnp.asarray(ids % b, jnp.int32)
            for n in (1, 32):
                node = jnp.asarray(rng.integers(0, n, r), jnp.int32)
                want = _oracle_hist(bins, g, h, node, n, b)
                want_gain = ref.split_scan_ref(want, n_bins=b, **scan)[0]
                modes = {"direct": None}
                if n > 1:
                    modes["subtract"] = _oracle_hist(bins, g, h, node // 2,
                                                     n // 2, b)
                for mode, parent in modes.items():
                    level = jax.jit(functools.partial(
                        ops.level_split, n_nodes=n, n_bins=b, **scan))
                    compiled = level.lower(bins, g, h, node,
                                           parent_hist=parent).compile()
                    if not holds_kernel(compiled):
                        raise AssertionError(
                            f"F={f} B={b} n={n} {mode}: the level program "
                            "holds no Pallas kernel")
                    out = compiled(bins, g, h, node, parent_hist=parent)
                    err = float(jnp.max(jnp.abs(out[0] - want))
                                / jnp.max(jnp.abs(want)))
                    if not err <= 1e-4:
                        raise AssertionError(
                            f"F={f} B={b} n={n} {mode}: histogram relative "
                            f"error {err:.3g}")
                    np.testing.assert_array_equal(
                        np.isfinite(np.asarray(out[1])),
                        np.isfinite(np.asarray(want_gain)))
                    ref.assert_split_decisions(want, out[1], out[2], out[3],
                                               n_bins=b, **scan)
                    worst = max(worst, err)
                    n_cases += 1
                    log(f"  parity F={f} R={r} B={b} nodes={n} {mode}: "
                        f"hist rel err {err:.3g}, decisions ok")
    return {"cases": n_cases, "worst_hist_rel_err": worst}


def coarse_bins_exact() -> None:
    """The in-graph ``bins // factor`` of GBDT training, with a traced factor
    as training has it, against ``//`` over every 8-bit id and factor."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.tabular.gbdt import _coarse_bins

    ids = jnp.arange(256, dtype=jnp.int32)
    factors = jnp.arange(1, 257, dtype=jnp.int32)
    got = jax.jit(jax.vmap(_coarse_bins, in_axes=(None, 0)))(ids, factors)
    want = np.arange(256)[None, :] // np.arange(1, 257)[:, None]
    np.testing.assert_array_equal(np.asarray(got), want)
    log("  coarse bins: 256 ids x 256 factors equal //")


# ---------------------------------------------------------------------------
# phases 2-3: searches
# ---------------------------------------------------------------------------

def layout_space():
    """The paper's space cut to one compiled program per family: GBDT,
    forest and MLP keep the configs at one value of each static (compiled)
    hyperparameter, all logreg configs stay."""
    from repro.core.grid import SearchSpace
    from repro.launch.search import paper_search_space

    spaces = paper_search_space(SEARCH_SCALE)
    keep = {"gbdt": {"max_bin": 64, "max_depth": 4,
                     "round": spaces[0].configs[0]["round"]},
            "forest": {"max_depth": 6,
                       "n_estimators": spaces[2].configs[0]["n_estimators"]},
            "mlp": {"network": "64_64",
                    "steps": spaces[1].configs[0]["steps"]}}
    return [SearchSpace(s.estimator, tuple(
        c for c in s.configs
        if all(c[k] == v for k, v in keep.get(s.estimator, {}).items())))
        for s in spaces]


def run_search(data, *, spaces, fuse: bool, n_executors: int = 1,
               backend=None, min_auc: float, label: str):
    """``spaces`` over ``data`` through SearchSpec -> Session; returns
    ``{config key: validation AUC}``, the best key and the results."""
    import numpy as np

    import repro.tabular  # noqa: F401  (registers the four estimators)
    from repro.core import AnalyticProfiler, SearchSpec, Session

    train, valid, _ = data.split((0.6, 0.2, 0.2), seed=0)
    train, mu, sd = train.standardize()
    valid, _, _ = valid.standardize(mu, sd)
    spec = SearchSpec(spaces=spaces, n_executors=n_executors,
                      policy="lpt", profiler=AnalyticProfiler(), metric="auc",
                      seed=0, fuse=fuse)
    session = Session(spec, backend=backend)
    t0 = time.perf_counter()
    results = []
    for r in session.results(train, valid):
        results.append(r)
        log(f"    {time.perf_counter() - t0:7.1f}s exec {r.executor_id} "
            f"{r.task.key()}: " + (
                f"train {r.train_seconds:.2f}s conv {r.convert_seconds:.2f}s "
                f"eval {r.eval_seconds:.2f}s auc {r.score}" if r.ok
                else f"ERROR {r.error}"))
    seconds = time.perf_counter() - t0
    failed = [r for r in results if not r.ok]
    if failed:
        raise AssertionError(f"{label}: {len(failed)} tasks failed, first "
                             f"{failed[0].task.key()}: {failed[0].error}")
    if len(results) != spec.n_grid_tasks:
        raise AssertionError(f"{label}: {len(results)} results for "
                             f"{spec.n_grid_tasks} configurations")
    scores = {r.task.key(): r.score for r in results}
    unscored = [k for k, s in scores.items()
                if s is None or not np.isfinite(s)]
    if unscored:
        raise AssertionError(f"{label}: unscored results {unscored[:4]}")
    families = {r.task.estimator for r in results}
    if families != FAMILIES:
        raise AssertionError(f"{label}: families {sorted(families)}")
    best = max(scores, key=scores.get)
    if not scores[best] >= min_auc:
        raise AssertionError(f"{label}: best validation AUC "
                             f"{scores[best]:.4f} < {min_auc}")
    by_family: dict[str, float] = {}
    for r in results:
        fam = r.task.estimator
        by_family[fam] = max(by_family.get(fam, -1.0), r.score)
    st = session.stats
    log(f"  {label}: {len(results)} configs in {seconds:.1f}s, best {best} "
        f"auc={scores[best]:.4f}; best per family "
        + " ".join(f"{k}={v:.4f}" for k, v in sorted(by_family.items()))
        + f"; convert={st.convert_seconds_total:.1f}s "
        f"eval={st.eval_seconds_total:.1f}s "
        f"train={sum(r.train_seconds for r in results):.1f}s")
    return scores, best, results


def four_chip_layout(rows: int) -> None:
    """HIGGS on four one-chip slices vs one executor on one chip."""
    import jax
    import numpy as np

    from repro.core import MeshSliceExecutorPool
    from repro.data.synthetic import make_higgs_like

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs four devices, JAX sees "
                             f"{len(devices)}")
    data = make_higgs_like(rows, seed=0)
    spaces = layout_space()
    t0 = time.perf_counter()
    one, best_one, _ = run_search(data, spaces=spaces, fuse=False,
                                  min_auc=HIGGS_MIN_AUC,
                                  label="HIGGS, one executor on chip 0")
    phase("one_chip_search", t0)
    t0 = time.perf_counter()
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    pool = MeshSliceExecutorPool(mesh, n_slices=4)
    four, best_four, results = run_search(
        data, spaces=spaces, fuse=False, n_executors=4, backend=pool,
        min_auc=HIGGS_MIN_AUC, label="HIGGS, four one-chip slices")
    phase("four_slice_search", t0)
    ran = collections.Counter(r.executor_id for r in results)
    held: dict[int, set] = collections.defaultdict(set)
    for key, payload in pool.prepared_cache.items():
        idx = key[2][2]
        for leaf in jax.tree.leaves(payload):
            if isinstance(leaf, jax.Array):
                held[idx] |= set(leaf.devices())
    for i, dev in enumerate(devices):
        log(f"  slice {i} on {dev}: {ran[i]} tasks, prepared data on "
            f"{sorted(str(d) for d in held[i])}")
        if ran[i] == 0:
            raise AssertionError(f"slice {i} ran no task")
        if held[i] != {dev}:
            raise AssertionError(f"slice {i}'s prepared data is on "
                                 f"{held[i]}, not on {dev}")
    if set(one) != set(four):
        raise AssertionError("the two layouts searched different configs")
    gap = max(abs(one[k] - four[k]) for k in one)
    log(f"  best one-chip {best_one}, best four-slice {best_four}, "
        f"largest score difference {gap:.3g}")
    if best_one != best_four or gap > SCORE_TOL:
        raise AssertionError("the four-slice search disagrees with the "
                             "one-chip search")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    log(f"jax {jax.__version__}; device {dev.platform} "
        f"kind={dev.device_kind!r} count={len(jax.devices())}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke run needs the chip",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **_: events.update([name]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: _DURATIONS.update({name: secs}))

    t_all = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_layout(HIGGS_ROWS)
        else:
            from repro.data.synthetic import make_higgs_like, make_secom_like
            from repro.launch.search import paper_search_space

            t0 = time.perf_counter()
            parity = kernel_parity(PARITY_ROWS)
            log(f"  {parity['cases']} cases, worst histogram relative "
                f"error {parity['worst_hist_rel_err']:.3g}")
            coarse_bins_exact()
            phase("kernel_parity", t0)
            t0 = time.perf_counter()
            run_search(make_higgs_like(HIGGS_ROWS, seed=0),
                       spaces=paper_search_space(SEARCH_SCALE),
                       fuse=False, min_auc=HIGGS_MIN_AUC,
                       label=f"HIGGS {HIGGS_ROWS}x28")
            phase("higgs_search", t0)
            t0 = time.perf_counter()
            run_search(make_secom_like(seed=0),
                       spaces=paper_search_space(SEARCH_SCALE), fuse=True,
                       min_auc=SECOM_MIN_AUC, label="SECOM 1567x590 fused")
            phase("secom_search", t0)
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    hits = events["/jax/compilation_cache/cache_hits"]
    asked = events["/jax/compilation_cache/compile_requests_use_cache"]
    log(f"total: {time.perf_counter() - t_all:.1f}s; persistent compile "
        f"cache: {hits} hits of {asked} lookups")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
