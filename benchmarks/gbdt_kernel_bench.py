"""Fused GBDT level kernel + histogram subtraction benchmarks (§3.8).

Two layers, matching the repo's smoke conventions:

* **Deterministic rows** (baseline-safe 0/1 flags): interpret-mode fused
  kernel vs the jnp oracle (split decisions under the near-tie contract of
  ``ref.assert_split_decisions``), integer-stat subtraction
  bit-equality, and a build_tree subtract-vs-direct bitwise pin — the same
  invariants tests/test_kernels.py proves, sampled here so a bench run on a
  real pod re-checks them against the COMPILED kernel, not just interpret.

* **Wall-clock rows** (``*.wallclock.*`` — excluded from the baseline):
  the ISSUE 9 acceptance gates, enforced IN-BENCH (RuntimeError on miss):
  histogram subtraction must cut the jitted per-tree level loop by >= 1.5x
  at the smoke shape, and the histogram phase alone by >= 1.3x at depth >= 4
  (n_nodes = 16). Timings are medians over ``_REPS`` post-warmup runs.
"""
from __future__ import annotations

import functools
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

Row = tuple[str, float, str]

#: smoke workload: higgs-like width at the default max_bin/depth grid point,
#: rows sized so the level loop is histogram-dominated (the training regime)
_R, _F, _B, _DEPTH = 24_000, 28, 64, 6
_REPS = 5
_LEVEL_LOOP_GATE = 1.5          # subtract vs direct, full build_tree
_HIST_PHASE_GATE = 1.3          # subtract vs direct, histogram phase only


def _workload(r=_R, f=_F, nb=_B, seed=0):
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 2, size=r), jnp.float32)
    p = jax.nn.sigmoid(jnp.asarray(rng.normal(size=r), jnp.float32))
    g, h = p - y, jnp.maximum(p * (1 - p), 1e-16)
    node = jnp.asarray(rng.integers(0, 16, size=r), jnp.int32)
    return bins, g, h, node


def _paired_times(slow_fn, fast_fn) -> tuple[float, float, float]:
    """(median_slow, median_fast, median per-rep ratio). The two sides are
    timed ALTERNATELY inside one window so background-load drift (e.g. the
    allocator still churning after a previous bench) hits both equally —
    a sequential A-then-B measurement can swing the ratio by 30%+ on a
    shared CI box."""
    slow_fn(), fast_fn()                        # compile + warm caches
    slows, fasts = [], []
    for _ in range(_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(slow_fn())
        slows.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fast_fn())
        fasts.append(time.perf_counter() - t0)
    ratio = statistics.median(s / f for s, f in zip(slows, fasts))
    return statistics.median(slows), statistics.median(fasts), ratio


# --------------------------------------------------------------------------
# Deterministic parity flags.
# --------------------------------------------------------------------------

def _parity_rows(tag: str) -> list[Row]:
    from repro.kernels import ops, ref

    rows: list[Row] = []
    rng = np.random.default_rng(1)
    r, f, nb, nn = 600, 9, 32, 8
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = jnp.asarray(rng.normal(size=r), jnp.float32)
    h = jnp.asarray(rng.random(r) + 0.1, jnp.float32)
    node = jnp.asarray(rng.integers(0, nn, size=r), jnp.int32)
    kw = dict(n_nodes=nn, n_bins=nb, lam=1.0, min_child_weight=1.0)
    _, bg_k, bf_k, bs_k = ops.level_split(bins, g, h, node, force="kernel", **kw)
    want = ref.histogram_ref(bins, g, h, node, nn, nb)
    try:
        ref.assert_split_decisions(want, bg_k, bf_k, bs_k, n_bins=nb,
                                   lam=1.0, min_child_weight=1.0)
        ok = True
    except AssertionError:
        ok = False
    rows.append((f"{tag}.fused_parity_ok", float(ok),
                 "fused kernel split decisions meet the oracle's near-tie "
                 "contract (R=600 F=9 B=32)"))

    gi = jnp.asarray(rng.integers(-8, 9, size=r), jnp.float32)
    hi = jnp.asarray(rng.integers(1, 5, size=r), jnp.float32)
    parent = ops._histogram_scatter(bins, gi, hi, node // 2, nn // 2, nb)
    hd, _, _, _ = ops.level_split(bins, gi, hi, node, **kw)
    hs, _, _, _ = ops.level_split(bins, gi, hi, node, parent_hist=parent, **kw)
    exact = bool((np.asarray(hd) == np.asarray(hs)).all())
    rows.append((f"{tag}.subtract_bit_exact_ok", float(exact),
                 "integer-stat subtraction histogram bitwise == direct build"))

    from repro.tabular.gbdt import build_tree

    bins2, g2, h2, _ = _workload(r=1200, f=6, nb=64, seed=2)
    run = lambda sub: jax.jit(functools.partial(  # noqa: E731
        build_tree, n_bins=64, max_depth=4, lam=1.0, gamma=0.0,
        min_child_weight=1.0, subtract=sub))(bins2, g2, h2)
    same = all(bool((np.asarray(a) == np.asarray(b)).all())
               for a, b in zip(run(True), run(False)))
    rows.append((f"{tag}.decision_parity_ok", float(same),
                 "build_tree subtract=True bitwise == subtract=False (depth 4)"))
    return rows


# --------------------------------------------------------------------------
# Wall-clock acceptance gates (raise on miss — never baseline-compared).
# --------------------------------------------------------------------------

def _level_loop_rows(tag: str) -> list[Row]:
    from repro.tabular.gbdt import build_tree

    bins, g, h, _ = _workload()
    runner = lambda sub: jax.jit(functools.partial(  # noqa: E731
        build_tree, n_bins=_B, max_depth=_DEPTH, lam=1.0, gamma=0.0,
        min_child_weight=1.0, subtract=sub))
    direct, subtract = runner(False), runner(True)
    t_direct, t_sub, speedup = _paired_times(
        lambda: direct(bins, g, h), lambda: subtract(bins, g, h))
    if speedup < _LEVEL_LOOP_GATE:
        raise RuntimeError(
            f"level-loop speedup {speedup:.2f}x < {_LEVEL_LOOP_GATE}x gate "
            f"(direct {t_direct * 1e3:.1f}ms vs subtract {t_sub * 1e3:.1f}ms, "
            f"R={_R} F={_F} B={_B} depth={_DEPTH})")
    return [
        (f"{tag}.wallclock.level_loop_direct_s", t_direct,
         f"jitted build_tree subtract=False, R={_R} F={_F} B={_B} D={_DEPTH}"),
        (f"{tag}.wallclock.level_loop_subtract_s", t_sub,
         "same tree build with histogram subtraction (the training default)"),
        (f"{tag}.wallclock.level_loop_speedup_x", speedup,
         f"acceptance: >= {_LEVEL_LOOP_GATE}x (raises in-bench below gate)"),
    ]


def _hist_phase_rows(tag: str) -> list[Row]:
    from repro.kernels import ops

    bins, g, h, node = _workload()               # node in [0, 16): depth 4+
    nn = 16
    kw = dict(n_nodes=nn, n_bins=_B, lam=1.0, min_child_weight=1.0)
    parent = ops._histogram_scatter(bins, g, h, node // 2, nn // 2, _B)
    direct = jax.jit(lambda: ops.level_split(bins, g, h, node, **kw))
    subtract = jax.jit(
        lambda: ops.level_split(bins, g, h, node, parent_hist=parent, **kw))
    t_direct, t_sub, speedup = _paired_times(direct, subtract)
    if speedup < _HIST_PHASE_GATE:
        raise RuntimeError(
            f"histogram-phase speedup {speedup:.2f}x < {_HIST_PHASE_GATE}x "
            f"gate at n_nodes={nn} (direct {t_direct * 1e3:.1f}ms vs "
            f"subtract {t_sub * 1e3:.1f}ms)")
    return [
        (f"{tag}.wallclock.hist_phase_direct_s", t_direct,
         f"level_split without parent hist, n_nodes={nn} (depth-4 level)"),
        (f"{tag}.wallclock.hist_phase_subtract_s", t_sub,
         "same level via smaller-child build + parent subtraction"),
        (f"{tag}.wallclock.hist_phase_speedup_x", speedup,
         f"acceptance: >= {_HIST_PHASE_GATE}x at depth >= 4 (raises below)"),
    ]


def smoke() -> list[Row]:
    """CI-gated rows: parity flags + the two in-bench speedup gates."""
    tag = "gbdt_kernel.smoke"
    return _parity_rows(tag) + _level_loop_rows(tag) + _hist_phase_rows(tag)


def full() -> list[Row]:
    """Smoke set plus a depth sweep showing where subtraction pays."""
    from repro.tabular.gbdt import build_tree

    rows = smoke()
    bins, g, h, _ = _workload()
    for depth in (3, 5, 7):
        runner = lambda sub: jax.jit(functools.partial(  # noqa: E731
            build_tree, n_bins=_B, max_depth=depth, lam=1.0, gamma=0.0,
            min_child_weight=1.0, subtract=sub))
        d_fn, s_fn = runner(False), runner(True)
        _, _, ratio = _paired_times(lambda: d_fn(bins, g, h),
                                    lambda: s_fn(bins, g, h))
        rows.append((f"gbdt_kernel.full.wallclock.depth{depth}_speedup_x",
                     ratio,
                     f"build_tree direct/subtract at depth {depth} "
                     f"(deeper trees amortize the level-0 full build more)"))
    return rows
