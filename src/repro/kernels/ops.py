"""Dispatching wrappers: Pallas kernel on TPU, pure-jnp path elsewhere.

Models and estimators call ``ops.*`` only — never a kernel or ref directly —
so the same model code runs on this CPU container (XLA path, used by the
dry-run: Mosaic kernels are TPU-only custom calls) and on a real pod (Pallas
path). ``force`` overrides dispatch for tests:

    force="kernel"    Pallas in interpret mode (CPU-executable kernel body)
    force="ref"       pure-jnp oracle
    force=None        backend-based: TPU → compiled kernel, else jnp
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

__all__ = ["attention", "decode_attention", "rglru", "rwkv6", "histogram",
           "level_split", "level_rows"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(
    q, k, v, *, causal=True, window=None, scale=None, logit_softcap=None,
    block_q=256, block_k=256, force=None, matmul_dtype="float32",
):
    """Multi-head attention (GQA via head-count ratio). See ``attention_ref``."""
    use_kernel = force == "kernel" or (force is None and _on_tpu())
    tq, tk = q.shape[2], k.shape[2]
    if use_kernel and tq % min(block_q, tq) == 0 and tk % min(block_k, tk) == 0:
        from repro.kernels.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
            interpret=not _on_tpu(),
        )
    if force is None and tq > 2048:
        # XLA path for long sequences: unrolled q-blocks, statically sliced
        # KV ranges — flash-equivalent memory, exact cost_analysis FLOPs
        return _ref.attention_xla_blocked(
            q, k, v, causal=causal, window=window, scale=scale,
            logit_softcap=logit_softcap, matmul_dtype=matmul_dtype,
        )
    return _ref.attention_ref(
        q, k, v, causal=causal, window=window, scale=scale,
        logit_softcap=logit_softcap, matmul_dtype=matmul_dtype,
    )


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None, scale=None,
                     logit_softcap=None, force=None, matmul_dtype="float32"):
    """Single-token decode over a KV cache. XLA path on both backends: the
    decode hot loop is HBM-bandwidth-bound (one pass over the cache) and XLA
    already emits a single fused pass; a Pallas kernel would add nothing
    (measured in EXPERIMENTS.md §Perf notes)."""
    del force
    return _ref.decode_attention_ref(
        q, k_cache, v_cache, cache_len, window=window, scale=scale,
        logit_softcap=logit_softcap, matmul_dtype=matmul_dtype,
    )


def rglru(x, input_gate, rec_gate, a_param, h0=None, *, c=8.0, force=None):
    use_kernel = force == "kernel" or (force is None and _on_tpu())
    t, d = x.shape[1], x.shape[2]
    if use_kernel and t % 8 == 0 and d % 128 == 0:
        from repro.kernels.rglru import rglru_tpu

        return rglru_tpu(
            x, input_gate, rec_gate, a_param, h0,
            c=c, block_t=min(256, t), block_d=min(256, d),
            interpret=not _on_tpu(),
        )
    return _ref.rglru_ref(x, input_gate, rec_gate, a_param, h0, c=c)


def rwkv6(r, k, v, w, u, s0=None, *, chunk=64, force=None):
    use_kernel = force == "kernel" or (force is None and _on_tpu())
    t = r.shape[2]
    if use_kernel and t % min(chunk, t) == 0:
        from repro.kernels.rwkv6 import rwkv6_tpu

        return rwkv6_tpu(r, k, v, w, u, s0, chunk=min(chunk, t), interpret=not _on_tpu())
    return _ref.rwkv6_ref(r, k, v, w, u, s0)


def _use_level_kernel(force, n_bins: int) -> bool:
    """Whether a GBDT level (or histogram) runs the Pallas kernel:
    ``force="kernel"`` always (interpret mode off the chip), and by default
    on TPU — except above ``MAX_KERNEL_BINS`` bins, whose ids the kernel's
    bf16 transport cannot hold exactly; those take the XLA path."""
    from repro.kernels.histogram import MAX_KERNEL_BINS

    if force == "kernel":
        return True
    return force is None and _on_tpu() and n_bins <= MAX_KERNEL_BINS


def _histogram_scatter(bins, grad, hess, node, n_nodes, n_bins):
    """XLA path: scatter-add formulation — O(R·F) adds, fast on CPU."""
    r, f = bins.shape
    flat = (node[:, None] * f + jnp.arange(f)[None, :]) * n_bins + bins  # (R, F)
    def acc(vals):
        return (
            jnp.zeros((n_nodes * f * n_bins,), jnp.float32)
            .at[flat]
            .add(jnp.broadcast_to(vals[:, None].astype(jnp.float32), (r, f)))
            .reshape(n_nodes, f, n_bins)
        )
    return jnp.stack([acc(grad), acc(hess)], axis=-1)


def histogram(bins, grad, hess, node, *, n_nodes, n_bins, force=None):
    """GBDT grad/hess histograms. See ``histogram_ref``.

    Training no longer calls this directly — ``build_tree`` routes through
    :func:`level_split`, which fuses the split scan in (and threads its own
    ``force``); this stays the standalone histogram entry point for tests
    and the histogram smoke bench.
    """
    if force == "ref":
        return _ref.histogram_ref(bins, grad, hess, node, n_nodes, n_bins)
    if _use_level_kernel(force, n_bins):
        from repro.kernels.histogram import histogram_tpu

        return histogram_tpu(
            bins, grad, hess, node, n_nodes=n_nodes, n_bins=n_bins,
            interpret=not _on_tpu(),
        )
    return _histogram_scatter(bins, grad, hess, node, n_nodes, n_bins)


def _row_cumsum(x, block: int = 1024):
    """Inclusive int32 prefix sum over a long row vector, in two levels
    (within blocks, then over block totals). The TPU compiler lowers a flat
    ``cumsum`` to one reduce-window as long as the vector and takes over ten
    seconds per call site at a million rows; two short windows compile at
    once. Integer sums, so the result equals ``jnp.cumsum`` exactly."""
    n = x.shape[0]
    blocks = jnp.pad(x.astype(jnp.int32), (0, (-n) % block)).reshape(-1, block)
    inner = jnp.cumsum(blocks, axis=1)
    carry = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    return (inner + carry[:, None]).reshape(-1)[:n]


#: one-hot width (features × bins) at or above which a level below the root
#: gathers its smaller children's rows before the level kernel reads them
#: (:func:`level_rows`). The kernel's work per row grows with this width, a
#: row gather's does not. Timed on a v5e, one level by gathered rows against
#: all rows (ms): 600,000 rows × 28 features at 896–3,584 lanes, 34–37
#: against 2.4–6.1; fused batches of 16 × 940 rows × 590 features at 18,880
#: lanes, 1.5–3.7 against 1.4–3.9, at 37,760 and 75,520 lanes, 2.3–7.6
#: against 2.5–9.0 (PERF.md, "Where the time goes")
COMPACT_MIN_LANES = 16_384


def level_rows(n_features: int, n_bins: int, force=None) -> str:
    """Which rows a tree level below the root feeds its histogram build
    under histogram subtraction (DESIGN.md §3.8): ``"half"`` — the smaller
    children's rows gathered into ``floor(R/2)`` slots — or ``"all"`` —
    every row where it lies, the larger children's under a dump node id.

    Both forms accumulate the same rows in the same order; they differ in
    cost. Gathering rows pays where the per-row histogram work is large: in
    the XLA scatter, whose updates it halves, and in the level kernel at a
    one-hot width of :data:`COMPACT_MIN_LANES` lanes or more. Narrower, the
    kernel reads a row faster than the chip gathers one, so it reads them
    all. Chosen from shapes alone, per fit."""
    if _use_level_kernel(force, n_bins) and (
            n_features * n_bins < COMPACT_MIN_LANES):
        return "all"
    return "half"


def _plan_smaller_child(node, n_nodes, *, compact: bool):
    """Histogram-subtraction plan for one tree level (DESIGN.md §3.8).

    ``node``: (R,) CHILD-level assignment in [0, n_nodes). For every sibling
    pair (2p, 2p+1) pick the child with fewer rows (ties → left): only its
    rows are accumulated, under their PARENT id ``p``; every other row takes
    the dump id ``n_nodes // 2``, which the scatter and the kernel drop.
    Returns ``(small_is_left, idx, snode)`` with ``small_is_left`` (N/2,)
    bool, in one of two forms (:func:`level_rows` picks):

    * ``compact=False``: rows stay in place — ``idx`` is None and ``snode``
      (R,) is every row's id;
    * ``compact=True``: the smaller children's rows are gathered into
      ``floor(R/2)`` slots (per-pair minima sum to at most R/2) — ``idx``
      (R//2,) int32 row indices in stable order, ``snode`` (R//2,) their
      ids, the dump id on unfilled slots. This halves the rows the XLA
      scatter (or the kernel) reads, at the price of the gathers.
    """
    # rows per node as a one-hot reduction: in HIGGS-width fits (600,000
    # rows) on a v5e the scatter-adds of the counts took 142 ms of device
    # time a configuration, this reduction with the row masks 44 ms
    cnt = (node[:, None] == jnp.arange(n_nodes)[None, :]).sum(
        axis=0, dtype=jnp.int32)
    small_is_left = cnt[0::2] <= cnt[1::2]
    is_small = jnp.stack([small_is_left, ~small_is_left], axis=1).reshape(-1)
    row_small = is_small[node]
    n_half = n_nodes // 2                    # the dump id
    if not compact:
        return small_is_left, None, jnp.where(row_small, node // 2, n_half)
    n_rows = node.shape[0]
    cap = n_rows // 2
    pos = _row_cumsum(row_small) - 1         # stable slot of each small row
    slot = jnp.where(row_small, pos, cap)    # cap = out of bounds → dropped
    idx = jnp.zeros((cap,), jnp.int32).at[slot].set(jnp.arange(n_rows))
    valid = jnp.arange(cap) < row_small.sum()
    return small_is_left, idx, jnp.where(valid, node[idx] // 2, n_half)


def _sharded_level_split(
    bins, g, h, node, *, n_nodes, n_bins, lam, min_child_weight, axis_name,
    row_valid, bin_limit=None, feat_mask=None, parent_hist=None,
    return_hist=True,
):
    """Cross-shard level build (DESIGN.md §3.9): per-shard partial
    histograms combined with a SINGLE ``psum`` before the split scan.

    Runs in the per-shard view of ``compat.sharded_call`` — ``bins``/``g``/
    ``h``/``node`` are this shard's row block, ``row_valid`` masks the
    zero-padded tail. Subtraction composes across shards, but the
    smaller-child PLAN must be global: per-shard row counts can disagree on
    which sibling is smaller, so the counts are psum'd first and every
    shard scatters its small-child rows through a dump slot (no compaction
    — a globally-small child's rows may concentrate on one shard, so a
    per-shard ``R/2`` cap would silently drop rows). After the psum the
    histogram — and therefore every split decision — is shard-invariant.
    """
    if row_valid is None:
        gv, hv = g, h
        ones = jnp.ones(node.shape, jnp.int32)
    else:
        gv = jnp.where(row_valid, g, 0.0)
        hv = jnp.where(row_valid, h, 0.0)
        ones = row_valid.astype(jnp.int32)
    subtract = parent_hist is not None and n_nodes > 1
    if subtract:
        cnt = jax.lax.psum(
            jnp.zeros((n_nodes,), jnp.int32).at[node].add(ones), axis_name)
        small_is_left = cnt[0::2] <= cnt[1::2]
        n_half = n_nodes // 2
        is_small = jnp.stack(
            [small_is_left, ~small_is_left], axis=1).reshape(-1)[node]
        if row_valid is not None:
            is_small = is_small & row_valid
        snode = jnp.where(is_small, node // 2, n_half)  # n_half = dump slot
        small = jax.lax.psum(
            _histogram_scatter(bins, gv, hv, snode, n_half, n_bins), axis_name)
        big = parent_hist - small
        silb = small_is_left[:, None, None, None]
        hist = jnp.stack(
            [jnp.where(silb, small, big), jnp.where(silb, big, small)], axis=1,
        ).reshape(n_nodes, bins.shape[1], n_bins, 2)
    else:
        hist = jax.lax.psum(
            _histogram_scatter(bins, gv, hv, node, n_nodes, n_bins), axis_name)
    bg, bf, bs = _ref.split_scan_ref(
        hist, lam=lam, min_child_weight=min_child_weight, n_bins=n_bins,
        bin_limit=bin_limit, feat_mask=feat_mask)
    return (hist if return_hist else None), bg, bf, bs


def level_split(
    bins, g, h, node, *, n_nodes, n_bins, lam, min_child_weight,
    bin_limit=None, feat_mask=None, parent_hist=None, return_hist=True,
    force=None, axis_name=None, row_valid=None,
):
    """One GBDT tree level: histogram build + best-split scan.
    See ``level_split_ref``; returns ``(hist, best_gain, best_feat,
    best_split)`` with ``hist=None`` when ``return_hist`` is False.

    ``parent_hist`` (the previous level's (n_nodes/2, F, B, 2) histograms)
    enables histogram subtraction: only the smaller child of each sibling
    pair is accumulated from rows, the sibling is ``parent − small``. The
    XLA fallback's DIRECT mode is op-for-op the pre-fusion ``build_tree``
    sequence (``_histogram_scatter`` + ``ref.split_scan_ref``), so CPU
    split decisions are bit-identical to the historical path. Subtraction
    rounds ``parent − small`` differently and may flip a near-tie; its
    decisions keep the near-tie contract of ``ref.assert_split_decisions``
    (DESIGN.md §3.8). ``force`` matches ``ops`` conventions and is threaded by
    ``build_tree`` so tests can pin a backend end to end.

    With ``axis_name`` the call runs in a per-shard SPMD view (row-sharded
    data plane, DESIGN.md §3.9): inputs are one shard's row block,
    ``row_valid`` masks pad rows, per-shard partial histograms are combined
    with one ``psum`` and the scan runs on the global histogram — the
    returned decisions (and ``hist``) are shard-invariant.
    """
    if axis_name is not None:
        return _sharded_level_split(
            bins, g, h, node, n_nodes=n_nodes, n_bins=n_bins, lam=lam,
            min_child_weight=min_child_weight, axis_name=axis_name,
            row_valid=row_valid, bin_limit=bin_limit, feat_mask=feat_mask,
            parent_hist=parent_hist, return_hist=return_hist)
    if force == "ref":
        hist, bg, bf, bs = _ref.level_split_ref(
            bins, g, h, node, n_nodes, n_bins, lam=lam,
            min_child_weight=min_child_weight, bin_limit=bin_limit,
            feat_mask=feat_mask)
        return (hist if return_hist else None), bg, bf, bs
    use_kernel = _use_level_kernel(force, n_bins)
    subtract = parent_hist is not None and n_nodes > 1
    sil = None
    if subtract:
        sil, idx, node = _plan_smaller_child(
            node, n_nodes,
            compact=level_rows(bins.shape[1], n_bins, force) == "half")
        if idx is not None:
            bins, g, h = bins[idx], g[idx], h[idx]
            if use_kernel:
                # materialize the compacted rows: fused into the kernel's
                # input padding, the vmapped gathers took the v5e compiler
                # ~25 s per level of a fused batch, against ~4 s
                bins, g, h, node = jax.lax.optimization_barrier(
                    (bins, g, h, node))
    if use_kernel:
        from repro.kernels.histogram import fused_level_split_tpu

        return fused_level_split_tpu(
            bins, g, h, node, n_nodes=n_nodes, n_bins=n_bins,
            lam=lam, min_child_weight=min_child_weight, bin_limit=bin_limit,
            feat_mask=feat_mask, parent_hist=parent_hist if subtract else None,
            small_is_left=sil, interpret=not _on_tpu(),
            return_hist=return_hist)
    if subtract:
        small = _histogram_scatter(bins, g, h, node, n_nodes // 2, n_bins)
        big = parent_hist - small
        silb = sil[:, None, None, None]
        hist = jnp.stack(
            [jnp.where(silb, small, big), jnp.where(silb, big, small)], axis=1,
        ).reshape(n_nodes, bins.shape[1], n_bins, 2)
    else:
        hist = _histogram_scatter(bins, g, h, node, n_nodes, n_bins)
    bg, bf, bs = _ref.split_scan_ref(
        hist, lam=lam, min_child_weight=min_child_weight, n_bins=n_bins,
        bin_limit=bin_limit, feat_mask=feat_mask)
    return (hist if return_hist else None), bg, bf, bs
