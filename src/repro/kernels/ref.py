"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function here is the *semantic definition* of the corresponding kernel:
straight-line jnp, no tiling, f32 accumulation. Kernel tests sweep shapes and
dtypes and ``assert_allclose`` against these; the CPU execution path of
``ops.py`` also dispatches here (Mosaic kernels are TPU-only custom calls).

Conventions
-----------
* Attention tensors are laid out ``(batch, heads, seq, head_dim)``.
* GQA: ``q`` has ``n_heads``; ``k``/``v`` have ``n_kv_heads`` which must
  divide ``n_heads``; kv heads are logically repeated.
* Recurrences (RG-LRU, WKV6) scan over the time axis of ``(B, T, ...)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "attention_ref",
    "attention_xla_blocked",
    "decode_attention_ref",
    "rglru_ref",
    "rwkv6_ref",
    "histogram_ref",
    "split_gains_ref",
    "assert_split_decisions",
    "split_scan_ref",
    "level_split_ref",
]


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, Hkv, T, D) -> (B, Hkv*n_rep, T, D) by head repetition."""
    if n_rep == 1:
        return x
    b, h, t, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, h, n_rep, t, d)).reshape(b, h * n_rep, t, d)


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    matmul_dtype: str = "float32",
) -> jax.Array:
    """Plain softmax attention with causal and/or sliding-window masking.

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D). When Tq < Tk the queries are
    assumed to occupy the LAST Tq key positions (decode/chunked-prefill
    convention). ``window``: key j is visible from query i iff
    ``i - j < window`` (in absolute positions); None = unlimited.
    ``matmul_dtype="input"`` keeps QK/PV operands in the input dtype (bf16
    on TPU) with f32 MXU accumulation — half the operand bytes; "float32"
    up-casts first (the conservative baseline).
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    s = scale if scale is not None else d ** -0.5
    if matmul_dtype == "input":
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * s
    else:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * s
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    q_pos = jnp.arange(tq) + (tk - tq)  # absolute positions of the queries
    k_pos = jnp.arange(tk)
    mask = jnp.ones((tq, tk), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    if matmul_dtype == "input":
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_xla_blocked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    block_q: int = 2048,
    matmul_dtype: str = "float32",
) -> jax.Array:
    """Flash-style attention in pure XLA ops: Q processed in UNROLLED blocks,
    each block attending only to its statically-reachable K range.

    Purpose: (i) the XLA path never materialises the (Tq, Tk) logits tensor
    (peak temp is (block_q × k_range)); (ii) the block loop is a *python*
    loop, so the compiled HLO contains every block — ``cost_analysis`` FLOPs
    stay exact, unlike a ``lax.scan`` body which XLA counts once.
    Semantics identical to ``attention_ref`` (same masking conventions).
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    if tq <= block_q:
        return attention_ref(q, k, v, causal=causal, window=window, scale=scale,
                             logit_softcap=logit_softcap, matmul_dtype=matmul_dtype)
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    sc = scale if scale is not None else d ** -0.5
    offset = tk - tq                     # absolute position of q block 0
    outs = []
    for start in range(0, tq, block_q):
        stop = min(start + block_q, tq)
        q_lo, q_hi = start + offset, stop - 1 + offset
        # statically-reachable K range for this block
        k_lo = 0 if window is None else max(0, q_lo - window + 1)
        k_hi = (q_hi if causal else tk - 1)
        k_hi = min(k_hi, tk - 1)
        kb = jax.lax.slice_in_dim(k, k_lo, k_hi + 1, axis=2)
        vb = jax.lax.slice_in_dim(v, k_lo, k_hi + 1, axis=2)
        qb = jax.lax.slice_in_dim(q, start, stop, axis=2)
        if matmul_dtype == "input":
            logits = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                                preferred_element_type=jnp.float32) * sc
        else:
            logits = jnp.einsum(
                "bhqd,bhkd->bhqk", qb.astype(jnp.float32), kb.astype(jnp.float32)
            ) * sc
        if logit_softcap is not None:
            logits = logit_softcap * jnp.tanh(logits / logit_softcap)
        q_pos = jnp.arange(start, stop) + offset
        k_pos = jnp.arange(k_lo, k_hi + 1)
        mask = jnp.ones((stop - start, k_hi + 1 - k_lo), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        if matmul_dtype == "input":
            o = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, vb.astype(jnp.float32))
        outs.append(o.astype(q.dtype))
    return jnp.concatenate(outs, axis=2)


def decode_attention_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array | int,
    *,
    window: int | None = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    matmul_dtype: str = "float32",
) -> jax.Array:
    """Single-position decode attention over a (possibly oversized) KV cache.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); ``cache_len`` = number of valid
    entries (the new token's K/V must already be written at cache_len-1).
    Positions >= cache_len are masked out; sliding ``window`` is honoured.
    ``matmul_dtype="input"`` reads the bf16 cache DIRECTLY (f32 MXU
    accumulation) instead of materialising an f32 copy — decode is one pass
    over the cache per token, so this halves-to-thirds the step's bytes.
    """
    b, hq, _, d = q.shape
    _, hkv, s_max, _ = k_cache.shape
    g = hq // hkv
    # GQA-GROUPED contraction: query heads are folded into a per-kv-head
    # group dim, so each KV element is read ONCE — the naive repeat_kv
    # broadcast costs g× the cache sweep, the decode step's entire bytes
    # budget (EXPERIMENTS.md §Perf, qwen2_decode iterations).
    qg = q.reshape(b, hkv, g, d)                     # tq == 1 folded away
    k, v = k_cache, v_cache
    sc = scale if scale is not None else d ** -0.5
    if matmul_dtype == "input":
        logits = jnp.einsum("bkgd,bksd->bkgs", qg.astype(k.dtype), k,
                            preferred_element_type=jnp.float32) * sc
    else:
        logits = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * sc
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    pos = jnp.arange(s_max)
    valid = pos < cache_len
    if window is not None:
        valid &= pos >= (cache_len - window)
    logits = jnp.where(valid[None, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    if matmul_dtype == "input":
        out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgs,bksd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def rglru_ref(
    x: jax.Array,
    input_gate: jax.Array,
    rec_gate: jax.Array,
    a_param: jax.Array,
    h0: jax.Array | None = None,
    *,
    c: float = 8.0,
) -> tuple[jax.Array, jax.Array]:
    """Real-Gated Linear Recurrent Unit (Griffin / RecurrentGemma).

    x, input_gate, rec_gate: (B, T, D) — gates are PRE-sigmoid logits.
    a_param: (D,) — the learnable Λ; log a_t = -c * softplus(Λ) * σ(r_t).
    Returns (y, h_T) where y: (B, T, D) and h_T: (B, D) final state.

        a_t = exp(-c · softplus(Λ) · σ(r_t))
        h_t = a_t ⊙ h_{t-1} + √(1 − a_t²) ⊙ (σ(i_t) ⊙ x_t)
    """
    b, t, d = x.shape
    xf = x.astype(jnp.float32)
    log_a = -c * jax.nn.softplus(a_param.astype(jnp.float32))[None, None, :] * jax.nn.sigmoid(
        rec_gate.astype(jnp.float32)
    )  # (B, T, D), <= 0
    a = jnp.exp(log_a)
    gated_x = jax.nn.sigmoid(input_gate.astype(jnp.float32)) * xf
    # multiplier uses log-space for stability: sqrt(1 - a^2) = sqrt(-expm1(2 log a))
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    h_init = jnp.zeros((b, d), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        a_t, u_t = inp
        h = a_t * h + u_t
        return h, h

    h_last, ys = jax.lax.scan(
        step,
        h_init,
        (jnp.moveaxis(a, 1, 0), jnp.moveaxis(beta * gated_x, 1, 0)),
    )
    y = jnp.moveaxis(ys, 0, 1)
    return y.astype(x.dtype), h_last


def rwkv6_ref(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    s0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """RWKV-6 (Finch) WKV recurrence with data-dependent decay.

    r, k, w: (B, H, T, Dk); v: (B, H, T, Dv); u: (H, Dk) bonus.
    ``w`` is the PRE-activation decay; effective decay is
    exp(-exp(w)) ∈ (0, 1), data-dependent per (position, channel).

        y_t = (S_{t-1} + (u ⊙ k_t) v_tᵀ)ᵀ r_t
        S_t = diag(d_t) S_{t-1} + k_t v_tᵀ,   d_t = exp(-exp(w_t))

    Returns (y, S_T): y (B, H, T, Dv); S_T (B, H, Dk, Dv).
    """
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    decay = jnp.exp(-jnp.exp(w.astype(jnp.float32)))  # (B, H, T, Dk)
    uf = u.astype(jnp.float32)
    s_init = jnp.zeros((b, h, dk, dv), jnp.float32) if s0 is None else s0.astype(jnp.float32)

    def step(s, inp):
        r_t, k_t, v_t, d_t = inp  # (B,H,Dk) ×3, (B,H,Dk)
        kv = k_t[..., :, None] * v_t[..., None, :]           # (B,H,Dk,Dv)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + uf[None, :, :, None] * kv)
        s = d_t[..., :, None] * s + kv
        return s, y

    s_last, ys = jax.lax.scan(
        step,
        s_init,
        (
            jnp.moveaxis(rf, 2, 0),
            jnp.moveaxis(kf, 2, 0),
            jnp.moveaxis(vf, 2, 0),
            jnp.moveaxis(decay, 2, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 2)  # (B, H, T, Dv)
    return y.astype(v.dtype), s_last


def histogram_ref(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    node: jax.Array,
    n_nodes: int,
    n_bins: int,
) -> jax.Array:
    """Gradient/hessian histograms for GBDT split finding.

    bins: (rows, features) int32 in [0, n_bins); grad/hess: (rows,);
    node: (rows,) int32 in [0, n_nodes) — current tree-node of each row.
    Returns (n_nodes, features, n_bins, 2) f32 with [..., 0] = Σgrad and
    [..., 1] = Σhess over rows in that (node, feature-bin) cell.
    """
    node_oh = jax.nn.one_hot(node, n_nodes, dtype=jnp.float32)          # (R, N)
    bin_oh = jax.nn.one_hot(bins, n_bins, dtype=jnp.float32)            # (R, F, B)
    gh = jnp.stack([grad, hess], axis=-1).astype(jnp.float32)           # (R, 2)
    # (N, R) @ (R, F*B*2) — one MXU-shaped contraction
    weighted = bin_oh[..., None] * gh[:, None, None, :]                 # (R, F, B, 2)
    return jnp.einsum("rn,rfbt->nfbt", node_oh, weighted)


def split_gains_ref(hist: jax.Array, *, lam, min_child_weight, n_bins: int,
                    bin_limit=None, feat_mask: jax.Array | None = None,
                    ) -> jax.Array:
    """Gain of every candidate split, ``(n_nodes, F, B)``; ``-inf`` where
    the split is not admissible (child weight, feature mask, bin limit)."""
    gl = jnp.cumsum(hist[..., 0], axis=-1)              # (N, F, B) left sums
    hl = jnp.cumsum(hist[..., 1], axis=-1)
    gt = gl[:, :1, -1:]                                  # (N, 1, 1) node totals
    ht = hl[:, :1, -1:]
    gr = gt - gl
    hr = ht - hl
    gain = gl**2 / (hl + lam) + gr**2 / (hr + lam) - gt**2 / (ht + lam)
    ok = (hl >= min_child_weight) & (hr >= min_child_weight)
    if feat_mask is not None:
        ok &= feat_mask[None, :, None]
    # splitting at the last bin sends every row left — not a real split
    last = n_bins - 1 if bin_limit is None else bin_limit - 1
    ok &= jnp.arange(n_bins)[None, None, :] < last
    return jnp.where(ok, gain, -jnp.inf)


def assert_split_decisions(hist, best_gain, best_feat, best_split, *, lam,
                           min_child_weight, n_bins: int, bin_limit=None,
                           feat_mask=None, rtol: float = 1e-4,
                           atol: float = 1e-4) -> None:
    """The split-decision contract of every ``level_split`` path that sums
    in another order than the oracle (DESIGN.md §3.8), checked against the
    REFERENCE histogram ``hist`` (``histogram_ref`` of the same rows).

    Adjacent thresholds with no rows between them give the same partition,
    so their gains tie in exact arithmetic and rounding picks the winner.
    So per node: the chosen ``(best_feat, best_split)``, evaluated on
    ``hist``, must be admissible and its gain must equal the best gain on
    ``hist`` within tolerance; the reported ``best_gain`` must match that
    best too (both ``-inf`` where no split is admissible). The tolerance
    scales with the parent term ``G²/(H+λ)``, whose cancellation bounds the
    gain's rounding error. Raises ``AssertionError`` naming the nodes."""
    hist = np.asarray(hist, np.float32)
    gain = np.asarray(split_gains_ref(
        jnp.asarray(hist), lam=lam, min_child_weight=min_child_weight,
        n_bins=n_bins, bin_limit=bin_limit, feat_mask=feat_mask))
    n = gain.shape[0]
    best = gain.reshape(n, -1).max(axis=1)
    chosen = gain[np.arange(n), np.asarray(best_feat), np.asarray(best_split)]
    gt, ht = hist[:, 0, :, 0].sum(axis=1), hist[:, 0, :, 1].sum(axis=1)
    tol = atol + rtol * np.maximum(np.abs(np.where(np.isfinite(best), best, 0)),
                                   gt * gt / np.maximum(ht + lam, 1e-30))
    none = best == -np.inf
    reported = np.asarray(best_gain, np.float32)
    with np.errstate(invalid="ignore"):
        choice_ok = none | (np.isfinite(chosen) & (best - chosen <= tol))
        gain_ok = np.where(none, reported == -np.inf,
                           np.abs(reported - best) <= tol)
    bad = np.flatnonzero(~(choice_ok & gain_ok))
    if bad.size:
        raise AssertionError(
            f"split decisions off at nodes {bad[:8].tolist()}: chosen gain "
            f"{chosen[bad[:8]].tolist()}, reported {reported[bad[:8]].tolist()}"
            f", reference best {best[bad[:8]].tolist()}, tol "
            f"{tol[bad[:8]].tolist()}")


def split_scan_ref(
    hist: jax.Array,
    *,
    lam,
    min_child_weight,
    n_bins: int,
    bin_limit=None,
    feat_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Best-split scan over one level's histograms: cumsum → gain → masked
    argmax. ``hist``: (n_nodes, F, B, 2); returns per-node
    ``(best_gain, best_feat, best_split)``.

    This is the semantic definition of the scan half of the fused level
    kernel AND, op for op, the sequence the pre-fusion ``build_tree`` ran
    inline — ``ops.level_split``'s XLA fallback calls it directly, so the
    CPU path stays bit-identical to the historical one. ``lam``/
    ``min_child_weight`` may be traced 0-d arrays and ``bin_limit`` a traced
    int (the fused-batch vmap contract). Node totals come from FEATURE 0's
    cumsum tail (every feature's bins sum to the same node total).
    """
    n_nodes, f = hist.shape[0], hist.shape[1]
    gain = split_gains_ref(hist, lam=lam, min_child_weight=min_child_weight,
                           n_bins=n_bins, bin_limit=bin_limit,
                           feat_mask=feat_mask)
    flat = gain.reshape(n_nodes, f * n_bins)
    best = jnp.argmax(flat, axis=-1)                     # first max wins ties
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=-1)[:, 0]
    feat = (best // n_bins).astype(jnp.int32)
    split = (best % n_bins).astype(jnp.int32)
    return best_gain, feat, split


def level_split_ref(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    node: jax.Array,
    n_nodes: int,
    n_bins: int,
    *,
    lam,
    min_child_weight,
    bin_limit=None,
    feat_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One GBDT tree level end to end: histogram build + best-split scan.

    The oracle for the fused level kernel
    (``kernels.histogram.fused_level_split_tpu``) — always the DIRECT
    formulation (no histogram subtraction): subtraction is an implementation
    strategy whose result must match this definition. Returns
    ``(hist, best_gain, best_feat, best_split)``.
    """
    hist = histogram_ref(bins, grad, hess, node, n_nodes, n_bins)
    best_gain, feat, split = split_scan_ref(
        hist, lam=lam, min_child_weight=min_child_weight, n_bins=n_bins,
        bin_limit=bin_limit, feat_mask=feat_mask)
    return hist, best_gain, feat, split
