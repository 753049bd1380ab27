"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
sweeping shapes and dtypes (the tests/ contract for kernels/)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.histogram import fused_level_split_tpu


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,t,d,causal,window",
    [
        (1, 2, 2, 128, 64, True, None),
        (2, 4, 2, 256, 64, True, None),      # GQA
        (1, 4, 1, 256, 128, True, None),     # MQA
        (1, 2, 2, 256, 64, False, None),     # bidirectional
        (1, 2, 1, 256, 64, True, 64),        # sliding window
    ],
)
def test_flash_attention_vs_ref(rng, b, hq, hkv, t, d, causal, window, dtype):
    q = _rand(rng, (b, hq, t, d), dtype)
    k = _rand(rng, (b, hkv, t, d), dtype)
    v = _rand(rng, (b, hkv, t, d), dtype)
    out_k = ops.attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, force="kernel")
    out_r = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol, rtol=tol)


def test_flash_attention_softcap(rng):
    q = _rand(rng, (1, 2, 128, 64), jnp.float32)
    k = _rand(rng, (1, 2, 128, 64), jnp.float32)
    v = _rand(rng, (1, 2, 128, 64), jnp.float32)
    out_k = ops.attention(q, k, v, logit_softcap=30.0, block_q=64, block_k=64,
                          force="kernel")
    out_r = ref.attention_ref(q, k, v, logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


def test_attention_xla_blocked_matches_ref(rng):
    q = _rand(rng, (1, 2, 4096, 64), jnp.float32)
    k = _rand(rng, (1, 2, 4096, 64), jnp.float32)
    v = _rand(rng, (1, 2, 4096, 64), jnp.float32)
    for window in (None, 512):
        blocked = ref.attention_xla_blocked(q, k, v, causal=True, window=window,
                                            block_q=1024)
        full = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(full),
                                   atol=3e-5, rtol=1e-4)


def test_decode_attention_matches_prefix(rng):
    """Decode over a cache == last row of full attention."""
    b, hq, hkv, t, d = 2, 4, 2, 64, 32
    q_all = _rand(rng, (b, hq, t, d), jnp.float32)
    k_all = _rand(rng, (b, hkv, t, d), jnp.float32)
    v_all = _rand(rng, (b, hkv, t, d), jnp.float32)
    full = ref.attention_ref(q_all, k_all, v_all, causal=True)
    cache_k = jnp.pad(k_all, ((0, 0), (0, 0), (0, 16), (0, 0)))
    cache_v = jnp.pad(v_all, ((0, 0), (0, 0), (0, 16), (0, 0)))
    dec = ref.decode_attention_ref(q_all[:, :, -1:], cache_k, cache_v, t)
    np.testing.assert_allclose(np.asarray(dec[:, :, 0]), np.asarray(full[:, :, -1]),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,d", [(1, 64, 128), (2, 128, 256), (1, 8, 128)])
def test_rglru_vs_ref(rng, b, t, d):
    x = _rand(rng, (b, t, d), jnp.float32)
    ig = _rand(rng, (b, t, d), jnp.float32)
    rg_ = _rand(rng, (b, t, d), jnp.float32)
    a = _rand(rng, (d,), jnp.float32)
    yk, hk = ops.rglru(x, ig, rg_, a, force="kernel")
    yr, hr = ref.rglru_ref(x, ig, rg_, a)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), atol=2e-5)


def test_rglru_state_chaining(rng):
    """Running [0:T] == running [0:T/2] then [T/2:T] with carried state."""
    b, t, d = 1, 64, 128
    x = _rand(rng, (b, t, d), jnp.float32)
    ig = _rand(rng, (b, t, d), jnp.float32)
    rg_ = _rand(rng, (b, t, d), jnp.float32)
    a = _rand(rng, (d,), jnp.float32)
    y_full, h_full = ref.rglru_ref(x, ig, rg_, a)
    h = None
    ys = []
    for lo, hi in ((0, t // 2), (t // 2, t)):
        y, h = ref.rglru_ref(x[:, lo:hi], ig[:, lo:hi], rg_[:, lo:hi], a, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,t,dk,dv,chunk", [
    (1, 2, 64, 32, 32, 16),
    (2, 2, 128, 64, 64, 64),
    (1, 1, 96, 16, 64, 32),
])
def test_rwkv6_vs_ref(rng, b, h, t, dk, dv, chunk):
    r = _rand(rng, (b, h, t, dk), jnp.float32)
    k = _rand(rng, (b, h, t, dk), jnp.float32)
    v = _rand(rng, (b, h, t, dv), jnp.float32)
    w = _rand(rng, (b, h, t, dk), jnp.float32)
    u = _rand(rng, (h, dk), jnp.float32)
    yk, sk = ops.rwkv6(r, k, v, w, u, chunk=chunk, force="kernel")
    yr, sr = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=5e-3)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), atol=5e-3)


def test_rwkv6_state_chaining(rng):
    b, h, t, dk, dv = 1, 2, 64, 32, 32
    r = _rand(rng, (b, h, t, dk), jnp.float32)
    k = _rand(rng, (b, h, t, dk), jnp.float32)
    v = _rand(rng, (b, h, t, dv), jnp.float32)
    w = _rand(rng, (b, h, t, dk), jnp.float32)
    u = _rand(rng, (h, dk), jnp.float32)
    y_full, s_full = ref.rwkv6_ref(r, k, v, w, u)
    s = None
    ys = []
    for lo, hi in ((0, 32), (32, 64)):
        y, s = ref.rwkv6_ref(r[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi],
                             w[:, :, lo:hi], u, s)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 2)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_full), atol=1e-4)


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,f,nb,nn", [(100, 5, 8, 1), (500, 7, 16, 4), (1000, 3, 64, 8)])
def test_histogram_all_paths_agree(rng, r, f, nb, nn):
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = _rand(rng, (r,), jnp.float32)
    h = jnp.abs(_rand(rng, (r,), jnp.float32)) + 0.1
    node = jnp.asarray(rng.integers(0, nn, size=(r,)), jnp.int32)
    oracle = ref.histogram_ref(bins, g, h, node, nn, nb)
    kernel = ops.histogram(bins, g, h, node, n_nodes=nn, n_bins=nb, force="kernel")
    scatter = ops.histogram(bins, g, h, node, n_nodes=nn, n_bins=nb)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(oracle), atol=1e-4)
    np.testing.assert_allclose(np.asarray(scatter), np.asarray(oracle), atol=1e-4)


def test_histogram_conservation(rng):
    """Σ over all cells of the grad histogram == Σ grads (per feature)."""
    r, f, nb, nn = 300, 4, 16, 4
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = _rand(rng, (r,), jnp.float32)
    h = jnp.ones((r,), jnp.float32)
    node = jnp.asarray(rng.integers(0, nn, size=(r,)), jnp.int32)
    hist = ops.histogram(bins, g, h, node, n_nodes=nn, n_bins=nb, force="kernel")
    total_g = np.asarray(hist[..., 0].sum(axis=(0, 2)))
    np.testing.assert_allclose(total_g, float(g.sum()) * np.ones(f), rtol=1e-4)
    total_h = np.asarray(hist[..., 1].sum(axis=(0, 2)))
    np.testing.assert_allclose(total_h, r * np.ones(f), rtol=1e-5)


def test_histogram_tile_table_respects_vmem_budget():
    """pick_tiles shrinks block_features as n_nodes grows: one grid step's
    buffers (accumulators, histogram planes, scan temporaries) must stay
    inside the VMEM budget at every tree level, not just the shallow ones,
    and every feature block must be a whole number of 128-lane tiles that
    reads one aligned column group of the bins."""
    from repro.kernels.histogram import (_VMEM_BUDGET, _bins_group,
                                         _vmem_bytes, pick_tiles)

    for n_feat in (28, 120, 590):
        for n_bins in (32, 64, 128, 256):
            for n_nodes in (1, 8, 64, 512, 2048):
                bf, br = pick_tiles(n_feat, n_bins, 4800, n_nodes=n_nodes)
                assert bf >= 1 and br >= 8
                assert (bf * n_bins) % 128 == 0
                group = _bins_group(n_feat, bf)
                assert group % bf == 0
                assert group == -(-n_feat // bf) * bf or group % 128 == 0
                assert (bf * n_bins == max(128, n_bins)
                        or _vmem_bytes(bf, n_bins, n_nodes, br, group)
                        <= _VMEM_BUDGET)
    # deep level really does shrink vs the shallow default
    assert pick_tiles(120, 64, 4800, n_nodes=2048)[0] < \
        pick_tiles(120, 64, 4800, n_nodes=8)[0]


def test_histogram_kernel_odd_feature_and_bin_shapes(rng):
    """Padded-tile audit (feature/bin axes, the PR 5 row-clamp pattern):
    a feature count that doesn't divide block_features pads inside the
    kernel and MUST be trimmed from the result; a bin count with no exact
    tile-table key goes through the nearest-key lookup. Either leak would
    change the output shape or pollute real cells."""
    for r, f, nb, nn in [(50, 19, 24, 3), (128, 13, 48, 5), (37, 9, 8, 2)]:
        bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
        g = _rand(rng, (r,), jnp.float32)
        h = jnp.abs(_rand(rng, (r,), jnp.float32)) + 0.1
        node = jnp.asarray(rng.integers(0, nn, size=(r,)), jnp.int32)
        kern = ops.histogram(bins, g, h, node, n_nodes=nn, n_bins=nb,
                             force="kernel")
        assert kern.shape == (nn, f, nb, 2)
        np.testing.assert_allclose(
            np.asarray(kern),
            np.asarray(ref.histogram_ref(bins, g, h, node, nn, nb)), atol=1e-4)


def test_pick_tiles_never_exceeds_rows(rng):
    """Regression: ``min(block_r, max(8, n_rows))`` returned block_rows=8
    for a 4-row histogram, silently padding tiny arrays — block_rows must
    be clamped to the array."""
    from repro.kernels.histogram import pick_tiles

    for n_rows in (1, 4, 7):
        _, br = pick_tiles(16, 64, n_rows)
        assert br == n_rows
    _, br = pick_tiles(16, 64, 4800)
    assert br == 256                       # row-tile default untouched
    # and a 4-row histogram actually computes correctly through the kernel
    r, f, nb, nn = 4, 3, 8, 2
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = _rand(rng, (r,), jnp.float32)
    h = jnp.abs(_rand(rng, (r,), jnp.float32)) + 0.1
    node = jnp.asarray(rng.integers(0, nn, size=(r,)), jnp.int32)
    from repro.kernels.histogram import histogram_tpu

    kern = histogram_tpu(bins, g, h, node, n_nodes=nn, n_bins=nb,
                         interpret=True)
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(ref.histogram_ref(bins, g, h, node, nn, nb)),
        atol=1e-4)


# ---------------------------------------------------------------------------
# fused level split (histogram + split scan + subtraction, DESIGN.md §3.8)
# ---------------------------------------------------------------------------

def _level_fixture(rng, r, f, nb, nn):
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = _rand(rng, (r,), jnp.float32)
    h = jnp.abs(_rand(rng, (r,), jnp.float32)) + 0.1
    node = jnp.asarray(rng.integers(0, nn, size=(r,)), jnp.int32)
    return bins, g, h, node


def _assert_decisions(hist_ref, bg_ref, out, **scan):
    """The split-decision contract shared with the chip parity check
    (``ref.assert_split_decisions``, DESIGN.md §3.8)."""
    np.testing.assert_array_equal(np.isfinite(np.asarray(out[1])),
                                  np.isfinite(np.asarray(bg_ref)))
    ref.assert_split_decisions(hist_ref, out[1], out[2], out[3], **scan)


def _parent_of(bins, g, h, node, nn, nb):
    """Level-above histograms over the same rows (node // 2)."""
    return ops._histogram_scatter(bins, g, h, node // 2, nn // 2, nb)


# the ISSUE parity grid: depths {1, 3, 6} (n_nodes = 2^(depth-1) at the
# deepest level) × bins {16, 64, 256}
@pytest.mark.parametrize("r,f,nb,nn", [
    (200, 5, 16, 1), (500, 7, 64, 4), (400, 12, 256, 4), (300, 9, 16, 32),
    (600, 3, 64, 32), (250, 6, 256, 32),
    (64, 300, 256, 2),   # wide: feature blocks in aligned bins column groups
])
def test_level_split_kernel_vs_ref(rng, r, f, nb, nn):
    bins, g, h, node = _level_fixture(rng, r, f, nb, nn)
    scan = dict(n_bins=nb, lam=1.0, min_child_weight=1.0)
    kw = dict(n_nodes=nn, **scan)
    hr, bgr, bfr, bsr = ops.level_split(bins, g, h, node, force="ref", **kw)
    hx, bgx, bfx, bsx = ops.level_split(bins, g, h, node, **kw)
    # the XLA direct path is op-for-op the oracle's scan: identical choices
    np.testing.assert_allclose(np.asarray(hx), np.asarray(hr), atol=1e-4)
    assert bool((bfx == bfr).all() and (bsx == bsr).all())
    # the kernel (direct) and both subtraction paths sum in another order:
    # same histograms within float tolerance, split choices under the
    # near-tie contract
    outs = [ops.level_split(bins, g, h, node, force="kernel", **kw)]
    if nn > 1:
        parent = _parent_of(bins, g, h, node, nn, nb)
        outs += [ops.level_split(bins, g, h, node, parent_hist=parent,
                                 force=force, **kw)
                 for force in (None, "kernel")]
    for out in outs:
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(hr),
                                   atol=1e-4)
        _assert_decisions(hr, bgr, out, **scan)


def _compacted_kernel(bins, g, h, node, parent, nn, **kw):
    """A subtraction level in the compacted form, as the kernel branch of
    ``ops.level_split`` runs it where ``ops.level_rows`` says "half": the
    smaller children's rows gathered into R/2 slots."""
    sil, idx, snode = ops._plan_smaller_child(node, nn, compact=True)
    return fused_level_split_tpu(bins[idx], g[idx], h[idx], snode,
                                 n_nodes=nn, parent_hist=parent,
                                 small_is_left=sil, interpret=True, **kw)


# narrow levels, where the kernel reads every row with a masked node id
@pytest.mark.parametrize("f,nb,nn", [
    (f, nb, nn) for f in (3, 28) for nb in (16, 64, 128) for nn in (2, 8, 32)])
def test_level_split_masked_subtraction_vs_ref_and_compacted(rng, f, nb, nn):
    assert ops.level_rows(f, nb, force="kernel") == "all"
    bins, g, h, node = _level_fixture(rng, 300, f, nb, nn)
    scan = dict(n_bins=nb, lam=1.0, min_child_weight=1.0)
    hr, bgr, _, _ = ops.level_split(bins, g, h, node, n_nodes=nn,
                                    force="ref", **scan)
    parent = _parent_of(bins, g, h, node, nn, nb)
    masked = ops.level_split(bins, g, h, node, n_nodes=nn, parent_hist=parent,
                             force="kernel", **scan)
    compacted = _compacted_kernel(bins, g, h, node, parent, nn, **scan)
    for out in (masked, compacted):
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(hr),
                                   atol=1e-4)
        _assert_decisions(hr, bgr, out, **scan)
    # the same rows in the same order, in other row blocks
    np.testing.assert_allclose(np.asarray(masked[0]),
                               np.asarray(compacted[0]), atol=1e-5)


def test_level_split_masked_subtraction_limits_and_mask(rng):
    """The masked form under a traced bin limit and a forest feature mask,
    with and without the histogram output: the same decisions as the
    compacted form's, within the near-tie contract of the oracle's."""
    f, nb, nn = 28, 64, 8
    bins, g, h, node = _level_fixture(rng, 400, f, nb, nn)
    mask = jnp.asarray(np.arange(f) % 3 == 0)
    scan = dict(n_bins=nb, lam=0.5, min_child_weight=1.0, feat_mask=mask)
    parent = _parent_of(bins, g, h, node, nn, nb)

    @functools.partial(jax.jit, static_argnames="return_hist")
    def masked(blim, return_hist):
        return ops.level_split(bins, g, h, node, n_nodes=nn, bin_limit=blim,
                               parent_hist=parent, return_hist=return_hist,
                               force="kernel", **scan)

    blim = jnp.int32(16)
    full, slim = masked(blim, True), masked(blim, False)
    assert slim[0] is None
    for a, b in zip(full[1:], slim[1:]):
        assert bool((np.asarray(a) == np.asarray(b)).all())
    hr = ref.histogram_ref(bins, g, h, node, nn, nb)
    bgr = ops.level_split(bins, g, h, node, n_nodes=nn, bin_limit=16,
                          force="ref", **scan)[1]
    compacted = _compacted_kernel(bins, g, h, node, parent, nn, bin_limit=blim,
                                  return_hist=False, **scan)
    for out in (slim, compacted):
        _assert_decisions(hr, bgr, out, bin_limit=16, **scan)
        real = np.isfinite(np.asarray(out[1]))
        assert bool(np.asarray(mask)[np.asarray(out[2])[real]].all())
        assert bool((np.asarray(out[3]) < 15).all())


@pytest.mark.parametrize("nb", [32, 64, 128])
def test_level_rows_rule_separates_higgs_from_secom(nb):
    """The kernel reads every row at HIGGS width (28 features) and the
    smaller children's, gathered, at SECOM width (590); the XLA scatter
    (past the kernel's 256 bins) always gathers."""
    assert ops.level_rows(28, nb, force="kernel") == "all"
    assert ops.level_rows(590, nb, force="kernel") == "half"
    assert ops.level_rows(28, 512) == "half"


def test_level_split_traced_bin_limit(rng):
    """bin_limit arrives as a traced int under jit (the fused-batch
    contract): splits at bins >= bin_limit - 1 must never win, and kernel
    and ref must agree under the same traced value."""
    bins, g, h, node = _level_fixture(rng, 400, 6, 64, 8)

    def make(force):
        @jax.jit
        def run(blim):
            return ops.level_split(
                bins, g, h, node, n_nodes=8, n_bins=64, lam=jnp.float32(0.5),
                min_child_weight=jnp.float32(1.0), bin_limit=blim,
                force=force)[1:]
        return run

    for force in ("kernel", "ref", None):
        bg, bf, bs = make(force)(jnp.int32(16))
        assert bool((np.asarray(bs) < 15).all())
    hist = ref.histogram_ref(bins, g, h, node, 8, 64)
    _assert_decisions(hist, make("ref")(jnp.int32(16))[0],
                      (None, *make("kernel")(jnp.int32(16))),
                      n_bins=64, lam=0.5, min_child_weight=1.0, bin_limit=16)


def test_level_split_feat_mask(rng):
    """Masked-off features (the forest √F subset) never produce a winning
    split on any backend; parity holds under the mask."""
    bins, g, h, node = _level_fixture(rng, 500, 10, 32, 8)
    mask = jnp.asarray(np.arange(10) % 3 == 0)     # features 0,3,6,9 allowed
    kw = dict(n_nodes=8, n_bins=32, lam=1.0, min_child_weight=1.0,
              feat_mask=mask)
    h_r, bg_r, bf_r, bs_r = ops.level_split(bins, g, h, node, force="ref", **kw)
    for force in ("kernel", None):
        out = ops.level_split(bins, g, h, node, force=force, **kw)
        _, bg, bf, bs = out
        if force is None:
            assert bool((bf == bf_r).all() and (bs == bs_r).all())
        _assert_decisions(h_r, bg_r, out, n_bins=32, lam=1.0,
                          min_child_weight=1.0, feat_mask=mask)
        real = np.isfinite(np.asarray(bg))
        assert bool(np.asarray(mask)[np.asarray(bf)[real]].all())


def test_level_split_subtraction_bit_equality_integer_stats(rng):
    """With integer-valued g/h every histogram sum is exact in f32, so
    ``parent − small`` is genuinely bit-equal to the direct build — this
    pins the subtraction indexing/assembly (smaller-child choice, row
    compaction, sibling interleave) with zero float slack, on both the XLA
    fallback and the fused kernel."""
    r, f, nb, nn = 600, 5, 32, 16
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = jnp.asarray(rng.integers(-8, 9, size=r), jnp.float32)
    h = jnp.asarray(rng.integers(1, 5, size=r), jnp.float32)
    node = jnp.asarray(rng.integers(0, nn, size=(r,)), jnp.int32)
    kw = dict(n_nodes=nn, n_bins=nb, lam=1.0, min_child_weight=1.0)
    parent = _parent_of(bins, g, h, node, nn, nb)
    hd, _, _, _ = ops.level_split(bins, g, h, node, **kw)
    for force in (None, "kernel"):
        hs, _, _, _ = ops.level_split(bins, g, h, node, parent_hist=parent,
                                      force=force, **kw)
        assert bool((np.asarray(hs) == np.asarray(hd)).all())


def test_level_split_empty_sibling_exact(rng):
    """Sentinel-split parents route every row LEFT, so the right child is
    empty and subtraction returns ``parent − 0`` — bit-exact even with
    real-valued g/h. This is what keeps depth_limit-padded levels identical
    between the subtraction and direct paths."""
    r, f, nb, nn = 300, 4, 16, 8
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    g = _rand(rng, (r,), jnp.float32)
    h = jnp.abs(_rand(rng, (r,), jnp.float32)) + 0.1
    node = jnp.asarray(2 * rng.integers(0, nn // 2, size=r), jnp.int32)  # even
    kw = dict(n_nodes=nn, n_bins=nb, lam=1.0, min_child_weight=1.0)
    parent = _parent_of(bins, g, h, node, nn, nb)
    hd, _, _, _ = ops.level_split(bins, g, h, node, **kw)
    for force in (None, "kernel"):
        hs, _, _, _ = ops.level_split(bins, g, h, node, parent_hist=parent,
                                      force=force, **kw)
        assert bool((np.asarray(hs) == np.asarray(hd)).all())


def test_level_split_return_hist_false_same_decisions(rng):
    bins, g, h, node = _level_fixture(rng, 200, 5, 16, 4)
    kw = dict(n_nodes=4, n_bins=16, lam=1.0, min_child_weight=1.0)
    for force in ("kernel", None, "ref"):
        full = ops.level_split(bins, g, h, node, force=force, **kw)
        slim = ops.level_split(bins, g, h, node, force=force,
                               return_hist=False, **kw)
        assert slim[0] is None
        for a, b in zip(full[1:], slim[1:]):
            assert bool((np.asarray(a) == np.asarray(b)).all())


def test_level_split_kernel_under_vmap(rng):
    """The fused-batch path vmaps build_tree over traced scalars; the
    kernel must map correctly over a batch of (g, h, node, lam)."""
    r, f, nb, nn, b = 160, 4, 16, 4, 3
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    gs = jnp.asarray(rng.normal(size=(b, r)), jnp.float32)
    hs = jnp.asarray(rng.random((b, r)) + 0.1, jnp.float32)
    nodes = jnp.asarray(rng.integers(0, nn, size=(b, r)), jnp.int32)
    lams = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)

    def one(g, h, node, lam, force):
        return ops.level_split(bins, g, h, node, n_nodes=nn, n_bins=nb,
                               lam=lam, min_child_weight=1.0, force=force)

    out_k = jax.vmap(lambda g, h, n, l: one(g, h, n, l, "kernel"))(
        gs, hs, nodes, lams)
    out_r = jax.vmap(lambda g, h, n, l: one(g, h, n, l, "ref"))(
        gs, hs, nodes, lams)
    np.testing.assert_allclose(np.asarray(out_k[0]), np.asarray(out_r[0]),
                               atol=1e-4)
    for i in range(b):
        _assert_decisions(out_r[0][i], out_r[1][i],
                          [o[i] for o in out_k], n_bins=nb, lam=float(lams[i]),
                          min_child_weight=1.0)


@pytest.mark.parametrize("depth,nb", [(1, 16), (3, 64), (6, 256), (6, 16)])
def test_build_tree_subtraction_parity(rng, depth, nb):
    """The acceptance grid: build_tree with histogram subtraction (the
    training default) is bit-identical — feat, split, leaf sums — to the
    pre-subtraction direct path, across depths × bin counts."""
    from repro.tabular.gbdt import build_tree

    r, f = 600, 8
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 2, size=r), jnp.float32)
    p = jax.nn.sigmoid(jnp.asarray(rng.normal(size=r), jnp.float32))
    g, h = p - y, jnp.maximum(p * (1 - p), 1e-16)

    import functools as ft
    run = lambda sub: jax.jit(ft.partial(
        build_tree, n_bins=nb, max_depth=depth, lam=1.0, gamma=0.0,
        min_child_weight=1.0, subtract=sub))(bins, g, h)
    for a, b in zip(run(True), run(False)):
        assert bool((np.asarray(a) == np.asarray(b)).all())


def test_build_tree_subtraction_parity_traced_limits_and_mask(rng):
    """Same bit-identity with the fused-batch knobs engaged: traced
    depth_limit/bin_limit plus a forest-style feature mask."""
    from repro.tabular.gbdt import build_tree

    r, f, nb, depth = 500, 10, 64, 5
    bins = jnp.asarray(rng.integers(0, nb, size=(r, f)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 2, size=r), jnp.float32)
    p = jax.nn.sigmoid(jnp.asarray(rng.normal(size=r), jnp.float32))
    g, h = p - y, jnp.maximum(p * (1 - p), 1e-16)
    mask = jnp.asarray(np.arange(f) % 2 == 0)

    def make(sub):
        @jax.jit
        def run(dlim, blim):
            return build_tree(
                bins, g, h, n_bins=nb, max_depth=depth, lam=jnp.float32(1.0),
                gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0),
                feat_mask=mask, depth_limit=dlim, bin_limit=blim,
                subtract=sub)
        return run

    run_sub, run_dir = make(True), make(False)
    for dlim, blim in ((jnp.int32(3), jnp.int32(32)),
                       (jnp.int32(5), jnp.int32(64))):
        for a, b in zip(run_sub(dlim, blim), run_dir(dlim, blim)):
            assert bool((np.asarray(a) == np.asarray(b)).all())
        # structural masking honoured: no split bin past the traced limit
        split = np.asarray(run_sub(dlim, blim)[1])
        assert bool(((split < int(blim) - 1) | (split == nb - 1)).all())
