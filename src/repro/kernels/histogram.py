"""GBDT split-finding hot path as a Pallas TPU kernel.

The paper's dominant workload is gradient-boosted trees (864 of its 1,211
search tasks run XGBoost); histogram construction is the per-level hot spot
of histogram-based GBDT training. On GPU this is a scatter-add into shared
memory with atomics; TPU has no fast scatter, so we ADAPT the algorithm to
the MXU: the scatter becomes ``(one-hot(node) ⊙ grad)ᵀ @ one-hot(bin)``, a
dense matmul per (feature-block, row-block) tile (DESIGN.md §2, §3.8).

Layout (lane-dense, what the chip's compiler accepts):

* feature·bin is flattened onto the LANE axis: lane ``j`` of a feature block
  is bin ``j mod B`` of feature ``j div B``, and a block is a multiple of
  128 lanes wide. Nodes sit on sublanes, padded to a multiple of 8;
* g and h are separate ``(nodes, F·B)`` planes, never a trailing size-2 axis;
* rows arrive as ``(rows, F)`` bf16 bin ids (exact: ids < 256) plus
  lane-major ``(1, rows)`` node / g / h vectors. The bin one-hot of a row
  block is built on the MXU: ``bins @ E`` (E the 0/1 feature→lane
  expansion) copies each row's bin id onto its feature's lanes, and one
  compare against the lane's bin id gives the one-hot — no 3-D reshapes.
  A feature block reads only its own column group of ``bins``
  (:func:`_bins_group`): all columns where there are at most 128 features
  (about one 128-deep MXU pass), else the aligned 128-column group holding
  the block, so E is never much deeper than the block needs;
* f32 statistics reach the MXU as three bf16 parts (hi + mid + lo = the
  f32 value), each product with the exact 0/1 one-hot is exact, and the MXU
  accumulates in f32 — f32-accurate sums with no reliance on the matmul
  precision default.

One kernel, :func:`fused_level_split_tpu`, is the training hot path: the
accumulate PLUS the split scan (segmented lane cumsum → gain → masked
first-max), so only ``(best_gain, best_feat, best_split)`` per node (and,
when the caller caches parents for histogram subtraction, the level's
histograms) leave VMEM. Fed the smaller-child rows (every row with a masked
node id, or those rows gathered, as ``ops.level_rows`` picks) and the cached
parent histograms it derives each sibling as ``parent − small`` before the
scan. :func:`histogram_tpu` is the same kernel returning histograms only.

Grid: ``(feature_blocks, row_blocks)``, rows minor-most, so the accumulators
live in VMEM scratch across the sequential row sweep and the scan runs once
per feature block at the last row block; per-node bests combine across
feature blocks with a strict ``>`` (earlier block wins ties). The scan's
summation order differs from the XLA oracle's, so a split whose gain ties
the best within float rounding may be chosen instead of the oracle's —
the contract (DESIGN.md §3.8) is that the chosen split's gain, evaluated on
the reference histogram, equals the best reference gain within tolerance.

Oracles: :func:`repro.kernels.ref.histogram_ref` /
:func:`repro.kernels.ref.level_split_ref`. Dispatch: ``ops.histogram`` /
``ops.level_split``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["histogram_tpu", "fused_level_split_tpu", "pick_tiles",
           "MAX_KERNEL_BINS"]

#: bin ids travel as bf16, exact for integers up to 256
MAX_KERNEL_BINS = 256

_LANES = 128
_SUBLANES = 8
#: rows per grid step; on the chip the lane-major node/g/h blocks round it
#: up to a multiple of 128
_ROW_TILE = 256
#: what :func:`pick_tiles` lets one grid step's buffers take (estimated by
#: :func:`_vmem_bytes`), and the scoped-VMEM limit handed to the compiler
#: (v5e has 128 MiB of VMEM per core; the default scoped limit is 16 MiB)
_VMEM_BUDGET = 24 << 20
_VMEM_LIMIT = 64 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _feature_step(n_bins: int) -> int:
    """Smallest feature count whose ``features · n_bins`` is lane-aligned."""
    return _LANES // math.gcd(n_bins, _LANES)


def _bins_group(n_features: int, block_f: int) -> int:
    """Width of the column group of ``bins`` that one feature block reads:
    every column where one block holds all features or there are at most
    128 (about one 128-deep MXU pass); otherwise (``block_f`` then a power
    of two below 128 or a multiple of 128, see :func:`pick_tiles`) the
    aligned group of ``max(block_f, 128)`` columns that holds the block."""
    fp = _round_up(n_features, block_f)
    if block_f == fp or n_features <= _LANES:
        return fp
    return max(block_f, _LANES)


def _vmem_bytes(block_f: int, n_bins: int, n_nodes: int, block_rows: int,
                group: int) -> int:
    """Upper estimate of one grid step's VMEM (worst case: subtraction mode
    with the histogram output on), counted from the buffer shapes; ``group``
    is the :func:`_bins_group` width."""
    w = block_f * n_bins
    n_pad = _round_up(max(1, n_nodes // 2), _SUBLANES)   # accumulated rows
    rb = _round_up(block_rows, _LANES)
    return (
        2 * rb * _round_up(group, _LANES) * 2     # bins blocks, double-buffered
        + _round_up(group, 16) * w * 2            # feature→lane expansion E
        + rb * w * 10                             # replicated ids + one-hot
        + 6 * n_pad * rb * 6                      # split stats, f32 + bf16
        + 6 * n_pad * w * 4                       # matmul result
        + 2 * n_pad * w * 4                       # g/h accumulators
        + 2 * 2 * 2 * 2 * n_pad * w * 4           # histogram out, 2 sides
        + 2 * 2 * n_pad * w * 4                   # cached parent in
        + 16 * 2 * n_pad * w * 4                  # split-scan temporaries
    )


def pick_tiles(n_features: int, n_bins: int, n_rows: int,
               n_nodes: int = 1) -> tuple[int, int]:
    """(block_features, block_rows) for a level shape.

    ``block_features · n_bins`` is always a multiple of 128 lanes; the block
    is the widest that fits :data:`_VMEM_BUDGET` (:func:`_vmem_bytes`), so
    deep levels (large ``n_nodes``) and wide bins take narrower blocks.
    Past 128 features a split block is a multiple of 128 or a power of two
    below it, so it lies inside one aligned bins column group
    (:func:`_bins_group`). ``block_rows`` is :data:`_ROW_TILE` clamped to
    ``n_rows`` — the honest tile; the chip path pads it up to lane
    alignment."""
    step = _feature_step(n_bins)
    block_r = max(1, min(_ROW_TILE, n_rows))
    whole = _round_up(n_features, step)
    if whole <= _LANES:
        sizes = list(range(whole, 0, -step))
    else:
        sizes = [whole] + list(range((whole - 1) // _LANES * _LANES, 0,
                                     -_LANES))
        sizes += [1 << k for k in range(6, -1, -1) if (1 << k) >= step]
    for block_f in sizes:
        if _vmem_bytes(block_f, n_bins, n_nodes, block_r, _bins_group(
                n_features, block_f)) <= _VMEM_BUDGET:
            break
    return block_f, block_r


def _segment_cumsum(x, lane_bin, n_bins: int):
    """Inclusive prefix sum along lanes within each feature's ``n_bins``
    segment (Hillis–Steele: log2(B) lane rotations)."""
    s = 1
    while s < n_bins:
        x = x + jnp.where(lane_bin >= s, pltpu.roll(x, s, 1), 0.0)
        s *= 2
    return x


def _level_kernel(
    bins_ref, node_ref, g_ref, h_ref, lanes_ref, fmask_ref, sil_ref,
    parent_ref, scal_ref, *rest,
    n_pad: int, n_bins: int, n_rblocks: int, per_group: int, subtract: bool,
    return_hist: bool,
):
    if return_hist:
        hist_ref, bg_ref, bf_ref, bs_ref, e_ref, acc_g, acc_h, tot_g, tot_h = rest
    else:
        hist_ref = None
        bg_ref, bf_ref, bs_ref, e_ref, acc_g, acc_h, tot_g, tot_h = rest
    fi = pl.program_id(0)
    ri = pl.program_id(1)
    f32, bf16 = jnp.float32, jnp.bfloat16

    @pl.when(ri == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_h[...] = jnp.zeros_like(acc_h)
        # E[k, j] = 1 where lane j belongs to feature k of this block's
        # bins column group (per_group feature blocks share a group)
        gw, w = e_ref.shape
        row_feat = (jax.lax.broadcasted_iota(jnp.int32, (gw, w), 0)
                    + (fi // per_group) * gw)
        e_ref[...] = (row_feat.astype(f32) == lanes_ref[0:1, :]).astype(bf16)

    # one-hot(bin) (rb, W): each row's bin id copied onto its feature's
    # lanes (exact: one 0/1 term per output), compared with the lane's bin
    rep = jnp.dot(bins_ref[...], e_ref[...], preferred_element_type=f32)
    onehot = (rep == lanes_ref[1:2, :]).astype(bf16)

    # (one-hot(node) ⊙ stat)ᵀ as hi/mid/lo bf16 parts, g then h: (6·N, rb)
    rb = node_ref.shape[1]
    hit = jax.lax.broadcasted_iota(jnp.int32, (n_pad, rb), 0) == node_ref[...]
    parts = []
    for stat_ref in (g_ref, h_ref):
        a = jnp.where(hit, stat_ref[...], 0.0)
        for _ in range(3):
            p = a.astype(bf16).astype(f32)
            parts.append(p)
            a = a - p
    lhs = jnp.concatenate(parts, axis=0).astype(bf16)
    res = jnp.dot(lhs, onehot, preferred_element_type=f32)   # (6·N, W)
    n = n_pad
    acc_g[...] += (res[0:n] + res[n:2 * n]) + res[2 * n:3 * n]
    acc_h[...] += (res[3 * n:4 * n] + res[4 * n:5 * n]) + res[5 * n:6 * n]

    @pl.when(ri == n_rblocks - 1)
    def _flush():
        lane_feat = lanes_ref[0:1, :]
        lane_bin = lanes_ref[1:2, :]
        lam = scal_ref[0, 0]
        mcw = scal_ref[0, 1]
        last = scal_ref[0, 2] - 1.0
        # feature subset (and this wrapper's padded features), and no split
        # at the last valid bin — it sends every row left
        lane_ok = (fmask_ref[...] > 0) & (lane_bin < last)
        g, h = acc_g[...], acc_h[...]
        if subtract:
            # accumulated = the SMALLER child of each sibling pair; the
            # other is parent − small. Side 0 = left children (node 2p),
            # side 1 = right (node 2p+1)
            sil = sil_ref[...] > 0                       # (N, 1)
            gb = parent_ref[0] - g
            hb = parent_ref[1] - h
            sides = ((jnp.where(sil, g, gb), jnp.where(sil, h, hb)),
                     (jnp.where(sil, gb, g), jnp.where(sil, hb, h)))
        else:
            sides = ((g, h),)
        for s, (gs, hs) in enumerate(sides):
            rows = pl.ds(s * n_pad, n_pad)
            if hist_ref is not None:
                hist_ref[0, s] = gs
                hist_ref[1, s] = hs

            @pl.when(fi == 0)
            def _totals():
                # node totals from feature 0 (the oracle's convention);
                # block 0 owns it, later blocks reuse the stash
                f0 = lane_feat == 0.0
                tot_g[rows, :] = jnp.sum(jnp.where(f0, gs, 0.0), axis=1,
                                         keepdims=True)
                tot_h[rows, :] = jnp.sum(jnp.where(f0, hs, 0.0), axis=1,
                                         keepdims=True)

            gl = _segment_cumsum(gs, lane_bin, n_bins)
            hl = _segment_cumsum(hs, lane_bin, n_bins)
            gt = tot_g[rows, :]
            ht = tot_h[rows, :]
            gr = gt - gl
            hr = ht - hl
            gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam)
            ok = lane_ok & (hl >= mcw) & (hr >= mcw)
            gain = jnp.where(ok, gain, -jnp.inf)
            loc_gain = jnp.max(gain, axis=1, keepdims=True)          # (N, 1)
            # first maximum in lane order = lowest (feature, bin)
            tie = gain == loc_gain
            big = jnp.float32(1e9)
            loc_feat = jnp.min(jnp.where(tie, lane_feat, big), axis=1,
                               keepdims=True)
            loc_split = jnp.min(
                jnp.where(tie & (lane_feat == loc_feat), lane_bin, big),
                axis=1, keepdims=True)
            loc_feat = loc_feat.astype(jnp.int32)
            loc_split = loc_split.astype(jnp.int32)

            @pl.when(fi == 0)
            def _first():
                bg_ref[rows, :] = loc_gain
                bf_ref[rows, :] = loc_feat
                bs_ref[rows, :] = loc_split

            @pl.when(fi > 0)
            def _combine():
                prev = bg_ref[rows, :]
                better = loc_gain > prev
                bg_ref[rows, :] = jnp.where(better, loc_gain, prev)
                bf_ref[rows, :] = jnp.where(better, loc_feat, bf_ref[rows, :])
                bs_ref[rows, :] = jnp.where(better, loc_split, bs_ref[rows, :])


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "interpret", "return_hist"),
)
def fused_level_split_tpu(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    node: jax.Array,
    *,
    n_nodes: int,
    n_bins: int,
    lam,
    min_child_weight,
    bin_limit=None,
    feat_mask: jax.Array | None = None,
    parent_hist: jax.Array | None = None,
    small_is_left: jax.Array | None = None,
    interpret: bool = False,
    return_hist: bool = True,
):
    """One GBDT tree level fused in VMEM; see ``ref.level_split_ref``.

    Direct mode (``parent_hist=None``): ``node`` holds each row's node in
    ``[0, n_nodes)`` and the kernel accumulates all ``n_nodes`` histograms.
    Subtraction mode: ``node`` holds the PARENT id in ``[0, n_nodes/2)`` of
    each row of the SMALLER child of its sibling pair and the dump id
    ``n_nodes/2`` on every other row, which lands in a padded accumulator
    row or matches none and is dropped. The caller (``ops.level_split``)
    passes every row in place, or only the smaller children's rows gathered
    into R/2 slots where the one-hot is wide enough for that to pay
    (``ops.level_rows``); the sums are the same. ``parent_hist`` holds the
    cached ``(n_nodes/2, F, B, 2)`` level-above histograms and
    ``small_is_left[p]`` whether pair p's smaller child is the left one; the
    kernel accumulates only the half-size small-child histograms and
    derives siblings as ``parent − small``.

    ``lam``/``min_child_weight`` may be traced 0-d arrays, ``bin_limit`` a
    traced int — they ride in SMEM. Returns ``(hist | None, best_gain,
    best_feat, best_split)``: ``hist`` is ``(n_nodes, F, B, 2)``, the
    per-node bests are ``(n_nodes,)`` arrays. ``n_bins`` must not exceed
    :data:`MAX_KERNEL_BINS`.
    """
    if n_bins > MAX_KERNEL_BINS:
        raise ValueError(f"n_bins={n_bins} > {MAX_KERNEL_BINS}: bin ids "
                         "would not be exact in bf16")
    r, f = bins.shape
    subtract = parent_hist is not None
    n_sides = 2 if subtract else 1
    n_acc = n_nodes // n_sides
    n_pad = _round_up(n_acc, _SUBLANES)
    block_features, block_rows = pick_tiles(f, n_bins, r, n_nodes)
    if not interpret:
        # lane-major (1, rows) blocks: a multiple of 128 rows per step
        block_rows = _round_up(block_rows, _LANES)
    fp = _round_up(f, block_features)
    w = block_features * n_bins
    rp = _round_up(r, block_rows)
    pad_r = rp - r
    f32 = jnp.float32

    group = _bins_group(f, block_features)
    per_group = group // block_features
    bins_p = jnp.pad(bins.astype(jnp.bfloat16),
                     ((0, pad_r), (0, _round_up(fp, group) - f)))
    node_p = jnp.pad(node.astype(jnp.int32), (0, pad_r),
                     constant_values=n_pad)[None, :]
    g_p = jnp.pad(grad.astype(f32), (0, pad_r))[None, :]
    h_p = jnp.pad(hess.astype(f32), (0, pad_r))[None, :]
    lane = jnp.arange(fp * n_bins, dtype=jnp.int32)
    lanes = jnp.stack([lane // n_bins, lane % n_bins]).astype(f32)  # (2, Fp·B)
    fm = (jnp.ones((f,), f32) if feat_mask is None
          else feat_mask.astype(f32))
    fmask = jnp.repeat(jnp.pad(fm, (0, fp - f)), n_bins)[None, :]
    scal = jnp.stack([
        jnp.asarray(lam, f32), jnp.asarray(min_child_weight, f32),
        jnp.asarray(n_bins if bin_limit is None else bin_limit, f32),
    ])[None, :]
    if subtract:
        sil = jnp.pad(small_is_left.astype(f32), (0, n_pad - n_acc))[:, None]
        planes = jnp.moveaxis(parent_hist.astype(f32), 3, 0)     # (2, N, F, B)
        parent = jnp.pad(planes, ((0, 0), (0, n_pad - n_acc), (0, fp - f),
                                  (0, 0))).reshape(2, n_pad, fp * n_bins)
        sil_spec = pl.BlockSpec((n_pad, 1), lambda fi, ri: (0, 0))
        parent_spec = pl.BlockSpec((2, n_pad, w), lambda fi, ri: (0, 0, fi))
    else:
        sil = jnp.zeros((_SUBLANES, 1), f32)
        parent = jnp.zeros((1, _SUBLANES, _LANES), f32)
        sil_spec = pl.BlockSpec((_SUBLANES, 1), lambda fi, ri: (0, 0))
        parent_spec = pl.BlockSpec((1, _SUBLANES, _LANES),
                                   lambda fi, ri: (0, 0, 0))
    grid = (fp // block_features, rp // block_rows)
    row_vec = pl.BlockSpec((1, block_rows), lambda fi, ri: (0, ri))
    lane_spec = lambda k: pl.BlockSpec((k, w), lambda fi, ri: (0, fi))  # noqa: E731
    best_spec = pl.BlockSpec((n_sides * n_pad, 1), lambda fi, ri: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((n_sides * n_pad, 1), f32),
        jax.ShapeDtypeStruct((n_sides * n_pad, 1), jnp.int32),
        jax.ShapeDtypeStruct((n_sides * n_pad, 1), jnp.int32),
    ]
    out_specs = [best_spec, best_spec, best_spec]
    if return_hist:
        out_shape.insert(0, jax.ShapeDtypeStruct(
            (2, n_sides, n_pad, fp * n_bins), f32))
        out_specs.insert(0, pl.BlockSpec((2, n_sides, n_pad, w),
                                         lambda fi, ri: (0, 0, 0, fi)))
    out = pl.pallas_call(
        functools.partial(
            _level_kernel, n_pad=n_pad, n_bins=n_bins,
            n_rblocks=grid[1], per_group=per_group, subtract=subtract,
            return_hist=return_hist),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, group),
                         lambda fi, ri: (ri, fi // per_group)),
            row_vec, row_vec, row_vec,
            lane_spec(2), lane_spec(1),
            sil_spec, parent_spec,
            pl.BlockSpec((1, 3), lambda fi, ri: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((group, w), jnp.bfloat16),
            pltpu.VMEM((n_pad, w), f32),
            pltpu.VMEM((n_pad, w), f32),
            pltpu.VMEM((n_sides * n_pad, 1), f32),
            pltpu.VMEM((n_sides * n_pad, 1), f32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the device op's name in a trace, whatever program calls the kernel
        name="fused_level_split_tpu",
    )(bins_p, node_p, g_p, h_p, lanes, fmask, sil, parent, scal)

    def heap(x):
        # (sides·N_pad, 1) → (n_nodes,) in heap order: node = n_sides·p + s
        return x.reshape(n_sides, n_pad)[:, :n_acc].T.reshape(-1)

    bg, bf, bs = (heap(o) for o in out[-3:])
    hist = None
    if return_hist:
        h5 = out[0].reshape(2, n_sides, n_pad, fp, n_bins)[:, :, :n_acc, :f]
        hist = h5.transpose(2, 1, 3, 4, 0).reshape(n_nodes, f, n_bins, 2)
    return hist, bg, bf, bs


def histogram_tpu(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    node: jax.Array,
    *,
    n_nodes: int,
    n_bins: int,
    interpret: bool = False,
) -> jax.Array:
    """Per-(node, feature, bin) grad/hess sums; see ``histogram_ref``.
    The fused level kernel with its decisions dropped."""
    hist, _, _, _ = fused_level_split_tpu(
        bins, grad, hess, node, n_nodes=n_nodes, n_bins=n_bins, lam=1.0,
        min_child_weight=0.0, interpret=interpret)
    return hist
