"""The GBDT level kernel compiled for a TPU v5e that is described, not
attached: the chip's own compiler refuses misaligned tiles, VMEM overruns
and unsupported kernel bodies here, at no chip time. Nothing runs, so these
tests say nothing about results or speed (``chip_smoke.py`` does, on a
chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.histogram import fused_level_split_tpu

#: HBM of one v5e chip
_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a chip compile written to the persistent cache cannot be read back
        # without a chip; keep these compiles out of it
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _level_shapes(sharding, r, f, b, n_nodes, subtract, batch=None):
    lead = () if batch is None else (batch,)

    def s(shape, dtype, batched=True):
        return jax.ShapeDtypeStruct((lead if batched else ()) + shape, dtype,
                                    sharding=sharding)

    n_parent = max(1, n_nodes // 2)
    return (s((r, f), jnp.int32, batched=False), s((r,), jnp.float32),
            s((r,), jnp.float32), s((r,), jnp.int32),
            s((n_parent, f, b, 2), jnp.float32), s((n_parent,), jnp.bool_),
            s((f,), jnp.bool_), s((), jnp.float32))


def _compile_level(sharding, r, f, b, n_nodes, subtract, return_hist,
                   batch=None):
    def level(bins, g, h, node, parent, sil, fmask, lam):
        return fused_level_split_tpu(
            bins, g, h, node, n_nodes=n_nodes, n_bins=b, lam=lam,
            min_child_weight=1.0, feat_mask=fmask,
            parent_hist=parent if subtract else None,
            small_is_left=sil if subtract else None, return_hist=return_hist)

    fn = level if batch is None else jax.vmap(level, in_axes=(None,) + (0,) * 7)
    compiled = jax.jit(fn).lower(
        *_level_shapes(sharding, r, f, b, n_nodes, subtract, batch)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < _HBM_BYTES
    return compiled


# HIGGS at 1M rows × 28 features and SECOM at its published 1,567 × 590:
# the root level direct, a deep level by subtraction, with and without the
# cached histograms
@pytest.mark.parametrize("r,f,b,n_nodes,subtract,return_hist", [
    (1_048_576, 28, 64, 1, False, True),
    (1_048_576, 28, 256, 32, True, True),
    (1_567, 590, 256, 1, False, True),
    (1_567, 590, 64, 32, True, False),
    (1_567, 590, 32, 512, True, False),
])
def test_level_kernel_compiles_for_v5e(one_chip, r, f, b, n_nodes, subtract,
                                       return_hist):
    _compile_level(one_chip, r, f, b, n_nodes, subtract, return_hist)


def test_level_kernel_compiles_for_v5e_under_vmap(one_chip):
    """A fused batch of 4 configs: per-config g/h/node/λ/feature mask over
    shared bins, as ``train_batched`` vmaps ``build_tree``."""
    _compile_level(one_chip, 600_000, 28, 128, 8, True, True, batch=4)


@pytest.mark.parametrize("b,n_nodes,return_hist", [
    (b, n, rh) for b in (32, 128) for n in (2, 32) for rh in (True, False)])
def test_masked_level_compiles_for_v5e(one_chip, b, n_nodes, return_hist):
    """A level below the root as ``ops.level_split`` runs it at HIGGS width
    on the chip: every one of 600,000 rows where it lies, the larger
    children's under the dump id, into the subtraction kernel."""
    assert ops.level_rows(28, b, force="kernel") == "all"

    def level(bins, g, h, node, parent, _sil, fmask, lam):
        sil, _, snode = ops._plan_smaller_child(node, n_nodes, compact=False)
        return fused_level_split_tpu(
            bins, g, h, snode, n_nodes=n_nodes, n_bins=b, lam=lam,
            min_child_weight=1.0, feat_mask=fmask, parent_hist=parent,
            small_is_left=sil, return_hist=return_hist)

    text = jax.jit(level).lower(
        *_level_shapes(one_chip, 600_000, 28, b, n_nodes, True)).compile(
        ).as_text()
    assert "tpu_custom_call" in text
    assert "[300000" not in text        # no row is gathered into R/2 slots


def test_level_kernel_names_its_own_device_op(one_chip):
    """The kernel's custom call is named ``fused_level_split_tpu`` (the name
    a trace shows, which the benchmark's kernel metrics match) by the kernel
    itself, not after the program that calls it."""
    def some_caller(bins, g, h, node, parent, sil, fmask, lam):
        return fused_level_split_tpu.__wrapped__(
            bins, g, h, node, n_nodes=8, n_bins=64, lam=lam,
            min_child_weight=1.0, feat_mask=fmask, parent_hist=parent,
            small_is_left=sil, return_hist=False)

    text = jax.jit(some_caller).lower(
        *_level_shapes(one_chip, 4096, 28, 64, 8, True)).compile().as_text()
    calls = re.findall(r"^\s*%?([\w.\-]+) = .* custom-call\(", text, re.M)
    assert calls and all(re.match(r"fused_level_split_tpu(\.\d+)?$", c)
                         for c in calls), calls


def test_class_axis_level_compiles_for_v5e(one_chip):
    """One level of all 7 trees of a Covertype round, as softmax GBDT runs
    it: the class axis mapped over ``ops.level_split``'s masked path (every
    one of 348,607 rows where it lies, 54 features at 128 bins), the bin
    matrix shared — one kernel launch for the seven trees."""
    r, f, b, n_nodes, k = 348_607, 54, 128, 8, 7
    assert ops.level_rows(f, b, force="kernel") == "all"

    def level(bins, g, h, node, parent, _sil, fmask, lam):
        sil, _, snode = ops._plan_smaller_child(node, n_nodes, compact=False)
        return fused_level_split_tpu(
            bins, g, h, snode, n_nodes=n_nodes, n_bins=b, lam=lam,
            min_child_weight=1.0, feat_mask=fmask, parent_hist=parent,
            small_is_left=sil, return_hist=True)

    classes = jax.vmap(level, in_axes=(None, 0, 0, 0, 0, 0, None, None))
    shapes = list(_level_shapes(one_chip, r, f, b, n_nodes, True, batch=k))
    shapes[6] = jax.ShapeDtypeStruct((f,), jnp.bool_, sharding=one_chip)
    shapes[7] = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = jax.jit(classes).lower(*shapes).compile().as_text()
    # one kernel launch, its outputs carrying the seven trees
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"fused_level_split_tpu[.\d]* = \(f32\[7,", text)
