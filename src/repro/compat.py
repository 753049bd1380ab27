"""Thin SPMD helpers over the installed JAX (0.9): the ambient-mesh
context, ``shard_map`` with optional manual axes, and the per-shard launcher
the row-sharded tabular families run through.
"""
from __future__ import annotations

import jax

__all__ = ["set_mesh", "shard_map", "sharded_call"]

#: context manager binding a mesh as the ambient mesh
set_mesh = jax.set_mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True, axis_names=None):
    """``jax.shard_map``; ``axis_names`` (the manual axes) defaults to all."""
    kw = {"axis_names": set(axis_names)} if axis_names is not None else {}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def sharded_call(f, *, n_shards, axis="shards", mesh=None):
    """SPMD launcher for per-shard functions over leading-axis-stacked args.

    ``f`` receives ONE shard's block per program instance (arrays whose
    leading axis is the shard axis arrive with it stripped) and may use
    ``jax.lax.psum(..., axis)`` to combine across shards; its outputs must
    be shard-invariant (i.e. already reduced). The returned callable takes
    the stacked ``(n_shards, ...)`` arrays and returns the un-stacked,
    shard-invariant outputs.

    Two lowering paths, mathematically the same program:

    * ``mesh`` with a matching ``axis`` of size ``n_shards`` — real SPMD
      via :func:`shard_map`, one device per shard (the multi-device lane);
    * otherwise — ``jax.vmap`` with ``axis_name=axis``, a single-device
      virtual sharding in which ``psum`` sums over the mapped axis. This
      is the path every single-device session (and tier-1) takes.
    """
    mesh_axes = dict(getattr(mesh, "shape", None) or {}) if mesh is not None else {}
    if mesh_axes.get(axis) == n_shards:
        from jax.sharding import PartitionSpec as P

        def per_device(*args):
            # shard_map hands each device a (1, ...) block; strip it so f
            # sees exactly the per-shard view the vmap path provides
            squeezed = jax.tree.map(lambda a: a[0], args)
            return f(*squeezed)

        return shard_map(per_device, mesh=mesh, in_specs=P(axis),
                         out_specs=P(), check_vma=False)

    def virtual(*args):
        out = jax.vmap(f, axis_name=axis)(*args)
        # outputs are shard-invariant: every shard's copy is identical
        return jax.tree.map(lambda o: o[0], out)

    return virtual
