"""Distribution-layer tests. Multi-device cases run in SUBPROCESSES with
``--xla_force_host_platform_device_count`` so the main test process keeps
the single-device view (the smoke-test contract)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

# Multi-device SPMD compiles take minutes each on a CPU host; they run in
# the nightly/heavy CI lane (ci.yml) rather than every tier-1 invocation.
heavy = pytest.mark.skipif(
    os.environ.get("REPRO_HEAVY_TESTS") != "1",
    reason="multi-device subprocess test (minutes of XLA CPU compile); "
           "set REPRO_HEAVY_TESTS=1 to run",
)

from repro import configs
from repro.distributed import sharding as shd
from repro.models import init_params
from repro.train import make_optimizer
from repro.train.train_step import make_train_state_specs, opt_pspecs


def run_subprocess(code: str, devices: int = 8) -> str:
    """Run a python snippet with N fake host devices; returns stdout."""
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
            # the child must never reach for a chip its parent may hold
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin",
            "HOME": "/root",
        },
    )
    assert res.returncode == 0, f"subprocess failed:\n{res.stderr[-3000:]}"
    return res.stdout


# ---------------------------------------------------------------------------
# sharding rules (no devices needed — pure pspec logic)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_param_pspecs_cover_every_leaf(arch):
    cfg = configs.get_smoke_config(arch)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = shd.param_pspecs(shapes, fsdp=True)
    n_leaves = len(jax.tree.leaves(shapes))
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(spec_leaves) == n_leaves
    for leaf, spec in zip(jax.tree.leaves(shapes), spec_leaves):
        assert len(spec) <= len(leaf.shape)
        used = [a for a in spec if a is not None]
        assert len(used) == len(set(used)), f"axis reused in {spec}"


def test_embed_and_ffn_rules():
    shapes = {
        "embed": jax.ShapeDtypeStruct((1024, 64), jnp.float32),
        "blocks": {"b0": {"ffn": {
            "w_gate": jax.ShapeDtypeStruct((2, 64, 256), jnp.float32),
            "w_down": jax.ShapeDtypeStruct((2, 256, 64), jnp.float32),
        }}},
    }
    specs = shd.param_pspecs(shapes, fsdp=False)
    assert specs["embed"] == P("tp", None)
    assert specs["blocks"]["b0"]["ffn"]["w_gate"] == P(None, None, "tp")
    assert specs["blocks"]["b0"]["ffn"]["w_down"] == P(None, "tp", None)


def test_zero1_shards_largest_free_dim():
    shapes = {"w": jax.ShapeDtypeStruct((64, 512), jnp.float32)}
    specs = {"w": P(None, "tp")}
    z = shd.zero1_pspecs(specs, shapes, data_size=16)
    assert z["w"] == P("dp", "tp")
    # not divisible → untouched
    shapes2 = {"w": jax.ShapeDtypeStruct((7, 13), jnp.float32)}
    z2 = shd.zero1_pspecs({"w": P(None, None)}, shapes2, data_size=16)
    assert z2["w"] == P(None, None)


def test_opt_pspecs_adafactor_drops_dims():
    shapes = {"w": jax.ShapeDtypeStruct((64, 512), jnp.float32),
              "b": jax.ShapeDtypeStruct((512,), jnp.float32)}
    p_specs = {"w": P("dp", "tp"), "b": P("tp")}
    o = opt_pspecs("adafactor", p_specs, shapes)
    assert o["w"]["row"] == P("dp")
    assert o["w"]["col"] == P("tp")
    assert o["b"]["v"] == P("tp")


def test_logical_to_mesh_multipod_tuples():
    mapped = shd.logical_to_mesh({"x": P("dp", "tp"), "y": P(("dp", "tp"))},
                                 {"dp": ("pod", "data"), "tp": "model"})
    assert mapped["x"] == P(("pod", "data"), "model")
    assert mapped["y"] == P(("pod", "data", "model"))


def test_state_pspecs_divisibility_fallbacks():
    kv = {"blocks": {"b0": {"kv": {
        "k": jax.ShapeDtypeStruct((2, 1, 3, 64, 16), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((2, 1, 3, 64, 16), jnp.bfloat16),
    }}}}
    # hkv=3 doesn't divide tp=4 → fall back to sequence sharding (64 % 4 == 0)
    specs = shd.state_pspecs(kv, dp_size=1, tp_size=4)
    assert specs["blocks"]["b0"]["kv"]["k"] == P(None, None, None, "tp", None)
    # batch=2 doesn't divide dp=4 → batch unsharded
    specs2 = shd.state_pspecs(kv, dp_size=4, tp_size=4)
    assert specs2["blocks"]["b0"]["kv"]["k"][1] is None


# ---------------------------------------------------------------------------
# multi-device behaviour (subprocesses with 8 fake devices)
# ---------------------------------------------------------------------------

def test_train_state_specs_build():
    cfg = configs.get_smoke_config("qwen3_moe_235b")
    shapes, specs = make_train_state_specs(
        cfg, make_optimizer("adafactor"), fsdp=True, zero1=True, data_size=2
    )
    assert set(specs) == {"step", "params", "opt_state"}
    moe_spec = specs["params"]["blocks"]["b0"]["moe"]["w_gate"]
    assert moe_spec == P(None, "tp", "dp", None)


@heavy
def test_pipeline_parallel_subprocess():
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.launch.mesh import compat_make_mesh
        from repro.distributed.pipeline import pipeline_apply, bubble_fraction
        mesh = compat_make_mesh((4,), ("stage",))
        S, B, D, M = 4, 8, 16, 4
        w = jax.random.normal(jax.random.key(0), (S, D, D), jnp.float32) * 0.3
        x = jax.random.normal(jax.random.key(1), (B, D), jnp.float32)
        fn = lambda p, h: jax.nn.gelu(h @ p["w"])
        with compat.set_mesh(mesh):
            y = pipeline_apply(fn, {"w": w}, x, mesh, n_microbatches=M)
        ref = x
        for s in range(S):
            ref = jax.nn.gelu(ref @ w[s])
        print("ERR", float(jnp.abs(y - ref).max()))
        print("BUBBLE", bubble_fraction(S, M))
    """)
    err = float(out.split("ERR ")[1].split()[0])
    assert err < 1e-5
    assert "BUBBLE 0.42" in out               # (4−1)/(4+4−1) = 3/7


@heavy
def test_int8_compressed_allreduce_subprocess():
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.launch.mesh import compat_make_mesh
        from repro.distributed.collectives import compressed_psum
        mesh = compat_make_mesh((8,), ("dp",))
        g = jax.random.normal(jax.random.key(0), (8, 64), jnp.float32)
        def f(gs):
            out, res = compressed_psum({"g": gs}, "dp")
            return out["g"], res["g"]
        with compat.set_mesh(mesh):
            mean, resid = compat.shard_map(f, mesh=mesh, in_specs=P("dp"),
                                           out_specs=(P(), P("dp")), check_vma=False)(g)
        true = g.mean(0)
        rel = float(jnp.abs(mean[0] - true).max() / jnp.abs(true).max())
        print("REL", rel)
        # error feedback residual bounded by one quantisation step
        print("RESID", float(jnp.abs(resid).max()))
    """)
    rel = float(out.split("REL ")[1].split()[0])
    assert rel < 0.02
    resid = float(out.split("RESID ")[1].split()[0])
    assert resid < 0.1


@heavy
def test_fsdp_trainer_subprocess():
    """FSDP + ZeRO-1 + int8-DP trainer converges on 2×4 mesh."""
    out = run_subprocess("""
        import jax
        from repro.launch.mesh import compat_make_mesh
        from repro import configs
        from repro.train import Trainer, make_optimizer
        from repro.data.pipeline import make_lm_stream
        mesh = compat_make_mesh((4, 2), ("data", "model"))
        cfg = configs.get_smoke_config("tinyllama_1_1b")
        stream = make_lm_stream(mesh, batch=8, seq_len=32, vocab=cfg.vocab)
        tr = Trainer(cfg, make_optimizer("adamw", lr=3e-3), mesh, stream,
                     fsdp=True, zero1=True)
        m = tr.run(10)
        stream.close()
        print("FIRST", m.history[0]["loss"], "LAST", m.history[-1]["loss"])
    """)
    first = float(out.split("FIRST ")[1].split()[0])
    last = float(out.split("LAST ")[1].split()[0])
    assert last < first                        # learning under FSDP sharding


@heavy
def test_shard_map_int8_dp_mode_subprocess():
    out = run_subprocess("""
        import jax
        from repro.launch.mesh import compat_make_mesh
        from repro import configs
        from repro.train import Trainer, make_optimizer
        from repro.data.pipeline import make_lm_stream
        mesh = compat_make_mesh((4, 2), ("data", "model"))
        cfg = configs.get_smoke_config("qwen2_1_5b")
        stream = make_lm_stream(mesh, batch=8, seq_len=32, vocab=cfg.vocab)
        tr = Trainer(cfg, make_optimizer("adamw", lr=3e-3), mesh, stream,
                     dp_mode="shard_map_int8")
        m = tr.run(8)
        stream.close()
        print("FIRST", m.history[0]["loss"], "LAST", m.history[-1]["loss"])
    """)
    first = float(out.split("FIRST ")[1].split()[0])
    last = float(out.split("LAST ")[1].split()[0])
    assert last < first


@heavy
def test_serve_engine_sharded_subprocess():
    out = run_subprocess("""
        import jax, numpy as np
        from repro import compat
        from repro.launch.mesh import compat_make_mesh
        from repro import configs
        from repro.models import init_params
        from repro.serve import ServeEngine, Request
        mesh = compat_make_mesh((2, 4), ("data", "model"))
        cfg = configs.get_smoke_config("gemma_2b")
        with compat.set_mesh(mesh):
            params = init_params(cfg, jax.random.key(0))
        eng = ServeEngine(cfg, params, mesh, batch_size=4, max_len=64)
        reqs = [Request(i, np.arange(1, 5 + i, dtype=np.int32), max_new_tokens=4)
                for i in range(4)]
        done = eng.serve(reqs)
        print("TOKENS", sum(len(r.output) for r in done))
    """)
    assert int(out.split("TOKENS ")[1].split()[0]) == 16
