"""Dry-run machinery on a SMALL mesh (subprocess with 8 fake devices):
build_cell + lower + compile + roofline report for representative cells.
The full 16×16 / 2×16×16 sweeps run via ``python -m repro.launch.dryrun``
(results under experiments/); this test keeps the machinery honest in CI.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Cell compiles on a forced-8-device host take minutes each on CPU; they run
# in the nightly/heavy CI lane (ci.yml) rather than every tier-1 invocation.
pytestmark = pytest.mark.skipif(
    os.environ.get("REPRO_HEAVY_TESTS") != "1",
    reason="multi-device subprocess compile (minutes on CPU); "
           "set REPRO_HEAVY_TESTS=1 to run",
)


def run_sub(code: str, devices: int = 8) -> str:
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
        env={"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
             # the child must never reach for a chip its parent may hold
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.parametrize("arch,shape", [
    ("qwen2_1_5b", "train_4k"),
    ("rwkv6_7b", "decode_32k"),
    ("qwen3_moe_235b", "train_4k"),
    ("whisper_medium", "prefill_32k"),
])
def test_cell_compiles_on_small_mesh(arch, shape):
    out = run_sub(f"""
        import jax, json
        from repro.launch.mesh import compat_make_mesh
        from repro.launch.dryrun import run_cell
        mesh = compat_make_mesh((2, 4), ("data", "model"))
        rep, secs = run_cell("{arch}", "{shape}", mesh=mesh, scan=True,
                             verbose=False)
        print("REPORT", json.dumps({{
            "dominant": rep.dominant,
            "flops": rep.flops_per_device,
            "coll": rep.collective_bytes["total"],
        }}))
    """)
    rep = json.loads(out.split("REPORT ")[1])
    assert rep["dominant"] in ("compute", "memory", "collective")
    assert rep["flops"] > 0
    assert rep["coll"] > 0          # sharded step must communicate


def test_multipod_mesh_small():
    """pod axis shards: same cell compiles on a (2,2,2) pod mesh."""
    out = run_sub("""
        import jax
        from repro.launch.mesh import compat_make_mesh
        from repro.launch.dryrun import run_cell
        mesh = compat_make_mesh((2, 2, 2), ("pod", "data", "model"))
        rep, _ = run_cell("tinyllama_1_1b", "train_4k", mesh=mesh, scan=True,
                          verbose=False)
        print("OK", rep.mesh, rep.n_devices)
    """)
    assert "OK 2x2x2 8" in out
