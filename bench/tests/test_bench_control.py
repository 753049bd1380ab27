"""The control: the plain reference computed in bfloat16, put in the
program's place, fails the limits that the program's own answers meet, on
three seeds, at a size a test run can hold."""
import json
import os
import subprocess
import sys

from bench import check
from bench.tests import tiny

SEEDS = (5, 2**31 + 3, 4_000_000_007)


def test_control_fails_where_the_program_passes(tmp_path):
    root = tiny.make_root(tmp_path)
    tiny.add_cell(root, "tiny", tiny.TINY_HIGGS, "tiny-traffic",
                  tiny.TINY_TRAFFIC)
    out = tmp_path / "cal.jsonl"
    code = (f"import sys; sys.path.insert(0, {str(root)!r})\n"
            "from bench import calibrate, run\n"
            "run.chips_or_none = lambda jax, n: jax.devices()[:n]\n"
            f"sys.exit(calibrate.main(['--workload', 'tiny', '--seeds', "
            f"{','.join(map(str, SEEDS))!r}, '--out', {str(out)!r}]))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(root / "src")}
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [ln["seed"] for ln in lines] == list(SEEDS)
    limits = json.loads((root / "bench/configs/tiny-higgs.json").read_text())[
        "correct_limits"]
    for ln in lines:
        assert ln["failed"] == 0
        assert check.judge(ln["program"], limits)[0], ln
        assert not check.judge(ln["control"], limits)[0], ln
