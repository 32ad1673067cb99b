"""Entry-point plumbing of the on-chip path, checked without a chip: where
the compile cache goes, and that ``chip_smoke.py`` refuses the CPU.

Everything runs in child processes: the harness itself never enables the
persistent cache (tests compile cold), and a child of this JAX-holding
process is a CPU process by the repo's own rule.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, jax
from openembedding_tpu.utils.compile_cache import enable_compile_cache
print(json.dumps({"calls": [enable_compile_cache(), enable_compile_cache()],
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _child(code_or_script, env_extra, *, script=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env_extra)
    cmd = [sys.executable] + ([code_or_script] if script
                              else ["-c", code_or_script])
    return subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                          text=True, timeout=300)


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout():
    want = os.path.join(ROOT, ".jax_cache")
    seen = []
    for _ in range(2):                       # two processes, two calls each
        out = _child(_PROBE, {})
        assert out.returncode == 0, out.stderr[-2000:]
        seen.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert seen[0] == seen[1] == {"calls": [want, want], "config": want}


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "cache")
    out = _child(_PROBE, {"JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "calls": [placed, placed], "config": placed}


def test_chip_smoke_refuses_the_cpu_naming_it():
    out = _child(os.path.join(ROOT, "chip_smoke.py"), {}, script=True)
    assert out.returncode not in (0, None)
    assert "'platform': 'cpu'" in out.stderr
    assert out.stdout == ""                  # no result line without a chip
