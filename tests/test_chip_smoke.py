"""The on-chip entry points, checked where no chip exists.

``chip_smoke.py`` must refuse to run without a TPU and outside a checkout
of the repo, and never print its success line there.  The compile-cache
helper must leave ``JAX_COMPILATION_CACHE_DIR`` to jax and otherwise put
the cache at the fixed ``<repo>/.jax_cache``.  Each case runs in a
subprocess: the helper changes process-wide jax configuration.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def _printed_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_refuses_without_tpu_or_checkout(tmp_path, where):
    if where == "alone":
        shutil.copy(SMOKE, tmp_path)
        r = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    else:
        r = _run([SMOKE], cwd=ROOT)
    assert r.returncode != 0
    assert not _printed_ok(r.stdout)
    assert "FAILED" in r.stderr


_CACHE_PROBE = """
import jax
from repro.launch.compile_cache import setup_compile_cache
print(setup_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_env_dir_wins(tmp_path):
    r = _run(["-c", _CACHE_PROBE], cwd=ROOT,
             PYTHONPATH=os.path.join(ROOT, "src"),
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr
    # jax itself picked the directory up from the environment
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_compile_cache_defaults_to_repo_dir(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    want = os.path.join(ROOT, ".jax_cache")
    assert r.stdout.split() == [want, want]
