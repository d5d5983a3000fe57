"""Multi-device tests (subprocess: device count is fixed at jax init)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_dev: int = 8, timeout: int = 300):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_distributed_sparse_decode_exact():
    r = run_py(
        "import runpy, sys; sys.argv=['x'];"
        f"runpy.run_path('{ROOT}/examples/long_context_decode.py',"
        "run_name='__main__')")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "full-coverage distributed decode == exact attention" in r.stdout


@pytest.mark.slow
def test_sharded_train_step_on_host_mesh():
    code = """
import jax, numpy as np
from repro.configs import get_config
from repro.data import pipeline
from repro.train import trainer
from repro.launch import mesh as meshlib

cfg = get_config("qwen2-1.5b").reduced()
mesh = meshlib.make_host_mesh(2, 2, pod=2)   # 2x2x2 = 8 devices, 3 axes
dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
tc = trainer.TrainConfig(steps=4, log_every=100, remat="none")
it = ((s, {"tokens": t, "labels": l})
      for s, (t, l) in pipeline.batches(dcfg))
with jax.set_mesh(mesh):
    state, hist = trainer.run(cfg, tc, it, mesh=mesh)
losses = [h["loss"] for h in hist]
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0] + 0.5
print("SHARDED_OK", losses[0], losses[-1])
"""
    r = run_py(code)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "SHARDED_OK" in r.stdout


@pytest.mark.slow
def test_compressed_psum_matches_exact():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compress

mesh = jax.make_mesh((8,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = jnp.arange(64.0).reshape(8, 8) / 7.0
err = jnp.zeros((8, 8), jnp.float32)

def f(g, err):
    return compress.compressed_psum({"g": g}, {"g": err}, "pod")

out = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                    out_specs=(P("pod"), P("pod")), check_vma=False)(g, err)
red = np.asarray(out[0]["g"])
want = np.broadcast_to(np.asarray(g).mean(0, keepdims=True), (8, 8))
np.testing.assert_allclose(red, want, rtol=2e-2, atol=2e-2)
print("COMPRESS_OK")
"""
    r = run_py(code)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "COMPRESS_OK" in r.stdout
