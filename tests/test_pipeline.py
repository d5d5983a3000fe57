"""Pipeline parallelism (pod axis): forward equivalence + trainability."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.train import pipeline_parallel as pp

mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
L, D, MB, M = 8, 16, 4, 6
rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32)
x = jnp.asarray(rng.normal(size=(M, MB, D)), jnp.float32)

def layer_fn(w, h):
    return jnp.tanh(h @ w)

stage_fn = pp.make_stage_fn(layer_fn)
stages = pp.split_stages(W, 4)

with jax.set_mesh(mesh):
    out_pp = pp.pipeline_forward(stages, x, stage_fn, mesh)

# sequential reference
def seq(h):
    for i in range(L):
        h = layer_fn(W[i], h)
    return h
ref = jax.vmap(seq)(x)
np.testing.assert_allclose(np.asarray(out_pp), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("PP_FWD_OK")

# trainability: grads flow through ppermute
def loss(stages_, x_):
    y = pp.pipeline_forward(stages_, x_, stage_fn, mesh)
    return jnp.mean(y ** 2)

with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(stages, x)
gn = sum(float(jnp.sum(jnp.abs(t))) for t in jax.tree.leaves(g))
assert np.isfinite(gn) and gn > 0
# compare against sequential-model grads
def loss_seq(W_, x_):
    def seq1(h):
        for i in range(L):
            h = layer_fn(W_[i], h)
        return h
    return jnp.mean(jax.vmap(seq1)(x_) ** 2)
g_ref = jax.grad(loss_seq)(W, x)
g_pp = jax.tree.leaves(g)[0].reshape(L, D, D)
np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                           rtol=2e-4, atol=2e-5)
print("PP_GRAD_OK  bubble=%.2f" % pp.bubble_fraction(4, M))
"""


@pytest.mark.slow
def test_pipeline_parallel_forward_and_grads():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", CODE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "PP_FWD_OK" in r.stdout and "PP_GRAD_OK" in r.stdout
