"""Random weights from ``--seed``, made on the device in one jitted call.

The pytree is the layout ``PagedEngine`` serves (stacked layers, norm
weights stored as offsets from 1, ``lm_head`` only when untied), in the
configuration's dtype.  Biases (where the model has them) and norm
offsets are drawn non-zero so that the comparison with the reference
covers them.  The plain reference
draws the same pytree from the same seed and reads it its own way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BIAS_SCALE = 0.5     # q/k/v bias, against projections of unit scale
NORM_SCALE = 0.1     # spread of the RMSNorm gains around 1
EMBED_SCALE = 0.02


def key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one beyond 32 bits."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def _normal(k, shape, dtype, scale):
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _make(shape_items: tuple, k: jax.Array) -> dict:
    s = dict(shape_items)
    dt = jnp.bfloat16 if s["dtype_bytes"] == 2 else jnp.float32
    n, d, f, v = s["n_layers"], s["d_model"], s["d_ff"], s["vocab"]
    hq = s["n_heads"] * s["head_dim"]
    hkv = s["n_kv_heads"] * s["head_dim"]
    ks = iter(jax.random.split(k, 16))
    layers = {
        "ln1": _normal(next(ks), (n, d), jnp.float32, NORM_SCALE),
        "ln2": _normal(next(ks), (n, d), jnp.float32, NORM_SCALE),
        "wq": _normal(next(ks), (n, d, hq), dt, d ** -0.5),
        "wk": _normal(next(ks), (n, d, hkv), dt, d ** -0.5),
        "wv": _normal(next(ks), (n, d, hkv), dt, d ** -0.5),
        "wo": _normal(next(ks), (n, hq, d), dt, hq ** -0.5),
    }
    # the bias keys are drawn either way, so that the other leaves do
    # not depend on whether the model has biases
    bias_keys = [next(ks) for _ in range(3)]
    if s["qkv_bias"]:
        for name, width, kb in zip(("bq", "bk", "bv"), (hq, hkv, hkv),
                                   bias_keys):
            layers[name] = _normal(kb, (n, width), dt, BIAS_SCALE)
    layers.update({
        "wi": _normal(next(ks), (n, d, f), dt, d ** -0.5),
        "wg": _normal(next(ks), (n, d, f), dt, d ** -0.5),
        "wo_mlp": _normal(next(ks), (n, f, d), dt, f ** -0.5),
    })
    params = {
        "embed": _normal(next(ks), (v, d), dt, EMBED_SCALE),
        "layers": layers,
        "ln_f": _normal(next(ks), (d,), jnp.float32, NORM_SCALE),
    }
    if not s["tie_embeddings"]:
        params["lm_head"] = _normal(next(ks), (d, v), dt, d ** -0.5)
    return params


def make(shape: dict, seed: int) -> dict:
    """The weights of a model of ``shape`` (``spec.model_shape``)."""
    return _make(tuple(sorted(shape.items())), key(seed))
