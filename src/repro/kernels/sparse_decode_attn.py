"""TopK sparse KV decode attention — the paper's flagship LLM use case
(Double Sparsity [5] / H2O [29]) as a TPU-native runahead kernel.

One new query token attends to only the ``K`` highest-scoring KV *pages*
(page = ``page_size`` consecutive tokens; ``page_size = 1`` is exact row
selection, larger pages are the paper's *fuzzy / coverage-oriented* fetch:
slightly more data per request, far fewer requests, MXU-aligned tiles).

The page indices (resolved TopK chain) are scalar-prefetched; the Pallas
pipeline double-buffers the indirect K/V page DMAs across grid steps —
speculative gather depth = pipeline depth, the NVR mechanism.

Layout: q [B, Hkv, G, D] (GQA groups), k/v [B, S, Hkv, D], idx [B, Hkv, P]
with page indices in [0, S/page_size).  Output [B, Hkv, G, D].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _attn_kernel(idx_ref, q_ref, k_ref, v_ref, out_ref,
                 acc_ref, m_ref, l_ref, *, n_pages: int, scale: float):
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [G, D]
    k = k_ref[0, 0, :, 0, :].astype(jnp.float32)   # [P, D]
    v = v_ref[0, 0, :, 0, :].astype(jnp.float32)   # [P, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # [G, P]
    m_prev = m_ref[:, :1]                          # [G, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)                      # [G, P]
    l_new = l_prev * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == n_pages - 1)
    def _fini():
        out_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret"))
def sparse_decode_attn(idx: jax.Array, q: jax.Array, k: jax.Array,
                       v: jax.Array, *, page_size: int = 8,
                       interpret: bool) -> jax.Array:
    """TopK-page decode attention.

    Args:
      idx: int32 [B, Hkv, P] page indices into [0, S // page_size).
      q:   [B, Hkv, G, D] query (one decode step, GQA-grouped).
      k,v: [B, S, Hkv, D] KV cache.
      page_size: tokens per gathered page (fuzzy-fetch granularity).
      interpret: run the Pallas interpreter (``ops`` passes the
        platform decision).
    Returns: [B, Hkv, G, D]
    """
    b, hkv, g, d = q.shape
    _, s, _, _ = k.shape
    _, _, n_pages = idx.shape
    assert s % page_size == 0
    scale = 1.0 / (d ** 0.5)
    kp = k.reshape(b, s // page_size, page_size, hkv, d)
    vp = v.reshape(b, s // page_size, page_size, hkv, d)
    grid = (b, hkv, n_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, pi, c: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page_size, 1, d),
                         lambda bi, hi, pi, c: (bi, c[bi, hi, pi], 0, hi, 0)),
            pl.BlockSpec((1, 1, page_size, 1, d),
                         lambda bi, hi, pi, c: (bi, c[bi, hi, pi], 0, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, pi, c: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )
    kern = functools.partial(_attn_kernel, n_pages=n_pages, scale=scale)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret)(idx.astype(jnp.int32), q, kp, vp)
