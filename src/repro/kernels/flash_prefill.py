"""Fused causal flash attention for prefill (TPU Pallas).

The prefill cells are the compute-heaviest in the dry-run (t_comp up to
5.4 s/step on grok); this kernel fuses QK^T -> online softmax -> PV in
VMEM tiles so scores never round-trip HBM.  GQA is handled in the K/V
BlockSpec index map (query head h reads KV head h // group); causal
blocks above the diagonal are masked with ``pl.when`` guarding the FMAs.

Grid: (B, H, Sq/bq, Sk/bk) — the trailing Sk axis iterates sequentially
per (B, H, q-block), carrying (m, l, acc) in VMEM scratch: the same
online-softmax recurrence as ``layers.chunked_attention`` (the pure-JAX
oracle used under pjit), validated against it in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref, *,
                  n_kb: int, bq: int, bk: int, scale: float, causal: bool):
    kb = pl.program_id(3)
    qb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (not causal) or (kb * bk <= qb * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale   # [bq, D]
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # [bk, D]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _fini():
        out_ref[0, :, 0, :] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, block_q: int = 128,
                  block_k: int = 128, interpret: bool) -> jax.Array:
    """q [B,S,H,D]; k, v [B,S,KV,D]; H = KV * G.  Returns [B,S,H,D].
    ``interpret`` runs the Pallas interpreter (``ops`` passes the
    platform decision)."""
    b, s, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    bq = min(block_q, s)
    bk = min(block_k, sk)
    assert s % bq == 0 and sk % bk == 0
    grid = (b, h, s // bq, sk // bk)
    scale = 1.0 / (d ** 0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, d),
                         lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, bk, 1, d),
                         lambda bi, hi, qi, ki: (bi, ki, hi // g, 0)),
            pl.BlockSpec((1, bk, 1, d),
                         lambda bi, hi, qi, ki: (bi, ki, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, d),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    kern = functools.partial(_flash_kernel, n_kb=sk // bk, bq=bq, bk=bk,
                             scale=scale, causal=causal)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, h, d), q.dtype),
        interpret=interpret)(q, k, v)
