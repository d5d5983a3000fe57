"""Paged TopK sparse-decode attention on the serve layer's physical pools.

Where ``sparse_decode_attn`` runs on contiguous ``[B, S, KV, D]`` caches
(the single-request layout), this kernel consumes the continuous-batching
engine's *native* memory model directly: one layer of the physical page
pool ``k/v_pool [P, KV, page, D]`` shared by every request, plus the
per-request TopK selection already resolved to **physical page ids**
through the block table (``sparse_attention.select_pages_blocktable``).

The NVR mechanism, mapped onto the Pallas pipeline:

* the resolved page-id chain (``phys``), the logical ids (``idx``, for
  causal masking) and the per-request frontiers (``pos``) are
  **scalar-prefetched** — available before the kernel body runs, exactly
  the role of NVR's resolved-address runahead state;
* the grid walks ``(request, kv_head, selected_page)`` and the pipeline
  **double-buffers the indirect page DMAs** across grid steps: while page
  ``p`` is attended, page ``p+1``'s HBM fetch is in flight.  Pipeline
  depth = runahead depth — the paper's decoupled speculative fetch,
  expressed as a BlockSpec index map;
* gather and online-softmax attention are **fused**: the gathered K/V
  tile lives only in VMEM, never materialised in HBM (the XLA path
  ``sparse_attention.attend_pages_paged`` builds the full
  ``[R, KV, K, page, D]`` gather in memory first).

Masking matches the XLA oracle bit-for-bit in structure: a selected page
may straddle the frontier (tokens at absolute position > ``pos[r]`` are
masked), NULL-padded selection slots of short requests are fully masked,
and fully-masked rows (padded batch slots) produce zeros, not NaNs.

Layout: phys/idx int32 ``[R, KV, K]``; pos int32 ``[R]``;
q ``[R, KV, G, D]``; k/v_pool ``[P, KV, page, D]`` (fp or int8 with the
shared fixed-scale quant).  Output ``[R, KV, G, D]``.  The pool keeps
the KV head outside the page so that one head's page is a contiguous
``[page, D]`` slab: a K/V block is ``(1, 1, page, D)``, whose two minor
dims are whole array dims — the tiling Mosaic accepts for any head
count.  (Heads inside the page, ``[P, page, KV, D]``, would need a
block of one head against the tiled KV dim, which the TPU compiler
refuses.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fixed-scale symmetric int8 KV quantisation: RoPE preserves key norms,
# so one static scale suffices.  Canonical definition — the model layer
# (``models.sparse_attention``) imports it from here, since the kernel
# package must never import the model stack.
KV_QSCALE = 16.0


def _paged_kernel(phys_ref, idx_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
                  acc_ref, m_ref, l_ref, *, k_sel: int, page: int,
                  scale: float, kv_scale: float):
    ri, hi, pi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                      # [G, D]
    k = k_ref[0, 0].astype(jnp.float32) * kv_scale           # [page, D]
    v = v_ref[0, 0].astype(jnp.float32) * kv_scale
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # causal frontier mask in absolute token positions: the logical page
    # id places this physical page on the request's timeline
    lp = idx_ref[ri, hi, pi]
    tok = lp * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jnp.where(tok <= pos_ref[ri], s, -jnp.inf)           # [G, page]

    # online softmax, -inf-safe: a fully-masked tile (NULL-padded
    # selection slot, or a padded batch row) contributes nothing
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(pi == k_sel - 1)
    def _fini():
        l = l_ref[:, :1]
        out_ref[0, 0] = jnp.where(
            l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_decode_attn(phys, idx, pos, q, k_pool, v_pool, *, page_size: int,
                       interpret: bool):
    r, kv, g, d = q.shape
    _, _, k_sel = phys.shape
    scale = 1.0 / (d ** 0.5)
    kv_scale = (1.0 / KV_QSCALE if k_pool.dtype == jnp.int8 else 1.0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # phys, idx, pos — the resolved
        grid=(r, kv, k_sel),                # runahead chain, known up front
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda ri, hi, pi, ph, ix, ps: (ri, hi, 0, 0)),
            # indirect page DMA: the index map consults the prefetched
            # physical id — the pipeline prefetches page pi+1 while pi is
            # attended (double-buffered speculative gather, depth = K)
            pl.BlockSpec((1, 1, page_size, d),
                         lambda ri, hi, pi, ph, ix, ps:
                         (ph[ri, hi, pi], hi, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda ri, hi, pi, ph, ix, ps:
                         (ph[ri, hi, pi], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda ri, hi, pi, ph, ix, ps: (ri, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, k_sel=k_sel, page=page_size,
                             scale=scale, kv_scale=kv_scale)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, kv, g, d), q.dtype),
        interpret=interpret)(
            phys.astype(jnp.int32), idx.astype(jnp.int32),
            pos.astype(jnp.int32), q, k_pool, v_pool)


def paged_decode_attn(phys: jax.Array, idx: jax.Array, pos: jax.Array,
                      q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      *, page_size: int, interpret: bool | None = None,
                      hot_map: jax.Array | None = None,
                      n_demand: int = 0) -> jax.Array:
    """Paged TopK decode attention on one layer of the physical pool.

    Args:
      phys: int32 [R, KV, K] physical page ids (the gather targets).
      idx:  int32 [R, KV, K] logical page ids (causal masking).
      pos:  int32 [R] per-request frontier positions.
      q:    [R, KV, G, D] one decode step's queries, GQA-grouped.
      k_pool, v_pool: [P, KV, page, D] one layer of the physical pools
        (int8 pools dequant with the shared fixed scale).
      page_size: tokens per physical page.
      interpret: run the Pallas interpreter (defaults to True off-TPU).
      hot_map: optional int32 [n_demand] runahead hot-map, demand page
        id -> staged NSB slot (-1 = not staged).  Page ids with a live
        slot are remapped to the pool's contiguous staging tail at
        ``n_demand + slot`` before the gather: the scalar-prefetched
        index map then DMAs the staged copy — a sequential read from the
        hot tier — instead of the scattered demand page.  Staged pages
        are byte-exact copies, so the result is bitwise-unchanged.
      n_demand: demand-region page count (tail slots start here);
        required with ``hot_map``.
    Returns: [R, KV, G, D], parity with
      ``sparse_attention.attend_pages_paged`` (fp32 online softmax).
    """
    from .ops import on_tpu
    if interpret is None:
        interpret = not on_tpu()
    if hot_map is not None:
        slot = hot_map[phys]                   # [R, KV, K]; -1 = demand
        phys = jnp.where(slot >= 0, n_demand + slot, phys)
    return _paged_decode_attn(phys, idx, pos, q, k_pool, v_pool,
                              page_size=page_size, interpret=interpret)
