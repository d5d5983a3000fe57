"""Public jit'd kernel API + the VMIG/LBD-style index preprocessing.

The platform picks the mode: ``interpret`` defaults to True off-TPU
(the CPU tests validate kernel bodies in the Pallas interpreter) and to
False on a TPU backend, where the same calls compile to Mosaic.  The
kernel modules themselves take ``interpret`` without a default, so no
caller can run one interpreted on the chip by leaving it out.  Every op
has a pure-jnp oracle in ``ref.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .flash_prefill import flash_prefill as _flash_prefill
from .gather_rows import gather_rows as _gather_rows
from .gather_spmm import gather_spmm as _gather_spmm
from .moe_dispatch import moe_dispatch_matmul as _moe_dispatch_matmul
from .moe_dispatch import moe_paged_down, moe_paged_gateup  # noqa: F401
from .sparse_decode_attn import sparse_decode_attn as _sparse_decode_attn


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp(interpret: bool | None) -> bool:
    return (not on_tpu()) if interpret is None else interpret


# -- kernels -----------------------------------------------------------------

def gather_rows(idx, table, *, interpret: bool | None = None):
    return _gather_rows(idx, table, interpret=_interp(interpret))


def flash_prefill(q, k, v, *, causal: bool = True, block_q: int = 128,
                  block_k: int = 128, interpret: bool | None = None):
    return _flash_prefill(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=_interp(interpret))


def gather_spmm(cols, vals, dense, *, block_n: int = 0,
                interpret: bool | None = None):
    return _gather_spmm(cols, vals, dense, block_n=block_n,
                        interpret=_interp(interpret))


def sparse_decode_attn(idx, q, k, v, *, page_size: int = 8,
                       interpret: bool | None = None):
    return _sparse_decode_attn(idx, q, k, v, page_size=page_size,
                               interpret=_interp(interpret))


def moe_dispatch_matmul(group_ids, x, w, *, block_t: int = 0,
                        block_f: int = 0, block_d: int = 0,
                        interpret: bool | None = None):
    return _moe_dispatch_matmul(group_ids, x, w, block_t=block_t,
                                block_f=block_f, block_d=block_d,
                                interpret=_interp(interpret))


# -- VMIG / LBD-style preprocessing ------------------------------------------

def coalesce_indices(idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """MSHR-coalescing analogue: sort + first-occurrence mask.

    Returns (sorted_idx, inverse_perm) such that
    ``gathered[inverse_perm]`` restores request order while duplicate rows
    hit the same (now adjacent) DMA.
    """
    order = jnp.argsort(idx)
    inv = jnp.argsort(order)
    return idx[order], inv


def csr_to_ell(rowptr: np.ndarray, col: np.ndarray, val: np.ndarray,
               nnz_pad: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """CSR -> ELL (fixed-width rows, zero-padded): the LBD bound-to-tile
    transform.  Host-side (data preparation)."""
    m = len(rowptr) - 1
    width = nnz_pad or int(max(1, (rowptr[1:] - rowptr[:-1]).max()))
    cols = np.zeros((m, width), dtype=np.int32)
    vals = np.zeros((m, width), dtype=val.dtype)
    for r in range(m):
        lo, hi = int(rowptr[r]), int(rowptr[r + 1])
        k = min(hi - lo, width)
        cols[r, :k] = col[lo:lo + k]
        vals[r, :k] = val[lo:lo + k]
    return cols, vals


def group_tokens_by_expert(expert_ids: jax.Array, n_experts: int,
                           block_t: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sort tokens by expert and pad each group to a block_t multiple.

    Returns (perm [T_pad] gather indices into x with T used as "padding
    token", group_ids [T_pad // block_t], inv_pos [T] scatter positions).
    Capacity is static: each expert gets ceil(T / n_experts / block_t) + 1
    blocks (tokens beyond capacity are dropped — standard MoE capacity).
    """
    t = expert_ids.shape[0]
    cap_blocks = int(np.ceil(t / n_experts / block_t)) + 1
    cap = cap_blocks * block_t
    # position of each token within its expert group
    sort_ord = jnp.argsort(expert_ids)
    sorted_eids = expert_ids[sort_ord]
    pos_in_grp = jnp.arange(t) - jnp.searchsorted(sorted_eids, sorted_eids)
    slot = sorted_eids * cap + pos_in_grp
    keep = pos_in_grp < cap
    perm = jnp.full((n_experts * cap,), t, dtype=jnp.int32)
    perm = perm.at[jnp.where(keep, slot, n_experts * cap - 1)].set(
        jnp.where(keep, sort_ord, t).astype(jnp.int32), mode="drop")
    group_ids = jnp.repeat(jnp.arange(n_experts, dtype=jnp.int32), cap_blocks)
    inv_pos = jnp.full((t + 1,), -1, dtype=jnp.int32)
    inv_pos = inv_pos.at[perm].set(jnp.arange(n_experts * cap,
                                              dtype=jnp.int32), mode="drop")
    return perm, group_ids, inv_pos[:t]


def topk_pages(scores: jax.Array, n_pages: int, page_size: int,
               k_pages: int) -> jax.Array:
    """Fuzzy (page-granular) TopK: aggregate token scores into page scores
    and select the K highest pages — the coverage-oriented selection."""
    b, h, s = scores.shape
    ps = scores.reshape(b, h, n_pages, page_size).max(axis=-1)
    _, idx = jax.lax.top_k(ps, k_pages)
    return idx.astype(jnp.int32)
