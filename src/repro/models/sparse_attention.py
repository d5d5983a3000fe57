"""TopK sparse-KV decode attention — the paper's technique as a model layer.

Double-Sparsity/H2O-style decode: approximate per-page scores from a small
*label cache* (page-pooled key summaries), select the TopK pages, and attend
only to those pages.  The gather is the NVR-accelerated operation: on TPU it
lowers to the ``sparse_decode_attn`` Pallas kernel (scalar-prefetched
runahead); the XLA path (used under pjit and on CPU) expresses the same
computation with ``take_along_axis``.

For sequence-sharded caches (long_500k) ``sparse_decode_sharded`` runs the
selection per shard under ``shard_map`` and merges partial attention with a
log-sum-exp combine: the attended set is the union of per-shard TopKs — a
coverage-oriented superset of the global TopK (the paper's fuzzy-fetch
philosophy, applied across chips).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


# int8 KV-cache quantisation (beyond-paper §Perf lever): fixed-scale
# symmetric quant; quality is checked in tests (corr > 0.99 vs bf16 at
# full coverage).  The scale's canonical home is the kernel package —
# the Pallas paged kernel dequantises with the same constant, and
# kernels must not import the model stack.
from ..kernels.paged_decode_attn import KV_QSCALE


def kv_quant(x: jax.Array, dtype) -> jax.Array:
    if jnp.dtype(dtype) != jnp.int8:
        return x.astype(dtype)
    return jnp.clip(jnp.round(x.astype(jnp.float32) * KV_QSCALE),
                    -127, 127).astype(jnp.int8)


def kv_dequant_f32(x: jax.Array) -> jax.Array:
    if x.dtype == jnp.int8:
        return x.astype(jnp.float32) * (1.0 / KV_QSCALE)
    return x.astype(jnp.float32)


def page_scores(q: jax.Array, kpage: jax.Array) -> jax.Array:
    """q [B,KV,G,D], kpage [B,NP,KV,D] -> scores [B,KV,NP] (max over group)."""
    s = jnp.einsum("bkgd,bpkd->bkgp", q.astype(jnp.float32),
                   kpage.astype(jnp.float32))
    return jnp.max(s, axis=2)


def select_pages(q: jax.Array, kpage: jax.Array, n_pages_valid: jax.Array,
                 k_pages: int) -> jax.Array:
    """TopK page ids per (batch, kv head); invalid pages score -inf."""
    s = page_scores(q, kpage)                       # [B,KV,NP]
    npg = s.shape[-1]
    valid = jnp.arange(npg)[None, None, :] < n_pages_valid
    s = jnp.where(valid, s, -jnp.inf)
    _, idx = jax.lax.top_k(s, k_pages)
    return idx.astype(jnp.int32)


def select_pages_recorded(q: jax.Array, kpage: jax.Array,
                          n_pages_valid: jax.Array, k_pages: int,
                          stream) -> jax.Array:
    """``select_pages`` + trace capture: records the concrete selection
    into a :class:`repro.core.nvr.capture.PageStream` (one event per
    (batch, kv-head) slot) so serving traffic can be replayed through the
    NVR simulator.  Must run outside jit (the recorder needs values)."""
    idx = select_pages(q, kpage, n_pages_valid, k_pages)
    stream.record_batched(np.asarray(idx))
    return idx


def page_token_positions(idx: jax.Array, page: int) -> jax.Array:
    """Absolute token positions ``[..., P, page]`` of the tokens inside
    the selected pages ``idx [..., P]`` (shared by the attend variants
    and the capture adapters)."""
    return idx[..., None] * page + jnp.arange(page)


def attend_pages(q: jax.Array, k: jax.Array, v: jax.Array, idx: jax.Array,
                 pos: jax.Array, page: int) -> jax.Array:
    """Attend q [B,KV,G,D] to gathered pages of k/v [B,S,KV,D].

    idx [B,KV,P] page ids; tokens at absolute position > pos are masked
    (a selected page may straddle the frontier).
    Returns [B,KV,G,D].
    """
    b, s, kv, d = k.shape
    kp = k.reshape(b, s // page, page, kv, d)
    vp = v.reshape(b, s // page, page, kv, d)
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(kv)[None, :, None]
    kg = jnp.moveaxis(kp, 3, 1)[bi, hi, idx]        # [B,KV,P,page,D]
    vg = jnp.moveaxis(vp, 3, 1)[bi, hi, idx]
    scores = jnp.einsum("bkgd,bkptd->bkgpt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / (d ** 0.5)
    tok_pos = page_token_positions(idx, page)
    mask = tok_pos <= pos                           # [B,KV,P,page]
    scores = jnp.where(mask[:, :, None], scores, -jnp.inf)
    bp, pt = scores.shape[-2], scores.shape[-1]
    flat = scores.reshape(*scores.shape[:-2], bp * pt)
    w = jax.nn.softmax(flat, axis=-1).reshape(scores.shape)
    out = jnp.einsum("bkgpt,bkptd->bkgd", w, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def sparse_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                  kpage: jax.Array, pos: jax.Array, *, page: int,
                  k_pages: int) -> jax.Array:
    """Full sparse decode: select + attend.  q [B,KV,G,D] -> [B,KV,G,D]."""
    n_valid = (pos // page) + 1
    idx = select_pages(q, kpage, n_valid, k_pages)
    return attend_pages(q, k, v, idx, pos, page)


def sparse_decode_distributed(q, k, v, kpage, pos, *, page: int,
                              k_pages: int, mesh, batch_axes=(),
                              seq_axes=(), kv_axes=()):
    """Distributed TopK sparse decode under shard_map.

    Three orthogonal shardings compose:
      * ``batch_axes``  — B sharded (DP), selection independent per row.
      * ``kv_axes``     — KV heads sharded (TP), selection per local head.
      * ``seq_axes``    — the KV *sequence* sharded (SP, long_500k): each
        shard TopKs its local pages and partial attentions merge with a
        log-sum-exp psum.  The attended set is the union of per-shard
        TopKs — a coverage-oriented superset of the global TopK (the
        paper's fuzzy-fetch philosophy across chips).

    q [B,KV,G,D]; k/v [B,S,KV,D]; kpage [B,NP,KV,D]; pos scalar.
    """
    ba = tuple(a for a in batch_axes if a in mesh.shape)
    sa = tuple(a for a in seq_axes if a in mesh.shape)
    ka = tuple(a for a in kv_axes if a in mesh.shape)
    n_seq = 1
    for a in sa:
        n_seq *= mesh.shape[a]
    # coverage-oriented local budget: over-select 4x the proportional share
    k_local = max(2, (4 * k_pages) // n_seq) if n_seq > 1 else k_pages

    def local(qv, kl, vl, kpl, posv):
        b, sl, kv_h, d = kl.shape
        npl = kpl.shape[1]
        start = (jax.lax.axis_index(sa) * sl) if sa else 0
        local_pos = posv - start
        n_valid = jnp.clip(local_pos // page + 1, 0, npl)
        kp = int(min(k_local, npl))
        s = page_scores(qv, kpl)
        valid = jnp.arange(npl)[None, None, :] < n_valid
        s = jnp.where(valid, s, -jnp.inf)
        _, idx = jax.lax.top_k(s, kp)
        idx = idx.astype(jnp.int32)
        kpg = kl.reshape(b, sl // page, page, kv_h, d)
        vpg = vl.reshape(b, sl // page, page, kv_h, d)
        bi = jnp.arange(b)[:, None, None]
        hi = jnp.arange(kv_h)[None, :, None]
        kg = jnp.moveaxis(kpg, 3, 1)[bi, hi, idx]
        vg = jnp.moveaxis(vpg, 3, 1)[bi, hi, idx]
        sc = jnp.einsum("bkgd,bkptd->bkgpt", qv.astype(jnp.float32),
                        kg.astype(jnp.float32)) / (d ** 0.5)
        tok = start + page_token_positions(idx, page)
        mask = tok <= posv
        sc = jnp.where(mask[:, :, None], sc, -jnp.inf)
        flat = sc.reshape(*sc.shape[:3], -1)
        m = jnp.max(flat, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(flat - m_safe[..., None])
        p = jnp.where(jnp.isfinite(flat), p, 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkgn,bknd->bkgd", p,
                         vg.reshape(b, kv_h, -1, d))
        if not sa:
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            return out.astype(qv.dtype)
        # LSE merge across sequence shards
        m_glob = jax.lax.pmax(m, sa)
        m_gsafe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_gsafe), 0.0)
        l_glob = jax.lax.psum(l * scale, sa)
        acc_glob = jax.lax.psum(acc * scale[..., None], sa)
        out = acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.astype(qv.dtype)

    bspec = ba if ba else None
    kspec = ka if ka else None
    sspec = sa if sa else None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, kspec, None, None),
                  P(bspec, sspec, kspec, None),
                  P(bspec, sspec, kspec, None),
                  P(bspec, sspec, kspec, None), P()),
        out_specs=P(bspec, kspec, None, None), check_vma=False)(
            q, k, v, kpage, pos)


# -- layer-indexed ("full-cache") variants -------------------------------------
#
# §Perf iteration: the scan-carried cache is [L,B,S,KV,D]; slicing layer li
# out (dynamic_index) and transposing (moveaxis) copies the WHOLE layer
# cache every step — O(cache) HBM traffic for an O(TopK) computation.
# These variants gather straight from the stacked cache with the layer
# index folded into the gather, so traffic is O(pages_read) as the paper
# intends.

def gather_pages_full(cache_full: jax.Array, li, idx: jax.Array,
                      page: int) -> jax.Array:
    """cache_full [L,B,S,KV,D], idx [B,KV,P] -> [B,KV,P,page,D] (one fused
    gather, no per-layer slice/transpose copies)."""
    l, b, s, kv, d = cache_full.shape
    kp6 = cache_full.reshape(l, b, s // page, page, kv, d)
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(kv)[None, :, None]
    return kp6[li, bi, idx, :, hi, :]


def attend_pages_full(q, k_full, v_full, li, idx, pos, page: int):
    """q [B,KV,G,D] attends gathered pages of layer ``li``."""
    d = q.shape[-1]
    kg = kv_dequant_f32(gather_pages_full(k_full, li, idx, page))
    vg = kv_dequant_f32(gather_pages_full(v_full, li, idx, page))
    scores = jnp.einsum("bkgd,bkptd->bkgpt", q.astype(jnp.float32),
                        kg) / (d ** 0.5)
    tok_pos = page_token_positions(idx, page)
    mask = tok_pos <= pos
    scores = jnp.where(mask[:, :, None], scores, -jnp.inf)
    bp, pt = scores.shape[-2], scores.shape[-1]
    flat = scores.reshape(*scores.shape[:-2], bp * pt)
    w = jax.nn.softmax(flat, axis=-1).reshape(scores.shape)
    out = jnp.einsum("bkgpt,bkptd->bkgd", w, vg)
    return out.astype(q.dtype)


def sparse_decode_full(q, k_full, v_full, kpage_li, li, pos, *, page: int,
                       k_pages: int):
    """Layer-indexed sparse decode: kpage_li [B,NP,KV,D] is this layer's
    (small) label cache; K/V pages gather straight from the stacked
    cache."""
    n_valid = (pos // page) + 1
    idx = select_pages(q, kpage_li, n_valid, k_pages)
    return attend_pages_full(q, k_full, v_full, li, idx, pos, page)


def sparse_decode_distributed_full(q, k_full, v_full, kpage_li, li, pos, *,
                                   page: int, k_pages: int, mesh,
                                   batch_axes=(), seq_axes=(), kv_axes=()):
    """Distributed variant of ``sparse_decode_full`` (shard_map)."""
    ba = tuple(a for a in batch_axes if a in mesh.shape)
    sa = tuple(a for a in seq_axes if a in mesh.shape)
    ka = tuple(a for a in kv_axes if a in mesh.shape)
    n_seq = 1
    for a in sa:
        n_seq *= mesh.shape[a]
    k_local = max(2, (4 * k_pages) // n_seq) if n_seq > 1 else k_pages

    def local(qv, kl, vl, kpl, liv, posv):
        b, npl, kv_h, d = kpl.shape
        sl = kl.shape[2]
        start = (jax.lax.axis_index(sa) * sl) if sa else 0
        local_pos = posv - start
        n_valid = jnp.clip(local_pos // page + 1, 0, npl)
        kp = int(min(k_local, npl))
        s = page_scores(qv, kpl)
        valid = jnp.arange(npl)[None, None, :] < n_valid
        s = jnp.where(valid, s, -jnp.inf)
        _, idx = jax.lax.top_k(s, kp)
        idx = idx.astype(jnp.int32)
        kg = kv_dequant_f32(gather_pages_full(kl, liv, idx, page))
        vg = kv_dequant_f32(gather_pages_full(vl, liv, idx, page))
        sc = jnp.einsum("bkgd,bkptd->bkgpt", qv.astype(jnp.float32),
                        kg) / (d ** 0.5)
        tok = start + page_token_positions(idx, page)
        mask = tok <= posv
        sc = jnp.where(mask[:, :, None], sc, -jnp.inf)
        flat = sc.reshape(*sc.shape[:3], -1)
        m = jnp.max(flat, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(flat - m_safe[..., None])
        p = jnp.where(jnp.isfinite(flat), p, 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkgn,bknd->bkgd", p,
                         vg.reshape(b, kv_h, -1, d))
        if not sa:
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            return out.astype(qv.dtype)
        m_glob = jax.lax.pmax(m, sa)
        m_gsafe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_gsafe), 0.0)
        l_glob = jax.lax.psum(l * scale, sa)
        acc_glob = jax.lax.psum(acc * scale[..., None], sa)
        out = acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.astype(qv.dtype)

    bspec = ba if ba else None
    kspec = ka if ka else None
    sspec = sa if sa else None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, kspec, None, None),
                  P(None, bspec, sspec, kspec, None),
                  P(None, bspec, sspec, kspec, None),
                  P(bspec, sspec, kspec, None), P(), P()),
        out_specs=P(bspec, kspec, None, None), check_vma=False)(
            q, k_full, v_full, kpage_li, li, pos)


# -- block-table-indexed ("paged") variants ------------------------------------
#
# Continuous-batching serve path: the KV cache is a pool of physical pages
# shared by all requests; each request maps logical page j -> physical page
# bt[j].  Selection scores logical pages from the physical page-summary
# pool and returns BOTH index spaces: logical ids feed the causal masking
# (absolute token positions), physical ids feed the gather — and are the
# very ids the KV allocator, the NSB hot-set model (capture.PageCache),
# and the captured simulator trace account in.

def select_pages_blocktable(q: jax.Array, kpage_pool_li: jax.Array,
                            block_table: jax.Array, n_pages_valid: jax.Array,
                            k_pages: int) -> tuple[jax.Array, jax.Array]:
    """TopK pages through a block table.

    q [R,KV,G,D]; kpage_pool_li [P,KV,D] (physical page summaries, one
    layer); block_table [R,NL] physical ids (NULL-padded); n_pages_valid
    [R].  Returns (logical idx [R,KV,K], physical idx [R,KV,K]).
    """
    kp = kpage_pool_li[block_table]                 # [R,NL,KV,D]
    s = page_scores(q, kp)                          # [R,KV,NL]
    nl = s.shape[-1]
    valid = jnp.arange(nl)[None, None, :] < n_pages_valid[:, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    _, idx = jax.lax.top_k(s, k_pages)
    idx = idx.astype(jnp.int32)
    bt_b = jnp.broadcast_to(block_table[:, None, :],
                            (idx.shape[0], idx.shape[1], nl))
    phys = jnp.take_along_axis(bt_b, idx, axis=-1).astype(jnp.int32)
    return idx, phys


def attend_pages_paged(q: jax.Array, k_pool_li: jax.Array,
                       v_pool_li: jax.Array, idx: jax.Array,
                       phys: jax.Array, pos: jax.Array,
                       page: int, tp_axis: str | None = None,
                       hot_map: jax.Array | None = None,
                       n_demand: int = 0) -> jax.Array:
    """Attend q [R,KV,G,D] to physically-gathered pages.

    k_pool_li / v_pool_li [P,KV,page,D] (one layer of the pool); idx
    [R,KV,K] logical page ids (for position masking), phys [R,KV,K]
    physical page ids (for the gather); pos [R] per-request frontier.
    Fully-masked rows (padded batch slots) produce zeros, not NaNs.

    ``tp_axis`` (inside ``shard_map`` only): the KV-head axis is sharded
    — q/idx/phys and the pools carry this shard's head slice.  The page
    *gather* runs locally against the local pool slice (the
    memory-local NVR operation), then the small gathered TopK tiles —
    not the pools — are all-gathered and the attention math runs at the
    full-KV shape, identically replicated on every shard.  That split
    is what keeps tp>1 *bitwise* equal to tp=1: XLA's fused
    scores/softmax lowering is shape- and head-position-dependent at
    ulp level, so per-head math must run at the same shapes/positions
    as the unsharded oracle.  Returns the full-head [R,KV_total,G,D]
    when ``tp_axis`` is given.

    ``hot_map``/``n_demand`` (runahead): page ids with a staged NSB
    slot (``hot_map[p] >= 0``) redirect to the pool's contiguous
    staging tail at ``n_demand + slot`` — a byte-exact copy, so the
    output is bitwise-unchanged; only where the bytes are read from
    moves.  The remap happens *before* the tp all-gather, on local
    ids: the hot-map is replicated and the page axis never sharded,
    so every shard resolves identically.
    """
    if hot_map is not None:
        slot = hot_map[phys]                       # [R,KV,K]; -1 = demand
        phys = jnp.where(slot >= 0, n_demand + slot, phys)
    kv = k_pool_li.shape[1]
    hi = jnp.arange(kv)[None, :, None]
    # advanced indices (phys [R,KV,K], head [1,KV,1]) broadcast together,
    # picking each KV head's own selected pages: [R,KV,K,page,D]
    kg = kv_dequant_f32(k_pool_li[phys, hi])
    vg = kv_dequant_f32(v_pool_li[phys, hi])
    if tp_axis is not None:
        q, idx, kg, vg = jax.lax.all_gather(
            (q, idx, kg, vg), tp_axis, axis=1, tiled=True)
    d = q.shape[-1]
    scores = jnp.einsum("bkgd,bkptd->bkgpt", q.astype(jnp.float32),
                        kg) / (d ** 0.5)
    tok_pos = page_token_positions(idx, page)       # [R,KV,K,page]
    mask = tok_pos <= pos[:, None, None, None]
    scores = jnp.where(mask[:, :, None], scores, -jnp.inf)
    bp, pt = scores.shape[-2], scores.shape[-1]
    flat = scores.reshape(*scores.shape[:-2], bp * pt)
    m = jnp.max(flat, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(flat - m)
    p = jnp.where(jnp.isfinite(flat), p, 0.0)
    w = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
         ).reshape(scores.shape)
    out = jnp.einsum("bkgpt,bkptd->bkgd", w, vg)
    return out.astype(q.dtype)


def attend_pages_paged_kernel(q: jax.Array, k_pool_li: jax.Array,
                              v_pool_li: jax.Array, idx: jax.Array,
                              phys: jax.Array, pos: jax.Array, page: int,
                              interpret: bool | None = None,
                              hot_map: jax.Array | None = None,
                              n_demand: int = 0) -> jax.Array:
    """Pallas-kernel twin of :func:`attend_pages_paged`.

    Same signature, same masking semantics, same fp32 online-softmax
    numerics (tolerance-level, not bitwise: the kernel streams pages
    through a running max/sum while the XLA path materialises the full
    gather then normalises once).  On TPU the selected pages are
    scalar-prefetched and the grid pipeline double-buffers the indirect
    page DMAs — the NVR runahead mechanism on the serve layer's native
    block-table layout; off-TPU it runs in interpret mode.  The XLA path
    stays the CPU fallback and the parity oracle.
    """
    from ..kernels.paged_decode_attn import paged_decode_attn
    return paged_decode_attn(phys, idx, pos, q, k_pool_li, v_pool_li,
                             page_size=page, interpret=interpret,
                             hot_map=hot_map, n_demand=n_demand)


def page_summary_from_pool(k_pool_li: jax.Array, phys: jax.Array,
                           n_tokens: jax.Array) -> jax.Array:
    """Exact label-cache entries for pool pages: mean of the first
    ``n_tokens`` keys of each page ``phys``.

    k_pool_li [P,KV,page,D]; phys [M]; n_tokens [M] (>=1).  Returns
    [M,KV,D].  Both the chunked-prefill and the paged-decode paths
    recompute summaries through this one function so the selection
    scores cannot drift between the two (preemption-recompute relies on
    bitwise-identical replay).
    """
    rows = kv_dequant_f32(k_pool_li[phys])          # [M,KV,page,D]
    page = rows.shape[2]
    tmask = (jnp.arange(page)[None, None, :, None]
             < n_tokens[:, None, None, None])
    cnt = jnp.maximum(n_tokens, 1).astype(jnp.float32)
    return (rows * tmask).sum(axis=2) / cnt[:, None, None]


def update_page_summary(kpage: jax.Array, k_new: jax.Array, pos: jax.Array,
                        page: int) -> jax.Array:
    """Incremental label-cache update: running mean of keys per page.

    kpage [B,NP,KV,D]; k_new [B,1,KV,D] written at absolute position pos.
    Implemented as a masked elementwise update: a dynamic-start slice on
    the (sequence-sharded) page dim would force GSPMD to all-gather the
    whole label cache every layer (§Perf iteration 2 — measured 537 MB/
    layer on gemma long_500k).
    """
    p_id = pos // page
    off = (pos % page).astype(jnp.float32)
    match = (jnp.arange(kpage.shape[1]) == p_id)[None, :, None, None]
    upd = (kpage * off + k_new.astype(kpage.dtype)) / (off + 1.0)
    return jnp.where(match, upd, kpage)
