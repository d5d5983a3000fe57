"""Serving engines with NVR sparse-KV decode.

Two engines share one memory-system story:

:class:`Engine` — the single-batch baseline.  One fixed batch prefills
together and decodes in lockstep; no new request joins until the batch
drains.  Kept as the reference point ``benchmarks/serve_bench.py``
measures continuous batching against.

:class:`PagedEngine` — the continuous-batching engine.  Requests arrive
through an admission queue (:mod:`.scheduler`), an iteration-level
scheduler mixes prefill chunks and decode steps under a token budget, and
the KV cache is a pool of physical pages managed by
:class:`.kv_allocator.KVBlockAllocator` (block table per request,
free-list, preempt-and-evict under pressure).  The step loop is the
repo's serving fast path: pool buffers are *donated* into the decode and
prefill jits (no per-call pool copy), ragged decode batches pad to
power-of-two row buckets (O(log max_batch) traces, padded compute that
tracks the live batch), and the decode attention can run either the XLA
gather oracle or the fused Pallas runahead kernel
(``kernels.paged_decode_attn``) on the same pool layout.  The *physical page id* is
the shared currency across layers: the TopK paged-attention gather
(``sparse_attention.select_pages_blocktable``), the NSB hot-set
accounting (``capture.PageCache``), and the captured simulator trace
(``capture.PageStream`` with request/step tags) all account in the
allocator's page ids, so eviction policy, hot-set reuse, and NVR
prefetch simulation see one memory model.

Preemption is engineered for *bitwise-identical* resume under either
policy.  With a host spill tier (``spill_pages > 0``) eviction is
**swap-out**: the victim's pages snapshot whole (K, V, and the fp32
page summaries the TopK selection reads) into a host pool
(:mod:`.spill`), and resume restores them onto fresh physical ids —
identical content in identical logical order, and the paged attention
selects and gathers through the block table, so physical renaming
cannot change a logit.  Without the tier (or when it is full) the
recompute policy applies: prompts re-prefill through the same chunk
schedule, and already-generated tokens *replay* through the decode path
(teacher forcing), so the same jitted functions see the same inputs and
the request's logits are reproduced exactly.  The int8-compressed spill
tier (``spill_compress=True``) trades bitwise K/V restore for ~2x fewer
host bytes with a per-page ``scale/2`` error bound — summaries stay
exact, so page *selection* survives even compressed swaps.

With ``mesh=`` the engine is tensor-parallel: pools and QKV weights
shard along the KV-head axis over a ``("model",)`` mesh while the page
id space, block tables and scheduler state stay global, and every
cross-shard combine is a concatenation — logits remain bitwise-identical
to the unsharded engine (see ``_paged_decode_fn`` and the sharded-serve
section of ARCHITECTURE.md).

Per-step page traffic is scored against the NSB model, and with
``capture_trace=True`` each decode step's *layer-0* TopK selection (the
same layer-0 traffic proxy the single-batch engine uses, but computed
from the real decode queries) is recorded, tagged with request id and
scheduler iteration, into a
:class:`~repro.core.nvr.capture.PageStream`; ``captured_trace()`` lowers
it to a simulator ``Trace``, so multi-tenant serving traffic — not a
synthetic generator — drives the NVR/inorder comparison.  This container
is CPU-only: reported rates are traffic counts, not wall-clock.
"""

from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import sharding
from ..configs.base import ArchConfig
from ..core.nvr import capture
from ..models import api, sparse_attention, transformer
from ..models import layers as mlayers
from . import expert_pool as expert_pool_mod
from . import runahead as runahead_mod
from . import scheduler as scheduler_mod
from .kv_allocator import NULL_PAGE, KVBlockAllocator, PagePoolConfig
from .scheduler import PrefillJob, Request, RequestState, Scheduler
from .spill import HostSpillPool


def percentile(xs, q: float) -> float | None:
    """Nearest-rank (ceil-rank) percentile: the ``ceil(q*n)``-th order
    statistic, 1-indexed — numpy's ``inverted_cdf`` method, and the one
    definition engine metrics and serve_bench share.  (The earlier
    ``round(q*(n-1))`` form banker's-rounded ``.5`` ranks upward: p50 of
    4 samples returned the 3rd order statistic instead of the 2nd.)

    Empty input returns None, not NaN: ``metrics()`` flows into
    ``json.dumps``, and a NaN there emits a non-strict-JSON token that
    breaks downstream parsers on zero-traffic smoke runs."""
    xs = sorted(xs)
    if not xs:
        return None
    return float(xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))])


@dataclass
class ServeStats:
    steps: int = 0
    pages_touched: int = 0
    pages_unique: int = 0
    nsb_hits: int = 0
    nsb_misses: int = 0
    tokens_out: int = 0
    row_bytes: int = 0              # K+V bytes fetched per demanded page

    @property
    def hot_hit_rate(self) -> float | None:
        """NSB hit rate, or None before any traffic (keeps metrics
        strict-JSON-serialisable on zero-traffic runs)."""
        tot = self.nsb_hits + self.nsb_misses
        return self.nsb_hits / tot if tot else None

    @property
    def demand_bytes(self) -> int:
        """Total off-chip demand: every touched page is one K+V page
        fetch of ``row_bytes`` (the same per-row size the capture
        recorder charges, so serve metrics and simulator replay count
        the same bytes)."""
        return (self.nsb_hits + self.nsb_misses) * self.row_bytes

    @property
    def offchip_reduction(self) -> float | None:
        """Fetch-bytes reduction from the NSB hot-set: bytes *not*
        fetched (hot-set hits x per-page fetch bytes) over total demand
        bytes — the bytes-over-bytes definition the NVR simulator's
        ``demand_miss_reduction`` uses, so the two metrics compare like
        with like.  None until the engine sets ``row_bytes`` and traffic
        has been scored."""
        tot = self.demand_bytes
        return (self.nsb_hits * self.row_bytes) / tot if tot else None


class Engine:
    """Single-batch baseline: batched prefill + lockstep sparse decode."""

    def __init__(self, cfg: ArchConfig, params, max_len: int = 1024,
                 sparse: bool = True, nsb_pages: int = 64,
                 capture_trace: bool = False,
                 kv_dtype_bytes: int = 2) -> None:
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.sparse = sparse and cfg.sparse_kv
        self.stats = ServeStats(
            row_bytes=2 * cfg.kv_page * cfg.hd * kv_dtype_bytes)
        # NSB hot-set accounting on the shared simulator cache model
        self.hot = capture.PageCache(nsb_pages)
        self._seen_pages: set[int] = set()
        self.recorder = None
        if capture_trace and self.sparse:
            self.recorder = capture.kv_page_stream(
                f"serve-{cfg.name}", n_pages=max_len // cfg.kv_page,
                page_tokens=cfg.kv_page, head_dim=cfg.hd,
                dtype_bytes=kv_dtype_bytes)
        self._decode = jax.jit(
            lambda p, c, t: api.decode_fn(cfg, p, c, t, sparse=self.sparse))
        self.cache = None
        self._last = None

    def prefill(self, batch: dict) -> jax.Array:
        logits, cache = api.prefill_fn(self.cfg, self.params, batch,
                                       remat="none")
        self.cache = self._pad_cache(cache)
        self._last = jnp.argmax(logits, axis=-1)
        return self._last

    def _pad_cache(self, cache: dict) -> dict:
        cfg = self.cfg
        l, b, s, kv, hd = cache["k"].shape
        pad = self.max_len - s
        if pad <= 0:
            return cache
        z = jnp.zeros((l, b, pad, kv, hd), cache["k"].dtype)
        out = dict(cache)
        out["k"] = jnp.concatenate([cache["k"], z], axis=2)
        out["v"] = jnp.concatenate([cache["v"], z], axis=2)
        if "kpage" in cache:
            npad = self.max_len // cfg.kv_page - cache["kpage"].shape[2]
            out["kpage"] = jnp.concatenate(
                [cache["kpage"],
                 jnp.zeros((l, b, npad, kv, hd), jnp.float32)], axis=2)
        return out

    def _track_pages(self) -> None:
        """NSB accounting: which pages would the next step's selection
        touch (layer-0 scorer as the traffic proxy)."""
        cfg = self.cfg
        cache = self.cache
        if "kpage" not in cache:
            return
        kp0 = cache["kpage"][0]
        b = kp0.shape[0]
        q = jnp.ones((b, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
                      cfg.hd), kp0.dtype)
        n_valid = cache["pos"] // cfg.kv_page + 1
        k_pages = min(cfg.kv_topk_pages, kp0.shape[1])
        if self.recorder is not None:
            idx = np.asarray(sparse_attention.select_pages_recorded(
                q, kp0, n_valid, k_pages, self.recorder))
        else:
            idx = np.asarray(sparse_attention.select_pages(
                q, kp0, n_valid, k_pages))
        uniq = np.unique(idx)
        self._seen_pages.update(int(p) for p in uniq)
        self.stats.pages_unique = len(self._seen_pages)  # run footprint
        for p in uniq:
            self.stats.pages_touched += 1
            if self.hot.touch(int(p)):
                self.stats.nsb_hits += 1
            else:
                self.stats.nsb_misses += 1

    def step(self) -> jax.Array:
        if self.sparse:
            self._track_pages()
        logits, self.cache = self._decode(self.params, self.cache,
                                          self._last)
        self._last = jnp.argmax(logits, axis=-1)
        self.stats.steps += 1
        self.stats.tokens_out += int(self._last.shape[0])
        return self._last

    def generate(self, batch: dict, n_steps: int) -> np.ndarray:
        toks = [self.prefill(batch)]
        for _ in range(n_steps - 1):
            toks.append(self.step())
        return np.stack([np.asarray(t) for t in toks], axis=1)

    def captured_trace(self):
        """The decode run's recorded page traffic as a simulator Trace
        (requires ``capture_trace=True`` and at least one sparse step)."""
        if self.recorder is None:
            raise RuntimeError(
                "no trace recorder: construct the Engine with "
                "capture_trace=True AND the sparse-KV path enabled "
                "(sparse=True and cfg.sparse_kv) to record selections")
        return self.recorder.to_trace()


# -- continuous batching -------------------------------------------------------

@dataclass
class PagedServeStats(ServeStats):
    iterations: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    preemptions: int = 0
    finished: int = 0
    cow_page_copies: int = 0
    decode_rows_padded: int = 0     # NULL rows computed across the run
    prefill_calls: int = 0          # executed prefill-chunk jit calls
    swap_out_pages: int = 0         # pages snapshotted device -> host
    swap_in_pages: int = 0          # pages restored host -> device
    fetch_backs: int = 0            # runahead-window early swap-resumes
    # multi-turn session layer (session_hold=True)
    session_holds: int = 0          # finished turns whose KV was pinned
    turns_submitted: int = 0        # follow-up turns re-entering the door
    idle_swap_outs: int = 0         # holds parked in the host spill tier
    idle_swap_ins: int = 0          # holds restored for their next turn
    idle_evictions: int = 0         # holds released under page pressure
    # expert-weight page traffic (expert_pool != "off"): unique tile
    # pages demanded per decode step, scored against the expert NSB
    expert_pages_touched: int = 0
    expert_nsb_hits: int = 0
    expert_nsb_misses: int = 0
    # per-stream iteration accounting (the disaggregated executor's
    # TTFT/TPOT split): an iteration belongs to the prefill stream when
    # it ran >=1 prompt chunk, to the decode stream when it ran a decode
    # batch, and to both when the streams overlap
    prefill_iterations: int = 0
    decode_iterations: int = 0
    overlap_iterations: int = 0     # iterations where both streams ran
    # (n_prefill_chunks, n_decode_rows) per iteration — the shared
    # timeline overlap_bench's deterministic cost model replays to
    # compare sync (streams serial) vs async (streams overlapped)
    iter_log: list = field(default_factory=list)

    @property
    def expert_hot_hit_rate(self) -> float | None:
        """Expert-tile NSB hit rate (None before expert traffic)."""
        tot = self.expert_nsb_hits + self.expert_nsb_misses
        return self.expert_nsb_hits / tot if tot else None


# sentinel distinguishing "run _fetch_back inline" (sync loop) from "the
# executor already ran it in the overlap window, possibly returning None"
_FETCH_UNSET = object()


def _paged_decode_fn(cfg: ArchConfig, kernel: str = "xla", tp: int = 1,
                     tp_axis: str | None = None, n_demand: int = 0,
                     ep_mode: str = "off", ep_n_demand: int = 0):
    """Build the ragged decode step over the physical page pools.

    One call advances R requests by one token each: per-request positions
    (no lockstep), KV written through the block table into physical
    pages, page summaries recomputed exactly, TopK selection + gather by
    physical page id.  Padded rows carry block table NULLs and scribble
    the reserved scratch page 0.

    ``kernel`` picks the attention implementation: ``"xla"`` is the
    ``attend_pages_paged`` gather (runs everywhere; the parity oracle),
    ``"pallas"`` is the fused ``kernels.paged_decode_attn`` runahead
    kernel on the same pool layout (scalar-prefetched page ids,
    double-buffered indirect DMAs; interpret mode off-TPU).

    With ``tp > 1`` this is the *per-shard* body run under ``shard_map``
    (see :func:`_shard_serve_fn`): params carry this shard's head slice
    of the QKV projections, pools its KV-head slice, and the per-head
    attention outputs are all-gathered (``tp_axis``) before the
    replicated output projection.  Every cross-shard combine is a
    concatenation of independent per-head results — never an arithmetic
    reduction — which is what keeps tp>1 logits bitwise-identical to
    tp=1.  Block tables, frontiers and the returned TopK ids stay in the
    one global physical page-id space.

    With ``n_demand > 0`` the function is the *runahead* variant: it
    takes a trailing ``hot_map`` argument (int32 [n_demand], demand page
    id -> staged NSB slot, -1 = not staged) and the attention gather
    resolves TopK ids through it, reading staged copies from the pool's
    contiguous tail at ``n_demand + slot`` (see
    ``sparse_attention.attend_pages_paged``).  Staged pages are
    byte-exact copies, so logits — and the *returned selection*, which
    stays in original demand page ids — are bitwise-identical to the
    no-runahead variant; with ``n_demand == 0`` the built graph is
    exactly the historic one (no extra argument, no remap ops).

    ``ep_mode`` selects the expert-FFN implementation for MoE configs
    (see :mod:`.expert_pool`): ``"off"`` keeps the historic
    ``transformer._ffn`` (capacity-dispatch ``moe_ffn``); ``"dense"``
    takes a trailing dense-materialised ``ep_rows [L,E,3,NT,tile,D]``
    operand; ``"paged"`` takes trailing ``(ep_bt [L,E,3,NT], ep_pool
    [P+slots,tile,D])`` and resolves routed expert ids through the
    block table (plus a trailing ``ep_hot`` hot-map when
    ``ep_n_demand > 0`` — expert tiles staged in the pool's NSB tail).
    Both expert modes additionally return the per-layer routed expert
    ids ``esel [L, R, top_k]``; dense and paged gather bitwise-
    identical weight bytes into the same combine graph, so tokens and
    logits are bitwise-invariant across dense / paged / paged+runahead.
    Expert weights and routing are replicated under tp (only QKV
    shards), so ``esel`` is shard-invariant.
    """
    page = cfg.kv_page
    dt = jnp.dtype(cfg.param_dtype)
    kv_l = cfg.n_kv_heads // tp              # KV heads on this shard
    g = cfg.n_heads // cfg.n_kv_heads        # GQA groups stay whole
    h_l = kv_l * g

    def fn(params, k_pool, v_pool, s_pool, token, pos, bt, *extra):
        i = 0
        hot_map = None
        if n_demand:
            hot_map, i = extra[0], 1
        ep_rows = ep_bt = ep_pool = ep_hot = None
        if ep_mode == "dense":
            ep_rows = extra[i]
        elif ep_mode == "paged":
            ep_bt, ep_pool = extra[i], extra[i + 1]
            if ep_n_demand:
                ep_hot = extra[i + 2]
        r = token.shape[0]
        nl = bt.shape[1]
        k_sel = int(min(cfg.kv_topk_pages, nl))
        x = jnp.take(params["embed"], token[:, None], axis=0).astype(dt)
        if getattr(cfg, "scale_embed", False):
            x = x * (cfg.d_model ** 0.5)
        pos_arr = pos[:, None]                       # [R,1]
        lp_w = pos // page
        off = pos % page
        phys_w = jnp.take_along_axis(bt, lp_w[:, None], axis=1)[:, 0]
        n_valid = lp_w + 1
        lidx = jnp.arange(cfg.n_layers, dtype=jnp.int32)

        def body(carry, lp_li):
            xc, kp_, vp_, sp_ = carry
            lp, li = lp_li
            h = mlayers.rms_norm(xc, lp["ln1"], cfg.norm_eps)
            q, k_new, v_new = mlayers.gqa_project(h, lp, cfg, h_l, kv_l)
            q = mlayers.apply_rope(q, pos_arr, cfg.rope_theta)
            k_new = mlayers.apply_rope(k_new, pos_arr, cfg.rope_theta)
            kq = sparse_attention.kv_quant(k_new[:, 0], kp_.dtype)
            vq = sparse_attention.kv_quant(v_new[:, 0], vp_.dtype)
            kp_ = kp_.at[li, phys_w, :, off].set(kq)
            vp_ = vp_.at[li, phys_w, :, off].set(vq)
            if n_demand:
                # write-through into the NSB tail: when the frontier
                # page has a staged copy, mirror the new KV bytes into
                # it so staging survives the decode's own writes.  For
                # unstaged pages the target collapses to the primary
                # location — a re-write of the identical values — so
                # pool contents are unchanged either way.
                slot_w = hot_map[phys_w]
                wt = jnp.where(slot_w >= 0, n_demand + slot_w, phys_w)
                kp_ = kp_.at[li, wt, :, off].set(kq)
                vp_ = vp_.at[li, wt, :, off].set(vq)
            summ = sparse_attention.page_summary_from_pool(
                kp_[li], phys_w, off + 1)
            sp_ = sp_.at[li, phys_w].set(summ)
            qh = q.reshape(r, kv_l, g, cfg.hd)
            idx, phys = sparse_attention.select_pages_blocktable(
                qh, sp_[li], bt, n_valid, k_sel)
            if kernel == "pallas":
                # the fused runahead kernel streams its shard's pages
                # end to end; per-head outputs concat across shards
                # (tolerance-level parity, as on a single shard)
                o = sparse_attention.attend_pages_paged_kernel(
                    qh, kp_[li], vp_[li], idx, phys, pos, page,
                    hot_map=hot_map, n_demand=n_demand)
                o = o.reshape(r, 1, h_l, cfg.hd)
                if tp_axis is not None:
                    o = jax.lax.all_gather(o, tp_axis, axis=2,
                                           tiled=True)
            else:
                # XLA oracle: local pool gather, then the small TopK
                # tiles all-gather and the softmax math replays at the
                # full-KV shape — bitwise equal to tp=1 (see
                # attend_pages_paged)
                o = sparse_attention.attend_pages_paged(
                    qh, kp_[li], vp_[li], idx, phys, pos, page,
                    tp_axis=tp_axis, hot_map=hot_map, n_demand=n_demand)
                o = o.reshape(r, 1, cfg.n_heads if tp_axis is not None
                              else h_l, cfg.hd)
            xc = xc + mlayers.attn_out(o, lp, cfg.d_model)
            h2 = mlayers.rms_norm(xc, lp["ln2"], cfg.norm_eps)
            if ep_mode == "off":
                xc = xc + transformer._ffn(h2, lp, cfg)
                return (xc, kp_, vp_, sp_), phys
            if ep_mode == "dense":
                y, eids = expert_pool_mod.dense_moe_ffn(
                    h2, lp, jnp.take(ep_rows, li, axis=0), cfg)
            else:
                y, eids = expert_pool_mod.paged_moe_ffn(
                    h2, lp, jnp.take(ep_bt, li, axis=0), ep_pool, cfg,
                    hot_map=ep_hot, n_demand=ep_n_demand, kernel=kernel)
            xc = xc + y
            return (xc, kp_, vp_, sp_), (phys, eids)

        (x, k2, v2, s2), sel = mlayers.scan_layers(
            body, (x, k_pool, v_pool, s_pool), (params["layers"], lidx))
        x = mlayers.rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = transformer.logits_last(params, cfg, x)
        if ep_mode == "off":
            return logits, k2, v2, s2, sel
        return logits, k2, v2, s2, sel[0], sel[1]

    return fn


def _paged_prefill_fn(cfg: ArchConfig, chunk: int, tp: int = 1,
                      tp_axis: str | None = None):
    """Build the chunked-prefill step for one request.

    Processes ``t_valid <= chunk`` prompt tokens starting at absolute
    position ``start``: dense causal attention over the request's paged
    context (gathered through the block table), KV scattered into the
    pool, page summaries recomputed through the same
    ``page_summary_from_pool`` the decode path uses.  Padded positions
    write to scratch page 0.

    ``tp``/``tp_axis`` follow :func:`_paged_decode_fn`: with tp > 1 this
    is the per-shard body — projection and pool writes run on local KV
    heads, then the per-request *context view* (block-table-gathered
    from the sharded pools) all-gathers and the dense attention replays
    at the full-head shape identically on every shard, the same
    bitwise mechanism as the decode path.
    """
    page = cfg.kv_page
    dt = jnp.dtype(cfg.param_dtype)
    ntp = chunk // page + 2           # touched-page bound per chunk
    kv_l = cfg.n_kv_heads // tp
    h_l = (cfg.n_heads // cfg.n_kv_heads) * kv_l

    def fn(params, k_pool, v_pool, s_pool, tokens, start, t_valid, bt):
        nl = bt.shape[0]
        c = tokens.shape[0]
        x = jnp.take(params["embed"], tokens[None, :], axis=0).astype(dt)
        if getattr(cfg, "scale_embed", False):
            x = x * (cfg.d_model ** 0.5)
        pos = start + jnp.arange(c)                  # [C]
        in_chunk = jnp.arange(c) < t_valid
        lp_w = jnp.clip(pos // page, 0, nl - 1)
        phys_w = jnp.where(in_chunk, bt[lp_w], 0)
        off = pos % page
        end = start + t_valid
        lps = start // page + jnp.arange(ntp)
        pvalid = lps <= (end - 1) // page
        phys_s = jnp.where(pvalid, bt[jnp.clip(lps, 0, nl - 1)], 0)
        cnts = jnp.clip(end - lps * page, 1, page)
        lidx = jnp.arange(cfg.n_layers, dtype=jnp.int32)

        def body(carry, lp_li):
            xc, kp_, vp_, sp_ = carry
            lp, li = lp_li
            h = mlayers.rms_norm(xc, lp["ln1"], cfg.norm_eps)
            q, k_new, v_new = mlayers.gqa_project(h, lp, cfg, h_l, kv_l)
            q = mlayers.apply_rope(q, pos[None, :], cfg.rope_theta)
            k_new = mlayers.apply_rope(k_new, pos[None, :], cfg.rope_theta)
            kq = sparse_attention.kv_quant(k_new[0], kp_.dtype)
            vq = sparse_attention.kv_quant(v_new[0], vp_.dtype)
            kp_ = kp_.at[li, phys_w, :, off].set(kq)
            vp_ = vp_.at[li, phys_w, :, off].set(vq)
            summ = sparse_attention.page_summary_from_pool(
                kp_[li], phys_s, cnts)
            sp_ = sp_.at[li, phys_s].set(summ)
            # dense causal attention over the paged context: the block
            # table linearises this request's pages back into logical
            # order, so positions align with q_offset=start
            kctx = kp_[li, bt].swapaxes(1, 2).reshape(1, nl * page, kv_l,
                                                      cfg.hd)
            vctx = vp_[li, bt].swapaxes(1, 2).reshape(1, nl * page, kv_l,
                                                      cfg.hd)
            if tp_axis is not None:
                # same bitwise mechanism as decode: the context view is
                # gathered from the sharded pools (pool *storage* stays
                # 1/tp) and the attention math replays at the full-head
                # shape identically on every shard — per-head softmax
                # lowering is shape-dependent at ulp level, so local-
                # shape attention would drift from the tp=1 oracle
                q, kctx, vctx = jax.lax.all_gather(
                    (q, kctx, vctx), tp_axis, axis=2, tiled=True)
            o = mlayers.chunked_attention(
                q, kctx, vctx, causal=True, q_offset=start,
                chunk=min(1024, nl * page),
                logit_softcap=cfg.logit_softcap)
            xc = xc + mlayers.attn_out(o, lp, cfg.d_model)
            h2 = mlayers.rms_norm(xc, lp["ln2"], cfg.norm_eps)
            xc = xc + transformer._ffn(h2, lp, cfg)
            return (xc, kp_, vp_, sp_), None

        (x, k2, v2, s2), _ = mlayers.scan_layers(
            body, (x, k_pool, v_pool, s_pool), (params["layers"], lidx))
        x = mlayers.rms_norm(x, params["ln_f"], cfg.norm_eps)
        hl = jax.lax.dynamic_index_in_dim(x[0], t_valid - 1, 0,
                                          keepdims=True)      # [1,D]
        logits = transformer.logits_last(params, cfg, hl[None])[0]
        return logits, k2, v2, s2

    return fn


def _shard_serve_fn(fn, mesh, param_specs, n_rep_args: int,
                    sel_out: bool = False, esel_out: bool = False,
                    axis: str = sharding.SERVE_TP_AXIS):
    """Wrap a per-shard decode/prefill body in ``shard_map`` over the
    KV-head axis.

    In: params per ``sharding.serve_param_specs`` (QKV head-sharded,
    rest replicated), k/v/s pools sharded on their KV-head dim, and
    ``n_rep_args`` replicated host args (tokens, positions, block
    tables).  Out: logits replicated (each shard computes the identical
    post-gather value — no reduction ever crosses shards), pools sharded
    as they came in (donation-compatible), and for decode the stacked
    TopK physical ids sharded on their KV-head dim — ``np.asarray`` on
    the host reassembles the global ``[L,R,KV,K]`` selection, so the
    allocator/NSB/capture layers keep seeing one physical page-id space.
    """
    kv_spec, s_spec = sharding.serve_pool_specs(axis)
    in_specs = (param_specs, kv_spec, kv_spec, s_spec) \
        + (P(),) * n_rep_args
    out_specs = (P(), kv_spec, kv_spec, s_spec)
    if sel_out:
        out_specs = out_specs + (P(None, None, axis),)
    if esel_out:
        # routed expert ids: router and residual stream are replicated
        # under serve TP, so every shard computes the identical routing
        out_specs = out_specs + (P(),)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class PagedEngine:
    """Continuous-batching serve engine on a paged KV allocator.

    ``submit()`` enqueues requests; ``step()`` runs one scheduler
    iteration (admission + mixed prefill chunks / ragged decode batch);
    ``run()`` drives an arrival workload to completion.

    With ``prefix_cache=True`` (default) physical prompt pages are
    shared across requests: completed whole prompt pages are published
    to the allocator's content-addressed prefix index after each prefill
    chunk, admission attaches matching cached pages (refcount++) and
    fast-forwards the request's KV frontier past them — shared prefixes
    cost zero model FLOPs while logits stay bitwise-identical to the
    uncached run (the final prompt token is always recomputed, on a
    copy-on-write private page when the whole prompt was cached).

    Step-loop fast-path knobs (all default-on except the kernel):

    * ``kernel="xla" | "pallas"`` — the decode attention implementation.
      ``"xla"`` (default) is the ``attend_pages_paged`` gather: runs on
      any backend and is the parity oracle the bitwise-resume guarantees
      are pinned to.  ``"pallas"`` fuses gather + online-softmax in
      ``kernels.paged_decode_attn`` with the TopK physical page ids
      scalar-prefetched (the NVR runahead pipeline on the pool layout);
      off-TPU it runs in interpret mode — parity is tolerance-level
      (fp32 online softmax), not bitwise.
    * ``donate_pools`` — donate the k/v/s pool buffers into the decode
      and prefill jits, so XLA updates pages in place instead of copying
      the full ``[L,P,KV,page,D]`` pools every call.
    * ``row_bucketing`` — pad ragged decode batches to power-of-two row
      buckets (NULL block-table rows) instead of always to
      ``max_batch``: padded compute tracks the live batch while the
      trace count stays O(log max_batch) (``metrics()["n_decode_traces"]``),
      and the scheduler tops buckets up with budget-deferred rows
      (free-slot decode).
    * ``mesh`` — tensor parallelism over a 1-axis ``("model",)`` mesh
      (``launch.mesh.make_serve_mesh``): the physical k/v/s pools and
      the QKV projection weights shard along the KV-head axis (1/tp of
      the pool bytes per shard), block tables / frontiers / TopK page
      ids stay replicated in the one global physical page-id space, and
      both step functions run as per-shard ``shard_map`` bodies whose
      only cross-shard traffic is an all-gather of independent per-head
      attention outputs — logits are *bitwise-identical* to the tp=1
      engine, so preemption-resume and prefix-cache guarantees survive
      sharding unchanged.  Requires ``tp`` to divide ``n_heads`` and
      ``n_kv_heads``; each shard runs its own NSB hot-set
      (``metrics()["nsb_shard_hit_rates"]``).
    * ``runahead="off" | "imp" | "nvr"`` — the online runahead stage
      (see :mod:`.runahead` and the "online runahead" section of
      ARCHITECTURE.md).  ``"nvr"`` predicts each live request's
      next-iteration TopK pages between decode steps (history
      predictors filtered DARE-style, layer-0 proxy scoring for the
      rest) and stages them into a physical NSB tail appended to the
      k/v pools; the decode gather resolves TopK ids through the
      hot-map into the staged copies.  ``"imp"`` is the one-step-behind
      baseline: it stages exactly the pages the current step selected.
      Staged pages are byte-exact copies and block tables stay
      authoritative, so tokens and logits are bitwise-identical with
      runahead on or off — mispredictions cost staging bandwidth only.
      ``runahead_pages`` bounds staging copies per iteration;
      ``nsb_pages`` sizes the staging tail (and the demand-LRU
      comparator).
    """

    def __init__(self, cfg: ArchConfig, params, max_len: int = 64,
                 n_pages: int = 0, max_batch: int = 8, chunk: int = 16,
                 token_budget: int = 0, nsb_pages: int = 64,
                 capture_trace: bool = False,
                 kv_dtype_bytes: int = 2,
                 prefix_cache: bool = True,
                 kernel: str = "xla",
                 donate_pools: bool = True,
                 row_bucketing: bool = True,
                 mesh=None,
                 runahead: str = "off",
                 runahead_pages: int = 8,
                 expert_pool: str = "off",
                 expert_tile_rows: int = 32,
                 expert_nsb_slots: int = 32,
                 expert_runahead: str = "off",
                 expert_runahead_pages: int = 16,
                 spill_pages: int = 0,
                 spill_compress: bool = False,
                 executor: str = "sync",
                 policy=None,
                 session_hold: bool = False,
                 idle_swap: bool = False) -> None:
        if cfg.family not in ("dense", "moe") or cfg.mrope_sections:
            raise NotImplementedError(
                "PagedEngine supports dense/moe decoder-only configs")
        if not cfg.sparse_kv:
            raise NotImplementedError(
                "PagedEngine requires the sparse-KV decode path")
        if max_len % cfg.kv_page:
            raise ValueError("max_len must be a multiple of cfg.kv_page")
        if kernel not in ("xla", "pallas"):
            raise ValueError(f"kernel must be 'xla' or 'pallas', "
                             f"got {kernel!r}")
        if runahead not in runahead_mod.MODES:
            raise ValueError(f"runahead must be one of "
                             f"{runahead_mod.MODES}, got {runahead!r}")
        if expert_pool not in expert_pool_mod.MODES:
            raise ValueError(f"expert_pool must be one of "
                             f"{expert_pool_mod.MODES}, got {expert_pool!r}")
        if expert_pool != "off" and not cfg.n_experts:
            raise ValueError("expert_pool requires an MoE-family config "
                             f"(cfg {cfg.name!r} has n_experts=0)")
        if expert_runahead not in runahead_mod.EXPERT_MODES:
            raise ValueError(
                f"expert_runahead must be one of "
                f"{runahead_mod.EXPERT_MODES}, got {expert_runahead!r}")
        if expert_runahead != "off" and expert_pool != "paged":
            raise ValueError(
                "expert_runahead needs expert_pool='paged': only the "
                "paged path resolves tiles through a hot-map")
        if executor not in ("sync", "async"):
            raise ValueError(f"executor must be 'sync' or 'async', "
                             f"got {executor!r}")
        self.mesh = mesh
        if mesh is not None:
            if sharding.SERVE_TP_AXIS not in dict(mesh.shape):
                raise ValueError(
                    f"serve mesh needs a {sharding.SERVE_TP_AXIS!r} "
                    f"axis, got {tuple(dict(mesh.shape))} (use "
                    "launch.mesh.make_serve_mesh)")
            self.tp = int(dict(mesh.shape)[sharding.SERVE_TP_AXIS])
            if cfg.n_kv_heads % self.tp or cfg.n_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide both head counts "
                    f"(n_heads={cfg.n_heads}, n_kv_heads="
                    f"{cfg.n_kv_heads}): GQA groups shard whole, one "
                    "KV-head slice per shard")
        else:
            self.tp = 1
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.page = cfg.kv_page
        self.n_logical = max_len // self.page
        chunk = min(chunk, max_len)
        # pool default: every batch slot can hold a full-length request,
        # +1 for the reserved scratch page
        self.n_pages = n_pages or (1 + max_batch * self.n_logical)
        self.allocator = KVBlockAllocator(self.n_pages, self.page,
                                          prefix_cache=prefix_cache,
                                          spill_pages=spill_pages)
        self.kernel = kernel
        self.donate_pools = donate_pools
        self.row_buckets = (scheduler_mod.row_buckets(max_batch)
                            if row_bucketing else ())
        # online runahead: a physical NSB staging tail appended to the
        # k/v pools, a hot-map resolving TopK ids into it, and a
        # predict->filter->stage pipeline between decode steps.  With
        # runahead="off" everything below is inert and the decode graph
        # is the exact historic one.
        self.runahead = runahead
        self.runahead_pages = runahead_pages
        self.nsb_slots = (min(nsb_pages, self.n_pages - 1)
                          if runahead != "off" else 0)
        self._tier = (runahead_mod.NSBHotTier(self.n_pages,
                                              self.nsb_slots)
                      if runahead != "off" else None)
        self._predictor = (runahead_mod.RunaheadPredictor(mode=runahead)
                           if runahead != "off" else None)
        # paged expert-weight pool (MoE family): expert FFN weights as
        # fixed row-tile pages with per-layer block tables, an NSB
        # staging tail for router-predicted tiles, and a demand-LRU
        # comparator — the KV machinery's layout applied to the one
        # read-only gather workload the paper is about
        self.expert_pool_mode = expert_pool
        self.expert_runahead = expert_runahead
        self.expert_runahead_pages = expert_runahead_pages
        self.ep = None
        self._ep_tier = None
        self._ep_predictor = None
        self._ep_rows = None
        self._ep_bt = None
        self._ep_stage = None
        self._router_proxy = None
        self.ep_hot = None
        self.ep_recorder = None
        if expert_pool != "off":
            self.ep = expert_pool_mod.ExpertPool(
                cfg, params, tile_rows=expert_tile_rows,
                nsb_slots=(expert_nsb_slots if expert_runahead != "off"
                           else 0))
            self._ep_tier = self.ep.tier
            # the same demand traffic scored against a demand-install
            # LRU of the staging tier's capacity: the in-run baseline
            # the router-keyed hit rate is lifted over
            self.ep_hot = capture.PageCache(expert_nsb_slots)
            if expert_pool == "dense":
                self._ep_rows = self.ep.dense_rows()
            else:
                self._ep_bt = self.ep.table_device()
            if capture_trace:
                self.ep_recorder = capture.expert_page_stream(
                    f"serve-ep-{cfg.name}", n_pages=self.ep.n_pages,
                    tile_rows=self.ep.tile_rows, d_model=cfg.d_model,
                    dtype_bytes=self.ep.pool.dtype.itemsize)
            if expert_runahead != "off":
                self._ep_predictor = runahead_mod.RunaheadPredictor(
                    mode="nvr")
                self._router_proxy = jax.jit(
                    runahead_mod.make_router_scorer(cfg))

                # expert-tile staging gather: same donated fixed-shape
                # pattern as the KV _stage jit ((0,0) scratch self-copy
                # padding)
                def _ep_stage_body(pool, src, dst):
                    return pool.at[dst].set(pool[src])
                self._ep_stage = jax.jit(_ep_stage_body,
                                         donate_argnums=(0,))
        self.scheduler = Scheduler(
            self.allocator, max_batch=max_batch, chunk=chunk,
            token_budget=token_budget or (max_batch + chunk),
            row_buckets=self.row_buckets,
            # either runahead flavour claims the decode stream's
            # per-iteration staging grant
            runahead_pages=(runahead_pages if runahead != "off" else
                            (expert_runahead_pages
                             if expert_runahead != "off" else 0)),
            policy=policy)
        # multi-turn session layer: with session_hold, a finished
        # conversation turn's KV stays pinned under a *holder* rid until
        # the next turn arrives (idle_swap parks it in the host spill
        # tier instead of HBM), and the scheduler's idle-eviction hook
        # releases holds — idle sessions first — whenever live traffic
        # is starved for pages
        self.session_hold = session_hold
        self.idle_swap = idle_swap
        if idle_swap and spill_pages <= 0:
            raise ValueError("idle_swap=True needs a host spill tier "
                             "(spill_pages > 0) to park idle sessions in")
        self._sessions: dict[int, dict] = {}
        self._hold_order: list[int] = []    # sids with live holders, LRU
        self._deferred: list[int] = []      # sids with a pending turn
        self._next_sid = 0
        if session_hold:
            self.scheduler.idle_evict_hook = self._evict_idle_hold
        self.max_batch = max_batch
        self.chunk = chunk
        self.stats = PagedServeStats()
        self.hot = capture.PageCache(nsb_pages)
        # per-shard NSBs under TP: each model shard scores only the
        # pages its own KV heads select (the paper's per-NPU buffer)
        self.hot_shards = (capture.ShardedPageCache(self.tp, nsb_pages)
                           if self.tp > 1 else None)
        self._seen_pages: set[int] = set()
        self.recorder = None
        if capture_trace:
            self.recorder = capture.kv_page_stream(
                f"serve-cb-{cfg.name}", n_pages=self.n_pages,
                page_tokens=self.page, head_dim=cfg.hd,
                dtype_bytes=kv_dtype_bytes)
        kv_dt = (jnp.int8 if cfg.kv_dtype == "int8"
                 else jnp.dtype(cfg.param_dtype))
        # host spill tier: preemption becomes swap-out instead of
        # free-and-recompute (slot ids allocated by the allocator, bytes
        # owned by the pool, copies performed by _apply_spill_outs /
        # _apply_swap_ins in the step loop)
        self.spill_pool = (HostSpillPool(
            spill_pages, cfg.n_layers, self.page, cfg.n_kv_heads,
            cfg.hd, np.dtype(kv_dt), compress=spill_compress)
            if spill_pages > 0 else None)
        self._spill_err = 0.0       # running max dequant error bound
        self.pool_cfg = PagePoolConfig(
            n_pages=self.n_pages, page_tokens=self.page,
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd, dtype_bytes=jnp.dtype(kv_dt).itemsize)
        # same per-page fetch size the capture recorder charges
        # (kv_dtype_bytes models the production KV dtype, bf16 by
        # default), so demand_bytes and the captured-trace replay count
        # identical bytes per page
        self.stats.row_bytes = 2 * self.page * cfg.hd * kv_dtype_bytes
        # the k/v pools carry the demand region [0, n_pages) plus the
        # contiguous NSB staging tail [n_pages, n_pages + nsb_slots):
        # staged copies live there, addressed via the hot-map.  The
        # summary pool stays demand-sized — selection never reads the
        # tail, only the attention gather does.
        # [L, P, KV, page, D]: one head's page is a contiguous
        # [page, D] slab, the tile the Pallas kernel DMAs per grid step
        shape = (cfg.n_layers, self.n_pages + self.nsb_slots,
                 cfg.n_kv_heads, self.page, cfg.hd)
        self.k_pool = jnp.zeros(shape, kv_dt)
        self.v_pool = jnp.zeros(shape, kv_dt)
        self.s_pool = jnp.zeros(
            (cfg.n_layers, self.n_pages, cfg.n_kv_heads, cfg.hd),
            jnp.float32)
        # pool buffers are donated into both jits: the step loop rebinds
        # self.{k,v,s}_pool to the outputs, so XLA updates the pools in
        # place instead of round-tripping a full pool-sized copy per call
        donate = (1, 2, 3) if donate_pools else ()
        # runahead variants take the trailing replicated hot_map arg and
        # remap gathers into the staging tail; n_demand=0 builds the
        # exact historic graph (bitwise anchor for runahead="off")
        n_demand = self.n_pages if runahead != "off" else 0
        # expert-pool decode variants thread their (replicated) weight
        # operands as trailing args: dense rows, or block table + pool
        # (+ the expert hot-map when the staging tier is live)
        ep_n_demand = (self.ep.n_pages
                       if self._ep_tier is not None else 0)
        n_rep_decode = 3 if runahead == "off" else 4
        if expert_pool == "dense":
            n_rep_decode += 1
        elif expert_pool == "paged":
            n_rep_decode += 2 + (1 if ep_n_demand else 0)
        if mesh is None:
            self._pool_shardings = None
            self._decode = jax.jit(
                _paged_decode_fn(cfg, kernel, n_demand=n_demand,
                                 ep_mode=expert_pool,
                                 ep_n_demand=ep_n_demand),
                donate_argnums=donate)
            self._prefill = jax.jit(_paged_prefill_fn(cfg, chunk),
                                    donate_argnums=donate)
        else:
            # tensor parallelism: pools live KV-head-sharded on the mesh
            # (1/tp of the pool bytes per shard), params per the serve
            # TP rules (QKV head-sharded, the rest replicated), and both
            # step functions run as per-shard shard_map bodies — see
            # _paged_decode_fn for why this keeps logits bitwise equal
            # to tp=1
            kv_spec, s_spec = sharding.serve_pool_specs()
            self._pool_shardings = (
                NamedSharding(mesh, kv_spec),
                NamedSharding(mesh, kv_spec),
                NamedSharding(mesh, s_spec))
            self.k_pool = jax.device_put(self.k_pool,
                                         self._pool_shardings[0])
            self.v_pool = jax.device_put(self.v_pool,
                                         self._pool_shardings[1])
            self.s_pool = jax.device_put(self.s_pool,
                                         self._pool_shardings[2])
            pspecs = sharding.serve_param_specs(params)
            self.params = jax.device_put(
                params, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                     pspecs,
                                     is_leaf=lambda x: isinstance(x, P)))
            axis = sharding.SERVE_TP_AXIS
            self._decode = jax.jit(
                _shard_serve_fn(
                    _paged_decode_fn(cfg, kernel, self.tp, axis,
                                     n_demand=n_demand,
                                     ep_mode=expert_pool,
                                     ep_n_demand=ep_n_demand),
                    mesh, pspecs, n_rep_args=n_rep_decode, sel_out=True,
                    esel_out=(expert_pool != "off")),
                donate_argnums=donate)
            self._prefill = jax.jit(
                _shard_serve_fn(
                    _paged_prefill_fn(cfg, chunk, self.tp, axis),
                    mesh, pspecs, n_rep_args=4),
                donate_argnums=donate)
        self._proxy = None
        self._stage = None
        self.tier_shards = None
        if self._tier is not None:
            # the staging gather: copy predicted demand pages into the
            # NSB tail in one donated jit (in-place pool update, no
            # pool-sized round trip).  src/dst are padded to a fixed
            # length with (0, 0) self-copies — page 0 is the reserved
            # scratch page, so padding is a value-identical no-op and
            # the call compiles exactly once.
            def _stage_body(k_pool, v_pool, src, dst):
                return (k_pool.at[:, dst].set(k_pool[:, src]),
                        v_pool.at[:, dst].set(v_pool[:, src]))
            self._stage = jax.jit(
                _stage_body, donate_argnums=(0, 1),
                out_shardings=(None if mesh is None else
                               (self._pool_shardings[0],
                                self._pool_shardings[1])))
            if runahead == "nvr":
                # the address-generation slice (layer-0 proxy scorer);
                # speculation-only, so plain jit is fine under tp (GSPMD
                # handles the sharded wq; no bitwise contract needed)
                self._proxy = jax.jit(runahead_mod.make_proxy_scorer(cfg))
            if self.tp > 1:
                # per-shard runahead rollups: the page axis is never
                # sharded, so one staging copy lands every shard's NSB —
                # mirror stage/drop into per-shard accounting twins
                self.tier_shards = capture.ShardedPageCache(
                    self.tp, self.nsb_slots)
                self._tier.mirrors.append(self.tier_shards)
        self.now = 0
        self._next_rid = 0
        self.requests: dict[int, Request] = {}
        # pipelined executor (executor="async"): prefill/decode streams
        # dispatch before either materialises, plans double-buffer via
        # schedule_speculative/commit, and runahead transfers ride the
        # overlap window.  The synchronous loop (_step_sync) stays as
        # the bitwise parity oracle.
        self.executor = executor
        if executor == "async":
            from .executor import PipelinedExecutor
            self._pipeline = PipelinedExecutor(self)
        else:
            self._pipeline = None

    # -- request lifecycle ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               arrival: float | None = None,
               tenant: str = "default", priority: int = 0,
               session: int = -1, turn: int = 1,
               slo_ttft: float | None = None,
               slo_tpot: float | None = None) -> int:
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt+gen {len(prompt)}+{max_new_tokens} exceeds "
                f"max_len {self.max_len}")
        if not len(prompt) or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and >=1 new token")
        need = self.allocator.pages_for_tokens(len(prompt) + max_new_tokens)
        if need > self.allocator.capacity:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.allocator.capacity}: even a lone request could "
                "never finish (preemption cannot help)")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      arrival=self.now if arrival is None else arrival,
                      tenant=tenant, priority=priority,
                      session=session, turn=turn,
                      slo_ttft=slo_ttft, slo_tpot=slo_tpot)
        self.requests[rid] = req
        self.scheduler.add(req)
        return rid

    def _finish_if_done(self, req: Request) -> None:
        if req.done:
            if req.session >= 0 and req.session in self._sessions:
                # before finish() frees the block table: the session
                # layer may adopt it as an idle hold for the next turn
                self._session_turn_done(req)
            self.scheduler.finish(req, self.now)
            self.stats.finished += 1
            if self._predictor is not None:
                self._predictor.forget(req.rid)
            if self._ep_predictor is not None:
                self._ep_predictor.forget(req.rid)

    # -- multi-turn sessions -------------------------------------------------
    #
    # A conversation's turn N+1 re-enters the front door carrying the
    # full history (turn N's prompt + generated tokens + fresh user
    # tokens) as its prompt.  Correctness never depends on what happened
    # to the old KV — prefix-cache attach, host-restore and recompute
    # all produce bitwise-identical logits — so the session layer is a
    # pure performance tier: with ``session_hold`` the finished turn's
    # pages stay pinned under a holder rid (registered in the prefix
    # index so the next turn attaches them), with ``idle_swap`` the hold
    # parks in the host spill tier between turns, and under page
    # pressure the scheduler's idle-eviction hook releases holds before
    # any live request is preempted.

    def _session_turn_done(self, req: Request) -> None:
        sess = self._sessions[req.session]
        sess["history"] = req.seq
        sess["hist_computed"] = req.computed
        if not sess["turns"]:
            del self._sessions[req.session]
            return
        if self.session_hold \
                and self.allocator.adopt_table(self._next_rid, req.rid):
            holder = self._next_rid
            self._next_rid += 1
            sess["holder"] = holder
            self._hold_order.append(req.session)
            self.stats.session_holds += 1
            swapped = False
            if self.idle_swap:
                # park the idle KV in the host tier right away; the
                # snapshot reads drain at the next iteration boundary,
                # before any pool write (same contract as preemption
                # swap-out)
                swapped = self.allocator.spill_request(holder)
                if swapped:
                    self.stats.idle_swap_outs += 1
            if not swapped:
                # publish the full sequence — prompt *and* generated
                # tokens — so the next turn's admission attaches it
                self.allocator.register_prefix(holder, sess["history"],
                                               req.computed)
        turn = sess["turns"].popleft()
        sess["next"] = (self.now + turn.think_time, turn)
        self._deferred.append(req.session)

    def _evict_idle_hold(self) -> bool:
        """The scheduler's idle-eviction hook: release one idle-session
        KV hold (oldest first) and return True, or False when no hold
        is pinning HBM pages.  Swap-out to the host tier is preferred —
        the session keeps its restore path; freeing is the fallback
        (registered pages park in the cached LRU, still attachable
        until evicted)."""
        for sid in self._hold_order:
            holder = self._sessions[sid].get("holder")
            if holder is None or self.allocator.is_spilled(holder):
                continue        # spilled holds pin no HBM pages
            if self.spill_pool is not None \
                    and self.allocator.spill_request(holder):
                self.stats.idle_swap_outs += 1
            else:
                self.allocator.free_request(holder)
                self._sessions[sid]["holder"] = None
                self._hold_order.remove(sid)
            self.stats.idle_evictions += 1
            return True
        return False

    def _submit_due_turns(self) -> None:
        due = [sid for sid in self._deferred
               if self._sessions[sid]["next"][0] <= self.now]
        # deterministic re-entry order: by due tick, then session id
        for sid in sorted(due, key=lambda s: (self._sessions[s]["next"][0],
                                              s)):
            self._deferred.remove(sid)
            self._start_next_turn(sid)

    def _start_next_turn(self, sid: int) -> None:
        sess = self._sessions[sid]
        tick, turn = sess.pop("next")
        hist = sess["history"]
        holder = sess.get("holder")
        if holder is not None:
            # an idle swap-out queued at the previous turn's finish may
            # still be awaiting its device->host snapshot read: drain it
            # before the restore below can reuse its source pages (and
            # before the restore reads the host slot it fills)
            self._apply_spill_outs()
            if self.allocator.is_spilled(holder) \
                    and self.allocator.resume_spilled(holder, 0):
                # restored byte-exact onto fresh page ids; republish so
                # this turn's admission attaches them (the copies
                # themselves drain before any compute reads them)
                self.stats.idle_swap_ins += 1
                self.allocator.register_prefix(holder, hist,
                                               sess["hist_computed"])
            # release the hold: restored/held pages drop to the cached
            # LRU (refcount 0, content registered) where admission
            # attaches them — or pressure evicts them, costing
            # recompute only.  An unrestorable snapshot (pool full) is
            # discarded; the turn re-prefills, still bitwise-identical.
            self.allocator.free_request(holder)
            # perform the queued restores *now*: once the holder's refs
            # drop, the next schedule() may hand the restored pages to
            # anyone — no restore may still be in flight when it does
            self._apply_swap_ins()
            sess["holder"] = None
            if sid in self._hold_order:
                self._hold_order.remove(sid)
        prompt = np.concatenate([hist, turn.user_tokens]) \
            if len(turn.user_tokens) else hist
        sess["turn"] = sess.get("turn", 1) + 1
        self.stats.turns_submitted += 1
        self.submit(prompt, turn.max_new_tokens, arrival=tick,
                    tenant=sess["tenant"], priority=sess["priority"],
                    session=sid, turn=sess["turn"],
                    slo_ttft=sess["slo_ttft"], slo_tpot=sess["slo_tpot"])

    def _apply_cow_copies(self) -> None:
        """Replay the allocator's pending copy-on-write page copies onto
        the physical pools (K, V, and page-summary planes), before any
        prefill/decode in this iteration reads the destination pages."""
        copies = self.allocator.drain_copies()
        if not copies:
            return
        src = np.asarray([s for s, _ in copies], dtype=np.int32)
        dst = np.asarray([d for _, d in copies], dtype=np.int32)
        if self._tier is not None:
            # COW destinations are about to carry fresh bytes: no staged
            # copy of their previous life may survive
            self._tier.invalidate(int(d) for d in dst)
        self.k_pool = self.k_pool.at[:, dst].set(self.k_pool[:, src])
        self.v_pool = self.v_pool.at[:, dst].set(self.v_pool[:, src])
        self.s_pool = self.s_pool.at[:, dst].set(self.s_pool[:, src])
        self._repin_pools()
        self.stats.cow_page_copies += len(copies)

    def _repin_pools(self) -> None:
        """Eager (non-donated) pool updates leave output sharding to
        propagation: re-pin so the next donated jit call sees the exact
        pool layout it expects (no-op when propagation already matched,
        and under tp=1)."""
        if self._pool_shardings is None:
            return
        self.k_pool = jax.device_put(self.k_pool, self._pool_shardings[0])
        self.v_pool = jax.device_put(self.v_pool, self._pool_shardings[1])
        self.s_pool = jax.device_put(self.s_pool, self._pool_shardings[2])

    # -- the host spill tier -------------------------------------------------

    def _apply_spill_outs(self) -> None:
        """Perform pending device->host page snapshots (swap-outs).

        Must run before *any* pool write of this iteration: a spilled
        source id is released the moment the scheduler swaps it out, so
        the same schedule can hand it to a COW copy, a swap-in, or a
        prefill as a destination — the snapshot read has to win."""
        if self.spill_pool is None:
            return
        moves = self.allocator.drain_spill_outs()
        if not moves:
            return
        pages = np.asarray([p for p, _ in moves], dtype=np.int32)
        slots = [s for _, s in moves]
        # pool-major [L, n, ...] -> slot-major [n, L, ...]
        k = np.asarray(self.k_pool[:, pages]).swapaxes(0, 1)
        v = np.asarray(self.v_pool[:, pages]).swapaxes(0, 1)
        s = np.asarray(self.s_pool[:, pages]).swapaxes(0, 1)
        self.spill_pool.store(slots, k, v, s)
        self.stats.swap_out_pages += len(moves)
        if self.recorder is not None:
            self.recorder.record(pages, step=self.now,
                                 tier=capture.TIER_HOST)

    def _apply_swap_ins(self) -> None:
        """Perform pending host->device restores (swap-ins) and carry
        the physical-id renames into the runahead predictor.

        Runs after spill-out reads and COW copies (both *read* pages a
        restore may be about to overwrite) and before any prefill or
        decode touches the restored pages."""
        if self.spill_pool is None:
            return
        moves = self.allocator.drain_swap_ins()
        if moves:
            slots = [s for s, _ in moves]
            pages = np.asarray([p for _, p in moves], dtype=np.int32)
            if self._tier is not None:
                # restored bytes land on re-taken ids: no staged copy
                # of a destination page's previous life may survive
                self._tier.invalidate(int(p) for p in pages)
            k, v, s = self.spill_pool.load(slots)
            self._spill_err = max(self._spill_err,
                                  self.spill_pool.error_bound(slots))
            self.k_pool = self.k_pool.at[:, pages].set(k.swapaxes(0, 1))
            self.v_pool = self.v_pool.at[:, pages].set(v.swapaxes(0, 1))
            self.s_pool = self.s_pool.at[:, pages].set(s.swapaxes(0, 1))
            self._repin_pools()
            self.stats.swap_in_pages += len(moves)
            if self.recorder is not None:
                self.recorder.record(pages, step=self.now,
                                     tier=capture.TIER_HOST)
        for rid, page_map in self.allocator.drain_remaps():
            if self._predictor is not None:
                self._predictor.remap(rid, page_map)

    def _dispatch_prefill(self, job: PrefillJob):
        """Dispatch one prefill chunk and return its (device-resident)
        logits without materialising them.

        Everything that must happen at *dispatch* time lives here: the
        staged-copy invalidation (the chunk rewrites KV on its pages),
        the jit call itself, the ``computed`` frontier advance, and the
        prefix registration — all host bookkeeping downstream scheduling
        depends on, none of it reading a sampled value.  The pipelined
        executor calls this for every chunk before blocking on any
        stream; :meth:`_commit_prefill` does the sampling."""
        req = job.req
        toks = np.zeros((self.chunk,), dtype=np.int32)
        toks[: job.n_tokens] = req.prompt[job.start:job.start + job.n_tokens]
        bt = self.allocator.table_array(req.rid, self.n_logical)
        if self._tier is not None:
            # the chunk rewrites KV (and summaries) on these pages:
            # staged copies of them are stale the moment the call runs
            tbl = self.allocator.table(req.rid)
            p0 = job.start // self.page
            p1 = (job.start + job.n_tokens - 1) // self.page
            self._tier.invalidate(tbl[p0:p1 + 1])
        logits, self.k_pool, self.v_pool, self.s_pool = self._prefill(
            self.params, self.k_pool, self.v_pool, self.s_pool,
            jnp.asarray(toks), np.int32(job.start), np.int32(job.n_tokens),
            jnp.asarray(bt))
        req.computed += job.n_tokens
        # whole prompt pages materialised by this chunk become
        # attachable by later requests with the same prefix
        self.allocator.register_prefix(req.rid, req.prompt,
                                       min(req.computed, req.prompt_len))
        self.stats.prefill_tokens += job.n_tokens
        self.stats.prefill_calls += 1
        return logits

    def _commit_prefill(self, job: PrefillJob, logits) -> None:
        """The prefill stream's sample/commit boundary: materialise the
        final chunk's logits and sample the first token."""
        req = job.req
        if req.computed == req.prompt_len:
            lg = np.asarray(logits)
            # first pass samples the first token here; a preemption
            # resume already holds it and moves on to decode replay
            if not req.out_tokens:
                req.out_tokens.append(int(lg.argmax()))
                req.first_token_at = self.now
                req.last_token_at = self.now
                req.token_ticks.append(self.now)
                req.first_logits = req.last_logits = lg
                self.stats.tokens_out += 1
                if req.resumed_at >= 0:
                    # preempted before its first token: the resume gap
                    # ends at this prefill-produced token
                    req.resume_gaps.append(self.now - req.resumed_at)
                    req.resumed_at = -1.0
                self._finish_if_done(req)

    def _run_prefill(self, job: PrefillJob) -> None:
        self._commit_prefill(job, self._dispatch_prefill(job))

    def _dispatch_decode(self, pairs: list, rb: int):
        """Dispatch one decode batch over ``(row_slot, request)`` pairs
        and return its device-resident ``(logits, sel)``.

        The slot indirection is what lets the pipelined executor keep
        each request's decode row stable across iterations (maxtext-
        style per-slot insertion): a slot with no request behind it is a
        hole, and holes carry exactly the NULL-row padding the bucketed
        sync path pads with (token 0, pos 0, zeroed block table — every
        write lands on the reserved scratch page), so row placement
        never changes any occupied row's logits.  The synchronous loop
        passes the dense ``enumerate(rows)`` pairing."""
        token = np.zeros((rb,), dtype=np.int32)
        pos = np.zeros((rb,), dtype=np.int32)
        bts = np.zeros((rb, self.n_logical), dtype=np.int32)
        for slot, req in pairs:
            token[slot] = req.seq[req.computed]
            pos[slot] = req.computed
            bts[slot] = self.allocator.table_array(req.rid, self.n_logical)
        hot_args = ()
        if self._tier is not None:
            # frontier pages are written inside this call, but the
            # decode body write-throughs the new bytes into any staged
            # copy (see _paged_decode_fn), so their entries stay live —
            # snapshot the hot-map the gather will resolve through
            hot_args = (jnp.asarray(self._tier.hot_map().copy()),)
        if self.ep is not None:
            if self.expert_pool_mode == "dense":
                hot_args += (self._ep_rows,)
            else:
                hot_args += (self._ep_bt, self.ep.pool)
                if self._ep_tier is not None:
                    # expert tiles are read-only: staged copies never go
                    # stale, so the snapshot is only for dispatch-time
                    # consistency with the staging gather
                    hot_args += (self.ep.hot_map_device(),)
        out = self._decode(
            self.params, self.k_pool, self.v_pool, self.s_pool,
            jnp.asarray(token), jnp.asarray(pos), jnp.asarray(bts),
            *hot_args)
        if self.ep is not None:
            logits, self.k_pool, self.v_pool, self.s_pool, sel, esel = out
        else:
            logits, self.k_pool, self.v_pool, self.s_pool, sel = out
            esel = None
        return logits, sel, esel

    def _commit_decode(self, pairs: list, logits, sel, rb: int,
                       esel=None) -> None:
        """The decode stream's sample/commit boundary.

        Commits run in *plan order* (the order ``pairs`` carries), not
        slot order: request finishes free pages through the allocator's
        LIFO free list, so commit order is observable in later physical
        page assignment — plan order is what the synchronous loop uses,
        and following it keeps the async executor's allocator state
        bitwise-identical, not just its tokens."""
        lg = np.asarray(logits)
        sel0 = np.asarray(sel[0])                    # layer-0 [R,KV,K]
        kv_l = self.cfg.n_kv_heads // self.tp        # KV heads per shard
        for slot, req in pairs:
            frontier = req.computed == req.total_len - 1
            req.computed += 1
            self.stats.decode_tokens += 1
            if self.recorder is not None:
                # a request with fewer valid pages than the TopK budget
                # pads its selection with NULL (masked in attention, no
                # data fetched) — drop those from the traffic record.
                # Under TP the event is tagged with the shard whose KV
                # heads produced it (heads shard in contiguous slices).
                for h, head_sel in enumerate(sel0[slot]):
                    self.recorder.record(
                        head_sel[head_sel != NULL_PAGE],
                        rid=req.rid, step=self.now,
                        shard=h // kv_l if self.tp > 1 else -1,
                        tier=capture.TIER_HBM)
            if frontier:
                req.out_tokens.append(int(lg[slot].argmax()))
                req.last_logits = lg[slot].copy()
                req.last_token_at = self.now
                req.token_ticks.append(self.now)
                self.stats.tokens_out += 1
                if req.resumed_at >= 0:
                    # resume-TTFT sample: re-admission (swap or
                    # recompute) to the next *new* token
                    req.resume_gaps.append(self.now - req.resumed_at)
                    req.resumed_at = -1.0
                self._finish_if_done(req)
        self.stats.decode_rows_padded += rb - len(pairs)
        # NSB accounting over the iteration's unique physical pages —
        # indexed by occupied slots, so hole rows (all-NULL selections)
        # never enter; np.unique sorts, making the touch order a
        # function of the page *set* alone, identical however the
        # executor placed rows
        occ = np.asarray([slot for slot, _ in pairs], dtype=np.int64)
        uniq = np.unique(sel0[occ])
        uniq = uniq[uniq != NULL_PAGE]
        self._seen_pages.update(int(p) for p in uniq)
        self.stats.pages_unique = len(self._seen_pages)
        for p in uniq:
            self.stats.pages_touched += 1
            # the demand-LRU model is always scored: with runahead on it
            # is the in-run no-runahead comparator (nsb_demand_lru_hit_rate)
            lru_hit = self.hot.touch(int(p))
            hit = (self._tier.touch(int(p)) if self._tier is not None
                   else lru_hit)
            if hit:
                self.stats.nsb_hits += 1
            else:
                self.stats.nsb_misses += 1
        if self.hot_shards is not None:
            # per-shard NSBs see only their own KV heads' selections
            for s in range(self.tp):
                su = np.unique(sel0[occ][:, s * kv_l:(s + 1) * kv_l])
                for p in su[su != NULL_PAGE]:
                    self.hot_shards.touch(int(p), s)
                    if self.tier_shards is not None:
                        self.tier_shards.touch(int(p), s, install=False)
        if self._predictor is not None:
            # per-request history for the next prediction round (layer-0
            # selections — the repo's traffic-proxy convention)
            for slot, req in pairs:
                rp = np.unique(sel0[slot])
                self._predictor.observe(req.rid, rp[rp != NULL_PAGE])
        if esel is not None:
            self._account_expert_pages(pairs, np.asarray(esel), occ)

    def _account_expert_pages(self, pairs: list, es: np.ndarray,
                              occ: np.ndarray) -> None:
        """Expert-tile demand accounting for one committed decode step.

        ``es`` is the step's routed expert ids ``[L, R, top_k]``.  Every
        routed (request, layer, expert) demands the expert's full tile
        range (3 planes x NT pages); traffic is recorded per request
        (tier-tagged HBM demand), fed to the per-request history
        predictor across *all* layers, and scored — by unique page over
        the whole step, np.unique-sorted so the touch order is a
        function of the page set alone — against both the staging tier
        (when live) and the always-on demand-LRU comparator."""
        ep = self.ep
        layers = range(ep.n_layers)
        for slot, req in pairs:
            pages = np.concatenate(
                [ep.pages_for_experts(li, es[li, slot]) for li in layers])
            if self.ep_recorder is not None:
                self.ep_recorder.record(pages, rid=req.rid,
                                        step=self.now,
                                        tier=capture.TIER_HBM)
            if self._ep_predictor is not None:
                self._ep_predictor.observe(req.rid, np.unique(pages))
        uniq = np.unique(np.concatenate(
            [ep.pages_for_experts(li, es[li, occ]) for li in layers]))
        for p in uniq:
            self.stats.expert_pages_touched += 1
            lru_hit = self.ep_hot.touch(int(p))
            hit = (self._ep_tier.touch(int(p))
                   if self._ep_tier is not None else lru_hit)
            if hit:
                self.stats.expert_nsb_hits += 1
            else:
                self.stats.expert_nsb_misses += 1

    def _run_decode(self, rows: list, bucket: int = 0) -> None:
        # ragged batches pad to the scheduler's power-of-two row bucket
        # (NULL block tables, scratch-page scribbles) instead of always
        # to max_batch: O(log R_max) distinct decode traces, and the
        # padded compute shrinks with the actual batch
        rb = bucket or self.max_batch
        pairs = list(enumerate(rows))
        logits, sel, esel = self._dispatch_decode(pairs, rb)
        self._commit_decode(pairs, logits, sel, rb, esel)

    # -- iteration loop ------------------------------------------------------

    def step(self) -> int:
        """One scheduler iteration; returns scheduled token count.

        Dispatches to the pipelined executor when constructed with
        ``executor="async"`` (see :mod:`.executor`); the synchronous
        loop below is the bitwise parity oracle both paths answer to.
        """
        if self._pipeline is not None:
            return self._pipeline.step()
        return self._step_sync()

    def _step_sync(self) -> int:
        """The synchronous step loop: schedule, drain transfers, run
        prefill then decode to completion, then the runahead stage —
        every phase strictly ordered on the host.

        With runahead on, the iteration ends with the speculative
        stage: predict each live request's next-iteration TopK pages
        (history for stable selections, the layer-0 proxy slice for the
        rest), stage them into the NSB tail with one async-dispatched
        gather, and let the *next* decode resolve through the updated
        hot-map — the paper's decoupled runahead sub-thread, riding the
        host-side gap while the device drains this iteration's work.
        """
        self.now += 1
        self.stats.iterations += 1
        plan = self.scheduler.schedule(self.now)
        # strict transfer order: snapshot reads (swap-outs) before any
        # pool write, COW copies next, restores (swap-ins) last, all
        # before compute — see the individual method docstrings
        self._apply_spill_outs()
        if self._tier is not None:
            # pages whose last reference dropped since the previous
            # iteration (preemption, finish, COW release) may be
            # re-taken and rewritten at any point: staged copies of
            # their old content must never resolve again
            self._tier.invalidate(self.allocator.drain_released())
        self._apply_cow_copies()
        self._apply_swap_ins()
        for job in plan.prefill:
            self._run_prefill(job)
        if plan.decode:
            self._run_decode(plan.decode, plan.decode_bucket)
            self.stats.steps += 1
        if ((self._tier is not None or self._ep_tier is not None)
                and plan.runahead_budget > 0):
            self._run_runahead(plan)
        self._account_streams(plan)
        self.stats.preemptions = self.scheduler.n_preemptions
        return plan.n_tokens

    def _account_streams(self, plan) -> None:
        """Per-stream iteration accounting, shared by both executors so
        their iteration logs compare like with like."""
        n_p, n_d = len(plan.prefill), len(plan.decode)
        if n_p:
            self.stats.prefill_iterations += 1
        if n_d:
            self.stats.decode_iterations += 1
        if n_p and n_d:
            self.stats.overlap_iterations += 1
        self.stats.iter_log.append((n_p, n_d))

    def _run_runahead(self, plan, fetched=_FETCH_UNSET) -> None:
        """The between-steps runahead stage, per staging tier: the KV
        tier's predict/filter/stage (plus fetch-back) when KV runahead
        is on, then the expert-weight tier's router-keyed stage when
        expert runahead is on — both riding the same decode-stream
        budget window."""
        if self._tier is not None:
            self._run_kv_runahead(plan, fetched)
        if self._ep_tier is not None:
            self._run_expert_runahead(plan)

    def _run_kv_runahead(self, plan, fetched=_FETCH_UNSET) -> None:
        """The between-steps KV runahead stage: predict, filter, stage.

        Candidates are every request decoding next iteration — the
        rows just decoded plus requests that completed prefill this
        iteration (whose first decode selection is exactly what a
        demand-installed NSB always cold-misses).  The DARE-style
        filter routes stable selections to their history predictor and
        only the rest through the proxy scorer; staged pages land in
        the pool tail via one fixed-shape donated gather.  Everything
        here is speculative: it steers where bytes are *read from*
        next iteration, never what is computed.

        ``fetched``: the pipelined executor performs :meth:`_fetch_back`
        in its overlap window (while the device drains the dispatched
        streams) and passes the result here; the synchronous loop leaves
        it unset and fetch-back runs inline.
        """
        tier, pred = self._tier, self._predictor
        pages: list = []
        # fetch-back: a spilled queue head swap-resumes inside this
        # window (host -> HBM), and its remapped history pages go to
        # the *front* of the staging list (HBM -> NSB) — so the first
        # post-resume demand gather never touches a host page
        if fetched is _FETCH_UNSET:
            fetched = self._fetch_back()
        if fetched is not None and not fetched.done:
            hist = list(pred.history(fetched.rid))
            pages.extend(hist)
            if self.recorder is not None and hist:
                self.recorder.record(np.asarray(hist, dtype=np.int64),
                                     rid=fetched.rid, step=self.now,
                                     tier=capture.TIER_NSB)
        cands = [r for r in plan.decode if not r.done]
        seen = {r.rid for r in cands}
        for job in plan.prefill:
            req = job.req
            if (not req.done and req.rid not in seen
                    and req.computed >= req.prompt_len
                    and req.rid in self.allocator._tables):
                cands.append(req)
                seen.add(req.rid)
        if not cands and not pages:
            return
        if cands:
            covered, proxy = pred.split([r.rid for r in cands])
            tier.stats.filtered_rows += len(covered)
            for rid in covered:
                pages.extend(pred.history(rid))
            if proxy and self._proxy is not None:
                pages.extend(self._predict_proxy(
                    [self.requests[rid] for rid in proxy]))
        copies = tier.stage(pages, max_copies=plan.runahead_budget)
        if not copies:
            return
        # fixed-shape staging gather: pad with (0, 0) — a self-copy of
        # the reserved scratch page, value-identical — so the jit
        # compiles once for any copy count
        src = np.zeros((max(1, self.runahead_pages),), dtype=np.int32)
        dst = np.zeros((max(1, self.runahead_pages),), dtype=np.int32)
        for j, (s, slot) in enumerate(copies):
            src[j] = s
            dst[j] = self.n_pages + slot
        self.k_pool, self.v_pool = self._stage(
            self.k_pool, self.v_pool, jnp.asarray(src), jnp.asarray(dst))
        tier.stats.stage_calls += 1

    def _fetch_back(self):
        """Runahead-window early swap-resume of the spilled queue head.

        ``_admit`` would resume it at the *next* ``schedule()`` anyway;
        doing it here moves the host->device restore into the same
        between-steps window the staging gather rides (the decoupled
        sub-thread's budget), one iteration ahead of demand.  The resume
        follows ``_admit``'s exact state transitions — all-or-nothing
        restore, FIFO head only, ``max_running`` respected — so the
        schedule a fetch-back produces is one the admission path could
        also have produced.  Returns the resumed request, or None.
        """
        sched = self.scheduler
        if (self.spill_pool is None or not sched.waiting
                or len(sched.running) >= sched.max_running):
            return None
        # the candidate is whoever the *policy* would admit first —
        # under FIFO that is exactly the queue head, so the historic
        # behaviour is unchanged; under fairness/priority policies the
        # fetch-back restores the same request _admit would pick next
        head = sched.policy.admit_order(list(sched.waiting), self.now)[0]
        if not head.spilled or not self.allocator.resume_spilled(
                head.rid, max(head.prompt_len, head.computed)):
            return None
        # pending idle-session snapshot reads must land before this
        # restore writes pool pages (no-op without the session layer)
        self._apply_spill_outs()
        sched.waiting.remove(head)
        head.spilled = False
        head.state = RequestState.RUNNING
        if head.n_preemptions > 0:
            head.resumed_at = self.now
        sched.running.append(head)
        sched.n_swap_ins += 1
        sched.policy.on_admit(head, self.now)
        self.stats.fetch_backs += 1
        # the restore itself rides this window too, not the next step's
        self._apply_swap_ins()
        return head

    def _predict_proxy(self, reqs: list) -> list:
        """Run the layer-0 proxy scorer over ``reqs`` and return their
        predicted next-step physical pages (padded rows and NULL-page
        selections filtered out)."""
        tier = self._tier
        tier.stats.proxy_rows += len(reqs)
        out: list = []
        mb = self.max_batch
        for i0 in range(0, len(reqs), mb):
            grp = reqs[i0:i0 + mb]
            rb = (scheduler_mod.bucket_for(len(grp), self.row_buckets)
                  if self.row_buckets else mb)
            token = np.zeros((rb,), dtype=np.int32)
            pos = np.zeros((rb,), dtype=np.int32)
            bts = np.zeros((rb, self.n_logical), dtype=np.int32)
            nv = np.ones((rb,), dtype=np.int32)
            for i, req in enumerate(grp):
                token[i] = req.seq[req.computed]
                pos[i] = req.computed
                bts[i] = self.allocator.table_array(req.rid,
                                                    self.n_logical)
                nv[i] = pos[i] // self.page + 1
            phys = np.asarray(self._proxy(
                self.params, self.s_pool, jnp.asarray(token),
                jnp.asarray(pos), jnp.asarray(bts), jnp.asarray(nv)))
            for i in range(len(grp)):
                u = np.unique(phys[i])
                out.extend(int(p) for p in u if p != NULL_PAGE)
        return out

    def _run_expert_runahead(self, plan) -> None:
        """The expert-weight runahead stage: stage the tile pages the
        next decode step's routing will demand.

        Candidates are the requests decoding next iteration (rows just
        decoded plus prefill completions entering decode).  The
        DARE-style filter routes requests whose routed-expert selection
        has stabilised to their history predictor — covering *all*
        layers' tiles — and only the rest through the router scorer,
        which predicts layer-0 routing from each row's known next
        token (:func:`runahead.make_router_scorer`).  Staged tiles are
        byte-exact copies of read-only weights: no invalidation path
        exists or is needed, and a misprediction costs staging
        bandwidth, never a logit."""
        tier, pred = self._ep_tier, self._ep_predictor
        cands = [r for r in plan.decode if not r.done]
        seen = {r.rid for r in cands}
        for job in plan.prefill:
            req = job.req
            if (not req.done and req.rid not in seen
                    and req.computed >= req.prompt_len):
                cands.append(req)
                seen.add(req.rid)
        if not cands:
            return
        covered, proxy = pred.split([r.rid for r in cands])
        tier.stats.filtered_rows += len(covered)
        pages: list = []
        for rid in covered:
            pages.extend(pred.history(rid))
        if proxy:
            pages.extend(self._predict_router(
                [self.requests[rid] for rid in proxy]))
        copies = tier.stage(pages, max_copies=self.expert_runahead_pages)
        if not copies:
            return
        # fixed-shape staging gather, (0, 0) scratch-page self-copies
        # as padding — compiles once for any copy count
        src = np.zeros((max(1, self.expert_runahead_pages),),
                       dtype=np.int32)
        dst = np.zeros_like(src)
        for j, (s, slot) in enumerate(copies):
            src[j] = s
            dst[j] = self.ep.n_pages + slot
        self.ep.pool = self._ep_stage(self.ep.pool, jnp.asarray(src),
                                      jnp.asarray(dst))
        tier.stats.stage_calls += 1
        if self.ep_recorder is not None:
            self.ep_recorder.record(
                np.asarray([s for s, _ in copies], dtype=np.int64),
                step=self.now, tier=capture.TIER_NSB)

    def _predict_router(self, reqs: list) -> list:
        """Run the router scorer over ``reqs`` and return the predicted
        layer-0 expert tile pages (the proxy's reach; deeper layers are
        the history predictor's job)."""
        tier = self._ep_tier
        tier.stats.proxy_rows += len(reqs)
        out: list = []
        mb = self.max_batch
        for i0 in range(0, len(reqs), mb):
            grp = reqs[i0:i0 + mb]
            rb = (scheduler_mod.bucket_for(len(grp), self.row_buckets)
                  if self.row_buckets else mb)
            token = np.zeros((rb,), dtype=np.int32)
            for i, req in enumerate(grp):
                token[i] = req.seq[req.computed]
            eids = np.asarray(self._router_proxy(self.params,
                                                 jnp.asarray(token)))
            for i in range(len(grp)):
                out.extend(int(p) for p in
                           self.ep.pages_for_experts(0, eids[i]))
        return out

    def _submit_item(self, item) -> int:
        """Front-door entry for a workload.WorkItem: submit turn 1 and
        register the session when follow-up turns exist (they re-enter
        via :meth:`_submit_due_turns` after the previous turn finishes
        plus think time — a closed loop, like a real user)."""
        sid = -1
        if item.turns:
            sid = self._next_sid
            self._next_sid += 1
            self._sessions[sid] = {
                "turns": deque(item.turns), "holder": None,
                "history": None, "hist_computed": 0, "turn": 1,
                "tenant": item.tenant, "priority": item.priority,
                "slo_ttft": item.slo_ttft, "slo_tpot": item.slo_tpot,
            }
        return self.submit(item.prompt, item.max_new_tokens,
                           arrival=item.arrival, tenant=item.tenant,
                           priority=item.priority, session=sid,
                           slo_ttft=item.slo_ttft,
                           slo_tpot=item.slo_tpot)

    def run(self, workload=None, max_iters: int = 100000) -> dict:
        """Drive ``workload`` to completion; returns the request table.

        Items are either legacy ``(tick, prompt, max_new)`` tuples or
        :class:`~repro.serve.workload.WorkItem` rows (tenant, priority,
        SLOs, multi-turn conversations).  Multi-turn items are
        closed-loop: each follow-up turn is submitted only after the
        previous turn finishes plus its think time, carrying the full
        conversation history as its prompt.
        """
        def _tick(w):
            return w[0] if isinstance(w, tuple) else w.arrival
        pending = deque(sorted(workload or [], key=_tick))
        while (pending or self._deferred or self.scheduler.has_work):
            if max_iters <= 0:
                raise RuntimeError("run() exceeded max_iters")
            max_iters -= 1
            while pending and _tick(pending[0]) <= self.now:
                item = pending.popleft()
                if isinstance(item, tuple):
                    tick, prompt, max_new = item
                    self.submit(prompt, max_new, arrival=tick)
                else:
                    self._submit_item(item)
            self._submit_due_turns()
            self.step()
        return self.requests

    # -- reporting -----------------------------------------------------------

    def captured_trace(self):
        """Recorded multi-tenant page traffic as a simulator Trace."""
        if self.recorder is None:
            raise RuntimeError("construct PagedEngine with "
                               "capture_trace=True to record selections")
        return self.recorder.to_trace()

    @staticmethod
    def _trace_count(jitted) -> int:
        """Compilation count of a jitted function, via the (private)
        jax cache-size hook; -1 if a jax upgrade removes it — metrics
        must degrade, not raise."""
        try:
            return int(jitted._cache_size())
        except AttributeError:
            return -1

    def n_decode_traces(self) -> int:
        """Distinct decode-step compilations so far: one per row bucket
        actually used (bucketing caps this at O(log max_batch); padding
        every batch to max_batch pins it at 1 but wastes the padded
        rows' compute)."""
        return self._trace_count(self._decode)

    def n_prefill_traces(self) -> int:
        """Distinct prefill-chunk compilations (fixed chunk shape: 1)."""
        return self._trace_count(self._prefill)

    def metrics(self) -> dict:
        done = [r for r in self.requests.values()
                if r.finished_at >= 0]
        # the accessors are None-guarded (an unfinished request has no
        # latency, a one-token request no inter-token gap): filter, so
        # percentiles never mix sentinel negatives into the tail
        lat = [x for x in (r.latency() for r in done) if x is not None]
        ttft = [x for x in (r.ttft() for r in done) if x is not None]
        tpot = [x for x in (r.tpot() for r in done) if x is not None]
        out = {
            "n_finished": len(done),
            "iterations": self.stats.iterations,
            "tokens_out": self.stats.tokens_out,
            "p50_latency": percentile(lat, 0.50),
            "p99_latency": percentile(lat, 0.99),
            "p50_ttft": percentile(ttft, 0.50),
            "p99_ttft": percentile(ttft, 0.99),
            "p50_tpot": percentile(tpot, 0.50),
            "p99_tpot": percentile(tpot, 0.99),
            "executor": self.executor,
            "prefill_iterations": self.stats.prefill_iterations,
            "decode_iterations": self.stats.decode_iterations,
            "overlap_iterations": self.stats.overlap_iterations,
            "overlap_fraction": (
                self.stats.overlap_iterations / self.stats.iterations
                if self.stats.iterations else None),
            "nsb_hot_hit_rate": self.stats.hot_hit_rate,
            "offchip_fetch_reduction": self.stats.offchip_reduction,
            "tp": self.tp,
            "preemptions": self.stats.preemptions,
            "pages_peak_in_use": self.allocator.stats.peak_in_use,
            "kv_pool_mib": self.pool_cfg.pool_bytes / 2 ** 20,
            "kv_pool_mib_per_shard":
                self.pool_cfg.pool_bytes / 2 ** 20 / self.tp,
            "prefill_tokens_run": self.stats.prefill_tokens,
            "prefill_tokens_skipped": self.scheduler.prefill_tokens_skipped,
            "prefix_hit_pages": self.allocator.stats.prefix_hits,
            "prefix_evictions": self.allocator.stats.prefix_evictions,
            "cow_copies": self.allocator.stats.cow_copies,
            "n_decode_traces": self.n_decode_traces(),
            "n_prefill_traces": self.n_prefill_traces(),
            "decode_rows_padded": self.stats.decode_rows_padded,
        }
        # double-buffered plan quality (async executor; zeros under sync)
        sch = self.scheduler
        out["plan_commits"] = sch.plan_commits
        out["plan_repairs"] = sch.plan_repairs
        out["plan_reuse_fraction"] = (
            sch.plan_reuse / sch.plan_commits if sch.plan_commits
            else None)
        # resume-TTFT: re-admission to next new token, both policies —
        # the swap-vs-recompute headline spill_bench compares
        gaps = [g for r in self.requests.values() for g in r.resume_gaps]
        out["n_resumes"] = len(gaps)
        out["p50_resume_ttft"] = percentile(gaps, 0.50)
        out["p99_resume_ttft"] = percentile(gaps, 0.99)
        out["spill_pages"] = self.allocator.spill_pages
        if self.spill_pool is not None:
            out["swap_outs"] = self.scheduler.n_swap_outs
            out["swap_ins"] = self.scheduler.n_swap_ins
            out["swap_out_pages"] = self.stats.swap_out_pages
            out["swap_in_pages"] = self.stats.swap_in_pages
            out["spill_fallbacks"] = self.allocator.stats.spill_failures
            out["fetch_backs"] = self.stats.fetch_backs
            out["spill_host_mib"] = self.spill_pool.host_bytes / 2 ** 20
            out["spill_compressed"] = self.spill_pool.compress
            out["spill_dequant_error_bound"] = self._spill_err
        if self.hot_shards is not None:
            roll = self.hot_shards.rollup()
            out["nsb_shard_hit_rates"] = roll["per_shard"]
            out["nsb_shard_rollup_hit_rate"] = roll["hit_rate"]
        out["runahead_mode"] = self.runahead
        if self._tier is not None:
            t = self._tier
            out["nsb_staging_slots"] = self.nsb_slots
            out["runahead_staged_pages"] = t.stats.staged_pages
            out["runahead_stage_calls"] = t.stats.stage_calls
            out["runahead_invalidations"] = t.stats.invalidations
            out["runahead_proxy_rows"] = t.stats.proxy_rows
            out["runahead_filtered_rows"] = t.stats.filtered_rows
            out["runahead_accuracy"] = t.accuracy
            out["runahead_coverage"] = t.coverage
            out["runahead_overfetch"] = t.overfetch
            # the same demand traffic scored against a demand-install
            # LRU NSB of the same class: the in-run baseline the
            # runahead hit rate (nsb_hot_hit_rate above) is lifted over
            out["nsb_demand_lru_hit_rate"] = self.hot.hit_rate
            if self.tier_shards is not None:
                out["runahead_shard_hit_rates"] = \
                    self.tier_shards.hit_rates()
        out["expert_pool"] = self.expert_pool_mode
        if self.ep is not None:
            out["expert_pool_pages"] = self.ep.n_pages
            out["expert_pool_mib"] = self.ep.pool_bytes / 2 ** 20
            out["expert_tile_rows"] = self.ep.tile_rows
            out["expert_pages_touched"] = self.stats.expert_pages_touched
            out["expert_nsb_hit_rate"] = self.stats.expert_hot_hit_rate
            # the same demand traffic scored against a demand-install
            # LRU of the tier's capacity — the in-run baseline the
            # router-keyed hit rate is lifted over
            out["expert_demand_lru_hit_rate"] = self.ep_hot.hit_rate
            out["expert_runahead_mode"] = self.expert_runahead
        if self._ep_tier is not None:
            t = self._ep_tier
            out["expert_nsb_slots"] = self.ep.nsb_slots
            out["expert_staged_pages"] = t.stats.staged_pages
            out["expert_stage_calls"] = t.stats.stage_calls
            out["expert_proxy_rows"] = t.stats.proxy_rows
            out["expert_filtered_rows"] = t.stats.filtered_rows
            out["expert_runahead_accuracy"] = t.accuracy
            out["expert_runahead_coverage"] = t.coverage
            out["expert_runahead_overfetch"] = t.overfetch
        # front-door rollups: SLO attainment over requests that carry
        # deadlines (None when nothing does), plus per-tenant/per-class
        # slices of the same finished-request percentiles
        out["policy"] = self.scheduler.policy.name
        slos = [x for x in (r.slo_attained()
                            for r in self.requests.values())
                if x is not None]
        out["slo_attainment"] = (sum(slos) / len(slos)) if slos else None

        def _rollup(group) -> dict:
            per = {}
            for key, rs in sorted(group.items()):
                g_done = [r for r in rs if r.finished_at >= 0]
                g_ttft = [x for x in (r.ttft() for r in g_done)
                          if x is not None]
                g_slo = [x for x in (r.slo_attained() for r in rs)
                         if x is not None]
                per[key] = {
                    "n_finished": len(g_done),
                    "p50_ttft": percentile(g_ttft, 0.50),
                    "p99_ttft": percentile(g_ttft, 0.99),
                    "slo_attainment": (sum(g_slo) / len(g_slo)
                                       if g_slo else None),
                }
            return per

        by_tenant: dict[str, list] = {}
        by_class: dict[int, list] = {}
        for r in self.requests.values():
            by_tenant.setdefault(r.tenant, []).append(r)
            by_class.setdefault(r.priority, []).append(r)
        if len(by_tenant) > 1 or "default" not in by_tenant:
            out["per_tenant"] = _rollup(by_tenant)
        if len(by_class) > 1 or 0 not in by_class:
            out["per_class"] = _rollup(by_class)
        if self.session_hold or self.stats.turns_submitted:
            out["session_holds"] = self.stats.session_holds
            out["turns_submitted"] = self.stats.turns_submitted
            out["idle_swap_outs"] = self.stats.idle_swap_outs
            out["idle_swap_ins"] = self.stats.idle_swap_ins
            out["idle_evictions"] = self.stats.idle_evictions
            out["pages_session_held"] = \
                self.allocator.pages_session_held
        return out
