"""Online vector runahead for the paged serve engine.

The paper's core mechanism is a decoupled, speculative, lightweight
sub-thread that runs *ahead* of the compute stream and stages sparse
gather targets into a small Near-Storage Buffer (NSB) before the demand
access arrives.  This module is that mechanism mapped onto the serving
layer, closing ROADMAP priority #1: NVR stops being an offline replay
tool (``capture.py`` -> simulator) and becomes a live stage in
``PagedEngine.step()``.

Three pieces, mirroring the paper's decomposition:

:class:`NSBHotTier` — the physical staging buffer.  The engine extends
its K/V pools with ``n_slots`` extra *tail* pages (``[L, n_demand +
n_slots, KV, page, D]``); this class owns the mapping from demand
physical page id -> staged tail slot (the *hot-map*), FIFO slot
recycling, and explicit invalidation.  Staged pages are byte copies made
by a jitted gather; the demand region and the block tables stay
authoritative, so a stale entry is *dropped*, never patched — the
soundness contract is "the hot-map never resolves a page whose demand
copy has been written or freed since staging" (see ARCHITECTURE.md and
the hypothesis property test).  Accounting runs through a mirrored
:class:`~repro.core.nvr.capture.PageCache` twin so serve metrics and the
simulator share one accuracy/coverage definition.

:class:`RunaheadPredictor` — the DARE-style filter (PAPERS.md): per
request, a *history* predictor (last TopK selection; trivially right
while the selection is stable) plus a stability counter.  Only requests
the trivial predictor cannot cover — new rows entering decode, rows
whose selection churns — are handed to the expensive proxy scorer, so
runahead effort concentrates where speculation pays.

:func:`make_proxy_scorer` — the vector-runahead address-generation
slice.  Between decode steps the engine already knows each row's *next*
input token and position (teacher-forced replay rows trivially; frontier
rows from the argmax just computed), so the slice embeds that token,
applies layer 0's pre-attention norm + query projection + RoPE at the
next position, and scores the ``s_pool`` page summaries through the
block table — the same ``select_pages_blocktable`` the demand path runs,
one iteration early, at a tiny fraction of a forward pass.  Mispredicted
pages cost staging bandwidth only (fuzzy-fetch philosophy: over-fetch is
reported, never corrected-for).

IMP's one-batch-ahead limitation (``core/nvr/prefetchers.py``) is kept
as the in-repo baseline: ``mode="imp"`` stages exactly the pages the
*current* step selected — always one step behind the selection drift —
with no proxy slice and no stability filter.

With the host spill tier configured (``PagedEngine(spill_pages=...)``)
the same between-steps window also performs **fetch-back**: when the
waiting-queue head is a swapped-out request, the engine swap-resumes it
inside ``_run_runahead`` — host slots restore to fresh HBM pages, the
predictor's history renames through :meth:`RunaheadPredictor.remap`,
and the remapped history pages are staged into the NSB tail ahead of
the demand pile-up, so a resumed request's first post-resume gather
never touches a host page (host -> HBM -> NSB in one budget window).

Invariants this module holds (checked by the hypothesis suite):

* **Slot bijection** — every staged slot is owned by exactly one demand
  page and ``hot_map[page] == slot`` iff ``page`` owns ``slot``; free,
  staged, and (nothing else) partition the slot space.
* **Staleness-free resolution** — the hot-map never resolves a page
  whose demand copy was rewritten or freed after staging: writers
  invalidate first (or write through, for the decode frontier).
* **Speculation never steers computation** — predictor output and
  staged bytes only change where reads are served from; block tables
  and the demand pool stay authoritative, so tokens are bitwise
  invariant to runahead mode.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from ..core.nvr.capture import PageCache

MODES = ("off", "imp", "nvr")

# expert-weight runahead modes (PagedEngine(expert_runahead=...)):
# "router" predicts the next decode batch's routed experts with the
# router itself as the address-generation slice (see make_router_scorer)
EXPERT_MODES = ("off", "router")


@dataclass
class RunaheadStats:
    staged_pages: int = 0           # staging copies issued (bandwidth)
    stage_calls: int = 0            # jitted gather dispatches
    invalidations: int = 0          # staged entries dropped by writes/frees
    proxy_rows: int = 0             # rows sent through the proxy scorer
    filtered_rows: int = 0          # rows the stability filter covered
    budget_truncated: int = 0       # candidate pages dropped by the budget


class NSBHotTier:
    """Hot-map + slot allocator over the pool's staged tail region.

    ``n_demand`` is the size of the demand page region (the allocator's
    id space); slots ``0..n_slots-1`` name the tail pages ``n_demand +
    slot`` of the physical pools.  ``stage()`` assigns slots (FIFO
    recycling, matching the machine-model NSB's insertion-order
    eviction) and returns the ``(src_page, slot)`` copies the engine's
    jitted gather must perform; ``invalidate()`` drops entries whose
    demand copy is about to be (or was) rewritten or freed.  The
    ``hot_map`` array — demand page id -> slot, -1 when unstaged — is
    what the decode step resolves TopK ids through.
    """

    def __init__(self, n_demand: int, n_slots: int) -> None:
        if n_slots < 1:
            raise ValueError(f"need >= 1 staging slot, got {n_slots}")
        self.n_demand = n_demand
        self.n_slots = n_slots
        self._slot_of: OrderedDict[int, int] = OrderedDict()  # staged order
        self._page_of = np.full((n_slots,), -1, dtype=np.int32)
        self._free = list(range(n_slots - 1, -1, -1))
        self._hot = np.full((n_demand,), -1, dtype=np.int32)
        # accounting twin: same capacity, mirrored stage/drop, so
        # accuracy/coverage use the one shared PageCache definition
        self.model = PageCache(n_slots)
        # extra mirrors (e.g. a ShardedPageCache for per-shard rollups
        # under tp): receive every stage/drop the twin does — eviction
        # victims are pre-dropped here, so mirrors never self-evict and
        # cannot drift from the tier's FIFO order
        self.mirrors: list = []
        self.stats = RunaheadStats()

    # -- queries -------------------------------------------------------------

    @property
    def n_staged(self) -> int:
        return len(self._slot_of)

    def resolve(self, page: int) -> int:
        """Staged slot of ``page``, or -1."""
        return self._slot_of.get(int(page), -1)

    def hot_map(self) -> np.ndarray:
        """The live demand-page-id -> slot map (int32 [n_demand]; -1 =
        not staged).  Returned by reference: snapshot with
        ``jnp.asarray`` / ``.copy()`` before mutating the tier."""
        return self._hot

    def staged_pages(self) -> list:
        return list(self._slot_of)

    # -- mutation ------------------------------------------------------------

    def _evict_oldest(self) -> int:
        victim, slot = self._slot_of.popitem(last=False)
        self._page_of[slot] = -1
        self._hot[victim] = -1
        self.model.drop(victim)
        for m in self.mirrors:
            m.drop(victim)
        return slot

    def stage(self, pages, max_copies: int | None = None) -> list:
        """Assign slots to ``pages`` (skipping NULL/out-of-range ids and
        pages already staged); returns the ``(src_page, slot)`` copy
        list, at most ``max_copies`` long.  The caller owns making the
        copies land before the next decode reads the hot-map.

        Every slot appears at most once per call: the caller performs
        all copies in one unordered scatter, so reusing a slot within a
        call (FIFO-evicting a page staged moments earlier) would leave
        the slot's bytes to scatter ordering while the hot-map names one
        owner.  When the only eviction victims left were staged by this
        same call, the remaining candidates are dropped as
        budget-truncated instead."""
        copies: list = []
        budget = self.n_slots if max_copies is None else max_copies
        new_slots: set = set()
        for p in pages:
            p = int(p)
            if p <= 0 or p >= self.n_demand or p in self._slot_of:
                continue
            if len(copies) >= budget:
                self.stats.budget_truncated += 1
                continue
            if self._free:
                slot = self._free.pop()
            else:
                # FIFO victim; same-call entries are the newest, so if
                # the oldest is one of ours the tier is all same-call
                oldest_slot = next(iter(self._slot_of.values()))
                if oldest_slot in new_slots:
                    self.stats.budget_truncated += 1
                    continue
                slot = self._evict_oldest()
            new_slots.add(slot)
            self._slot_of[p] = slot
            self._page_of[slot] = p
            self._hot[p] = slot
            self.model.stage(p)
            for m in self.mirrors:
                m.stage(p)
            self.stats.staged_pages += 1
            copies.append((p, slot))
        return copies

    def touch(self, page: int) -> bool:
        """Demand access accounting: True if ``page`` is staged.  Keeps
        the PageCache twin's hit/miss/prefetch-used counters (the
        accuracy/coverage source) in sync with the physical map."""
        hit = int(page) in self._slot_of
        model_hit = self.model.touch(int(page), install=False)
        assert model_hit == hit, \
            f"hot-tier accounting twin diverged on page {page}"
        return hit

    def invalidate(self, pages) -> int:
        """Drop staged entries for ``pages`` (rewritten or freed demand
        copies).  Idempotent; returns the number dropped."""
        n = 0
        for p in pages:
            slot = self._slot_of.pop(int(p), None)
            if slot is None:
                continue
            self._page_of[slot] = -1
            self._hot[int(p)] = -1
            self._free.append(slot)
            self.model.drop(int(p))
            for m in self.mirrors:
                m.drop(int(p))
            self.stats.invalidations += 1
            n += 1
        return n

    # -- derived metrics -----------------------------------------------------

    @property
    def hit_rate(self):
        """Demand hit rate against the staged tier (None pre-traffic)."""
        return self.model.hit_rate

    @property
    def accuracy(self):
        """Of the pages staged, the fraction demanded before eviction
        (the paper's prediction-accuracy axis; None before staging)."""
        return self.model.accuracy

    @property
    def coverage(self):
        """Of the pages demanded, the fraction served by a staged entry
        (the coverage axis; equals hit_rate for a pure-speculative
        tier — demand misses never install)."""
        return self.model.coverage

    @property
    def overfetch(self):
        """Staged-but-never-used fraction: wasted staging bandwidth
        (1 - accuracy; the fuzzy-fetch cost axis)."""
        acc = self.accuracy
        return None if acc is None else 1.0 - acc


@dataclass
class _ReqHistory:
    sel: tuple = ()                 # last observed selection (sorted ids)
    stable: int = 0                 # consecutive identical selections


@dataclass
class RunaheadPredictor:
    """Per-request history predictors + the DARE stability filter.

    ``observe()`` records each decode step's selected demand pages per
    request; a request whose selection repeats ``stable_after`` times is
    *stable* — its history predicts the next step, no proxy needed.
    ``split()`` partitions next-step rows into (covered, needs-proxy).
    """

    mode: str = "nvr"
    stable_after: int = 2
    _hist: dict = field(default_factory=dict)

    def observe(self, rid: int, pages: np.ndarray) -> None:
        sel = tuple(sorted(int(p) for p in pages))
        h = self._hist.setdefault(rid, _ReqHistory())
        h.stable = h.stable + 1 if sel == h.sel and sel else 0
        h.sel = sel

    def history(self, rid: int) -> tuple:
        h = self._hist.get(rid)
        return h.sel if h is not None else ()

    def is_stable(self, rid: int) -> bool:
        h = self._hist.get(rid)
        return h is not None and h.stable >= self.stable_after

    def forget(self, rid: int) -> None:
        self._hist.pop(rid, None)

    def remap(self, rid: int, page_map: dict) -> None:
        """Rename ``rid``'s history through ``page_map`` (old physical
        page id -> new), preserving the stability counter: a swap-resume
        restores identical page *content* onto fresh physical ids, so
        the request's selection pattern — and therefore its stability —
        carries over; only the ids it is expressed in change.  Ids not
        in the map (e.g. still-live shared prefix pages) pass through."""
        h = self._hist.get(rid)
        if h is not None and h.sel:
            h.sel = tuple(sorted(page_map.get(p, p) for p in h.sel))

    def split(self, rids) -> tuple[list, list]:
        """(history-covered rids, proxy rids) for the next step.  In
        ``imp`` mode everything is history — IMP has no runahead slice,
        so it is structurally one step behind any selection drift."""
        if self.mode == "imp":
            return list(rids), []
        covered = [r for r in rids if self.is_stable(r)]
        proxy = [r for r in rids if not self.is_stable(r)]
        return covered, proxy


def make_proxy_scorer(cfg):
    """Build the address-generation slice: next-step TopK prediction.

    Returns ``fn(params, s_pool, token, pos, bt, n_valid) -> phys``
    with token/pos int32 [R], bt int32 [R, NL], n_valid int32 [R] and
    phys int32 [R, KV, K] — the *predicted* next-iteration physical
    page selection.  Only layer 0's ln1/wq (+bq) and the embedding are
    read: the slice approximates the next decode's layer-0 selection
    query from the known next token, skipping the residual stream
    entirely — the few-percent-of-a-forward-pass cost budget the
    paper's decoupled sub-thread rides in.  Speculative by
    construction: its output steers staging only, never the demand
    computation, so prediction error costs bandwidth, not correctness.
    """
    import jax
    import jax.numpy as jnp

    from ..models import layers as mlayers
    from ..models import sparse_attention

    dt = jnp.dtype(cfg.param_dtype)
    g = cfg.n_heads // cfg.n_kv_heads

    def fn(params, s_pool, token, pos, bt, n_valid):
        r = token.shape[0]
        k_sel = int(min(cfg.kv_topk_pages, bt.shape[1]))
        x = jnp.take(params["embed"], token[:, None], axis=0).astype(dt)
        if getattr(cfg, "scale_embed", False):
            x = x * (cfg.d_model ** 0.5)
        lp0 = jax.tree.map(lambda a: a[0], params["layers"])
        h = mlayers.rms_norm(x, lp0["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dh->bsh", h, lp0["wq"].astype(h.dtype))
        if cfg.qkv_bias:
            q = q + lp0["bq"].astype(h.dtype)
        q = q.reshape(r, 1, cfg.n_heads, cfg.hd)
        q = mlayers.apply_rope(q, pos[:, None], cfg.rope_theta)
        qh = q[:, 0].reshape(r, cfg.n_kv_heads, g, cfg.hd)
        _, phys = sparse_attention.select_pages_blocktable(
            qh, s_pool[0], bt, n_valid, k_sel)
        return phys

    return fn


def make_router_scorer(cfg):
    """Build the expert-weight address-generation slice: next-step
    TopK *expert* prediction from the router itself.

    Returns ``fn(params, token) -> eids`` with token int32 [R] (each
    row's known next input token) and eids int32 [R, top_k] — the
    predicted layer-0 routing of the next decode step.  The slice
    embeds the token, applies layer 0's pre-FFN norm, and scores it
    through layer 0's router: the router *is* the paper's cheap
    address-generation function here (NeutronSparse's coordinated-
    engines framing — routing computes the gather addresses an
    iteration before the FFN demands the tiles), and skipping the
    attention/residual stream keeps it inside the decoupled
    sub-thread's few-percent cost budget.  Deeper layers' routing is
    not modelled — the per-request history predictor covers them once a
    request's expert selection stabilises, the same DARE-style division
    of labour as the KV proxy.  Speculative by construction: output
    steers staging only, so a misrouted prediction costs staging
    bandwidth, never a logit.
    """
    import jax
    import jax.numpy as jnp

    from ..models import layers as mlayers

    dt = jnp.dtype(cfg.param_dtype)

    def fn(params, token):
        x = jnp.take(params["embed"], token[:, None], axis=0).astype(dt)
        if getattr(cfg, "scale_embed", False):
            x = x * (cfg.d_model ** 0.5)
        lp0 = jax.tree.map(lambda a: a[0], params["layers"])
        h = mlayers.rms_norm(x, lp0["ln2"], cfg.norm_eps)[:, 0]
        logits = jnp.einsum("rd,de->re", h.astype(jnp.float32),
                            lp0["router"].astype(jnp.float32))
        _, eids = jax.lax.top_k(logits, cfg.top_k)
        return eids.astype(jnp.int32)

    return fn
