"""Host KV spill tier: allocator swap-out/swap-in, the host pool's
round-trip guarantees, and swap-resume parity through the paged engine.

Acceptance: preemption under a spill tier snapshots pages to host and
resume restores them onto fresh HBM ids with **bitwise-identical**
tokens and logits vs the free-and-recompute baseline (int8 spill trades
the bitwise K/V claim for a scale/2 dequantisation bound, asserted at
the pool level); every physical page id lives in exactly one tier at
all times (``check_tier_invariants``)."""

import numpy as np
import pytest

from repro.core.nvr import capture
from repro.serve.kv_allocator import KVBlockAllocator
from repro.serve.scheduler import RequestState
from repro.serve.spill import HostSpillPool


class TestSpillAllocator:
    def test_spill_releases_pages_and_resume_remaps(self):
        al = KVBlockAllocator(n_pages=8, page_tokens=4, spill_pages=8)
        assert al.ensure(0, 12)                    # 3 pages
        old = list(al.table(0))
        assert al.spill_request(0)
        assert al.is_spilled(0) and al.pages_in_use == 0
        assert al.pages_spilled == 3
        # snapshots queued before the ids were released
        outs = al.drain_spill_outs()
        assert [p for p, _ in outs] == old
        # another request may take the released ids meanwhile
        assert al.ensure(1, 8)
        assert al.resume_spilled(0)
        assert not al.is_spilled(0) and al.owned(0) == 3
        ins = al.drain_swap_ins()
        assert [p for _, p in ins] == al.table(0)
        assert set(al.table(0)).isdisjoint(al.table(1))
        [(rid, remap)] = al.drain_remaps()
        assert rid == 0 and set(remap) == set(old)
        assert sorted(remap.values()) == sorted(al.table(0))
        al.check_tier_invariants()

    def test_spill_disabled_or_short_is_all_or_nothing(self):
        al = KVBlockAllocator(n_pages=8, page_tokens=4)   # tier off
        al.ensure(0, 4)
        assert not al.spill_request(0)
        assert al.stats.spill_failures == 1
        assert al.owned(0) == 1                    # state untouched
        al2 = KVBlockAllocator(n_pages=8, page_tokens=4, spill_pages=2)
        al2.ensure(0, 12)                          # 3 pages > 2 slots
        assert not al2.spill_request(0)
        assert al2.owned(0) == 3 and al2.pages_spilled == 0
        al2.check_tier_invariants()

    def test_resume_blocked_then_retried(self):
        al = KVBlockAllocator(n_pages=4, page_tokens=4, spill_pages=4)
        al.ensure(0, 8)                            # 2 of 3 pages
        assert al.spill_request(0)
        al.drain_spill_outs()
        al.ensure(1, 12)                           # pool now full
        assert not al.resume_spilled(0)
        assert al.is_spilled(0)                    # snapshot kept
        assert al.stats.admission_blocks == 1
        al.free_request(1)
        assert al.resume_spilled(0)
        al.drain_swap_ins()
        al.check_tier_invariants()

    def test_resume_covers_extra_prompt_pages(self):
        """A request spilled mid-prefill resumes with enough private
        pages for the whole reserved prompt, not just the snapshots."""
        al = KVBlockAllocator(n_pages=16, page_tokens=4, spill_pages=8)
        al.ensure(0, 8)                            # 2 pages computed
        assert al.spill_request(0)
        al.drain_spill_outs()
        assert al.resume_spilled(0, n_tokens=14)   # needs 4 pages total
        assert al.owned(0) == 4
        assert len(al.drain_swap_ins()) == 2       # only snapshots restore
        al.check_tier_invariants()

    def test_spilled_shared_pages_never_park_in_cached_lru(self):
        """The one-home-per-content bugfix: a page whose bytes live on in
        a host snapshot is unregistered from the prefix index when its
        last HBM holder releases it — free list, never the cached LRU
        (a later prefix attach would resurrect a page a resume is about
        to overwrite)."""
        al = KVBlockAllocator(n_pages=16, page_tokens=4, spill_pages=8)
        prompt = np.arange(100, 112)               # 3 full pages
        al.ensure_prompt(0, prompt)
        al.register_prefix(0, prompt, 12)
        al.ensure_prompt(1, prompt)        # attaches 2, COWs the tail
        shared = al.table(0)[:2]
        assert al.table(1)[:2] == shared
        assert al.table(1)[2] != al.table(0)[2]
        assert al.spill_request(1)                 # snapshots shared pages
        al.drain_spill_outs()
        al.free_request(0)                         # last HBM holder gone
        assert set(shared).isdisjoint(al._cached)
        assert set(shared) <= set(al._free)
        assert al.stats.spill_unregistered == 2
        al.check_tier_invariants()
        # a fresh identical prompt gets no stale attach
        ok, cached = al.ensure_prompt(2, prompt)
        assert ok and cached == 0
        al.check_tier_invariants()

    def test_free_while_spilled_recycles_slots(self):
        al = KVBlockAllocator(n_pages=8, page_tokens=4, spill_pages=3)
        al.ensure(0, 12)
        assert al.spill_request(0)
        al.drain_spill_outs()
        assert al.spill_slots_free == 0
        al.free_request(0)                         # snapshot discarded
        assert al.spill_slots_free == 3 and not al.is_spilled(0)
        al.check_tier_invariants()

    def test_slots_drain_before_recycling(self):
        """Resumed slots stay off the free list until the engine takes
        the host->device restores — recycling them earlier would let a
        new spill overwrite bytes still queued for restore."""
        al = KVBlockAllocator(n_pages=8, page_tokens=4, spill_pages=2)
        al.ensure(0, 8)
        assert al.spill_request(0)
        al.drain_spill_outs()
        assert al.resume_spilled(0)
        assert al.spill_slots_free == 0            # draining, not free
        al.ensure(1, 8)
        assert not al.spill_request(1)             # tier genuinely full
        al.drain_swap_ins()
        assert al.spill_slots_free == 2
        assert al.spill_request(1)
        al.check_tier_invariants()


class TestHostSpillPool:
    def _planes(self, rng, n, layers=2, page=4, kv=2, d=8):
        k = rng.normal(size=(n, layers, kv, page, d)).astype(np.float32)
        v = rng.normal(size=(n, layers, kv, page, d)).astype(np.float32)
        s = rng.normal(size=(n, layers, kv, d)).astype(np.float32)
        return k, v, s

    def test_uncompressed_roundtrip_is_bitwise(self):
        rng = np.random.default_rng(0)
        pool = HostSpillPool(4, 2, 4, 2, 8, np.dtype(np.float32))
        k, v, s = self._planes(rng, 3)
        pool.store([0, 2, 3], k, v, s)
        k2, v2, s2 = pool.load([0, 2, 3])
        assert np.array_equal(k, k2) and np.array_equal(v, v2)
        assert np.array_equal(s, s2)
        assert pool.error_bound([0, 2, 3]) == 0.0

    def test_int8_roundtrip_within_scale_bound(self):
        rng = np.random.default_rng(1)
        pool = HostSpillPool(4, 2, 4, 2, 8, np.dtype(np.float32),
                             compress=True)
        k, v, s = self._planes(rng, 2)
        pool.store([1, 3], k, v, s)
        k2, v2, s2 = pool.load([1, 3])
        bound = pool.error_bound([1, 3])
        assert bound > 0.0
        assert np.abs(k - k2).max() <= bound + 1e-6
        assert np.abs(v - v2).max() <= bound + 1e-6
        # page summaries drive TopK selection: always stored exact
        assert np.array_equal(s, s2)

    def test_int8_halves_host_bytes(self):
        a = HostSpillPool(4, 2, 4, 2, 8, np.dtype(np.float16))
        b = HostSpillPool(4, 2, 4, 2, 8, np.dtype(np.float16),
                          compress=True)
        assert b.host_bytes < a.host_bytes


def _mk(cfg, params, work, n_pages, **kw):
    from repro.serve.engine import PagedEngine

    eng = PagedEngine(cfg, params, max_len=48, n_pages=n_pages,
                      max_batch=4, chunk=8, nsb_pages=8, **kw)
    eng.run([(t, p.copy(), g) for t, p, g in work])
    eng.allocator.check_tier_invariants()
    return eng


@pytest.mark.slow
class TestSpillEngine:
    @pytest.fixture(scope="class")
    def setup(self):
        import jax

        from repro.configs import get_config
        from repro.models import api

        cfg = get_config("qwen2-1.5b").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        work = [(0.0, rng.integers(1, cfg.vocab, size=12), 6)
                for _ in range(5)]
        return cfg, params, work

    def test_swap_resume_bitwise_identical(self, setup):
        """Forced preemption with the spill tier: swap-out + swap-in
        reproduces the recompute run bit-for-bit (same tokens, same
        logits) while skipping the re-prefill."""
        cfg, params, work = setup
        base = _mk(cfg, params, work, 9)                 # recompute
        swap = _mk(cfg, params, work, 9, spill_pages=16)
        assert base.scheduler.n_preemptions > 0
        assert swap.scheduler.n_swap_outs > 0
        assert swap.scheduler.n_swap_ins == swap.scheduler.n_swap_outs
        assert swap.stats.swap_in_pages == swap.stats.swap_out_pages > 0
        for rid in base.requests:
            a, b = base.requests[rid], swap.requests[rid]
            assert a.out_tokens == b.out_tokens
            assert np.array_equal(a.last_logits, b.last_logits)
        # swap resumes skip the replay prefill the recompute path pays
        assert (swap.scheduler.prefill_tokens_skipped
                < base.scheduler.prefill_tokens_skipped) \
            or swap.stats.prefill_tokens < base.stats.prefill_tokens
        assert swap.allocator.pages_spilled == 0         # tier drained
        m = swap.metrics()
        assert m["swap_outs"] > 0 and m["spill_host_mib"] > 0

    def test_cow_shared_pages_spill_bitwise(self, setup):
        """A request holding COW-shared prefix pages is spilled while
        another request still holds them: the snapshot reads shared
        bytes, resume lands on private ids, logits stay bitwise."""
        cfg, params, _ = setup
        rng = np.random.default_rng(5)
        sys_p = rng.integers(1, cfg.vocab, size=8)       # 2 shared pages
        work = [(0.0, np.concatenate(
            [sys_p, rng.integers(1, cfg.vocab, size=6)]), 6)
            for _ in range(5)]
        base = _mk(cfg, params, work, 10)
        swap = _mk(cfg, params, work, 10, spill_pages=16)
        assert swap.scheduler.n_swap_outs > 0
        assert swap.allocator.stats.prefix_hits > 0
        for rid in base.requests:
            a, b = base.requests[rid], swap.requests[rid]
            assert a.out_tokens == b.out_tokens
            assert np.array_equal(a.last_logits, b.last_logits)

    def test_runahead_fetch_back_bitwise(self, setup):
        """nvr runahead + spill: fetch-back swap-resumes the queue head
        in the between-steps window and pre-stages its history pages —
        still bitwise vs the recompute baseline."""
        cfg, params, work = setup
        base = _mk(cfg, params, work, 9)
        ra = _mk(cfg, params, work, 9, spill_pages=16,
                 runahead="nvr", runahead_pages=8)
        assert ra.stats.fetch_backs > 0
        for rid in base.requests:
            a, b = base.requests[rid], ra.requests[rid]
            assert a.out_tokens == b.out_tokens
            assert np.array_equal(a.last_logits, b.last_logits)

    def test_int8_spill_stays_within_reported_bound(self, setup):
        """Compressed spill completes the oversubscribed workload and
        reports the worst-case dequantisation bound it actually hit;
        logits track the exact run within a loose envelope of it."""
        cfg, params, work = setup
        base = _mk(cfg, params, work, 9, spill_pages=16)
        q = _mk(cfg, params, work, 9, spill_pages=16, spill_compress=True)
        assert q.scheduler.n_swap_outs > 0
        m = q.metrics()
        assert m["spill_compressed"]
        assert 0.0 < m["spill_dequant_error_bound"] < 0.5
        assert all(r.state is RequestState.FINISHED
                   for r in q.requests.values())
        for rid in base.requests:
            np.testing.assert_allclose(
                base.requests[rid].last_logits,
                q.requests[rid].last_logits, atol=0.5, rtol=0.1)

    def test_resume_ttft_metrics_both_policies(self, setup):
        """Resume-TTFT (re-admission to next new token) is measured for
        recompute *and* swap so the bench comparison is apples-to-apples
        — and swap's gap excludes the replay the recompute path pays."""
        cfg, params, work = setup
        base = _mk(cfg, params, work, 9)
        swap = _mk(cfg, params, work, 9, spill_pages=16)
        mb, ms = base.metrics(), swap.metrics()
        assert mb["n_resumes"] > 0 and ms["n_resumes"] > 0
        assert ms["p50_resume_ttft"] <= mb["p50_resume_ttft"]
        assert "swap_outs" not in mb                 # gated on the tier

    def test_capture_tags_swap_traffic_as_host_tier(self, setup):
        cfg, params, work = setup
        eng = _mk(cfg, params, work, 9, spill_pages=16,
                  capture_trace=True)
        rec = eng.recorder
        assert capture.TIER_HOST in rec.tier_ids()
        host = rec.subset_tier(capture.TIER_HOST)
        assert host.n_events > 0
        hbm = rec.subset_tier(capture.TIER_HBM)
        assert hbm.n_events > 0
        assert host.n_events + hbm.n_events <= rec.n_events
