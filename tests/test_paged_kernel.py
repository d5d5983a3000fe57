"""Paged sparse-decode Pallas kernel vs the XLA oracle, and the engine
kernel switch.

``kernels.paged_decode_attn`` consumes the serve layer's native layout
(physical page pools + block-table-resolved TopK page ids); its
correctness contract is ``sparse_attention.attend_pages_paged`` — the
XLA path the continuous-batching engine uses on CPU and pins its
bitwise-resume guarantees to.  Parity here is tolerance-based: the
kernel runs an fp32 online softmax (streaming max/sum), the oracle
normalises the materialised gather once.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_decode_attn
from repro.models import sparse_attention

RNG = np.random.default_rng(1234)


def _pool_case(r, kv, g, d, n_pages, n_logical, k_sel, page,
               pool_dtype=jnp.float32, short_row=True, shared_rows=False):
    """Synthetic pool + block tables in the allocator's conventions:
    page 0 reserved (NULL), per-request tables over physical ids,
    per-request frontiers, TopK selection by the real scorer."""
    q = jnp.asarray(RNG.normal(size=(r, kv, g, d)), jnp.float32)
    kp = sparse_attention.kv_quant(
        jnp.asarray(RNG.normal(size=(n_pages, kv, page, d)), jnp.float32),
        pool_dtype)
    vp = sparse_attention.kv_quant(
        jnp.asarray(RNG.normal(size=(n_pages, kv, page, d)), jnp.float32),
        pool_dtype)
    spool = jnp.asarray(RNG.normal(size=(n_pages, kv, d)), jnp.float32)
    bt = np.zeros((r, n_logical), np.int32)
    for i in range(r):
        bt[i] = RNG.choice(np.arange(1, n_pages), size=n_logical,
                           replace=False)
    if shared_rows and r >= 2:
        # COW-style sharing: rows 0/1 share their prompt pages but own
        # private tails (the prefix-cache layout)
        bt[1, :n_logical - 1] = bt[0, :n_logical - 1]
    pos = RNG.integers(page, n_logical * page, size=r).astype(np.int32)
    if short_row:
        # fewer valid pages than the TopK budget: the selection pads
        # with frontier-masked slots (and NULL physical ids via bt)
        pos[0] = page // 2
    n_valid = jnp.asarray(pos) // page + 1
    idx, phys = sparse_attention.select_pages_blocktable(
        q, spool, jnp.asarray(bt), n_valid, k_sel)
    return q, kp, vp, idx, phys, jnp.asarray(pos)


@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("r,kv,g,d,k_sel", [
    (4, 2, 2, 32, 4),
    (2, 2, 6, 64, 3),     # wide GQA group
    (3, 1, 1, 32, 2),     # MQA, single-head group
])
def test_paged_kernel_matches_xla_oracle(page, r, kv, g, d, k_sel):
    q, kp, vp, idx, phys, pos = _pool_case(
        r, kv, g, d, n_pages=24, n_logical=8, k_sel=k_sel, page=page)
    want = sparse_attention.attend_pages_paged(q, kp, vp, idx, phys,
                                               pos, page)
    got = paged_decode_attn(phys, idx, pos, q, kp, vp, page_size=page,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_kernel_pool_dtypes(pool_dtype):
    q, kp, vp, idx, phys, pos = _pool_case(
        3, 2, 2, 32, n_pages=16, n_logical=6, k_sel=3, page=8,
        pool_dtype=pool_dtype)
    want = sparse_attention.attend_pages_paged(q, kp, vp, idx, phys,
                                               pos, 8)
    got = paged_decode_attn(phys, idx, pos, q, kp, vp, page_size=8,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_null_padded_batch_row():
    """A padded batch slot (all-NULL block table, pos 0) must produce
    finite output matching the oracle, never NaNs."""
    r, kv, g, d, page = 2, 2, 2, 32, 8
    q, kp, vp, _, _, _ = _pool_case(r, kv, g, d, n_pages=16, n_logical=6,
                                    k_sel=3, page=page, short_row=False)
    bt = np.zeros((r, 6), np.int32)
    bt[0] = RNG.choice(np.arange(1, 16), size=6, replace=False)
    pos = jnp.asarray([2 * page + 1, 0], jnp.int32)
    n_valid = pos // page + 1
    spool = jnp.asarray(RNG.normal(size=(16, kv, d)), jnp.float32)
    idx, phys = sparse_attention.select_pages_blocktable(
        q, spool, jnp.asarray(bt), n_valid, 3)
    want = sparse_attention.attend_pages_paged(q, kp, vp, idx, phys,
                                               pos, page)
    got = paged_decode_attn(phys, idx, pos, q, kp, vp, page_size=page,
                            interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_cow_shared_block_tables():
    """Two requests whose block tables share physical prompt pages (the
    prefix-cache COW layout) attend through the same pool bytes; each
    row's output must still match the oracle independently."""
    q, kp, vp, idx, phys, pos = _pool_case(
        2, 2, 2, 32, n_pages=16, n_logical=4, k_sel=3, page=8,
        short_row=False, shared_rows=True)
    assert len(set(np.asarray(phys[0]).ravel())
               & set(np.asarray(phys[1]).ravel())) > 0
    want = sparse_attention.attend_pages_paged(q, kp, vp, idx, phys,
                                               pos, 8)
    got = paged_decode_attn(phys, idx, pos, q, kp, vp, page_size=8,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
class TestEngineKernelSwitch:
    """PagedEngine(kernel="pallas") vs the XLA path on the shared-prefix
    multi-tenant workload (the TestPrefixCacheEngine fixture shape):
    same tokens greedily decoded, logits within fp32 online-softmax
    tolerance, across page-size {8,16} pool geometries, NULL-padded rows
    and COW-shared block tables."""

    @pytest.fixture(scope="class")
    def setup(self):
        import jax

        from repro.configs import get_config
        from repro.models import api

        cfg = get_config("qwen2-1.5b").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        sys_prompts = [rng.integers(1, cfg.vocab, size=12)
                       for _ in range(2)]
        work = []
        for i in range(4):
            suffix = rng.integers(1, cfg.vocab,
                                  size=int(rng.integers(2, 6)))
            prompt = np.concatenate([sys_prompts[i % 2], suffix])
            work.append((float(i) * 0.5, prompt, 4))
        return cfg, params, work

    def _run(self, cfg, params, work, kernel):
        from repro.serve.engine import PagedEngine

        eng = PagedEngine(cfg, params, max_len=48, max_batch=4, chunk=8,
                          nsb_pages=32, kernel=kernel)
        eng.run([(t, p.copy(), g) for t, p, g in work])
        return eng

    @pytest.mark.parametrize("kv_page", [8, 16])
    def test_pallas_engine_matches_xla_engine(self, setup, kv_page):
        from dataclasses import replace

        cfg, params, work = setup
        cfg = replace(cfg, kv_page=kv_page)
        xla = self._run(cfg, params, work, "xla")
        pal = self._run(cfg, params, work, "pallas")
        if kv_page == 8:
            # 12-token system prompts fill a whole page only at page=8:
            # that geometry exercises COW-shared block tables
            assert pal.allocator.stats.prefix_hits > 0
        for rid in xla.requests:
            a, b = xla.requests[rid], pal.requests[rid]
            assert a.out_tokens == b.out_tokens
            np.testing.assert_allclose(a.last_logits, b.last_logits,
                                       rtol=2e-5, atol=2e-5)

    def test_bitwise_resume_stays_on_xla_path(self, setup):
        """The preemption bitwise-resume contract is pinned to the XLA
        oracle: the default engine kernel must remain "xla"."""
        from repro.serve.engine import PagedEngine

        cfg, params, _ = setup
        eng = PagedEngine(cfg, params, max_len=48, max_batch=2, chunk=8)
        assert eng.kernel == "xla"

    def test_rejects_unknown_kernel(self, setup):
        from repro.serve.engine import PagedEngine

        cfg, params, _ = setup
        with pytest.raises(ValueError):
            PagedEngine(cfg, params, max_len=48, kernel="cuda")
