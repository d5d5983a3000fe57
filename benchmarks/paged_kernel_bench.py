"""Paged-decode fast-path benchmark: the donated + bucketed step loop
(and the Pallas paged kernel) vs the pre-PR serve hot path.

Two measurements on the same Poisson serve workload:

1. **Step-loop speedup** — ``PagedEngine`` with pool-buffer donation and
   power-of-two row bucketing (the defaults) vs the pre-PR configuration
   (no donation: every jitted call round-trips a full ``[L,P,KV,page,D]``
   pool copy; no bucketing: every ragged decode batch pads to
   ``max_batch``).  On this CPU container the win is dominated by the
   pool-copy and padded-row eliminations — the same levers, scaled up,
   that dominate at production pool sizes.

2. **Kernel parity + micro-timing** — one decode-attention call on the
   post-run's real pool state through both implementations:
   ``attend_pages_paged`` (XLA oracle) and ``kernels.paged_decode_attn``
   (Pallas, interpret mode here; the TPU lowering is exercised
   structurally).  Parity is asserted in the same run; interpret-mode
   wall time is a Python-loop number, reported for completeness, not a
   hardware claim.

  PYTHONPATH=src python -m benchmarks.paged_kernel_bench
  PYTHONPATH=src python -m benchmarks.run paged_kernel_bench
"""

from __future__ import annotations

import os
import time

import numpy as np

SCALE = float(os.environ.get("BENCH_SCALE", "0.5"))
from .paths import results_dir


def _workload(cfg, n_req: int, seed: int = 0):
    from repro.serve.scheduler import PoissonArrivals

    rng = np.random.default_rng(seed)
    arrivals = PoissonArrivals(n_req, rate=0.5, prompt_len=(8, 24),
                               gen_len=(6, 12), seed=seed)
    return [(t, rng.integers(1, cfg.vocab, size=p), g)
            for t, p, g in arrivals]


def _warm(eng) -> None:
    """Pre-trace every decode bucket and the prefill chunk so the timed
    run measures the steady-state serving loop, not XLA compiles.  The
    warmup rows carry all-NULL block tables, so they only scribble the
    reserved scratch page 0 (same contract as real padded rows)."""
    import jax.numpy as jnp

    buckets = eng.row_buckets or (eng.max_batch,)
    for rb in buckets:
        token = jnp.zeros((rb,), jnp.int32)
        pos = jnp.zeros((rb,), jnp.int32)
        bts = jnp.zeros((rb, eng.n_logical), jnp.int32)
        _, eng.k_pool, eng.v_pool, eng.s_pool, _ = eng._decode(
            eng.params, eng.k_pool, eng.v_pool, eng.s_pool, token, pos,
            bts)
    toks = jnp.zeros((eng.chunk,), jnp.int32)
    bt = jnp.zeros((eng.n_logical,), jnp.int32)
    _, eng.k_pool, eng.v_pool, eng.s_pool = eng._prefill(
        eng.params, eng.k_pool, eng.v_pool, eng.s_pool, toks,
        np.int32(0), np.int32(1), bt)


def _run_engine(cfg, params, workload, **kw):
    from repro.serve.engine import PagedEngine

    # max_batch 16 with modest Poisson concurrency: the pre-PR pad-to-max
    # path computes mostly NULL rows, the bucketed path does not — and
    # the larger pool makes the undonated per-call copy an honest cost
    eng = PagedEngine(cfg, params, max_len=384, max_batch=16, chunk=16,
                      nsb_pages=32, **kw)
    _warm(eng)
    t0 = time.perf_counter()
    eng.run([(t, p.copy(), g) for t, p, g in workload])
    wall = time.perf_counter() - t0
    return eng, wall


def _kernel_parity_and_timing(cfg, eng, n_timing: int = 20):
    """One decode-attention call on the run's real layer-0 pool state,
    both implementations; returns (max_abs_err, us_xla, us_pallas)."""
    import jax
    import jax.numpy as jnp

    from repro.models import sparse_attention

    rng = np.random.default_rng(3)
    r, kv, g = 8, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    nl = eng.n_logical
    page = eng.page
    q = jnp.asarray(rng.normal(size=(r, kv, g, cfg.hd)), jnp.float32)
    bt = np.zeros((r, nl), np.int32)
    for i in range(r):
        bt[i] = rng.choice(np.arange(1, eng.n_pages), size=nl,
                           replace=False)
    pos = jnp.asarray(rng.integers(page, nl * page, size=r), jnp.int32)
    n_valid = pos // page + 1
    k_sel = int(min(cfg.kv_topk_pages, nl))
    idx, phys = sparse_attention.select_pages_blocktable(
        q, eng.s_pool[0], jnp.asarray(bt), n_valid, k_sel)

    xla = jax.jit(lambda *a: sparse_attention.attend_pages_paged(*a, page))
    pal = lambda *a: sparse_attention.attend_pages_paged_kernel(*a, page)
    args = (q, eng.k_pool[0], eng.v_pool[0], idx, phys, pos)
    want = jax.block_until_ready(xla(*args))
    got = jax.block_until_ready(pal(*args))
    err = float(np.abs(np.asarray(got, np.float32)
                       - np.asarray(want, np.float32)).max())

    def timeit(fn):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n_timing):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n_timing * 1e6

    return err, timeit(xla), timeit(pal), r


def paged_kernel_bench():
    """Registered in benchmarks.run as ``paged_kernel_bench``."""
    import jax

    from repro.configs import get_config
    from repro.core.nvr.engine.sweep import write_artifacts
    from repro.models import api

    from dataclasses import replace

    # the reduced smoke config, scaled back up where it matters for this
    # measurement: a capacity-sized pool (production pools are sized for
    # max_len x max_batch, not current load), 4 layers, head_dim 64 —
    # the per-call k/v/s round-trip the undonated path pays is ~14 MiB
    cfg = replace(get_config("qwen2-1.5b").reduced(),
                  n_layers=4, head_dim=64, kv_page=8)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    n_req = max(16, int(32 * SCALE))
    workload = _workload(cfg, n_req)

    pre, pre_wall = _run_engine(cfg, params, workload,
                                donate_pools=False, row_bucketing=False)
    post, post_wall = _run_engine(cfg, params, workload)

    # sanity: the fast path must not change anyone's output
    for rid in pre.requests:
        a, b = pre.requests[rid], post.requests[rid]
        assert a.out_tokens == b.out_tokens, f"rid {rid} diverged"

    m_pre, m_post = pre.metrics(), post.metrics()
    pre_tps = m_pre["tokens_out"] / pre_wall
    post_tps = m_post["tokens_out"] / post_wall
    # the copies donation eliminated: without donation every decode step
    # and every executed prefill chunk materialises fresh k/v/s pools
    jit_calls = pre.stats.steps + pre.stats.prefill_calls
    pool_bytes = (pre.pool_cfg.pool_bytes                # K+V pools
                  + pre.s_pool.size * pre.s_pool.dtype.itemsize)
    copy_mib = jit_calls * pool_bytes / 2 ** 20

    err, us_xla, us_pal, r_k = _kernel_parity_and_timing(cfg, post)
    assert err < 1e-5, f"pallas/XLA parity broke: max_err={err}"

    rows = [
        ("pre_pr_path", m_pre["tokens_out"], f"{pre_wall:.3f}",
         f"{pre_tps:.1f}", m_pre["n_decode_traces"],
         m_pre["decode_rows_padded"]),
        ("donated_bucketed", m_post["tokens_out"], f"{post_wall:.3f}",
         f"{post_tps:.1f}", m_post["n_decode_traces"],
         m_post["decode_rows_padded"]),
        ("kernel_xla_us", r_k, f"{us_xla:.0f}", "", "", ""),
        ("kernel_pallas_interpret_us", r_k, f"{us_pal:.0f}", "", "", ""),
    ]
    headline = {
        "n_requests": float(n_req),
        "tok_per_s_pre_pr": pre_tps,
        "tok_per_s_donated_bucketed": post_tps,
        "step_loop_speedup_x": post_tps / pre_tps,
        "pool_copy_mib_eliminated": copy_mib,
        "decode_rows_padded_pre": float(m_pre["decode_rows_padded"]),
        "decode_rows_padded_post": float(m_post["decode_rows_padded"]),
        "n_decode_traces_post": float(m_post["n_decode_traces"]),
        "kernel_parity_max_err": err,
        "xla_oracle_us_per_call": us_xla,
        "pallas_interpret_us_per_call": us_pal,
        "paper": "NVR runahead kernel on the serve pool layout; step-loop "
                 "speedup from donation + row bucketing (CPU measurement "
                 "dominated by pool-copy / padded-row elimination)",
    }
    write_artifacts(
        "paged_kernel_bench",
        "config,tokens_or_rows,wall_s_or_us,tok_per_s,decode_traces,"
        "rows_padded", rows, results_dir(), scale=SCALE)
    return rows, headline


def main() -> None:
    rows, headline = paged_kernel_bench()
    print(f"paged_kernel_bench: {len(rows)} rows")
    for k, v in headline.items():
        print(f"    {k:34s} {v:.4g}" if isinstance(v, float)
              else f"    {k:34s} {v}")


if __name__ == "__main__":
    main()
