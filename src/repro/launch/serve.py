"""Serving launcher: batched decode with NVR sparse-KV attention.

Single-batch (lockstep) mode:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --batch 4 --prompt-len 64 --gen 32

Continuous-batching mode — Poisson arrivals through the paged engine
(admission queue, chunked prefill, preempt-and-evict KV allocator):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --continuous --requests 16 --rate 0.5 --max-batch 8 --pages 49

Multi-tenant prefix reuse — requests share one of N system prompts and
the COW prefix cache skips their recomputation (--no-prefix-cache to
compare against the uncached run):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --continuous --requests 16 --shared-prefix 4 --capture

Tensor-parallel serving — the physical KV pools and QKV weights shard
across a ("model",) mesh along the KV-head axis (1/tp pool bytes per
shard, per-shard NSBs, logits bitwise-identical to --tp 1).  On CPU,
force host devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --continuous --tp 2 --requests 16

Online runahead — between decode steps the engine predicts each live
request's next-iteration TopK pages and stages them into a physical NSB
tail on the KV pools (tokens stay bitwise-identical; see
ARCHITECTURE.md "online runahead"):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --continuous --requests 16 --shared-prefix 4 --runahead nvr

Host KV spill tier — preemption under pool pressure swaps pages to a
host pool and resume swaps them back (no re-prefill, tokens unchanged;
--spill-compress stores the spilled K/V planes int8 with per-page
scales).  Pair with a small --pages to oversubscribe (every request
must still fit the pool alone: pages > (prompt_len + gen) / kv_page):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --continuous --requests 16 --prompt-len 24 --gen 8 --pages 12 \
      --spill 64 --runahead nvr

Paged expert-weight streaming (MoE archs) — expert FFN weights become
fixed row-tile pages resolved through block tables, optionally with
router-keyed runahead staging predicted tiles into the expert pool's
NSB tail (tokens bitwise-identical to --expert-pool dense; see
ARCHITECTURE.md "paged expert-weight streaming"):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-235b-a22b \
      --reduced --continuous --requests 16 --expert-pool paged \
      --expert-runahead router

Scheduling policies + trace-driven workloads — the front door delegates
admission order and eviction victims to a pluggable policy
(``--policy fifo|priority|slo_fair``; fifo is the bitwise-parity
default), and ``--workload`` replaces the synthetic Poisson stream with
a trace file (``serve/workload.py`` schema: bursty multi-tenant
arrivals, priority classes, TTFT/TPOT SLOs, multi-turn conversations).
Multi-turn sessions hold their KV between turns (``--session-hold``)
and can park it in the host spill tier during think time
(``--idle-swap``, needs ``--spill``):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --continuous --policy slo_fair \
      --workload traces/bursty_multiturn.json \
      --pages 28 --spill 64 --session-hold --idle-swap --runahead nvr
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs import get_config
from ..models import api
from .compile_cache import setup_compile_cache
from ..serve.engine import Engine, PagedEngine
from ..serve.scheduler import PoissonArrivals


def _run_single_batch(cfg, params, args):
    from ..configs.base import ShapeCell
    cell = ShapeCell("serve", args.prompt_len, args.batch, "prefill")
    batch = api.make_inputs(cfg, cell, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_len=args.prompt_len + args.gen,
                 sparse=not args.dense)
    out = eng.generate(batch, args.gen)
    s = eng.stats
    print(f"[serve] generated {out.shape} tokens; sparse={eng.sparse}")
    if eng.sparse and s.hot_hit_rate is not None:
        print(f"[serve] NSB hot-set hit rate {s.hot_hit_rate:.3f} "
              f"(pages touched {s.pages_touched}, unique-miss "
              f"{s.nsb_misses}) -> off-chip fetch reduction "
              f"{100 * s.offchip_reduction:.1f}%")
    return out


def _fmt(x, spec: str = ".3f") -> str:
    """Format a metric that is None before any traffic (zero-traffic
    smoke runs) without crashing the report."""
    return "n/a" if x is None else format(x, spec)


def _run_continuous(cfg, params, args):
    rng = np.random.default_rng(args.seed)
    if args.workload:
        from ..serve.workload import load_trace, materialize
        specs = load_trace(args.workload)
        workload = materialize(specs, cfg.vocab, seed=args.seed)
        n_requests = sum(1 + len(w.turns) for w in workload)
        # a turn-N prompt is the whole conversation so far: size max_len
        # for the longest possible final turn
        longest = max(len(w.prompt) + w.max_new_tokens
                      + sum(len(t.user_tokens) + t.max_new_tokens
                            for t in w.turns)
                      for w in workload)
    elif args.shared_prefix:
        # multi-tenant shape: every request opens with one of a handful
        # of system prompts, so whole prompt pages repeat across requests
        sys_len = max(cfg.kv_page,
                      args.prompt_len // 2 // cfg.kv_page * cfg.kv_page)
        sys_prompts = [rng.integers(1, cfg.vocab, size=sys_len)
                       for _ in range(args.shared_prefix)]
        arrivals = PoissonArrivals(
            args.requests, rate=args.rate,
            prompt_len=(1, max(1, args.prompt_len - sys_len)),
            gen_len=(max(1, args.gen // 2), args.gen), seed=args.seed)
        workload = [(t, np.concatenate(
            [sys_prompts[i % args.shared_prefix],
             rng.integers(1, cfg.vocab, size=p)]), g)
            for i, (t, p, g) in enumerate(arrivals)]
    else:
        arrivals = PoissonArrivals(
            args.requests, rate=args.rate,
            prompt_len=(max(1, args.prompt_len // 2), args.prompt_len),
            gen_len=(max(1, args.gen // 2), args.gen), seed=args.seed)
        workload = [(t, rng.integers(1, cfg.vocab, size=p), g)
                    for t, p, g in arrivals]
    if not args.workload:
        n_requests = len(workload)
        # sized from the built workload: a shared-prefix prompt (system
        # prompt + suffix) may exceed --prompt-len
        longest = max(len(p) + g for _, p, g in workload)
    max_len = -(-longest // cfg.kv_page) * cfg.kv_page
    mesh = None
    if args.tp > 1:
        from .mesh import make_serve_mesh
        mesh = make_serve_mesh(args.tp)
    eng = PagedEngine(cfg, params, max_len=max_len, n_pages=args.pages,
                      max_batch=args.max_batch, chunk=args.chunk,
                      nsb_pages=args.nsb_pages, capture_trace=args.capture,
                      prefix_cache=not args.no_prefix_cache,
                      kernel=args.kernel,
                      donate_pools=not args.no_donate,
                      row_bucketing=not args.no_buckets,
                      mesh=mesh,
                      runahead=args.runahead,
                      runahead_pages=args.runahead_pages,
                      spill_pages=args.spill,
                      spill_compress=args.spill_compress,
                      executor=args.executor,
                      expert_pool=args.expert_pool,
                      expert_tile_rows=args.expert_tile_rows,
                      expert_nsb_slots=args.expert_nsb_slots,
                      expert_runahead=args.expert_runahead,
                      expert_runahead_pages=args.expert_runahead_pages,
                      policy=args.policy,
                      session_hold=args.session_hold,
                      idle_swap=args.idle_swap)
    eng.run(workload)
    m = eng.metrics()
    print(f"[serve-cb] {m['n_finished']}/{n_requests} requests in "
          f"{m['iterations']} iterations ({m['tokens_out']} tokens, "
          f"{m['preemptions']} preemptions, peak "
          f"{m['pages_peak_in_use']}/{eng.allocator.capacity} pages)")
    if eng.tp > 1:
        rates = ", ".join(_fmt(r) for r in m["nsb_shard_hit_rates"])
        print(f"[serve-cb] tp={eng.tp}: "
              f"{m['kv_pool_mib_per_shard']:.2f} MiB KV pool per shard, "
              f"per-shard NSB hit rates [{rates}] "
              f"(roll-up {_fmt(m['nsb_shard_rollup_hit_rate'])})")
    print(f"[serve-cb] step loop: {m['n_decode_traces']} decode traces "
          f"({eng.kernel} kernel), {m['decode_rows_padded']} padded "
          f"decode rows")
    print(f"[serve-cb] latency p50/p99 {_fmt(m['p50_latency'], '.0f')}/"
          f"{_fmt(m['p99_latency'], '.0f')} iters; TTFT p50/p99 "
          f"{_fmt(m['p50_ttft'], '.0f')}/{_fmt(m['p99_ttft'], '.0f')}; "
          f"TPOT p50/p99 {_fmt(m['p50_tpot'], '.2f')}/"
          f"{_fmt(m['p99_tpot'], '.2f')}")
    if args.executor == "async":
        print(f"[serve-cb] executor=async: overlap fraction "
              f"{_fmt(m['overlap_fraction'])} "
              f"({m['prefill_iterations']} prefill / "
              f"{m['decode_iterations']} decode / "
              f"{m['overlap_iterations']} overlapped iterations), "
              f"plan reuse {_fmt(m['plan_reuse_fraction'])} "
              f"({m['plan_repairs']} repairs), p99 TPOT "
              f"{_fmt(m['p99_tpot'], '.2f')} iters/token")
    print(f"[serve-cb] NSB hot-set hit rate "
          f"{_fmt(m['nsb_hot_hit_rate'])}")
    if args.runahead != "off":
        print(f"[serve-cb] runahead={m['runahead_mode']}: "
              f"{m['runahead_staged_pages']} pages staged "
              f"({m['runahead_stage_calls']} gathers, "
              f"{m['runahead_invalidations']} invalidations), "
              f"accuracy {_fmt(m['runahead_accuracy'])}, coverage "
              f"{_fmt(m['runahead_coverage'])}, over-fetch "
              f"{_fmt(m['runahead_overfetch'])}; demand-LRU baseline "
              f"hit rate {_fmt(m['nsb_demand_lru_hit_rate'])}")
    if args.spill > 0:
        print(f"[serve-cb] spill: {m['swap_outs']} swap-outs / "
              f"{m['swap_ins']} swap-ins ({m['swap_out_pages']} pages "
              f"out, {m['swap_in_pages']} in, {m['fetch_backs']} "
              f"runahead fetch-backs, {m['spill_fallbacks']} recompute "
              f"fallbacks); host pool {m['spill_host_mib']:.2f} MiB"
              + (f", int8 err bound "
                 f"{m['spill_dequant_error_bound']:.2e}"
                 if m["spill_compressed"] else "")
              + f"; resume-TTFT p50 {_fmt(m['p50_resume_ttft'], '.0f')}")
    if args.expert_pool != "off":
        print(f"[serve-cb] expert pool={m['expert_pool']}: "
              f"{m['expert_pool_pages']} tile pages "
              f"({m['expert_pool_mib']:.2f} MiB, "
              f"{m['expert_tile_rows']}-row tiles), "
              f"{m['expert_pages_touched']} demand touches, hit rate "
              f"{_fmt(m['expert_nsb_hit_rate'])} (demand-LRU baseline "
              f"{_fmt(m['expert_demand_lru_hit_rate'])})")
    if args.expert_runahead != "off":
        print(f"[serve-cb] expert runahead={m['expert_runahead_mode']}: "
              f"{m['expert_staged_pages']} tiles staged "
              f"({m['expert_stage_calls']} gathers, "
              f"{m['expert_nsb_slots']} NSB slots), accuracy "
              f"{_fmt(m['expert_runahead_accuracy'])}, coverage "
              f"{_fmt(m['expert_runahead_coverage'])}, over-fetch "
              f"{_fmt(m['expert_runahead_overfetch'])}")
    if args.policy != "fifo" or m["slo_attainment"] is not None:
        print(f"[serve-cb] policy={m['policy']}: SLO attainment "
              f"{_fmt(m['slo_attainment'])}")
        for kind in ("per_tenant", "per_class"):
            for key, g in m.get(kind, {}).items():
                print(f"[serve-cb]   {kind[4:]} {key}: "
                      f"{g['n_finished']} finished, TTFT p50/p99 "
                      f"{_fmt(g['p50_ttft'], '.0f')}/"
                      f"{_fmt(g['p99_ttft'], '.0f')}, SLO "
                      f"{_fmt(g['slo_attainment'])}")
    if m.get("turns_submitted"):
        print(f"[serve-cb] sessions: {m['turns_submitted']} follow-up "
              f"turns, {m['session_holds']} KV holds, "
              f"{m['idle_swap_outs']} idle swap-outs / "
              f"{m['idle_swap_ins']} swap-ins, "
              f"{m['idle_evictions']} idle evictions")
    if not args.no_prefix_cache:
        print(f"[serve-cb] prefix cache: {m['prefix_hit_pages']} page "
              f"hits, {m['prefill_tokens_skipped']} prompt tokens "
              f"skipped ({m['prefill_tokens_run']} run), "
              f"{m['cow_copies']} COW copies, "
              f"{m['prefix_evictions']} evictions")
    if args.capture:
        from ..core.nvr import demand_miss_reduction_from, run_modes
        rs = {r.label: r for r in run_modes(eng.captured_trace(), 2)}
        ino, nvr = rs["inorder"], rs["nvr"]
        red = demand_miss_reduction_from(rs)
        print(f"[serve-cb] captured-trace NVR: demand-miss reduction "
              f"{100 * red:.1f}% ({ino.demand_misses} -> "
              f"{nvr.demand_misses}), speedup "
              f"{ino.total / nvr.total:.2f}x vs in-order")
    return eng


def main(argv=None):
    setup_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--dense", action="store_true",
                   help="disable the NVR sparse-KV path")
    p.add_argument("--continuous", action="store_true",
                   help="continuous batching on the paged KV allocator")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--rate", type=float, default=0.5,
                   help="Poisson arrivals per scheduler iteration")
    p.add_argument("--pages", type=int, default=0,
                   help="physical KV pages (0 = worst-case sized)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--chunk", type=int, default=16,
                   help="prefill chunk tokens per iteration")
    p.add_argument("--nsb-pages", type=int, default=64)
    p.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                   help="draw prompts over N shared system prompts "
                        "(multi-tenant prefix-reuse workload; 0 = "
                        "independent random prompts)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable cross-request COW prefix caching")
    p.add_argument("--kernel", choices=("xla", "pallas"), default="xla",
                   help="paged decode attention impl (pallas = fused "
                        "runahead kernel; interpret mode off-TPU)")
    p.add_argument("--no-donate", action="store_true",
                   help="disable pool-buffer donation (pre-PR copies)")
    p.add_argument("--no-buckets", action="store_true",
                   help="pad every decode batch to --max-batch")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel shards: KV pools + QKV weights "
                        "shard along the KV-head axis over a (model,) "
                        "mesh (continuous mode; head counts must divide; "
                        "on CPU force devices with XLA_FLAGS=--xla_force"
                        "_host_platform_device_count=N)")
    p.add_argument("--runahead", choices=("off", "imp", "nvr"),
                   default="off",
                   help="online runahead: predict next-iteration TopK "
                        "pages between decode steps and stage them into "
                        "a physical NSB tail (nvr = history + proxy "
                        "scoring; imp = one-step-behind baseline; "
                        "tokens bitwise-identical either way)")
    p.add_argument("--runahead-pages", type=int, default=8,
                   help="staging copies per iteration (runahead budget)")
    p.add_argument("--spill", type=int, default=0, metavar="SLOTS",
                   help="host spill-tier slots (pages): preemption "
                        "swaps KV to a host pool and resume swaps it "
                        "back instead of re-prefilling; 0 = recompute "
                        "policy (the historic behaviour)")
    p.add_argument("--spill-compress", action="store_true",
                   help="int8-compress spilled K/V planes (per-page "
                        "scales via optim.compress; page summaries stay "
                        "exact, so TopK selection survives bitwise)")
    p.add_argument("--expert-pool", choices=("off", "dense", "paged"),
                   default="off",
                   help="MoE expert-weight serving: dense = per-layer "
                        "materialised expert rows; paged = expert FFN "
                        "weights as fixed row-tile pages resolved "
                        "through block tables (MoE archs only; tokens "
                        "bitwise-identical across modes)")
    p.add_argument("--expert-tile-rows", type=int, default=32,
                   help="rows of d_ff per expert weight tile page")
    p.add_argument("--expert-nsb-slots", type=int, default=32,
                   help="expert-pool NSB staging-tail slots (tiles)")
    p.add_argument("--expert-runahead", choices=("off", "router"),
                   default="off",
                   help="router-keyed expert runahead: score the next "
                        "decode batch's tokens against the layer-0 "
                        "router between steps and stage the predicted "
                        "expert tiles into the pool's NSB tail (needs "
                        "--expert-pool paged)")
    p.add_argument("--expert-runahead-pages", type=int, default=16,
                   help="expert tile staging copies per iteration")
    p.add_argument("--executor", choices=("sync", "async"),
                   default="sync",
                   help="step-loop executor: sync = monolithic oracle "
                        "loop; async = pipelined prefill/decode streams "
                        "with double-buffered plans and overlapped "
                        "runahead staging (tokens + logits bitwise-"
                        "identical to sync)")
    p.add_argument("--policy", choices=("fifo", "priority", "slo_fair"),
                   default="fifo",
                   help="scheduling policy: fifo = strict arrival order "
                        "(bitwise-parity default); priority = strict "
                        "classes, FIFO within; slo_fair = per-tenant "
                        "deficit-round-robin admission + SLO-aware "
                        "eviction (serve/policy.py)")
    p.add_argument("--workload", metavar="TRACE.json", default=None,
                   help="trace-driven workload (serve/workload.py "
                        "schema: tenants, priorities, SLOs, multi-turn "
                        "conversations) instead of Poisson arrivals; "
                        "see traces/bursty_multiturn.json")
    p.add_argument("--session-hold", action="store_true",
                   help="hold a finished turn's KV pages for the "
                        "session's next turn (COW prefix reuse across "
                        "turns; multi-turn traces only)")
    p.add_argument("--idle-swap", action="store_true",
                   help="park held session KV in the host spill tier "
                        "during think time (needs --session-hold and "
                        "--spill)")
    p.add_argument("--capture", action="store_true",
                   help="record page traffic and replay through the "
                        "NVR simulator")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the random weights and the workload")
    args = p.parse_args(argv)
    if args.tp > 1 and not args.continuous:
        p.error("--tp needs --continuous (only the paged engine shards)")
    if args.workload and not args.continuous:
        p.error("--workload needs --continuous (trace-driven front door)")
    if args.idle_swap and not args.session_hold:
        p.error("--idle-swap needs --session-hold (nothing to park)")
    if args.idle_swap and args.spill <= 0:
        p.error("--idle-swap needs --spill (the host tier holds the "
                "parked pages)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.continuous:
        return _run_continuous(cfg, params, args)
    return _run_single_batch(cfg, params, args)


if __name__ == "__main__":
    main()
