"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark) plus the
per-figure headline metrics vs the paper's claims.  Detailed per-row
artifacts (paired CSV + JSON, via the engine sweep runner's writer) land
in benchmarks/results/.

Every completed benchmark additionally writes a committed-format
perf-trajectory artifact ``benchmarks/results/BENCH_<name>.json``:
the headline metrics (non-finite values nulled, keys sorted), the
BENCH_SCALE it ran at, the git sha and the harness wall time — one
stable file per bench that CI uploads and successive commits can diff.

Beyond the paper figures, eleven engineering benches ride along:
  engine_speedup    — full Fig. 5 sweep, event-driven engine vs the frozen
                      seed loop, with bit-exact parity asserted per row
  sweep_grid        — workload x dtype x prefetcher x nsb_kb grid through
                      the sweep runner (CSV + JSON artifacts)
  capture_roundtrip — replay *captured* serving/MoE traffic through the
                      simulator (needs jax; all paper figs are numpy-only)
  serve_bench       — continuous-batching Poisson load vs the single-batch
                      baseline, with multi-tenant capture -> NVR replay
  prefix_bench      — shared-system-prompt load with vs without the COW
                      prefix cache: prefill savings, TTFT, NVR replay
  paged_kernel_bench — the donated + bucketed paged-decode step loop vs
                      the pre-PR path (pool-copy / padded-row
                      elimination), with Pallas paged-kernel parity
                      asserted against the XLA oracle in the same run
  runahead_bench    — online vector runahead off/imp/nvr on shared-prefix
                      Poisson serving: bitwise parity across modes, NSB
                      hit-rate lift + modeled stall gain asserted in-run
  spill_bench       — host KV spill tier under pool oversubscription:
                      preemption as swap-out vs free-and-recompute (+
                      runahead fetch-back, int8 spill), bitwise parity
                      and resume-TTFT improvement asserted in-run
  overlap_bench     — pipelined executor vs the synchronous step loop
                      under mixed long-prefill/steady-decode load:
                      bitwise parity + identical iteration log asserted
                      in-run, TTFT/TPOT split per stream, modeled p99
                      TPOT improvement from stream overlap
  moe_serve_bench   — paged expert-weight streaming on a live MoE serve
                      load: expert tiles as pages, router-keyed runahead
                      staging into the NSB tail — bitwise parity
                      dense=paged=paged+router (and tp=2) asserted
                      in-run, expert-tile hit-rate lift over demand-LRU
  workload_bench    — the scheduling-policy layer on a bursty
                      multi-tenant multi-turn trace: slo_fair beats
                      fifo on SLO attainment + SLO-tenant p99 TTFT,
                      per-(item, turn) tokens/logits bitwise-identical
                      to a never-swapped run (idle-session swap + COW
                      cross-turn reuse are correctness-free), NSB hit
                      rate re-measured under realistic locality

CI gates the deterministic headline metrics against committed baselines
(benchmarks/check_regressions.py; see benchmarks/README.md).

Exit status: 0 only if every requested benchmark ran clean; a benchmark
that raises is reported (traceback + summary line) and the process exits
1 after the remaining benchmarks finish, so CI smoke jobs fail loudly
instead of swallowing a broken figure.  Unknown names exit 2.

  PYTHONPATH=src python -m benchmarks.run            # all figures
  BENCH_SCALE=1.0 PYTHONPATH=src python -m benchmarks.run fig5_latency
  PYTHONPATH=src python -m benchmarks.run engine_speedup serve_bench
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time
import traceback

from .paths import results_dir


def _jsonable(v):
    """Strict-JSON view of a headline value: non-finite numbers become
    null (the committed artifact must diff cleanly and parse under
    ``allow_nan=False``), numpy scalars collapse to Python numbers,
    anything opaque falls back to ``str``."""
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if v is None or isinstance(v, (bool, str, int)):
        return v
    try:
        f = float(v)
    except (TypeError, ValueError):
        return str(v)
    return f if math.isfinite(f) else None


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def _write_bench_json(name: str, headline: dict, us: float,
                      sha: str) -> str:
    """Perf-trajectory artifact: ``BENCH_<name>.json`` in the committed
    format (sorted keys, no NaNs) so successive runs diff cleanly."""
    path = os.path.join(results_dir(), f"BENCH_{name}.json")
    payload = {
        "bench": name,
        "bench_scale": float(os.environ.get("BENCH_SCALE", "0.5")),
        "git_sha": sha,
        "harness_us": round(us, 1),
        "headline": _jsonable(headline),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")
    return path


def main(argv=None) -> int:
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from . import paper_figs
    names = list(argv if argv is not None else sys.argv[1:]) \
        or list(paper_figs.ALL)
    unknown = [n for n in names if n not in paper_figs.ALL]
    if unknown:
        print(f"unknown benchmark(s): {', '.join(unknown)}",
              file=sys.stderr)
        print(f"available: {', '.join(paper_figs.ALL)}", file=sys.stderr)
        return 2
    print("name,us_per_call,derived")
    summaries = []
    failures = []
    sha = _git_sha()
    for name in names:
        fn = paper_figs.ALL[name]
        t0 = time.perf_counter()
        try:
            rows, headline = fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"{name},FAILED,")
            continue
        us = (time.perf_counter() - t0) * 1e6
        derived = ";".join(f"{k}={v:.4g}" if isinstance(v, float)
                           else f"{k}={v}" for k, v in headline.items()
                           if k != "paper")
        print(f"{name},{us:.0f},{derived}")
        _write_bench_json(name, headline, us, sha)
        summaries.append((name, headline))
    print("\n=== headline metrics vs paper claims ===")
    for name, h in summaries:
        print(f"[{name}]")
        for k, v in h.items():
            if k == "paper":
                print(f"    paper claim : {v}")
            else:
                print(f"    {k:38s} {v:.4g}" if isinstance(v, float)
                      else f"    {k:38s} {v}")
    if failures:
        print(f"\nFAILED: {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
