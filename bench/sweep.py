"""Find the knee of an open-loop cell: the highest arrival rate whose
backlog does not grow over the window.

  python3 bench/sweep.py --workload qwen2-1.5b.chat --seed 5 \
      --seconds 30 --rates 2,3,4,5,6

One process, one set of weights; for each rate a fresh engine serves the
cell's traffic at that rate for ``--seconds`` after the pre-roll.  Each
rate prints one line: the requests due and finished in the window, the
backlog (requests waiting or running) averaged over the window's first
and last quarters, and the TTFT percentiles.  The cell's traffic file
then states 0.8 of the knee as a number; the benchmark never searches.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sweep(workload: str, seed: int, seconds: float, rates: list[float]):
    from bench import harness, spec, traffic, weights

    wl = spec.workload(workload)
    cfg_json = spec.config(wl["config"])
    shape = spec.model_shape(cfg_json)
    cfg = spec.arch_config(cfg_json)
    params = weights.make(shape, seed)
    for rate in rates:
        tr = dict(spec.traffic(wl["traffic"]), rate_per_s=rate)
        sched = traffic.schedule(tr)
        eng = harness.build_engine(cfg, params, tr["engine"], sched.max_len)
        harness.warm_programs(eng, {})
        drv = harness.Driver(eng, harness._no_span)
        loop = harness.OpenLoop(drv, sched, seed, cfg.vocab,
                                time.perf_counter() + tr["preroll_s"])
        w_open = loop.t_open
        while time.perf_counter() - w_open < seconds:
            loop.iterate()
        its = [it for it in drv.iters if it.t0 >= w_open]
        q = max(1, len(its) // 4)
        due = [rid for rid, t in loop.due.items()
               if 0 <= t - w_open < seconds]
        ttft = []
        for rid in due:
            r = eng.requests[rid]
            ttft.append(drv.times[r.first_token_at][1] - loop.due[rid]
                        if r.first_token_at >= 0 else math.inf)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "finished": sum(eng.requests[r].finished_at >= 0 for r in due),
            "backlog_first_quarter": sum(i.backlog for i in its[:q]) / q,
            "backlog_last_quarter": sum(i.backlog for i in its[-q:]) / q,
            "iterations": len(its),
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 0.5),
            "ttft_p90_ms": 1e3 * harness.percentile(ttft, 0.9)}),
            flush=True)
        del eng, drv, loop
        gc.collect()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated arrival rates, requests/s")
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    sweep(args.workload, args.seed, args.seconds,
          [float(r) for r in args.rates.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
