"""Run one cell of the chip benchmark once.

  python3 bench/run.py --workload qwen1.5-1.8b.docqa --seed 7 \
      --seconds 30 --trace 0

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` and found under ``bench/`` by name (see
``bench/spec.py``).  The run exits non-zero and prints no result when
JAX finds no TPU, fewer chips than the cell asks for, or a device_kind
missing from ``bench/peaks.json``.

Output: a ``SETUP`` line (the parts of set-up), a ``WORK`` line (what
the window did), then, as the last line of standard output, the result
object; the numbers that decide ``correct`` go to standard error too.
``--trace 1`` reports the per-layer metrics instead of the end-to-end
ones.  ``--control 1`` puts the float8 control in the program's place:
the limits judge its first-ranked tokens and its logits at the served
positions instead of the program's, so the run has to come out not
correct (for setting the limits; the benchmark's own runs leave it off).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program to measure: {ROOT}/src/repro is missing",
              file=sys.stderr)
        return 2
    # the compile cache lives at a fixed path inside the checkout, so
    # only a cell's first run there compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                control=bool(args.control), t_start=T_PROCESS)
    return 0


if __name__ == "__main__":
    sys.exit(main())
