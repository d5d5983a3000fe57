"""Smoke test on the chip: the paged serving engine at published widths.

  python chip_smoke.py             # one TPU: qwen2-1.5b, XLA then Pallas
  python chip_smoke.py --chips 4   # four TPUs: llama3.2-1b, --tp 4 vs --tp 1

Every phase drives ``repro.launch.serve.main`` -- the entry point a user
calls -- with ``--continuous`` and no ``--reduced``: all layers at the
model's published widths, bf16, random weights from ``--seed 0``.  The
workload is 8 Poisson requests with 256-512 token prompts and up to 32
new tokens each (``--max-batch 8``).  Contexts stay under 34 pages of 16
tokens, inside the TopK cover of 64 pages, so decode attention is exact
and the phases compute the same function.

One chip (no options):
  1. qwen2-1.5b with ``--kernel xla``;
  2. the same requests with ``--kernel pallas`` (the Mosaic-compiled
     paged kernel).  Every request must finish, every logit be finite,
     each request's prefill logits agree with phase 1 (PREFILL_TOL) and
     the final logits of requests whose tokens agree throughout stay
     within DECODE_TOL.  The token agreement rate is printed;
  3. the compiled kernel against the XLA attention it replaces, on one
     layer at qwen2-1.5b's widths (KERNEL_TOL).

Four chips (``--chips 4``), and nothing else: llama3.2-1b (32/8 heads, so
tp=4 divides) with ``--tp 4`` against ``--tp 1`` on the same requests.
The tp=4 engine's parameters and pools must span all four devices.  It
prints whether the logits are bitwise equal, the max |dlogit| and the
token agreement, and holds them to DECODE_TOL.

Every phase runs in this one process: it holds the chips, so no child
may use them.  The script exits non-zero -- and never prints its last
line -- when JAX finds no TPU, when it runs outside a checkout of this
repo, or when any phase raises or fails a check.  Its last line on
stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

WORKLOAD = ["--continuous", "--requests", "8", "--prompt-len", "512",
            "--gen", "32", "--max-batch", "8", "--chunk", "128",
            "--seed", "0"]

# bf16 keeps 8 significant bits: one step (ulp) of a value is 2**-8 to
# 2**-7 of it, and rounding moves a value by at most 2**-8 of itself.
BF16_STEP = 2.0 ** -8
# Prefill logits: the kernel switch changes decode attention only, so both
# phases run the same prefill program on the same inputs.  A difference
# beyond 2**-8 of the logit scale means one phase computed something
# else.
PREFILL_TOL = BF16_STEP
# Decode logits: the two attention paths round differently (XLA's f32
# einsums vs the kernel's streamed f32 online softmax, each attention
# output rounded to bf16).  Allow 2**-8 of the logit scale per layer the
# difference passes through.  A kernel that reads the wrong page or head
# is off by the logit scale itself, far outside this bound.
DECODE_TOL_PER_LAYER = BF16_STEP
# One attention call, kernel against XLA: both read the same bf16 pages
# and accumulate in f32, and each output is rounded to bf16 once, so they
# differ by rounding order and that last rounding: about one step of the
# largest outputs, which 2 * 2**-8 of the output scale always covers.  A
# wrong page or head is off by the scale.
KERNEL_TOL = 2 * BF16_STEP


class CompileStats:
    """Seconds jax spent lowering and compiling (or loading a compiled
    program from the persistent cache), and that cache's lookups and
    hits, summed from jax's own monitoring events.  Tracing is left out:
    a jit traced inside another one reports its own trace time within
    its caller's, so those events overlap."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.lookups = self.hits = 0

    def listen(self) -> "CompileStats":
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.lookups, self.hits


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def run_phase(name: str, argv: list, stats: CompileStats):
    """Serve once through ``repro.launch.serve.main``; check that every
    request finished with finite logits; print the phase's times.
    Returns the engine: callers keep what they compare and let the rest
    go, so no two phases' weights share the device."""
    import jax
    import numpy as np

    from repro.launch import serve

    gc.collect()       # the engines of earlier phases hold reference cycles
    s0, n0, h0 = stats.snapshot()
    t0 = time.perf_counter()
    eng = serve.main(argv)
    wall = time.perf_counter() - t0
    comp = stats.seconds - s0
    m = eng.metrics()
    reqs = list(eng.requests.values())
    if m["n_finished"] != len(reqs) or not reqs:
        fail(f"{name}: {m['n_finished']}/{len(reqs)} requests finished")
    for r in reqs:
        if len(r.out_tokens) != r.max_new_tokens:
            fail(f"{name}: rid {r.rid} emitted {len(r.out_tokens)} of "
                 f"{r.max_new_tokens} tokens")
        for lg in (r.first_logits, r.last_logits):
            if lg is None or not np.isfinite(lg).all():
                fail(f"{name}: rid {r.rid} has non-finite logits")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"{name}: {m['n_finished']} requests, {m['tokens_out']} tokens "
        f"out, {m['iterations']} iterations, wall {wall:.3f} s = compile "
        f"{comp:.3f} s + rest {wall - comp:.3f} s; persistent cache "
        f"{stats.hits - h0} hits of {stats.lookups - n0} lookups; decode "
        f"traces {m['n_decode_traces']}, kernel {eng.kernel}, tp "
        f"{eng.tp}; device 0 peak_bytes_in_use "
        f"{mem.get('peak_bytes_in_use', 'n/a')}")
    return eng


def compare(name: str, ref: dict, got: dict, n_layers: int):
    """Hold the requests ``got`` to ``ref`` (rid -> Request, the same
    workload): prefill logits within PREFILL_TOL, final logits of
    token-identical requests within n_layers x DECODE_TOL_PER_LAYER,
    both relative to the logit scale."""
    import numpy as np

    agree = total = 0
    d_first = d_last = 0.0
    bitwise = True
    scale = 0.0
    for rid, a in ref.items():
        b = got[rid]
        ta, tb = a.out_tokens, b.out_tokens
        agree += sum(x == y for x, y in zip(ta, tb))
        total += len(ta)
        scale = max(scale, float(np.abs(a.first_logits).max()))
        d_first = max(d_first, float(np.abs(a.first_logits
                                            - b.first_logits).max()))
        bitwise &= ta == tb and np.array_equal(a.first_logits,
                                               b.first_logits)
        if ta == tb:
            d_last = max(d_last, float(np.abs(a.last_logits
                                              - b.last_logits).max()))
            bitwise &= np.array_equal(a.last_logits, b.last_logits)
    if scale <= 0.0:
        fail(f"{name}: logits are all zero")
    rate = agree / total
    decode_tol = n_layers * DECODE_TOL_PER_LAYER
    log(f"{name}: logit scale {scale:.6g}; prefill max|dlogit| "
        f"{d_first:.6g} (bound {PREFILL_TOL * scale:.6g}); final max"
        f"|dlogit| over token-identical requests {d_last:.6g} (bound "
        f"{decode_tol * scale:.6g}); bitwise {bitwise}; token agreement "
        f"{agree}/{total} = {rate:.4f}")
    if d_first > PREFILL_TOL * scale:
        fail(f"{name}: prefill logits differ by {d_first:.6g}")
    if d_last > decode_tol * scale:
        fail(f"{name}: final logits differ by {d_last:.6g}")


def kernel_parity(cfg, rows: int = 8, topk: int = 64) -> None:
    """The paged kernel against ``attend_pages_paged``, the XLA attention
    it replaces, on one layer at ``cfg``'s widths: random bf16 queries
    and pools, each row attending to ``topk`` pages of its own in a
    per-head order, with its frontier inside its last page."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_decode_attn
    from repro.models import sparse_attention

    rng = np.random.default_rng(0)
    kv, g, page = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.kv_page
    pool = (1 + rows * topk, kv, page, cfg.hd)
    kp, vp = (jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(rows, kv, g, cfg.hd)), jnp.bfloat16)
    idx = np.argsort(rng.random((rows, kv, topk)), axis=-1).astype(np.int32)
    phys = 1 + np.arange(rows, dtype=np.int32)[:, None, None] * topk + idx
    pos = rng.integers((topk - 1) * page, topk * page, size=rows,
                       dtype=np.int32)
    args = (jnp.asarray(idx), jnp.asarray(phys), jnp.asarray(pos))
    want = np.asarray(sparse_attention.attend_pages_paged(
        q, kp, vp, *args, page), np.float32)
    got = np.asarray(paged_decode_attn(
        args[1], args[0], args[2], q, kp, vp, page_size=page), np.float32)
    scale = float(np.abs(want).max())
    diff = float(np.abs(got - want).max())
    log(f"kernel vs xla attention at R {rows}, KV {kv}, G {g}, D {cfg.hd},"
        f" page {page}, K {topk}: max|dout| {diff:.6g} (bound "
        f"{KERNEL_TOL * scale:.6g}, output scale {scale:.6g})")
    if not np.isfinite(got).all() or diff > KERNEL_TOL * scale:
        fail(f"paged kernel differs from the XLA attention by {diff:.6g}")


def one_chip(stats: CompileStats) -> None:
    from repro.configs import get_config

    arch = "qwen2-1.5b"
    cfg = get_config(arch)
    xla = run_phase("phase 1 qwen2-1.5b xla",
                    ["--arch", arch, *WORKLOAD, "--kernel", "xla"],
                    stats).requests
    # main() has checked for a TPU, so the kernel compiles through Mosaic
    # (kernels.ops interprets off-TPU only)
    pal = run_phase("phase 2 qwen2-1.5b pallas",
                    ["--arch", arch, *WORKLOAD, "--kernel", "pallas"],
                    stats).requests
    compare("pallas vs xla", xla, pal, cfg.n_layers)
    kernel_parity(cfg)


def four_chips(stats: CompileStats) -> None:
    import jax

    from repro.configs import get_config

    arch = "llama3.2-1b"
    n_layers = get_config(arch).n_layers
    base = run_phase("tp=1 llama3.2-1b",
                     ["--arch", arch, *WORKLOAD, "--tp", "1"],
                     stats).requests
    tp4 = run_phase("tp=4 llama3.2-1b",
                    ["--arch", arch, *WORKLOAD, "--tp", "4"], stats)
    devs = set(jax.devices()[:4])
    placed = [("k_pool", tp4.k_pool), ("v_pool", tp4.v_pool),
              ("s_pool", tp4.s_pool)] + [
        (jax.tree_util.keystr(path), leaf) for path, leaf in
        jax.tree_util.tree_leaves_with_path(tp4.params)]
    for what, x in placed:
        if set(x.sharding.device_set) != devs:
            fail(f"tp=4 {what} lives on devices "
                 f"{sorted(d.id for d in x.sharding.device_set)}")
    log(f"tp=4: all {len(placed)} parameter and pool arrays span devices "
        f"{sorted(d.id for d in devs)}")
    # nothing of the served model may stay behind on one device: the
    # tp=1 engine is gone, and the tp=4 engine's arrays span the mesh
    gc.collect()
    left = [x for x in jax.live_arrays()
            if len(x.sharding.device_set) == 1 and x.nbytes >= 2 ** 20]
    if left:
        fail(f"{len(left)} arrays of >= 1 MiB left on one device, "
             f"{sum(x.nbytes for x in left)} bytes")
    for d in sorted(devs, key=lambda d: d.id):
        st = d.memory_stats() or {}
        log(f"device {d.id}: bytes_in_use {st.get('bytes_in_use', 'n/a')}, "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use', 'n/a')}")
    compare("tp=4 vs tp=1", base, tp4.requests, n_layers)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 = only the tensor-parallel path on four chips")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no src/repro beside {__file__}: run from a checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    stats = CompileStats().listen()
    log(f"device_kind {devices[0].device_kind}, {len(devices)} device(s), "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(stats)
    else:
        one_chip(stats)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s; compile "
        f"{stats.seconds:.3f} s, persistent cache {stats.hits} hits of "
        f"{stats.lookups} lookups")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
