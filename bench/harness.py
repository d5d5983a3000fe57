"""One run of one cell: set-up, the measured window, the check, the result.

``run()`` builds the engine as ``repro.launch.serve --continuous`` does,
with the cell's flags, on weights drawn from the seed; warms every
program the cell uses; fills what the traffic needs before it (shared
documents, a pre-roll of traffic); then drives ``PagedEngine.submit``
and ``PagedEngine.step`` for ``seconds`` seconds, recording every
iteration.  After the window it reads the device's memory peak, frees
the engine and compares a sample of the served tokens with the plain
reference.
"""

from __future__ import annotations

import contextlib
import gc
import hashlib
import json
import math
import sys
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from . import spec, traffic, weights
from . import trace as trace_mod
from .metrics import _costs


def percentile(xs, q: float) -> float | None:
    """Nearest-rank (ceil-rank) percentile, numpy's ``inverted_cdf``; None
    for no samples.  ``inf`` entries are samples that never came."""
    xs = sorted(xs)
    if not xs:
        return None
    return float(xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))])


class CompileCounter:
    """Names of the programs jax traces, from its monitoring events: each
    is a compile or a load from the persistent cache."""

    def __init__(self) -> None:
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **kw) -> None:
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.names.append(str(kw.get("fun_name", "?")))


@dataclass
class Iteration:
    tick: int
    t0: float                  # host clock before submitting
    t1: float                  # host clock after the step returned
    rows: int = 0              # decode rows
    decode_pos: list = field(default_factory=list)
    prefill: list = field(default_factory=list)      # (start, n_tokens)
    tokens_out: int = 0
    prefix_hit_pages: int = 0
    cow_copies: int = 0        # shared prefix pages copied on write
    preemptions: int = 0
    evictions: int = 0
    backlog: int = 0           # requests waiting or running after it


class Driver:
    """Steps the engine and records what each iteration did."""

    def __init__(self, eng, span) -> None:
        self.eng = eng
        self.span = span
        self.live: dict[int, object] = {}
        self.iters: list[Iteration] = []
        self.times: dict[int, tuple[float, float]] = {}

    def submit(self, prompt, max_new: int, arrival: float) -> int:
        rid = self.eng.submit(prompt, max_new, arrival=arrival)
        self.live[rid] = self.eng.requests[rid]
        return rid

    def step(self, t0: float) -> Iteration:
        eng = self.eng
        st, al, sc = eng.stats, eng.allocator.stats, eng.scheduler
        before = {rid: (r.computed, r.admitted_at < 0)
                  for rid, r in self.live.items()}
        c = (st.tokens_out, st.decode_tokens, st.prefill_tokens,
             al.prefix_hits, sc.n_preemptions, al.prefix_evictions,
             al.cow_copies)
        with self.span("bench.step"):
            eng.step()
        t1 = time.perf_counter()
        with self.span("bench.count"):
            it = Iteration(tick=eng.now, t0=t0, t1=t1)
            it.tokens_out = st.tokens_out - c[0]
            it.prefix_hit_pages = al.prefix_hits - c[3]
            it.preemptions = sc.n_preemptions - c[4]
            it.evictions = al.prefix_evictions - c[5]
            it.cow_copies = al.cow_copies - c[6]
            for rid, (c0, waiting) in before.items():
                r = self.live[rid]
                if waiting and r.admitted_at >= 0:
                    c0 = r.cached_tokens      # fast-forwarded at admission
                c1 = r.computed
                if c1 <= c0:
                    continue
                if c0 < r.prompt_len:
                    it.prefill.append((c0, c1 - c0))
                else:
                    it.decode_pos.append(c0)
            it.rows = len(it.decode_pos)
            if (it.rows != st.decode_tokens - c[1]
                    or sum(n for _, n in it.prefill)
                    != st.prefill_tokens - c[2]):
                raise RuntimeError("per-request deltas disagree with the "
                                   "engine's token counters")
            for rid in [rid for rid, r in self.live.items()
                        if r.finished_at >= 0]:
                del self.live[rid]
            it.backlog = len(self.live)
        self.iters.append(it)
        self.times[it.tick] = (t0, t1)
        return it


def _no_span(_name):
    return contextlib.nullcontext()


def _trace_span(name):
    return jax.profiler.TraceAnnotation(name)


# -- set-up -----------------------------------------------------------------

def build_engine(cfg, params, eng_flags: dict, max_len: int):
    from repro.serve.engine import PagedEngine

    page = cfg.kv_page
    return PagedEngine(
        cfg, params, max_len=-(-max_len // page) * page,
        n_pages=eng_flags.get("pages", 0),
        max_batch=eng_flags["max_batch"], chunk=eng_flags["chunk"],
        prefix_cache=eng_flags.get("prefix_cache", True),
        kernel=eng_flags["kernel"], runahead=eng_flags["runahead"])


def warm_programs(eng, setup: dict) -> None:
    """Run each program the cell's traffic can reach once, on padding
    rows whose writes land on the reserved scratch page 0: every decode
    row bucket up to ``max_batch`` and the one prefill chunk."""
    buckets = eng.row_buckets or (eng.max_batch,)
    for rb in buckets:
        t = time.perf_counter()
        z = np.zeros((rb,), np.int32)
        out = eng._decode(eng.params, eng.k_pool, eng.v_pool, eng.s_pool,
                          jax.numpy.asarray(z), jax.numpy.asarray(z),
                          jax.numpy.asarray(np.zeros((rb, eng.n_logical),
                                                     np.int32)))
        logits, eng.k_pool, eng.v_pool, eng.s_pool, sel = out
        np.asarray(logits)
        np.asarray(sel[0])      # the commit reads layer 0's selection
        setup[f"decode_{rb}_s"] = time.perf_counter() - t
    t = time.perf_counter()
    logits, eng.k_pool, eng.v_pool, eng.s_pool = eng._prefill(
        eng.params, eng.k_pool, eng.v_pool, eng.s_pool,
        jax.numpy.asarray(np.zeros((eng.chunk,), np.int32)), np.int32(0),
        np.int32(1), jax.numpy.asarray(np.zeros((eng.n_logical,),
                                                np.int32)))
    np.asarray(logits)
    setup["prefill_s"] = time.perf_counter() - t


def prefill_documents(drv: Driver, sched, seed: int, vocab: int) -> None:
    """Serve each shared document once (one output token), so its whole
    pages sit in the prefix index before any question comes."""
    for d in range(sched.n_docs):
        drv.submit(traffic.document(seed, d, sched, vocab), 1,
                   arrival=drv.eng.now)
    while drv.live:
        drv.step(time.perf_counter())


# -- the two loops ------------------------------------------------------------

class ClosedLoop:
    """``workers`` callers; worker w's k-th request is request k*W + w, and
    it is submitted in the iteration after the worker's previous answer
    ended.  The schedule therefore follows engine iterations only."""

    def __init__(self, drv: Driver, sched, seed: int, vocab: int) -> None:
        self.drv, self.sched, self.seed, self.vocab = drv, sched, seed, vocab
        self.w = int(sched.spec["workers"])
        self.next_k = [0] * self.w
        self.current: list[int | None] = [None] * self.w
        self.index: dict[int, int] = {}     # rid -> request index

    def iterate(self) -> Iteration:
        t0 = time.perf_counter()
        with self.drv.span("bench.submit"):
            for w in range(self.w):
                rid = self.current[w]
                if rid is not None and \
                        self.drv.eng.requests[rid].finished_at < 0:
                    continue
                i = self.next_k[w] * self.w + w
                self.next_k[w] += 1
                _, _, n_out = self.sched.request(i)
                p = traffic.prompt(self.seed, i, self.sched, self.vocab)
                rid = self.drv.submit(p, n_out, arrival=self.drv.eng.now)
                self.current[w] = rid
                self.index[rid] = i
        return self.drv.step(t0)


class OpenLoop:
    """Requests due at fixed offsets from the window's opening, submitted
    at the first iteration that starts after they are due."""

    def __init__(self, drv: Driver, sched, seed: int, vocab: int,
                 t_open: float) -> None:
        self.drv, self.sched, self.seed, self.vocab = drv, sched, seed, vocab
        self.t_open = t_open
        self.i = 0
        self.due: dict[int, float] = {}     # rid -> due, host clock
        self.index: dict[int, int] = {}

    def next_due(self) -> float:
        return self.t_open + float(self.sched.arrival_s[self.i])

    def iterate(self) -> Iteration | None:
        t0 = time.perf_counter()
        with self.drv.span("bench.submit"):
            while self.next_due() <= t0:
                _, _, n_out = self.sched.request(self.i)
                p = traffic.prompt(self.seed, self.i, self.sched, self.vocab)
                rid = self.drv.submit(p, n_out, arrival=self.drv.eng.now)
                self.due[rid] = self.next_due()
                self.index[rid] = self.i
                self.i += 1
            if not self.drv.eng.scheduler.has_work:
                # idle: wait for the next arrival without stepping
                time.sleep(max(0.0, min(self.next_due() - t0, 0.05)))
                return None
        return self.drv.step(t0)


# -- the result ---------------------------------------------------------------

def _hist(values) -> dict:
    out: dict[str, int] = {}
    for v in values:
        out[str(v)] = out.get(str(v), 0) + 1
    return dict(sorted(out.items(), key=lambda kv: int(kv[0])))


def work_line(iters: list[Iteration], compiled: list[str]) -> dict:
    """What the window did, for comparing runs: counts, the histogram of
    decode rows, and a digest of the per-iteration work every 50
    iterations (two runs agree up to the shorter one's last digest)."""
    h = hashlib.sha1()
    digests = []
    for k, it in enumerate(iters, 1):
        h.update(repr((it.rows, tuple(it.prefill), it.tokens_out,
                       it.prefix_hit_pages, it.cow_copies)).encode())
        if k % 50 == 0:
            digests.append(h.hexdigest()[:8])
    return {
        "iterations": len(iters),
        "rows_hist": _hist(it.rows for it in iters),
        "prefill_tokens": sum(n for it in iters for _, n in it.prefill),
        "prefix_hit_pages": sum(it.prefix_hit_pages for it in iters),
        "cow_copies": sum(it.cow_copies for it in iters),
        "prefix_evictions": sum(it.evictions for it in iters),
        "preemptions": sum(it.preemptions for it in iters),
        "compiles_in_window": len(compiled),
        "compiled_in_window": sorted(set(compiled)),
        "tokens_out": sum(it.tokens_out for it in iters),
        "digest_every_50": digests,
    }


def logit_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest |got - ref| over a row, over the row's RMS; the worst row."""
    rms = np.sqrt(np.mean(np.square(ref), axis=-1))
    return float(np.max(np.abs(got - ref).max(axis=-1) / rms))


def sample_requests(reqs: list, n: int, seed: int) -> list:
    """The request with the most served tokens; the one that computed
    the fewest of its prompt's tokens itself, where any was served from
    the prefix cache (a whole-prompt hit, whose last page was copied on
    write, where there is one); and others drawn from the seed, n in
    all."""
    reqs = sorted(reqs, key=lambda r: r.rid)
    if not reqs:
        return []
    fixed = [max(reqs, key=lambda r: (len(r.out_tokens), r.prompt_len))]
    cached = [r for r in reqs if r.cached_tokens > 0 and r is not fixed[0]]
    if cached and n > 1:
        fixed.append(min(cached,
                         key=lambda r: r.prompt_len - r.cached_tokens))
    rest = [r for r in reqs if all(r is not f for f in fixed)]
    rng = np.random.default_rng([seed % 2 ** 63, 7])
    pick = rng.choice(len(rest), size=min(n - len(fixed), len(rest)),
                      replace=False)
    return fixed + [rest[int(k)] for k in sorted(pick)]


def run(workload: str, seed: int, seconds: float, trace: bool,
        control: bool = False, require_tpu: bool = True,
        overrides: dict | None = None, t_start: float | None = None,
        out=sys.stdout, err=sys.stderr) -> dict:
    """One run of a cell.  Returns the result object it printed last.

    ``overrides`` (tests) may replace ``config``, ``traffic``, ``checks``
    and ``peaks``.  With ``control`` the check judges the float8 control
    of the reference (``compare(control=True)``) in the program's place.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    overrides = overrides or {}
    bench = spec.benchmark()
    wl = spec.workload(workload, bench)
    cfg_json = overrides.get("config") or spec.config(wl["config"])
    tr = overrides.get("traffic") or spec.traffic(wl["traffic"])
    checks = overrides.get("checks") or spec.checks(workload)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: jax found {dev.platform!r} devices")
    if len(devs) < wl["chips"]:
        raise SystemExit(f"cell needs {wl['chips']} chips, jax found "
                         f"{len(devs)}")
    peaks = overrides.get("peaks") or spec.peaks(dev.device_kind)
    shape = spec.model_shape(cfg_json)
    cfg = spec.arch_config(cfg_json)
    sched = traffic.schedule(tr)
    counter = CompileCounter()
    setup: dict[str, float] = {"init_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    params = weights.make(shape, seed)
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = build_engine(cfg, params, tr["engine"], sched.max_len)
    jax.block_until_ready((eng.k_pool, eng.v_pool, eng.s_pool))
    setup["engine_s"] = time.perf_counter() - t
    warm_programs(eng, setup)
    span = _trace_span if trace else _no_span
    drv = Driver(eng, span)
    t = time.perf_counter()
    prefill_documents(drv, sched, seed, cfg.vocab)
    setup["documents_s"] = time.perf_counter() - t

    t = time.perf_counter()
    if tr["loop"] == "closed":
        loop = ClosedLoop(drv, sched, seed, cfg.vocab)
        for _ in range(int(tr["warm_iterations"])):
            loop.iterate()
    else:
        t_open = time.perf_counter() + float(tr["preroll_s"])
        loop = OpenLoop(drv, sched, seed, cfg.vocab, t_open)
        while time.perf_counter() < t_open:
            loop.iterate()
    setup["preroll_s"] = time.perf_counter() - t
    n_before = len(drv.iters)
    traces_before = len(counter.names)
    setup_s = time.perf_counter() - t_start

    tdir = None
    if trace:
        tdir = trace_mod.start()
    w_open = time.perf_counter() if tr["loop"] == "closed" else loop.t_open
    with span("bench.window"):
        while True:
            it = loop.iterate()
            if it is not None and it.t1 - w_open >= seconds:
                break
            if it is None and time.perf_counter() - w_open >= seconds:
                break
    if trace:
        jax.block_until_ready((eng.k_pool, eng.v_pool))
        trace_mod.stop()
    w_iters = drv.iters[n_before:]
    w_close = w_iters[-1].t1 if w_iters else time.perf_counter()
    window_s = w_close - w_open
    compiled_in_window = counter.names[traces_before:]

    # -- end-to-end numbers ---------------------------------------------------
    reqs = [eng.requests[rid] for rid in loop.index]
    ticks = {it.tick for it in w_iters}
    metrics: dict[str, dict] = {}
    extra: dict[str, float] = {}
    if tr["loop"] == "closed":
        tokens = sum(it.tokens_out for it in w_iters)
        metrics["output_tok_s"] = {"value": tokens / window_s,
                                   "unit": "tokens/s"}
        # a request submitted before iteration k carries arrival k - 1
        attempted = [r for r in reqs if r.arrival + 1 in ticks]
        done = [r for r in reqs if r.finished_at in ticks]
        queue = []
    else:
        times = drv.times
        attempted = [r for r in reqs
                     if 0.0 <= loop.due[r.rid] - w_open < window_s]
        ttft, queue = [], []
        for r in attempted:
            if r.first_token_at >= 0 and \
                    times[r.first_token_at][1] <= w_close:
                ttft.append(times[r.first_token_at][1] - loop.due[r.rid])
            else:
                ttft.append(math.inf)
            if r.admitted_at >= 0 and times[r.admitted_at][0] <= w_close:
                queue.append(times[r.admitted_at][0] - loop.due[r.rid])
            else:
                queue.append(math.inf)
        gaps = []
        for r in reqs:
            tt = [times[k][1] for k in r.token_ticks]
            gaps += [b - a for a, b in zip(tt, tt[1:])
                     if w_open <= b <= w_close]
        metrics["ttft_p90_ms"] = {"value": 1e3 * percentile(ttft, 0.90),
                                  "unit": "ms"}
        metrics["itl_p95_ms"] = {"value": 1e3 * percentile(gaps, 0.95),
                                 "unit": "ms"}
        extra = {"ttft_p50_ms": 1e3 * percentile(ttft, 0.50),
                 "itl_p50_ms": 1e3 * percentile(gaps, 0.50),
                 "n_ttft": len(ttft), "n_itl": len(gaps),
                 "late_p90_ms": 1e3 * percentile(
                     [times[r.arrival + 1][0] - loop.due[r.rid]
                      for r in attempted if r.arrival + 1 in times], 0.90)}
        done = [r for r in attempted if r.finished_at >= 0]
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    work = work_line(w_iters, compiled_in_window)
    work.update(extra)
    print("SETUP " + json.dumps(setup), file=out, flush=True)
    print("WORK " + json.dumps(work), file=out, flush=True)

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": wl["chips"],
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    # -- per-layer numbers (traced run) -------------------------------------
    breakdown = None
    if trace:
        red = trace_mod.reduce(trace_mod.load(tdir))
        trace_mod.cleanup(tdir)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx = _costs.Context(iters=w_iters, window_s=window_s, trace=red,
                             shape=shape, peaks=peaks, queue_s=queue,
                             requests=attempted)
        metrics = {}
        for m in spec.metrics_for(workload, "per_layer", bench):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- correctness, once the window's state is freed -----------------------
    n_attempted = len(attempted)
    need = int(checks["sample_requests"])
    t = time.perf_counter()
    while len(done) < need and drv.live and time.perf_counter() - t < 60:
        # too few answers finished inside the window: let the ones in
        # flight finish (no new requests), so the check does not depend
        # on the program's speed
        it = drv.step(time.perf_counter())
        done += [eng.requests[rid] for rid in loop.index
                 if eng.requests[rid].finished_at == it.tick]
    sample = [(np.asarray(r.prompt), np.asarray(r.out_tokens),
               np.stack([r.first_logits, r.last_logits]))
              for r in sample_requests(done, need, seed)]
    del eng, drv, loop, reqs, attempted, done, params
    gc.collect()
    t = time.perf_counter()
    ref = spec.reference(cfg_json["reference"])
    params = weights.make(shape, seed)
    # the numbers compared are those the cell's checks file gives a limit
    lim = {k: float(checks[k]["limit"]) for k in ("logit_gap", "logit_err")
           if k in checks}
    readings = dict.fromkeys(lim, 0.0)
    served = 0
    for prompt, out_toks, prog_ends in sample:
        g = ref.compare(params, shape, prompt, out_toks, control=control)
        served += len(out_toks)
        if control:
            # the control stands in the program's place: its first-ranked
            # tokens and its logits are what the limits judge
            got = {"logit_gap": g["control_gap"].max(),
                   "logit_err": logit_err(g["control_ends"], g["ends"])}
        else:
            got = {"logit_gap": g["gap"].max(),
                   "logit_err": logit_err(prog_ends, g["ends"])}
        for k in readings:
            readings[k] = max(readings[k], float(got[k]))
    compared = {k: {"value": v, "limit": lim[k]} for k, v in readings.items()}
    compared["served_tokens"] = {"value": served,
                                 "limit": int(checks["min_served"])}
    correct = (len(sample) == need and served >= int(checks["min_served"])
               and all(compared[k]["value"] <= lim[k] for k in lim))

    print("CHECK " + json.dumps({"seconds": time.perf_counter() - t,
                                 "requests": len(sample)}), file=out)
    result = {"correct": correct, "attempted": n_attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = compared
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result
