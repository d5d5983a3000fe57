"""Tensor-parallel paged serving: KV-head-sharded pools over a host mesh.

Sharded engines need more than one jax device, and the device count is
fixed at jax init — so every sharded test runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the same pattern
as tests/test_distributed.py).  The meshes come from
``launch.mesh.make_serve_mesh`` (``Auto`` axes) and the steps run under
``jax.shard_map``.

The acceptance bar is *bitwise*: at tp=2 (and tp=4 where head counts
divide) every request's token stream and logits must equal the tp=1
engine's exactly — including across a forced preemption/resume — while
the pools physically shard (1/tp of the KV-head dim per device) and pool
donation keeps consuming buffers.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_dev: int, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


# -- fast, device-free: the serve TP sharding rules ---------------------------

class TestServeParamSpecs:
    def test_qkv_sharded_rest_replicated(self):
        from repro import sharding

        params = {
            "embed": np.zeros((512, 128)),
            "layers": {
                "wq": np.zeros((2, 128, 128)),
                "wk": np.zeros((2, 128, 64)),
                "wv": np.zeros((2, 128, 64)),
                "bq": np.zeros((2, 128)),
                "wo": np.zeros((2, 128, 128)),
                "ln1": np.zeros((2, 128)),
                "wi": np.zeros((2, 128, 256)),
                "wo_mlp": np.zeros((2, 256, 128)),
            },
            "ln_f": np.zeros((128,)),
            "lm_head": np.zeros((128, 512)),
        }
        specs = sharding.serve_param_specs(params)
        lay = specs["layers"]
        assert lay["wq"] == P(None, None, "model")
        assert lay["wk"] == P(None, None, "model")
        assert lay["wv"] == P(None, None, "model")
        assert lay["bq"] == P(None, "model")
        # everything feeding the post-gather (replicated) math stays
        # unsharded: no psum may ever cross shards
        for name in ("wo", "ln1", "wi", "wo_mlp"):
            assert lay[name] == P(), name
        assert specs["embed"] == P()
        assert specs["lm_head"] == P()

    def test_pool_specs_never_shard_the_page_axis(self):
        from repro import sharding

        kv_spec, s_spec = sharding.serve_pool_specs()
        # pools are [L, P, KV, page, D] and [L, P, KV, D]: dim 2 only
        assert kv_spec == P(None, None, "model")
        assert s_spec == P(None, None, "model")


# -- sharded engines (subprocess, forced host devices) ------------------------

_COMMON = """
import numpy as np, jax
from repro.configs import get_config
from repro.models import api
from repro.launch.mesh import make_serve_mesh
from repro.serve.engine import PagedEngine

cfg = get_config("qwen2-1.5b").reduced()
params = api.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(11)
sys_prompts = [rng.integers(1, cfg.vocab, size=12) for _ in range(2)]
work = []
for i in range(6):
    sfx = rng.integers(1, cfg.vocab, size=int(rng.integers(2, 6)))
    work.append((float(i) * 0.5,
                 np.concatenate([sys_prompts[i % 2], sfx]), 5))

def run(mesh=None, n_pages=0, kernel="xla", capture=False,
        runahead="off", spill=0, executor="sync"):
    eng = PagedEngine(cfg, params, max_len=48, n_pages=n_pages,
                      max_batch=4, chunk=8, nsb_pages=32, mesh=mesh,
                      kernel=kernel, capture_trace=capture,
                      runahead=runahead, runahead_pages=8,
                      spill_pages=spill, executor=executor)
    eng.run([(t, p.copy(), g) for t, p, g in work])
    return eng

def assert_bitwise(a_eng, b_eng):
    for rid in a_eng.requests:
        a, b = a_eng.requests[rid], b_eng.requests[rid]
        assert a.out_tokens == b.out_tokens, f"rid {rid} tokens"
        assert np.array_equal(a.last_logits, b.last_logits), \\
            f"rid {rid} logits"
"""


@pytest.mark.slow
def test_tp2_bitwise_sharded_pools_preemption_and_nsb():
    """The tp=2 engine on the shared-prefix fixture: pools physically
    sharded, logits/token streams bitwise-identical to tp=1 — in the
    calm run AND across a forced preemption/resume — with per-shard NSB
    stats rolled up and the captured stream shard-tagged."""
    code = _COMMON + """
from repro.core.nvr.capture import nsb_shard_rollup

base = run()
mesh = make_serve_mesh(2)
tp2 = run(mesh=mesh, capture=True)
assert_bitwise(base, tp2)

# pools physically sharded: each device holds half the KV-head dim
shards = tp2.k_pool.addressable_shards
assert len(shards) == 2
assert [s.data.shape[2] for s in shards] == [cfg.n_kv_heads // 2] * 2
assert tp2.s_pool.addressable_shards[0].data.shape[2] \\
    == cfg.n_kv_heads // 2

# forced preemption under sharding resumes bitwise (vs the calm tp=1)
tight = run(mesh=mesh, n_pages=1 + 9)
assert tight.scheduler.n_preemptions > 0
assert_bitwise(base, tight)

# per-shard NSBs: one rate per shard, traffic shard-tagged end to end
m = tp2.metrics()
assert m["tp"] == 2 and len(m["nsb_shard_hit_rates"]) == 2
assert all(0.0 <= r <= 1.0 for r in m["nsb_shard_hit_rates"])
assert sorted(tp2.recorder.shard_ids()) == [0, 1]
roll = nsb_shard_rollup(tp2.recorder, 32, 2)
assert roll["hits"] + roll["misses"] > 0
assert len(roll["per_shard"]) == 2
print("TP2_OK")
"""
    r = run_py(code, n_dev=2)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP2_OK" in r.stdout


@pytest.mark.slow
def test_tp2_donation_buckets_and_pallas():
    """Step-loop invariants survive sharding: pool donation consumes the
    sharded buffers, decode-trace count stays O(log max_batch), and the
    per-shard Pallas runahead kernel matches the sharded XLA oracle at
    tolerance (same contract as on a single shard)."""
    code = _COMMON + """
import math
mesh = make_serve_mesh(2)

# donation: the jitted step consumes the sharded input pool buffers
eng = PagedEngine(cfg, params, max_len=48, max_batch=4, chunk=8,
                  nsb_pages=32, mesh=mesh)
eng.submit(np.arange(1, 15), max_new_tokens=4)
k0, v0, s0 = eng.k_pool, eng.v_pool, eng.s_pool
eng.step()
assert k0.is_deleted() and v0.is_deleted() and s0.is_deleted()

# bucketing: a full run still compiles <= O(log max_batch) decode traces
full = run(mesh=mesh)
m = full.metrics()
assert m["n_decode_traces"] <= math.ceil(math.log2(4)) + 1
assert m["n_prefill_traces"] == 1

# pallas path per shard vs sharded XLA oracle: tokens equal, logits at
# interpret-mode tolerance
pal = run(mesh=mesh, kernel="pallas")
for rid in full.requests:
    a, b = full.requests[rid], pal.requests[rid]
    assert a.out_tokens == b.out_tokens, f"rid {rid}"
    np.testing.assert_allclose(a.last_logits, b.last_logits,
                               rtol=2e-5, atol=2e-5)
print("TP2_FAST_OK")
"""
    r = run_py(code, n_dev=2)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP2_FAST_OK" in r.stdout


@pytest.mark.slow
def test_tp4_bitwise_where_heads_divide_and_guard():
    """tp=4 on a 4-KV-head config variant is bitwise vs tp=1; tp=4 on
    the stock 2-KV-head config raises the GQA-divisibility error."""
    code = """
import numpy as np, jax, dataclasses
from repro.configs import get_config
from repro.models import api
from repro.launch.mesh import make_serve_mesh
from repro.serve.engine import PagedEngine

cfg2 = get_config("qwen2-1.5b").reduced()
cfg4 = dataclasses.replace(cfg2, n_kv_heads=4)
params = api.init_params(cfg4, jax.random.PRNGKey(0))
rng = np.random.default_rng(3)
work = [(0.0, rng.integers(1, cfg4.vocab, size=int(p)), 4)
        for p in rng.integers(8, 20, size=4)]

def run(mesh=None):
    eng = PagedEngine(cfg4, params, max_len=48, max_batch=4, chunk=8,
                      mesh=mesh)
    eng.run([(t, p.copy(), g) for t, p, g in work])
    return eng

base = run()
tp4 = run(make_serve_mesh(4))
for rid in base.requests:
    a, b = base.requests[rid], tp4.requests[rid]
    assert a.out_tokens == b.out_tokens, f"rid {rid} tokens"
    assert np.array_equal(a.last_logits, b.last_logits), f"rid {rid}"
assert len(tp4.k_pool.addressable_shards) == 4

try:
    PagedEngine(cfg2, api.init_params(cfg2, jax.random.PRNGKey(0)),
                max_len=48, mesh=make_serve_mesh(4))
    raise SystemExit("divisibility guard did not fire")
except ValueError as e:
    assert "divide" in str(e)
print("TP4_OK")
"""
    r = run_py(code, n_dev=4)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP4_OK" in r.stdout


@pytest.mark.slow
def test_tp2_runahead_bitwise_and_staged_tail_sharded():
    """Online runahead composes with tensor parallelism: the staged NSB
    tail rides the KV-head-sharded pools (1/tp of the head dim per
    device, page axis never sharded), the hot-map remap replays inside
    the sharded decode, and tokens/logits stay bitwise-identical to the
    unsharded runahead-off engine — including across a forced
    preemption/resume with staging active."""
    code = _COMMON + """
base = run()                                   # tp=1, runahead off
mesh = make_serve_mesh(2)
tp2 = run(mesh=mesh, runahead="nvr")
assert_bitwise(base, tp2)

# the staging tail extends the *page* axis of the sharded pools: each
# shard still holds half the KV-head dim, over demand + staged pages
shards = tp2.k_pool.addressable_shards
assert len(shards) == 2
assert tp2.k_pool.shape[1] == tp2.n_pages + tp2.nsb_slots
assert [s.data.shape[2] for s in shards] == [cfg.n_kv_heads // 2] * 2

m = tp2.metrics()
assert m["runahead_staged_pages"] > 0
# per-shard staged-tier mirrors: one rate per shard, rollup defined
assert len(m["runahead_shard_hit_rates"]) == 2
assert all(r is None or 0.0 <= r <= 1.0
           for r in m["runahead_shard_hit_rates"])

# forced preemption under sharding + staging resumes bitwise
tight = run(mesh=mesh, n_pages=1 + 9, runahead="nvr")
assert tight.scheduler.n_preemptions > 0
assert_bitwise(base, tight)
assert tight.metrics()["runahead_invalidations"] > 0
print("TP2_RUNAHEAD_OK")
"""
    r = run_py(code, n_dev=2)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP2_RUNAHEAD_OK" in r.stdout


@pytest.mark.slow
def test_tp2_host_spill_swap_resume_bitwise():
    """The host spill tier composes with tensor parallelism: swap-out
    snapshots the *sharded* pools (device->host gather re-assembles the
    full KV-head dim), swap-in restores onto freshly re-pinned sharded
    pools, and tokens/logits stay bitwise-identical to the calm tp=1
    run — including with runahead fetch-back active."""
    code = _COMMON + """
base = run()                                   # tp=1, calm, no spill
mesh = make_serve_mesh(2)
tight = run(mesh=mesh, n_pages=1 + 9, spill=16)
assert tight.scheduler.n_swap_outs > 0
assert tight.scheduler.n_swap_ins == tight.scheduler.n_swap_outs
assert_bitwise(base, tight)

# restored pools stay physically sharded after the host round-trip
shards = tight.k_pool.addressable_shards
assert len(shards) == 2
assert [s.data.shape[2] for s in shards] == [cfg.n_kv_heads // 2] * 2
tight.allocator.check_tier_invariants()
m = tight.metrics()
assert m["tp"] == 2 and m["swap_out_pages"] == m["swap_in_pages"] > 0

# fetch-back under sharding: the spilled queue head resumes in the
# runahead window and its history pages stage onto the sharded tail
ra = run(mesh=mesh, n_pages=1 + 9, spill=16, runahead="nvr")
assert ra.scheduler.n_swap_outs > 0
assert_bitwise(base, ra)
print("TP2_SPILL_OK")
"""
    r = run_py(code, n_dev=2)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP2_SPILL_OK" in r.stdout


@pytest.mark.slow
def test_tp2_prefix_cache_cow_under_sharding():
    """COW prefix caching composes with sharding: cached pages attach,
    COW pool copies replay onto the sharded pools, and logits stay
    bitwise-identical to the uncached tp=1 run."""
    code = _COMMON + """
mesh = make_serve_mesh(2)
base = PagedEngine(cfg, params, max_len=48, max_batch=4, chunk=8,
                   nsb_pages=32, prefix_cache=False)
base.run([(t, p.copy(), g) for t, p, g in work])
tp2 = run(mesh=mesh)                      # prefix cache on (default)
assert tp2.allocator.stats.prefix_hits > 0
assert_bitwise(base, tp2)

# an identical page-aligned prompt pair forces a tail-page COW whose
# bytes must land on the *sharded* pools
rng2 = np.random.default_rng(13)
prompt = rng2.integers(1, cfg.vocab, size=16)
pair = [(0.0, prompt, 4), (4.0, prompt.copy(), 4)]
cow = PagedEngine(cfg, params, max_len=48, max_batch=4, chunk=8,
                  nsb_pages=32, mesh=mesh)
cow.run([(t, p.copy(), g) for t, p, g in pair])
ref = PagedEngine(cfg, params, max_len=48, max_batch=4, chunk=8,
                  nsb_pages=32, prefix_cache=False)
ref.run([(t, p.copy(), g) for t, p, g in pair])
assert cow.stats.cow_page_copies >= 1
for rid in ref.requests:
    assert ref.requests[rid].out_tokens == cow.requests[rid].out_tokens
    assert np.array_equal(ref.requests[rid].last_logits,
                          cow.requests[rid].last_logits)
print("TP2_COW_OK")
"""
    r = run_py(code, n_dev=2)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP2_COW_OK" in r.stdout


@pytest.mark.slow
def test_tp2_async_executor_bitwise():
    """The pipelined executor composes with tensor parallelism: both
    streams dispatch as shard_map jits over the KV-head-sharded pools,
    the overlap-window fetch-back restores onto sharded pools, and the
    async tp=2 engine stays bitwise-identical to the synchronous tp=1
    oracle — calm, under forced preemption, and with runahead + spill."""
    code = _COMMON + """
base = run()                                   # sync, tp=1: the oracle
mesh = make_serve_mesh(2)
pipe = run(mesh=mesh, executor="async")
assert_bitwise(base, pipe)
m = pipe.metrics()
assert m["tp"] == 2 and m["executor"] == "async"
assert m["plan_commits"] > 0 and m["overlap_iterations"] > 0

# forced preemption/resume: draft repairs recover, tokens stay bitwise
tight = run(mesh=mesh, n_pages=1 + 9, executor="async")
assert tight.scheduler.n_preemptions > 0
assert_bitwise(base, tight)

# runahead staging + spill fetch-back in the overlap window, sharded
ra = run(mesh=mesh, n_pages=1 + 9, runahead="nvr", spill=16,
         executor="async")
assert ra.scheduler.n_swap_outs > 0
assert_bitwise(base, ra)
ra.allocator.check_tier_invariants()
print("TP2_ASYNC_OK")
"""
    r = run_py(code, n_dev=2)
    assert r.returncode == 0, (r.stderr[-3000:], r.stdout[-500:])
    assert "TP2_ASYNC_OK" in r.stdout
