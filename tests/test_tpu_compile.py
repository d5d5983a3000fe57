"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler ships with jax, so the serve path's kernel and whole
steps compile here at published widths against a ``v5e:2x2`` topology
description.  That catches what the Pallas interpreter cannot: tiling
the Mosaic compiler refuses, fast memory a kernel may not use, and a
step that does not fit the chip's 16 GiB.  Nothing runs, so these say
nothing about results or times.

The topology is described inside a fixture, never while this module is
imported: only one process at a time may load the TPU library, and under
pytest-xdist every worker imports every test file.  Each compile turns
the persistent compilation cache off, since an entry written for a
described chip cannot be read back without one.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_decode_attn
from repro.models import api
from repro.serve.engine import _paged_decode_fn, _paged_prefill_fn

HBM_BYTES = 16 * 1024 ** 3       # one TPU v5e
ROWS, TOPK_PAGES, N_PAGES = 8, 64, 1 + 8 * 64   # 8 rows, full TopK cover


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kv,g,d", [
    (2, 6, 128),    # qwen2-1.5b: 12 query / 2 KV heads, head_dim 128
    (2, 4, 64),     # llama3.2-1b at tp=4: one shard's 2 of 8 KV heads
], ids=["qwen2-1.5b", "llama3.2-1b-tp4-shard"])
def test_paged_decode_attn_compiles(one_chip, no_persistent_cache, kv, g,
                                    d):
    page = 16
    sel = _on(one_chip, (ROWS, kv, TOPK_PAGES), jnp.int32)
    pool = _on(one_chip, (N_PAGES, kv, page, d), jnp.bfloat16)
    fn = functools.partial(paged_decode_attn, page_size=page,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        sel, sel, _on(one_chip, (ROWS,), jnp.int32),
        _on(one_chip, (ROWS, kv, g, d), jnp.bfloat16), pool, pool).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _full_width_step(one_chip, build, extra):
    """Compile a whole qwen2-1.5b serve step (params, k/v/s pools, then
    ``extra``) from shapes alone; returns its memory analysis."""
    cfg = get_config("qwen2-1.5b")
    params = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype),
                          api.param_specs(cfg))
    kv_shape = (cfg.n_layers, N_PAGES, cfg.n_kv_heads, cfg.kv_page, cfg.hd)
    kv = _on(one_chip, kv_shape, jnp.dtype(cfg.param_dtype))
    s = _on(one_chip, (cfg.n_layers, N_PAGES, cfg.n_kv_heads, cfg.hd),
            jnp.float32)
    compiled = jax.jit(build(cfg), donate_argnums=(1, 2, 3)).lower(
        params, kv, kv, s, *extra).compile()
    return compiled.memory_analysis()


def _fits_hbm(ma) -> bool:
    return ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


def test_full_width_decode_step_fits_hbm(one_chip, no_persistent_cache):
    i32 = jnp.int32
    ma = _full_width_step(
        one_chip, lambda cfg: _paged_decode_fn(cfg, "xla"),
        [_on(one_chip, (ROWS,), i32), _on(one_chip, (ROWS,), i32),
         _on(one_chip, (ROWS, TOPK_PAGES), i32)])
    assert _fits_hbm(ma), ma


def test_full_width_prefill_step_fits_hbm(one_chip, no_persistent_cache):
    chunk, i32 = 128, jnp.int32
    ma = _full_width_step(
        one_chip, lambda cfg: _paged_prefill_fn(cfg, chunk),
        [_on(one_chip, (chunk,), i32), _on(one_chip, (), i32),
         _on(one_chip, (), i32), _on(one_chip, (TOPK_PAGES,), i32)])
    assert _fits_hbm(ma), ma
