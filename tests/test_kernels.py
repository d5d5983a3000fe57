"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).

Kernels execute in interpret mode on CPU (the TPU lowering is exercised
structurally — BlockSpecs, scalar prefetch — with the same code path)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (coalesce_indices, csr_to_ell, gather_rows,
                           gather_spmm, group_tokens_by_expert,
                           moe_dispatch_matmul, ops, sparse_decode_attn,
                           topk_pages)
from repro.kernels import ref

RNG = np.random.default_rng(42)


def rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,k", [(32, 128, 8), (64, 256, 24), (16, 512, 5)])
def test_gather_rows(n, d, k, dtype):
    idx = jnp.asarray(RNG.integers(0, n, k), jnp.int32)
    tbl = rand((n, d), dtype)
    out = gather_rows(idx, tbl)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.gather_rows_ref(idx, tbl)))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("m,j,nin,n,bn", [(8, 4, 16, 128, 128),
                                          (16, 8, 32, 256, 128),
                                          (4, 16, 64, 512, 256)])
def test_gather_spmm(m, j, nin, n, bn, dtype, rtol):
    cols = jnp.asarray(RNG.integers(0, nin, (m, j)), jnp.int32)
    vals = rand((m, j), dtype)
    dense = rand((nin, n), dtype)
    out = gather_spmm(cols, vals, dense, block_n=bn)
    want = ref.gather_spmm_ref(cols, vals, dense)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=rtol, atol=rtol)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 3e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("b,hkv,g,d,s,p,page", [
    (2, 2, 4, 64, 128, 6, 8),
    (1, 4, 2, 128, 256, 8, 16),
    (3, 1, 8, 64, 64, 4, 1),     # page=1: exact row selection
])
def test_sparse_decode_attn(b, hkv, g, d, s, p, page, dtype, rtol):
    q = rand((b, hkv, g, d), dtype)
    k = rand((b, s, hkv, d), dtype)
    v = rand((b, s, hkv, d), dtype)
    idx = jnp.asarray(RNG.integers(0, s // page, (b, hkv, p)), jnp.int32)
    out = sparse_decode_attn(idx, q, k, v, page_size=page)
    want = ref.sparse_decode_attn_ref(idx, q, k, v, page_size=page)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-4),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("t,d,e,f,bt", [(256, 128, 4, 256, 64),
                                        (128, 256, 8, 128, 32),
                                        (512, 64, 2, 512, 128)])
def test_moe_dispatch_matmul(t, d, e, f, bt, dtype, rtol):
    x = rand((t, d), dtype)
    w = rand((e, d, f), dtype)
    gids = jnp.asarray(RNG.integers(0, e, t // bt), jnp.int32)
    out = moe_dispatch_matmul(gids, x, w, block_t=bt,
                              block_f=min(f, 128), block_d=min(d, 128))
    want = ref.moe_dispatch_matmul_ref(gids, x, w, block_t=bt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol * 10)


@pytest.mark.parametrize("capacity_factor", [1.25, 0.5, 0.25])
def test_moe_ffn_vs_dispatch_matmul_with_drops(capacity_factor):
    """``moe_ffn``'s einsum expert compute vs the ``moe_dispatch_matmul``
    grouped-GEMM kernel on the *same* dispatch plan (``moe._route_row``),
    including capacity factors low enough that pairs get dropped — the
    two paths must drop identically and agree on every surviving token."""
    import jax

    from repro.models import moe

    class Cfg:
        d_model, n_experts, top_k, d_ff_expert = 64, 4, 2, 128

    cfg, s, bt = Cfg(), 64, 16
    key = jax.random.PRNGKey(7)
    p = moe.init_moe(cfg, key, jnp.float32)
    x = rand((1, s, cfg.d_model), jnp.float32)
    want = moe.moe_ffn(x, p, cfg, capacity_factor=capacity_factor)

    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    cap = moe._capacity(s, k, e, capacity_factor)
    assert cap % bt == 0, "capacity is 16-aligned by construction"
    if capacity_factor < 1.0:
        assert cap < s * k / e + 1, "low factor must actually drop pairs"
    slot, keep, pair_token, gates, order = moe._route_row(
        x[0], p["router"].astype(jnp.float32), e, k, cap)
    src = jnp.where(keep[:, None], x[0][pair_token], 0.0)
    xg = jnp.zeros((e * cap, d), x.dtype).at[
        jnp.where(keep, slot, 0)].add(src, mode="drop")
    # grouped GEMMs over the dispatched rows: block group ids walk the
    # experts cap/bt blocks at a time
    gids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), cap // bt)
    gate = jax.nn.silu(moe_dispatch_matmul(gids, xg, p["we_gate"],
                                           block_t=bt))
    up = moe_dispatch_matmul(gids, xg, p["we_up"], block_t=bt)
    yg = moe_dispatch_matmul(gids, gate * up, p["we_down"], block_t=bt)
    pair_out = jnp.where(keep[:, None], yg[slot], 0.0)
    pair_gate = gates.reshape(-1)[order].astype(yg.dtype)
    got = jnp.zeros((s, d), yg.dtype).at[pair_token].add(
        pair_out * pair_gate[:, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("tile_f", [16, 32, 64])
def test_moe_paged_gemm_vs_dense(tile_f, dtype, rtol):
    """Paged gate/up/down GEMMs vs the dense einsum on the same expert
    weights, across tile sizes: page 0 is the zero scratch page, pages
    1.. are each expert's ``[F, D]`` plane cut into ``tile_f``-row tiles
    (the expert_pool layout), and the tiling must be value-invisible."""
    from repro.kernels import moe_paged_down, moe_paged_gateup

    r, k, e, f, d = 4, 2, 4, 128, 64
    nt = f // tile_f
    wg = rand((e, f, d), dtype)               # gate/up plane, [F, D] rows
    wd = rand((e, f, d), dtype)               # down plane
    pool_g = jnp.concatenate([jnp.zeros((1, tile_f, d), dtype),
                              wg.reshape(e * nt, tile_f, d)])
    pool_d = jnp.concatenate([jnp.zeros((1, tile_f, d), dtype),
                              wd.reshape(e * nt, tile_f, d)])
    table = jnp.arange(1, 1 + e * nt, dtype=jnp.int32).reshape(e, nt)
    eids = jnp.asarray(RNG.integers(0, e, (r, k)), jnp.int32)
    pids = table[eids]                        # [R, K, NT]
    x = rand((r, d), dtype)
    h = rand((r, k, f), dtype)

    got_g = moe_paged_gateup(pids, x, pool_g)
    want_g = jnp.einsum("rd,rkfd->rkf", x.astype(jnp.float32),
                        wg[eids].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got_g, np.float32),
                               np.asarray(want_g), rtol=rtol, atol=rtol)
    np.testing.assert_array_equal(
        np.asarray(got_g),
        np.asarray(ref.moe_paged_gateup_ref(pids, x, pool_g)))

    got_d = moe_paged_down(pids, h, pool_d)
    want_d = jnp.einsum("rkf,rkfd->rkd", h.astype(jnp.float32),
                        wd[eids].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got_d, np.float32),
                               np.asarray(want_d), rtol=rtol,
                               atol=rtol * 10)
    np.testing.assert_allclose(
        np.asarray(got_d, np.float32),
        np.asarray(ref.moe_paged_down_ref(pids, h, pool_d), np.float32),
        rtol=rtol, atol=rtol * 10)


def test_coalesce_indices_roundtrip():
    idx = jnp.asarray(RNG.integers(0, 50, 64), jnp.int32)
    sorted_idx, inv = coalesce_indices(idx)
    assert bool(jnp.all(jnp.diff(sorted_idx) >= 0))
    np.testing.assert_array_equal(np.asarray(sorted_idx[inv]),
                                  np.asarray(idx))


def test_csr_to_ell_matches_dense():
    m, n = 16, 32
    dense = (RNG.random((m, n)) < 0.2) * RNG.normal(size=(m, n))
    rowptr = np.zeros(m + 1, np.int32)
    cols, vals = [], []
    for r in range(m):
        nz = np.nonzero(dense[r])[0]
        rowptr[r + 1] = rowptr[r] + len(nz)
        cols.extend(nz)
        vals.extend(dense[r, nz])
    ecols, evals = csr_to_ell(rowptr, np.array(cols, np.int32),
                              np.array(vals, np.float32))
    rhs = RNG.normal(size=(n, 8)).astype(np.float32)
    out = ref.gather_spmm_ref(jnp.asarray(ecols), jnp.asarray(evals),
                              jnp.asarray(rhs))
    np.testing.assert_allclose(np.asarray(out), dense @ rhs, rtol=1e-5,
                               atol=1e-5)


def test_topk_pages_selects_highest():
    scores = jnp.asarray(RNG.normal(size=(2, 3, 64)), jnp.float32)
    idx = topk_pages(scores, n_pages=8, page_size=8, k_pages=3)
    ps = np.asarray(scores).reshape(2, 3, 8, 8).max(-1)
    want = np.argsort(-ps, axis=-1)[..., :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want, -1))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 3e-4),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("b,s,h,kv,d,causal,bq,bk", [
    (2, 64, 4, 2, 32, True, 32, 32),
    (1, 128, 8, 8, 64, True, 64, 32),
    (2, 64, 4, 1, 32, False, 32, 64),
    (1, 256, 2, 2, 128, True, 128, 128),
])
def test_flash_prefill(b, s, h, kv, d, causal, bq, bk, dtype, rtol):
    from repro.kernels import flash_prefill
    from repro.models.layers import chunked_attention
    q = rand((b, s, h, d), dtype)
    k = rand((b, s, kv, d), dtype)
    v = rand((b, s, kv, d), dtype)
    out = flash_prefill(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref_out = chunked_attention(q, k, v, causal=causal, chunk=32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=rtol, atol=rtol)
