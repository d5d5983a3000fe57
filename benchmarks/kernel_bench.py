"""TPU-layer kernel benchmark: runahead kernels vs their XLA-path oracles.

On this CPU container the Pallas kernels run in interpret mode (Python) —
wall-clock is meaningless for them — so this bench reports (a) oracle
XLA-path wall time (a real number on CPU), (b) the kernel's structural
roofline: bytes moved per call vs the dense alternative, i.e. the
NVR-mechanism win the dry-run measures at model scale.

  PYTHONPATH=src python -m benchmarks.kernel_bench
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref


def timeit(fn, *args, n=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def main():
    rng = np.random.default_rng(0)
    rows = []

    # sparse_decode_attn: TopK pages vs dense attention over the cache
    b, hkv, g, d, s, p, page = 4, 4, 8, 128, 4096, 16, 16
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.bfloat16)
    idx = jnp.asarray(rng.integers(0, s // page, (b, hkv, p)), jnp.int32)
    sparse_fn = jax.jit(lambda i, q_, k_, v_: ref.sparse_decode_attn_ref(
        i, q_, k_, v_, page_size=page))
    us_sparse = timeit(sparse_fn, idx, q, k, v)

    def dense_attn(q_, k_, v_):
        sc = jnp.einsum("bkgd,bskd->bkgs", q_.astype(jnp.float32),
                        k_.astype(jnp.float32)) / (d ** 0.5)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgs,bskd->bkgd", w, v_.astype(jnp.float32))
    us_dense = timeit(jax.jit(dense_attn), q, k, v)
    bytes_sparse = b * hkv * p * page * d * 2 * 2
    bytes_dense = b * s * hkv * d * 2 * 2
    rows.append(("sparse_decode_attn", us_sparse,
                 f"dense_us={us_dense:.0f};kv_bytes_ratio="
                 f"{bytes_dense / bytes_sparse:.1f}x"))

    # paged sparse decode on the serve layer's block-table layout: the
    # same TopK computation as above, but on the [P,KV,page,D] physical
    # pool + per-request block tables the continuous-batching engine
    # actually produces (contiguous [B,S,KV,D] never exists there) — so
    # kernel numbers and serve_bench numbers are comparable
    from repro.models import sparse_attention

    r, nl, pp = 8, s // page, 1 + 8 * (s // page)
    kpool = jnp.asarray(rng.normal(size=(pp, hkv, page, d)), jnp.bfloat16)
    vpool = jnp.asarray(rng.normal(size=(pp, hkv, page, d)), jnp.bfloat16)
    spool = jnp.asarray(rng.normal(size=(pp, hkv, d)), jnp.float32)
    bt = np.stack([rng.choice(np.arange(1, pp), size=nl, replace=False)
                   for _ in range(r)])
    qr = jnp.asarray(rng.normal(size=(r, hkv, g, d)), jnp.float32)
    pos = jnp.asarray(rng.integers(page, nl * page, size=r), jnp.int32)
    n_valid = pos // page + 1
    idx_bt, phys = sparse_attention.select_pages_blocktable(
        qr, spool, jnp.asarray(bt), n_valid, p)

    paged_fn = jax.jit(lambda q_, k_, v_, i_, ph_, po_:
                       sparse_attention.attend_pages_paged(
                           q_, k_, v_, i_, ph_, po_, page))
    us_paged = timeit(paged_fn, qr, kpool, vpool, idx_bt, phys, pos)
    # structural run + parity of the Pallas paged kernel on this layout
    from repro.kernels import on_tpu, paged_decode_attn
    got = paged_decode_attn(phys, idx_bt, pos, qr, kpool, vpool,
                            page_size=page, interpret=not on_tpu())
    want = paged_fn(qr, kpool, vpool, idx_bt, phys, pos)
    err = float(np.abs(np.asarray(got, np.float32)
                       - np.asarray(want, np.float32)).max())
    assert err < 1e-5, f"paged kernel parity: {err}"
    bytes_paged = r * hkv * p * page * d * 2 * 2
    rows.append(("paged_decode_attn", us_paged,
                 f"layout=blocktable_pool;pallas_parity_err={err:.1e};"
                 f"kv_bytes_ratio={bytes_dense / bytes_paged:.1f}x"))

    # gather_spmm: ELL sparse vs dense matmul
    m, j, nin, n = 256, 16, 1024, 1024
    cols = jnp.asarray(rng.integers(0, nin, (m, j)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(m, j)), jnp.float32)
    dense = jnp.asarray(rng.normal(size=(nin, n)), jnp.float32)
    us_spmm = timeit(jax.jit(ref.gather_spmm_ref), cols, vals, dense)
    wd = jnp.asarray(rng.normal(size=(m, nin)), jnp.float32)
    us_mm = timeit(jax.jit(lambda a, b_: a @ b_), wd, dense)
    rows.append(("gather_spmm", us_spmm,
                 f"dense_matmul_us={us_mm:.0f};"
                 f"flops_ratio={nin / j:.0f}x_fewer"))

    # moe grouped GEMM vs dense all-experts
    t, dm, e, f, bt = 512, 256, 8, 512, 64
    x = jnp.asarray(rng.normal(size=(t, dm)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(e, dm, f)), jnp.bfloat16)
    gids = jnp.asarray(rng.integers(0, e, t // bt), jnp.int32)
    us_moe = timeit(jax.jit(lambda g_, x_, w_: ref.moe_dispatch_matmul_ref(
        g_, x_, w_, block_t=bt)), gids, x, w)
    us_all = timeit(jax.jit(lambda x_, w_: jnp.einsum("td,edf->etf", x_, w_)),
                    x, w)
    rows.append(("moe_dispatch_matmul", us_moe,
                 f"all_experts_us={us_all:.0f};compute_ratio={e}x_fewer"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    from repro.core.nvr.engine.sweep import write_artifacts

    from .paths import results_dir
    paths = write_artifacts(
        "kernel_bench", "name,us_per_call,derived",
        [(n, f"{us:.0f}", d) for n, us, d in rows],
        results_dir(),
        backend=jax.default_backend())
    print(f"# artifacts: {paths['csv']} {paths['json']}")


if __name__ == "__main__":
    main()
