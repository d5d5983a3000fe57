"""Runahead row gather — the NVR primitive, TPU-native.

``table`` lives in HBM; ``idx`` (the resolved sparse chain, SCD-analogue) is
*scalar-prefetched* into SMEM before the kernel body runs, so the Pallas
pipeline engine issues the indirect HBM->VMEM DMA for grid step ``k+1``
while step ``k`` computes — a software vector-runahead with depth equal to
the pipeline's multiple-buffering depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, tbl_ref, out_ref):
    out_ref[...] = tbl_ref[...]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gather_rows(idx: jax.Array, table: jax.Array, *, block_d: int = 0,
                interpret: bool) -> jax.Array:
    """out[k, :] = table[idx[k], :].

    Args:
      idx: int32 [K] row indices (may repeat — MSHR-coalescing is done by
        the caller via ``repro.core.sparse.coalesce``).
      table: [N, D] source rows in HBM.
      block_d: tile width along D (0 = full row).
      interpret: run the Pallas interpreter (``ops`` passes the
        platform decision).
    """
    k_rows, = idx.shape
    n, d = table.shape
    bd = block_d or d
    grid = (k_rows, d // bd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bd), lambda k, j, idx_ref: (idx_ref[k], j))],
        out_specs=pl.BlockSpec((1, bd), lambda k, j, idx_ref: (k, j)),
    )
    return pl.pallas_call(
        _gather_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_rows, d), table.dtype),
        interpret=interpret)(idx.astype(jnp.int32), table)
