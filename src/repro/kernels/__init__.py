"""NVR TPU kernels: runahead gather, sparse SpMM, TopK decode attention
(contiguous and block-table paged layouts), grouped MoE GEMM.  See ops.py
for the public API, ref.py for oracles."""

from .ops import (coalesce_indices, csr_to_ell, flash_prefill, gather_rows,
                  gather_spmm, group_tokens_by_expert, moe_dispatch_matmul,
                  moe_paged_down, moe_paged_gateup, on_tpu,
                  sparse_decode_attn, topk_pages)
from .paged_decode_attn import paged_decode_attn

__all__ = [
    "coalesce_indices", "csr_to_ell", "flash_prefill", "gather_rows",
    "gather_spmm", "group_tokens_by_expert", "moe_dispatch_matmul",
    "moe_paged_down", "moe_paged_gateup", "on_tpu", "paged_decode_attn",
    "sparse_decode_attn", "topk_pages",
]
