"""Operations and bytes of the serving step, from shapes alone.

Every per-layer reader gets a :class:`Context`.  The counts below are
the least work an iteration needs: weights read once per iteration,
every matrix product of the tokens computed (decode rows and prefill
tokens not served from the prefix cache), one row of logits per decode
row and per prefill chunk, attention over the tokens the TopK pages
hold (causal over the whole context in prefill), the page summaries
that selection reads, the KV written, and the shared pages copied on
write.  Keys and values are in the
configuration's dtype; page summaries are float32.
"""

from __future__ import annotations

from dataclasses import dataclass, field

SUMMARY_BYTES = 4


@dataclass
class Context:
    iters: list                  # harness.Iteration of the window
    window_s: float              # host clock
    trace: dict | None           # trace.reduce() of the window
    shape: dict                  # spec.model_shape()
    peaks: dict                  # bench/peaks.json entry
    queue_s: list = field(default_factory=list)
    requests: list = field(default_factory=list)


def layer_matmul_params(s: dict) -> int:
    d, hd, f = s["d_model"], s["head_dim"], s["d_ff"]
    q, kv = s["n_heads"] * hd, s["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def weight_bytes(s: dict) -> int:
    """Weights one iteration reads: every layer, the head matrix, the
    norm gains (float32)."""
    hd = s["head_dim"]
    bias = (s["n_heads"] + 2 * s["n_kv_heads"]) * hd * s["qkv_bias"]
    per_layer = (layer_matmul_params(s) + bias) * s["dtype_bytes"]
    head = s["d_model"] * s["vocab"] * s["dtype_bytes"]
    norms = (2 * s["n_layers"] + 1) * s["d_model"] * 4
    return s["n_layers"] * per_layer + head + norms


def decode_row(s: dict, pos: int) -> tuple[float, float]:
    """(flops, bytes) of one decode row at position ``pos`` (the token
    at ``pos`` is fed; ``pos + 1`` tokens are in context)."""
    L, hd = s["n_layers"], s["head_dim"]
    h, kv, page = s["n_heads"], s["n_kv_heads"], s["kv_page"]
    n_valid = pos // page + 1
    sel = min(s["kv_topk_pages"], n_valid)
    attended = min(pos + 1, sel * page)
    flops = (2 * L * layer_matmul_params(s) + 2 * s["d_model"] * s["vocab"]
             + L * (4 * h * hd * attended + 2 * h * hd * n_valid))
    kv_bytes = s["dtype_bytes"] * 2 * hd * kv * L
    byts = (sel * page * kv_bytes                       # pages gathered
            + L * kv * n_valid * hd * SUMMARY_BYTES     # summaries read
            + kv_bytes)                                 # KV written
    return float(flops), float(byts)


def prefill_chunk(s: dict, start: int, n: int) -> tuple[float, float]:
    """(flops, bytes) of ``n`` prompt tokens from position ``start``."""
    L, hd = s["n_layers"], s["head_dim"]
    h, kv = s["n_heads"], s["n_kv_heads"]
    causal = n * start + n * (n + 1) // 2           # sum of context sizes
    flops = (2 * L * layer_matmul_params(s) * n
             + 2 * s["d_model"] * s["vocab"] + L * 4 * h * hd * causal)
    kv_bytes = s["dtype_bytes"] * 2 * hd * kv * L
    return float(flops), float((start + n) * kv_bytes + n * kv_bytes)


def iteration(s: dict, it) -> tuple[float, float]:
    """(flops, bytes) of one harness iteration; (0, 0) when it did no
    model work."""
    if not it.decode_pos and not it.prefill:
        return 0.0, 0.0
    flops, byts = 0.0, float(weight_bytes(s))
    for p in it.decode_pos:
        f, b = decode_row(s, p)
        flops, byts = flops + f, byts + b
    for start, n in it.prefill:
        f, b = prefill_chunk(s, start, n)
        flops, byts = flops + f, byts + b
    # a page copied on write: its K, V and summary read once and written
    kv_page = (s["kv_page"] * s["dtype_bytes"] * 2 + SUMMARY_BYTES) * (
        s["n_layers"] * s["n_kv_heads"] * s["head_dim"])
    return flops, byts + 2.0 * it.cow_copies * kv_page


def least_time_s(s: dict, peaks: dict, it) -> tuple[float, str]:
    """The iteration's least time on the chip and the bound that sets
    it (``"compute"`` or ``"memory"``)."""
    f, b = iteration(s, it)
    tc, tm = f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
