"""Whole step: model FLOPs of every token computed in the window (prefill
tokens not served from the prefix cache and decode rows, attention over
the attended pages) over the traced window times the chip's peak."""

from bench.metrics import _costs


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    flops = sum(_costs.iteration(ctx.shape, it)[0] for it in ctx.iters)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["flops_bf16"])
