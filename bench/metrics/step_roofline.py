"""Step programs and kernels: the least time of the window's iterations
(the larger of operations over peak FLOP/s and bytes over peak HBM
bandwidth, from the shapes and each iteration's rows and prefill tokens)
over the device's busy time."""

from bench.metrics import _costs


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    least = sum(_costs.least_time_s(ctx.shape, ctx.peaks, it)[0]
                for it in ctx.iters)
    if least <= 0:
        return None
    return 100.0 * least / ctx.trace["busy_s"]
