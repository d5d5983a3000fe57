"""Step functions: device busy time per engine iteration, from the union
of chip 0's op intervals in the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.iters:
        return None
    return 1e3 * ctx.trace["busy_s"] / len(ctx.iters)
