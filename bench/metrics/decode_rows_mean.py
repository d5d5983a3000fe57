"""Scheduler: mean decode rows per engine iteration in the window."""


def read(ctx):
    if not ctx.iters:
        return None
    return sum(it.rows for it in ctx.iters) / len(ctx.iters)
