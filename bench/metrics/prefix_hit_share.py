"""KV allocator and prefix cache: prompt tokens attached from the prefix
index over the prompt tokens of the requests admitted from the window."""


def read(ctx):
    admitted = [r for r in ctx.requests if r.admitted_at >= 0]
    total = sum(r.prompt_len for r in admitted)
    if not total:
        return None
    return 100.0 * sum(r.cached_tokens for r in admitted) / total
