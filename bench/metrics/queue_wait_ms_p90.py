"""Scheduler: 90th percentile of the wait from a request's due time to
the start of the iteration that first scheduled it, over the requests
due in the window (one never scheduled by the window's end waits
forever)."""

import math


def read(ctx):
    xs = sorted(ctx.queue_s)
    if not xs:
        return None
    v = xs[min(len(xs) - 1, max(0, math.ceil(0.90 * len(xs)) - 1))]
    return 1e3 * v
