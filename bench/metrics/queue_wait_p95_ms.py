"""Scheduler: 95th percentile of admission minus arrival over the requests
that arrived in the window (censored at the window's end), ms."""
from bench.window import percentile, queue_wait_values


def read(ctx):
    v = queue_wait_values(ctx.seen, ctx.end)
    return percentile(v, 95) * 1e3 if v else None
