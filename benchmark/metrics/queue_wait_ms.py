"""Median wait between admission and dispatch, from the program's
serve_queue_wait_seconds histogram: the buckets filled from the window's
start until the profiler started, interpolated as Prometheus'
histogram_quantile does."""

from benchmark import stats


def read(ctx):
    q = stats.histogram_quantile(ctx.queue_wait_edges, ctx.queue_wait_delta, 0.5)
    return None if q is None else q * 1e3
