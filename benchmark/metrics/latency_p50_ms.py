"""Median submit-to-verdict latency at the client, from each request's
due time to the poll that saw it done; a request refused, unanswered or
answered wrongly counts as waiting until the load generator gave up."""

from benchmark import latency, stats


def read(ctx):
    values = latency.client_latencies_ms(ctx)
    return stats.percentile(values, 50.0) if values else None
