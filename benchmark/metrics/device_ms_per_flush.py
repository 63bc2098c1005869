"""Device busy time in the traced seconds per serve.dispatch span in them,
each span counted by its share inside: what one flush costs the chip (the
kernels behind backend.py)."""

from benchmark import trace_reduce


def read(ctx):
    value = trace_reduce.per_flush(ctx.trace, 1)
    return None if value is None else value * 1e3
