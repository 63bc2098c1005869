"""Verify-program executions in the traced seconds per serve.dispatch span
in them, each span counted by its share inside: the real batch plus the
integrity guard's canary batches plus the ladder's bisection calls, per
flush."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.per_flush(ctx.trace, 0)
