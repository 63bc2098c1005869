"""Percent of the traced window in which the chip ran no operation,
in the closed-loop cell that keeps the service saturated."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.idle_share(ctx.trace)
