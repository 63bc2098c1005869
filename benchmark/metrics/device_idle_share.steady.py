"""Percent of the traced window in which the chip ran no operation,
in the open-loop cell below the knee."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.idle_share(ctx.trace)
