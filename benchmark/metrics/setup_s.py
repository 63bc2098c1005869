"""Seconds from process start to the end of the key warm-up: loading or
compiling every program, the canary corpus, building the traffic, and
decoding every pool key through the edge."""


def read(ctx):
    return ctx.setup_s
