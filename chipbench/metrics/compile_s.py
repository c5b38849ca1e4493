"""Seconds of set-up spent tracing, lowering and compiling (the union of
JAX's compile spans from process start to the first timed call); with
every program in the persistent cache, what is left is the cache load."""


def read(ctx):
    return ctx["compile_s"]
