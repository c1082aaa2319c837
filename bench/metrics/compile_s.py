"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) during set-up, from its own monitoring events."""


def read(run):
    return run.compile_s
