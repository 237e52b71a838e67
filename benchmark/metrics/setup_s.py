"""Seconds from the process's start to the end of the warm-up: JAX's start,
table generation, the compile or cache load, and the warm-up queries."""


def read(ctx):
    return ctx["setup_s"]
