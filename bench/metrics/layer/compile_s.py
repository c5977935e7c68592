"""Seconds JAX spent compiling, or loading from the persistent compile
cache, during set-up (its own ``backend_compile_duration`` events)."""


def read(ctx):
    return ctx["compile_s"]
