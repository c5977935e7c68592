"""Simulated milliseconds of the whole grid per wall second in the
window (all chips step together: no division by chips)."""


def read(ctx):
    dt_ms = ctx["config"]["model"]["dt_ms"]
    return ctx["steps"] * dt_ms / ctx["window_s"]
