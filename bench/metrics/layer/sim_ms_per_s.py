"""Simulated milliseconds per wall second in the window."""


def read(ctx):
    dt_ms = ctx["config"]["model"]["dt_ms"]
    return ctx["steps"] * dt_ms / ctx["window_s"]
