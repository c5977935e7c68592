"""Seconds from process start to the first measured segment: table
build, placement, compile or cache load, and warm-up."""


def read(ctx):
    return ctx["setup_s"]
