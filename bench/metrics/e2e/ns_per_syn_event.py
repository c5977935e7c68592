"""Wall nanoseconds per synaptic event over the measured window: the
window's host-clock time over every event delivered in it on all the
cell's chips (the sum of the window segments' event counts), the
paper's cost unit."""


def read(ctx):
    if ctx["events"] <= 0:
        return None
    return ctx["window_s"] / ctx["events"] * 1e9
