"""Device milliseconds of the ``synaptic_accum`` delivery kernel per
simulated step on one chip: its time in the profiler trace of the
window, averaged over the chips, over the window's steps."""

KERNEL = "synaptic_accum"


def read(ctx):
    t = ctx["trace"]
    if t is None or t["op_s"].get(KERNEL, 0.0) <= 0 or not ctx["steps"]:
        return None
    return 1e3 * t["op_s"][KERNEL] / ctx["steps"]
