"""Share (%) of the traced window in which no operation ran on a chip:
one minus the busy union over the window, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
