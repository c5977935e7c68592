"""Share (%) of the window's device-idle time that no program span
explains: the main thread had no span open, or only the outer
``segment`` span (``bench/spans.py``); both idle times are averaged
over the chips."""


def read(ctx):
    idle = ctx.get("span_idle")
    if not idle or idle["idle_s"] <= 0:
        return None
    return 100.0 * idle["unattributed_s"] / idle["idle_s"]
