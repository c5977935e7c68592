"""Share of the window (%) outside the driver's ``segment.compute``
spans: metric pulls, checkpoint snapshots, host bookkeeping.  One
driver thread serves all chips: no division by chips."""


def read(ctx):
    inside = sum(s["dur"] for s in ctx["spans"]
                 if s["name"] == "segment.compute")
    if inside <= 0:
        return None
    return 100.0 * (1.0 - inside / ctx["window_s"])
