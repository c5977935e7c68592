"""Seconds of moving the sampled tables into place in set-up: stacking
and compressing them (``setup.tables.compress``) and putting them on
the mesh (``setup.tables.place``): host seconds for all chips."""

NAMES = ("setup.tables.compress", "setup.tables.place")


def read(ctx):
    spans = [s for s in ctx.get("setup_spans") or () if s["name"] in NAMES]
    if not spans:
        return None
    return sum(s["dur"] for s in spans)
