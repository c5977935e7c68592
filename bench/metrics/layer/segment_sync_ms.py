"""Host milliseconds per window segment spent between segments on the
driver's thread: the counter read-back (``segment.metrics``) and the
checkpoint hand-off (``ckpt.save``), for all chips at once."""

NAMES = ("segment.metrics", "ckpt.save")


def read(ctx):
    spans = [s for s in ctx["spans"] if s["name"] in NAMES]
    if not any(s["name"] == NAMES[0] for s in spans) or not ctx["segments"]:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx["segments"]
