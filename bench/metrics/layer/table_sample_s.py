"""Seconds of host synapse sampling and row packing in set-up: the
sum of the ``setup.tables.sample`` spans, one per shard (one per chip,
sampled one after another), each with its copy of the shard's tables
to the default device."""


def read(ctx):
    spans = [s for s in ctx.get("setup_spans") or ()
             if s["name"] == "setup.tables.sample"]
    if not spans:
        return None
    return sum(s["dur"] for s in spans)
