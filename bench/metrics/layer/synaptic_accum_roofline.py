"""Share (%) of its roofline that the ``synaptic_accum`` kernel reaches
on one chip: the least time for one chip's share of delivery's required
FLOPs and bytes (``bench/work.py``: the whole grid's, over the cell's
chips) at the chip's peaks, over the kernel's device time in the trace
averaged over the chips.  At these counts the bound is HBM bytes
(table entries read per event)."""

KERNEL = "synaptic_accum"


def read(ctx):
    t, w, st = ctx["trace"], ctx["work"], ctx["storage"]
    if t is None or ctx["peak"] is None or t["op_s"].get(KERNEL, 0.0) <= 0:
        return None
    need = w.delivery(ctx["events"], ctx["neurons"], ctx["steps"],
                      w.entry_bytes(st["tgt_dtype"], st["weight_dtype"]))
    least, _ = w.roofline_seconds(need, ctx["peak"])
    return 100.0 * least / ctx["cell"]["chips"] / t["op_s"][KERNEL]
