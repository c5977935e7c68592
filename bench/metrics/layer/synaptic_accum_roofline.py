"""Share (%) of its roofline that the ``synaptic_accum`` kernel reaches:
the least time for delivery's required FLOPs and bytes at the chip's
peaks (``bench/work.py``), over the kernel's device time in the trace.
At these counts the bound is HBM bytes (table entries read per event)."""

KERNEL = "synaptic_accum"


def read(ctx):
    t, w, st = ctx["trace"], ctx["work"], ctx["storage"]
    if t is None or ctx["peak"] is None or t["op_s"].get(KERNEL, 0.0) <= 0:
        return None
    need = w.delivery(ctx["events"], ctx["neurons"], ctx["steps"],
                      w.entry_bytes(st["tgt_dtype"], st["weight_dtype"]))
    least, _ = w.roofline_seconds(need, ctx["peak"])
    return 100.0 * least / t["op_s"][KERNEL]
