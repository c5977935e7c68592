"""Share (%) of the chips' peak FLOP/s that the window's required step
work (``bench/work.py``: delivery plus the neuron update of the whole
grid) reaches over the window's wall time: divided by the window times
the cell's chips times one chip's peak."""


def read(ctx):
    w, st = ctx["work"], ctx["storage"]
    if ctx["peak"] is None:
        return None
    need = w.step(ctx["events"], ctx["neurons"], ctx["steps"],
                  w.entry_bytes(st["tgt_dtype"], st["weight_dtype"]))
    chips = ctx["cell"]["chips"]
    return 100.0 * need.flops / (ctx["window_s"] * chips
                                 * ctx["peak"]["flops_per_s"])
