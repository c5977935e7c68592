"""Device memory per synapse, as a deployment provisions it: the fullest
chip's ``peak_bytes_in_use`` after the window, times the cell's chips,
over the configuration's recurrent synapses (the paper's memory
metric; on one chip, that chip's peak over the synapses)."""


def read(ctx):
    if ctx["memory_peak_bytes"] <= 0:
        return None
    return ctx["memory_peak_bytes"] * ctx["cell"]["chips"] / ctx["n_synapses"]
