"""Device memory per synapse: the fullest chip's ``peak_bytes_in_use``
after the window over the configuration's recurrent synapses (the
paper's memory metric)."""


def read(ctx):
    if ctx["memory_peak_bytes"] <= 0:
        return None
    return ctx["memory_peak_bytes"] / ctx["n_synapses"]
