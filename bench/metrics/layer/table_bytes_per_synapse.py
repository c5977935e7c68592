"""Bytes of the materialized synapse tables per synapse, as the
simulator counts them: ``table_bytes_per_shard`` times the cell's
chips (one shard each) over the configuration's synapses."""


def read(ctx):
    return ctx["table_bytes"] / ctx["n_synapses"]
