"""Bytes of the materialized synapse tables per synapse, as the
simulator counts them (``table_bytes_per_shard`` over all shards)."""


def read(ctx):
    return ctx["table_bytes"] / ctx["n_synapses"]
