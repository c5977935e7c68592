"""Seconds to build the driver: the host synapse-table build and
placing the tables on the cell's chips (``build_sim_driver``)."""


def read(ctx):
    return ctx["table_build_s"]
