"""Seconds to build the driver: the host synapse-table build and
placing the tables on the device (``build_sim_driver``)."""


def read(ctx):
    return ctx["table_build_s"]
