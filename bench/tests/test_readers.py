"""Readers that divide by chips count per chip: on one chip each reads
what a single-chip formula gives, on four the per-chip figure."""

import pytest

from bench import run, work

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def context(chips):
    """A synthetic run of a whole grid: its event and neuron counts are
    the grid's, the trace's times are averages over the chips."""
    return {"cell": {"chips": chips}, "memory_peak_bytes": 3_224_925_696,
            "n_synapses": 88_581_493, "table_bytes": 790_000_000,
            "events": 2.9e9, "neurons": 60_760, "steps": 105,
            "window_s": 30.1, "peak": PEAK, "work": work,
            "storage": {"tgt_dtype": "int16", "weight_dtype": "bfloat16"},
            "trace": {"op_s": {"synaptic_accum": 28.3}, "busy_s": 29.8,
                      "window_s": 30.1}}


def read(name, ctx, kind="layer"):
    return run.reader(kind, name)(ctx)


def test_one_chip_reads_the_single_chip_formulas():
    ctx = context(1)
    need = work.delivery(2.9e9, 60_760, 105,
                         work.entry_bytes("int16", "bfloat16"))
    least, bound = work.roofline_seconds(need, PEAK)
    assert bound == "hbm"
    assert read("bytes_per_synapse", ctx, "e2e") == 3_224_925_696 / 88_581_493
    assert read("synaptic_accum_roofline", ctx) == 100.0 * least / 28.3
    assert read("delivery_ms_per_step", ctx) == 1e3 * 28.3 / 105
    assert read("table_bytes_per_synapse", ctx) == 790_000_000 / 88_581_493


def test_four_chips_read_per_chip():
    one, four = context(1), context(4)
    assert read("bytes_per_synapse", four, "e2e") == pytest.approx(
        4 * read("bytes_per_synapse", one, "e2e"))
    assert read("synaptic_accum_roofline", four) == pytest.approx(
        read("synaptic_accum_roofline", one) / 4)
    assert read("step_mfu", four) == pytest.approx(
        read("step_mfu", one) / 4)
    # per-device averages and whole-grid rates do not change with chips
    for name in ("delivery_ms_per_step", "device_idle_share",
                 "table_bytes_per_synapse"):
        assert read(name, four) == pytest.approx(read(name, one)), name
    assert read("ns_per_syn_event", four, "e2e") \
        == read("ns_per_syn_event", one, "e2e")
