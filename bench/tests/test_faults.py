"""The comparison catches a broken timed path.

Each test drives a whole run of the harness at the test size on the
CPU -- set-up, warm-up, window, checkpoints, reference comparison,
result -- skipping only its look for a chip, with the simulator broken
underneath in one way the cell can be broken, and expects ``correct``
to come out false.  A run of the unbroken simulator comes out true.
"""

import time

import jax.numpy as jnp

from bench import run


def run_small(small, seed=7):
    cell, config, traffic = small
    ctx = run.run_cell(cell, config, traffic, seed, seconds=1.0,
                       trace=False, peaks={}, require_chip=False,
                       t_start=time.perf_counter())
    bench = {"end_to_end": [], "per_layer": []}
    return run.result(bench, cell, ctx, False, [FakeDevice()])


class FakeDevice:
    platform = "cpu"
    device_kind = "cpu"


def test_sound_run_is_correct(small):
    out = run_small(small)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def test_state_left_unchanged(small, monkeypatch):
    from repro.runtime import sim_driver

    def frozen(*args, **kwargs):
        return lambda state, inputs: (state, None)

    monkeypatch.setattr(sim_driver, "make_sim_fn", frozen)
    assert not run_small(small)["correct"]


def test_half_the_sources_left_out(small, monkeypatch):
    from repro.kernels import ops
    real = ops.synaptic_accum_banded

    def half(tiers, *args, **kwargs):
        cut = []
        for tables, spikes, cap in tiers:
            keep = jnp.arange(spikes.shape[0]) < spikes.shape[0] // 2
            cut.append((tables, jnp.where(keep, spikes, 0.0), cap))
        return real(cut, *args, **kwargs)

    monkeypatch.setattr(ops, "synaptic_accum_banded", half)
    assert not run_small(small)["correct"]


def test_one_spike_altered(small, monkeypatch):
    from repro.kernels import ops
    real = ops.lif_step

    def altered(*args, **kwargs):
        neuron, spikes = real(*args, **kwargs)
        return neuron, spikes.at[0].set(1.0 - spikes[0])

    monkeypatch.setattr(ops, "lif_step", altered)
    assert not run_small(small)["correct"]
