"""The comparison catches a broken timed path.

Each test drives a whole run of the harness at the test size on the
CPU -- set-up, warm-up, window, checkpoints, reference comparison,
result -- skipping only its look for a chip, with the simulator broken
underneath in one way the cell can be broken, and expects ``correct``
to come out false.  A run of the unbroken simulator comes out true.
Each runs on one tile and on 2x2 tiles (four host devices); the halo
exchange exists only on more than one.
"""

import time

import jax.numpy as jnp
import pytest

from bench import run


def run_small(net, seed=7):
    cell, config, traffic = net
    ctx = run.run_cell(cell, config, traffic, seed, seconds=1.0,
                       trace=False, peaks={}, require_chip=False,
                       t_start=time.perf_counter())
    bench = {"end_to_end": [], "per_layer": []}
    return run.result(bench, cell, ctx, False, [FakeDevice()])


class FakeDevice:
    platform = "cpu"
    device_kind = "cpu"


def test_tiles_must_match_chips(four_tiles):
    cell, config, traffic = four_tiles
    with pytest.raises(run.Mismatch, match="need 4 chips"):
        run.run_cell(dict(cell, chips=1), config, traffic, 7, seconds=1.0,
                     trace=False, peaks={}, require_chip=False)


def test_sound_run_is_correct(tiled):
    out = run_small(tiled)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    # state, ring and spikes agree bit for bit on any tiling; only the
    # float32 event sums may differ by their order
    gaps = {k: c["value"] for k, c in out["checks"].items()
            if k != "events_gap"}
    assert set(gaps.values()) == {0}, gaps


def test_state_left_unchanged(tiled, monkeypatch):
    from repro.runtime import sim_driver

    def frozen(*args, **kwargs):
        return lambda state, inputs: (state, None)

    monkeypatch.setattr(sim_driver, "make_sim_fn", frozen)
    assert not run_small(tiled)["correct"]


def test_half_the_sources_left_out(tiled, monkeypatch):
    from repro.kernels import ops
    real = ops.synaptic_accum_banded

    def half(tiers, *args, **kwargs):
        cut = []
        for tables, spikes, cap in tiers:
            keep = jnp.arange(spikes.shape[0]) < spikes.shape[0] // 2
            cut.append((tables, jnp.where(keep, spikes, 0.0), cap))
        return real(cut, *args, **kwargs)

    monkeypatch.setattr(ops, "synaptic_accum_banded", half)
    assert not run_small(tiled)["correct"]


def test_one_spike_altered(tiled, monkeypatch):
    from repro.kernels import ops
    real = ops.lif_step

    def altered(*args, **kwargs):
        neuron, spikes = real(*args, **kwargs)
        return neuron, spikes.at[0].set(1.0 - spikes[0])

    monkeypatch.setattr(ops, "lif_step", altered)
    assert not run_small(tiled)["correct"]


def test_halo_exchange_left_out(four_tiles, monkeypatch):
    """Each tile sees only its own spikes: no source outside it reaches
    it."""
    from repro.core import dist_engine

    def alone(x, *, radius, **kwargs):
        pad = [(radius, radius)] * 2 + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, pad)

    monkeypatch.setattr(dist_engine, "exchange_halo_2d", alone)
    assert not run_small(four_tiles)["correct"]
