"""The plain reference realizes the simulator's connectome, and its
lower-precision control fails the cells' limits."""

import time

import jax
import numpy as np
import pytest

from bench import control, reference, run


def simulator_synapses(cfg, tables, ty, tx):
    """(pre neuron, post slot, weight bits, delay) of every synapse in
    the simulator's tables of tile ``(ty, tx)``, its rows read back to
    neurons through the tile's geometry."""
    spec = cfg.engine.spec()
    d = spec.decomp
    n, n_exc, g_w = spec.n_per_col, spec.n_exc_per_col, d.grid.width
    oy, ox = d.tile_origin(ty, tx)
    # local rows: (tile column, neuron); band rows: (band column, neuron)
    lcol = np.arange(d.tile_cols)
    row_pre = [((oy + lcol // d.tile_w) * g_w + ox + lcol % d.tile_w)
               [:, None] * n + np.arange(n)]
    for band in spec.halo_bands():
        ry, rx = np.divmod(band["cols"], d.region_w)
        col = (oy - d.radius + ry) * g_w + ox - d.radius + rx
        row_pre.append(col[:, None] * n + np.arange(n_exc))
    out = []
    for tab, pre in zip([tables.local] + list(tables.halo), row_pre):
        t = {k: np.asarray(v)[ty, tx] for k, v in tab.items()}
        live = np.arange(t["tgt"].shape[1])[None, :] < t["nnz"][:, None]
        rows = np.nonzero(live)[0]
        out.append(np.stack([pre.ravel()[rows], t["tgt"][live],
                             t["w"][live].astype(np.float32).view(np.int32),
                             t["dslot"][live]], 1).astype(np.int64))
    return np.concatenate(out)


@pytest.mark.parametrize("grid,tiles,n", [
    (4, (1, 1), 200), (4, (1, 2), 200), (4, (2, 2), 200), (5, (2, 2), 200),
    (6, (2, 2), 100)])
@pytest.mark.parametrize("law", ["gaussian", "exponential"])
def test_reference_connectome_is_the_simulators(small, law, grid, tiles, n):
    """Synapse for synapse -- pre, post, weight bits, delay -- the
    reference's realization equals the simulator's tables, tile by tile.
    A 5x5 grid on 2x2 tiles pads its last row and column of tiles; with
    100-neuron columns the Gaussian law's farthest offsets send under
    ``halo_floor`` synapses into a tile, and those are dropped."""
    from repro.core.dist_engine import build_dist_tables
    from repro.runtime.jobs import dist_config_for
    _, config, traffic = small
    law_params = run.load_json(
        run.BENCH / "configs" / f"dpsnn-7x7-{law}.json")["model"]
    model = dict(config["model"], law=law, grid=grid, n_per_column=n,
                 amplitude=law_params["amplitude"],
                 scale_um=law_params["scale_um"])
    config = dict(config, law=law, grid=grid, n_per_column=n,
                  tiles=list(tiles), model=model)
    spec = run.job_spec(config, traffic, seed=1, ckpt_dir="unused")
    cfg = dist_config_for(spec, tiles)
    tables, stats = build_dist_tables(cfg)
    blocks = list(reference.connectome(reference.Model.from_config(config)))
    assert stats["clipped"] == 0
    assert sum(len(b["pre"]) for b in blocks) == stats["n_synapses"]
    for k, blk in enumerate(blocks):
        ty, tx = divmod(k, tiles[1])
        prog = simulator_synapses(cfg, tables, ty, tx)
        ref = np.stack([blk["pre"], blk["post"], blk["w"].view(np.int32),
                        blk["delay"]], 1).astype(np.int64)
        prog = prog[np.lexsort(prog.T[::-1])]
        ref = ref[np.lexsort(ref.T[::-1])]
        assert np.array_equal(prog, ref), (ty, tx)


@pytest.mark.parametrize("name", ["dpsnn-7x7-exponential",
                                  "dpsnn-7x7-gaussian"])
def test_control_fails_the_limits(small, name):
    """The reference with its ring kept in bfloat16, put in the
    simulator's place, fails the cell's limits; the simulator passes.
    The window runs into the test network's burst and every segment in
    it is checked: in the first few after the warm-up too few spikes
    arrive for a bfloat16 ring to flip one."""
    cell, config, traffic = small
    traffic = dict(traffic, check_segments=64)
    limits = run.load_json(run.BENCH / "configs" / f"{name}.json")["limits"]
    ctx = run.run_cell(cell, config, traffic, 2 ** 31 + 3, seconds=6.0,
                       trace=False, peaks={}, require_chip=False,
                       t_start=time.perf_counter())
    assert reference.verdict(ctx["readings"], limits)
    readings = control.control_readings(
        config, traffic["job"]["segment_steps"], ctx["check_inputs"],
        jax.devices()[:1])
    assert reference.verdict(readings, limits) is False
