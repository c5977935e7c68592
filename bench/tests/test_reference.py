"""The plain reference realizes the simulator's connectome, and its
lower-precision control fails the cells' limits."""

import time

import numpy as np
import pytest

from bench import control, reference, run


@pytest.mark.parametrize("law", ["gaussian", "exponential"])
def test_reference_connectome_is_the_simulators(small, law):
    """Synapse for synapse -- pre, post, weight bits, delay -- the
    reference's realization equals the simulator's tables."""
    from repro.core.dist_engine import build_dist_tables
    from repro.runtime.jobs import dist_config_for
    _, config, traffic = small
    law_params = run.load_json(
        run.BENCH / "configs" / f"dpsnn-7x7-{law}.json")["model"]
    model = dict(config["model"], law=law,
                 amplitude=law_params["amplitude"],
                 scale_um=law_params["scale_um"])
    config = dict(config, law=law, model=model)
    spec = run.job_spec(config, traffic, seed=1, ckpt_dir="unused")
    tables, stats = build_dist_tables(dist_config_for(spec, (1, 1)))
    t = {k: np.asarray(v)[0, 0] for k, v in tables.local.items()}
    live = np.arange(t["tgt"].shape[1])[None, :] < t["nnz"][:, None]
    prog = np.stack([np.nonzero(live)[0], t["tgt"][live],
                     t["w"][live].astype(np.float32).view(np.int32),
                     t["dslot"][live]], 1).astype(np.int64)
    syn = reference.connectome(reference.Model.from_config(config))
    ref = np.stack([syn["pre"], syn["post"], syn["w"].view(np.int32),
                    syn["delay"]], 1).astype(np.int64)
    assert stats["clipped"] == 0
    assert len(ref) == stats["n_synapses"]
    prog = prog[np.lexsort(prog.T[::-1])]
    ref = ref[np.lexsort(ref.T[::-1])]
    assert np.array_equal(prog, ref)


@pytest.mark.parametrize("name", ["dpsnn-7x7-exponential",
                                  "dpsnn-7x7-gaussian"])
def test_control_fails_the_limits(small, name):
    """The reference with its ring kept in bfloat16, put in the
    simulator's place, fails the cell's limits; the simulator passes."""
    cell, config, traffic = small
    limits = run.load_json(run.BENCH / "configs" / f"{name}.json")["limits"]
    ctx = run.run_cell(cell, config, traffic, 2 ** 31 + 3, seconds=1.0,
                       trace=False, peaks={}, require_chip=False,
                       t_start=time.perf_counter())
    assert reference.verdict(ctx["readings"], limits)
    readings = control.control_readings(
        config, traffic["job"]["segment_steps"], ctx["check_inputs"])
    assert reference.verdict(readings, limits) is False
