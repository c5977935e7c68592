"""The required work is a function of counts and storage dtypes only."""

import dataclasses
import inspect

import jax
import numpy as np
import pytest

from bench import work


def test_work_takes_counts_only():
    for fn in (work.delivery, work.step):
        assert list(inspect.signature(fn).parameters) == [
            "events", "neurons", "steps", "entry_size"]
    assert work.entry_bytes("int32", "bfloat16") == 7
    assert work.entry_bytes("int16", "float32") == 7
    d = work.delivery(1e6, 1000, 10, 7)
    assert d.flops == 2e6
    assert d.bytes == 7e6 + 10 * 2 * 1000 * 4
    s = work.step(1e6, 1000, 10, 7)
    assert s.flops == d.flops + 10 * 1000 * work.LIF_FLOPS
    assert s.bytes == d.bytes + 10 * 1000 * work.LIF_BYTES


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_seconds(work.Work(flops=2.0, bytes=7.0), peak)
    assert bound == "hbm" and t == pytest.approx(7.0 / 819e9)
    t, bound = work.roofline_seconds(work.Work(flops=1e15, bytes=1.0), peak)
    assert bound == "flops" and t == pytest.approx(1e15 / 197e12)


def test_two_delivery_paths_are_charged_the_same(small):
    """The Pallas kernel and the XLA scatter deliver the same events
    from the same state, so the work counted for them is equal."""
    from repro.core.dist_engine import (SimInputs, build_dist_tables,
                                        dist_shardings, init_dist_state,
                                        make_sim_fn)
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.jobs import dist_config_for
    from bench import run
    _, config, traffic = small
    spec = run.job_spec(config, traffic, seed=3, ckpt_dir="unused")
    cfg = dist_config_for(spec, (1, 1))
    mesh = make_host_mesh((1, 1))
    tables, _ = build_dist_tables(cfg)
    state_sh, table_sh = dist_shardings(cfg, mesh, tables.storage)
    inputs = SimInputs(tables=jax.device_put(tables, table_sh))
    counted = []
    for kernels in (True, False):
        c = dataclasses.replace(cfg, engine=dataclasses.replace(
            cfg.engine, use_kernels=kernels))
        sim = make_sim_fn(c, mesh, 40, storage=tables.storage)
        state, _ = sim(jax.device_put(init_dist_state(cfg), state_sh),
                       inputs)
        events = float(np.asarray(state["metrics"]["events"]).sum())
        counted.append(work.step(events, 3200, 40, 5))
    assert counted[0].flops > 40 * 3200 * work.LIF_FLOPS
    assert counted[0] == counted[1]
