"""Distributed plasticity: single-shard vs distributed bit-identity,
checkpointed plastic resume, the global-synapse-id table relay, and the
STDP-identity resume refusals (ISSUE 5).

All single-device (1x1 mesh) -- the 2-device plastic retile-resume case
lives in tests/test_multidevice.py, and CI's plastic resume-smoke leg
drives the same path through the repro.launch.sim CLI.
"""

import jax
import numpy as np
import pytest

from repro.core.connectivity import exponential_law, gaussian_law
from repro.core.dist_engine import (DistConfig, SimInputs,
                                    build_dist_inverse_index,
                                    build_dist_tables,
                                    init_dist_plastic_state,
                                    init_dist_state, make_sim_fn)
from repro.core.engine import (EngineConfig, build_shard_tables,
                               init_plasticity, init_sim_state,
                               simulate)
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.retile import (gather_synapse_stream, local_gid_map,
                               retile_plastic, retile_tables)
from repro.core.stdp import STDPParams
from repro.parallel.compat import make_mesh
from repro.runtime import DriverConfig, SimDriver

N = 40          # spiking sets in around step ~36 at this scale/seed


def _dist(law="gaussian", tiles=(1, 1), seed=3, stdp=STDPParams()):
    law_ = gaussian_law() if law == "gaussian" else exponential_law()
    dec = TileDecomposition(grid=ColumnGrid(4, 4, 10), tiles_y=tiles[0],
                            tiles_x=tiles[1], radius=law_.radius)
    return DistConfig(engine=EngineConfig(decomp=dec, law=law_, seed=seed,
                                          stdp=stdp))


def _driver(ckpt_dir, seg, stdp=STDPParams(), **kw):
    cfg = DriverConfig(ckpt_dir=str(ckpt_dir),
                       ckpt_every=kw.pop("ckpt_every", 1),
                       backoff_s=0.01, handle_sigterm=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    return SimDriver(cfg, _dist(stdp=stdp), mesh, segment_steps=seg, **kw)


def _canon(stream):
    """Canonical (pre, post, dslot, w-bits) rows of a synapse stream --
    the tiling-invariant identity the relay must preserve bit-exactly."""
    w = np.ascontiguousarray(stream["w"]).astype(np.float32)
    wbits = w.view(np.uint32)
    order = np.lexsort((wbits, stream["dslot"], stream["post"],
                        stream["pre"]))
    return np.column_stack([stream["pre"][order], stream["post"][order],
                            stream["dslot"][order].astype(np.int64),
                            wbits[order].astype(np.int64)])


# ---------------------------------------------------------------------------
# Single-shard plastic simulate vs the distributed carry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("law", ["gaussian", "exponential"])
def test_dist_plastic_matches_single_shard(law):
    """The distributed plastic scan at 1x1 is bit-identical to the
    single-shard plastic ``simulate`` reference: spikes, final weights and
    both trace arrays."""
    steps = 60
    dist = _dist(law)
    cfg = dist.engine
    tabs = build_shard_tables(cfg)
    aux = init_plasticity(tabs, cfg)
    (st, tabs1, traces), per = jax.jit(
        lambda s, t: simulate(s, t, cfg, steps, plasticity=aux))(
            init_sim_state(cfg), tabs)

    mesh = make_mesh((1, 1), ("data", "model"))
    state = init_dist_state(dist)
    dtabs, _ = build_dist_tables(dist)
    state["plastic"] = init_dist_plastic_state(dist, dtabs)
    slots, _ = build_dist_inverse_index(dist, dtabs)
    sim = make_sim_fn(dist, mesh, steps, storage=dtabs.storage)
    dstate, per_d = sim(state, SimInputs(tables=dtabs, inv_slots=slots))

    assert np.asarray(per).sum() > 0            # the run actually spiked
    np.testing.assert_array_equal(np.asarray(per_d)[0, 0],
                                  np.asarray(per))
    np.testing.assert_array_equal(
        np.asarray(dstate["plastic"]["w"][0][0, 0]),
        np.asarray(tabs1["local"]["w"]))
    np.testing.assert_array_equal(
        np.asarray(dstate["plastic"]["x_post"][0, 0]),
        np.asarray(traces["x_post"]))
    np.testing.assert_array_equal(
        np.asarray(dstate["plastic"]["x_pre"][0][0, 0]),
        np.asarray(traces["x_pre"][0]))
    np.testing.assert_array_equal(np.asarray(dstate["neuron"]["v"][0, 0]),
                                  np.asarray(st["neuron"]["v"]))
    # plasticity moved excitatory weights (the run is not a no-op)
    delta = np.abs(np.asarray(tabs1["local"]["w"])
                   - np.asarray(tabs["local"]["w"]))
    assert delta.sum() > 0


def test_plastic_simulate_ignores_halo_tiers_of_multitile_tables():
    """``init_plasticity`` covers every tier, but the single-shard
    plastic ``simulate`` consumer steps only the local one -- handing it a
    multi-tile shard's tables (halo tiers present) must not corrupt the
    scan carry (regression: the N-tier trace state used to collapse to
    1 tier after the first step)."""
    import dataclasses
    cfg = dataclasses.replace(_dist(tiles=(1, 2)).engine,
                              use_kernels=False)
    tabs = build_shard_tables(cfg, 0, 0)
    aux = init_plasticity(tabs, cfg)
    assert len(aux["masks"]) > 1                 # halo tiers present
    (st, t1, traces), per = jax.jit(
        lambda s, t: simulate(s, t, cfg, 5, plasticity=aux))(
            init_sim_state(cfg), tabs)
    assert np.asarray(per).shape == (5,)
    assert len(traces["x_pre"]) == 1             # local tier only


# ---------------------------------------------------------------------------
# Checkpointed plastic segments (SimDriver)
# ---------------------------------------------------------------------------

def test_plastic_resume_bit_identity(tmp_path):
    """A preempted-and-resumed plastic run ends with weight tables and
    traces bit-identical to an unpreempted run: the plastic carry rides
    every checkpoint."""
    steps = 60          # spiking sets in at step ~36: room for STDP to act
    straight = _driver(tmp_path / "a", seg=steps)
    out_a = straight.run(steps)
    assert out_a["final_step"] == steps

    first = _driver(tmp_path / "b", seg=steps // 2)
    first.run(steps // 2)
    second = _driver(tmp_path / "b", seg=steps // 2)
    out_b = second.run(steps)
    assert out_b["final_step"] == steps

    for la, lb in zip(jax.tree.leaves(out_a["state"]),
                      jax.tree.leaves(out_b["state"])):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    sa = straight.plastic_summary(out_a["state"])
    sb = second.plastic_summary(out_b["state"])
    assert sa["weight_checksum"] == sb["weight_checksum"]
    assert sa["w_l1_delta"] > 0                  # learning happened
    assert sa["n_plastic"] > 0


def test_plastic_recording_is_pure_observer(tmp_path):
    """The spike observatory composes with plasticity without touching
    the dynamics: final weights bit-identical with recording on/off."""
    off = _driver(tmp_path / "off", seg=20)
    out_off = off.run(N)
    on = _driver(tmp_path / "on", seg=20, record_events=True)
    out_on = on.run(N)
    for a, b in zip(jax.tree.leaves(out_off["state"]),
                    jax.tree.leaves(out_on["state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert on.spike_counts(N).sum() > 0


# ---------------------------------------------------------------------------
# STDP-identity refusals (mirrors the grid/law/seed refusals)
# ---------------------------------------------------------------------------

def test_plastic_refuses_static_checkpoint(tmp_path):
    mesh = make_mesh((1, 1), ("data", "model"))
    SimDriver(DriverConfig(ckpt_dir=str(tmp_path), handle_sigterm=False),
              _dist(stdp=None), mesh, segment_steps=10).run(10)
    d = _driver(tmp_path, seg=10)
    with pytest.raises(ValueError, match="stdp"):
        d._restore_or_init()


def test_static_refuses_plastic_checkpoint(tmp_path):
    _driver(tmp_path, seg=10).run(10)
    mesh = make_mesh((1, 1), ("data", "model"))
    d = SimDriver(DriverConfig(ckpt_dir=str(tmp_path),
                               handle_sigterm=False),
                  _dist(stdp=None), mesh, segment_steps=10)
    with pytest.raises(ValueError, match="stdp"):
        d._restore_or_init()


def test_plastic_refuses_stdp_param_drift(tmp_path):
    """Resuming under different STDP parameters is a different model --
    refused, like a seed or law drift."""
    _driver(tmp_path, seg=10).run(10)
    d = _driver(tmp_path, seg=10, stdp=STDPParams(a_plus=0.009))
    with pytest.raises(ValueError, match="stdp"):
        d._restore_or_init()


# ---------------------------------------------------------------------------
# Global-synapse-id table relay (host-side; no mesh needed)
# ---------------------------------------------------------------------------

def test_retile_tables_preserves_global_synapse_multiset():
    """Relaying 1x2 -> 2x1 preserves every (pre, post, dslot, weight)
    record bit-exactly -- nothing re-sampled, nothing dropped."""
    a, b = _dist(tiles=(1, 2)), _dist(tiles=(2, 1))
    ta, _ = build_dist_tables(a)
    relaid = retile_tables(ta, a.engine.decomp, a.engine.spec(),
                           b.engine.decomp, b.engine.spec())
    sa = gather_synapse_stream(ta, a.engine.decomp, a.engine.spec())
    sb = gather_synapse_stream(relaid, b.engine.decomp, b.engine.spec())
    assert len(sa["pre"]) > 0
    np.testing.assert_array_equal(_canon(sa), _canon(sb))
    # occupancy bookkeeping survives: same total synapse count
    assert int(np.asarray(relaid["local"]["nnz"]).sum()
               + sum(int(np.asarray(t["nnz"]).sum())
                     for t in relaid["halo"])) == len(sa["pre"])


def test_retile_tables_roundtrip_is_canonical():
    """Relays compose: A -> B -> A lands bit-identically to the direct
    canonicalization A -> A (so any chain of retiles yields the same
    layout as relaying from birth directly)."""
    a, b = _dist(tiles=(1, 2)), _dist(tiles=(2, 1))
    ta, _ = build_dist_tables(a)
    da, sa = a.engine.decomp, a.engine.spec()
    db, sb = b.engine.decomp, b.engine.spec()
    r1 = retile_tables(ta, da, sa, db, sb)
    r2 = retile_tables(r1, db, sb, da, sa)
    canon = retile_tables(ta, da, sa, da, sa)
    for got, want in zip(jax.tree.leaves(r2), jax.tree.leaves(canon)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_retile_plastic_relays_weights_and_traces():
    """The plastic carry follows the realization: live weights by
    global synapse id, pre-traces by pre neuron id (local tier only --
    halo replicas are exchanged per step, never carried), post-traces
    like the membrane state."""
    a, b = _dist(tiles=(1, 2)), _dist(tiles=(2, 1))
    ta, _ = build_dist_tables(a)
    da, speca = a.engine.decomp, a.engine.spec()
    db, specb = b.engine.decomp, b.engine.spec()
    tiers = [ta["local"]] + list(ta["halo"])
    rng = np.random.default_rng(0)

    # live weights: perturbed copies of the build weights (plastic mask
    # = excitatory entries); traces: the pre/post neuron's gid as value
    w_live = []
    for t in tiers:
        w = np.asarray(t["w"]).copy()
        w += (w > 0) * rng.uniform(0.0, 0.1, size=w.shape).astype(w.dtype)
        w_live.append(w)
    x_pre = [np.zeros((1, 2, tiers[0]["tgt"].shape[2]), np.float32)]
    x_post = np.zeros((1, 2, speca.n_local), np.float32)
    for ty in range(1):
        for tx in range(2):
            lmap = local_gid_map(da, ty, tx)
            x_pre[0][ty, tx, :len(lmap)] = np.maximum(lmap, 0) + 0.5
            x_post[ty, tx] = np.where(lmap >= 0, lmap + 0.25, 0.0)

    out = retile_plastic({"w": w_live, "x_pre": x_pre, "x_post": x_post},
                         ta, da, speca, db, specb)

    # weights: multiset of live (pre, post, dslot, w) preserved exactly
    live_a = gather_synapse_stream(
        {"local": dict(ta["local"], w=w_live[0]),
         "halo": [dict(t, w=w) for t, w in zip(ta["halo"], w_live[1:])]},
        da, speca)
    relaid_tabs = retile_tables(
        {"local": dict(ta["local"], w=w_live[0]),
         "halo": [dict(t, w=w) for t, w in zip(ta["halo"], w_live[1:])]},
        da, speca, db, specb)
    for got, want in zip(out["w"],
                         [relaid_tabs["local"]["w"]]
                         + [t["w"] for t in relaid_tabs["halo"]]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    live_b = gather_synapse_stream(relaid_tabs, db, specb)
    np.testing.assert_array_equal(_canon(live_a), _canon(live_b))

    # traces: every new-tiling row carries its neuron's gid pattern;
    # the pre-trace list stays local-only across the relay
    assert len(out["x_pre"]) == 1
    for ty in range(2):
        for tx in range(1):
            lmap = local_gid_map(db, ty, tx)
            np.testing.assert_array_equal(
                np.asarray(out["x_pre"][0][ty, tx, :len(lmap)]),
                np.where(lmap >= 0, np.maximum(lmap, 0) + 0.5, 0.0)
                .astype(np.float32))
            np.testing.assert_array_equal(
                np.asarray(out["x_post"][ty, tx]),
                np.where(lmap >= 0, lmap + 0.25, 0.0).astype(np.float32))
