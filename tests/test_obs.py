"""Spike observatory: device-side recording, spool contract, analysis.

The spool contract under test (ISSUE 4): zero-spike segments leave
valid empty logs; resume-after-preemption (and failure replay) yields
exactly-once events, bit-compared against an unpreempted run; and
recording on/off leaves the engine spike trains bit-identical.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.connectivity import exponential_law, gaussian_law
from repro.core.dist_engine import DistConfig
from repro.core.engine import (EngineConfig, build_shard_tables,
                               init_sim_state, simulate)
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.kernels.spike_compact import spike_compact_pallas
from repro.kernels.synaptic_accum import compact_events
from repro.obs.analysis import (analyze_run, compare_runs, ks_statistic,
                                updown_segmentation)
from repro.obs.record import recorder_spec, stacked_gid_maps, tile_gid_map
from repro.obs.spool import (SpikeSpooler, load_events, read_header,
                             shard_events)
from repro.parallel.compat import make_mesh
from repro.runtime import DriverConfig, SimDriver

N = 40


def _dist_cfg(seed=3, **engine_kw):
    law = gaussian_law()
    dec = TileDecomposition(grid=ColumnGrid(4, 4, 10), tiles_y=1,
                            tiles_x=1, radius=law.radius)
    return DistConfig(engine=EngineConfig(decomp=dec, law=law, seed=seed,
                                          **engine_kw))


def _driver(ckpt_dir, seg, dist=None, **kw):
    cfg = DriverConfig(ckpt_dir=str(ckpt_dir),
                       ckpt_every=kw.pop("ckpt_every", 1),
                       backoff_s=0.01, handle_sigterm=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    return SimDriver(cfg, dist or _dist_cfg(), mesh, segment_steps=seg,
                     **kw)


# ---------------------------------------------------------------------------
# Device-side recorder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,cap,p", [(160, 40, 0.1), (160, 40, 0.9),
                                     (7, 7, 0.5), (1024, 16, 0.3),
                                     (2000, 600, 0.0), (513, 520, 1.0),
                                     (3840, 3104, 0.05)])
def test_spike_compact_kernel_matches_xla(n, cap, p):
    """The Pallas compaction kernel is bit-identical to the XLA
    ``compact_events`` fallback: ascending indices, sink padding, and
    the uncapped spike count."""
    rng = np.random.default_rng(n + cap)
    spk = jnp.asarray((rng.random(n) < p).astype(np.float32))
    i_x, c_x = compact_events(spk, n, cap)
    i_k, c_k = spike_compact_pallas(spk, n, cap, interpret=True)
    assert int(c_x) == int(c_k)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_k))


def test_gid_map_is_global_and_tiling_invariant():
    """Each local slot maps to its global neuron id; padded slots and
    the compaction sink map to -1; the union over tiles covers every
    logical neuron exactly once, whatever the tiling."""
    law = gaussian_law()
    grid = ColumnGrid(3, 3, 4)          # 3x3 does not divide 2 -> padding
    seen = {}
    for ty, tx in [(1, 1), (1, 2), (2, 2)]:
        dec = TileDecomposition(grid=grid, tiles_y=ty, tiles_x=tx,
                                radius=law.radius)
        g = stacked_gid_maps(dec)
        assert g.shape == (ty, tx, dec.n_local + 1)
        assert (g[..., -1] == -1).all()
        live = g[..., :-1][g[..., :-1] >= 0]
        np.testing.assert_array_equal(np.sort(live),
                                      np.arange(grid.n_neurons))
        seen[(ty, tx)] = np.sort(live)
    assert all((v == seen[(1, 1)]).all() for v in seen.values())


def test_recording_is_pure_observer(tmp_path):
    """Recording on/off leaves the engine dynamics (the full final
    state) bit-identical -- the recorder is an observer, not a
    participant -- and the spool's per-step counts (the driver's only
    per-step record, via ``spike_counts``) match the raw logs and the
    non-recording run's cumulative totals exactly."""
    off = _driver(tmp_path / "off", seg=10)
    out_off = off.run(N)
    on = _driver(tmp_path / "on", seg=10, record_events=True)
    out_on = on.run(N)
    for a, b in zip(jax.tree.leaves(out_off["state"]),
                    jax.tree.leaves(out_on["state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    counts = on.spike_counts(N)
    assert counts.shape == (N,) and counts.sum() > 0
    assert counts.sum() == float(
        np.asarray(jnp.sum(out_off["state"]["metrics"]["spikes"])))
    # spike_counts without recording is an error now, not a stale dict
    with pytest.raises(ValueError, match="record_events"):
        off.spike_counts()
    # and the spooled log agrees with the per-step counts exactly
    on.spool.close()
    ev = load_events(str(tmp_path / "on"))
    assert len(ev) == int(counts.sum())
    np.testing.assert_array_equal(
        np.bincount(ev["step"], minlength=N).astype(np.float32), counts)


def test_single_shard_run_records_events():
    cfg = _dist_cfg().engine
    tabs = build_shard_tables(cfg)
    rspec = recorder_spec(cfg, N)
    st, per_step, rec = jax.jit(
        lambda s: simulate(s, tabs, cfg, N, recorder=rspec))(init_sim_state(cfg))
    cnt = int(rec["count"])
    assert cnt == int(np.asarray(per_step).sum())
    assert int(rec["dropped"]) == 0
    gids = np.asarray(rec["gid"][:cnt])
    steps = np.asarray(rec["step"][:cnt])
    assert (np.diff(steps) >= 0).all()
    assert gids.min() >= 0 and gids.max() < cfg.decomp.grid.n_neurons
    # every recorded gid names a real (non-padded) neuron slot
    gmap = tile_gid_map(cfg.decomp, 0, 0)
    assert set(gids).issubset(set(gmap[gmap >= 0]))


# ---------------------------------------------------------------------------
# Spool contract
# ---------------------------------------------------------------------------

def test_zero_spike_segments_produce_valid_empty_logs(tmp_path):
    """No drive -> no spikes: the spool still holds a valid header and
    (empty) shard logs, and the analysis pipeline handles them."""
    law = gaussian_law()
    dec = TileDecomposition(grid=ColumnGrid(4, 4, 10), tiles_y=1,
                            tiles_x=1, radius=law.radius)
    dist = DistConfig(engine=EngineConfig(decomp=dec, law=law, seed=3,
                                          ext_rate_hz=0.0))
    d = _driver(tmp_path, seg=10, dist=dist, record_events=True)
    out = d.run(20)
    d.spool.close()
    assert float(np.asarray(jnp.sum(out["state"]["metrics"]["spikes"]))) == 0
    shards = shard_events(str(tmp_path))
    assert list(shards) == ["events_000_000.spk"]
    assert len(shards["events_000_000.spk"]) == 0
    assert read_header(str(tmp_path))["law"] == "gaussian"
    rep = analyze_run(str(tmp_path))
    assert rep["n_events"] == 0 and rep["t_steps"] == 20
    assert rep["rates"]["mean_hz"] == 0.0
    assert rep["population"]["updown"]["regime"] == "silent"


def test_spool_exactly_once_after_preemption(tmp_path):
    """A run preempted mid-way and resumed spools logs identical (after
    (step, gid) ordering) to an unpreempted run's."""
    straight = _driver(tmp_path / "a", seg=10, record_events=True)
    straight.run(N)
    straight.spool.close()

    d1 = _driver(tmp_path / "b", seg=10, record_events=True,
                 preempt_after_segments=2)
    out1 = d1.run(N)
    assert out1["preempted"]
    d1.spool.close()
    d2 = _driver(tmp_path / "b", seg=10, record_events=True)
    out2 = d2.run(N)
    assert out2["final_step"] == N
    d2.spool.close()

    ev_a = load_events(str(tmp_path / "a"))
    ev_b = load_events(str(tmp_path / "b"))
    assert len(ev_a) > 0
    np.testing.assert_array_equal(ev_a, ev_b)      # byte-identical stream


def test_spool_exactly_once_after_failure_replay(tmp_path):
    """A segment failure after un-checkpointed (but already spooled)
    segments rewinds the logs to the checkpoint frontier before
    replaying: each event lands exactly once."""
    straight = _driver(tmp_path / "ref", seg=10, record_events=True)
    straight.run(N)
    straight.spool.close()

    fired = []

    def hook(step):
        if step == 30 and not fired:
            fired.append(step)
            raise RuntimeError("injected failure after unsaved segment")

    d = _driver(tmp_path / "x", seg=10, ckpt_every=2, record_events=True,
                fault_hook=hook)
    out = d.run(N)
    assert fired == [30] and out["final_step"] == N
    d.spool.close()
    np.testing.assert_array_equal(load_events(str(tmp_path / "ref")),
                                  load_events(str(tmp_path / "x")))


def test_recorder_overflow_is_counted_not_silent(tmp_path):
    """An undersized event buffer drops the excess spikes and says so:
    the spooled logs keep the per-segment prefix, and the drop counter
    surfaces through the driver."""
    steps = 60                    # 6 segments, ~15 spikes at this seed
    full = _driver(tmp_path / "full", seg=10, record_events=True)
    full.run(steps)
    full.spool.close()
    n_total = len(load_events(str(tmp_path / "full")))
    assert n_total > steps // 10             # more than the tiny cap holds

    tiny = _driver(tmp_path / "tiny", seg=10, record_events=True,
                   record_capacity=1)
    tiny.run(steps)
    tiny.spool.close()
    ev = load_events(str(tmp_path / "tiny"))
    assert len(ev) <= steps // 10            # <= capacity x segments
    assert tiny.recorder_dropped == n_total - len(ev)
    # drops ride the checkpoint manifest too (resume keeps the count)
    again = _driver(tmp_path / "tiny", seg=10, record_events=True,
                    record_capacity=1)
    start, _ = again._restore_or_init()
    assert start == steps and again.recorder_dropped == tiny.recorder_dropped


def test_spooler_refuses_foreign_header(tmp_path):
    """A spool directory left behind by a different model is refused,
    not silently appended to (analysis normalizes by the header's
    n_neurons -- mixing models would poison every rate)."""
    sp = SpikeSpooler(str(tmp_path), (1, 1),
                      header={"n_neurons": 160, "law": "gaussian"})
    sp.close()
    # same model: fine (resume path)
    SpikeSpooler(str(tmp_path), (1, 1),
                 header={"n_neurons": 160, "law": "gaussian"}).close()
    with pytest.raises(ValueError, match="different model"):
        SpikeSpooler(str(tmp_path), (1, 1),
                     header={"n_neurons": 3840, "law": "gaussian"})


def test_spooler_truncate_rejects_tampered_logs(tmp_path):
    sp = SpikeSpooler(str(tmp_path), (1, 1), header={"n_neurons": 4})
    sp.append(0, 0, np.asarray([1, 2]), np.asarray([3, 0]))
    sp.wait()
    os.truncate(tmp_path / "events_000_000.spk", 0)
    with pytest.raises(IOError, match="truncated or deleted"):
        sp.truncate({"events_000_000.spk": 2})
    sp.close()


# ---------------------------------------------------------------------------
# Analysis
# ---------------------------------------------------------------------------

def _write_synthetic(directory, events, n_neurons=8, dt_ms=1.0):
    sp = SpikeSpooler(str(directory), (1, 1),
                      header={"grid": [2, 2, n_neurons // 4],
                              "law": "gaussian", "seed": 0,
                              "dt_ms": dt_ms, "n_neurons": n_neurons})
    steps = np.asarray([e[0] for e in events], np.int32)
    gids = np.asarray([e[1] for e in events], np.int32)
    sp.append(0, 0, steps, gids)
    sp.close()


def test_analysis_statistics_on_synthetic_events(tmp_path):
    """Known spike trains -> exact statistics: a perfectly regular
    train has ISI CV 0; rates are counts / duration; a square-wave
    population alternation segments into Up/Down states."""
    # neuron 0: every 10 steps (regular); neuron 1: two spikes;
    # steps 0-49 active, 50-99 silent, 100-149 active (square wave)
    ev = [(t, 0) for t in range(0, 150, 10)]
    ev += [(5, 1), (25, 1)]
    burst = [(t, g) for t in list(range(0, 50, 2)) + list(range(100, 150, 2))
             for g in (2, 3, 4)]
    ev += burst
    _write_synthetic(tmp_path, sorted(ev), n_neurons=8)
    rep = analyze_run(str(tmp_path), t_steps=150, bin_steps=5)
    assert rep["n_events"] == len(ev)
    # neuron 0 fired 15 times in 0.15 s -> 100 Hz
    rates = rep["_neuron_rates"]
    assert rates[0] == pytest.approx(100.0)
    assert rates[1] == pytest.approx(2 / 0.15)
    assert rep["isi"]["n_excluded"] >= 1        # neuron 1: only 2 spikes
    assert rep["isi"]["n_neurons"] == 4
    # the regular neuron pins the low percentile near 0 (its CV is 0);
    # the bursty neurons push the mean well above it
    assert rep["isi"]["p05"] < 0.5 < rep["isi"]["mean_cv"]
    ud = rep["population"]["updown"]
    assert ud["regime"] == "slow_wave_like"
    assert ud["n_down_periods"] >= 1 and ud["n_up_periods"] >= 2
    assert 0.3 < ud["up_fraction"] < 0.9


def test_ks_statistic_separates_distinct_distributions():
    rng = np.random.default_rng(0)
    a = rng.normal(8.0, 1.0, 400)
    same = rng.normal(8.0, 1.0, 400)
    b = rng.normal(35.0, 5.0, 400)
    assert ks_statistic(a, b) > 0.9
    assert ks_statistic(a, same) < 0.2
    assert ks_statistic(a, a) == 0.0


def test_updown_silent_and_awake_edges():
    assert updown_segmentation(np.zeros(50))["regime"] == "silent"
    steady = np.full(100, 10.0) + np.linspace(0, 0.1, 100)
    assert updown_segmentation(steady)["regime"] == "awake_like"


@pytest.mark.slow
def test_analyze_reports_rate_separation_direction(tmp_path):
    """Acceptance: at 8x8x60 / 300 steps the analyze pipeline reports a
    higher mean firing rate and a distinct per-neuron rate distribution
    for the exponential law vs Gaussian -- same direction as
    test_engine.py::test_rate_separation_exponential_vs_gaussian, but
    measured from the spooled logs instead of engine counters."""
    reports = {}
    for name, law in [("gauss", gaussian_law()),
                      ("expo", exponential_law())]:
        dec = TileDecomposition(grid=ColumnGrid(8, 8, 60), tiles_y=1,
                                tiles_x=1, radius=law.radius)
        cfg = EngineConfig(decomp=dec, law=law, use_kernels=False)
        tabs = build_shard_tables(cfg)
        rspec = recorder_spec(cfg, 300)
        st, _, rec = jax.jit(
            lambda s, c=cfg, t=tabs, r=rspec: simulate(s, t, c, 300,
                                                    recorder=r))(
            init_sim_state(cfg))
        cnt = int(rec["count"])
        assert int(rec["dropped"]) == 0
        d = tmp_path / name
        sp = SpikeSpooler(str(d), (1, 1),
                          header={"grid": [8, 8, 60], "law": law.kind,
                                  "seed": 0, "dt_ms": cfg.lif.dt_ms,
                                  "n_neurons": dec.grid.n_neurons})
        sp.append(0, 0, np.asarray(rec["step"][:cnt]),
                  np.asarray(rec["gid"][:cnt]))
        sp.close()
        reports[name] = analyze_run(str(d), t_steps=300)
    cmp = compare_runs(reports)
    pair = cmp["pairs"]["gauss_vs_expo"]
    assert reports["expo"]["mean_rate_hz"] > \
        1.4 * reports["gauss"]["mean_rate_hz"], cmp["mean_rate_hz"]
    assert pair["rate_ks_statistic"] > 0.3       # distinct distributions


# ---------------------------------------------------------------------------
# Retile metric carry (satellite: totals as manifest global scalars)
# ---------------------------------------------------------------------------

def test_retile_resume_carries_metric_totals_in_manifest(tmp_path):
    """After an elastic retile the per-tile state metrics restart at
    zero; the history travels as global scalars in the manifest and the
    driver's reported totals are tiling-independent."""
    from repro.checkpoint.store import checkpoint_meta

    d1 = _driver(tmp_path, seg=10)
    out1 = d1.run(N)
    totals1 = d1.metric_totals(out1["state"])
    assert totals1["spikes"] > 0
    meta = checkpoint_meta(str(tmp_path), N)
    assert meta["metric_base"] == {"spikes": 0.0, "events": 0.0,
                                   "dropped": 0.0}
    assert meta["metric_totals"] == totals1

    # resume the 1x1 checkpoint on a 2x1 tiling (host-side relayout;
    # the 1-device mesh partially replicates -- fine for restore-only)
    law = gaussian_law()
    dec = TileDecomposition(grid=ColumnGrid(4, 4, 10), tiles_y=2,
                            tiles_x=1, radius=law.radius)
    dist = DistConfig(engine=EngineConfig(decomp=dec, law=law, seed=3))
    d2 = _driver(tmp_path, seg=10, dist=dist, allow_retile=True)
    start, state = d2._restore_or_init()
    assert start == N
    # state metrics zeroed on every tile; base holds the history
    for k in ("spikes", "events", "dropped"):
        assert float(np.asarray(jnp.sum(state["metrics"][k]))) == 0.0
    assert d2.metric_totals(state) == totals1
    assert d2.firing_rate_hz(state) == pytest.approx(
        totals1["spikes"] / 160 / (N * 1e-3))
    # the next checkpoint's manifest publishes the carried base
    d2._save(N, state)
    d2.ckpt.wait()
    meta2 = checkpoint_meta(str(tmp_path), N)
    assert meta2["metric_base"] == totals1
    assert meta2["metric_totals"] == totals1
