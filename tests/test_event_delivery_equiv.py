"""The fused Pallas event-delivery pipeline is a drop-in for the XLA
path: interpret-mode kernel vs ``deliver_events`` vs ``kernels.ref``
across both connectivity laws and multiple halo fan-out bands."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.connectivity import exponential_law, gaussian_law
from repro.core.engine import (EngineConfig, build_shard_tables,
                               init_sim_state, simulate)
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.synapses import SynapseTableSpec, build_tables, deliver_events
from repro.kernels import ref
from repro.kernels.synaptic_accum import (ENTRY_BLOCK, LANES, TILE_N,
                                          compact_events, event_delivery,
                                          event_delivery_banded,
                                          synaptic_accum_pallas)


def _dist_spec(law, grid=8, n_per_col=12, tiles=(4, 2)):
    # n_per_col=12 keeps the kernel/XLA/ref triple comparison fast; the
    # gaussian law needs 20 for the fan-out map to split into >= 2 bands.
    # rate_cap_hz=25 shrinks the compaction head-room (and with it the
    # interpret-mode trace cost) while staying far above the ~8% spike
    # rates these tests drive.
    d = TileDecomposition(grid=ColumnGrid(grid, grid, n_per_col),
                          tiles_y=tiles[0], tiles_x=tiles[1],
                          radius=law.radius)
    return SynapseTableSpec(decomp=d, law=law, rate_cap_hz=25.0)


def _band_spikes(spec, rng, rate=0.05):
    return [jnp.asarray((rng.random(b["rows"]) < rate).astype(np.float32))
            for b in spec.halo_bands()]


@pytest.mark.parametrize("law_name", ["gaussian", "exponential"])
def test_banded_delivery_matches_xla_and_ref(law_name, rng):
    """Local tier + every halo band, one fused kernel launch vs the
    per-tier XLA loop vs the pure-jnp oracle."""
    law = gaussian_law() if law_name == "gaussian" else exponential_law()
    spec = _dist_spec(law, n_per_col=20 if law_name == "gaussian" else 12)
    bands = spec.halo_bands()
    assert len(bands) >= 2, "need at least two halo fan-out bands"

    tabs = build_tables(spec, 1, 1, j_exc=0.4, j_inh=-2.0, seed=3)
    spikes_local = jnp.asarray(
        (rng.random(spec.n_local) < 0.08).astype(np.float32))
    spikes_bands = _band_spikes(spec, rng)
    ring0 = jnp.asarray(rng.normal(size=(spec.d_ring, spec.n_local)),
                        jnp.float32)
    t_slot = 5

    tiers = [(tabs["local"], spikes_local, spec.active_cap_local)]
    tiers += [(tab, spk, spec.active_cap_band(b))
              for b, tab, spk in zip(bands, tabs["halo"], spikes_bands)]

    # fused Pallas (interpret on CPU)
    ring_k, ev_k, dr_k = jax.jit(
        lambda r: event_delivery_banded(tiers, r, t_slot, spec.d_ring,
                                        interpret=True))(ring0)

    # XLA per-tier loop
    ring_x = ring0
    ev_x = jnp.zeros((), jnp.int32)
    for tab, spk, cap in tiers:
        ring_x, ev, dr = deliver_events(tab, spk, ring_x, t_slot,
                                        spec.d_ring, cap)
        ev_x = ev_x + ev.astype(jnp.int32)

    # pure-jnp oracle, tier by tier
    ring_r = ring0
    for tab, spk, cap in tiers:
        n_rows = tab["tgt"].shape[0] - 1
        idx, _ = compact_events(spk, n_rows, cap)
        ring_r = ref.synaptic_accum_ref(idx, t_slot, tab["tgt"], tab["w"],
                                        tab["dslot"], ring_r)

    np.testing.assert_allclose(np.asarray(ring_k), np.asarray(ring_x),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ring_k), np.asarray(ring_r),
                               rtol=1e-5, atol=1e-5)
    assert int(ev_k) == int(ev_x)
    assert int(dr_k) == 0


@pytest.mark.parametrize("law_name", ["gaussian", "exponential"])
def test_zero_spike_delivery_is_identity(law_name, rng):
    """All-padding event lists (no spikes anywhere) leave the ring
    bit-identical: every entry block is skipped."""
    law = gaussian_law() if law_name == "gaussian" else exponential_law()
    spec = _dist_spec(law)
    tabs = build_tables(spec, 0, 0, j_exc=0.4, j_inh=-2.0, seed=1)
    ring0 = jnp.asarray(rng.normal(size=(spec.d_ring, spec.n_local)),
                        jnp.float32)
    tiers = [(tabs["local"], jnp.zeros(spec.n_local), spec.active_cap_local)]
    tiers += [(tab, jnp.zeros(b["rows"]), spec.active_cap_band(b))
              for b, tab in zip(spec.halo_bands(), tabs["halo"])]
    ring_k, ev, dr = jax.jit(
        lambda r: event_delivery_banded(tiers, r, 2, spec.d_ring,
                                        interpret=True))(ring0)
    np.testing.assert_array_equal(np.asarray(ring_k), np.asarray(ring0))
    assert int(ev) == 0 and int(dr) == 0


def test_single_tier_fused_equals_deliver_events(rng):
    """ops.synaptic_accum_events (the fused single-tier wrapper) is a
    drop-in for core.synapses.deliver_events."""
    law = gaussian_law()
    d = TileDecomposition(grid=ColumnGrid(4, 4, 30), tiles_y=1, tiles_x=1,
                          radius=law.radius)
    spec = SynapseTableSpec(decomp=d, law=law, single_shard=True)
    tabs = build_tables(spec, 0, 0, j_exc=0.4, j_inh=-2.0, seed=2)
    spikes = jnp.asarray((rng.random(spec.n_local) < 0.1).astype(np.float32))
    ring0 = jnp.zeros((spec.d_ring, spec.n_local), jnp.float32)
    r1, e1, d1 = jax.jit(
        lambda r: event_delivery(tabs["local"], spikes, r, 1,
                                 spec.d_ring, spec.active_cap_local,
                                 interpret=True))(ring0)
    r2, e2, d2 = deliver_events(tabs["local"], spikes, ring0, 1,
                                spec.d_ring, spec.active_cap_local)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                               rtol=1e-5, atol=1e-5)
    assert int(e1) == int(e2) and int(d1) == int(d2)


def test_engine_auto_kernels_matches_xla_engine():
    """use_kernels="auto" on CPU (interpret-mode Pallas) reproduces the
    pure-XLA engine's spike trains exactly."""
    law = gaussian_law()
    d = TileDecomposition(grid=ColumnGrid(3, 3, 30), tiles_y=1, tiles_x=1,
                          radius=law.radius)
    cfg = EngineConfig(decomp=d, law=law, use_kernels="auto")
    cfg_x = dataclasses.replace(cfg, use_kernels=False)
    tabs = build_shard_tables(cfg)
    _, sp_k = jax.jit(lambda s: simulate(s, tabs, cfg, 60))(init_sim_state(cfg))
    _, sp_x = jax.jit(lambda s: simulate(s, tabs, cfg_x, 60))(
        init_sim_state(cfg_x))
    np.testing.assert_array_equal(np.asarray(sp_k), np.asarray(sp_x))


def test_delivery_plan_shapes():
    """The spec's kernel-facing plan matches the materialized tables."""
    law = exponential_law()
    spec = _dist_spec(law)
    plan = spec.delivery_plan()
    tabs = build_tables(spec, 1, 1, j_exc=0.4, j_inh=-2.0, seed=0)
    tiers = [tabs["local"]] + list(tabs["halo"])
    assert len(plan) == len(tiers)
    assert plan[0].rows == spec.n_local
    assert spec.band_caps() == [p.cap for p in plan[1:]]
    for p, tab in zip(plan, tiers):
        assert tab["tgt"].shape == (p.rows + 1, p.cap)
        assert p.active_cap <= p.rows + 1
        assert p.entries == p.active_cap * p.cap
        assert p.entries_padded >= p.entries
        assert p.entries_padded % LANES == 0
    # a compressed build's realized caps ride in its storage descriptor,
    # and the plan sized from it matches the truncated tables
    from repro.core.synapses import compress_tables
    ctabs = compress_tables(tabs)
    cplan = spec.delivery_plan(ctabs.storage)
    for p, tab in zip(cplan, [ctabs["local"]] + list(ctabs["halo"])):
        assert tab["tgt"].shape == (p.rows + 1, p.cap)


def test_entry_geometry_contract():
    """The spec's lane-packed launch geometry is consistent with its
    per-tier plan and with the kernel layout constants."""
    spec = _dist_spec(exponential_law())
    plan = spec.delivery_plan()
    geo = spec.entry_geometry()
    assert geo.lanes == LANES and geo.entry_block == ENTRY_BLOCK
    assert geo.entries == sum(p.entries_padded for p in plan)
    assert geo.entries_padded % ENTRY_BLOCK == 0
    assert geo.entries_padded >= max(geo.entries, ENTRY_BLOCK)
    assert geo.n_blocks == geo.entries_padded // ENTRY_BLOCK
    assert geo.packed_shape == (geo.entries_padded // LANES, LANES)


def test_plan_mismatch_is_rejected(rng):
    """A tier that does not match its delivery plan fails loudly (the
    plan is the spec contract the engines compile against)."""
    spec = _single_spec(gaussian_law(), n_per_col=12)
    tabs = build_tables(spec, 0, 0, j_exc=0.4, j_inh=-2.0, seed=0)
    ring0 = jnp.zeros((spec.d_ring, spec.n_local), jnp.float32)
    tiers = [(tabs["local"], jnp.zeros(spec.n_local),
              spec.active_cap_local)]
    plan = spec.delivery_plan()
    bad = [dataclasses.replace(plan[0], cap=plan[0].cap + 1)]
    with pytest.raises(ValueError, match="does not match"):
        event_delivery_banded(tiers, ring0, 0, spec.d_ring, plan=bad,
                              interpret=True)
    with pytest.raises(ValueError, match="plan has"):
        event_delivery_banded(tiers, ring0, 0, spec.d_ring,
                              plan=plan + plan, interpret=True)
    # and the matching plan goes through the lane-packed kernel cleanly
    ring_k, _, _ = jax.jit(
        lambda r: event_delivery_banded(tiers, r, 0, spec.d_ring,
                                        plan=plan, interpret=True))(ring0)
    np.testing.assert_array_equal(np.asarray(ring_k), np.asarray(ring0))


# ---------------------------------------------------------------------------
# Lane-packed layout edge cases: ragged n_local / partial entry blocks
# ---------------------------------------------------------------------------

def _single_spec(law, grid=5, n_per_col=9, rate_cap=25.0):
    d = TileDecomposition(grid=ColumnGrid(grid, grid, n_per_col),
                          tiles_y=1, tiles_x=1, radius=law.radius)
    return SynapseTableSpec(decomp=d, law=law, rate_cap_hz=rate_cap,
                            single_shard=True)


@pytest.mark.parametrize("grid,n_per_col", [
    (5, 9),     # n_local = 225: not a multiple of LANES (128)
    (10, 45),   # n_local = 4500: > TILE_N and not a multiple of it
])
def test_ragged_n_local_matches_xla_and_ref(grid, n_per_col, rng):
    """n_local that fills neither the lane dim nor the ring tiling:
    kernel vs deliver_events vs the jnp oracle, random initial ring."""
    law = gaussian_law()
    spec = _single_spec(law, grid=grid, n_per_col=n_per_col)
    tabs = build_tables(spec, 0, 0, j_exc=0.4, j_inh=-2.0, seed=5)
    spikes = jnp.asarray(
        (rng.random(spec.n_local) < 0.08).astype(np.float32))
    ring0 = jnp.asarray(rng.normal(size=(spec.d_ring, spec.n_local)),
                        jnp.float32)
    cap = spec.active_cap_local
    r_k, e_k, d_k = jax.jit(
        lambda r: event_delivery(tabs["local"], spikes, r, 3, spec.d_ring,
                                 cap, interpret=True))(ring0)
    r_x, e_x, d_x = deliver_events(tabs["local"], spikes, ring0, 3,
                                   spec.d_ring, cap)
    idx, _ = compact_events(spikes, spec.n_local, cap)
    r_r = ref.synaptic_accum_ref(idx, 3, tabs["local"]["tgt"],
                                 tabs["local"]["w"],
                                 tabs["local"]["dslot"], ring0)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_x),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                               rtol=1e-5, atol=1e-5)
    assert int(e_k) == int(e_x) and int(d_k) == int(d_x) == 0


def test_active_cap_overflow_drops_like_xla(rng):
    """More spiking rows than the event list holds: the kernel delivers
    the same truncated prefix as the XLA path and reports the same
    drop count."""
    law = gaussian_law()
    spec = _single_spec(law)
    tabs = build_tables(spec, 0, 0, j_exc=0.4, j_inh=-2.0, seed=7)
    spikes = jnp.asarray(
        (rng.random(spec.n_local) < 0.5).astype(np.float32))
    n_spk = int(np.asarray(spikes).sum())
    cap = max(n_spk // 3, 1)           # force overflow
    assert n_spk > cap
    ring0 = jnp.asarray(rng.normal(size=(spec.d_ring, spec.n_local)),
                        jnp.float32)
    r_k, e_k, d_k = jax.jit(
        lambda r: event_delivery(tabs["local"], spikes, r, 1, spec.d_ring,
                                 cap, interpret=True))(ring0)
    r_x, e_x, d_x = deliver_events(tabs["local"], spikes, ring0, 1,
                                   spec.d_ring, cap)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_x),
                               rtol=1e-5, atol=1e-5)
    assert int(d_k) == int(d_x) == n_spk - cap
    assert int(e_k) == int(e_x)


def test_partial_last_block_and_lane(rng):
    """Entry counts that fill neither the last lane (E % 128 != 0) nor
    the last lane-packed block (E % ENTRY_BLOCK != 0) deliver exactly;
    the trailing padding is skipped, not scattered."""
    rows, cap, d_ring, n_local = 11, 7, 4, 150
    assert (rows + 1) * cap % LANES != 0
    tgt = jnp.asarray(rng.integers(0, n_local, (rows + 1, cap)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(rows + 1, cap)), jnp.float32)
    w = w.at[-1].set(0)
    ds = jnp.asarray(rng.integers(0, d_ring, (rows + 1, cap)), jnp.int8)
    ring = jnp.asarray(rng.normal(size=(d_ring, n_local)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, rows + 1, 5), jnp.int32)
    got = synaptic_accum_pallas(idx, 2, tgt, w, ds, ring, interpret=True)
    want = ref.synaptic_accum_ref(idx, 2, tgt, w, ds, ring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Weight-stream dtype: one bfloat16 MXU pass vs float32 at HIGHEST
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("law_name", ["gaussian", "exponential"])
def test_bf16_stream_matches_float32_copy_bitwise(law_name, rng):
    """Banded delivery over bfloat16 tables (one bfloat16 pass a lane
    row) is bit-identical to the same call over a float32 copy of the
    tables (``Precision.HIGHEST``).  Entry 1 of every row repeats entry
    0's target and delay (duplicate targets within a lane row), and the
    ring spans two ring tiles with every other entry moved one tile up
    at the same in-tile offset, so each tile sees out-of-tile entries
    whose fused row id aliases a live one."""
    law = gaussian_law() if law_name == "gaussian" else exponential_law()
    spec = dataclasses.replace(
        _dist_spec(law, n_per_col=20 if law_name == "gaussian" else 12),
        weight_dtype="bfloat16")
    tabs = build_tables(spec, 1, 1, j_exc=0.4, j_inh=-2.0, seed=3)
    n_wide = TILE_N + spec.n_local

    def remap(tab):
        tgt = np.asarray(tab["tgt"]).astype(np.int32)
        ds = np.asarray(tab["dslot"]).copy()
        tgt[:, 1], ds[:, 1] = tgt[:, 0], ds[:, 0]
        tgt[:, 2::2] += TILE_N
        return dict(tab, tgt=jnp.asarray(tgt), dslot=jnp.asarray(ds))

    bf16 = [remap(t) for t in [tabs["local"]] + list(tabs["halo"])]
    assert all(t["w"].dtype == jnp.bfloat16 for t in bf16)
    f32 = [dict(t, w=t["w"].astype(jnp.float32)) for t in bf16]
    caps = [spec.active_cap_local] + [spec.active_cap_band(b)
                                      for b in spec.halo_bands()]
    spikes = [jnp.asarray((rng.random(spec.n_local) < 0.08)
                          .astype(np.float32))] + _band_spikes(spec, rng)
    ring0 = jnp.asarray(rng.normal(size=(spec.d_ring, n_wide)), jnp.float32)

    def deliver(tables):
        tiers = list(zip(tables, spikes, caps))
        return jax.jit(lambda r: event_delivery_banded(
            tiers, r, 5, spec.d_ring, interpret=True))(ring0)

    ring_b, ev_b, _ = deliver(bf16)
    ring_f, ev_f, _ = deliver(f32)
    np.testing.assert_array_equal(np.asarray(ring_b), np.asarray(ring_f))
    assert int(ev_b) == int(ev_f) > 0
    assert not np.array_equal(np.asarray(ring_b), np.asarray(ring0))
