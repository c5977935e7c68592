"""Single-shard DPSNN engine: time-driven outer loop, event-driven delivery.

One engine instance simulates one tile of the column grid.  The
distributed engine (``dist_engine.py``) runs this per-shard body inside a
``shard_map`` with a halo exchange supplying remote spikes.

Step structure (dt = 1 ms):

  1. read the delayed-current ring slot for t, add external Poisson drive
  2. LIF+SFA update -> spikes
  3. zero the consumed ring slot
  4. deliver local+halo spikes through the synapse tables into future
     ring slots (event mode: cost ~ spikes x fan-out = synaptic events)

State is a pytree; ``simulate`` is a ``lax.scan`` and jit-compatible.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .connectivity import (ConnectivityLaw, EXTERNAL_RATE_HZ,
                           EXTERNAL_SYNAPSES)
from .grid import TileDecomposition
from .neuron import LIFParams, init_state, lif_sfa_step
from .synapses import (SynapseTableSpec, SynapseTables, build_tables,
                       compress_tables, deliver_events, deliver_gather_all,
                       with_local_tier)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    decomp: TileDecomposition
    law: ConnectivityLaw
    lif: LIFParams = LIFParams()
    d_ring: int = 8
    mode: str = "event"              # "event" | "gather_all"
    ext_synapses: int = EXTERNAL_SYNAPSES
    ext_rate_hz: float = EXTERNAL_RATE_HZ
    rate_cap_hz: float = 100.0
    cap_headroom: float = 8.0        # event-list sizing (perf knob)
    seed: int = 0
    # Weight *storage* dtype.  bfloat16 by default: sampled weights are
    # quantized to this dtype at build time (realization v3) and every
    # delivery sum accumulates in float32 (the kernels contract the
    # bfloat16 stream in one exact MXU pass), so event delivery is
    # bit-identical to a float32 copy of the same tables while the
    # weight array -- the largest memory term after target ids --
    # halves.  Plastic runs override to float32 (see ``spec()``).
    weight_dtype: str = "bfloat16"
    # Pallas kernel routing for LIF + event delivery:
    #   "auto" (default) -- kernels everywhere: compiled on TPU,
    #       interpret-mode on CPU/GPU so every environment exercises the
    #       identical kernel code path;
    #   True  -- same as "auto" (kept for older call sites);
    #   False -- pure-XLA reference path (deliver_events / lif_sfa_step).
    use_kernels: Union[bool, str] = "auto"
    stdp: object = None              # Optional[STDPParams]; plastic when set
    # Seed for the *state* realization (membrane init + per-step Poisson
    # drive).  ``None`` (default) follows ``seed``, which also fixes the
    # synapse-table realization.  Ensemble runs share one table
    # realization (``seed``) across members while varying ``state_seed``
    # per member, so ensemble member m is bit-identical to a solo run
    # with the same ``seed`` and ``state_seed=member_seed_m``.
    state_seed: Optional[int] = None

    @property
    def state_seed_value(self) -> int:
        return self.seed if self.state_seed is None else self.state_seed

    @property
    def kernels_enabled(self) -> bool:
        if isinstance(self.use_kernels, str):
            if self.use_kernels != "auto":
                raise ValueError(
                    f"use_kernels={self.use_kernels!r}: expected 'auto' "
                    "or a bool")
            return True
        return bool(self.use_kernels)

    def delivery_w_dtype(self, weight_dtype: str) -> str:
        """The dtype event delivery contracts weights stored as
        ``weight_dtype`` in: the kernels keep bfloat16 (one MXU pass a
        lane row) and widen the rest to float32; the XLA path widens
        every weight."""
        if self.mode == "event" and self.kernels_enabled:
            from ..kernels.synaptic_accum import weight_stream_dtype
            return weight_stream_dtype(weight_dtype).name
        return "float32"

    def spec(self) -> SynapseTableSpec:
        single = self.decomp.tiles_y == 1 and self.decomp.tiles_x == 1
        # plastic runs keep band rows for every stencil-reachable halo
        # column (floor 0.0): the learned realization must relay across
        # tilings without a floor-dropped column orphaning its weights.
        # They also force float32 weights: STDP increments (a_plus ~
        # 5e-3 of j_exc) fall below the bfloat16 ulp at typical weight
        # magnitudes and would silently round away.
        plastic = self.stdp is not None
        return SynapseTableSpec(
            decomp=self.decomp, law=self.law, d_ring=self.d_ring,
            dt_ms=self.lif.dt_ms, rate_cap_hz=self.rate_cap_hz,
            cap_headroom=self.cap_headroom,
            weight_dtype="float32" if plastic else self.weight_dtype,
            single_shard=single,
            halo_floor=0.0 if plastic else 0.5)


def init_sim_state(cfg: EngineConfig, tile_y: int = 0, tile_x: int = 0,
                   seed_offset: int = 0) -> dict:
    spec = cfg.spec()
    n_local = spec.n_local
    sseed = cfg.state_seed_value
    rng = np.random.default_rng(
        np.random.SeedSequence([sseed, 7 + seed_offset, tile_y, tile_x]))
    neuron = init_state(n_local, cfg.lif, rng)
    active_cols = cfg.decomp.active_mask(tile_y, tile_x).ravel()
    active = np.repeat(active_cols, cfg.decomp.grid.n_per_column)
    return {
        "neuron": neuron,
        "i_ring": jnp.zeros((cfg.d_ring, n_local), dtype=jnp.float32),
        "t": jnp.zeros((), dtype=jnp.int32),
        "rng": jax.random.PRNGKey(sseed + 1000 * seed_offset
                                  + 17 * tile_y + tile_x),
        "active": jnp.asarray(active),
        "metrics": {
            "spikes": jnp.zeros((), jnp.float32),
            "events": jnp.zeros((), jnp.float32),
            "dropped": jnp.zeros((), jnp.float32),
        },
    }


def init_ensemble_state(cfg: EngineConfig, seeds) -> dict:
    """Stack ``len(seeds)`` member states on a leading ensemble axis.

    Member ``m`` is ``init_sim_state`` of the same config with
    ``state_seed=seeds[m]`` -- every member shares the table realization
    (``cfg.seed``) but draws its own membrane init and Poisson stream,
    so ``simulate(..., ensemble=M)`` over this state reproduces each
    member's solo run bit-for-bit.
    """
    seeds = tuple(int(s) for s in seeds)
    if not seeds:
        raise ValueError("ensemble needs at least one member seed")
    members = [init_sim_state(dataclasses.replace(cfg, state_seed=s))
               for s in seeds]
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *members)


def build_shard_tables(cfg: EngineConfig, tile_y: int = 0,
                       tile_x: int = 0,
                       compress: bool = True) -> SynapseTables:
    """Materialize (and by default compress) one shard's tables.

    Compression truncates all-padding trailing columns per tier
    (value-exact; see ``synapses.compress_tables``).  The returned
    tables carry the realized ``storage`` descriptor -- pass it to
    anything that sizes launches from the spec (``delivery_plan``,
    ``make_sim_fn``, shardings).
    """
    spec = cfg.spec()
    tabs = build_tables(spec, tile_y, tile_x, j_exc=cfg.lif.j_exc_mv,
                        j_inh=cfg.lif.j_inh_mv, seed=cfg.seed)
    return compress_tables(tabs) if compress else tabs


def external_drive(rng_key, n_local: int, cfg: EngineConfig):
    """Poisson thalamo-cortical drive: ext_synapses firing at ext_rate."""
    lam = cfg.ext_synapses * cfg.ext_rate_hz * 1e-3 * cfg.lif.dt_ms
    events = jax.random.poisson(rng_key, lam, (n_local,))
    return events.astype(jnp.float32) * cfg.lif.j_ext_mv


def deliver_event_tiers(tables, spikes, halo_band_spikes, spec, i_ring,
                        slot, d_ring: int, kernels_enabled: bool,
                        plan: Optional[list] = None):
    """Event-driven delivery of the local tier + every halo band.

    The single source of truth for both step bodies (single-shard
    ``step`` and the distributed ``shard_step``): tier sizing comes from
    ``spec.delivery_plan()`` (precompute it once per trace and pass it
    as ``plan``), and the kernel path hands all tiers plus the plan to
    one fused ``synaptic_accum_banded`` launch -- the kernel validates
    its tables against the plan's lane-packed entry geometry -- while
    the XLA path loops ``deliver_events`` per tier.  Returns (i_ring,
    events, dropped) as f32 scalars.
    """
    if plan is None:
        plan = spec.delivery_plan(getattr(tables, "storage", None))
    halo = list(zip(plan[1:], tables["halo"], halo_band_spikes))
    if kernels_enabled:
        from ..kernels import ops as kops
        tiers = [(tables["local"], spikes, plan[0].active_cap)]
        tiers += [(tab, spk, p.active_cap) for p, tab, spk in halo]
        i_ring, ev, dr = kops.synaptic_accum_banded(
            tiers, i_ring, slot, d_ring, plan=plan)
        return i_ring, ev.astype(jnp.float32), dr.astype(jnp.float32)
    i_ring, ev, dr = deliver_events(
        tables["local"], spikes, i_ring, slot, d_ring,
        plan[0].active_cap)
    ev = ev.astype(jnp.float32)
    dr = dr.astype(jnp.float32)
    for p, tab, spk in halo:
        i_ring, ev_b, dr_b = deliver_events(
            tab, spk, i_ring, slot, d_ring, p.active_cap)
        ev = ev + ev_b.astype(jnp.float32)
        dr = dr + dr_b.astype(jnp.float32)
    return i_ring, ev, dr


def plastic_delivery_stdp(tiers, masks, inv, traces, spike_tiers, spec,
                          i_ring, slot, cfg: EngineConfig, plan):
    """One plastic update: event delivery + STDP over ``tiers``.

    The single source of truth for both plastic step bodies
    (``_run_plastic`` and the distributed ``shard_step``).  Routing:

      * kernels enabled and the shard fits the resident-ring kernel
        (``kernels.plastic_step.fused_supported``): ONE Pallas launch
        applies delivery and the LTD weight update in the same pass
        over the lane-packed entry stream, then the shared XLA
        ``stdp_ltp_finalize`` adds LTP / clamp / trace increments;
      * otherwise: the two-pass reference -- ``deliver_event_tiers``
        (which itself routes kernel vs XLA delivery) followed by the
        full ``stdp_step``.

    Both routes are bit-identical (kernel contract, tested at tier-1
    sizes).  ``tiers`` carry the *live* weights (the scan carry is the
    single weight source); ``traces`` is ``{"x_pre": [per tier],
    "x_post"}``.  Returns (i_ring, new_tiers, new_traces, events,
    dropped) with events/dropped as f32 scalars.
    """
    from .stdp import stdp_ltp_finalize, stdp_step
    p = cfg.stdp
    spikes_local = spike_tiers[0]
    post_cap = spec.active_cap_local
    if cfg.kernels_enabled:
        from ..kernels import ops as kops
        from ..kernels.plastic_step import fused_supported
        if fused_supported(spec.n_local):
            # decay first (updates read *previous* activity), exactly as
            # stdp_step does; the kernel consumes the decayed post trace
            x_pre_d = [xp * p.decay_plus for xp in traces["x_pre"]]
            x_post_d = traces["x_post"] * p.decay_minus
            tier_args = [(t, spk, tp.active_cap)
                         for t, spk, tp in zip(tiers, spike_tiers, plan)]
            i_ring, new_w, ev, dr = kops.plastic_step_banded(
                tier_args, masks, x_post_d, i_ring, slot, cfg.d_ring,
                -p.a_minus, plan=plan)
            new_tiers = [dict(t, w=w) for t, w in zip(tiers, new_w)]
            new_tiers, new_traces = stdp_ltp_finalize(
                new_tiers, masks, inv, x_pre_d, x_post_d, spike_tiers,
                spikes_local, p, post_cap)
            return (i_ring, new_tiers, new_traces,
                    ev.astype(jnp.float32), dr.astype(jnp.float32))
    tabs = {"local": tiers[0], "halo": list(tiers[1:])}
    i_ring, ev, dr = deliver_event_tiers(
        tabs, spikes_local, list(spike_tiers[1:]), spec, i_ring, slot,
        cfg.d_ring, cfg.kernels_enabled, plan=plan)
    new_tiers, new_traces = stdp_step(
        tiers, masks, inv, traces, spike_tiers, spikes_local, p,
        [tp.active_cap for tp in plan], post_cap)
    return i_ring, new_tiers, new_traces, ev, dr


def step(state: dict, tables: dict, cfg: EngineConfig,
         halo_band_spikes: Optional[list] = None, deliver: bool = True):
    """One simulation step.

    ``halo_band_spikes``: list of per-band (rows_b,) spike vectors for the
    halo excitatory sources this step (None when running single-shard).
    ``deliver=False`` stops after the LIF update and ring-slot consume --
    the plastic scan body uses it so delivery can run fused with the
    STDP update (``plastic_delivery_stdp``) instead of here.
    Returns (new_state, local_spikes).
    """
    spec = cfg.spec()
    n_local = spec.n_local
    plan = (spec.delivery_plan(getattr(tables, "storage", None))
            if cfg.mode == "event" and deliver else None)
    key, k_ext = jax.random.split(state["rng"])
    slot = state["t"] % cfg.d_ring

    i_now = state["i_ring"][slot] + external_drive(k_ext, n_local, cfg)
    if cfg.kernels_enabled:
        from ..kernels import ops as kops
        neuron, spikes = kops.lif_step(state["neuron"], i_now, cfg.lif,
                                       state["active"])
    else:
        neuron, spikes = lif_sfa_step(state["neuron"], i_now, cfg.lif,
                                      state["active"])

    i_ring = state["i_ring"].at[slot].set(0.0)

    halo_band_spikes = halo_band_spikes or []
    metrics = state["metrics"]
    if not deliver:
        metrics = dict(metrics, spikes=metrics["spikes"] + jnp.sum(spikes))
    elif cfg.mode == "event":
        i_ring, ev, dr = deliver_event_tiers(
            tables, spikes, halo_band_spikes, spec, i_ring, slot,
            cfg.d_ring, cfg.kernels_enabled, plan=plan)
        metrics = {
            "spikes": metrics["spikes"] + jnp.sum(spikes),
            "events": metrics["events"] + ev,
            "dropped": metrics["dropped"] + dr,
        }
    elif cfg.mode == "gather_all":
        i_ring = deliver_gather_all(tables["local"], spikes, i_ring, slot,
                                    cfg.d_ring)
        nnz_l = tables["local"]["nnz"][:n_local].astype(jnp.float32)
        ev = jnp.sum(nnz_l * spikes)
        for tab, spk in zip(tables["halo"], halo_band_spikes):
            i_ring = deliver_gather_all(tab, spk, i_ring, slot, cfg.d_ring)
            nnz_h = tab["nnz"][:-1].astype(jnp.float32)
            ev = ev + jnp.sum(nnz_h * spk)
        metrics = {
            "spikes": metrics["spikes"] + jnp.sum(spikes),
            "events": metrics["events"] + ev,
            "dropped": metrics["dropped"],
        }
    else:
        raise ValueError(f"unknown mode {cfg.mode}")

    new_state = {
        "neuron": neuron, "i_ring": i_ring, "t": state["t"] + 1,
        "rng": key, "active": state["active"], "metrics": metrics,
    }
    return new_state, spikes


def simulate(state: dict, tables, cfg: EngineConfig, n_steps: int,
             plasticity: Optional[dict] = None,
             record_spikes: bool = False, recorder=None,
             ensemble: Optional[int] = None):
    """Scan ``n_steps`` of single-shard simulation (no halo sources).

    The one entry point for both static and plastic runs:

      - ``plasticity=None`` (static): returns ``(state, out)`` where
        ``out`` is the per-step spike count, or the full spike raster
        with ``record_spikes=True``.
      - ``plasticity=init_plasticity(tables, cfg)``: STDP is applied
        each step and the synapse tables join the scan carry; returns
        ``((state, tables, traces), per_step_spike_counts)``.

    ``recorder``: optional ``obs.record.RecorderSpec`` (static runs
    only) -- when given, every spike is also appended as a
    ``(sim_step, global_neuron_id)`` event to a bounded buffer carried
    through the scan, and the return becomes ``(state, out,
    recorder_state)``.  Recording is a pure observer: the spike trains
    are bit-identical with it on or off.

    ``ensemble``: number of member realizations stacked on the leading
    axis of every ``state`` leaf (see ``init_ensemble_state``).  The
    solo scan is vmapped over the member axis -- one trace, one
    compiled step, M realizations sharing the same ``tables`` -- and
    every return leaf (final state, per-step outputs, recorder buffers,
    plastic tables/traces) grows the matching leading member axis.
    Member m's outputs are bit-identical to the solo run seeded with
    that member's ``state_seed``.
    """
    if ensemble is not None:
        m = int(ensemble)
        lead = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(state)}
        if lead != {m}:
            raise ValueError(
                f"ensemble={m} but state leading axes are {sorted(lead)}; "
                "build the state with init_ensemble_state(cfg, seeds)")
        return jax.vmap(lambda st: simulate(
            st, tables, cfg, n_steps, plasticity=plasticity,
            record_spikes=record_spikes, recorder=recorder))(state)
    if plasticity is not None:
        if recorder is not None or record_spikes:
            raise ValueError("plastic runs do not support recorder/"
                             "record_spikes (use the distributed driver)")
        return _run_plastic(state, tables, plasticity, cfg, n_steps)
    if recorder is not None:
        from ..obs.record import (init_recorder_state, record_step,
                                  tile_gid_map)
        gids = jnp.asarray(tile_gid_map(cfg.decomp, 0, 0))

        def body_rec(carry, _):
            st, rec = carry
            new_state, spikes = step(st, tables, cfg, halo_band_spikes=None)
            rec = record_step(rec, spikes, gids, st["t"], recorder)
            out = spikes if record_spikes else jnp.sum(spikes)
            return (new_state, rec), out

        (state, rec), out = jax.lax.scan(
            body_rec, (state, init_recorder_state(recorder)), None,
            length=n_steps)
        return state, out, rec

    def body(carry, _):
        new_state, spikes = step(carry, tables, cfg, halo_band_spikes=None)
        out = spikes if record_spikes else jnp.sum(spikes)
        return new_state, out

    return jax.lax.scan(body, state, None, length=n_steps)


def _run_plastic(state: dict, tables, stdp_aux: dict,
                 cfg: EngineConfig, n_steps: int):
    """Scan with STDP enabled: synapse tables join the carry.

    ``stdp_aux`` comes from ``init_plasticity`` (inverse index, masks,
    trace state).  Single-shard only: there is no halo source here, so
    only the local tier is stepped -- halo tiers in ``stdp_aux`` (a
    multi-tile config's tables) are ignored, exactly like delivery
    ignores them without halo spikes.  The distributed plastic path is
    ``dist_engine.make_sim_fn`` with ``EngineConfig.stdp`` set.

    Delivery and STDP run through ``plastic_delivery_stdp`` -- one
    fused Pallas launch when kernels are enabled, the two-pass
    reference otherwise.
    """
    if cfg.mode != "event":
        raise ValueError(
            f"plastic runs require mode='event' (got {cfg.mode!r}): the "
            "STDP update is event-driven on the same compaction as "
            "delivery")
    spec = cfg.spec()
    plan = spec.delivery_plan(getattr(tables, "storage", None))[:1]
    masks = stdp_aux["masks"][:1]
    traces_init = {"x_pre": stdp_aux["traces"]["x_pre"][:1],
                   "x_post": stdp_aux["traces"]["x_post"]}

    def body(carry, _):
        st, tabs, traces = carry
        slot = st["t"] % cfg.d_ring
        new_state, spikes = step(st, tabs, cfg, halo_band_spikes=None,
                                 deliver=False)
        i_ring, tiers, traces, ev, dr = plastic_delivery_stdp(
            [tabs["local"]], masks, stdp_aux["inv"], traces, [spikes],
            spec, new_state["i_ring"], slot, cfg, plan)
        m = new_state["metrics"]
        new_state = dict(new_state, i_ring=i_ring,
                         metrics=dict(m, events=m["events"] + ev,
                                      dropped=m["dropped"] + dr))
        tabs = with_local_tier(tabs, tiers[0])
        return (new_state, tabs, traces), jnp.sum(spikes)

    return jax.lax.scan(body, (state, tables, traces_init), None,
                        length=n_steps)


def init_plasticity(tables: dict, cfg: EngineConfig) -> dict:
    """Build the STDP auxiliaries (inverse index, plastic masks, traces).

    Covers every tier the tables carry -- local plus any halo bands --
    so post-spikes reach their cross-tile incoming synapses through the
    inverse index.  Single-shard tables have no halo tiers, so this
    reduces to the local-only index the plastic ``simulate`` path
    consumes; the distributed engine builds the same structures per
    shard via ``dist_engine.build_dist_inverse_index``.
    """
    from .stdp import (build_inverse_index, check_weight_invariant,
                       init_stdp_state, plastic_masks)

    tiers = [tables["local"]] + list(tables.get("halo", []))
    n_local = cfg.spec().n_local
    check_weight_invariant(tiers, cfg.stdp)
    return {
        "inv": build_inverse_index(tiers, n_local),
        "masks": plastic_masks(tiers),
        "traces": init_stdp_state(tiers, n_local),
    }


def firing_rate_hz(state: dict, cfg: EngineConfig,
                   n_steps: Optional[int] = None) -> float:
    """Mean firing rate over the simulated window (active neurons only).

    ``n_steps=None`` derives the window from the state's own step
    counter ``t`` -- correct for same-tiling resumed/segmented runs and
    for stacked ``(TY, TX, ...)`` distributed state (the metrics are
    per-tile partial sums; ``jnp.sum`` totals them).  NOT
    retile-proof: an elastic retile zeroes the per-tile metrics (the
    history moves to the checkpoint manifest), so for runs that may
    have retiled use ``SimDriver.firing_rate_hz``, which re-adds the
    manifest-carried base.
    """
    if n_steps is None:
        n_steps = int(np.asarray(jnp.max(state["t"])))
    n_active = float(np.asarray(jnp.sum(state["active"])))
    spikes = float(np.asarray(jnp.sum(state["metrics"]["spikes"])))
    sim_sec = n_steps * cfg.lif.dt_ms * 1e-3
    return spikes / max(n_active, 1.0) / max(sim_sec, 1e-9)
