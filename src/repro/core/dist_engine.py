"""Distributed DPSNN step: shard_map over the TPU mesh + halo exchange.

The DPSNN process <-> column-set mapping becomes: one mesh shard owns one
``tile_h x tile_w`` rectangle of cortical columns.  Every state / table
array carries two leading *tile* dims ``(TY, TX)`` sharded over the mesh
axes -- ``("data", "model")`` on the single-pod 16x16 mesh, and
``(("pod","data"), "model")`` on the multi-pod 2x16x16 mesh (the pod axis
splits the slab's y dimension further, exactly like adding more rows of
MPI processes in DPSNN).

Step structure per shard (dt = 1 ms):

  1. read ring slot t, add external Poisson drive
  2. LIF+SFA update -> local spikes
  3. halo-exchange excitatory spike blocks (``ppermute`` strips)
  4. event-driven delivery through local + per-band halo synapse tables
     into future ring slots

The per-step spike exchange is the paper's communication cost: Gaussian
law -> radius 3 halo, exponential law -> radius 10 halo.  Everything else
is local.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.telemetry import NULL, Telemetry
from .engine import (EngineConfig, deliver_event_tiers, external_drive,
                     init_sim_state, plastic_delivery_stdp)
from .halo import exchange_halo_2d, pack_bits, unpack_bits
from .neuron import lif_sfa_step
from .synapses import (SynapseTables, TableStorage, build_tables,
                       compress_tables, deliver_gather_all)

AxisName = Union[str, Tuple[str, ...]]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SimInputs:
    """The non-donated inputs of the distributed sim function, named.

    ``make_sim_fn``'s second argument: synapse ``tables`` always,
    ``inv_slots`` (the stacked target-major inverse index) when the
    engine is plastic, ``gids`` (the stacked global-neuron-id maps)
    when a recorder is attached.  Replaces the old positional
    ``sim(state, tables[, inv_slots][, gids])`` sprawl -- unused fields
    stay ``None`` and vanish from the pytree, so sharding/in_specs
    trees built with the same ``None``s always line up.
    """
    tables: Any
    inv_slots: Any = None
    gids: Any = None

    def tree_flatten(self):
        return (self.tables, self.inv_slots, self.gids), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Distribution settings layered on an EngineConfig.

    ``ensemble_seeds``: member state seeds for an ensemble run.  When
    set, every state leaf grows a member axis at position 2 --
    ``(TY, TX, M, ...)``, *after* the sharded tile dims so the member
    axis rides unsharded (replicated layout specs stay ``pspec(ndim-2)``)
    -- and the per-shard scan is vmapped over it: one table
    realization (``engine.seed``), one compiled step, M realizations.
    Member m is bit-identical to a solo run with
    ``engine.state_seed = ensemble_seeds[m]``.
    """

    engine: EngineConfig
    axis_y: AxisName = "data"        # ("pod","data") on the multi-pod mesh
    axis_x: AxisName = "model"
    halo_mode: str = "strip"         # "strip" (exact) | "block" (naive)
    pack_spikes: bool = True         # bit-pack halo payload (1 bit/neuron)
    ensemble_seeds: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.ensemble_seeds is not None:
            seeds = tuple(int(s) for s in self.ensemble_seeds)
            if not seeds:
                raise ValueError("ensemble_seeds must be a non-empty "
                                 "tuple (or None for a solo run)")
            object.__setattr__(self, "ensemble_seeds", seeds)

    @property
    def tiles(self) -> Tuple[int, int]:
        d = self.engine.decomp
        return d.tiles_y, d.tiles_x

    @property
    def n_members(self) -> Optional[int]:
        """Ensemble width M, or None for a solo run."""
        if self.ensemble_seeds is None:
            return None
        return len(self.ensemble_seeds)

    def pspec(self, extra_dims: int = 0) -> P:
        return P(self.axis_y, self.axis_x, *([None] * extra_dims))


# ---------------------------------------------------------------------------
# Global (stacked) state / tables
# ---------------------------------------------------------------------------

def init_dist_state(cfg: DistConfig) -> dict:
    """Stack per-tile states into (TY, TX, ...) host arrays.

    Ensemble runs (``cfg.ensemble_seeds``) stack one such tree per
    member seed along axis 2: ``(TY, TX, M, ...)``.
    """
    ty, tx = cfg.tiles

    def init_tiles(e: EngineConfig):
        states = [[init_sim_state(e, y, x, seed_offset=y * tx + x)
                   for x in range(tx)] for y in range(ty)]

        def stack(path_leaves):
            return jnp.stack([jnp.stack(row) for row in path_leaves])

        flat = [[jax.tree.leaves(states[y][x]) for x in range(tx)]
                for y in range(ty)]
        treedef = jax.tree.structure(states[0][0])
        leaves = [stack([[flat[y][x][i] for x in range(tx)]
                         for y in range(ty)])
                  for i in range(len(flat[0][0]))]
        # PRNGKey leaves stack to (TY,TX,2) automatically via structure
        return jax.tree.unflatten(treedef, leaves)

    if cfg.ensemble_seeds is None:
        return init_tiles(cfg.engine)
    members = [init_tiles(dataclasses.replace(cfg.engine, state_seed=s))
               for s in cfg.ensemble_seeds]
    return jax.tree.map(lambda *ls: jnp.stack(ls, axis=2), *members)


def build_dist_tables(cfg: DistConfig, compress: bool = True,
                      telemetry: Telemetry = NULL
                      ) -> Tuple[SynapseTables, dict]:
    """Materialize all shards' synapse tables stacked on (TY, TX).

    Per-shard builds happen at the analytic caps (identical shapes, so
    stacking is trivial), then ``compress_tables`` truncates the
    all-padding trailing columns jointly across shards -- the realized
    caps are cross-shard maxima, so the compressed storage descriptor
    is identical on every shard (SPMD-safe).

    ``telemetry`` times each shard's host sampling and packing (with
    its copy to the default device) as ``setup.tables.sample`` and the
    stacking and compression as ``setup.tables.compress``.
    """
    ty, tx = cfg.tiles
    e = cfg.engine

    def sample(y, x):
        with telemetry.span("setup.tables.sample", tile=[y, x]):
            tab = build_tables(e.spec(), y, x, j_exc=e.lif.j_exc_mv,
                               j_inh=e.lif.j_inh_mv, seed=e.seed)
            telemetry.annotate(synapses=tab.stats["n_synapses"])
        return tab

    tabs = [[sample(y, x) for x in range(tx)] for y in range(ty)]
    stats = [[tabs[y][x].stats for x in range(tx)] for y in range(ty)]

    def stack_tree(trees):
        return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)

    with telemetry.span("setup.tables.compress"):
        rows = [stack_tree([tabs[y][x] for x in range(tx)])
                for y in range(ty)]
        out = stack_tree(rows)
        if compress:
            out = compress_tables(out)
    from .synapses import materialized_table_bytes
    out_stats = {
        "n_synapses": int(sum(s["n_synapses"] for r in stats for s in r)),
        "clipped": int(sum(s["clipped"] for r in stats for s in r)),
        "table_bytes_per_shard": materialized_table_bytes(out, ty * tx),
    }
    return out, out_stats


def abstract_dist_inputs(cfg: DistConfig,
                         storage: Optional[TableStorage] = None):
    """ShapeDtypeStructs for (state, tables) -- dry-run inputs, no alloc.

    ``storage``: the materialized tables' storage descriptor.  Leave it
    ``None`` for the spec's analytic (uncompressed) layout -- the
    dry-run case; pass ``tables.storage`` when shapes must match
    compressed tables (shardings, checkpoint restore).

    When the engine is plastic (``cfg.engine.stdp`` set) the state grows
    a ``plastic`` subtree -- per-tier synaptic weights plus the STDP
    pre/post traces -- because plastic weights are *dynamics*, carried
    through the scan and checkpointed with the neuron state.  The carry
    is then the *single* live float copy of the weights: the static
    ``tables`` argument only supplies the realization's structure
    (targets, delays, occupancy) and its ``w`` leaves fold down to the
    int8 plastic mask (``fold_plastic_tables``).  Pre-traces are
    local-tier only -- halo replicas arrive per step through the halo
    exchange, never stored.
    """
    ty, tx = cfg.tiles
    e = cfg.engine
    spec = e.spec()
    n_local = spec.n_local
    # member axis sits between the sharded tile dims and the per-shard
    # shape so pspec(ndim-2) keeps it unsharded/replicated-free
    mdim = () if cfg.n_members is None else (cfg.n_members,)

    def sd(shape, dt):
        return jax.ShapeDtypeStruct((ty, tx) + mdim + shape, dt)

    state = {
        "neuron": {"v": sd((n_local,), jnp.float32),
                   "c": sd((n_local,), jnp.float32),
                   "refrac": sd((n_local,), jnp.int32)},
        "i_ring": sd((e.d_ring, n_local), jnp.float32),
        "t": sd((), jnp.int32),
        "rng": sd((2,), jnp.uint32),
        "active": sd((n_local,), jnp.bool_),
        "metrics": {"spikes": sd((), jnp.float32),
                    "events": sd((), jnp.float32),
                    "dropped": sd((), jnp.float32)},
    }
    abst = spec.abstract_tables(storage)
    if e.stdp is not None:
        # carry abstracts read the *full-width* weight dtype before the
        # tables fold to masks below -- the carry is the live copy
        tiers = abst.tiers()
        state["plastic"] = {
            "w": [sd(t["w"].shape, t["w"].dtype) for t in tiers],
            "x_pre": [sd((tiers[0]["tgt"].shape[0],), jnp.float32)],
            "x_post": sd((n_local,), jnp.float32),
        }
        abst = fold_plastic_tables(abst)

    def lift(t):
        return {k: jax.ShapeDtypeStruct((ty, tx) + v.shape, v.dtype)
                for k, v in t.items()}

    tables = SynapseTables(lift(abst.local),
                           [lift(t) for t in abst.halo], abst.storage)
    return state, tables


def init_dist_plastic_state(cfg: DistConfig, tables: dict) -> dict:
    """Fresh plastic carry: weights copied from the stacked build tables
    (copies, never views -- the sim donates its state argument, and the
    static tables must survive every segment), traces at zero.

    ``tables`` must carry the *build weights* (float), not the folded
    int8 masks the device tables hold (``fold_plastic_tables``) -- the
    carry initialized here becomes the run's single live weight copy.
    The pre-trace is local-tier only: halo replicas are exchanged per
    step, never carried."""
    ty, tx = cfg.tiles
    n_local = cfg.engine.spec().n_local
    tiers = [tables["local"]] + list(tables["halo"])
    if any(np.dtype(t["w"].dtype) == np.int8 for t in tiers):
        raise ValueError(
            "init_dist_plastic_state needs the build-weight tables "
            "(float w); got int8-folded mask tables -- pass the host "
            "copy taken before fold_plastic_tables")
    from .stdp import check_weight_invariant
    check_weight_invariant(tiers, cfg.engine.stdp)
    m = cfg.n_members

    def member_w(t):
        w = np.asarray(t["w"])
        if m is None:
            return jnp.asarray(w)
        # every member starts from the same build realization; weights
        # diverge per member through the carried STDP dynamics
        return jnp.asarray(np.ascontiguousarray(np.broadcast_to(
            w[:, :, None], (ty, tx, m) + w.shape[2:])))

    mdim = () if m is None else (m,)
    pre_rows = tiers[0]["tgt"].shape[-2]
    return {
        "w": [member_w(t) for t in tiers],
        "x_pre": [jnp.zeros((ty, tx) + mdim + (pre_rows,), jnp.float32)],
        "x_post": jnp.zeros((ty, tx) + mdim + (n_local,), jnp.float32),
    }


def fold_plastic_tables(tables: SynapseTables) -> SynapseTables:
    """Fold the static tables' weight leaves down to the int8 plastic
    mask (``w > 0``: excitatory-at-build = plastic, DPSNN's convention).

    Plastic runs read live weights exclusively from the scan carry
    (``init_dist_plastic_state``), so keeping the build weights resident
    on device would duplicate every weight tier at full width; after the
    fold the device tables cost 1 B/synapse for the mask instead of
    ``weight_dtype`` bytes.  Accepts abstract (ShapeDtypeStruct) or
    materialized tables; host-side for the latter."""

    def fold(t):
        w = t["w"]
        if isinstance(w, jax.ShapeDtypeStruct):
            return dict(t, w=jax.ShapeDtypeStruct(w.shape, jnp.int8))
        return dict(t, w=jnp.asarray((np.asarray(w) > 0).astype(np.int8)))

    return tables.replace(local=fold(tables.local),
                          halo=[fold(t) for t in tables.halo])


def build_dist_inverse_index(cfg: DistConfig, tables: dict):
    """Per-shard target-major inverse indices, stacked on (TY, TX).

    Each shard's index maps a local (target) neuron to the virtual flat
    slots of its incoming synapses across *all* tiers -- local and halo
    -- which is how a post-spike reaches the cross-tile synapses it must
    potentiate.  In-degree padding (``K_in``) differs per shard, so
    slots are padded to the max with each shard's ``total`` sentinel
    (already the "no synapse" value the LTP scatter masks on).

    Returns ``(slots, aux)``: ``slots`` a (TY, TX, n_local, K) int32
    array, ``aux`` the tier geometry (``bases``/``sizes``/``total``),
    identical across shards by construction.
    """
    from .stdp import build_inverse_index
    ty, tx = cfg.tiles
    n_local = cfg.engine.spec().n_local
    invs = []
    for y in range(ty):
        row = []
        for x in range(tx):
            tiers = [{k: np.asarray(v[y, x])
                      for k, v in tables["local"].items()}]
            tiers += [{k: np.asarray(v[y, x]) for k, v in t.items()}
                      for t in tables["halo"]]
            row.append(build_inverse_index(tiers, n_local))
        invs.append(row)
    aux = {"bases": invs[0][0]["bases"], "sizes": invs[0][0]["sizes"],
           "total": invs[0][0]["total"]}
    k_max = max(int(np.asarray(i["slots"]).shape[1])
                for r in invs for i in r)
    stacked = np.full((ty, tx, n_local, k_max), aux["total"], np.int32)
    for y in range(ty):
        for x in range(tx):
            s = np.asarray(invs[y][x]["slots"])
            stacked[y, x, :, :s.shape[1]] = s
    return jnp.asarray(stacked), aux


def dist_shardings(cfg: DistConfig, mesh: Mesh,
                   storage: Optional[TableStorage] = None):
    """NamedSharding pytrees matching ``abstract_dist_inputs``.

    Pass the materialized tables' ``storage`` so the table sharding
    tree shares the compressed tables' treedef (the descriptor is the
    pytree's static aux data)."""
    state, tables = abstract_dist_inputs(cfg, storage)

    def shard(leaf):
        return NamedSharding(mesh, cfg.pspec(len(leaf.shape) - 2))

    return jax.tree.map(shard, state), jax.tree.map(shard, tables)


# ---------------------------------------------------------------------------
# The distributed step / run
# ---------------------------------------------------------------------------

def make_sim_fn(cfg: DistConfig, mesh: Mesh, n_steps: int,
                record_rate: bool = True, recorder=None,
                storage: Optional[TableStorage] = None):
    """Build the jitted multi-shard simulation function.

    Returns ``sim(state, inputs) -> (state, per_step_spikes (TY,TX,S))``
    where ``inputs`` is a ``SimInputs`` pytree (``tables`` always,
    ``inv_slots`` for plastic engines, ``gids`` when recording).
    The whole ``n_steps`` scan runs inside one ``shard_map`` call so the
    halo exchanges appear as ``collective-permute`` ops inside the scan
    body -- one lowered program, n_steps iterations, no per-step dispatch.

    ``storage``: the tables' storage descriptor.  Defaults to the
    spec's analytic layout; pass ``tables.storage`` when driving
    compressed tables (``build_dist_tables`` output) so the delivery
    plan, the plastic weight shapes, and the shard_map in_specs all
    size against the materialized caps.

    The state argument is **donated**: callers must rebind to the
    returned state and drop every other reference (analyzer-checked:
    repro-lint's ``donation`` pass tracks this factory and flags reads
    of an already-donated argument).  For arbitrarily long
    runs, build once with ``n_steps = segment_steps`` and call
    repeatedly -- the state carries ``t``, so each call continues
    seamlessly where the last segment stopped (this is the segmented
    pattern ``runtime.sim_driver.SimDriver`` drives, with checkpoints
    between segments).

    ``recorder``: optional ``obs.record.RecorderSpec``.  When given the
    signature grows a trailing ``gids`` argument -- the stacked ``(TY,
    TX, n_local+1)`` global-neuron-id map (``obs.record.
    stacked_gid_maps``) -- and a trailing ``recorder_state`` output
    holding each shard's per-segment ``(step, gid)`` event buffer,
    valid-prefix ``count`` and overflow ``dropped`` counter, freshly
    zeroed at the start of every call (the host spooler drains it
    between segments).  Recording is a pure observer of the spike
    vector: dynamics and ``per_step`` outputs are bit-identical with or
    without it.

    **Plasticity** (``cfg.engine.stdp`` set): the STDP weight tables
    and pre/post trace arrays join the scan carry as
    ``state["plastic"]`` (see ``abstract_dist_inputs``) and
    ``inputs.inv_slots`` must carry the stacked per-shard target-major
    inverse index from ``build_dist_inverse_index``.  The carry is the
    single live weight copy -- ``inputs.tables`` supplies structure
    plus the int8 plastic mask (``fold_plastic_tables``) -- and each
    step routes through ``engine.plastic_delivery_stdp``: one fused
    Pallas launch applying delivery + LTD in the same pass over the
    entry stream when kernels are on (two-pass reference otherwise),
    then the shared LTP/clamp/trace finalize.  Cross-tile synapses
    depress from the halo spike vectors delivery consumed and
    potentiate through the inverse index; the per-band halo
    *pre-traces* they need arrive through the same halo exchange as
    the spikes (the owner's local trace, bit-identical to a
    locally-maintained replica), so only the local trace is carried.

    **Ensemble** (``cfg.ensemble_seeds`` set, M members): state leaves
    carry the member axis at position 2 (``init_dist_state``), the
    per-shard scan is vmapped over it inside the shard body -- one
    compiled step shared by all members and by every job with the same
    shapes -- and the outputs grow the matching axis: ``per_step``
    becomes ``(TY, TX, M, S)`` and every recorder leaf gains a leading
    member dim after the tile dims.  The tables/inverse-index/gid
    inputs stay member-free: all members share one realization.
    """
    e = cfg.engine
    spec = e.spec()
    d = e.decomp
    n_local, n_per_col = spec.n_local, spec.n_per_col
    n_exc = spec.n_exc_per_col
    bands = spec.halo_bands()
    band_idx = [jnp.asarray(spec.band_positions_exc(b)) for b in bands]
    radius = d.radius
    # Hoisted: the static lane-packed delivery sizing the kernel layer
    # compiles against (recomputing it per scan trace re-runs the
    # numpy fan-out analysis behind halo_bands()).
    plan = spec.delivery_plan(storage) if e.mode == "event" else None
    plastic = e.stdp is not None
    if plastic:
        from .stdp import _tier_sizes
        abst = spec.abstract_tables(storage)
        inv_bases, inv_sizes = _tier_sizes(abst.tiers())
        inv_total = (int(inv_bases[-1] + inv_sizes[-1])
                     if len(inv_sizes) else 0)
        pre_caps = [spec.active_cap_local] \
            + [spec.active_cap_band(b) for b in bands]

    def shard_step(state, tables, masks, inv):
        key, k_ext = jax.random.split(state["rng"])
        slot = state["t"] % e.d_ring
        i_now = state["i_ring"][slot] + external_drive(k_ext, n_local, e)
        if e.kernels_enabled:
            from ..kernels import ops as kops
            neuron, spikes = kops.lif_step(state["neuron"], i_now, e.lif,
                                           state["active"])
        else:
            neuron, spikes = lif_sfa_step(state["neuron"], i_now, e.lif,
                                          state["active"])
        i_ring = state["i_ring"].at[slot].set(0.0)

        # --- halo exchange: excitatory spikes only --------------------
        exc_blk = spikes.reshape(d.tile_h, d.tile_w, n_per_col)[..., :n_exc]
        payload = pack_bits(exc_blk) if cfg.pack_spikes else exc_blk
        region = exchange_halo_2d(payload, radius=radius,
                                  axis_y=cfg.axis_y, axis_x=cfg.axis_x,
                                  mode=cfg.halo_mode)
        if cfg.pack_spikes:
            region = unpack_bits(region, n_exc)
        region_flat = region.reshape(-1)
        halo_spikes = [region_flat[idx] for idx in band_idx]

        # --- delivery (plastic runs read weights from the carry) ------
        if plastic:
            pl = state["plastic"]
            tabs = {"local": dict(tables["local"], w=pl["w"][0]),
                    "halo": [dict(t, w=w) for t, w in
                             zip(tables["halo"], pl["w"][1:])]}
            # halo pre-trace replicas ride the same halo path as the
            # spikes: each band row's trace is the owner's local x_pre
            # carry at the start of this step -- bit-identical to the
            # replica a shard would maintain itself (same decay/increment
            # recurrence in the same order), so the carry only holds the
            # local tier.  Sent pre-decay; stdp decays uniformly in-step.
            xpre0 = pl["x_pre"][0]
            x_pre_tiers = [xpre0]
            if band_idx:
                xpre_blk = xpre0[:n_local].reshape(
                    d.tile_h, d.tile_w, n_per_col)[..., :n_exc]
                xpre_region = exchange_halo_2d(
                    xpre_blk, radius=radius, axis_y=cfg.axis_y,
                    axis_x=cfg.axis_x, mode=cfg.halo_mode).reshape(-1)
                sink = jnp.zeros((1,), jnp.float32)
                x_pre_tiers += [
                    jnp.concatenate([xpre_region[idx], sink])
                    for idx in band_idx]
            traces_in = {"x_pre": x_pre_tiers, "x_post": pl["x_post"]}
            tiers = [tabs["local"]] + list(tabs["halo"])
        else:
            tabs = tables
        m = state["metrics"]
        new_plastic = None
        if e.mode == "event":
            if plastic:
                i_ring, new_tiers, traces, ev, dr = plastic_delivery_stdp(
                    tiers, masks, inv, traces_in, [spikes] + halo_spikes,
                    spec, i_ring, slot, e, plan)
                new_plastic = {"w": [t["w"] for t in new_tiers],
                               "x_pre": traces["x_pre"][:1],
                               "x_post": traces["x_post"]}
            else:
                i_ring, ev, dr = deliver_event_tiers(
                    tabs, spikes, halo_spikes, spec, i_ring, slot,
                    e.d_ring, e.kernels_enabled, plan=plan)
        else:
            i_ring = deliver_gather_all(tabs["local"], spikes, i_ring,
                                        slot, e.d_ring)
            ev = jnp.sum(tabs["local"]["nnz"][:n_local].astype(jnp.float32)
                         * spikes)
            dr = jnp.zeros((), jnp.float32)
            for tab, spk in zip(tabs["halo"], halo_spikes):
                i_ring = deliver_gather_all(tab, spk, i_ring, slot, e.d_ring)
                ev += jnp.sum(tab["nnz"][:-1].astype(jnp.float32) * spk)
            if plastic:
                from .stdp import stdp_step
                new_tiers, traces = stdp_step(
                    tiers, masks, inv, traces_in, [spikes] + halo_spikes,
                    spikes, e.stdp, pre_caps, spec.active_cap_local)
                new_plastic = {"w": [t["w"] for t in new_tiers],
                               "x_pre": traces["x_pre"][:1],
                               "x_post": traces["x_post"]}

        new_state = {
            "neuron": neuron, "i_ring": i_ring, "t": state["t"] + 1,
            "rng": key, "active": state["active"],
            "metrics": {"spikes": m["spikes"] + jnp.sum(spikes),
                        "events": m["events"] + ev,
                        "dropped": m["dropped"] + dr},
        }
        if new_plastic is not None:
            new_state["plastic"] = new_plastic
        return new_state, spikes

    abs_state, abs_tables = abstract_dist_inputs(cfg, storage)
    state_sp = jax.tree.map(
        lambda leaf: cfg.pspec(len(leaf.shape) - 2), abs_state)
    table_sp = jax.tree.map(
        lambda leaf: cfg.pspec(len(leaf.shape) - 2), abs_tables)

    from ..parallel.compat import shard_map

    if recorder is not None:
        from ..obs.record import init_recorder_state, record_step

    n_members = cfg.n_members

    def shard_body(state_blk, inputs_blk):
        state = jax.tree.map(lambda a: a[0, 0], state_blk)
        tables = jax.tree.map(lambda a: a[0, 0], inputs_blk.tables)
        masks = inv = None
        if plastic:
            from .stdp import plastic_masks
            inv = {"slots": inputs_blk.inv_slots[0, 0], "bases": inv_bases,
                   "sizes": inv_sizes, "total": inv_total}
            masks = plastic_masks([tables["local"]] + list(tables["halo"]))
        if recorder is not None:
            gids = inputs_blk.gids[0, 0]

        def run_member(member_state):
            """Scan one realization's carry; tables/gids close over
            unbatched, so under vmap every member shares them."""
            if recorder is not None:
                def body(carry, _):
                    st, rec = carry
                    new_state, spikes = shard_step(st, tables, masks, inv)
                    rec = record_step(rec, spikes, gids, st["t"], recorder)
                    return (new_state, rec), jnp.sum(spikes)

                (st, rec), per_step = jax.lax.scan(
                    body, (member_state, init_recorder_state(recorder)),
                    None, length=n_steps)
                return st, per_step, rec

            def body(carry, _):
                st, spikes = shard_step(carry, tables, masks, inv)
                return st, jnp.sum(spikes)

            st, per_step = jax.lax.scan(body, member_state, None,
                                        length=n_steps)
            return st, per_step, None

        if n_members is None:
            state, per_step, rec = run_member(state)
        else:
            # one trace, M member carries: the halo ppermutes inside are
            # batched per member (vmap-of-collective is bit-identical to
            # per-member solo exchanges; tested both laws)
            state, per_step, rec = jax.vmap(run_member)(state)
        lift = lambda a: a[None, None]                          # noqa: E731
        out = (jax.tree.map(lift, state),
               per_step[None, None] if record_rate else None)
        if recorder is not None:
            out += (jax.tree.map(lift, rec),)
        return out

    member_dims = 0 if n_members is None else 1
    inputs_sp = SimInputs(
        tables=table_sp,
        inv_slots=cfg.pspec(2) if plastic else None,   # inverse-index slots
        gids=cfg.pspec(1) if recorder is not None else None)  # gid maps
    in_specs = [state_sp, inputs_sp]
    out_specs = [state_sp,
                 cfg.pspec(1 + member_dims) if record_rate else None]
    if recorder is not None:
        out_specs.append(jax.tree.map(
            lambda leaf: cfg.pspec(leaf.ndim + member_dims),
            init_recorder_state(recorder)))
    mapped = shard_map(shard_body, mesh=mesh,
                       in_specs=tuple(in_specs),
                       out_specs=tuple(out_specs))
    # Pin the state output's shardings to the input's NamedShardings:
    # XLA's propagation may legally mark some outputs replicated (it
    # does under the ensemble vmap), and a donated output fed back with
    # a different-but-equivalent sharding than the first call's input
    # would recompile the segment on its second invocation.
    out_shardings = [jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                  state_sp), None]
    if recorder is not None:
        out_shardings.append(None)
    return jax.jit(mapped, donate_argnums=(0,),
                   out_shardings=tuple(out_shardings))


def simulate(cfg: DistConfig, mesh: Mesh, n_steps: int, timed: bool = False):
    """Convenience driver (small configs): build, run, report.

    ``timed=True`` runs a warm-up segment first (compile excluded) and
    reports ``elapsed_s`` for a second same-length segment.
    """
    import time

    if cfg.engine.stdp is not None:
        raise ValueError(
            "simulate() is the static convenience driver; plastic runs "
            "carry their weight tables through checkpoints -- drive them "
            "via runtime.sim_driver.SimDriver (CLI: repro.launch.sim "
            "--plastic)")
    state = init_dist_state(cfg)
    tables, stats = build_dist_tables(cfg)
    sharding_state, sharding_tables = dist_shardings(cfg, mesh,
                                                     tables.storage)
    state = jax.device_put(state, sharding_state)
    tables = jax.device_put(tables, sharding_tables)
    sim = make_sim_fn(cfg, mesh, n_steps, storage=tables.storage)
    inputs = SimInputs(tables=tables)
    elapsed = None
    # ``sim`` donates its state argument (donate_argnums=(0,)): always
    # rebind to the returned state and keep no other reference, or a
    # later read would touch a donated buffer.
    state, per_step = sim(state, inputs)
    if timed:
        jax.block_until_ready(per_step)
        before = float(jnp.sum(state["metrics"]["events"]))
        t0 = time.perf_counter()
        state, per_step = sim(state, inputs)
        jax.block_until_ready(per_step)
        elapsed = time.perf_counter() - t0
    n_active = float(jnp.sum(state["active"]))
    spikes = float(jnp.sum(state["metrics"]["spikes"]))
    total_steps = n_steps * (2 if timed else 1)
    sim_sec = total_steps * cfg.engine.lif.dt_ms * 1e-3
    out = {
        "state": state,
        "per_step_spikes": per_step,
        "stats": stats,
        "rate_hz": spikes / max(n_active, 1.0) / max(sim_sec, 1e-9),
        "events": float(jnp.sum(state["metrics"]["events"])),
        "dropped": float(jnp.sum(state["metrics"]["dropped"])),
    }
    if timed:
        out["elapsed_s"] = elapsed
        out["events_timed"] = out["events"] - before
    return out
