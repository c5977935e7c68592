"""Resilient long-run SNN simulation driver (segmented + elastic).

DPSNN's production runs are long-lived distributed jobs resubmitted
across MPI geometries (companion scaling study, arXiv:1511.09325); this
driver gives the distributed SNN engine the same operational envelope:

  * the single ``n_steps`` ``lax.scan`` becomes fixed-size **segments**
    driven from the host -- one compiled program reused per segment,
    with the state donated segment-to-segment (no copy, no growth in
    peak memory with run length);
  * an ``AsyncCheckpointer`` snapshot between segments, labelled by the
    simulation step ``t`` (resume works across different segment
    sizes);
  * SIGTERM preemption, bounded-retry restore-and-replay and the
    straggler watchdog are inherited from the training runtime
    (``FaultTolerantLoop``);
  * **elastic re-tiling**: a run checkpointed on tiles ``(a, b)`` can
    resume on tiles ``(c, d)`` -- neuron state and the in-flight delay
    ring are permuted by global column id (``core.retile``) while the
    synapse tables are rebuilt deterministically for the new
    decomposition from the engine seed;
  * **spike recording** (``record_events=True``): the device-side
    recorder (``obs.record``) streams every spike as a ``(step, global
    neuron id)`` event into a bounded per-segment buffer, which the
    host spooler (``obs.spool``) drains asynchronously into sharded
    append-only logs under ``<ckpt_dir>/spool``.  Per-shard spool
    offsets ride in every checkpoint manifest, and every restore
    truncates the logs back to that frontier, so preemption/failure
    replay yields each event exactly once.  The spool is also the
    *only* per-step spike record the driver keeps: ``spike_counts()``
    reads it back (the former per-step host dict is gone -- it
    duplicated the spool and grew without bound on long runs);
  * **plasticity** (``dist_cfg.engine.stdp`` set): the STDP weight
    tables and pre/post traces ride in the scan carry
    (``state["plastic"]``, see ``core.dist_engine``), so every
    checkpoint snapshots the learned weights alongside the neuron
    state and a preempted plastic run resumes bit-identically.  Across
    an elastic retile the *realization itself* is relaid by global
    (pre, post) synapse id (``core.retile.retile_tables``) -- never
    re-sampled, which would silently discard all learning.  The
    checkpoint meta records the STDP parameters (a static checkpoint
    can never resume plastic, nor across an STDP-parameter change) and
    ``born_tiles``, the tiling the realization was sampled on, from
    which any later tiling's table layout is derived deterministically.

  * **ensembles** (``dist_cfg.ensemble_seeds`` set): the driver runs M
    member realizations through the one compiled segment function
    (state stacked on a member axis, see ``core.dist_engine``), drains
    each member's recorder rows into its own ``member_{m:03d}/`` spool
    stream, and carries the member seeds in the checkpoint meta --
    preempt→resume restores every member's carry and spool frontier
    exactly-once.  Elastic retiling of ensembles is refused (resume on
    the checkpointed tiling).

The tiling, grid, seed and connectivity law of the saved state ride
inside each checkpoint's manifest (atomic with the checkpoint), so a
resuming process detects a geometry change -- and refuses a silently
different model -- without guessing from array shapes.

Cumulative metric totals (spikes/events/dropped) are **global scalars**:
the manifest carries ``metric_base`` (totals lost to state zeroing at
an elastic retile) and ``metric_totals`` (base + current state sums),
and every total the driver reports adds the base back -- so totals are
identical whatever tiling history a run went through.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..checkpoint.store import (checkpoint_meta, latest_step,
                                refuse_meta_drift, restore_checkpoint)
from ..core.dist_engine import (DistConfig, SimInputs, abstract_dist_inputs,
                                build_dist_inverse_index, build_dist_tables,
                                dist_shardings, fold_plastic_tables,
                                init_dist_plastic_state, init_dist_state,
                                make_sim_fn)
from ..core.retile import (gather_synapse_stream, retile_config,
                           retile_plastic, retile_state, retile_tables)
from ..core.synapses import TableStorage, compress_tables
from ..obs.telemetry import NULL, Telemetry
from .driver import DriverConfig, FaultTolerantLoop, log

METRIC_KEYS = ("spikes", "events", "dropped")


def sim_fingerprint(dist_cfg: DistConfig, segment_steps: int, recorder,
                    storage) -> tuple:
    """Cache key for the compiled segment function.

    Everything that shapes the traced program is in the key; everything
    that only changes *values* is normalized out -- the seed (tables
    are data, not structure), the member seeds (only the ensemble width
    M is a shape), the state seed.  Two jobs differing only in seeds
    therefore share one compiled step when constructed with the same
    ``sim_cache`` dict -- the server's resident-mesh contract.
    """
    e = dataclasses.replace(dist_cfg.engine, seed=0, state_seed=None)
    seeds = dist_cfg.ensemble_seeds
    dc = dataclasses.replace(
        dist_cfg, engine=e,
        ensemble_seeds=None if seeds is None
        else tuple(range(len(seeds))))
    return (repr(dc), int(segment_steps), repr(recorder),
            repr(storage.meta() if storage is not None else None))


class SimDriver(FaultTolerantLoop):
    """Segmented, checkpointed distributed SNN simulation.

    ``run(n_steps)`` advances the simulation to step ``n_steps`` (rounded
    up to a whole segment) in segments of ``segment_steps``; the loop's
    step counter *is* the simulation time ``t``, so checkpoint labels
    and resume targets are sim steps, not segment indices.

    ``cfg.ckpt_every`` counts **segments** between checkpoints.
    ``allow_retile=True`` permits resuming a checkpoint written under a
    different tiling (state is relaid out by global column id; plastic
    weight tables by global synapse id).
    ``preempt_after_segments`` deterministically simulates a SIGTERM
    after that many segments (counted in this process) -- the driver
    checkpoints at the segment boundary and exits, exactly like the
    signal path.

    ``record_events=True`` turns on the spike observatory;
    ``record_capacity`` overrides the per-shard per-segment event
    buffer (default: the no-drop bound ``active_cap_local *
    segment_steps``; overflow is counted, never silent).  Recording is
    a pure observer -- spike trains are bit-identical with it on or
    off -- but for *complete* logs it must be enabled for the whole
    run: segments simulated with recording off are simply absent from
    the spool.
    """

    def __init__(self, cfg: DriverConfig, dist_cfg: DistConfig, mesh,
                 segment_steps: int,
                 allow_retile: bool = False,
                 fault_hook: Optional[Callable] = None,
                 preempt_after_segments: Optional[int] = None,
                 record_events: bool = False,
                 record_capacity: Optional[int] = None,
                 telemetry: Telemetry = NULL,
                 sim_cache: Optional[dict] = None,
                 job_meta: Optional[dict] = None):
        super().__init__(cfg, telemetry=telemetry)
        if segment_steps <= 0:
            raise ValueError(f"segment_steps={segment_steps} must be > 0")
        self.dist_cfg = dist_cfg
        self.mesh = mesh
        self.step_size = segment_steps
        self.allow_retile = allow_retile
        self.fault_hook = fault_hook
        self._preempt_after = preempt_after_segments
        self._segments_done = 0
        e = dist_cfg.engine
        self.plastic = e.stdp is not None
        self.n_members = dist_cfg.n_members
        self._job_meta = job_meta

        # ---- synapse tables ------------------------------------------
        # A plastic realization is *born* on one tiling and relaid to
        # every later one by global synapse id (re-sampling would build
        # a different network under the learned weights).  The birth
        # tiling rides in the checkpoint meta.
        with self.tel.span("setup.tables"):
            self._born_tiles = dist_cfg.tiles
            if self.plastic:
                last0 = latest_step(cfg.ckpt_dir)
                if last0 is not None:
                    born = checkpoint_meta(cfg.ckpt_dir,
                                           last0).get("born_tiles")
                    if born:
                        self._born_tiles = tuple(born)
            self._birth_tables = None
            if self.plastic and self._born_tiles != dist_cfg.tiles:
                from ..core.synapses import materialized_table_bytes
                born_cfg = retile_config(dist_cfg, *self._born_tiles)
                birth, self.table_stats = build_dist_tables(
                    born_cfg, telemetry=telemetry)
                with self.tel.span("setup.tables.retile"):
                    self._birth_tables = jax.tree.map(np.asarray, birth)
                    # relayed at the analytic caps, then compressed: the
                    # caps derive from the realized occupancy, which the
                    # relay preserves exactly, so any process resuming
                    # on this tiling reconstructs the identical storage
                    # descriptor
                    tables = compress_tables(retile_tables(
                        self._birth_tables, born_cfg.engine.decomp,
                        born_cfg.engine.spec(), e.decomp, e.spec()))
                ty, tx = dist_cfg.tiles
                self.table_stats = dict(
                    self.table_stats,
                    table_bytes_per_shard=materialized_table_bytes(
                        tables, ty * tx))
            else:
                tables, self.table_stats = build_dist_tables(
                    dist_cfg, telemetry=telemetry)
                if self.plastic:
                    self._birth_tables = jax.tree.map(np.asarray, tables)
            # the materialized (compressed) storage descriptor:
            # everything that sizes shapes from the spec -- shardings,
            # the delivery plan, plastic weight abstracts, checkpoint
            # meta -- goes through it
            self.storage = tables.storage
            self._state_sh, table_sh = dist_shardings(dist_cfg, mesh,
                                                      self.storage)
            self._tables_host = (jax.tree.map(np.asarray, tables)
                                 if self.plastic else None)
            if self.plastic:
                # the plastic carry is the single live weight copy; the
                # resident tables keep only the int8 plastic mask
                tables = fold_plastic_tables(tables)
            nbytes = (sum(a.nbytes for a in jax.tree.leaves(tables))
                      if self.tel.enabled else None)   # a span attribute
            with self.tel.span("setup.tables.place", bytes=nbytes):
                self.tables = jax.device_put(tables, table_sh)
                if self.tel.enabled:
                    # fenced only when traced, as segment.compute is
                    jax.block_until_ready(self.tables)
            self._inv_slots = None
            if self.plastic:
                with self.tel.span("setup.tables.inverse"):
                    slots, _ = build_dist_inverse_index(dist_cfg,
                                                        self._tables_host)
                    self._inv_slots = jax.device_put(
                        slots, NamedSharding(mesh, dist_cfg.pspec(2)))
                    # the birth-weight stream is constant over the
                    # driver's lifetime; gather it once for
                    # plastic_summary's drift stats
                    self._birth_stream = gather_synapse_stream(
                        self._tables_host, e.decomp, e.spec())

        # cumulative totals not represented in the (possibly retiled)
        # device state -- see module docstring
        self._metric_base = {k: 0.0 for k in METRIC_KEYS}
        # previous segment's cumulative totals: per-segment *deltas* in
        # the telemetry stream come from here (reset on every restore,
        # so replayed segments report their own deltas, not the gap to
        # the abandoned timeline)
        self._prev_totals: Optional[Dict[str, float]] = None
        self.recorder = None
        self.spool = None
        self.recorder_dropped = 0
        if record_events:
            from ..obs.record import recorder_spec, stacked_gid_maps
            from ..obs.spool import SpikeSpooler
            d = e.decomp
            self.recorder = recorder_spec(e, segment_steps,
                                          capacity=record_capacity)
            self._gids = jax.device_put(
                jnp.asarray(stacked_gid_maps(d)),
                NamedSharding(mesh, dist_cfg.pspec(1)))
            header = {"grid": [d.grid.height, d.grid.width,
                               d.grid.n_per_column],
                      "law": e.law.kind, "seed": e.seed,
                      "dt_ms": e.lif.dt_ms,
                      "n_neurons": d.grid.n_neurons,
                      "recorder_capacity": self.recorder.capacity}
            if self.n_members is None:
                # the member spoolers carry their own state_seed; a
                # solo spool records it at the top so an ensemble
                # member's stream is comparable header-for-header
                header["state_seed"] = e.state_seed_value
            self.spool = SpikeSpooler(
                os.path.join(cfg.ckpt_dir, "spool"), dist_cfg.tiles,
                header=header, telemetry=telemetry,
                members=dist_cfg.ensemble_seeds)
        # the driver never consumes the per-step spike output (the
        # spool is the per-step record), so don't materialize it.
        # ``sim_cache``: a caller-owned dict (the job server's resident
        # compiled-mesh cache) mapping ``sim_fingerprint`` keys to the
        # jitted segment fn -- jobs differing only in seeds share one
        # compiled step.
        self._sim_key = sim_fingerprint(dist_cfg, segment_steps,
                                        self.recorder, self.storage)
        self._sim = None if sim_cache is None else sim_cache.get(
            self._sim_key)
        if self._sim is None:
            # the delivery plan imports the kernels (Pallas) here; the
            # trace and compile come with the first segment
            with self.tel.span("setup.sim_fn"):
                self.tel.annotate(delivery_w_dtype=e.delivery_w_dtype(
                    self.storage.weight_dtype))
                self._sim = make_sim_fn(dist_cfg, mesh, segment_steps,
                                        record_rate=False,
                                        recorder=self.recorder,
                                        storage=self.storage)
            if sim_cache is not None:
                sim_cache[self._sim_key] = self._sim
        self._sim_inputs = SimInputs(
            tables=self.tables, inv_slots=self._inv_slots,
            gids=self._gids if self.recorder is not None else None)

    # ---- checkpoint metadata (identity of the saved state) ------------
    def _meta(self) -> dict:
        from ..core.synapses import TABLE_REALIZATION_VERSION
        e = self.dist_cfg.engine
        d = e.decomp
        return {"tiles_y": d.tiles_y, "tiles_x": d.tiles_x,
                "grid": [d.grid.height, d.grid.width, d.grid.n_per_column],
                "law": e.law.kind, "radius": d.radius, "seed": e.seed,
                "state_seed": e.state_seed_value,
                "table_realization": TABLE_REALIZATION_VERSION,
                "storage": self.storage.meta(),
                # repro-lint: ignore[meta-drift] report-only: resume is
                # bit-identical across segment sizes by design
                "segment_steps": self.step_size,
                "stdp": (dataclasses.asdict(e.stdp)
                         if self.plastic else None),
                "ensemble_seeds": (None if self.n_members is None
                                   else list(self.dist_cfg.ensemble_seeds)),
                # repro-lint: ignore[meta-drift] report-only: full job
                # provenance (the typed SimJobSpec); identity fields are
                # refused individually above
                "job": self._job_meta,
                "born_tiles": (list(self._born_tiles)
                               if self.plastic else None),
                "metric_base": dict(self._metric_base)}

    def _save(self, step: int, state):
        # meta rides inside the checkpoint's manifest: atomic with the
        # checkpoint, so a crash can never publish meta describing a
        # tiling (or spool frontier) the newest on-disk checkpoint does
        # not have
        meta = self._meta()
        # repro-lint: ignore[meta-drift] report-only running totals; the
        # resumable base rides (and is validated via) 'metric_base'
        meta["metric_totals"] = self.metric_totals(state)
        if self.spool is not None:
            # the manifest's spool offsets must never reference bytes
            # that are not yet durable: a hard crash between manifest
            # publish and the spool worker's write would otherwise leave
            # logs permanently shorter than every manifest's frontier --
            # an unresumable run.  Drain the (small) spool queue first.
            with self.tel.span("ckpt.spool_sync", step=step):
                self.spool.wait()
            meta["spool_offsets"] = self.spool.offsets()
            meta["recorder_dropped"] = self.recorder_dropped
        self.ckpt.save(step, state, meta=meta)

    # ---- restore / init ----------------------------------------------
    def _restore_or_init(self):
        last = latest_step(self.cfg.ckpt_dir)
        self._prev_totals = None           # deltas restart per timeline
        if last is None:
            self._metric_base = {k: 0.0 for k in METRIC_KEYS}
            with self.tel.span("restore.init"):
                if self.spool is not None:
                    self.spool.truncate({})
                state = init_dist_state(self.dist_cfg)
                if self.plastic:
                    # from the host build tables: the device tables
                    # carry only the folded int8 mask, not the build
                    # weights
                    state["plastic"] = init_dist_plastic_state(
                        self.dist_cfg, self._tables_host)
                state = jax.device_put(state, self._state_sh)
            return 0, state
        d = self.dist_cfg.engine.decomp
        meta = checkpoint_meta(self.cfg.ckpt_dir, last)
        mine = self._meta()
        # plasticity identity first: the plastic weight tables live in
        # the checkpointed state, so a static checkpoint cannot resume
        # plastic (there are no tables to continue from), a plastic
        # checkpoint cannot resume static (the learned weights would be
        # silently replaced by the seed realization), and an STDP
        # parameter change mid-run is a different model
        theirs = meta.get("stdp")
        if theirs != mine["stdp"]:
            raise ValueError(
                f"checkpoint in {self.cfg.ckpt_dir} was written with "
                f"stdp={theirs} but the current config has "
                f"stdp={mine['stdp']} -- a plastic run resumes only a "
                "checkpoint with identical STDP parameters, and a "
                "static run only a static checkpoint")
        # the state relayout is only valid for the *same model*: grid,
        # connectivity law, synapse seed AND sampling-procedure version
        # must match -- same seed under a different table_realization
        # rebuilds a different network (keys absent from older
        # checkpoints are skipped: pre-versioning manifests)
        refuse_meta_drift(
            meta, mine,
            ("grid", "law", "radius", "seed", "state_seed",
             "ensemble_seeds", "table_realization"),
            self.cfg.ckpt_dir)
        base = meta.get("metric_base", {})
        self._metric_base = {k: float(base.get(k, 0.0))
                             for k in METRIC_KEYS}
        old_tiles = (meta.get("tiles_y", d.tiles_y),
                     meta.get("tiles_x", d.tiles_x))
        if old_tiles == (d.tiles_y, d.tiles_x):
            # same tiling => deterministically the same storage
            # descriptor; drift means the checkpointed bytes (weight
            # dtype, compressed caps) no longer describe this build --
            # refuse rather than reinterpret (keys absent from older
            # manifests are skipped by refuse_meta_drift)
            refuse_meta_drift(meta, mine, ("storage",), self.cfg.ckpt_dir)
            self.tel.event("resume", logger=log,
                           msg=f"resuming from sim step {last}",
                           step=last)
            with self.tel.span("restore.load", step=last):
                state = restore_checkpoint(
                    self.cfg.ckpt_dir, last,
                    abstract_dist_inputs(self.dist_cfg, self.storage)[0],
                    shardings=self._state_sh)
        else:
            if self.n_members is not None:
                raise ValueError(
                    f"checkpoint tiling {old_tiles} != configured "
                    f"{(d.tiles_y, d.tiles_x)} on an ensemble run: "
                    "elastic retiling of the stacked member axis is not "
                    "supported yet -- resume ensembles on the tiling "
                    "they were checkpointed under")
            if not self.allow_retile:
                raise ValueError(
                    f"checkpoint tiling {old_tiles} != configured "
                    f"{(d.tiles_y, d.tiles_x)}; pass allow_retile=True "
                    "(CLI: --retile) to relayout the state")
            self.tel.event(
                "resume", logger=log,
                msg=f"resuming from sim step {last} with retile "
                    f"{old_tiles} -> {(d.tiles_y, d.tiles_x)}",
                step=last, old_tiles=list(old_tiles),
                new_tiles=[d.tiles_y, d.tiles_x])
            with self.tel.span("restore.retile", step=last,
                               old_tiles=list(old_tiles),
                               new_tiles=[d.tiles_y, d.tiles_x]):
                old_cfg = retile_config(self.dist_cfg, *old_tiles)
                # the old tiling's storage descriptor (compressed caps,
                # weight dtype) sizes the checkpointed plastic weight
                # tiers; it rides in the manifest (any checkpoint new
                # enough to pass the table_realization gate carries it)
                old_storage = (TableStorage.from_meta(meta["storage"])
                               if meta.get("storage") is not None
                               else old_cfg.engine.spec().storage())
                host_state = restore_checkpoint(
                    self.cfg.ckpt_dir, last,
                    abstract_dist_inputs(old_cfg, old_storage)[0])
                # the relayout zeroes per-tile metrics: fold the
                # restored partial sums into the global base so totals
                # survive the retile exactly (whatever tiling we came
                # from)
                for k in METRIC_KEYS:
                    self._metric_base[k] += float(
                        np.sum(np.asarray(host_state["metrics"][k])))
                plastic_host = host_state.pop("plastic", None)
                state = retile_state(host_state, old_cfg.engine.decomp,
                                     d)
                if self.plastic:
                    # the checkpointed weights are laid out for the
                    # *old* tiling's structure (itself a deterministic
                    # relay of the birth realization); relay them
                    # onward by global synapse id
                    old_d = old_cfg.engine.decomp
                    old_spec = old_cfg.engine.spec()
                    if old_tiles == self._born_tiles:
                        old_tabs = self._birth_tables
                    else:
                        born_cfg = retile_config(self.dist_cfg,
                                                 *self._born_tiles)
                        # compressed exactly as the old process built
                        # them (the relay preserves per-row occupancy,
                        # so the realized caps -- and hence the
                        # checkpointed w shapes -- are reproduced
                        # deterministically)
                        old_tabs = compress_tables(retile_tables(
                            self._birth_tables, born_cfg.engine.decomp,
                            born_cfg.engine.spec(), old_d, old_spec))
                    state["plastic"] = retile_plastic(
                        plastic_host, old_tabs, old_d, old_spec, d,
                        self.dist_cfg.engine.spec(),
                        storage=self.storage)
                state = jax.device_put(state, self._state_sh)
        if self.spool is not None:
            # exactly-once: cut every log back to this checkpoint's
            # frontier; replayed segments re-append their events
            with self.tel.span("spool.truncate", step=last):
                self.spool.truncate(meta.get("spool_offsets", {}))
            self.recorder_dropped = int(meta.get("recorder_dropped", 0))
        return last, state

    # ---- one segment --------------------------------------------------
    def _step_once(self, state, step):
        if self.fault_hook:
            self.fault_hook(step)
        t0 = time.perf_counter()
        with self.tel.span("segment.compute", step=step):
            if self.recorder is not None:
                state, _, rec = self._sim(state, self._sim_inputs)
            else:
                state, _ = self._sim(state, self._sim_inputs)
            if self.tel.enabled:
                # fence so the span covers the device work it
                # dispatched, not just the host-side dispatch.  Pure
                # observer: the run loop blocks on this segment's
                # metrics immediately after anyway -- tracing only
                # moves the wait inside the span.
                jax.block_until_ready(state)
        d_rec_dropped = 0
        if self.recorder is not None:
            with self.tel.span("segment.spool_drain", step=step):
                d_rec_dropped = self._drain_recorder(rec, step)
        self._segments_done += 1
        if self._preempt_after is not None \
                and self._segments_done >= self._preempt_after:
            self.preempted = True
        with self.tel.span("segment.metrics", step=step):
            totals = self.metric_totals(state)
            prev = self._prev_totals or {k: 0.0 for k in METRIC_KEYS}
            delta = {k: totals[k] - prev[k] for k in METRIC_KEYS}
            self._prev_totals = totals
        if delta["dropped"] > 0:
            # at most once per segment, with the segment's own delta
            # (the old run-level warning fired once and went silent
            # however much worse the overflow got)
            self.tel.event(
                "delivery_drops", level="warning", logger=log,
                msg=f"event-delivery compaction dropped "
                    f"{int(delta['dropped'])} spike(s) this segment "
                    f"({int(totals['dropped'])} total; active_cap "
                    "overflow) -- results undercount synaptic events; "
                    "raise EngineConfig.cap_headroom",
                step=step, dropped=int(delta["dropped"]),
                dropped_total=int(totals["dropped"]))
        wall = time.perf_counter() - t0
        self.tel.metrics(
            "segment", step=step, wall_s=wall,
            steps_per_s=self.step_size / max(wall, 1e-9),
            d_spikes=delta["spikes"], d_events=delta["events"],
            d_dropped=delta["dropped"],
            d_recorder_dropped=float(d_rec_dropped),
            spikes=totals["spikes"], events=totals["events"],
            dropped=totals["dropped"])
        metrics = {"sim_t": jnp.max(state["t"]),
                   "spikes": totals["spikes"], "events": totals["events"],
                   "dropped": totals["dropped"],
                   "d_spikes": delta["spikes"],
                   "d_events": delta["events"],
                   "d_dropped": delta["dropped"],
                   "d_recorder_dropped": float(d_rec_dropped)}
        return state, metrics

    def _drain_recorder(self, rec, step=None) -> int:
        """Spool one segment's event buffers (all shards, all ensemble
        members); returns the segment's recorder-overflow drop count."""
        rec_h = jax.device_get(rec)
        ty, tx = self.dist_cfg.tiles
        for y in range(ty):
            for x in range(tx):
                if self.n_members is None:
                    cnt = int(rec_h["count"][y, x])
                    self.spool.append(y, x, rec_h["step"][y, x, :cnt],
                                      rec_h["gid"][y, x, :cnt])
                    continue
                for m in range(self.n_members):
                    cnt = int(rec_h["count"][y, x, m])
                    self.spool.append(
                        y, x, rec_h["step"][y, x, m, :cnt],
                        rec_h["gid"][y, x, m, :cnt], member=m)
        seg_dropped = int(np.sum(rec_h["dropped"]))
        if seg_dropped:
            self.recorder_dropped += seg_dropped
            self.tel.event(
                "recorder_drops", level="warning", logger=log,
                msg=f"spike recorder dropped {seg_dropped} event(s) "
                    f"this segment ({self.recorder_dropped} total) -- "
                    "raise record_capacity (CLI: --record-cap) for "
                    "complete logs",
                step=step, dropped=seg_dropped,
                dropped_total=self.recorder_dropped)
        return seg_dropped

    # ---- host-side views ----------------------------------------------
    def metric_totals(self, state) -> Dict[str, float]:
        """Cumulative run totals: the manifest-carried base (history
        predating an elastic retile) plus the live state's per-tile
        partial sums.  Tiling-independent by construction."""
        return {k: self._metric_base[k]
                + float(np.asarray(jnp.sum(state["metrics"][k])))
                for k in METRIC_KEYS}

    def firing_rate_hz(self, state) -> float:
        """Mean rate over the whole run (active neurons), retile-proof:
        uses ``metric_totals`` rather than raw state sums."""
        t = int(np.asarray(jnp.max(state["t"])))
        n_active = float(np.asarray(jnp.sum(state["active"])))
        sim_sec = t * self.dist_cfg.engine.lif.dt_ms * 1e-3
        return self.metric_totals(state)["spikes"] \
            / max(n_active, 1.0) / max(sim_sec, 1e-9)

    def spike_counts(self, n_steps: Optional[int] = None,
                     member: Optional[int] = None) -> np.ndarray:
        """Global per-step spike counts, read back from the spooled
        spike logs (sim step order; the exactly-once truncation
        contract guarantees replayed segments appear once).  Covers the
        whole run recorded into this checkpoint directory -- including
        segments written by earlier processes of a resumed run.

        ``n_steps`` fixes the returned length (steps past the last
        spike would otherwise be trimmed).  Requires
        ``record_events=True``: the spool *is* the per-step record (the
        former per-step host dict duplicated it and grew unboundedly).
        Ensemble runs read one member's stream -- pass ``member``.
        """
        if self.spool is None:
            raise ValueError(
                "spike_counts() reads the spike spool; construct the "
                "driver with record_events=True")
        from ..obs.spool import RECORD_DTYPE, member_name, shard_events
        if (member is None) != (self.n_members is None):
            raise ValueError(
                f"spike_counts(member={member!r}) on a driver with "
                f"n_members={self.n_members!r}: pass a member index "
                "exactly when the run is an ensemble")
        self.spool.wait()
        d = self.spool.directory
        if member is not None:
            d = os.path.join(d, member_name(member))
        shards = list(shard_events(d).values())
        ev = (np.concatenate(shards) if shards
              else np.empty(0, RECORD_DTYPE))
        if n_steps is None:
            n_steps = int(ev["step"].max()) + 1 if len(ev) else 0
        return np.bincount(ev["step"], minlength=n_steps)[:n_steps] \
            .astype(np.float32)

    def plastic_summary(self, state, member: Optional[int] = None) -> dict:
        """Tiling-invariant digest of the live plastic tables.

        ``weight_checksum`` hashes every synapse's ``(pre_gid,
        post_gid, dslot, weight-bits)`` record in canonical (sorted)
        order, so two runs agree iff their learned weights are
        bit-identical per global synapse -- whatever tilings either
        went through.  Drift stats compare against the birth weights.
        Ensemble runs digest one member's carried weights -- pass
        ``member``.
        """
        if not self.plastic:
            raise ValueError("plastic_summary() needs a plastic engine "
                             "(EngineConfig.stdp set)")
        if (member is None) != (self.n_members is None):
            raise ValueError(
                f"plastic_summary(member={member!r}) on a driver with "
                f"n_members={self.n_members!r}: pass a member index "
                "exactly when the run is an ensemble")
        e = self.dist_cfg.engine
        d, spec = e.decomp, e.spec()
        pl = state["plastic"]
        pick = ((lambda w: np.asarray(w)) if member is None
                else (lambda w: np.asarray(w)[:, :, member]))
        live_tabs = {
            "local": dict(self._tables_host["local"],
                          w=pick(pl["w"][0])),
            "halo": [dict(t, w=pick(pw)) for t, pw in
                     zip(self._tables_host["halo"], pl["w"][1:])],
        }
        live = gather_synapse_stream(live_tabs, d, spec)
        birth = self._birth_stream        # same gather order as `live`
        w = np.ascontiguousarray(live["w"])
        wbits = w.view({2: np.uint16, 4: np.uint32,
                        8: np.uint64}[w.dtype.itemsize])
        order = np.lexsort((wbits, live["dslot"], live["post"],
                            live["pre"]))
        rec = np.column_stack([
            live["pre"][order], live["post"][order],
            live["dslot"][order].astype(np.int64),
            wbits[order].astype(np.int64)]).astype(np.int64)
        checksum = hashlib.sha256(
            np.ascontiguousarray(rec).tobytes()).hexdigest()
        mask = birth["w"] > 0
        return {
            "weight_checksum": checksum,
            "n_synapses": int(len(w)),
            "n_plastic": int(mask.sum()),
            "w_sum": float(w.sum()),
            "w_l1_delta": float(np.abs(w - birth["w"])[mask].sum()),
        }

    def compiled_step_cache_size(self) -> Optional[int]:
        """Compiled-program count of this driver's segment function
        (``None`` when the runtime lacks jit cache introspection).
        Stays 1 however many segments -- and, through a shared
        ``sim_cache``, however many same-shaped jobs -- ran through it:
        the one-compile contract the ensemble service asserts in CI."""
        return (self._sim._cache_size()
                if hasattr(self._sim, "_cache_size") else None)

    def lower_segment(self, state):
        """The segment function lowered for ``state``: what every
        segment of ``run`` executes (``.compile().as_text()`` is its
        HLO, e.g. to check which Pallas kernels it launches)."""
        return self._sim.lower(state, self._sim_inputs)

    def run(self, n_steps: int):
        out = super().run(n_steps)
        if self.spool is not None:
            self.spool.wait()            # logs durable before we report
        return out
