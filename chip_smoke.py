#!/usr/bin/env python3
"""Smoke run of the simulator's main path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the 2x2 host mesh only

The model is the paper's column -- 1240 neurons with the published
Gaussian / exponential lateral laws -- on an 8x8 grid of columns: 79,360
neurons and ~1e8 recurrent synapses.  Only the grid is cut, never the
column.  Every phase goes through the entry points a user calls
(``SimJobSpec`` -> ``build_sim_driver`` -> ``SimDriver.run``, or an
in-process ``SimJobServer``) for two segments with a checkpoint
between them.  The one-chip phases run two ``SEG_ONE_CHIP``-step
segments: the first spikes come after ~14 steps from a cold start, and
once the network bursts a step costs seconds at this width (two 50-step
segments per phase, with the reference runs, took 1510 s on a v5e; two
12-step segments, which stay in the sparse onset, took 515 s).
``--four-chips`` runs its one phase for two ``SEG_FOUR_CHIPS``-step
segments.

One chip, in order:

  * static Gaussian with the spike recorder on;
  * static exponential;
  * plastic Gaussian (STDP; prints which plastic path ran);
  * one job-server session: a 2-member ensemble job, its spike stream
    read back by cursor while it runs.

Each static phase is then run again through the pure-XLA reference
(``use_kernels=False``) from the same initial state, with the same
tables, over the same steps.  Spike counts must be equal -- per step
where the phase records spikes, per segment otherwise -- as must event
counts, and the final delayed-current ring and membrane state must
agree within ``RTOL``/``ATOL``.  (That tolerance holds only while the
two trajectories agree spike for spike; on a v5e the two paths were
bit-equal at every step.)

``--four-chips`` runs only the sharded path: the exponential grid (halo
radius 10) on the 2x2 host mesh, the same comparison with the reference
on that mesh, and checks that state and tables span four devices and
that the compiled segment exchanges its halo with collective permutes.

Lines starting with ``[smoke]`` are smoke output, not measurements: the
segment wall includes host work, and nothing is repeated.  The last
line is ``{"ok": true, "device": {...}}``; any failed check, a job that
ends ``failed``, a ``step_failure`` telemetry event or a platform other
than ``tpu`` exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RUN_DIR = ROOT / ".smoke_runs"        # checkpoints and spools; removed
sys.path.insert(0, str(ROOT / "src"))

GRID = 8
NEURONS_PER_COLUMN = 1240
SEG_ONE_CHIP = 12
SEG_FOUR_CHIPS = 25
# kernel vs pure-XLA delivery: the tolerance of
# tests/test_event_delivery_equiv.py (float32 sums in another order)
RTOL = ATOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included), from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.total += duration


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


def spec_for(name: str, seg: int = SEG_ONE_CHIP, **kw):
    """The phase's job: two ``seg``-step segments."""
    from repro.runtime import SimJobSpec
    base = dict(ckpt_dir=str(RUN_DIR / name), grid=GRID,
                n_per_column=NEURONS_PER_COLUMN, t_steps=2 * seg,
                segment_steps=seg, tiles=(1, 1))
    base.update(kw)
    return SimJobSpec(**base)


def no_step_failures(tel, name: str):
    fails = tel.events("step_failure")
    check(not fails, f"{name}: {len(fails)} step_failure event(s), first: "
                     f"{fails[0].get('error') if fails else ''}")


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def compiled_kernels(hlo: str) -> set:
    """Names of the Pallas kernels launched in compiled HLO text."""
    return set(re.findall(
        r"^\s*(?:ROOT )?%([A-Za-z_]+)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo, re.M))


def drive(name: str, spec, clock: CompileClock):
    """Build the job's driver, run it, check the run; returns (driver,
    run output, compiled segment HLO)."""
    from repro.checkpoint.store import latest_step
    from repro.obs.telemetry import Telemetry
    from repro.runtime import build_sim_driver

    steps, seg = spec.t_steps, spec.segment_steps
    tel = Telemetry()
    t0 = time.perf_counter()
    driver = build_sim_driver(spec, telemetry=tel)
    build_s = time.perf_counter() - t0
    compile0 = clock.total
    out = driver.run(steps)
    compile_s = clock.total - compile0
    no_step_failures(tel, name)
    state = out["state"]
    check(out["final_step"] == steps and not out["preempted"],
          f"{name}: run stopped at step {out['final_step']}")
    check(latest_step(spec.ckpt_dir) == steps,
          f"{name}: no checkpoint at step {steps}")
    walls = [s["dur"] for s in tel.spans("segment.compute")]
    check(len(walls) == 2, f"{name}: {len(walls)} segments ran, expected 2")
    totals = driver.metric_totals(state)
    say(f"{name}: table_build_s={build_s:.1f} compile_s={compile_s:.1f} "
        f"segment_walls_s={[round(w, 2) for w in walls]} (first includes "
        f"compile) steps_per_s={seg / walls[1]:.2f} "
        f"rate_hz={driver.firing_rate_hz(state):.2f} "
        f"synapses={driver.table_stats['n_synapses']} "
        f"events={int(totals['events'])} dropped={int(totals['dropped'])} "
        f"peak_bytes_in_use={peak_bytes()}")
    check(totals["spikes"] > 0, f"{name}: no neuron spiked")
    hlo = driver.lower_segment(state).compile().as_text()
    return driver, out, hlo


def compare_with_xla(name: str, spec, driver, out):
    """Run the driver's steps again through the pure-XLA path from the
    same initial state and tables; check the counts and final state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.dist_engine import (SimInputs, dist_shardings,
                                        init_dist_state, make_sim_fn)

    steps, seg = spec.t_steps, spec.segment_steps
    cfg, mesh = driver.dist_cfg, driver.mesh
    xla_cfg = dataclasses.replace(
        cfg, engine=dataclasses.replace(cfg.engine, use_kernels=False))
    state_sh, _ = dist_shardings(cfg, mesh, driver.storage)
    sim = make_sim_fn(xla_cfg, mesh, seg, storage=driver.storage)
    inputs = SimInputs(tables=driver.tables)
    t0 = time.perf_counter()
    x = jax.device_put(init_dist_state(cfg), state_sh)
    counts_x = []
    for _ in range(steps // seg):
        x, per_step = sim(x, inputs)
        counts_x.append(np.asarray(per_step).sum(axis=(0, 1)))
    counts_x = np.concatenate(counts_x)
    wall = time.perf_counter() - t0

    if driver.recorder is not None:
        counts_k = driver.spike_counts(steps)
        check(np.array_equal(counts_k, counts_x),
              f"{name}: per-step spike counts differ from the XLA path's "
              f"from step {int(np.argmax(counts_k != counts_x))} on")
        unit = "per-step"
    else:
        seg_k = [m["d_spikes"] for m in out["metrics"]]
        seg_x = counts_x.reshape(-1, seg).sum(axis=1).tolist()
        check(seg_k == seg_x, f"{name}: spikes per segment {seg_k}, XLA "
                              f"path {seg_x}")
        unit = "per-segment"

    @jax.jit
    def compare(k, x):
        def excess(a, b):
            return jnp.max(jnp.abs(a - b) - (ATOL + RTOL * jnp.abs(b)))

        nk, nx = k["neuron"], x["neuron"]
        return {
            "events": jnp.sum(k["metrics"]["events"])
            - jnp.sum(x["metrics"]["events"]),
            "ring_excess": excess(k["i_ring"], x["i_ring"]),
            "ring_max_abs": jnp.max(jnp.abs(k["i_ring"] - x["i_ring"])),
            "neuron_excess": jnp.maximum(excess(nk["v"], nx["v"]),
                                         excess(nk["c"], nx["c"])),
            "refrac_equal": jnp.all(nk["refrac"] == nx["refrac"]),
            "bit_equal": jnp.all(jnp.asarray([
                jnp.all(a == b) for a, b in zip(jax.tree.leaves(k),
                                                jax.tree.leaves(x))])),
        }

    d = jax.device_get(compare(out["state"], x))
    check(d["events"] == 0, f"{name}: event counts differ by {d['events']}")
    check(d["ring_excess"] <= 0.0 and d["neuron_excess"] <= 0.0
          and d["refrac_equal"],
          f"{name}: final state differs beyond {ATOL} + {RTOL}*|ref| "
          f"(ring max |diff| {d['ring_max_abs']})")
    say(f"{name}: kernels vs use_kernels=False over {steps} steps: "
        f"{unit} spike counts and event counts equal; final ring max "
        f"|diff|={float(d['ring_max_abs']):.3g}; final state bit-equal="
        f"{bool(d['bit_equal'])}; XLA path {wall:.1f}s incl. compile")


def static_phase(name: str, law: str, record: bool, clock: CompileClock):
    kernels = {"synaptic_accum", "lif_step"} | (
        {"spike_compact"} if record else set())
    spec = spec_for(name, law=law, record=record)
    driver, out, hlo = drive(name, spec, clock)
    found = compiled_kernels(hlo)
    check(kernels <= found, f"{name}: compiled segment launches {found}, "
                            f"expected {sorted(kernels)}")
    say(f"{name}: compiled segment launches Pallas kernels {sorted(found)}")
    compare_with_xla(name, spec, driver, out)


def plastic_phase(clock: CompileClock):
    import jax
    import numpy as np
    from repro.checkpoint.store import restore_checkpoint
    from repro.core.dist_engine import abstract_dist_inputs
    from repro.kernels.plastic_step import fused_supported

    name = "plastic_gaussian"
    spec = spec_for(name, law="gaussian", plastic=True)
    driver, out, hlo = drive(name, spec, clock)
    cfg = driver.dist_cfg
    n_local = cfg.engine.spec().n_local
    say(f"{name}: n_local={n_local} took the "
        + ("fused one-launch plastic kernel" if fused_supported(n_local)
           else "two-pass path (delivery kernel, then the XLA STDP step)"))
    found = compiled_kernels(hlo)
    check({"lif_step"} <= found and found & {"synaptic_accum",
                                             "plastic_step"},
          f"{name}: compiled segment launches {found}")
    mid = restore_checkpoint(spec.ckpt_dir, spec.segment_steps,
                             abstract_dist_inputs(cfg, driver.storage)[0])
    w_max = cfg.engine.stdp.w_max
    changed = 0
    for w_end, w_mid in zip(out["state"]["plastic"]["w"],
                            mid["plastic"]["w"]):
        w_end = np.asarray(jax.device_get(w_end))
        check(np.isfinite(w_end).all() and w_end.max() <= w_max,
              f"{name}: weights non-finite or above w_max={w_max}")
        changed += int(np.count_nonzero(w_end != w_mid))
    check(changed > 0, f"{name}: STDP changed no weight in segment 2")
    say(f"{name}: STDP changed {changed} weights between the step-"
        f"{spec.segment_steps} checkpoint and step {spec.t_steps}")


def server_phase(clock: CompileClock):
    from repro.launch.serve import SimJobServer
    from repro.obs.telemetry import Telemetry
    from repro.parallel.compat import make_mesh

    name = "server_ensemble"
    tel = Telemetry()
    server = SimJobServer(mesh=make_mesh((1, 1), ("data", "model")),
                          telemetry=tel)
    try:
        t0, compile0 = time.perf_counter(), clock.total
        spec = spec_for(name, law="gaussian", seeds=(1, 2), record=True)
        jid = server.submit(spec)
        cursor, streamed, members, reads = None, 0, set(), 0
        done = False
        while True:
            r = server.stream(jid, cursor)
            cursor, reads = r["cursor"], reads + 1
            for member, g in r["streams"].items():
                members.add(member)
                streamed += g["n_new"]
            if done:
                break
            done = r["done"]       # one more read drains the last segment
            if not done:
                time.sleep(0.5)
        job = server.job(jid)
        wall = time.perf_counter() - t0
    finally:
        server.shutdown()
    no_step_failures(tel, name)
    check(job.status == "done", f"{name}: job {job.status}: {job.error}")
    res = job.result
    walls = [s["dur"] for s in tel.spans("segment.compute")]
    say(f"{name}: job {job.status} in {wall:.1f}s (tables, compile, run) "
        f"compile_s={clock.total - compile0:.1f} "
        f"segment_walls_s={[round(w, 2) for w in walls]} "
        f"rate_hz={res['rate_hz']:.2f} synapses={res['n_synapses']} "
        f"dropped={int(res['totals']['dropped'])} "
        f"streamed_events={streamed} in {reads} cursor reads "
        f"peak_bytes_in_use={peak_bytes()}")
    check(res["final_step"] == spec.t_steps and res["members"] == 2,
          f"{name}: result {res}")
    check(members == {"member_000", "member_001"},
          f"{name}: streams for {sorted(members)}")
    check(streamed == res["spooled_events"] > 0,
          f"{name}: streamed {streamed} events, spooled "
          f"{res['spooled_events']}")
    check(res["compiled_steps"] == 1, f"{name}: {res['compiled_steps']} "
                                      "compiled segment programs")


def four_chip_phase(clock: CompileClock):
    import jax

    name = "mesh2x2_exponential"
    spec = spec_for(name, SEG_FOUR_CHIPS, law="exponential", tiles=(2, 2))
    driver, out, hlo = drive(name, spec, clock)
    leaves = jax.tree.leaves(out["state"]) + jax.tree.leaves(driver.tables)
    spans = {len(leaf.sharding.device_set) for leaf in leaves}
    check(spans == {4} and not any(leaf.sharding.is_fully_replicated
                                   for leaf in leaves),
          f"{name}: state/table leaves span {spans} devices")
    n_perm = len(re.findall(r" collective-permute(?:-start)?\(", hlo))
    check(n_perm > 0, f"{name}: no collective permute in the segment")
    found = compiled_kernels(hlo)
    check({"synaptic_accum", "lif_step"} <= found,
          f"{name}: compiled segment launches {found}")
    say(f"{name}: state and tables sharded over 4 devices; compiled "
        f"segment has {n_perm} collective permutes (halo exchange) "
        f"and Pallas kernels {sorted(found)}")
    compare_with_xla(name, spec, driver, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded exponential grid on the "
                         "2x2 host mesh")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if args.four_chips and len(jax.devices()) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    say(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{enable_compile_cache()}")
    clock = CompileClock()
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phases = [four_chip_phase]
        else:
            phases = [
                lambda c: static_phase("static_gaussian", "gaussian",
                                       True, c),
                lambda c: static_phase("static_exponential", "exponential",
                                       False, c),
                plastic_phase, server_phase]
        for phase in phases:
            t = time.perf_counter()
            phase(clock)
            gc.collect()           # free the phase's device buffers
            say(f"phase done in {time.perf_counter() - t:.1f}s")
    finally:
        shutil.rmtree(RUN_DIR, ignore_errors=True)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
