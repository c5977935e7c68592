"""Paper Figure 2: simulation cost per synaptic event, exponential vs
Gaussian connectivity.  The paper measures 1.9-2.3x on its CPU cluster.

We measure the same metric -- elapsed / (simulated_sec x total_syn x
rate) -- on reduced grids (CPU container), in the event-driven mode
whose work is proportional to synaptic events, exactly like DPSNN.

Also emits ``BENCH_event_delivery.json``: a kernel-vs-XLA A/B of the
event-delivery hot path (fused Pallas pipeline vs pure-XLA
``deliver_events``) per connectivity law, plus a fused-vs-two-pass A/B
of the *plastic* step (one-launch delivery+LTD kernel vs the kernel
delivery + separate ``stdp_step`` fallback), so the perf trajectory of
the kernel layer is machine-readable across PRs.
"""

import time

import jax
import numpy as np

from repro.core.connectivity import exponential_law, gaussian_law
from repro.core.engine import (EngineConfig, build_shard_tables,
                               init_plasticity, init_sim_state,
                               simulate)
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.metrics import cost_per_synaptic_event
from repro.core.stdp import STDPParams

from .common import write_json


def measure(law, grid=8, n_per_col=60, steps=400, reps=3,
            use_kernels=False) -> dict:
    d = TileDecomposition(grid=ColumnGrid(grid, grid, n_per_col),
                          tiles_y=1, tiles_x=1, radius=law.radius)
    cfg = EngineConfig(decomp=d, law=law, use_kernels=use_kernels)
    tabs = build_shard_tables(cfg)
    st = init_sim_state(cfg)
    fn = jax.jit(lambda s: simulate(s, tabs, cfg, steps))
    # warmup + state advance past transient
    st, _ = fn(st)
    jax.block_until_ready(st["t"])
    times, rates, events = [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        st2, _ = fn(st)
        jax.block_until_ready(st2["t"])
        times.append(time.perf_counter() - t0)
        sp = float(st2["metrics"]["spikes"]) - float(st["metrics"]["spikes"])
        ev = float(st2["metrics"]["events"]) - float(st["metrics"]["events"])
        n_active = float(np.asarray(st2["active"]).sum())
        rates.append(sp / n_active / (steps * 1e-3))
        events.append(ev)
        st = st2
    elapsed = float(np.median(times))
    rate = float(np.mean(rates))
    n_syn = tabs["stats"]["n_synapses"]
    sim_s = steps * 1e-3
    return {
        "law": law.kind,
        "elapsed_s": elapsed,
        "rate_hz": rate,
        "synapses": n_syn,
        "recurrent_events": float(np.mean(events)),
        "cost_per_event": cost_per_synaptic_event(elapsed, sim_s, n_syn,
                                                  rate),
        "stencil": law.stencil_width,
    }


def analytic_fullscale(shards=1024, grid=96) -> dict:
    """TPU-target roofline model at the paper's scale."""
    from repro.core.grid import ColumnGrid, TileDecomposition
    from repro.core.metrics import step_time_model
    from repro.core.synapses import SynapseTableSpec
    import numpy as np
    ty = int(np.sqrt(shards))
    out = {}
    for name, law, rate in (("gaussian", gaussian_law(), 7.5),
                            ("exponential", exponential_law(), 35.0)):
        dec = TileDecomposition(grid=ColumnGrid(grid, grid), tiles_y=ty,
                                tiles_x=shards // ty, radius=law.radius)
        spec = SynapseTableSpec(decomp=dec, law=law)
        t = step_time_model(spec, rate)
        out[name] = t["step_s"] / t["events_per_step"]
    out["ratio"] = out["exponential"] / out["gaussian"]
    return out


def measure_pair(law, grid=8, n_per_col=60, steps=300, reps=3) -> dict:
    """Paired kernel-vs-XLA measurement of one law.

    Both arms reuse ONE table realization (the A/B times delivery, not
    setup) and are timed *interleaved*, one XLA segment then one kernel
    segment per rep, with the reported ratio the median of per-rep
    ratios: machine throughput drifts (shared containers swing ~2x over
    minutes), and pairing makes both arms sample the same machine state
    instead of comparing timings taken minutes apart.
    """
    d = TileDecomposition(grid=ColumnGrid(grid, grid, n_per_col),
                          tiles_y=1, tiles_x=1, radius=law.radius)
    cfgs = {"xla": EngineConfig(decomp=d, law=law, use_kernels=False),
            "kernel": EngineConfig(decomp=d, law=law, use_kernels="auto")}
    tabs = build_shard_tables(cfgs["xla"])
    fns, sts = {}, {}
    for arm, cfg in cfgs.items():
        fns[arm] = jax.jit(lambda s, c=cfg: simulate(s, tabs, c, steps))
        st = init_sim_state(cfg)
        st, _ = fns[arm](st)          # warmup: compile + transient
        jax.block_until_ready(st["t"])
        sts[arm] = st
    times = {"xla": [], "kernel": []}
    ratios, rates, events = [], [], []
    for _ in range(reps):
        rep = {}
        for arm in ("xla", "kernel"):
            st = sts[arm]
            t0 = time.perf_counter()
            st2, _ = fns[arm](st)
            jax.block_until_ready(st2["t"])
            rep[arm] = time.perf_counter() - t0
            times[arm].append(rep[arm])
            if arm == "kernel":       # identical dynamics in both arms
                sp = (float(st2["metrics"]["spikes"])
                      - float(st["metrics"]["spikes"]))
                events.append(float(st2["metrics"]["events"])
                              - float(st["metrics"]["events"]))
                n_active = float(np.asarray(st2["active"]).sum())
                rates.append(sp / n_active / (steps * 1e-3))
            sts[arm] = st2
        ratios.append(rep["kernel"] / max(rep["xla"], 1e-12))
    n_syn = tabs["stats"]["n_synapses"]
    sim_s = steps * 1e-3
    rate = float(np.mean(rates))
    ab = {}
    for arm in ("xla", "kernel"):
        elapsed = float(np.median(times[arm]))
        ab[arm] = {"elapsed_s": elapsed, "rate_hz": rate,
                   "recurrent_events": float(np.mean(events)),
                   "cost_per_event": cost_per_synaptic_event(
                       elapsed, sim_s, n_syn, rate)}
    ab["kernel_vs_xla_wall_ratio"] = float(np.median(ratios))
    ab["per_rep_ratios"] = [round(r, 4) for r in ratios]
    return ab


def measure_plastic_pair(law, grid=8, n_per_col=60, steps=300,
                         segment_steps=50, reps=3) -> dict:
    """Paired fused-vs-two-pass A/B of the plastic step for one law.

    Both arms run the SAME engine config with kernels enabled; the
    baseline ("twopass") arm is traced with
    ``kernels.plastic_step.RING_N_MAX`` forced to 0, which routes
    ``plastic_delivery_stdp`` through its fallback -- the delivery
    kernel followed by the separate XLA ``stdp_step`` pass, i.e. the
    pre-fusion plastic step.  The fused arm is the one-launch
    delivery+LTD kernel.  Routing is resolved at trace time, so the
    monkeypatch is restored as soon as each arm has compiled.

    Each rep runs ``steps`` as a chain of ``segment_steps``-long jitted
    calls -- the shape the segmented ``SimDriver`` actually executes
    (the committed benchmark config is 50-step segments) -- with the
    arms interleaved per segment so both sample the same machine state
    (see ``measure_pair``); the reported ratio is the median of
    per-rep ratios.  ``gc.collect()`` is fenced between timed segments:
    interpret-mode pallas calls generate enough per-call garbage that a
    collection landing inside one arm's segment skews the pair by
    ~1.5x.  Both arms evolve bit-identical dynamics (asserted on the
    warmup segment's weights) -- the A/B times the step, not the
    physics.
    """
    import gc

    import repro.kernels.plastic_step as ps

    if steps % segment_steps:
        raise ValueError(f"steps={steps} must be a multiple of "
                         f"segment_steps={segment_steps}")
    n_seg = steps // segment_steps
    d = TileDecomposition(grid=ColumnGrid(grid, grid, n_per_col),
                          tiles_y=1, tiles_x=1, radius=law.radius)
    cfg = EngineConfig(decomp=d, law=law, use_kernels="auto",
                       stdp=STDPParams())
    tabs = build_shard_tables(cfg)
    aux = init_plasticity(tabs, cfg)

    def segment(st, tb, traces):
        aux_seg = dict(aux, traces=traces)
        (st, tb, traces), _ = simulate(st, tb, cfg, segment_steps,
                                       plasticity=aux_seg)
        return st, tb, traces

    orig = ps.RING_N_MAX
    fns, carries = {}, {}
    for arm in ("twopass", "fused"):
        ps.RING_N_MAX = 0 if arm == "twopass" else orig
        try:
            fn = jax.jit(segment)
            # warmup inside the patched region: jit traces (and locks
            # in the routing) on this first call; run a full rep worth
            # of segments so the timed window starts at steady state
            carry = fn(init_sim_state(cfg), tabs, aux["traces"])
            for _ in range(n_seg - 1):
                carry = fn(*carry)
            jax.block_until_ready(carry[0]["t"])
        finally:
            ps.RING_N_MAX = orig
        fns[arm], carries[arm] = fn, carry
    np.testing.assert_array_equal(
        np.asarray(carries["twopass"][1]["local"]["w"]),
        np.asarray(carries["fused"][1]["local"]["w"]),
        err_msg="fused plastic step diverged from the two-pass "
                "reference -- the A/B is only meaningful bit-identical")

    times = {"twopass": [], "fused": []}
    ratios = []
    for _ in range(reps):
        rep = {"twopass": 0.0, "fused": 0.0}
        for _ in range(n_seg):
            for arm in ("twopass", "fused"):
                gc.collect()
                st, tb, tr = carries[arm]
                t0 = time.perf_counter()
                out = fns[arm](st, tb, tr)
                jax.block_until_ready(out[0]["t"])
                rep[arm] += time.perf_counter() - t0
                carries[arm] = out
        for arm in ("twopass", "fused"):
            times[arm].append(rep[arm])
        ratios.append(rep["fused"] / max(rep["twopass"], 1e-12))
    out = {"steps": steps, "segment_steps": segment_steps,
           "n_synapses": int(tabs["stats"]["n_synapses"])}
    for arm in ("twopass", "fused"):
        elapsed = float(np.median(times[arm]))
        out[arm] = {"elapsed_s": elapsed,
                    "ms_per_step": round(elapsed / steps * 1e3, 3)}
    out["fused_vs_twopass_wall_ratio"] = float(np.median(ratios))
    out["per_rep_ratios"] = [round(r, 4) for r in ratios]
    return out


def bench_event_delivery(grid=8, n_per_col=60, steps=300,
                         update_root=True, include_plastic=True,
                         plastic_steps=300) -> dict:
    """Kernel-vs-XLA A/B of the event-delivery hot path per law.

    ``kernel`` routes LIF + delivery through the fused Pallas pipeline
    (compiled on TPU, interpret-mode on CPU -- identical code path);
    ``xla`` is the pure-XLA reference; timing is paired (see
    ``measure_pair``).  With ``include_plastic`` the payload gains a
    ``plastic`` section: the fused one-launch plastic step vs the
    two-pass fallback per law (see ``measure_plastic_pair``).  Written
    to ``results/BENCH_event_delivery.json`` (CI artifact) and --
    unless ``update_root=False`` -- to the repo-root copy, the
    committed cross-PR perf trajectory that
    ``benchmarks.delivery_guard`` gates regressions against.
    """
    out = {"backend": jax.default_backend(),
           "interpret": jax.default_backend() != "tpu",
           "grid": f"{grid}x{grid}x{n_per_col}", "steps": steps,
           "laws": {}}
    for name, law in (("gaussian", gaussian_law()),
                      ("exponential", exponential_law())):
        out["laws"][name] = measure_pair(law, grid=grid,
                                         n_per_col=n_per_col, steps=steps)
    if include_plastic:
        out["plastic"] = {"steps": plastic_steps, "laws": {}}
        for name, law in (("gaussian", gaussian_law()),
                          ("exponential", exponential_law())):
            out["plastic"]["laws"][name] = measure_plastic_pair(
                law, grid=grid, n_per_col=n_per_col, steps=plastic_steps)
    write_json("BENCH_event_delivery.json", out, also_root=update_root)
    return out


def run_bench(grid=8, steps=400) -> dict:
    g = measure(gaussian_law(), grid=grid, steps=steps)
    e = measure(exponential_law(), grid=grid, steps=steps)
    out = {
        "gaussian": g, "exponential": e,
        "cost_ratio_single_shard": e["cost_per_event"]
        / g["cost_per_event"],
        "wall_ratio": e["elapsed_s"] / g["elapsed_s"],
        "analytic_tpu_1024shards": analytic_fullscale(),
        "paper_range": [1.9, 2.3],
        "note": (
            "The paper's 1.9-2.3x per-event penalty for exponential "
            "connectivity is a CPU/MPI substrate cost (per-message "
            "overhead + irregular event queues degrade with range). "
            "The TPU-native redesign (halo collectives + fixed-capacity "
            "tables) makes per-event cost range-independent, so the "
            "ratio drops below 1: longer-range connectivity amortizes "
            "fixed per-neuron work over 2.4x more events. Same metric, "
            "opposite sign -- a substrate win the paper's own scaling "
            "question makes visible."),
    }
    # update_root=False: the Fig-2 run reports the A/B but must not
    # silently rewrite the committed regression-guard baseline --
    # refreshing that is an explicit bench_event_delivery() run.
    # include_plastic=False: the plastic A/B belongs to the guard
    # trajectory, not the Fig-2 cost-ratio story
    out["event_delivery_ab"] = bench_event_delivery(
        grid=grid, update_root=False, include_plastic=False)
    write_json("fig2.json", out)
    return out


def main():
    out = run_bench()
    g, e = out["gaussian"], out["exponential"]
    print(f"gaussian:    cost/event {g['cost_per_event']:.3e} s "
          f"(rate {g['rate_hz']:.1f} Hz, {g['synapses']} syn)")
    print(f"exponential: cost/event {e['cost_per_event']:.3e} s "
          f"(rate {e['rate_hz']:.1f} Hz, {e['synapses']} syn)")
    print(f"cost ratio exp/gauss (single shard): "
          f"{out['cost_ratio_single_shard']:.2f}")
    for name, ab in out["event_delivery_ab"]["laws"].items():
        print(f"{name}: kernel/xla wall ratio "
              f"{ab['kernel_vs_xla_wall_ratio']:.2f} "
              f"(kernel {ab['kernel']['elapsed_s']:.3f}s, "
              f"xla {ab['xla']['elapsed_s']:.3f}s)")
    print(f"cost ratio (analytic TPU @1024 shards): "
          f"{out['analytic_tpu_1024shards']['ratio']:.2f}")
    print(f"paper (CPU/MPI cluster): 1.9-2.3  -- see note in fig2.json")


if __name__ == "__main__":
    main()
