import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x shape x mesh) cell
lowers, SPMD-partitions, and compiles on the production mesh.

The two lines above MUST run before any other import (jax locks the
device count at first init); 512 placeholder host devices back both the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh.

Per cell this script:
  1. builds the arch config + logical sharding rules for the mesh;
  2. assembles abstract inputs (ShapeDtypeStructs -- zero allocation):
     params (+ optimizer state + batch) for train cells, params (+
     decode state + token) for decode cells;
  3. ``jax.jit(step, in_shardings=...).lower(...).compile()``;
  4. records ``memory_analysis()`` (fits-per-device proof),
     ``cost_analysis()``, and the loop-aware HLO costs (FLOPs / bytes /
     collective wire bytes) into ``results/dryrun/<cell>.json``.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all [--force]
  python -m repro.launch.dryrun --snn          # paper's own configs
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

RESULTS = os.path.join(os.path.dirname(__file__),
                       "..", "..", "..", "results", "dryrun")


def _rules_for(cfg, mesh):
    """Divisibility-aware logical rules for this arch on this mesh."""
    from repro.parallel.sharding import rules_for_mesh
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = axes.get("model", 1)
    dp = axes.get("data", 1) * axes.get("pod", 1)
    ov = {}
    ov["heads"] = "model" if cfg.n_heads % model == 0 else None
    ov["mlp"] = "model" if cfg.d_ff and cfg.d_ff % model == 0 else None
    ov["experts"] = "model" if cfg.n_experts % model == 0 and \
        cfg.n_experts else None
    ov["d_inner"] = "model" if cfg.d_inner % model == 0 else None
    ov["vocab"] = "model" if cfg.padded_vocab % model == 0 else None
    return rules_for_mesh(mesh, **ov), dp


def _batch_rules(rules, shape, dp):
    import dataclasses as dc
    if shape.global_batch % dp:
        # e.g. long_500k (B=1): replicate batch, model axis still TPs
        return dc.replace(rules, batch=None)
    return rules


# ---------------------------------------------------------------------------
# Perf-iteration variants (section Perf of EXPERIMENTS.md): each entry is a
# named set of config/step overrides applied on top of the baseline.
# ---------------------------------------------------------------------------

VARIANTS = {
    # hillclimb 1 (worst roofline fraction: small models whose 12/8
    # heads don't divide the model axis, replicating attention 16x):
    # pad query/kv heads to 16 with zeroed extra out-proj rows --
    # function-exact (tests/test_variants.py), shards attention 16-way.
    "padded_heads": {"pad_heads_to": 16},
    # hillclimb 2 (most collective-bound: kimi train): fewer microbatch
    # loops -> 4x fewer FSDP weight all-gathers + grad reductions
    "micro1": {"microbatches": 1},
    "micro2": {"microbatches": 2},
    "micro4": {"microbatches": 4},
    # attention chunk-shape sweeps (memory term knob)
    "chunk512": {"attn_chunk_q": 512, "attn_chunk_k": 512},
    "chunk2048": {"attn_chunk_q": 2048, "attn_chunk_k": 2048},
    # SNN (paper-representative): f32 spike payload (paper-faithful
    # AER-ish baseline) vs 1-bit bitmap; whole-tile vs exact-strip halo
    "snn_f32_spikes": {"pack_spikes": False},
    "snn_block_halo": {"halo_mode": "block", "pack_spikes": False},
    "snn_packed": {"pack_spikes": True},
    # right-size the event-compaction capacity to the law's observed
    # rate (paper: exponential ~38 Hz) x1.5 headroom instead of
    # 100 Hz x8 -- delivery gather shrinks ~9x; drops are counted
    "snn_tight_caps": {"pack_spikes": True, "rate_cap_hz": 60.0,
                       "cap_headroom": 1.5},
    # + bf16 synapse weights: (tgt,w,dslot) row entry 9->7 bytes
    "snn_bf16_w": {"pack_spikes": True, "rate_cap_hz": 60.0,
                   "cap_headroom": 1.5, "weight_dtype": "bfloat16"},
    # combined LM variants
    "padded_chunk512": {"pad_heads_to": 16, "attn_chunk_q": 512,
                        "attn_chunk_k": 512},
    "padded_chunk2048": {"pad_heads_to": 16, "attn_chunk_q": 2048,
                         "attn_chunk_k": 2048},
    "micro2_chunk512": {"microbatches": 2, "attn_chunk_q": 512,
                        "attn_chunk_k": 512},
}


def _apply_cfg_variant(cfg, overrides: dict):
    import dataclasses as dc
    cfg_fields = {f.name for f in dc.fields(cfg)}
    patch = {}
    if overrides.get("pad_heads_to"):
        m = overrides["pad_heads_to"]
        h = -(-cfg.n_heads // m) * m
        kv = cfg.n_kv_heads if h % cfg.n_kv_heads == 0 else \
            -(-cfg.n_kv_heads // m) * m
        hd = cfg.resolved_head_dim
        patch.update(n_heads=h, n_kv_heads=kv, head_dim=hd)
    for k, v in overrides.items():
        if k in cfg_fields:
            patch[k] = v
    return dc.replace(cfg, **patch) if patch else cfg


def build_cell(arch: str, shape_name: str, mesh, variant: str | None = None):
    """Returns (step_fn, abstract_args, in_shardings, donate, meta)."""
    from repro.configs import get_config
    from repro.models.config import SHAPES
    from repro.models import model as M
    from repro.optim import adamw, adafactor, warmup_cosine

    cfg = get_config(arch)
    overrides = VARIANTS.get(variant, {}) if variant else {}
    cfg = _apply_cfg_variant(cfg, overrides)
    shape = SHAPES[shape_name]
    rules, dp = _rules_for(cfg, mesh)
    rules = _batch_rules(rules, shape, dp)

    params_abs, specs = M.abstract_params(cfg)
    param_sh = rules.shardings(specs, mesh)
    meta = {"params": int(sum(l.size for l in jax.tree.leaves(params_abs))),
            "active_params": cfg.active_param_count()}

    if shape.kind == "decode":
        step = M.make_serve_step(cfg, rules)
        state_abs = M.abstract_decode_state(cfg, shape)
        state_specs = M.decode_state_specs(cfg, shape)
        state_sh = rules.shardings(state_specs, mesh)
        token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        from jax.sharding import NamedSharding, PartitionSpec as P
        tok_sh = NamedSharding(mesh, rules.pspec("batch", None))
        pos_sh = NamedSharding(mesh, P())
        return (step, (params_abs, state_abs, token, pos),
                (param_sh, state_sh, tok_sh, pos_sh), (1,), meta)

    # train / prefill
    big = meta["params"] > 15e9          # adafactor: factored moments
    opt = adafactor(warmup_cosine(1e-4, 100, 10000)) if big else \
        adamw(warmup_cosine(3e-4, 100, 10000))
    batch_abs = M.input_specs(cfg, shape)
    from jax.sharding import NamedSharding
    batch_sh = {k: NamedSharding(mesh, rules.pspec(
        "batch", *([None] * (len(v.shape) - 1))))
        for k, v in batch_abs.items()}

    if shape.kind == "prefill":
        prefill = M.make_prefill(cfg, rules)
        state_abs = M.abstract_decode_state(cfg, shape)
        state_specs = M.decode_state_specs(cfg, shape)
        state_sh = rules.shardings(state_specs, mesh)
        return (prefill, (params_abs, batch_abs, state_abs),
                (param_sh, batch_sh, state_sh), (2,), meta)

    # gradient accumulation: bound saved layer-boundary activations
    # (per-microbatch tokens ~ 64k local) -- the memory knob at scale
    p_count = meta["params"]
    micro = 8 if p_count > 15e9 else (4 if p_count > 4e9 else 1)
    micro = overrides.get("microbatches", micro)
    meta["microbatches"] = micro
    step = M.make_train_step(cfg, rules, opt, microbatches=micro,
                             param_shardings=param_sh)
    opt_abs = opt.abstract_state(params_abs)
    opt_specs = opt.state_specs(specs)
    opt_sh = rules.shardings(opt_specs, mesh)
    return (step, (params_abs, opt_abs, batch_abs),
            (param_sh, opt_sh, batch_sh), (0, 1), meta)


def build_snn_cell(case_name: str, mesh, variant: str | None = None):
    from repro.configs.snn import CASES
    from repro.core.dist_engine import (DistConfig, SimInputs,
                                        abstract_dist_inputs, make_sim_fn)
    case = CASES[case_name]
    overrides = VARIANTS.get(variant, {}) if variant else {}
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ty = axes.get("pod", 1) * axes.get("data", 1)
    tx = axes.get("model", 1)
    eng_kw = {k: v for k, v in overrides.items()
              if k in ("rate_cap_hz", "cap_headroom", "d_ring", "mode",
                       "weight_dtype")}
    ecfg = case.engine_config(ty, tx, **eng_kw)
    dcfg = DistConfig(
        engine=ecfg,
        axis_y=("pod", "data") if "pod" in axes else "data",
        axis_x="model",
        halo_mode=overrides.get("halo_mode", "strip"),
        pack_spikes=overrides.get("pack_spikes", True))
    sim = make_sim_fn(dcfg, mesh, n_steps=10)
    state_abs, tables_abs = abstract_dist_inputs(dcfg)
    spec = ecfg.spec()
    meta = {"neurons": case.grid[0] * case.grid[1] * case.n_per_column,
            "synapses_per_shard": spec.expected_synapses(),
            "table_bytes_per_shard": spec.table_bytes(),
            "halo_radius": ecfg.law.radius,
            "tiles": (ty, tx)}
    return sim, (state_abs, SimInputs(tables=tables_abs)), None, (0,), meta


def analytic_memory(abstract_args, shardings, mesh) -> dict:
    """Exact per-device bytes of every jit INPUT (params, opt state,
    decode state, batch) from the abstract shapes and their
    NamedShardings.  This is the ground-truth state footprint on the
    bf16-native TPU target: XLA:CPU's memory_analysis() overstates
    bf16 models (float-normalization materializes f32 shadows of bf16
    arithmetic, and CPU fusion is weaker), so both numbers are
    reported.  Transient activations come on top -- bounded by the
    microbatch/remat policy."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(abstract_args),
                        jax.tree.leaves(shardings, is_leaf=lambda x:
                                        hasattr(x, "spec"))):
        n_bytes = 1
        for d in leaf.shape:
            n_bytes *= d
        n_bytes *= leaf.dtype.itemsize
        try:
            n_shards = len(set(map(tuple, sh.devices_indices_map(
                leaf.shape).values())))
        except Exception:
            n_shards = 1
        total += n_bytes // max(n_shards, 1)
    return {"input_state_bytes_per_device": int(total)}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str = RESULTS, force: bool = False,
             variant: str | None = None) -> dict:
    from repro.launch.mesh import make_production_mesh, mesh_chips
    from repro.perf.hlo_analysis import analyze_hlo
    from repro.perf.roofline import model_flops, roofline_terms

    cell_id = f"{arch}__{shape_name}__{mesh_kind}"
    if variant:
        cell_id += f"__{variant}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        if arch.startswith("snn-"):
            fn, args, shardings, donate, meta = build_snn_cell(
                arch, mesh, variant)
            jitted = fn  # make_sim_fn already jits (shard_map in_specs)
            lowered = jitted.lower(*args)
        else:
            fn, args, shardings, donate, meta = build_cell(
                arch, shape_name, mesh, variant)
            jitted = jax.jit(fn, in_shardings=shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        }
        if shardings is not None:
            mem_d.update(analytic_memory(args, shardings, mesh))
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        costs = analyze_hlo(hlo)

        if arch.startswith("snn-"):
            mflops = 0.0
        else:
            from repro.configs import get_config
            from repro.models.config import SHAPES
            mflops = model_flops(get_config(arch), SHAPES[shape_name])
        rep = roofline_terms(arch, shape_name, mesh_kind,
                             mesh_chips(mesh), costs, mflops,
                             peak_bytes=mem_d["peak_bytes"])
        kernelized = None
        if not arch.startswith("snn-"):
            from repro.configs import get_config
            from repro.models.config import SHAPES
            from repro.perf.attention_credit import chunk_traffic_bytes
            from repro.perf.roofline import HW
            cfg_v = _apply_cfg_variant(
                get_config(arch), VARIANTS.get(variant, {}) if variant
                else {})
            credit = chunk_traffic_bytes(
                cfg_v, SHAPES[shape_name], chips=mesh_chips(mesh),
                microbatches=meta.get("microbatches", 1))
            kernelized = {
                "attn_chunk_bytes": credit,
                "memory_s_flash": max(
                    costs.bytes - credit, 0.0) / HW().hbm_bw,
            }
        out = {
            "cell": cell_id, "ok": True,
            "kernelized": kernelized,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "meta": meta, "memory": mem_d,
            "xla_cost": {k: ca.get(k) for k in
                         ("flops", "bytes accessed")},
            "roofline": rep.to_dict(),
            "hlo_bytes_len": len(hlo),
        }
    except Exception as e:  # noqa: BLE001 - recorded as cell failure
        out = {"cell": cell_id, "ok": False, "error": repr(e),
               "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--snn", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default=None, choices=list(VARIANTS))
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all or (not args.arch and not args.snn):
        from repro.configs import all_cells
        cells = [(a, s.name) for a, s in all_cells()]
    elif args.arch and not args.arch.startswith("snn-"):
        from repro.configs import shape_cells
        shapes = [args.shape] if args.shape else \
            [s.name for s in shape_cells(args.arch)]
        cells = [(args.arch, s) for s in shapes]
    if args.snn:
        from repro.configs.snn import CASES
        cells += [(c, "sim") for c in CASES]
    if args.arch and args.arch.startswith("snn-"):
        cells = [(args.arch, "sim")]

    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            r = run_cell(arch, shape, mk, out_dir=args.out,
                         force=args.force, variant=args.variant)
            status = "OK " if r["ok"] else "FAIL"
            extra = ""
            if r["ok"]:
                rl = r["roofline"]
                extra = (f"dom={rl['dominant']:10s} "
                         f"peakGB={r['memory']['peak_bytes']/2**30:7.2f} "
                         f"compile={r['compile_s']:6.1f}s")
            else:
                failures += 1
                extra = r["error"][:120]
            print(f"[{status}] {arch:24s} {shape:12s} {mk:6s} {extra}",
                  flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
