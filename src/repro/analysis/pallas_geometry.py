"""Pallas geometry pass (``pallas-geometry``).

The delivery pipeline's kernels are only correct *and* only fit in
VMEM under specific alignment facts: the entry stream is lane-packed
as ``(E/128, 128)`` so ``LANES`` is pinned to the TPU lane width,
block minor dims must be multiples of 128 (or 1) and second-minor
multiples of 8 (or 1) to match Mosaic's (8, 128) f32 tiling, and the
per-row one-hot MXU factors scale with the ring tile ``d_ring *
TILE_N``.  Checks, per module under ``kernels/``:

* ``LANES == 128``; ``TILE_N`` / ``OUT_TILE`` / ``CHUNK`` divisible by
  ``LANES``; ``ENTRY_BLOCK == ENTRY_SUBLANES * LANES``;
* every ``pl.BlockSpec((a, b), ...)`` with statically-foldable dims:
  ``b % 128 == 0`` (or ``b == 1``) and ``a % 8 == 0`` (or ``a == 1``);
* the VMEM footprint at the engine's default ``d_ring`` (read from
  ``EngineConfig``) stays under the ~16 MiB VMEM budget -- both the
  ring-tiled delivery layout (a ``(d_ring, TILE_N)`` ring tile) and the
  fused plastic step's resident-ring layout (the whole ``(d_ring,
  RING_N_MAX)`` ring live across grid steps).  This is an estimate;
  ``tests/test_tpu_compile.py`` has the TPU compiler check the real
  kernels.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, Optional

from .core import (Checker, Finding, Module, Project, eval_const,
                   module_int_constants)

NAME = "pallas-geometry"

VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_LANE = 128
_SUBLANE = 8


def _engine_d_ring_default(project: Project) -> int:
    for mod in project.modules:
        if not mod.path.replace("\\", "/").endswith("core/engine.py"):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef) \
                    and node.name == "EngineConfig":
                for s in node.body:
                    if isinstance(s, ast.AnnAssign) \
                            and isinstance(s.target, ast.Name) \
                            and s.target.id == "d_ring" \
                            and isinstance(s.value, ast.Constant) \
                            and isinstance(s.value.value, int):
                        return s.value.value
    return 8


class PallasGeometryChecker(Checker):
    name = NAME
    description = ("lane/sublane alignment of kernel constants and "
                   "BlockSpecs, one-hot factor footprint vs the VMEM "
                   "budget")

    def run(self, project: Project) -> Iterable[Finding]:
        d_ring = _engine_d_ring_default(project)
        for mod in project.modules:
            p = mod.path.replace("\\", "/")
            if "/kernels/" not in p and not p.startswith("kernels/"):
                continue
            env = module_int_constants(mod)
            yield from self._constants(mod, env)
            yield from self._blockspecs(mod, env)
            yield from self._vmem_budget(mod, env, d_ring)

    # ---- named constants ----------------------------------------------
    def _constants(self, mod: Module, env: Dict[str, int]) \
            -> Iterable[Finding]:
        def line_of(name: str) -> int:
            for node in mod.tree.body:
                if isinstance(node, ast.Assign) \
                        and isinstance(node.targets[0], ast.Name) \
                        and node.targets[0].id == name:
                    return node.lineno
            return 1

        lanes = env.get("LANES")
        if lanes is not None and lanes != _LANE:
            yield Finding(
                mod.path, line_of("LANES"), self.name,
                f"LANES = {lanes}: the entry stream is lane-packed as "
                "(E/128, 128); LANES is the TPU lane width, not tunable")
        lanes = lanes or _LANE
        for cname in ("TILE_N", "OUT_TILE", "CHUNK"):
            v = env.get(cname)
            if v is not None and v % lanes:
                yield Finding(
                    mod.path, line_of(cname), self.name,
                    f"{cname} = {v} is not a multiple of LANES "
                    f"({lanes}): lane-packed blocks would straddle "
                    "tiles")
        eb, es = env.get("ENTRY_BLOCK"), env.get("ENTRY_SUBLANES")
        if eb is not None and es is not None and eb != es * lanes:
            yield Finding(
                mod.path, line_of("ENTRY_BLOCK"), self.name,
                f"ENTRY_BLOCK = {eb} != ENTRY_SUBLANES * LANES "
                f"({es} * {lanes}): the (sublanes, lanes) entry block "
                "reshape breaks")

    # ---- BlockSpec literal shapes -------------------------------------
    def _blockspecs(self, mod: Module, env: Dict[str, int]) \
            -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dn = mod.resolve_dotted(node.func)
            if not dn or dn.split(".")[-1] != "BlockSpec":
                continue
            shape = node.args[0] if node.args else None
            if not isinstance(shape, ast.Tuple) or len(shape.elts) < 2:
                continue
            dims = [eval_const(e, env) for e in shape.elts]
            minor, second = dims[-1], dims[-2]
            if minor is not None and minor != 1 and minor % _LANE:
                yield Finding(
                    mod.path, node.lineno, self.name,
                    f"BlockSpec minor dim {minor} is not a multiple of "
                    f"{_LANE}: Mosaic pads to the (8, 128) register "
                    "tile -- wasted VMEM and relayouts")
            if second is not None and second != 1 and second % _SUBLANE:
                yield Finding(
                    mod.path, node.lineno, self.name,
                    f"BlockSpec second-minor dim {second} is not a "
                    f"multiple of {_SUBLANE} (f32 sublane tile)")

    # ---- kernel VMEM footprint ----------------------------------------
    def _vmem_budget(self, mod: Module, env: Dict[str, int],
                     d_ring: int) -> Iterable[Finding]:
        lanes = env.get("LANES", _LANE)
        if not lanes:
            return
        f32 = 4
        lane_onehot = lanes * lanes * f32
        eb, tile_n = env.get("ENTRY_BLOCK"), env.get("TILE_N")
        if eb is not None and tile_n is not None:
            # ring-tiled delivery kernel: each 128-entry row builds a
            # (d_ring * TILE_N / LANES, LANES) row one-hot and adds its
            # product into a sum of that shape (three such live at
            # once), against a (d_ring, TILE_N) ring tile in and out;
            # pipelined inputs and outputs are double-buffered
            row_onehot = 3 * d_ring * tile_n * f32
            ring_tiles = 2 * 2 * d_ring * tile_n * f32
            entry_blocks = 2 * 3 * eb * f32
            total = row_onehot + lane_onehot + ring_tiles + entry_blocks
            if total > VMEM_BUDGET_BYTES:
                yield Finding(
                    mod.path, 1, self.name,
                    f"delivery kernel at ENTRY_BLOCK={eb}, "
                    f"TILE_N={tile_n}, d_ring={d_ring} needs "
                    f"~{total / 2**20:.1f} MiB of VMEM "
                    f"(budget {VMEM_BUDGET_BYTES / 2**20:.0f} MiB): "
                    "shrink TILE_N or ENTRY_BLOCK")
        rnm, chunk = env.get("RING_N_MAX"), env.get("CHUNK")
        if rnm is not None and chunk is not None:
            # resident-ring fused plastic kernel: the whole
            # (d_ring, RING_N_MAX) ring (in + accumulator) stays in
            # VMEM across grid steps, and each 128-entry row contracts
            # a (d_ring * RING_N_MAX / LANES, LANES) row one-hot
            # against it; the LTD gather holds the post-trace and a
            # one-hot of its size; 5 entry streams (tgt/w/d/mask in,
            # depressed w out) ride along, double-buffered
            row_onehot = 3 * d_ring * rnm * f32
            rings = 2 * 2 * d_ring * rnm * f32
            xpost = 3 * rnm * f32
            streams = 2 * 5 * (eb or chunk) * f32
            total = row_onehot + lane_onehot + rings + xpost + streams
            if total > VMEM_BUDGET_BYTES:
                yield Finding(
                    mod.path, 1, self.name,
                    f"resident-ring plastic kernel at "
                    f"RING_N_MAX={rnm}, d_ring={d_ring} needs "
                    f"~{total / 2**20:.1f} MiB of VMEM "
                    f"(budget {VMEM_BUDGET_BYTES / 2**20:.0f} MiB): "
                    "shrink RING_N_MAX (larger shards take the "
                    "two-pass fallback)")
