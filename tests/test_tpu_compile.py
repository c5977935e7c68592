"""Compile the Pallas kernels for a described TPU v5e at real widths.

Interpret mode (every other test) runs the kernel bodies with jnp ops,
so it cannot see what Mosaic refuses: relayouts between sublanes and
lanes, ops with no TPU lowering, more VMEM than a kernel may use.  Here
the installed TPU compiler compiles each kernel for a chip that is
described, not attached -- no chip is needed and nothing runs.

Widths: the paper's 1240-neuron column on an 8x8 grid, on one tile
(79,360 neurons, int32 target ids) and on the 2x2 tiling (19,840 per
tile, int16 ids, with the halo fan-out bands); the plastic kernel at
its largest resident ring, ``RING_N_MAX``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers import
every test file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import lif_step as ls
from repro.kernels import plastic_step as ps
from repro.kernels import spike_compact as sc
from repro.kernels import synaptic_accum as sa
from repro.runtime.jobs import SimJobSpec, dist_config_for

GRID, NPC = 8, 1240
N_ONE_TILE = GRID * GRID * NPC          # 79,360


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 - skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; return its HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _launches(hlo: str, kernel: str) -> bool:
    return any(kernel in line and 'custom_call_target="tpu_custom_call"'
               in line for line in hlo.splitlines())


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("w_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("law,tiles", [("gaussian", (1, 1)),
                                       ("exponential", (2, 2))])
def test_banded_delivery_compiles(one_chip, law, tiles, w_dtype):
    """Both contractions: the one-pass bfloat16 one (static storage) and
    the float32 one at ``Precision.HIGHEST``."""
    spec = dataclasses.replace(dist_config_for(
        SimJobSpec(ckpt_dir="unused", grid=GRID, n_per_column=NPC, law=law),
        tiles).engine.spec(), weight_dtype=w_dtype)
    plan = spec.delivery_plan()
    tables = _on(one_chip, spec.abstract_tables().tiers())
    assert all(t["w"].dtype == jnp.dtype(w_dtype) for t in tables)
    spikes = [jax.ShapeDtypeStruct((p.rows,), jnp.float32,
                                   sharding=one_chip) for p in plan]
    ring = jax.ShapeDtypeStruct((8, spec.n_local), jnp.float32,
                                sharding=one_chip)
    assert (len(plan) > 1) == (tiles != (1, 1))     # halo bands iff tiled

    def deliver(tables, spikes, ring):
        tiers = [(t, s, p.active_cap)
                 for t, s, p in zip(tables, spikes, plan)]
        return sa.event_delivery_banded(tiers, ring, 3, 8, plan=plan,
                                        interpret=False)

    assert _launches(_compile(deliver, tables, spikes, ring),
                     "synaptic_accum")


def test_plastic_step_compiles_at_ring_n_max(one_chip):
    n, cap, active = ps.RING_N_MAX, 1459, ps.RING_N_MAX * 4 // 5
    tables = _on(one_chip, {
        "tgt": jax.ShapeDtypeStruct((n + 1, cap), jnp.int16),
        "w": jax.ShapeDtypeStruct((n + 1, cap), jnp.float32),
        "dslot": jax.ShapeDtypeStruct((n + 1, cap), jnp.int8),
        "nnz": jax.ShapeDtypeStruct((n + 1,), jnp.int32)})
    mask, spikes, x_post = _on(one_chip, (
        jax.ShapeDtypeStruct((n + 1, cap), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32)))
    ring = jax.ShapeDtypeStruct((8, n), jnp.float32, sharding=one_chip)

    def step(tables, spikes, mask, x_post, ring):
        return ps.plastic_delivery_ltd([(tables, spikes, active)], [mask],
                                       x_post, ring, 3, 8, -0.01,
                                       interpret=False)

    assert _launches(_compile(step, tables, spikes, mask, x_post, ring),
                     "plastic_step")


def test_spike_compact_compiles(one_chip):
    spikes = jax.ShapeDtypeStruct((N_ONE_TILE,), jnp.float32,
                                  sharding=one_chip)
    hlo = _compile(lambda s: sc.spike_compact_pallas(
        s, N_ONE_TILE, N_ONE_TILE * 4 // 5, interpret=False), spikes)
    assert _launches(hlo, "spike_compact")


def test_lif_step_compiles(one_chip):
    f32 = jax.ShapeDtypeStruct((N_ONE_TILE,), jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((N_ONE_TILE,), jnp.int32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((N_ONE_TILE,), jnp.bool_, sharding=one_chip)
    params = dict(leak_decay=0.95, sfa_decay=0.99, g_sfa=0.1, dt_ms=1.0,
                  v_rest=0.0, v_reset=0.0, theta=20.0, alpha_c=0.1,
                  refrac_steps=2)
    hlo = _compile(lambda v, c, r, i, a: ls.lif_step_pallas(
        v, c, r, i, a, interpret=False, **params), f32, f32, i32, f32, act)
    assert _launches(hlo, "lif_step")


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((8, 2048, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 2048, 128), jnp.bfloat16,
                              sharding=one_chip)
    hlo = _compile(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=512, interpret=False), q, kv, kv)
    assert _launches(hlo, "flash_attention")
