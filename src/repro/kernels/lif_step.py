"""Fused LIF+SFA neuron update as a Pallas TPU kernel.

The neuron update is the per-step *memory-bound* stage: every state array
(v, c, refrac) plus the input current must stream HBM->VMEM->HBM exactly
once.  Unfused, XLA can end up re-reading state between the where-chains;
the kernel guarantees the single-pass roofline: 24 B/neuron/step
(3 x f32 state read + write) amortized across the chain of selects.

Layout: the flat (n,) neuron arrays are padded and viewed as (rows, 128)
lanes -- 128 is the TPU lane width; blocks of (block_rows, 128) keep the
VMEM working set (6 arrays x block) around 1.5 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 512


def _kernel(v_ref, c_ref, r_ref, i_ref, a_ref,
            vo_ref, co_ref, ro_ref, so_ref, *,
            leak_decay, sfa_decay, g_sfa, dt_ms, v_rest, v_reset, theta,
            alpha_c, refrac_steps):
    v = v_ref[...]
    c = c_ref[...]
    r = r_ref[...]
    i = i_ref[...]
    a = a_ref[...]
    refractory = r > 0
    v_int = v_rest + (v - v_rest) * leak_decay + i - g_sfa * c * dt_ms
    v_new = jnp.where(refractory, v_reset, v_int)
    spiked = jnp.logical_and(v_new >= theta, a)
    spk = spiked.astype(jnp.float32)
    vo_ref[...] = jnp.where(spiked, v_reset, v_new).astype(v.dtype)
    co_ref[...] = (c * sfa_decay + alpha_c * spk).astype(c.dtype)
    ro_ref[...] = jnp.where(spiked, jnp.int32(refrac_steps),
                            jnp.maximum(r - 1, 0)).astype(jnp.int32)
    so_ref[...] = spk


def lif_step_pallas(v, c, refrac, i_total, active, *, leak_decay, sfa_decay,
                    g_sfa, dt_ms, v_rest, v_reset, theta, alpha_c,
                    refrac_steps, interpret: bool,
                    block_rows: int = DEFAULT_BLOCK_ROWS):
    """Fused update on flat (n,) state arrays.  Returns (v, c, refrac, spk)."""
    n = v.shape[0]
    # clamp the block to the problem: small nets (tests, reduced grids)
    # must not pad up to a full 512x128 production block
    block_rows = min(block_rows, max(-(-n // LANES), 8))
    blk = block_rows * LANES
    n_pad = -n % blk

    def pad2d(x, fill=0):
        x = jnp.pad(x, (0, n_pad), constant_values=fill)
        return x.reshape(-1, LANES)

    args = (pad2d(v), pad2d(c), pad2d(refrac), pad2d(i_total),
            pad2d(active))
    rows = args[0].shape[0]
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    kern = functools.partial(
        _kernel, leak_decay=leak_decay, sfa_decay=sfa_decay, g_sfa=g_sfa,
        dt_ms=dt_ms, v_rest=v_rest, v_reset=v_reset, theta=theta,
        alpha_c=alpha_c, refrac_steps=refrac_steps)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=[spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), v.dtype),
            jax.ShapeDtypeStruct((rows, LANES), c.dtype),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="lif_step",
    )(*args)
    return tuple(x.reshape(-1)[:n] for x in out)
