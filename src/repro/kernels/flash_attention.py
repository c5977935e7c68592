"""Blocked online-softmax (flash) attention as a Pallas TPU kernel.

Targets the 32k-token prefill cells: O(Sq*Sk) compute on the MXU with
O(block) VMEM -- never materializing the (Sq, Sk) score matrix in HBM.
Supports causal masking, sliding-window masking (recurrentgemma's local
attention -- the 1-D analogue of the paper's distance-cutoff stencil),
GQA head grouping via the kv ``index_map`` (no KV repetition in memory),
and a static ``q_offset`` for chunked/decode use.

Grid: (B*H, nQ, nK) with the kv loop innermost; the output block's
index_map ignores the k axis, so the same (Bq, D) accumulator is
revisited across k steps with (m, l, acc) running stats in VMEM scratch.
Causally dead (q, k) block pairs still stream their KV block but skip the
matmul via ``pl.when`` -- block-sparsity on compute, which is what the
MXU actually cares about.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, q_offset, block_q, block_k, n_k,
            k_valid):
    _, qi, ki = (pl.program_id(0), pl.program_id(1), pl.program_id(2))

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block bounds as scalars; element positions as 2-D iotas (Mosaic
    # lowers neither 1-D iotas nor indexing into them)
    q_first = q_offset + qi * block_q
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1

    # block-level liveness: any (q, k) pair in this tile unmasked?
    live = k_first < k_valid
    if causal:
        live = jnp.logical_and(live, k_first <= q_last)
    if window is not None:
        live = jnp.logical_and(live, q_first - k_last < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (Bq, D)
        k = k_ref[0].astype(jnp.float32)            # (Bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_first + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < k_valid
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (Bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, ...] = (acc_ref[...] / safe * (l > 0.0)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    q_offset=0, block_q=128, block_k=128,
                    interpret: bool):
    """q: (BH, Sq, D); k, v: (BH_kv, Sk, D); BH % BH_kv == 0 (GQA).

    Returns (BH, Sq, D) in q.dtype.  Matches ``ref.attention_ref``.
    """
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    assert bh % bh_kv == 0
    group = bh // bh_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = -sq % block_q
    pad_k = -sk % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    n_q = (sq + pad_q) // block_q
    n_k = (sk + pad_k) // block_k

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_k=n_k,
        k_valid=sk)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d),
                           lambda b, i, j: (b // group, j, 0))
    out = pl.pallas_call(
        kern,
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :sq, :]
