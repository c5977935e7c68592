"""Spike compaction as a Pallas TPU kernel (device-side spike recording).

The spike observatory's device-side recorder needs, every step, the
ascending list of spiking-neuron indices -- the same compaction the
event-delivery pipeline performs with ``jnp.nonzero`` (see
``compact_events`` in ``synaptic_accum.py``).  This module provides that
compaction as a Pallas kernel so the recording path can ride the same
``use_kernels="auto"`` routing as delivery: compiled on TPU, interpreted
elsewhere, with ``compact_events`` as the bit-identical XLA fallback.

TPU shape of the problem (a stream compaction):

  * the spike mask is streamed in ``CHUNK = 8 x 128`` blocks; a running
    spike count in SMEM scratch carries the output base offset from
    chunk to chunk (the grid is sequential on TPU);
  * within a chunk, each live entry's output position is ``base +
    inclusive_prefix(mask) - 1`` in row-major order.  The prefix is
    three exact small-integer matmuls: ``mask @ U`` (U upper
    triangular ones) counts within a row, and ``(L @ mask) @ 1`` (L
    strictly lower triangular) counts the rows above;
  * the scatter to that position is the delivery kernel's two-level
    one-hot contraction (``synaptic_accum.row_contrib``): the output
    index list is laid out ``(cap / LANES, LANES)``, so a position
    splits into a row and a lane, and each mask row contracts its
    lanes against a row one-hot and a value-carrying lane one-hot;
  * the output index list is tiled ``OUT_TILE`` slots at a time on an
    outer grid dimension; every output tile re-streams the chunks
    (recomputing the cheap prefix) and scatters only when the chunk's
    positions overlap its window;
  * the last chunk of each output-tile pass rewrites the accumulated
    ``index + 1`` values to the ``compact_events`` contract: ascending
    spiking indices in the first ``min(count, cap)`` slots, the sink row
    ``n_rows`` everywhere else, and the (uncapped) spike count as a
    scalar output.

Indices ride the MXU as f32 at ``Precision.HIGHEST``, exact for
``n_rows < 2**24`` -- far above any per-shard neuron count this repo
targets (an 8x8 grid of 1240-neuron columns on one tile is 79,360).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .synaptic_accum import LANES, _ceil_to, row_contrib, split_target

SUBLANES = 8
CHUNK = SUBLANES * LANES       # spike-mask entries consumed per grid step
OUT_ROWS = 64                  # output index rows per outer grid step
OUT_TILE = OUT_ROWS * LANES    # output index slots per outer grid step


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _compact_kernel(mask_ref, out_ref, cnt_ref, base_ref, *, cap: int,
                    n_rows: int):
    """One (output-tile, chunk) grid step of the stream compaction.

    mask_ref: (SUBLANES, LANES) spike-mask chunk (f32, >0 == spiking)
    out_ref:  (OUT_ROWS, LANES) index-list tile, resident across chunks
    cnt_ref:  (1, 1) SMEM -- total (uncapped) spike count
    base_ref: (1,) SMEM scratch -- running count across chunks
    """
    o = pl.program_id(0)
    c = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(c == 0)
    def _reset():
        out_ref[...] = jnp.zeros_like(out_ref)
        base_ref[0] = 0

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(jnp.int32, shape, dim)

    live = (mask_ref[...] > 0.0).astype(jnp.float32)      # (8, 128)
    upper = (iota((LANES, LANES), 0) <= iota((LANES, LANES), 1)).astype(
        jnp.float32)
    below = (iota((SUBLANES, SUBLANES), 1)
             < iota((SUBLANES, SUBLANES), 0)).astype(jnp.float32)
    incl = _dot(live, upper) + _dot(_dot(below, live),
                                    jnp.ones((LANES, LANES), jnp.float32))
    incl = incl.astype(jnp.int32)                          # row-major
    n_live = jnp.sum(live).astype(jnp.int32)
    base = base_ref[0]
    lo_slot = o * OUT_TILE

    @pl.when(jnp.logical_and(base < jnp.minimum(lo_slot + OUT_TILE, cap),
                             base + n_live > lo_slot))
    def _scatter():
        pos = base + incl - 1
        rel = pos - lo_slot                                # tile's frame
        ok = jnp.logical_and(live > 0.0, jnp.logical_and(
            pos < cap, jnp.logical_and(rel >= 0, rel < OUT_TILE)))
        gidx = (c * CHUNK + iota((SUBLANES, LANES), 0) * LANES
                + iota((SUBLANES, LANES), 1))
        # out[p] += gidx + 1 at p = rel; rows of other tiles carry 0
        val = jnp.where(ok, gidx + 1, 0).astype(jnp.float32)
        hi, lo = split_target(jnp.where(ok, rel, 0))
        acc = jnp.zeros((OUT_ROWS, LANES), jnp.float32)
        for r in range(SUBLANES):
            acc = acc + row_contrib(hi[r:r + 1], lo[r:r + 1],
                                    val[r:r + 1], OUT_ROWS)
        out_ref[...] += acc.astype(jnp.int32)

    new_base = base + n_live
    base_ref[0] = new_base

    @pl.when(c == n_chunks - 1)
    def _finalize():
        k = jnp.minimum(new_base, cap)
        slot = (lo_slot + iota((OUT_ROWS, LANES), 0) * LANES
                + iota((OUT_ROWS, LANES), 1))
        out_ref[...] = jnp.where(slot < k, out_ref[...] - 1, n_rows)
        cnt_ref[0, 0] = new_base


def spike_compact_pallas(spikes, n_rows: int, active_cap: int, *,
                         interpret: bool):
    """Kernel-backed drop-in for ``synaptic_accum.compact_events``.

    ``spikes``: (>= n_rows,) spike vector (>0 == spiking).  Returns
    ``(idx, count)``: ``idx`` (active_cap,) int32 holds the ascending
    indices of the first ``min(count, active_cap)`` spiking rows, padded
    with the sink row ``n_rows``; ``count`` is the uncapped spike count
    (callers derive drops as ``max(count - active_cap, 0)``).
    """
    spk = spikes[:n_rows].astype(jnp.float32)
    n_pad = _ceil_to(max(n_rows, CHUNK), CHUNK)
    spk = jnp.pad(spk, (0, n_pad - n_rows))
    cap_pad = _ceil_to(max(active_cap, OUT_TILE), OUT_TILE)
    n_chunks = n_pad // CHUNK
    n_out = cap_pad // OUT_TILE

    mask_spec = pl.BlockSpec((SUBLANES, LANES), lambda o, c: (c, 0))
    out_spec = pl.BlockSpec((OUT_ROWS, LANES), lambda o, c: (o, 0))
    cnt_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    idx, cnt = pl.pallas_call(
        functools.partial(_compact_kernel, cap=active_cap, n_rows=n_rows),
        grid=(n_out, n_chunks),
        in_specs=[mask_spec],
        out_specs=(out_spec, cnt_spec),
        out_shape=(jax.ShapeDtypeStruct((cap_pad // LANES, LANES),
                                        jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="spike_compact",
    )(spk.reshape(-1, LANES))
    return idx.reshape(-1)[:active_cap], cnt[0, 0]
