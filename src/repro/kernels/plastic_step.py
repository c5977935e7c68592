"""One-launch plastic step: event delivery + LTD in a single Pallas call.

The plastic step used to make two full passes over the synapse tables:
the Pallas delivery kernel read every gathered event entry's weight into
the delayed-current ring, then the XLA STDP pass gathered the *same*
event rows again to apply the LTD (pre-spike) depression.  This module
fuses the two: one launch streams the lane-packed entry blocks once,
accumulating the ring contribution AND writing the depressed weights to
an output stream that the host scatters back over the event rows.

Division of labour (bitwise-equivalence argument in ``_ltd_math``):

  * **in kernel** -- delivery (identical math to
    ``synaptic_accum._accum_kernel``) plus LTD: every gathered entry is
    touched exactly once, so ``w_out = w + (-a_minus * x_post[tgt]) *
    mask`` rides the same stream read.
  * **in XLA, after the launch** -- LTP through the target-major
    inverse index (its access pattern is unrelated to the entry
    stream), the final [0, w_max] clamp, and the trace increments:
    ``core.stdp.stdp_ltp_finalize``, the *same* code the two-pass
    reference path runs.

Kernel geometry (vs. the delivery-only kernel): the grid is a single
``(n_blocks,)`` axis of ``ENTRY_BLOCK = 16384``-entry blocks and the
ring is **fully resident** -- interpret-mode profiling showed per-grid-
step overhead, not per-entry arithmetic, dominating the plastic step
(a skipped block still costs ~0.4 ms on CPU), so fewer/larger grid
steps win.  Event-proportional cost is recovered *inside* the block:
the body is a static loop over ``CHUNK = 4096``-entry chunks, each
guarded by a scalar-prefetched liveness flag (live = ``w != 0`` or
``mask != 0``; a weight can decay to exactly 0 while still plastic, and
skipping it would drop its LTD).  CHUNK equals the delivery kernel's
ENTRY_BLOCK, so the ring contribution reduces over the *same* 4096-
entry groups in the same order -- the float32 accumulation grouping the
kernel-vs-XLA bit-identity tests already pin down.

The resident ring caps the supported shard size: ``n_local`` padded to
``N_ALIGN`` must stay within ``RING_N_MAX`` (covers the committed
acceptance configs -- 8x8x60 pads to 4096 -- and any shard up to 8192
local neurons).  Every 128-entry row contracts a (d_ring * n_pad /
LANES, LANES) one-hot against the whole ring, so the MXU work per
entry grows with the ring; ``tests/test_tpu_compile.py`` compiles the
kernel at ``RING_N_MAX`` for a v5e.  Larger shards fall back to the
two-pass path -- ``fused_supported`` is the routing predicate -- which
is bit-identical, just slower.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .synaptic_accum import (_SUB, LANES, N_ALIGN, _ceil_to,
                             _gather_entries, _pad_flat, compact_events,
                             gather_rows, row_lanes, scatter_rows)

ENTRY_SUBLANES = 128        # sublanes per entry block (vs 32 for delivery)
ENTRY_BLOCK = ENTRY_SUBLANES * LANES   # 16384 entries per grid step
CHUNK = 4096                # entries per in-body liveness-gated chunk
RING_N_MAX = 8192           # max padded n_local the resident ring holds

_CSUB = CHUNK // LANES      # sublanes per chunk
_NCHUNK = ENTRY_BLOCK // CHUNK


def packed_total(entries: int) -> int:
    """Padded length of the fused plastic launch's entry stream."""
    return _ceil_to(max(entries, ENTRY_BLOCK), ENTRY_BLOCK)


def fused_supported(n_local: int) -> bool:
    """Whether the one-launch plastic step covers this shard size (the
    resident ring must fit); callers route to the two-pass path when
    not -- a pure perf fallback, both paths are bit-identical."""
    return _ceil_to(max(n_local, N_ALIGN), N_ALIGN) <= RING_N_MAX


def _plastic_kernel(neg_a_minus: float, d_ring: int,
                    meta_ref, blk_ref, chk_ref,
                    tgt_ref, w_ref, d_ref, m_ref, ring_ref, xpost_ref,
                    out_ring_ref, out_w_ref):
    """One entry-block grid step of the fused delivery + LTD pass.

    meta_ref:     scalar prefetch [t_slot]
    blk/chk_ref:  scalar prefetch liveness -- per entry block and per
                  CHUNK-entry chunk (count of live entries; 0 skips)
    tgt/w/d/m:    (ENTRY_SUBLANES, LANES) lane-packed entry block
                  (target id, weight, delay slot, plastic mask)
    ring:         full-resident (d_ring, n_hi, LANES) ring
    xpost_ref:    the decayed post-trace, transposed: (LANES, n_hi)
    out_ring:     (d_ring, n_hi, LANES) accumulator, resident across
                  blocks
    out_w:        (ENTRY_SUBLANES, LANES) depressed-weight stream
    """
    e = pl.program_id(0)

    @pl.when(e == 0)
    def _init():
        out_ring_ref[...] = ring_ref[...]

    # Unconditional: every entry's weight comes back (updated or not),
    # so the host-side scatter of event rows needs no liveness mask.
    out_w_ref[...] = w_ref[...]

    n_hi = out_ring_ref.shape[1]
    t0 = meta_ref[0]

    def ltd(r0, hi, lo):
        # LTD: exact one-hot gather of x_post[tgt] (x_post >= 0 and each
        # sum has a single nonzero term, so the gathered value is
        # bitwise x_post[tgt]), then the reference's association
        # ((-a_minus) * x) * mask.  mask == 0 (non-plastic + padding)
        # yields dw = -0.0, and w + (-0.0) == w bitwise for every
        # float32 w.
        sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANES), 0)
        xg = jnp.zeros((_SUB, LANES), jnp.float32)
        for r in range(_SUB):
            oh_hi = (hi[r:r + 1] == jax.lax.broadcasted_iota(
                jnp.int32, (n_hi, LANES), 0)).astype(jnp.float32)
            xcols = jax.lax.dot_general(
                xpost_ref[...], oh_hi,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)   # (LANES, entries)
            x_r = jnp.sum(jnp.where(
                lo[r:r + 1] == jax.lax.broadcasted_iota(
                    jnp.int32, (LANES, LANES), 0), xcols, 0.0),
                axis=0, keepdims=True)                # (1, entries)
            xg = jnp.where(sub == r, x_r, xg)
        dw = (neg_a_minus * xg) * m_ref[pl.ds(r0, _SUB), :]
        out_w_ref[pl.ds(r0, _SUB), :] = w_ref[pl.ds(r0, _SUB), :] + dw

    @pl.when(blk_ref[e] > 0)
    def _block():
        for c in range(_NCHUNK):
            @pl.when(chk_ref[e * _NCHUNK + c] > 0)
            def _chunk(c=c):
                # delivery: the delivery kernel's row contraction and
                # CHUNK-entry reduction grouping (padding entries carry
                # w == 0 and contribute an exact +0.0), with LTD riding
                # the same row loads
                acc = scatter_rows(tgt_ref, w_ref, d_ref, c * _CSUB, _CSUB,
                                   t0, 0, d_ring, n_hi, rows_hook=ltd)
                out_ring_ref[...] += acc.reshape(d_ring, n_hi, LANES)


def _chunk_liveness(w_e, m_e):
    """Per-block / per-chunk live-entry counts for the skip flags.

    Live = ``(w != 0) | (mask != 0)``: zero-weight zero-mask entries are
    delivery no-ops (+0.0 contribution) AND LTD no-ops (dw = -0.0), so
    skipping a chunk of them is bitwise free; a plastic entry whose
    weight decayed to exactly 0 keeps mask = 1 and stays live.
    """
    live = jnp.logical_or(w_e != 0.0, m_e != 0.0)
    chk = jnp.sum(live.reshape(-1, CHUNK), axis=1).astype(jnp.int32)
    blk = jnp.sum(chk.reshape(-1, _NCHUNK), axis=1).astype(jnp.int32)
    return blk, chk


def plastic_delivery_ltd(tiers: Sequence[Tuple[dict, jnp.ndarray, int]],
                         masks: Sequence[jnp.ndarray],
                         x_post_decayed: jnp.ndarray,
                         i_ring, t_slot, d_ring: int, neg_a_minus: float,
                         *, plan=None, interpret: bool):
    """Fused delivery + LTD over every tier in ONE kernel launch.

    ``tiers``: [(tables, spikes_src, active_cap)] with ``tables["w"]``
    the *live* (carry) float32 weights; ``masks``: per-tier float32
    plastic masks; ``x_post_decayed``: the (n_local,) post-synaptic
    trace *after* this step's decay (the value the reference LTD
    reads); ``neg_a_minus``: ``-params.a_minus``.  ``plan``: per-tier
    ``TierPlan`` list (validated, sizes the per-tier entry slices).

    Returns ``(ring, new_w, n_events, n_dropped)`` where ``new_w[i]``
    is tier i's full weight array with the LTD update scattered over
    this step's event rows -- bitwise equal to the reference
    ``stdp_step`` LTD phase (the full-tier ``where(mask > 0, ...)`` /
    ``clip(None, w_max)`` it applies are no-ops under the w <= w_max
    invariant ``check_weight_invariant`` enforces at init).
    """
    assert i_ring.shape[0] == d_ring
    if plan is not None and len(plan) != len(tiers):
        raise ValueError(f"delivery plan has {len(plan)} tiers, "
                         f"got {len(tiers)}")
    parts_t: List[jnp.ndarray] = []
    parts_w: List[jnp.ndarray] = []
    parts_d: List[jnp.ndarray] = []
    parts_m: List[jnp.ndarray] = []
    idxs: List[jnp.ndarray] = []
    offsets: List[int] = []
    n_events = jnp.zeros((), jnp.int32)
    n_dropped = jnp.zeros((), jnp.int32)
    off = 0
    for ti, (tables, spikes_src, active_cap) in enumerate(tiers):
        n_rows, cap = tables["tgt"].shape[0] - 1, tables["tgt"].shape[1]
        if plan is not None:
            p = plan[ti]
            if (p.rows, p.cap, p.active_cap) != (n_rows, cap, active_cap):
                raise ValueError(
                    f"tier {ti} does not match its delivery plan: tables "
                    f"are rows={n_rows} cap={cap} active_cap={active_cap}, "
                    f"plan says rows={p.rows} cap={p.cap} "
                    f"active_cap={p.active_cap}")
        idx, n_spk = compact_events(spikes_src, n_rows, active_cap)
        te, we, de = _gather_entries(tables, idx)
        me = gather_rows(masks[ti], idx, jnp.float32)
        parts_t.append(te)
        parts_w.append(we)
        parts_d.append(de)
        parts_m.append(me)
        idxs.append(idx)
        offsets.append(off)
        off += te.shape[0]
        n_events = n_events + jnp.sum(tables["nnz"][idx]).astype(jnp.int32)
        n_dropped = n_dropped + jnp.maximum(
            n_spk - active_cap, 0).astype(jnp.int32)

    total = packed_total(off)
    tgt_e, w_e, d_e = _pad_flat(jnp.concatenate(parts_t),
                                jnp.concatenate(parts_w),
                                jnp.concatenate(parts_d), total)
    m_e = jnp.pad(jnp.concatenate(parts_m), (0, total - off))

    d_r, n_local = i_ring.shape
    n_pad = _ceil_to(max(n_local, N_ALIGN), N_ALIGN)
    if n_pad > RING_N_MAX:
        raise ValueError(
            f"n_local={n_local} pads to {n_pad} > RING_N_MAX="
            f"{RING_N_MAX}: the resident-ring plastic kernel does not "
            "cover this shard size -- route through fused_supported()")
    n_hi = n_pad // LANES
    ring_p = jnp.pad(i_ring, ((0, 0), (0, n_pad - n_local))).reshape(
        d_r, n_hi, LANES)
    xpost_t = jnp.pad(x_post_decayed.astype(jnp.float32),
                      (0, n_pad - n_local)).reshape(n_hi, LANES).T
    blk, chk = _chunk_liveness(w_e, m_e)
    meta = jnp.asarray([t_slot], jnp.int32).reshape(1)
    n_blocks = total // ENTRY_BLOCK

    def packed(x, dt):
        return x.astype(dt).reshape(-1, LANES)

    entry_spec = pl.BlockSpec((ENTRY_SUBLANES, LANES),
                              lambda e, m, bl, ck: (e, 0))
    ring_spec = pl.BlockSpec((d_r, n_hi, LANES),
                             lambda e, m, bl, ck: (0, 0, 0))
    xpost_spec = pl.BlockSpec((LANES, n_hi), lambda e, m, bl, ck: (0, 0))
    gspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_blocks,),
        in_specs=[entry_spec, entry_spec, entry_spec, entry_spec,
                  ring_spec, xpost_spec],
        out_specs=[ring_spec, entry_spec])
    kernel = functools.partial(_plastic_kernel, neg_a_minus, d_r)
    ring_out, w_out = pl.pallas_call(
        kernel,
        grid_spec=gspec,
        out_shape=[jax.ShapeDtypeStruct(ring_p.shape, jnp.float32),
                   jax.ShapeDtypeStruct((total // LANES, LANES),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="plastic_step",
    )(meta, blk, chk, packed(tgt_e, jnp.int32), packed(w_e, jnp.float32),
      packed(d_e, jnp.int32), packed(m_e, jnp.float32), ring_p, xpost_t)

    w_flat = w_out.reshape(-1)
    new_w = []
    for (tables, _, active_cap), idx, off_t in zip(tiers, idxs, offsets):
        cap = tables["tgt"].shape[1]
        lanes = row_lanes(cap)
        rows_w = jax.lax.dynamic_slice(
            w_flat, (off_t,), (active_cap * lanes,)).reshape(
                active_cap, lanes)[:, :cap]
        # scatter-SET over the compacted (unique) event rows; duplicate
        # sink fills all write the sink row's unchanged 0.0
        new_w.append(tables["w"].at[idx].set(
            rows_w.astype(tables["w"].dtype)))
    return (ring_out.reshape(d_r, n_pad)[:, :n_local], new_w, n_events,
            n_dropped)
