"""Event-driven synaptic delivery as a fused Pallas TPU pipeline.

This is DPSNN's hot loop: deliver every spike through its synapse-table
row into the delayed-current ring.  The per-step sequence

    spike compaction -> event-index prefetch -> row gather -> ring
    scatter-add

is fused into one kernel-layer entry point (``event_delivery`` /
``event_delivery_banded``) so the engines never stitch the stages
together themselves.  The TPU shape of the problem:

  * **compaction** (``jnp.nonzero`` with a static ``active_cap``) puts
    the spiking rows first and pads with the all-zero sink row, so the
    valid synapse entries form a prefix of each tier's gathered event
    list.
  * **gather** streams only the event rows' (tgt, w, dslot) triples out
    of the synapse tables; the flattened entry list is what the kernel
    consumes, so tiers with different row capacities (the geometric halo
    fan-out bands) concatenate into ONE kernel launch per step instead
    of one launch per band.
  * **lane packing**: the flat entry stream is repacked to
    ``(E / LANES, LANES)`` so each grid step consumes an
    ``(ENTRY_SUBLANES, LANES)`` block -- ``ENTRY_BLOCK = 4096`` entries
    with every vector lane live, where the previous layout fed ``(E, 1)``
    columns that used 1 of 128 lanes.
  * **scatter-add** runs as a *two-level* one-hot contraction on the
    MXU.  The target id is factored as ``tgt = i * tile_n + a * LANES +
    b`` (ring tile, sublane group, lane); the ring tile ``i`` is a grid
    dimension, and within a tile the contribution is

        out[d, a, b] = sum_e w[e] * [slot[e] == d] * [hi[e] == a]
                                  * [lo[e] == b]

    The entry axis stays on lanes throughout: each sublane row of an
    entry block (``LANES`` entries) builds a ``(R, LANES)`` row one-hot
    (``R = d_ring * tile_n / LANES``, the fused (slot, sublane-group)
    row id) and a ``(LANES, LANES)`` lane one-hot carrying ``w``, and
    one matmul contracting the lanes of both gives the ``(R, LANES)``
    contribution.  The ring is handed to the kernel as ``(d_ring,
    n_pad / LANES, LANES)`` so that contribution adds in place; the
    reshape from and back to ``(d_ring, n_local)`` happens in XLA.
    Nothing in the kernel moves data between sublanes and lanes, which
    Mosaic cannot lower for these shapes.
  * **block skipping** is per (ring tile, entry block): scalar-prefetched
    per-block [first, last] target-tile windows (min/max of the live
    ``w != 0`` entries) let ``pl.when`` skip a block on every tile it
    does not touch -- all-padding blocks carry an empty window and are
    skipped everywhere, so runtime stays proportional to spikes x
    fan-out (synaptic events, the paper's cost unit) and a block whose
    targets live in ring tile 0 is no longer streamed through every
    other tile.
  * the grid is 2-D ``(n_tiles, n_blocks)`` with the entry-block
    dimension innermost: each ring tile stays VMEM-resident while every
    entry block streams past it.

The contraction's pass count follows the weight stream's dtype, which
is the tables' storage dtype (``weight_stream_dtype``).  A bfloat16
stream (static tables, quantized at build time) builds both one-hot
factors in bfloat16 and contracts them in ONE default-precision MXU
pass into a float32 accumulator: a one-hot factor holds only 0 and 1,
so every product is an exact bfloat16 weight, and the result is bit for
bit what ``Precision.HIGHEST`` gives on a float32 copy.  A float32
stream (plastic weights, float32 tables, and ``spike_compact``'s
neuron indices) keeps ``Precision.HIGHEST``: one bfloat16 pass would
round it.

Interpret mode (CPU) executes the identical BlockSpec tiling and kernel
body with jnp ops, so tests exercise the same code path that compiles
on TPU; ``tests/test_tpu_compile.py`` compiles it for a described v5e.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128               # vector lane width: packed entry minor dim
ENTRY_SUBLANES = 32       # sublanes per entry block
ENTRY_BLOCK = ENTRY_SUBLANES * LANES   # synapse entries per grid step
TILE_N = 4096             # max ring-tile width (lane dim, multiple of 128)
N_ALIGN = 8 * LANES       # ring width alignment: whole (8, 128) tiles

# Per sublane row the kernel holds a (d_ring * TILE_N / LANES, LANES)
# row one-hot and a (LANES, LANES) lane factor, both in the weight
# stream's dtype: 64 + 32 KiB for bfloat16, 128 + 64 KiB for float32 at
# d_ring=8.  The float32 ring tile in and out is 2 x 128 KiB.

_FAR = 2 ** 30            # min-reduction sentinel for non-live entries
_SUB = 8                  # sublanes per vreg: rows loaded at a time
_LANE_BITS = 7            # log2(LANES): tgt = hi * LANES + lo


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def packed_total(entries: int) -> int:
    """Total padded length of a lane-packed entry stream holding
    ``entries`` entries -- the single source of truth for the launch's
    block count, shared with ``SynapseTableSpec.entry_geometry()``."""
    return _ceil_to(max(entries, ENTRY_BLOCK), ENTRY_BLOCK)


def weight_stream_dtype(w_dtype) -> jnp.dtype:
    """The dtype delivery carries weights stored as ``w_dtype`` in:
    bfloat16 stays bfloat16 (one MXU pass per lane row), anything else
    is widened to float32 (``Precision.HIGHEST``)."""
    if jnp.dtype(w_dtype) == jnp.bfloat16:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def row_contrib(rid, lo, w, n_rows: int):
    """One-hot contraction of one lane-packed entry row.

    ``rid``/``lo``/``w``: (1, LANES) -- each entry's ring row id, lane
    and weight.  Returns the (n_rows, LANES) f32 contribution
    ``out[r, b] = sum_e w[e] * [rid[e] == r] * [lo[e] == b]``: the row
    one-hot and the weighted lane one-hot both keep the entries on
    lanes, and the matmul contracts those lanes.  Both factors take
    ``w``'s dtype: bfloat16 contracts in one exact MXU pass, float32 at
    ``Precision.HIGHEST``.
    """
    oh_row = (rid == jax.lax.broadcasted_iota(
        jnp.int32, (n_rows, LANES), 0)).astype(w.dtype)
    lane_w = jnp.where(lo == jax.lax.broadcasted_iota(
        jnp.int32, (LANES, LANES), 0), w.astype(jnp.float32),
        0.0).astype(w.dtype)
    precision = (jax.lax.Precision.DEFAULT if w.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    return jax.lax.dot_general(
        oh_row, lane_w, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def split_target(tgt):
    """``tgt = hi * LANES + lo`` (floor semantics for negative ids)."""
    return (jnp.right_shift(tgt, _LANE_BITS),
            jnp.bitwise_and(tgt, LANES - 1))


def scatter_rows(tgt_ref, w_ref, d_ref, row0: int, n_rows: int, t0,
                 base, d_ring: int, n_hi: int, rows_hook=None):
    """Sum the contributions of entry rows ``[row0, row0 + n_rows)`` to
    a ring tile of ``n_hi * LANES`` neurons starting at neuron ``base``.

    Returns the (d_ring * n_hi, LANES) f32 sum, row ``d * n_hi + a``
    holding slot ``d``, neurons ``base + a * LANES + [0, LANES)``.
    ``w_ref``'s dtype picks the contraction (``row_contrib``).
    Rows load ``_SUB`` at a time; the sum runs over the rows in order,
    so every kernel that calls this reduces a block identically.
    Entries outside the tile are zeroed through ``w``: their fused row
    id may alias a live (slot, hi) pair, and a zero weight is the one
    thing that is harmless under aliasing.  ``rows_hook(r0, hi, lo)``,
    if given, runs once per ``_SUB``-row group with the split of that
    group's targets (in the tile's frame).
    """
    r_out = d_ring * n_hi

    def group(g, acc):
        r0 = pl.multiple_of(row0 + g * _SUB, _SUB)
        tgt = tgt_ref[pl.ds(r0, _SUB), :]
        w = w_ref[pl.ds(r0, _SUB), :]
        slots = (t0 + d_ref[pl.ds(r0, _SUB), :]) % d_ring
        local = tgt - base
        w = jnp.where(jnp.logical_and(local >= 0, local < n_hi * LANES),
                      w, 0.0)
        hi, lo = split_target(local)
        rid = slots * n_hi + hi
        for r in range(_SUB):
            acc = acc + row_contrib(rid[r:r + 1], lo[r:r + 1],
                                    w[r:r + 1], r_out)
        if rows_hook is not None:
            rows_hook(r0, hi, lo)
        return acc

    return jax.lax.fori_loop(0, n_rows // _SUB, group,
                             jnp.zeros((r_out, LANES), jnp.float32))


def _accum_kernel(meta_ref, tmin_ref, tmax_ref, tgt_ref, w_ref, d_ref,
                  ring_ref, out_ref):
    """One (ring-tile, entry-block) grid step of the two-level scatter.

    meta_ref:    scalar prefetch [t_slot]
    tmin/tmax:   scalar prefetch (n_entry_blocks,) -- first/last ring
                 tile targeted by the block's live (w != 0) entries;
                 all-padding blocks carry an empty window (tmin > tmax)
    tgt/w/d:     (ENTRY_SUBLANES, LANES) lane-packed entry block
    ring/out:    (d_ring, n_hi, LANES) -- the accumulator tile, resident
                 across the inner (entry-block) grid dimension
    """
    i = pl.program_id(0)              # ring tile
    e = pl.program_id(1)              # entry block

    @pl.when(e == 0)
    def _init():
        out_ref[...] = ring_ref[...]

    @pl.when(jnp.logical_and(tmin_ref[e] <= i, i <= tmax_ref[e]))
    def _accum():
        d_ring, n_hi, _ = out_ref.shape
        acc = scatter_rows(tgt_ref, w_ref, d_ref, 0, tgt_ref.shape[0],
                           meta_ref[0], i * (n_hi * LANES), d_ring, n_hi)
        out_ref[...] += acc.reshape(d_ring, n_hi, LANES)


def _block_tile_windows(tgt_e, w_e, tile_n: int):
    """Per-entry-block [first, last] ring-tile windows over live entries.

    Live means ``w != 0``: gathered padding (sink rows, intra-row cap
    padding, lane/block padding) all carry zero weights, and a zero
    weight contributes exactly nothing to the scatter, so excluding it
    from the window is semantically free.  All-padding blocks come back
    with tmin > tmax and are skipped on every tile.
    """
    n_blocks = tgt_e.shape[0] // ENTRY_BLOCK
    tgt_b = tgt_e.reshape(n_blocks, ENTRY_BLOCK)
    live = w_e.reshape(n_blocks, ENTRY_BLOCK) != 0.0
    tmin = jnp.min(jnp.where(live, tgt_b, _FAR), axis=1) // tile_n
    tmax = jnp.max(jnp.where(live, tgt_b, -1), axis=1) // tile_n
    return tmin.astype(jnp.int32), tmax.astype(jnp.int32)


def _scatter_entries(tgt_e, w_e, d_e, ring, t_slot, *,
                     interpret: bool):
    """Two-level blocked scatter of a lane-packed entry stream into the
    tiled ring.

    tgt_e/w_e/d_e: flat (E,) with E a multiple of ENTRY_BLOCK; padding
    entries must carry w == 0.  ``w_e`` stays in its dtype
    (``weight_stream_dtype``).  One pallas_call covers every
    (ring tile, entry block) pair on a 2-D grid.
    """
    d_ring, n_local = ring.shape
    n_pad = _ceil_to(max(n_local, N_ALIGN), N_ALIGN)
    tile_n = min(TILE_N, n_pad)
    n_tiles = -(-n_pad // tile_n)
    n_pad = n_tiles * tile_n
    n_hi = tile_n // LANES
    ring_p = jnp.pad(ring, ((0, 0), (0, n_pad - n_local))).reshape(
        d_ring, n_pad // LANES, LANES)
    tmin, tmax = _block_tile_windows(tgt_e, w_e, tile_n)
    meta = jnp.asarray([t_slot], jnp.int32).reshape(1)
    n_blocks = tgt_e.shape[0] // ENTRY_BLOCK

    def packed(x, dt):
        return x.astype(dt).reshape(-1, LANES)

    entry_spec = pl.BlockSpec((ENTRY_SUBLANES, LANES),
                              lambda i, e, m, lo, hi: (e, 0))
    ring_spec = pl.BlockSpec((d_ring, n_hi, LANES),
                             lambda i, e, m, lo, hi: (0, i, 0))
    gspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_tiles, n_blocks),
        in_specs=[entry_spec, entry_spec, entry_spec, ring_spec],
        out_specs=ring_spec)
    out = pl.pallas_call(
        _accum_kernel,
        grid_spec=gspec,
        out_shape=jax.ShapeDtypeStruct(ring_p.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="synaptic_accum",
    )(meta, tmin, tmax, packed(tgt_e, jnp.int32), w_e.reshape(-1, LANES),
      packed(d_e, jnp.int32), ring_p)
    return out.reshape(d_ring, n_pad)[:, :n_local]


# ---------------------------------------------------------------------------
# Stage 1: compaction
# ---------------------------------------------------------------------------

def compact_events(spikes_src, n_rows: int, active_cap: int):
    """Spiking-row compaction: ascending row indices of the (at most
    ``active_cap``) spiking sources, padded with the sink row ``n_rows``.

    Returns (idx, n_spikes) -- ``idx`` is the event-index list; real
    events occupy a prefix because ``nonzero`` is order-preserving.
    """
    spk = spikes_src[:n_rows]
    (idx,) = jnp.nonzero(spk > 0, size=active_cap, fill_value=n_rows)
    return idx.astype(jnp.int32), jnp.sum(spk > 0)


# ---------------------------------------------------------------------------
# Stage 2+3: gather event rows and flatten to entry streams
# ---------------------------------------------------------------------------

def row_lanes(cap: int) -> int:
    """Entries one gathered event row occupies in the entry stream: its
    ``cap`` entries padded to whole lane rows."""
    return _ceil_to(cap, LANES)


def gather_rows(table, idx, dtype):
    """Gather event rows of one table leaf and flatten them row-major to
    an (A * row_lanes(cap),) stream, each row zero-padded to whole lane
    rows.  Padding entries carry zeros, which every consumer treats as
    no-ops (w == 0, mask == 0).  The padding is what lets XLA flatten
    the gathered rows: an (A, cap) -> (A * cap,) reshape with ``cap`` not
    a lane multiple is a relayout that takes the TPU compiler minutes at
    full column width."""
    rows = table[idx].astype(dtype)
    cap = rows.shape[1]
    return jnp.pad(rows, ((0, 0), (0, row_lanes(cap) - cap))).ravel()


def _gather_entries(tables: dict, idx):
    """Gather event rows into lane-padded (tgt, w, dslot) streams; the
    weights stay in their ``weight_stream_dtype``."""
    w = tables["w"]
    return (gather_rows(tables["tgt"], idx, jnp.int32),
            gather_rows(w, idx, weight_stream_dtype(w.dtype)),
            gather_rows(tables["dslot"], idx, jnp.int32))


def _pad_flat(te, we, de, n: int):
    pad = n - te.shape[0]
    if pad:
        te = jnp.pad(te, (0, pad))
        we = jnp.pad(we, (0, pad))
        de = jnp.pad(de, (0, pad))
    return te, we, de


# ---------------------------------------------------------------------------
# Fused entry points
# ---------------------------------------------------------------------------

def event_delivery(tables: dict, spikes_src, i_ring, t_slot,
                   d_ring: int, active_cap: int, *, interpret: bool):
    """Fused single-tier delivery.  Drop-in for
    ``core.synapses.deliver_events``: returns (ring, n_events, n_dropped).
    """
    return event_delivery_banded([(tables, spikes_src, active_cap)],
                                 i_ring, t_slot, d_ring,
                                 interpret=interpret)


def event_delivery_banded(tiers: Sequence[Tuple[dict, jnp.ndarray, int]],
                          i_ring, t_slot, d_ring: int, *,
                          plan=None, interpret: bool):
    """Fused multi-tier delivery: ONE kernel launch for the local table
    plus every halo fan-out band across every ring tile.

    ``tiers``: sequence of (tables, spikes_src, active_cap); each tier's
    tables may have a different row capacity (the banded-halo layout) --
    entry flattening makes the concatenation capacity-agnostic.
    ``plan``: optional per-tier ``TierPlan`` sequence from
    ``SynapseTableSpec.delivery_plan()``; when given, the tables are
    validated against it (the typed spec contract the engines compile
    against); each tier's slice of the packed entry stream is its
    ``entries_padded`` (rows padded to whole lane rows, ``gather_rows``).
    For compressed tables, pass the
    plan derived from the tables' ``storage`` descriptor.
    Returns (ring, n_events, n_dropped) summed over tiers.
    """
    assert i_ring.shape[0] == d_ring
    if plan is not None and len(plan) != len(tiers):
        raise ValueError(f"delivery plan has {len(plan)} tiers, "
                         f"got {len(tiers)}")
    parts_t: List[jnp.ndarray] = []
    parts_w: List[jnp.ndarray] = []
    parts_d: List[jnp.ndarray] = []
    n_events = jnp.zeros((), jnp.int32)
    n_dropped = jnp.zeros((), jnp.int32)
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
        parts_t.append(te)
        parts_w.append(we)
        parts_d.append(de)
        n_events = n_events + jnp.sum(tables["nnz"][idx]).astype(jnp.int32)
        n_dropped = n_dropped + jnp.maximum(
            n_spk - active_cap, 0).astype(jnp.int32)

    tgt_e = jnp.concatenate(parts_t)
    w_e = jnp.concatenate(parts_w)
    d_e = jnp.concatenate(parts_d)
    tgt_e, w_e, d_e = _pad_flat(tgt_e, w_e, d_e,
                                packed_total(tgt_e.shape[0]))
    ring = _scatter_entries(tgt_e, w_e, d_e, i_ring, t_slot,
                            interpret=interpret)
    return ring, n_events, n_dropped


# ---------------------------------------------------------------------------
# Legacy single-call API (kept for the kernel sweep tests)
# ---------------------------------------------------------------------------

def synaptic_accum_pallas(idx, t_slot, tgt, w, dslot, ring, *,
                          interpret: bool):
    """Deliver event rows ``idx`` (A,) through the tables into ``ring``.

    Equivalent to ``ref.synaptic_accum_ref``.  ``dslot`` int8/int32;
    ``ring`` (D, n_local) f32 -- returned updated.  Unlike
    ``event_delivery`` this takes a pre-compacted index list; callers
    may pass arbitrary, unsorted indices -- block skipping is purely
    data-driven (live-entry tile windows), so it still applies.
    """
    tables = {"tgt": tgt, "w": w, "dslot": dslot}
    te, we, de = _gather_entries(tables, idx.astype(jnp.int32))
    te, we, de = _pad_flat(te, we, de, packed_total(te.shape[0]))
    return _scatter_entries(te, we, de, ring, t_slot, interpret=interpret)
