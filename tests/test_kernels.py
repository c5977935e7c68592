"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic env: see tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.neuron import LIFParams
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.synaptic_accum import (LANES, row_contrib,
                                          synaptic_accum_pallas)


# ---------------------------------------------------------------------------
# lif_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 128, 1024, 4097, 70000])
def test_lif_kernel_sweep(n, rng):
    p = LIFParams()
    st = {"v": jnp.asarray(rng.uniform(-5, 25, n), jnp.float32),
          "c": jnp.asarray(rng.uniform(0, 3, n), jnp.float32),
          "refrac": jnp.asarray(rng.integers(0, 3, n), jnp.int32)}
    i = jnp.asarray(rng.uniform(-2, 6, n), jnp.float32)
    a = jnp.asarray(rng.uniform(0, 1, n) > 0.1)
    s1, k1 = ops.lif_step(st, i, p, a)
    s2, k2 = ops.lif_step_ref(st, i, p, a)
    for kk in s1:
        np.testing.assert_allclose(s1[kk], s2[kk], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(k1, k2)


@given(st.integers(1, 300), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_lif_kernel_property(n, seed):
    rng = np.random.default_rng(seed)
    p = LIFParams()
    st = {"v": jnp.asarray(rng.uniform(-10, 30, n), jnp.float32),
          "c": jnp.asarray(rng.uniform(0, 5, n), jnp.float32),
          "refrac": jnp.asarray(rng.integers(0, 4, n), jnp.int32)}
    i = jnp.asarray(rng.uniform(-5, 10, n), jnp.float32)
    new, spk = ops.lif_step(st, i, p)
    # invariants: spiking neurons reset + enter refractory
    spk = np.asarray(spk).astype(bool)
    assert (np.asarray(new["v"])[spk] == p.v_reset_mv).all()
    assert (np.asarray(new["refrac"])[spk] == p.refrac_steps).all()
    # non-spiking: refractory counter decremented toward 0
    old_r = np.asarray(st["refrac"])
    assert (np.asarray(new["refrac"])[~spk]
            == np.maximum(old_r[~spk] - 1, 0)).all()


# ---------------------------------------------------------------------------
# synaptic_accum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,cap,d_ring,n_local,n_events", [
    (5, 3, 2, 17, 2),
    (33, 17, 8, 300, 12),
    (64, 8, 4, 100, 64),
])
def test_synaptic_accum_sweep(rows, cap, d_ring, n_local, n_events, rng):
    tgt = jnp.asarray(rng.integers(0, n_local, (rows + 1, cap)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(rows + 1, cap)), jnp.float32)
    w = w.at[-1].set(0)
    ds = jnp.asarray(rng.integers(0, d_ring, (rows + 1, cap)), jnp.int8)
    ring = jnp.asarray(rng.normal(size=(d_ring, n_local)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, rows + 1, n_events), jnp.int32)
    got = synaptic_accum_pallas(idx, 3, tgt, w, ds, ring, interpret=True)
    want = ref.synaptic_accum_ref(idx, 3, tgt, w, ds, ring)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_synaptic_accum_sink_row_is_noop(rng):
    rows, cap, d_ring, n_local = 8, 4, 4, 20
    tgt = jnp.zeros((rows + 1, cap), jnp.int32)
    w = jnp.zeros((rows + 1, cap), jnp.float32)
    ds = jnp.zeros((rows + 1, cap), jnp.int8)
    ring = jnp.asarray(rng.normal(size=(d_ring, n_local)), jnp.float32)
    idx = jnp.full((6,), rows, jnp.int32)        # all padding events
    out = synaptic_accum_pallas(idx, 0, tgt, w, ds, ring, interpret=True)
    np.testing.assert_array_equal(out, ring)


def _dot_precisions(fn, *args):
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [(e.params["precision"], [v.aval.dtype for v in e.invars])
            for e in eqns if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
def test_row_contrib_pass_count_follows_weight_dtype(w_dtype):
    """float32 weights (plastic tables, spike_compact's indices) keep
    ``Precision.HIGHEST`` and their exact float32 sum; bfloat16 weights
    take one default-precision pass on bfloat16 factors."""
    # every entry on ring row 3, lane 5: a weight 1 + 2**-12 is not a
    # bfloat16, and 128 of them sum exactly to 128 + 2**-5 in float32
    rid = jnp.full((1, LANES), 3, jnp.int32)
    lo = jnp.full((1, LANES), 5, jnp.int32)
    val = 1.0 + 2.0 ** -12 if w_dtype == "float32" else 1.0 + 2.0 ** -7
    w = jnp.full((1, LANES), val, w_dtype)
    out = np.asarray(jax.jit(row_contrib, static_argnums=3)(rid, lo, w, 8))
    assert out.dtype == np.float32
    want = np.zeros((8, LANES), np.float32)
    want[3, 5] = np.float32(LANES * val)
    np.testing.assert_array_equal(out, want)
    (prec, dts), = _dot_precisions(lambda a, b, c: row_contrib(a, b, c, 8),
                                   rid, lo, w)
    want_prec = (jax.lax.Precision.HIGHEST if w_dtype == "float32"
                 else jax.lax.Precision.DEFAULT)
    assert prec == (want_prec, want_prec)
    assert dts == [jnp.dtype(w_dtype)] * 2


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    (4, 2, 64, 64, 32, True, None, 0),
    (8, 2, 100, 100, 64, True, 48, 0),
    (2, 2, 37, 129, 16, False, None, 0),
    (2, 1, 1, 77, 32, True, None, 76),
    (3, 3, 48, 16, 8, True, None, 0),
]


@pytest.mark.parametrize("bh,bhkv,sq,sk,d,causal,win,qoff", CASES)
def test_flash_attention_sweep(bh, bhkv, sq, sk, d, causal, win, qoff, rng):
    q = jnp.asarray(rng.normal(size=(bh, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(bhkv, sk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(bhkv, sk, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=win,
                          q_offset=qoff, block_q=32, block_k=32,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=win,
                             q_offset=qoff)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype, rng):
    q = jnp.asarray(rng.normal(size=(2, 64, 16)), dtype)
    k = jnp.asarray(rng.normal(size=(2, 64, 16)), dtype)
    v = jnp.asarray(rng.normal(size=(2, 64, 16)), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    assert got.dtype == dtype
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


def test_flash_attention_rows_fully_masked(rng):
    """Window smaller than block: early rows w/ no visible keys -> 0."""
    q = jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)
    # q_offset far beyond keys: every row masked by causality+window
    out = flash_attention(q, k, v, causal=False, window=2, q_offset=100,
                          block_q=8, block_k=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)
