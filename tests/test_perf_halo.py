"""HLO cost analyzer + spike bit-packing properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic env: see tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.halo import (halo_import_bytes, pack_bits, packed_width,
                             unpack_bits)
from repro.perf.hlo_analysis import analyze_hlo, parse_computations


def test_analyzer_multiplies_loop_bodies():
    """THE reason this analyzer exists: cost_analysis counts a scan body
    once; ours multiplies by the annotated trip count."""
    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y.sum()

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(scanned).lower(x, w).compile()
    costs = analyze_hlo(compiled.as_text())
    one_matmul = 2 * 128 ** 3
    assert costs.dot_flops == pytest.approx(10 * one_matmul, rel=0.01)
    ca = compiled.cost_analysis()
    xla_flops = ca["flops"]
    assert xla_flops == pytest.approx(one_matmul, rel=0.01)  # body once


def test_analyzer_nested_loops():
    def nested(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    costs = analyze_hlo(jax.jit(nested).lower(x, w).compile().as_text())
    assert costs.dot_flops == pytest.approx(12 * 2 * 64 ** 3, rel=0.01)


def test_parse_computations_finds_entry():
    txt = jax.jit(lambda x: x + 1).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    comps = parse_computations(txt)
    assert sum(c["entry"] for c in comps.values()) == 1


@given(st.integers(1, 200), st.integers(0, 2 ** 31 - 1),
       st.integers(1, 4))
@settings(max_examples=40, deadline=None)
def test_pack_bits_roundtrip(n, seed, lead):
    rng = np.random.default_rng(seed)
    x = (rng.random((lead, n)) < 0.3).astype(np.float32)
    packed = pack_bits(jnp.asarray(x))
    assert packed.shape == (lead, packed_width(n))
    back = unpack_bits(packed, n)
    np.testing.assert_array_equal(np.asarray(back), x)


def test_pack_bits_is_32x_smaller():
    x = jnp.zeros((10, 10, 992), jnp.float32)
    assert pack_bits(x).size * 8 * 4 == x.size * 4  # 1 bit vs 32 bits


def test_halo_import_bytes_strip_less_than_block():
    # radius < tile: block mode ships whole tiles, strip ships the rim
    s = halo_import_bytes(8, 8, 3, 100, mode="strip")
    b = halo_import_bytes(8, 8, 3, 100, mode="block")
    assert s < b
    # exact strip volume = dilated area - tile area
    assert s == ((8 + 6) ** 2 - 64) * 100
