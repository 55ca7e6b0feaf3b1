"""Pallas quantize/dequantize kernels vs the pure-jnp oracle.

Sweeps shapes/dtypes in the backend-resolved mode (interpret on CPU,
compiled on TPU/GPU — each test asserts which lane it exercised) and
property-tests the fixed-point round-trip contract of paper §5.2.1.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.backend import accelerator_present, pallas_mode
from repro.kernels.constants import INT32_MAX, INT32_MIN, SAT_MAX, SAT_MIN
from repro.kernels.dequantize import dequantize_pallas
from repro.kernels.quantize import quantize_pallas


SHAPES = [(256, 128), (512, 128), (1024, 128)]
BLOCKS = [256, 512]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("block_rows", BLOCKS)
def test_quantize_matches_ref(shape, block_rows):
    if shape[0] % block_rows:
        pytest.skip("rows % block_rows != 0")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 1e3)
    scale = jnp.float32(10.0 ** 4)
    # default lane (backend-resolved); assert the mode this run exercised
    got = quantize_pallas(x, scale, block_rows=block_rows)
    want = ref.quantize(x, scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert pallas_mode() == (
        "compiled" if accelerator_present() else "interpret")


@pytest.mark.parametrize("shape", SHAPES)
def test_dequantize_matches_ref(shape):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randint(INT32_MIN, INT32_MAX, size=shape,
                                dtype=np.int64).astype(np.int32))
    # plant sentinels
    q = q.at[0, 0].set(INT32_MAX).at[-1, -1].set(INT32_MIN)
    scale = jnp.float32(100.0)
    x, m = dequantize_pallas(q, scale)
    assert pallas_mode() == (
        "compiled" if accelerator_present() else "interpret")
    xr, mr = ref.dequantize(q, scale)
    np.testing.assert_allclose(np.asarray(x), np.asarray(xr))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    assert bool(m[0, 0]) and bool(m[-1, -1])


@settings(max_examples=200, deadline=None)
@given(st.floats(-1e5, 1e5, allow_nan=False), st.integers(0, 8))
def test_roundtrip_error_bound(v, p):
    """|dequant(quant(v)) - v| <= 0.5/scale for in-range values."""
    scale = 10.0 ** p
    if abs(v) * scale > SAT_MAX - 1:
        return
    q = ref.quantize(jnp.float32(v), jnp.float32(scale))
    x, m = ref.dequantize(q, jnp.float32(scale))
    assert not bool(m)
    assert abs(float(x) - v) <= 0.5 / scale + abs(v) * 1e-6


@settings(max_examples=100, deadline=None)
@given(st.floats(-1e30, 1e30, allow_nan=False), st.integers(0, 8))
def test_out_of_range_becomes_sentinel(v, p):
    scale = 10.0 ** p
    if abs(v) * scale <= SAT_MAX:
        return
    q = ref.quantize(jnp.float32(v), jnp.float32(scale))
    assert int(q) in (INT32_MAX, INT32_MIN)
    _, m = ref.dequantize(q, jnp.float32(scale))
    assert bool(m)


def test_sentinel_constants_reserved():
    assert SAT_MAX == INT32_MAX - 1 and SAT_MIN == INT32_MIN + 1
    assert SAT_MIN == -SAT_MAX          # negation-closed range
