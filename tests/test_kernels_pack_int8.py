"""Block-scaled int8 pack/unpack (netrpc-opt wire format)."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.backend import pallas_mode
from repro.kernels.pack_int8 import pack_int8_pallas, unpack_int8_pallas


@pytest.mark.parametrize("rows", [256, 512])
def test_matches_ref(rows):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(rows, 128).astype(np.float32))
    # this kernel pair pins interpret=True explicitly; say so on record
    assert pallas_mode(True) == "interpret"
    q, s = pack_int8_pallas(x, interpret=True)
    qr, sr = ref.pack_int8_block(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = unpack_int8_pallas(q, s, interpret=True)
    yr = ref.unpack_int8_block(qr, sr)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-6)


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_roundtrip_error_bound(seed):
    rng = np.random.RandomState(seed % (2**31))
    x = rng.randn(4, 128).astype(np.float32) * rng.uniform(1e-6, 1e6)
    q, s = ref.pack_int8_block(jnp.asarray(x))
    y = np.asarray(ref.unpack_int8_block(q, s))
    amax = np.abs(x).max(axis=1, keepdims=True)
    # error per element <= scale/2 = amax/254 (tiny slack: fp32 rounding at
    # quantization midpoints can exceed the exact bound by ~1 ulp)
    assert np.all(np.abs(y - x) <= amax / 254.0 * (1 + 1e-5) + 1e-12)


def test_zero_rows_exact():
    x = jnp.zeros((4, 128), jnp.float32)
    q, s = ref.pack_int8_block(x)
    y = ref.unpack_int8_block(q, s)
    np.testing.assert_array_equal(np.asarray(y), np.zeros((4, 128)))
