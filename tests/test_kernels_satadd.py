"""Saturating Map.addTo kernel (the per-hop switch accumulate).

Key reduction property (backs the overflow-fallback correctness of §5.2.1):
folding sat_add over any sequence yields either the exact integer sum (when
every running prefix stays in range) or a sentinel — never a silently wrong
value.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.backend import accelerator_present, pallas_mode
from repro.kernels.constants import INT32_MAX, INT32_MIN, SAT_MAX, SAT_MIN
from repro.kernels.inc_agg import sat_add_pallas


@pytest.mark.parametrize("shape", [(256, 128), (512, 128)])
def test_pallas_matches_ref(shape):
    rng = np.random.RandomState(2)
    a = jnp.asarray(rng.randint(-2**31, 2**31 - 1, size=shape,
                                dtype=np.int64).astype(np.int32))
    b = jnp.asarray(rng.randint(-2**31, 2**31 - 1, size=shape,
                                dtype=np.int64).astype(np.int32))
    # default lane: backend-resolved, and the test records which mode a
    # green run actually exercised (interpret on CPU, compiled on TPU/GPU)
    got = sat_add_pallas(a, b)
    want = ref.sat_add(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert pallas_mode() == (
        "compiled" if accelerator_present() else "interpret")


vals = st.integers(SAT_MIN, SAT_MAX)


@settings(max_examples=300, deadline=None)
@given(vals, vals)
def test_commutative(a, b):
    x = ref.sat_add(jnp.int32(a), jnp.int32(b))
    y = ref.sat_add(jnp.int32(b), jnp.int32(a))
    assert int(x) == int(y)


@settings(max_examples=300, deadline=None)
@given(vals, vals)
def test_exact_or_saturated_pair(a, b):
    s = int(ref.sat_add(jnp.int32(a), jnp.int32(b)))
    true = a + b
    if SAT_MIN <= true <= SAT_MAX:
        assert s == true
    else:
        assert s in (INT32_MAX, INT32_MIN)


@settings(max_examples=200, deadline=None)
@given(st.lists(vals, min_size=1, max_size=8))
def test_reduction_exact_or_sentinel(xs):
    acc = jnp.int32(0)
    ok = True                      # every prefix in range so far
    run = 0
    for v in xs:
        run += v
        ok = ok and SAT_MIN <= run <= SAT_MAX
        acc = ref.sat_add(acc, jnp.int32(v))
    if ok:
        assert int(acc) == sum(xs)
    else:
        assert int(acc) in (INT32_MAX, INT32_MIN)   # sticky sentinel


@settings(max_examples=200, deadline=None)
@given(vals, st.sampled_from([INT32_MAX, INT32_MIN]))
def test_sentinel_sticky(a, sent):
    assert int(ref.sat_add(jnp.int32(sent), jnp.int32(a))) == sent
    assert int(ref.sat_add(jnp.int32(a), jnp.int32(sent))) == sent


def test_never_produces_reserved_by_accident():
    # SAT_MAX + 0 etc. must not turn into a sentinel
    assert int(ref.sat_add(jnp.int32(SAT_MAX), jnp.int32(0))) == SAT_MAX
    assert int(ref.sat_add(jnp.int32(SAT_MIN), jnp.int32(0))) == SAT_MIN
    # ... but a genuine overflow must
    assert int(ref.sat_add(jnp.int32(SAT_MAX), jnp.int32(1))) == INT32_MAX
    assert int(ref.sat_add(jnp.int32(SAT_MIN), jnp.int32(-1))) == INT32_MIN
