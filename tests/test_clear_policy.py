"""Map.clear policies: copy / shadow / lazy (paper §5.2.2, Table 6)."""
import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis import example, given, settings
from hypothesis import strategies as st
from repro.core.clear_policy import make_clear_policy
from repro.core.quantize import quantize
from repro.kernels import ops
from repro.kernels.constants import INT32_MAX, INT32_MIN, SAT_MAX


@pytest.mark.parametrize("policy", ["copy", "shadow", "lazy"])
def test_rounds_produce_identical_values(policy):
    rng = np.random.RandomState(0)
    pol = make_clear_policy(policy, 64)
    for _ in range(5):
        total = np.zeros(64, np.int64)
        for _ in range(3):
            q = rng.randint(-1000, 1000, 64).astype(np.int32)
            total += q
            pol.addto(jnp.asarray(q))
        out = np.asarray(pol.read_and_clear())
        np.testing.assert_array_equal(out, total.astype(np.int32))


def test_memory_multipliers_match_table6():
    assert make_clear_policy("copy", 4).stats.memory_multiplier == 1
    assert make_clear_policy("shadow", 4).stats.memory_multiplier == 2
    assert make_clear_policy("lazy", 4).stats.memory_multiplier == 1


def test_lazy_overflow_triggers_fallback_reset():
    pol = make_clear_policy("lazy", 4)
    big = quantize(jnp.full((4,), 3.0e9), 0)   # saturates to sentinel
    pol.addto(big)
    out = pol.read_and_clear()
    assert pol.stats.fallback_resets == 1
    assert np.all(np.asarray(pol.acc) == 0)    # switch memory reset


# ---- batched reply-path fold (one pass per drained batch) -------------------

@pytest.mark.parametrize("policy", ["copy", "shadow", "lazy"])
def test_addto_batch_equals_sequential_addto(policy):
    """addto_batch(qs) must equal the per-call addto loop — including when
    intermediate sums saturate to sticky sentinels mid-batch."""
    rng = np.random.RandomState(7)
    batches = [
        [rng.randint(-1000, 1000, 16).astype(np.int32) for _ in range(5)],
        # saturating: two half-range updates overflow on the second one
        [np.full(16, SAT_MAX // 2 + 1, np.int32)] * 3,
        # sentinel inputs stay sticky through the fold
        [np.array([INT32_MAX, INT32_MIN, 5, -5] * 4, np.int32),
         rng.randint(-10, 10, 16).astype(np.int32)],
    ]
    for qs in batches:
        seq = make_clear_policy(policy, 16)
        bat = make_clear_policy(policy, 16)
        for q in qs:
            seq.addto(jnp.asarray(q))
        bat.addto_batch([jnp.asarray(q) for q in qs])
        np.testing.assert_array_equal(np.asarray(seq.read_and_clear()),
                                      np.asarray(bat.read_and_clear()))


# deadline=None: each new batch depth compiles sat_add_batch anew (~0.3 s
# on a CPU), so hypothesis's 200 ms deadline fails on the compile
@settings(max_examples=25, deadline=None)
@given(st.lists(st.lists(st.integers(INT32_MIN, INT32_MAX),
                         min_size=4, max_size=4),
                min_size=1, max_size=6))
@example(rows=[[-2_147_451_483, 7571263, -2_147_483_415, -2_147_455_276],
               [-2_147_483_402, -2_147_452_751, -1_039_105_100,
                1_979_117_169]])
@example(rows=[[-2_147_483_489, -2_147_483_623, -2_147_458_077,
                -2_147_434_584],
               [-2_147_481_671, 632753280, -2_147_442_731, -2_147_461_665],
               [-2_147_449_202, -2_147_429_171, -2_147_444_951,
                -2_147_460_498],
               [456587775, -2_147_422_409, 2_147_483_646, -2_147_424_200]])
def test_sat_add_batch_property(rows):
    """ops.sat_add_batch == the sequential sat_add fold, elementwise exact,
    over arbitrary values including the reserved sentinels."""
    acc = jnp.zeros(4, jnp.int32)
    qs = [jnp.asarray(np.array(r, np.int64).astype(np.int64)
                      .clip(INT32_MIN, INT32_MAX).astype(np.int32))
          for r in rows]
    want = acc
    for q in qs:
        want = ops.sat_add(want, q)
    got = ops.sat_add_batch(acc, jnp.stack(qs))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_lazy_monotone_between_clears():
    pol = make_clear_policy("lazy", 2)
    pol.addto(jnp.asarray([1, 2], jnp.int32))
    a = np.asarray(pol.read_and_clear())
    pol.addto(jnp.asarray([3, 4], jnp.int32))
    b = np.asarray(pol.read_and_clear())
    np.testing.assert_array_equal(a, [1, 2])
    np.testing.assert_array_equal(b, [3, 4])   # delta, not cumulative
    # but the underlying accumulator never cleared
    assert np.all(np.asarray(pol.acc) == np.asarray([4, 6]))
