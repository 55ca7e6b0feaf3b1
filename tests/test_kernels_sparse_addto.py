"""Sparse saturating scatter-add into the INC register file."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.backend import accelerator_present, pallas_mode
from repro.kernels.constants import INT32_MAX, INT32_MIN, SAT_MAX, SAT_MIN
from repro.kernels.ops import sparse_addto_host
from repro.kernels.sparse_addto import sparse_addto_pallas


@pytest.mark.parametrize("n,k", [(128, 32), (1024, 128), (4096, 64)])
def test_matches_ref(n, k):
    rng = np.random.RandomState(3)
    regs = jnp.asarray(rng.randint(-1000, 1000, n, dtype=np.int64)
                       .astype(np.int32))
    idx = jnp.asarray(rng.randint(0, n, k).astype(np.int32))
    val = jnp.asarray(rng.randint(-100, 100, k).astype(np.int32))
    # default lane: backend-resolved (interpret on CPU, compiled on
    # TPU/GPU) — a green run names the mode it actually exercised
    got = sparse_addto_pallas(regs, idx, val)
    want = ref.sparse_addto(regs, idx, val)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert pallas_mode() == (
        "compiled" if accelerator_present() else "interpret")


def test_duplicate_keys_accumulate_in_order():
    regs = jnp.zeros(8, jnp.int32)
    idx = jnp.asarray([3, 3, 3], jnp.int32)
    val = jnp.asarray([SAT_MAX - 1, 5, -5], jnp.int32)
    out = ref.sparse_addto(regs, idx, val)
    # (SAT_MAX-1) + 5 saturates -> sentinel sticks through the -5
    assert int(out[3]) == INT32_MAX
    out2 = sparse_addto_pallas(regs, idx, val, interpret=True)
    assert int(out2[3]) == INT32_MAX


@pytest.mark.parametrize("n,k", [(64, 16), (1024, 256)])
def test_host_kernel_matches_ref(n, k):
    """The numpy host-path kernel (ops.sparse_addto_host) is the data plane
    off-TPU; it must be result-identical to the sequential oracle."""
    rng = np.random.RandomState(7)
    regs0 = rng.randint(-1000, 1000, n).astype(np.int32)
    idx = rng.randint(0, n, k).astype(np.int32)
    val = rng.randint(-100, 100, k).astype(np.int32)
    want = np.asarray(ref.sparse_addto(jnp.asarray(regs0), jnp.asarray(idx),
                                       jnp.asarray(val)))
    got = sparse_addto_host(regs0.copy(), idx, val)
    np.testing.assert_array_equal(got, want)


def test_host_kernel_saturation_order_and_sticky_sentinel():
    # duplicate-key saturation: sequential order, sentinel sticks
    regs0 = np.zeros(8, np.int32)
    idx = np.array([3, 3, 3, 5], np.int32)
    val = np.array([SAT_MAX - 1, 5, -5, 7], np.int32)
    out = sparse_addto_host(regs0.copy(), idx, val)
    assert int(out[3]) == INT32_MAX       # saturated, then sticky through -5
    assert int(out[5]) == 7               # safe slot untouched by fallback
    # starting from a sentinel register stays a sentinel
    regs1 = np.full(4, INT32_MAX, np.int32)
    out1 = sparse_addto_host(regs1.copy(), np.array([2], np.int32),
                             np.array([-10], np.int32))
    assert int(out1[2]) == INT32_MAX


def test_duplicate_addresses_pallas_equals_host_fast_path():
    """Satellite-2 regression pin: duplicate physical addresses in one
    batch apply in stream order on EVERY implementation — the Pallas
    serial scatter, the numpy host fast path, and the sequential oracle
    agree, including saturation order at the sentinel boundaries (the
    differential sweep found zero divergence; keep it that way)."""
    cases = [
        # saturate up then pull back: sentinel must stick
        (np.zeros(8, np.int32), [3, 3, 3], [SAT_MAX - 1, 5, -5]),
        # saturate down then push up
        (np.zeros(8, np.int32), [1, 1, 1], [SAT_MIN + 1, -5, 5]),
        # land exactly on the rails (no sentinel), then step over
        (np.zeros(4, np.int32), [0, 0, 2, 2], [SAT_MAX, 0, SAT_MIN, 0]),
        (np.zeros(4, np.int32), [0, 0], [SAT_MAX, 1]),
        # start from a sentinel register: everything is a no-op
        (np.full(4, INT32_MAX, np.int32), [2, 2], [-10, -10]),
    ]
    rng = np.random.RandomState(13)
    for _ in range(6):      # randomized dup-heavy streams near the rails
        regs = rng.choice([0, 5, SAT_MAX - 3, SAT_MIN + 3],
                          8).astype(np.int32)
        idx = rng.randint(0, 8, 24)
        val = rng.choice([-3, -1, 0, 1, 3, SAT_MAX // 2, SAT_MIN // 2], 24)
        cases.append((regs, idx, val))
    for regs, idx, val in cases:
        idx = np.asarray(idx, np.int32)
        val = np.asarray(val, np.int32)
        want = np.asarray(ref.sparse_addto(jnp.asarray(regs),
                                           jnp.asarray(idx),
                                           jnp.asarray(val)))
        got_host = sparse_addto_host(regs.copy(), idx, val)
        got_pallas = np.asarray(sparse_addto_pallas(
            jnp.asarray(regs), jnp.asarray(idx), jnp.asarray(val),
            interpret=True))
        np.testing.assert_array_equal(got_host, want)
        np.testing.assert_array_equal(got_pallas, want)


def test_int32_min_sum_edge_consistent_everywhere():
    """The one known quirk of the wrapped-add overflow reconstruction: a
    running sum landing EXACTLY on -2**31 (one below the SAT_MIN rail,
    but still representable) is returned raw and unflagged — by the
    sequential oracle, the host fast path, and the Pallas kernel alike.
    Pinned so a 'fix' to any one implementation can't silently diverge
    from the other two."""
    regs = np.array([SAT_MIN], np.int32)            # -(2**31 - 2)
    idx = np.array([0], np.int32)
    val = np.array([-2], np.int32)
    want = np.asarray(ref.sparse_addto(jnp.asarray(regs), jnp.asarray(idx),
                                       jnp.asarray(val)))
    got_host = sparse_addto_host(regs.copy(), idx, val)
    got_pallas = np.asarray(sparse_addto_pallas(
        jnp.asarray(regs), jnp.asarray(idx), jnp.asarray(val),
        interpret=True))
    assert (int(want[0]) == int(got_host[0]) == int(got_pallas[0])
            == -2 ** 31)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 15), st.integers(-50, 50)),
                min_size=1, max_size=32))
def test_equals_dict_semantics(pairs):
    regs = jnp.zeros(16, jnp.int32)
    idx = jnp.asarray([p[0] for p in pairs], jnp.int32)
    val = jnp.asarray([p[1] for p in pairs], jnp.int32)
    out = np.asarray(ref.sparse_addto(regs, idx, val))
    d = {}
    for i, v in pairs:
        d[i] = d.get(i, 0) + v       # small values: no saturation
    for i, v in d.items():
        assert out[i] == v
