"""Four CPU devices: per-rank MoE load counts summed through
``inc_agg.sum_counts`` equal numpy's integer sum on every rank, and the
program sums them with the ring's collective permutes, not an
all-reduce. Run by test_bench_mla_moe.py with four forced host devices.
"""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core import inc_agg  # noqa: E402

assert len(jax.devices()) == 4, jax.devices()
mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(7)
# per rank: 5 layers x 64 experts, counts up to a few hundred million
# (far beyond float32's exact integers, inside int32 for four ranks)
loads = rng.integers(0, 400_000_000, size=(4, 5, 64), dtype=np.int64)
loads[:, 0, :3] = [[1, 2, 3]] * 4
fn = jax.jit(jax.shard_map(
    lambda c: inc_agg.sum_counts(c[0], ("data",))[None],
    mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    check_vma=False))
x = jnp.asarray(loads.astype(np.int32))
out = np.asarray(fn(x))
want = loads.sum(0)
for rank in range(4):
    np.testing.assert_array_equal(out[rank].astype(np.int64), want)
hlo = fn.lower(x).as_text()
assert "collective_permute" in hlo or "collective-permute" in hlo, hlo[:500]
assert "all_reduce" not in hlo and "all-reduce" not in hlo
print("SUM_COUNTS_EXACT")
