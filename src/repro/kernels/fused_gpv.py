"""Pallas TPU kernels: the fused device-resident GPV data plane.

The device-backed switch memory (core/inc_map.py:DeviceSegment) keeps a
register segment as an int32 jax array and lowers the two data-plane
verbs through ONE kernel launch each:

  - ``fused_addto_pallas``: transmit side — quantize (scale, round,
    saturate to the overflow sentinels) a float32 update stream and
    saturating-add it into a contiguous slot range of the segment, fused.
  - ``fused_scatter_pallas``: the same fuse for a sparse / duplicate-keyed
    stream — each update is quantized and applied serially in stream
    order (the switch's one-access-per-stage semantics; saturation order
    matches the sequential oracle exactly, including duplicate physical
    addresses within one batch). The kernel is kernels/sparse_addto.py's.
  - ``fused_read_pallas``: receive side — dequantize (reciprocal
    multiply) a contiguous slot range plus the overflow-sentinel mask,
    fused; the reply value block never exists as int32 in host memory.
  - ``fused_read_clear_pallas``: the same read, and in the same program
    the reply path's copy clear of the run (sentinels kept), so the
    registers never travel to the host to be cleared.
  - ``fused_addto_read_pallas``: a dense addTo and the read of the same
    run that follows it (with or without the clear), the two kernels
    above in one program: a SyncAgtr call's register work in one launch.
  - ``fused_fold_pallas``: quantize N rounds and fold them with the
    saturating add (client-side local aggregation).

Quantization matches the host oracle element-exactly for float32 streams
whose scaled values fit int32: both compute round-half-to-even on the same
float32 product (np.rint / jnp.round). Values outside the range saturate
to the INT32_MAX/INT32_MIN sentinels here (the switch's overflow
convention) where the host int64 path keeps the exact product — the
device lane therefore only carries streams inside the fixed-point range
(core/inc_map.py routes the rest to the host path).

Layout: the elementwise kernels see ``(rows, 128)`` int32/fp32 tiles,
``rows`` a multiple of 8, gridded over row blocks of at most
``_MAX_BLOCK_ROWS``. The contiguous slot range ``[start, start+n)`` is cut
out of (and written back into) the flat segment by XLA's dynamic slice
around the kernel, so no kernel indexes at an unaligned dynamic offset,
and one compiled kernel serves every start. Scalars (scale, reciprocal)
ride SMEM. The overflow mask leaves the kernel as int32 and becomes bool
outside it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.constants import INT32_MAX, INT32_MIN, LANES
from repro.kernels.inc_agg import _sat_add_block
from repro.kernels.quantize import quantize_block
from repro.kernels.sparse_addto import SUBLANES, scatter_pallas

_MAX_BLOCK_ROWS = 512              # 256 KiB per int32/fp32 block
_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def _row_blocks(n: int) -> tuple[int, int]:
    """(rows, block_rows) for an n-element stream: rows a multiple of
    block_rows, block_rows a multiple of 8."""
    rows = -(-max(n, 1) // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    block = min(rows, _MAX_BLOCK_ROWS)
    return -(-rows // block) * block, block


def _tiles(x: jax.Array, rows: int) -> jax.Array:
    """Flat (..., n) -> zero-padded (..., rows, 128)."""
    pad = rows * LANES - x.shape[-1]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], rows, LANES)


def _flat(x: jax.Array, n: int) -> jax.Array:
    return x.reshape(-1)[:n]


def _scalar(x) -> jax.Array:
    return jnp.asarray(x, jnp.float32).reshape(1)


def _fused_addto_kernel(scale_ref, val_ref, cur_ref, out_ref):
    q = quantize_block(val_ref[...], scale_ref[0])
    out_ref[...] = _sat_add_block(cur_ref[...], q)


def fused_addto_pallas(regs: jax.Array, start: jax.Array, fvals: jax.Array,
                       scale: jax.Array, *,
                       interpret: bool | None = None) -> jax.Array:
    """regs: int32 (n_slots,); fvals: fp32 (n,) -> updated regs with
    ``quantize(fvals)`` saturating-added over slots [start, start+n).
    Requires ``start + n <= n_slots`` (XLA clamps a slice that would run
    past the end).

    The dense GPV fast path: a tensor's flat indices map to a contiguous
    slot range (identity grant order), so the scatter is a slice and the
    whole transmit side is one fused elementwise pass. ``interpret=None``
    resolves per backend (kernels/backend.py).
    """
    n = fvals.shape[0]
    regs = regs.astype(jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    cur = jax.lax.dynamic_slice(regs, (start,), (n,))
    rows, block = _row_blocks(n)
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _fused_addto_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        grid=(rows // block,),
        in_specs=[_SCALAR, spec, spec],
        out_specs=spec,
        interpret=resolve_interpret(interpret),
    )(_scalar(scale), _tiles(fvals.astype(jnp.float32), rows),
      _tiles(cur, rows))
    return jax.lax.dynamic_update_slice(regs, _flat(out, n), (start,))


def fused_scatter_pallas(regs: jax.Array, idx: jax.Array, fvals: jax.Array,
                         scale: jax.Array, *,
                         interpret: bool | None = None) -> jax.Array:
    """regs: int32 (n_slots,); idx: int32 (k,); fvals: fp32 (k,) ->
    updated regs. Each update is quantized and saturating-added serially
    in stream order, so duplicate addresses accumulate exactly like the
    sequential oracle (sticky sentinels and all). Padding with (idx=0,
    fval=0.0) is a no-op update."""
    return scatter_pallas(regs, idx, fvals, scale, interpret=interpret)


def _fused_fold_kernel(scale_ref, x_ref, out_ref):
    q = quantize_block(x_ref[...], scale_ref[0])

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = q

    @pl.when(pl.program_id(1) > 0)
    def _():
        out_ref[...] = _sat_add_block(out_ref[...], q)


def fused_fold_pallas(fstack: jax.Array, scale: jax.Array, *,
                      interpret: bool | None = None) -> jax.Array:
    """fstack: fp32 (rounds, n) -> int32 (n,): quantize every round and
    fold them with the switch's saturating add in ONE kernel launch — the
    device lane of client-side local aggregation (``local_accum=N``).

    Each round quantizes exactly like ``fused_addto_pallas`` would have,
    and the rounds accumulate through ``_sat_add_block`` in round order
    (the innermost, sequential grid axis), so the folded update matches N
    separate switch addTo hops wherever no intermediate sum saturates (the
    same fixed-point-range contract the rest of the device lane carries)."""
    r, n = fstack.shape
    rows, block = _row_blocks(n)
    out = pl.pallas_call(
        _fused_fold_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        grid=(rows // block, r),
        in_specs=[
            _SCALAR,
            pl.BlockSpec((pl.Squeezed(), block, LANES),
                         lambda i, k: (k, i, 0)),
        ],
        out_specs=pl.BlockSpec((block, LANES), lambda i, k: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(_scalar(scale), _tiles(fstack.astype(jnp.float32), rows))
    return _flat(out, n)


def _fused_read_kernel(inv_ref, q_ref, val_ref, mask_ref):
    q = q_ref[...]
    val_ref[...] = q.astype(jnp.float32) * inv_ref[0]
    mask_ref[...] = ((q == INT32_MAX) | (q == INT32_MIN)).astype(jnp.int32)


def fused_read_pallas(regs: jax.Array, start: jax.Array, n: int,
                      scale: jax.Array, *,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """regs: int32 (n_slots,) -> (fp32 values (n,), bool overflow mask
    (n,)) for slots [start, start+n): the Map.get gather and the
    dequantize fused into one kernel, so a device-backed Get reply never
    materializes int32 registers host-side. The reciprocal is computed
    like kernels/dequantize.py (1 / float32(scale)), keeping device and
    host-fallback replies bit-identical. Requires ``start + n <=
    n_slots``."""
    inv = jnp.float32(1.0) / jnp.asarray(scale, jnp.float32)
    cur = jax.lax.dynamic_slice(regs.astype(jnp.int32),
                                (jnp.asarray(start, jnp.int32),), (n,))
    rows, block = _row_blocks(n)
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    vals, mask = pl.pallas_call(
        _fused_read_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ),
        grid=(rows // block,),
        in_specs=[_SCALAR, spec],
        out_specs=(spec, spec),
        interpret=resolve_interpret(interpret),
    )(_scalar(inv), _tiles(cur, rows))
    return _flat(vals, n), _flat(mask, n) != 0


def fused_read_clear_pallas(regs: jax.Array, start: jax.Array, n: int,
                            live: jax.Array, scale: jax.Array, *,
                            interpret: bool | None = None
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``fused_read_pallas`` over slots [start, start+n), and the segment
    with the first ``live`` slots of that run cleared: each register to
    zero, but a sentinel kept. That is what adding each register's
    negation gives under the sticky saturating add, without the add.
    ``live`` is a traced count, so a read bucketed past its run clears
    only the run and one program serves every run length of a bucket.
    Returns (values, overflow mask, new regs)."""
    vals, mask = fused_read_pallas(regs, start, n, scale,
                                   interpret=interpret)
    start = jnp.asarray(start, jnp.int32)
    cur = jax.lax.dynamic_slice(regs, (start,), (n,))
    keep = mask | (jnp.arange(n, dtype=jnp.int32) >= live)
    new = jax.lax.dynamic_update_slice(
        regs, jnp.where(keep, cur, jnp.zeros_like(cur)), (start,))
    return vals, mask, new


def fused_addto_read_pallas(regs: jax.Array, start: jax.Array,
                            fvals: jax.Array, scale: jax.Array,
                            bucket: int, clear: bool, *,
                            interpret: bool | None = None
                            ) -> tuple[jax.Array, jax.Array]:
    """``fused_addto_pallas`` of ``fvals`` over slots [start, start+n),
    then ``fused_read_pallas`` of that run (``fused_read_clear_pallas``
    with ``clear``) on the result, in one program. Both kernels run over
    ``bucket`` >= n slots, as two launches of the same run would: the
    stream is zero-padded to it (quantize(0.0) == 0 is a sat-add no-op)
    and the clear covers the run alone. Returns (fp32 values (n,), new
    regs)."""
    n = fvals.shape[0]
    fvals = jnp.pad(fvals.astype(jnp.float32), (0, bucket - n))
    regs = fused_addto_pallas(regs, start, fvals, scale,
                              interpret=interpret)
    if clear:
        vals, _, regs = fused_read_clear_pallas(regs, start, bucket, n,
                                                scale, interpret=interpret)
    else:
        vals, _ = fused_read_pallas(regs, start, bucket, scale,
                                    interpret=interpret)
    return vals[:n], regs
