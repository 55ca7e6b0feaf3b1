"""Dispatching wrappers around the INC kernel family.

Which lane runs where:

  - register segments: residency follows the declaration, never the
    backend. A segment declared ``device=True`` is a jnp int32 array and
    its verbs run the fused Pallas kernels (``device_*`` below) on every
    backend: compiled on TPU/GPU, interpreted on CPU. Every other segment
    is a numpy array updated in place by the exact host kernels
    (``sparse_addto_host``/``dense_addto_host``) on every
    backend, so a host-only process (the switch daemon) needs no device.
  - stream ops (quantize, dequantize, sat_add, pack_int8, flash attention):
    the Pallas kernel on TPU or when ``REPRO_PALLAS_INTERPRET=1`` (tests),
    the pure-jnp oracle in kernels/ref.py otherwise (the dry-run / CPU
    path — interpret-mode Pallas inside a 512-device lowering would be
    pointlessly slow and is not what ships on TPU).

All wrappers accept flat 1-D streams of arbitrary length; padding to the
(rows, 128) tile layout is handled here or in the kernel module, so
kernels only see aligned blocks.
"""
from __future__ import annotations

import os
import time
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.backend import resolve_interpret
from repro.kernels.constants import (DEFAULT_BLOCK_ROWS, INT32_MAX,
                                     INT32_MIN, LANES, SAT_MAX)
from repro.kernels.dequantize import dequantize_pallas
from repro.kernels.flash_attn import (flash_attention_chunked_ref,
                                      flash_attention_pallas)
from repro.kernels.fused_gpv import (fused_addto_pallas,
                                     fused_addto_read_pallas,
                                     fused_fold_pallas,
                                     fused_read_clear_pallas,
                                     fused_read_pallas, fused_scatter_pallas)
from repro.kernels.inc_agg import sat_add_pallas
from repro.kernels.pack_int8 import pack_int8_pallas, unpack_int8_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.sparse_addto import sparse_addto_pallas
from repro.obs import hooks as _obs


def use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return resolve_interpret(None)


def _to_tiles(x: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    """Flat (n,) -> padded (rows, LANES) with rows % block_rows == 0."""
    n = x.shape[0]
    tile = block_rows * LANES
    n_pad = (-n) % tile
    x = jnp.pad(x, (0, n_pad))
    return x.reshape(-1, LANES), n


def _from_tiles(x: jax.Array, n: int) -> jax.Array:
    return x.reshape(-1)[:n]


# -- public API --------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_rows",))
def quantize(x: jax.Array, scale: jax.Array,
             block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """fp32 (n,) -> int32 (n,) fixed point with sentinel saturation."""
    if not use_pallas():
        return ref.quantize(x, scale)
    t, n = _to_tiles(x.astype(jnp.float32), block_rows)
    q = quantize_pallas(t, jnp.asarray(scale), block_rows=block_rows,
                        interpret=_interpret())
    return _from_tiles(q, n)


@partial(jax.jit, static_argnames=("block_rows",))
def dequantize(q: jax.Array, scale: jax.Array,
               block_rows: int = DEFAULT_BLOCK_ROWS
               ) -> tuple[jax.Array, jax.Array]:
    """int32 (n,) -> (fp32 (n,), bool overflow mask (n,))."""
    if not use_pallas():
        return ref.dequantize(q, scale)
    t, n = _to_tiles(q, block_rows)
    x, m = dequantize_pallas(t, jnp.asarray(scale), block_rows=block_rows,
                             interpret=_interpret())
    return _from_tiles(x, n), _from_tiles(m, n)


@partial(jax.jit, static_argnames=("block_rows",))
def sat_add(a: jax.Array, b: jax.Array,
            block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """int32 saturating add with sticky sentinels (Map.addTo hop). Any shape."""
    if not use_pallas():
        return ref.sat_add(a, b)
    shape = a.shape
    ta, n = _to_tiles(a.reshape(-1), block_rows)
    tb, _ = _to_tiles(b.reshape(-1), block_rows)
    s = sat_add_pallas(ta, tb, block_rows=block_rows, interpret=_interpret())
    return _from_tiles(s, n).reshape(shape)


@jax.jit
def _sat_add_batch_scan(acc: jax.Array, qs: jax.Array) -> jax.Array:
    return jax.lax.scan(lambda a, q: (ref.sat_add(a, q), None), acc, qs)[0]


@jax.jit
def _sat_add_batch_fast(acc: jax.Array, qs: jax.Array):
    """(all_lanes_safe, plain int32 fold). A lane is safe when |acc| plus
    the batch's absolute mass cannot reach the sentinel region: then no
    prefix of the sequential fold can saturate (and no input can be a
    sentinel, whose magnitude alone exceeds SAT_MAX), so the fold is the
    plain sum — one fused reduction instead of a B-step scan.

    int64 is unavailable under the default jax_enable_x64=False, so the
    mass bound runs in float32 with a conservative rounding margin (a
    false "unsafe" only costs the scan fallback). When safe, every partial
    sum in any association order is bounded by the mass, so the int32 sum
    cannot wrap and is exact.
    """
    mass = (jnp.abs(acc.astype(jnp.float32))
            + jnp.abs(qs.astype(jnp.float32)).sum(0))
    margin = 1.0 + (qs.shape[0] + 1) * 2.0 ** -24
    safe = mass * margin <= float(SAT_MAX)
    return jnp.all(safe), acc + qs.sum(0)


@partial(jax.jit, static_argnames=("block_rows",))
def _sat_add_batch_tpu(acc: jax.Array, qs: jax.Array,
                       block_rows: int) -> jax.Array:
    shape = acc.shape
    ta, n = _to_tiles(acc.reshape(-1), block_rows)

    def body(a, q):
        tq, _ = _to_tiles(q.reshape(-1), block_rows)
        return sat_add_pallas(a, tq, block_rows=block_rows,
                              interpret=_interpret()), None

    out, _ = jax.lax.scan(body, ta, qs)
    return _from_tiles(out, n).reshape(shape)


def sat_add_batch(acc: jax.Array, qs: jax.Array,
                  block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """Fold a stacked batch of updates into ``acc`` in ONE fused dispatch.

    ``qs`` has one extra leading dim over ``acc``. Result-identical to the
    sequential fold ``for q in qs: acc = sat_add(acc, q)`` — the fold is a
    lax.scan inside a single jit, so sticky-sentinel order is preserved
    while a drained batch of N reply-path updates costs one dispatch
    instead of N (the batched clear path of core/clear_policy.py).
    """
    qs = jnp.asarray(qs, jnp.int32)
    if qs.ndim == jnp.asarray(acc).ndim:        # single update, no batch dim
        return sat_add(acc, qs, block_rows)
    if qs.shape[0] == 1:
        return sat_add(acc, qs[0], block_rows)
    if not use_pallas():
        acc = jnp.asarray(acc, jnp.int32)
        ok, fast = _sat_add_batch_fast(acc, qs)
        if bool(ok):          # host path: the sync is a numpy read
            return fast
        return _sat_add_batch_scan(acc, qs)
    return _sat_add_batch_tpu(acc, qs, block_rows=block_rows)


def fold_stream_host(logical: np.ndarray, vals: np.ndarray | None = None
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """Fold a duplicate-keyed update stream into per-key aggregates.

    Returns ``(keys, counts, sums)`` where ``keys`` holds each distinct
    address in FIRST-OCCURRENCE order (the order ``Counter.update(stream)``
    would insert them — the INC-map LRU breaks most_common ties by that
    insertion order, so the fold must preserve it), ``counts`` the number
    of occurrences, and ``sums`` the per-key value totals (``None`` when
    ``vals`` is ``None``).  This is the host-side GPV fold: one C-level
    pass over however many RPC calls contributed to the flush, replacing
    the per-element Python loops of the dict data plane.

    Already-strictly-increasing streams (the dense tensor-index case) skip
    the sort entirely.
    """
    logical = np.asarray(logical)
    n = len(logical)
    if n == 0:
        empty = np.zeros(0, logical.dtype if logical.dtype.kind in "iu"
                         else np.int64)
        return empty, np.zeros(0, np.int64), \
            (np.zeros(0, np.int64) if vals is not None else None)
    if vals is not None:
        vals = np.asarray(vals, np.int64)
    if n == 1 or bool((np.diff(logical.astype(np.int64)) > 0).all()):
        # strictly increasing => already unique and "first-occurrence"
        # ordered; dense tensor addresses land here every call
        return logical, np.ones(n, np.int64), vals
    uniq, first, inv, cnt = np.unique(logical, return_index=True,
                                      return_inverse=True,
                                      return_counts=True)
    order = np.argsort(first, kind="stable")
    sums = None
    if vals is not None:
        sums = np.zeros(len(uniq), np.int64)
        np.add.at(sums, inv, vals)
        sums = sums[order]
    return uniq[order], cnt[order].astype(np.int64), sums


def fold_rounds(qrounds: list[np.ndarray]) -> np.ndarray:
    """Fold N quantized addTo rounds into one switch-bound update: one
    fused int64 reduction over the stacked rounds (client-side local
    aggregation, ``Agg[...](local_accum=N)``).

    Each round is already in the fixed-point integer domain (the per-round
    ``rint(x*scale)`` of inc_map.quantize_stream), so the client-side sum
    is EXACT — int64 cannot wrap on any realistic depth — and the single
    saturating switch addTo at flush matches N sequential addTo hops
    wherever no intermediate switch sum saturates (the same contract the
    device lane documents).
    """
    if len(qrounds) == 1:
        return np.asarray(qrounds[0], np.int64)
    return np.add.reduce(np.stack([np.asarray(q, np.int64)
                                   for q in qrounds]), axis=0)


def _sat_add_scalar(a: int, b: int) -> int:
    """Exact scalar ref.sat_add: sticky sentinels (a's wins), then the
    wrapped-add overflow reconstruction on the true integer sum."""
    for x in (a, b):
        if x == INT32_MAX:
            return INT32_MAX
        if x == INT32_MIN:
            return INT32_MIN
    s = a + b
    if s > 2**31 - 1:
        return INT32_MAX
    if s < -2**31:
        return INT32_MIN
    return s


def dense_addto_host(regs: np.ndarray, start: int,
                     val: np.ndarray) -> np.ndarray:
    """Saturating add of a contiguous update run — result-identical to
    ``sparse_addto_host(regs, arange(start, start+len(val)), val)`` (the
    strictly-increasing branch: one update per slot, so sequential order
    is vacuous), but slice arithmetic instead of fancy gather/scatter.
    MUTATES ``regs`` in place and returns it. The switch daemon's dense
    GPV path (repro.net) lands here."""
    n = len(val)
    if n == 0:
        return regs
    cur = regs[start:start + n].astype(np.int64)
    val = np.asarray(val, np.int64)
    safe = np.abs(cur) + np.abs(val) <= SAT_MAX
    new = cur + np.where(safe, val, 0)
    for i in np.nonzero(~safe)[0]:
        new[i] = _sat_add_scalar(int(cur[i]), int(val[i]))
    regs[start:start + n] = new.astype(np.int32)
    return regs


def sparse_addto_host(regs: np.ndarray, idx: np.ndarray,
                      val: np.ndarray) -> np.ndarray:
    """Numpy sparse_addto, result-identical to ref.sparse_addto; MUTATES
    ``regs`` in place (it is the host-path register file) and returns it.

    The sequential oracle order only matters where saturation can occur.
    Work is confined to the touched slots: a slot for which |reg| + sum|val|
    stays within the SAT range can never produce (or have started from) a
    sentinel at any prefix of the update stream, so its updates collapse to
    one segment-sum; only updates to the remaining slots run the exact
    scalar loop. A host flush of a large batched-RPC window is thus O(k)
    numpy instead of an O(k) sequential XLA loop over an O(n_slots) array.
    """
    idx = np.asarray(idx, np.int64)
    val = np.asarray(val, np.int64)
    if len(idx) == 0:
        return regs
    if len(idx) == 1 or bool((np.diff(idx) > 0).all()):
        # strictly increasing => every slot gets exactly ONE update, so the
        # sequential order is vacuous: no unique/searchsorted/segment-sum,
        # just a masked saturating add (the dense GPV flush lands here)
        cur = regs[idx].astype(np.int64)
        safe = np.abs(cur) + np.abs(val) <= SAT_MAX
        new = cur + np.where(safe, val, 0)
        for i in np.nonzero(~safe)[0]:
            new[i] = _sat_add_scalar(int(cur[i]), int(val[i]))
        regs[idx] = new.astype(np.int32)
        return regs
    touched = np.unique(idx)
    pos = np.searchsorted(touched, idx)     # update -> touched-slot index
    cur = regs[touched].astype(np.int64)
    abs_sum = np.zeros(len(touched), np.int64)
    np.add.at(abs_sum, pos, np.abs(val))
    safe = np.abs(cur) + abs_sum <= SAT_MAX         # -SAT_MIN == SAT_MAX
    safe_upd = safe[pos]
    sums = np.zeros(len(touched), np.int64)
    np.add.at(sums, pos[safe_upd], val[safe_upd])
    new = cur + sums
    for i in np.nonzero(~safe_upd)[0]:      # exact order where it matters
        t = pos[i]
        new[t] = _sat_add_scalar(int(new[t]), int(val[i]))
    regs[touched] = new.astype(np.int32)
    return regs


def zeros_regs(n_slots: int, device: bool = False):
    """A fresh register segment: a jnp array when the segment is declared
    device-resident, numpy otherwise (so host flushes never round-trip
    through a device), whatever the backend."""
    if device:
        return jnp.zeros(n_slots, jnp.int32)
    return np.zeros(n_slots, np.int32)


# -- device-resident GPV lane (fused quantize/dequantize kernels) ------------
#
# These wrappers always run the Pallas path on jnp register files,
# regardless of backend (interpret resolves per kernels/backend.py).
# They serve core/inc_map.py:DeviceSegment; the host path never calls them.
# Each call is one kernel launch, recorded under its kernel name when obs
# metrics are on (the jitted bodies run only when traced, so the record
# sits here, per call).

@lru_cache(maxsize=256)
def _device_scalar(kind: type, v, dtype=None) -> jax.Array:
    """``jnp.asarray(v, dtype)`` of a Python number, made once per value.

    A Python scalar handed to a jitted program is copied to the device on
    every call; a device array is not. ``jnp.asarray`` keeps the weak type
    jit gives a Python scalar, so the cached operand compiles and computes
    as the number did. Keyed by type as well (``1 == 1.0`` as dict keys);
    the values are partition offsets, run lengths and precisions, so a
    small LRU holds them all."""
    return jnp.asarray(v, dtype)


def _operand(v, dtype=None) -> jax.Array:
    """A dynamic scalar operand of a device-lane program (a Python
    number), as a cached device scalar."""
    return _device_scalar(type(v), v, dtype)


def _launch(kernel: str, n: int, fn, *args):
    t0 = time.perf_counter() if _obs.METRICS else 0.0
    out = fn(*args)
    if _obs.METRICS:
        _obs.kernel_launch(kernel, n, t0)
    return out


@jax.jit
def _fused_fold_jit(fstack, scale):
    return fused_fold_pallas(fstack, scale)


def device_fold_rounds(frounds: list, scale) -> jax.Array:
    """Quantize N fp32 addTo rounds and fold them in the int32 switch
    domain in ONE fused kernel launch (kernels/fused_gpv.py) — the
    ``device=True`` lane of ``local_accum``. Returns the folded int32
    stream; agrees with :func:`fold_rounds` over host-quantized rounds
    wherever no intermediate sum saturates."""
    fstack = jnp.stack([
        (_obs.h2d(f, "rpc.fold", jnp.float32) if _obs.ON
         else jnp.asarray(f, jnp.float32)).reshape(-1) for f in frounds])
    return _launch("fused_fold", int(fstack.size), _fused_fold_jit, fstack,
                   _operand(scale, jnp.float32))


@jax.jit
def _fused_addto_jit(regs, start, fvals, scale):
    return fused_addto_pallas(regs, start, fvals, scale)


@jax.jit
def _fused_scatter_jit(regs, idx, fvals, scale):
    return fused_scatter_pallas(regs, idx, fvals, scale)


@jax.jit
def _device_scatter_int_jit(regs, idx, vals):
    return sparse_addto_pallas(regs, idx, vals)


@partial(jax.jit, static_argnames=("n", "clear"))
def _fused_read_jit(regs, start, n, scale, clear=False, live=None):
    if clear:
        return fused_read_clear_pallas(regs, start, n, live, scale)
    return fused_read_pallas(regs, start, n, scale)


@partial(jax.jit, static_argnames=("bucket", "clear"))
def _fused_addto_read_jit(regs, start, fvals, scale, bucket, clear):
    return fused_addto_read_pallas(regs, start, fvals, scale, bucket, clear)


def _run_bucket(regs, start: int, n: int) -> int:
    """A dense run's power-of-two length, which bounds the jit cache, or
    ``n`` where the bucket would run past the segment end (one extra jit
    entry)."""
    bucket = 1 << (n - 1).bit_length()
    return n if start + bucket > int(regs.shape[0]) else bucket


def device_addto_dense(regs, start: int, fvals, scale) -> jax.Array:
    """Fused quantize + saturating add of an fp32 stream over the
    contiguous slot range [start, start+len). The stream is padded to its
    bucket (``_run_bucket``; quantize(0.0) == 0 is a sat-add no-op), never
    sent to the serial scatter, whose per-element loop is pathological in
    interpret mode for a full-segment slice."""
    n = int(fvals.shape[0])
    if n == 0:
        return regs
    bucket = _run_bucket(regs, start, n)
    if bucket != n:
        fvals = jnp.pad(jnp.asarray(fvals, jnp.float32), (0, bucket - n))
    return _launch("fused_addto", n, _fused_addto_jit, regs,
                   _operand(start), fvals, _operand(scale))


def device_addto_scatter(regs, idx, fvals, scale) -> jax.Array:
    """Fused quantize + serial saturating scatter-add of an fp32 stream;
    duplicate addresses accumulate in stream order, exactly like the host
    sequential oracle. Power-of-two padded with (idx=0, fval=0.0) no-ops."""
    k = int(idx.shape[0])
    if k == 0:
        return regs
    idx = (_obs.h2d(idx, "regfile.addto_scatter", jnp.int32) if _obs.ON
           else jnp.asarray(idx, jnp.int32))
    bucket = 1 << (k - 1).bit_length()
    if bucket != k:
        idx = jnp.pad(idx, (0, bucket - k))
        fvals = jnp.pad(jnp.asarray(fvals, jnp.float32), (0, bucket - k))
    return _launch("fused_scatter", k, _fused_scatter_jit, regs, idx,
                   fvals, _operand(scale))


def device_addto_int(regs, idx, vals) -> jax.Array:
    """Saturating scatter-add of an already-quantized int32 stream into a
    device register file — the int lane of a DeviceSegment (spill
    restores, host-quantized updates, a reply's clear outside a fused
    read). Runs the Pallas kernel on every backend so the segment stays a
    jnp array. Power-of-two padded with (idx=0, val=0) no-ops."""
    k = int(idx.shape[0])
    if k == 0:
        return regs
    if _obs.ON:
        idx = _obs.h2d(idx, "regfile.addto_int", jnp.int32)
        vals = _obs.h2d(vals, "regfile.addto_int", jnp.int32)
    else:
        idx = jnp.asarray(idx, jnp.int32)
        vals = jnp.asarray(vals, jnp.int32)
    bucket = 1 << (k - 1).bit_length()
    if bucket != k:
        idx = jnp.pad(idx, (0, bucket - k))
        vals = jnp.pad(vals, (0, bucket - k))
    return _launch("sparse_addto", k, _device_scatter_int_jit, regs, idx,
                   vals)


def device_read_dense(regs, start: int, n: int, scale
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused gather + dequantize of slots [start, start+n) -> (fp32
    values, bool overflow-sentinel mask), both jnp. Reads are bucketed to
    a power-of-two length and sliced back."""
    if n == 0:
        return (jnp.zeros(0, jnp.float32), jnp.zeros(0, jnp.bool_))
    vals, mask = _launch("fused_read", n, _fused_read_jit, regs,
                         _operand(start), _run_bucket(regs, start, n),
                         _operand(scale))
    return vals[:n], mask[:n]


def device_read_clear_dense(regs, start: int, n: int, scale
                            ) -> tuple[jax.Array, jax.Array]:
    """``device_read_dense``'s values, and the segment with the run
    cleared in the same program (kernels/fused_gpv.py
    ``fused_read_clear_pallas``: each register of [start, start+n) to
    zero, a sentinel kept) -> (fp32 values, new regs). The bucket past
    the run is read, never cleared."""
    if n == 0:
        return jnp.zeros(0, jnp.float32), regs
    vals, _, regs = _launch("fused_read", n, _fused_read_jit, regs,
                            _operand(start), _run_bucket(regs, start, n),
                            _operand(scale), True, _operand(n))
    return vals[:n], regs


def device_addto_read_dense(regs, start: int, fvals, scale, clear: bool
                            ) -> tuple[jax.Array, jax.Array]:
    """``device_addto_dense`` of ``fvals`` over [start, start+n), then
    ``device_read_dense`` of that run (``device_read_clear_dense`` with
    ``clear``), in ONE launch (kernels/fused_gpv.py
    ``fused_addto_read_pallas``) -> (fp32 values (n,), new regs): the
    same kernels and buckets as the two launches, and the reply comes
    back cut to the run."""
    n = int(fvals.shape[0])
    return _launch("fused_addto_read", n, _fused_addto_read_jit, regs,
                   _operand(start), fvals, _operand(scale),
                   _run_bucket(regs, start, n), clear)


@partial(jax.jit, static_argnames=("block_rows",))
def pack_int8(x: jax.Array, block_rows: int = DEFAULT_BLOCK_ROWS
              ) -> tuple[jax.Array, jax.Array]:
    """fp32 (n,) -> (int8 (rows,128), fp32 scales (rows,)). Padded tiles.

    The caller keeps x.shape[0] to truncate after unpack_int8.
    """
    t, _ = _to_tiles(x.astype(jnp.float32), block_rows)
    if not use_pallas():
        q, s = ref.pack_int8_block(t)
    else:
        q, s = pack_int8_pallas(t, block_rows=block_rows,
                                interpret=_interpret())
    return q, s


@partial(jax.jit, static_argnames=("block_rows", "n"))
def unpack_int8(q: jax.Array, scale: jax.Array, n: int,
                block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """(int8 tiles, scales, n) -> fp32 (n,)."""
    if not use_pallas():
        x = ref.unpack_int8_block(q, scale)
    else:
        x = unpack_int8_pallas(q, scale, block_rows=block_rows,
                               interpret=_interpret())
    return _from_tiles(x, n)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    window: int | None = None) -> jax.Array:
    """(B,S,H,D) x (B,S,KV,D) -> (B,S,H,D) flash attention.

    Wrapped in a named_scope so the roofline analyzer can attribute this
    region to the VMEM-resident Pallas kernel (kernels/flash_attn.py): on
    CPU the oracle lowers instead (same math), and its HBM-traffic lines
    are replaced by the kernel's analytic q+o+nq*(k+v) model.
    """
    with jax.named_scope("flash_attention"):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        if use_pallas():
            o = flash_attention_pallas(qt, kt, vt, causal=causal,
                                       window=window,
                                       interpret=_interpret())
        else:
            o = flash_attention_chunked_ref(qt, kt, vt, causal=causal,
                                            window=window)
        return jnp.swapaxes(o, 1, 2)
