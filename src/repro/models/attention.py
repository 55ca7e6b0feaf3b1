"""GQA attention for the zoo: train (full / chunked / local / bidir / cross)
and decode (batch-local or sequence-sharded KV with log-sum-exp combine).

Sharding notes (all under the auto 'model' axis of the step shard_map):
  - head projections are sharded over heads -> GSPMD tensor-parallelizes the
    attention and inserts the out-proj partial-sum all-reduce;
  - decode with seq-sharded KV (long_500k, batch=1) uses explicit collectives
    over the *manual* data axes: a flash-decoding-style partial-softmax
    combine (pmax + two psums) instead of gathering half a terabyte of KV.

FLOP accounting note for §Roofline: causal attention is computed dense with
masking (train & prefill), so compiled HLO_FLOPs include the ~2x causal
waste on the attention score terms, which MODEL_FLOPS/HLO_FLOPs reflects.
Local-window layers avoid the waste structurally (each query
chunk touches exactly two W-sized KV chunks).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.common import (COMPUTE_DTYPE, apply_rope, dense_init,
                                 rms_norm)

NEG_INF = -1e30
# above this seq len, causal attention scans over query chunks (peak
# transient (B,H,Cq,S) instead of (B,H,S,S) — required to fit HBM at 4k+)
CHUNKED_THRESHOLD = 2_048
Q_CHUNK = 1_024


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_attn(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qkv_bias: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads, head_dim), fan_in=d_model),
        "wk": dense_init(ks[1], (d_model, n_kv, head_dim), fan_in=d_model),
        "wv": dense_init(ks[2], (d_model, n_kv, head_dim), fan_in=d_model),
        "wo": dense_init(ks[3], (n_heads, head_dim, d_model),
                         fan_in=n_heads * head_dim),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), COMPUTE_DTYPE)
        p["bk"] = jnp.zeros((n_kv, head_dim), COMPUTE_DTYPE)
        p["bv"] = jnp.zeros((n_kv, head_dim), COMPUTE_DTYPE)
    return p


def qkv(p: dict, x: jax.Array, x_kv: jax.Array | None = None):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,Skv,KV,hd)."""
    xk = x if x_kv is None else x_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xk, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xk, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# core attention math (grouped heads)
# ---------------------------------------------------------------------------

def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,S,H,hd) -> (B,S,KV,G,hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def _attend(q, k, v, mask) -> jax.Array:
    """q: (B,Sq,KV,G,hd), k/v: (B,Sk,KV,hd), mask: broadcastable
    (B?,1?,Sq,Sk) boolean (True = attend). Returns (B,Sq,KV,G,hd)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    if mask is not None:
        scores = jnp.where(mask[:, None, None] if mask.ndim == 3
                           else mask, scores, NEG_INF)
    # fp32 softmax, guarding fully-masked rows (empty local windows)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jnp.maximum(m, NEG_INF / 2))
    if mask is not None:
        e = jnp.where(mask[:, None, None] if mask.ndim == 3 else mask, e, 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    w = (e / jnp.maximum(den, 1e-30)).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v)


def _merge(o: jax.Array) -> jax.Array:
    b, s, kv, g, hd = o.shape
    return o.reshape(b, s, kv * g, hd)


# ---------------------------------------------------------------------------
# train / prefill attention
# ---------------------------------------------------------------------------

def attn_full(q, k, v, n_kv: int, causal: bool) -> jax.Array:
    """Single-shot attention; used when S is small enough to fuse."""
    sq, sk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool))[None, :, :]
        mask = mask[:, None]                       # (1,1,Sq,Sk)
    return _merge(_attend(_group(q, n_kv), k, v, mask))


def attn_causal_chunked(q, k, v, n_kv: int, q_chunk: int = Q_CHUNK,
                        remat: bool = False) -> jax.Array:
    """Memory-efficient causal attention: scan over query chunks, each
    attending to the full (masked) KV. Peak transient is (B,H,Cq,S);
    with ``remat`` each chunk is recomputed in the backward pass, so the
    backward keeps one chunk's scores at a time, not all of them. The
    value head size may differ from the query/key head size (MLA)."""
    b, s, h, _ = q.shape
    assert s % q_chunk == 0, (s, q_chunk)
    nq = s // q_chunk
    qg = _group(q, n_kv)

    def body(_, i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, axis=1)
        qpos = i * q_chunk + jnp.arange(q_chunk)
        mask = (jnp.arange(s)[None, :] <= qpos[:, None])[None, None]
        return None, _attend(qs, k, v, mask)

    if remat:
        body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, jnp.arange(nq))   # (nq,B,Cq,KV,G,hv)
    o = jnp.moveaxis(outs, 0, 1).reshape(b, s, n_kv, h // n_kv, v.shape[-1])
    return _merge(o)


def attn_local(q, k, v, n_kv: int, window: int) -> jax.Array:
    """Exact sliding-window causal attention, O(S*W): query chunk i (chunk
    size == window) attends KV chunks i-1 and i with a band mask. Ragged
    tails are padded to a window multiple (padded keys sit at positions
    beyond every real query, so the causal band masks them out)."""
    b, s, h, hd = q.shape
    w = window
    if s <= w:
        return attn_full(q, k, v, n_kv, causal=True)
    pad = (-s) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = attn_local(q, k, v, n_kv, window)
        return out[:, :s]
    nq = s // w
    qg = _group(q, n_kv)

    def body(_, i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * w, w, axis=1)
        start = jnp.maximum(i - 1, 0) * w           # i=0 re-reads chunk 0
        ks = jax.lax.dynamic_slice_in_dim(k, start, 2 * w, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, 2 * w, axis=1)
        qpos = i * w + jnp.arange(w)
        kpos = start + jnp.arange(2 * w)
        mask = ((kpos[None, :] <= qpos[:, None])
                & (kpos[None, :] > qpos[:, None] - w))[None, None]
        return None, _attend(qs, ks, vs, mask)

    _, outs = jax.lax.scan(body, None, jnp.arange(nq))
    o = jnp.moveaxis(outs, 0, 1).reshape(b, s, n_kv, h // n_kv, hd)
    return _merge(o)


def attn_train(p: dict, x: jax.Array, *, n_kv: int, kind: str,
               window: int, theta: float, positions: jax.Array,
               memory: jax.Array | None = None) -> jax.Array:
    """Dispatch one attention sub-layer over a full sequence.

    kind: "global" | "local" | "bidir" | "cross".
    """
    if kind == "cross":
        q, k, v = qkv(p, x, memory)
        o = attn_full(q, k, v, n_kv, causal=False)
        return out_proj(p, o)
    q, k, v = qkv(p, x)
    if kind != "bidir":
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    if kind == "bidir":
        o = attn_full(q, k, v, n_kv, causal=False)
    elif kind == "local":
        if use_flash() and x.shape[1] > window:
            from repro.kernels import ops
            o = ops.flash_attention(q, k, v, causal=True, window=window)
        else:
            o = attn_local(q, k, v, n_kv, window)
    elif use_flash():
        from repro.kernels import ops
        o = ops.flash_attention(q, k, v, causal=True)
    elif x.shape[1] > CHUNKED_THRESHOLD:
        o = attn_causal_chunked(q, k, v, n_kv)
    else:
        o = attn_full(q, k, v, n_kv, causal=True)
    return out_proj(p, o)


def use_flash() -> bool:
    """Beyond-paper perf toggle: route causal global attention through the
    Pallas flash kernel (kernels/flash_attn.py). Env-driven so the dry-run
    sweep can A/B it per cell."""
    import os
    return os.environ.get("REPRO_FLASH_ATTN") == "1"


# ---------------------------------------------------------------------------
# decode attention (one new token against a KV cache)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KVLayout:
    """How the decode KV cache is laid out across the manual mesh axes.

    seq_axes=None: cache is batch-sharded (every rank holds full-length KV
    for its batch slice). seq_axes=(...): cache dim 1 is sharded over those
    manual axes (long-context, batch too small to shard) and attention uses
    a partial-softmax combine.
    """
    length: int                  # per-rank cache length
    seq_axes: tuple | None = None

    def offset(self) -> jax.Array:
        if self.seq_axes is None:
            return jnp.int32(0)
        idx = 0
        for ax in self.seq_axes:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        return (idx * self.length).astype(jnp.int32)


def decode_attn(p: dict, x1: jax.Array, k_cache, v_cache, pos, *,
                n_kv: int, theta: float, layout: KVLayout,
                window: int | None = None, rope: bool = True):
    """x1: (B,1,d). Returns (out (B,1,d), k_cache, v_cache).

    window=None: global cache, slot = pos (minus rank offset if sharded).
    window=W: ring-buffer cache of length W, slot = pos % W (never sharded:
    a ring is already O(W) memory).
    """
    q, k, v = qkv(p, x1)
    if rope:
        posb = jnp.broadcast_to(pos, (x1.shape[0], 1))
        q = apply_rope(q, posb, theta)
        k = apply_rope(k, posb, theta)

    if window is not None:
        slot = (pos % window).astype(jnp.int32)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, 1)
        idx = jnp.arange(window)
        # slot j currently holds absolute position p_j <= pos, p_j = j mod W
        p_j = pos - ((pos - idx) % window)
        valid = (p_j >= 0) & (p_j > pos - window)
        out = _decode_attend(q, k_cache, v_cache, valid, n_kv, None)
        return out_proj(p, out), k_cache, v_cache

    off = layout.offset()
    local = (pos - off).astype(jnp.int32)
    writable = (local >= 0) & (local < layout.length)
    slot = jnp.clip(local, 0, layout.length - 1)
    k_new = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, 1)
    v_new = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, 1)
    k_cache = jnp.where(writable, k_new, k_cache)
    v_cache = jnp.where(writable, v_new, v_cache)
    idx = off + jnp.arange(layout.length)
    valid = idx <= pos
    out = _decode_attend(q, k_cache, v_cache, valid, n_kv, layout.seq_axes)
    return out_proj(p, out), k_cache, v_cache


def _decode_attend(q, k_cache, v_cache, valid, n_kv: int,
                   seq_axes: tuple | None) -> jax.Array:
    """q: (B,1,H,hd); cache: (B,L,KV,hd); valid: (L,) bool.
    Partial-softmax combine over seq_axes when the cache is seq-sharded."""
    b, _, h, hd = q.shape
    qg = _group(q, n_kv)[:, 0]                       # (B,KV,G,hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32)
    s = s * (hd ** -0.5)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)           # (B,KV,G,1)
    if seq_axes is not None:
        m = jax.lax.pmax(m, seq_axes)
    e = jnp.where(valid[None, None, None, :],
                  jnp.exp(s - jnp.maximum(m, NEG_INF / 2)), 0.0)
    den = jnp.sum(e, axis=-1)                        # (B,KV,G)
    o = jnp.einsum("bkgs,bskh->bkgh", e.astype(v_cache.dtype), v_cache)
    if seq_axes is not None:
        den = jax.lax.psum(den, seq_axes)
        o = jax.lax.psum(o.astype(jnp.float32), seq_axes)
    o = o.astype(jnp.float32) / jnp.maximum(den[..., None], 1e-30)
    return o.reshape(b, 1, h, v_cache.shape[-1]).astype(v_cache.dtype)


def decode_cross_attn(p: dict, x1: jax.Array, k_mem, v_mem, n_kv: int):
    """Cross-attention during decode against precomputed memory K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x1, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    valid = jnp.ones(k_mem.shape[1], bool)
    out = _decode_attend(q, k_mem, v_mem, valid, n_kv, None)
    return out_proj(p, out)


def memory_kv(p: dict, memory: jax.Array):
    """Precompute cross-attention K/V from encoder/frontend memory."""
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    return k, v


# ---------------------------------------------------------------------------
# multi-head latent attention (MLA, DeepSeek-V2/V3; no query compression)
# ---------------------------------------------------------------------------
# Keys and values come from one latent per position: [c_kv, k_pe] = h W_kv_a,
# c_kv RMS-normed, [k_nope, v] = c_kv W_kv_b per head; the rotary part of
# the key (k_pe) is one vector shared by every head. Queries are h W_q,
# split per head into q_nope and q_pe. Scores are
# [q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope). The rotary halves
# use this module's split layout (apply_rope); the published checkpoint
# stores them interleaved, which is the same map up to a fixed permutation
# of the rope columns of W_q and W_kv_a. The decode cache holds the normed
# latent and the rotated k_pe, not per-head keys and values.

MLA_Q_CHUNK = 512
# eps of the latent's RMSNorm: the DeepSeek-V2/V3 modeling code's RMSNorm
# default, which their configs do not override (rms_norm_eps is the
# blocks' own norms)
MLA_LATENT_EPS = 1e-6


def init_mla(key, d_model: int, n_heads: int, kv_rank: int, nope: int,
             rope: int, v_dim: int) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d_model, n_heads, nope + rope),
                         fan_in=d_model),
        "wkv_a": dense_init(ks[1], (d_model, kv_rank + rope), fan_in=d_model),
        "kv_norm": jnp.zeros((kv_rank,), jnp.float32),
        "wkv_b": dense_init(ks[2], (kv_rank, n_heads, nope + v_dim),
                            fan_in=kv_rank),
        "wo": dense_init(ks[3], (n_heads, v_dim, d_model),
                         fan_in=n_heads * v_dim),
    }


def mla_latent(p: dict, x: jax.Array, positions, *, rope: int,
               theta: float):
    """x: (B,S,d) -> (normed latent (B,S,rank), rotated k_pe (B,S,rope)):
    what the decode cache keeps per position."""
    kv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"])
    c, k_pe = kv[..., :-rope], kv[..., -rope:]
    c = rms_norm(c, p["kv_norm"], MLA_LATENT_EPS)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, theta)[:, :, 0]
    return c, k_pe


def mla_expand(p: dict, c: jax.Array, k_pe: jax.Array, *, nope: int):
    """Latent -> per-head keys (nope + rope) and values."""
    kvb = jnp.einsum("bsc,chk->bshk", c, p["wkv_b"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    h = k_nope.shape[2]
    k_rot = jnp.broadcast_to(k_pe[:, :, None, :],
                             k_pe.shape[:2] + (h, k_pe.shape[-1]))
    return jnp.concatenate([k_nope, k_rot], axis=-1), v


def mla_query(p: dict, x: jax.Array, positions, *, nope: int, theta: float):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_pe = apply_rope(q[..., nope:], positions, theta)
    return jnp.concatenate([q[..., :nope], q_pe], axis=-1)


def mla_train(p: dict, x: jax.Array, *, nope: int, rope: int, theta: float,
              positions: jax.Array) -> jax.Array:
    """Causal MLA over a full sequence: (B,S,d) -> (B,S,d)."""
    with jax.named_scope("mla"):
        q = mla_query(p, x, positions, nope=nope, theta=theta)
        c, k_pe = mla_latent(p, x, positions, rope=rope, theta=theta)
        k, v = mla_expand(p, c, k_pe, nope=nope)
        h = q.shape[2]
        if x.shape[1] > CHUNKED_THRESHOLD:
            o = attn_causal_chunked(q, k, v, h,
                                    min(MLA_Q_CHUNK, x.shape[1]), remat=True)
        else:
            o = attn_full(q, k, v, h, causal=True)
        return out_proj(p, o)


def mla_decode(p: dict, x1: jax.Array, c_cache, pe_cache, pos, *, nope: int,
               rope: int, theta: float):
    """x1: (B,1,d) against a latent cache of positions < pos; returns
    (out (B,1,d), c_cache, pe_cache) with position ``pos`` written."""
    posb = jnp.broadcast_to(pos, (x1.shape[0], 1))
    q = mla_query(p, x1, posb, nope=nope, theta=theta)
    c, k_pe = mla_latent(p, x1, posb, rope=rope, theta=theta)
    slot = pos.astype(jnp.int32)
    c_cache = jax.lax.dynamic_update_slice_in_dim(c_cache, c, slot, 1)
    pe_cache = jax.lax.dynamic_update_slice_in_dim(pe_cache, k_pe, slot, 1)
    k, v = mla_expand(p, c_cache, pe_cache, nope=nope)
    valid = jnp.arange(c_cache.shape[1]) <= pos
    out = _decode_attend(q, k, v, valid, q.shape[2], None)
    return out_proj(p, out), c_cache, pe_cache
