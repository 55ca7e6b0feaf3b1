"""Block-level dispatch: one init/train/prefill/decode entry per block type.

A block is one element of an ArchConfig pattern (pre-norm residual layout).
The stack (api.py) scans over repeats of a pattern unit; these functions
define what each slot of the unit does and what decode cache it carries.

Cache entries per block type:
  global/moe   {"k","v"}: (B, S, KV, hd)  (seq-shardable)
  local        {"k","v"}: (B, W, KV, hd)  ring buffer
  cross        {"mk","mv"}: (B, M, KV, hd) static memory K/V
  selfcross    self {"k","v"} + static {"mk","mv"}
  ssd          {"state"}: (B,H,P,N) fp32, {"conv"}: (B,K-1,Cc)
  rglru        {"state"}: (B,rnn) fp32, {"conv"}: (B,K-1,rnn)
  mla/mla_moe  {"ckv"}: (B, S, kv_lora_rank) normed latent,
               {"kpe"}: (B, S, qk_rope_head_dim) rotated shared rope key
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import ffn
from repro.models.common import COMPUTE_DTYPE, rms_norm, rms_norm_init
from repro.models.rglru import init_rglru, rglru_decode, rglru_train
from repro.models.ssm import init_mamba2, mamba2_decode, mamba2_train


@dataclass(frozen=True)
class Ctx:
    """Everything a block needs besides params and activations."""
    cfg: ArchConfig
    positions: jax.Array | None = None      # (S,) for train/prefill
    memory: jax.Array | None = None         # (B,M,d) cross-attn memory
    seq_axes: tuple | None = None           # manual axes sharding decode KV
    # FSDP hook: (scope, group_idx, sliced_params) -> gathered params.
    # Applied inside the layer scan so only one layer's params are ever
    # materialized; its custom_vjp makes the INC reduce-scatter the
    # gradient path (see launch/steps.py).
    param_gather: object = None

    def gather(self, scope: str, gi: int, pslice):
        if self.param_gather is None:
            return pslice
        return self.param_gather(scope, gi, pslice)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, bt: str, cfg: ArchConfig) -> dict:
    d, eps = cfg.d_model, cfg.norm_eps
    ks = jax.random.split(key, 3)
    if bt in ("mla", "mla_moe"):
        p = {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
             "attn": attn.init_mla(ks[0], d, cfg.n_heads, cfg.kv_lora_rank,
                                   cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                   cfg.v_head_dim)}
        if bt == "mla":
            p["mlp"] = ffn.init_swiglu(ks[1], d, cfg.dense_d_ff)
        else:
            p["moe"] = ffn.init_sigmoid_moe(
                ks[1], d, cfg.d_ff, cfg.n_experts, cfg.experts_held,
                cfg.expert_offset, cfg.n_shared_experts)
        return p
    if bt == "ssd":
        return {"n1": rms_norm_init(d),
                "mix": init_mamba2(ks[0], d, cfg.ssm_heads, cfg.ssm_head_dim,
                                   cfg.ssm_state, cfg.ssm_conv)}
    if bt == "rglru":
        return {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
                "mix": init_rglru(ks[0], d, cfg.rnn_width or d),
                "mlp": ffn.init_swiglu(ks[1], d, cfg.d_ff)}
    if bt == "cross":
        return {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
                "xattn": attn.init_attn(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                        cfg.hd),
                "gate": jnp.zeros((), jnp.float32),
                "mlp": ffn.init_swiglu(ks[1], d, cfg.d_ff)}
    if bt == "selfcross":
        return {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
                "n3": rms_norm_init(d),
                "attn": attn.init_attn(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd),
                "xattn": attn.init_attn(ks[1], d, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.hd),
                "mlp": ffn.init_gelu_mlp(ks[2], d, cfg.d_ff)}
    if bt == "bidir":
        return {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
                "attn": attn.init_attn(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd),
                "mlp": ffn.init_gelu_mlp(ks[1], d, cfg.d_ff)}
    if bt == "moe":
        return {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
                "attn": attn.init_attn(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd, cfg.qkv_bias),
                "moe": ffn.init_moe(ks[1], d, cfg.d_ff, cfg.n_experts)}
    # global / local
    return {"n1": rms_norm_init(d), "n2": rms_norm_init(d),
            "attn": attn.init_attn(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.hd, cfg.qkv_bias),
            "mlp": ffn.init_swiglu(ks[1], d, cfg.d_ff)}


# ---------------------------------------------------------------------------
# train (full sequence, no cache)
# ---------------------------------------------------------------------------

def _mla(p: dict, x: jax.Array, cfg: ArchConfig, positions) -> jax.Array:
    return attn.mla_train(p["attn"], rms_norm(x, p["n1"], cfg.norm_eps),
                          nope=cfg.qk_nope_head_dim,
                          rope=cfg.qk_rope_head_dim, theta=cfg.rope_theta,
                          positions=positions)


def _sigmoid_moe(p: dict, x: jax.Array, cfg: ArchConfig):
    return ffn.sigmoid_moe(p["moe"], rms_norm(x, p["n2"], cfg.norm_eps),
                           top_k=cfg.top_k, scaling=cfg.routed_scaling,
                           held=cfg.experts_held, offset=cfg.expert_offset)


def block_train_stats(p: dict, bt: str, x: jax.Array, ctx: Ctx
                      ) -> tuple[jax.Array, jax.Array, dict | None]:
    """``block_train`` plus the routing stats of a sigmoid-routed MoE
    block (``ffn.sigmoid_moe``); None for every other block."""
    if bt != "mla_moe":
        return (*block_train(p, bt, x, ctx), None)
    x = x + _mla(p, x, ctx.cfg, ctx.positions)
    y, aux, stats = _sigmoid_moe(p, x, ctx.cfg)
    return x + y, aux, stats


def block_train(p: dict, bt: str, x: jax.Array, ctx: Ctx
                ) -> tuple[jax.Array, jax.Array]:
    cfg = ctx.cfg
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    if bt == "mla":
        x = x + _mla(p, x, cfg, ctx.positions)
        return x + ffn.swiglu(p["mlp"], rms_norm(x, p["n2"], eps)), aux
    if bt == "mla_moe":
        x, aux, _ = block_train_stats(p, bt, x, ctx)
        return x, aux
    if bt == "ssd":
        y, _, _ = mamba2_train(p["mix"], rms_norm(x, p["n1"], eps),
                               n_heads=cfg.ssm_heads,
                               head_dim=cfg.ssm_head_dim,
                               d_state=cfg.ssm_state, norm_eps=eps)
        return x + y, aux
    if bt == "rglru":
        y, _, _ = rglru_train(p["mix"], rms_norm(x, p["n1"], eps))
        x = x + y
        return x + ffn.swiglu(p["mlp"], rms_norm(x, p["n2"], eps)), aux
    if bt == "cross":
        y = attn.attn_train(p["xattn"], rms_norm(x, p["n1"], eps),
                            n_kv=cfg.n_kv_heads, kind="cross",
                            window=cfg.window, theta=cfg.rope_theta,
                            positions=ctx.positions, memory=ctx.memory)
        x = x + jnp.tanh(p["gate"]).astype(x.dtype) * y
        return x + ffn.swiglu(p["mlp"], rms_norm(x, p["n2"], eps)), aux
    if bt == "selfcross":
        y = attn.attn_train(p["attn"], rms_norm(x, p["n1"], eps),
                            n_kv=cfg.n_kv_heads, kind="global",
                            window=cfg.window, theta=cfg.rope_theta,
                            positions=ctx.positions)
        x = x + y
        y = attn.attn_train(p["xattn"], rms_norm(x, p["n2"], eps),
                            n_kv=cfg.n_kv_heads, kind="cross",
                            window=cfg.window, theta=cfg.rope_theta,
                            positions=ctx.positions, memory=ctx.memory)
        x = x + y
        return x + ffn.gelu_mlp(p["mlp"], rms_norm(x, p["n3"], eps)), aux
    if bt == "bidir":
        y = attn.attn_train(p["attn"], rms_norm(x, p["n1"], eps),
                            n_kv=cfg.n_kv_heads, kind="bidir",
                            window=cfg.window, theta=cfg.rope_theta,
                            positions=ctx.positions)
        x = x + y
        return x + ffn.gelu_mlp(p["mlp"], rms_norm(x, p["n2"], eps)), aux
    # global / local / moe self-attention
    kind = "local" if bt == "local" else "global"
    y = attn.attn_train(p["attn"], rms_norm(x, p["n1"], eps),
                        n_kv=cfg.n_kv_heads, kind=kind, window=cfg.window,
                        theta=cfg.rope_theta, positions=ctx.positions)
    x = x + y
    if bt == "moe":
        y, aux = ffn.moe_apply(p["moe"], rms_norm(x, p["n2"], eps),
                               top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor)
        return x + y, aux
    return x + ffn.swiglu(p["mlp"], rms_norm(x, p["n2"], eps)), aux


# ---------------------------------------------------------------------------
# prefill: train-path compute that also emits the decode cache entry
# ---------------------------------------------------------------------------

def _roped_kv(p_attn, x, positions, theta, rope=True):
    _, k, v = attn.qkv(p_attn, x)
    if rope:
        k = attn.apply_rope(k, positions, theta)
    return k, v


def _ring_pack(k, window):
    """Last `window` positions of (B,S,KV,hd) arranged by ring slot
    (slot j holds the latest position p with p % W == j)."""
    s = k.shape[1]
    if s <= window:
        return jnp.pad(k, ((0, 0), (0, window - s), (0, 0), (0, 0)))
    if s % window == 0:
        return k[:, -window:]          # identity arrangement
    tail = k[:, -window:]
    slots = (jnp.arange(s - window, s)) % window
    out = jnp.zeros_like(tail)
    return out.at[:, slots].set(tail)


def block_prefill(p: dict, bt: str, x: jax.Array, ctx: Ctx
                  ) -> tuple[jax.Array, dict]:
    """Returns (x_out, cache_entry). Norm of x for KV must match decode."""
    cfg = ctx.cfg
    eps = cfg.norm_eps
    if bt == "ssd":
        xn = rms_norm(x, p["n1"], eps)
        y, state, conv_tail = mamba2_train(
            p["mix"], xn, n_heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
            d_state=cfg.ssm_state, norm_eps=eps)
        return x + y, {"state": state, "conv": conv_tail}
    if bt == "rglru":
        xn = rms_norm(x, p["n1"], eps)
        y, state, conv_tail = rglru_train(p["mix"], xn)
        x = x + y
        x = x + ffn.swiglu(p["mlp"], rms_norm(x, p["n2"], eps))
        return x, {"state": state, "conv": conv_tail}
    if bt == "cross":
        mk, mv = attn.memory_kv(p["xattn"], ctx.memory)
        x, _ = block_train(p, bt, x, ctx)
        return x, {"mk": mk, "mv": mv}
    if bt == "selfcross":
        xn = rms_norm(x, p["n1"], eps)
        k, v = _roped_kv(p["attn"], xn, ctx.positions, cfg.rope_theta,
                         rope=False)   # whisper: sinusoidal, no rope on k
        mk, mv = attn.memory_kv(p["xattn"], ctx.memory)
        x, _ = block_train(p, bt, x, ctx)
        return x, {"k": k, "v": v, "mk": mk, "mv": mv}
    if bt in ("mla", "mla_moe"):
        c, k_pe = attn.mla_latent(p["attn"], rms_norm(x, p["n1"], eps),
                                  ctx.positions, rope=cfg.qk_rope_head_dim,
                                  theta=cfg.rope_theta)
        x, _ = block_train(p, bt, x, ctx)
        return x, {"ckv": c, "kpe": k_pe}
    # attention blocks: capture roped K/V of the *normed* input
    xn = rms_norm(x, p["n1"], eps)
    k, v = _roped_kv(p["attn"], xn, ctx.positions, cfg.rope_theta)
    x, _ = block_train(p, bt, x, ctx)
    if bt == "local":
        return x, {"k": _ring_pack(k, cfg.window),
                   "v": _ring_pack(v, cfg.window)}
    return x, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# decode (one token, cache update)
# ---------------------------------------------------------------------------

def block_decode(p: dict, bt: str, x1: jax.Array, cache: dict,
                 pos: jax.Array, ctx: Ctx) -> tuple[jax.Array, dict]:
    cfg = ctx.cfg
    eps = cfg.norm_eps
    if bt == "ssd":
        y, state, conv = mamba2_decode(
            p["mix"], rms_norm(x1, p["n1"], eps), cache["state"],
            cache["conv"], n_heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
            d_state=cfg.ssm_state, norm_eps=eps)
        return x1 + y, {"state": state, "conv": conv}
    if bt == "rglru":
        y, state, conv = rglru_decode(p["mix"], rms_norm(x1, p["n1"], eps),
                                      cache["state"], cache["conv"])
        x1 = x1 + y
        x1 = x1 + ffn.swiglu(p["mlp"], rms_norm(x1, p["n2"], eps))
        return x1, {"state": state, "conv": conv}
    if bt == "cross":
        y = attn.decode_cross_attn(p["xattn"], rms_norm(x1, p["n1"], eps),
                                   cache["mk"], cache["mv"],
                                   n_kv=cfg.n_kv_heads)
        x1 = x1 + jnp.tanh(p["gate"]).astype(x1.dtype) * y
        x1 = x1 + ffn.swiglu(p["mlp"], rms_norm(x1, p["n2"], eps))
        return x1, cache
    if bt == "selfcross":
        layout = attn.KVLayout(cache["k"].shape[1], ctx.seq_axes)
        y, k, v = attn.decode_attn(p["attn"], rms_norm(x1, p["n1"], eps),
                                   cache["k"], cache["v"], pos,
                                   n_kv=cfg.n_kv_heads, theta=cfg.rope_theta,
                                   layout=layout, rope=False)
        x1 = x1 + y
        y = attn.decode_cross_attn(p["xattn"], rms_norm(x1, p["n2"], eps),
                                   cache["mk"], cache["mv"],
                                   n_kv=cfg.n_kv_heads)
        x1 = x1 + y
        x1 = x1 + ffn.gelu_mlp(p["mlp"], rms_norm(x1, p["n3"], eps))
        return x1, {"k": k, "v": v, "mk": cache["mk"], "mv": cache["mv"]}
    if bt in ("mla", "mla_moe"):
        y, c, pe = attn.mla_decode(p["attn"], rms_norm(x1, p["n1"], eps),
                                   cache["ckv"], cache["kpe"], pos,
                                   nope=cfg.qk_nope_head_dim,
                                   rope=cfg.qk_rope_head_dim,
                                   theta=cfg.rope_theta)
        x1 = x1 + y
        if bt == "mla":
            x1 = x1 + ffn.swiglu(p["mlp"], rms_norm(x1, p["n2"], eps))
        else:
            x1 = x1 + _sigmoid_moe(p, x1, cfg)[0]
        return x1, {"ckv": c, "kpe": pe}
    # global / local / moe
    window = cfg.window if bt == "local" else None
    seq_axes = None if bt == "local" else ctx.seq_axes
    layout = attn.KVLayout(cache["k"].shape[1], seq_axes)
    y, k, v = attn.decode_attn(p["attn"], rms_norm(x1, p["n1"], eps),
                               cache["k"], cache["v"], pos,
                               n_kv=cfg.n_kv_heads, theta=cfg.rope_theta,
                               layout=layout, window=window)
    x1 = x1 + y
    if bt == "moe":
        y, _ = ffn.moe_apply(p["moe"], rms_norm(x1, p["n2"], eps),
                             top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor)
        return x1 + y, {"k": k, "v": v}
    x1 = x1 + ffn.swiglu(p["mlp"], rms_norm(x1, p["n2"], eps))
    return x1, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# cache specs (global shapes; the launcher shards them)
# ---------------------------------------------------------------------------

def cache_entry_shape(bt: str, cfg: ArchConfig, batch: int, seq_len: int
                      ) -> dict:
    """Global-shape ShapeDtypeStructs for one block's decode cache."""
    sds = jax.ShapeDtypeStruct
    kv, hd, m = cfg.n_kv_heads, cfg.hd, cfg.frontend_tokens
    if bt == "ssd":
        return {"state": sds((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
                "conv": sds((batch, cfg.ssm_conv - 1,
                             cfg.ssm_heads * cfg.ssm_head_dim
                             + 2 * cfg.ssm_state), COMPUTE_DTYPE)}
    if bt == "rglru":
        rnn = cfg.rnn_width or cfg.d_model
        return {"state": sds((batch, rnn), jnp.float32),
                "conv": sds((batch, 3, rnn), COMPUTE_DTYPE)}
    if bt == "cross":
        return {"mk": sds((batch, m, kv, hd), COMPUTE_DTYPE),
                "mv": sds((batch, m, kv, hd), COMPUTE_DTYPE)}
    if bt == "selfcross":
        return {"k": sds((batch, seq_len, kv, hd), COMPUTE_DTYPE),
                "v": sds((batch, seq_len, kv, hd), COMPUTE_DTYPE),
                "mk": sds((batch, m, kv, hd), COMPUTE_DTYPE),
                "mv": sds((batch, m, kv, hd), COMPUTE_DTYPE)}
    if bt in ("mla", "mla_moe"):
        return {"ckv": sds((batch, seq_len, cfg.kv_lora_rank), COMPUTE_DTYPE),
                "kpe": sds((batch, seq_len, cfg.qk_rope_head_dim),
                           COMPUTE_DTYPE)}
    if bt == "local":
        w = min(cfg.window, seq_len)
        return {"k": sds((batch, w, kv, hd), COMPUTE_DTYPE),
                "v": sds((batch, w, kv, hd), COMPUTE_DTYPE)}
    return {"k": sds((batch, seq_len, kv, hd), COMPUTE_DTYPE),
            "v": sds((batch, seq_len, kv, hd), COMPUTE_DTYPE)}
