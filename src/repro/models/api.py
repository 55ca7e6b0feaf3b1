"""Uniform model API over the 10-arch zoo.

  init_params(rng, cfg)                 -> params pytree (bf16 leaves)
  train_loss(params, cfg, batch)        -> (loss, metrics)
  prefill(params, cfg, batch)           -> (last_logits, cache)
  decode_step(params, cfg, token, pos, cache, seq_axes) -> (logits, cache)
  cache_specs(cfg, batch, seq_len)      -> pytree of ShapeDtypeStruct
  input_specs(cfg, shape)               -> dict of ShapeDtypeStruct

The stack scans over repeats of each pattern unit (scan-over-layers), with
`jax.checkpoint` on the train body (remat). Params for a pattern group are
{"s{i}": stacked leaves} per slot i of the unit.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import blocks
from repro.models.blocks import Ctx
from repro.models.common import (COMPUTE_DTYPE, dense_init, rms_norm,
                                 rms_norm_init, sinusoidal_positions,
                                 stack_layers)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ArchConfig) -> dict:
    keys = jax.random.split(rng, 8)
    # std = d^-0.5 keeps both the (sqrt(d)-scaled) input embeddings and the
    # tied-unembed logits at unit variance from step 0
    p: dict = {"embed": dense_init(keys[0], (cfg.vocab_padded, cfg.d_model),
                                   fan_in=cfg.d_model)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], (cfg.vocab_padded, cfg.d_model),
                                  fan_in=cfg.d_model)
    p["final_norm"] = rms_norm_init(cfg.d_model)

    groups = []
    for gi, (pat, n) in enumerate(cfg.pattern_groups):
        g = {}
        for si, bt in enumerate(pat):
            kg = jax.random.fold_in(keys[2], gi * 16 + si)
            g[f"s{si}"] = stack_layers(
                lambda k, _bt=bt: blocks.init_block(k, _bt, cfg), kg, n)
        groups.append(g)
    p["groups"] = tuple(groups)

    if cfg.family == "vlm":
        p["mproj"] = dense_init(keys[3], (cfg.d_model, cfg.d_model))
    if cfg.is_encdec:
        p["enc"] = {
            "blocks": stack_layers(
                lambda k: blocks.init_block(k, "bidir", cfg), keys[4],
                cfg.enc_layers),
            "norm": rms_norm_init(cfg.d_model),
        }
    return p


# ---------------------------------------------------------------------------
# stack drivers
# ---------------------------------------------------------------------------

def _scan_train(params: dict, cfg: ArchConfig, x, ctx: Ctx, remat: bool):
    """Returns (x, summed balance term, routing stats): per pattern group
    a dict by slot of the sigmoid-routed MoE blocks' stats, stacked over
    the group's repeats (empty for every other block)."""
    aux = jnp.zeros((), jnp.float32)
    stats = []
    for gi, ((pat, n), gp) in enumerate(zip(cfg.pattern_groups,
                                            params["groups"])):
        def body(carry, pslice, _pat=pat, _gi=gi):
            h, a = carry
            pslice = ctx.gather("groups", _gi, pslice)
            ys = {}
            for i, bt in enumerate(_pat):
                h, ai, st = blocks.block_train_stats(pslice[f"s{i}"], bt, h,
                                                     ctx)
                a = a + ai
                if st is not None:
                    ys[f"s{i}"] = st
            return (h, a), ys
        if remat:
            body = jax.checkpoint(body)
        (x, aux), ys = jax.lax.scan(body, (x, aux), gp)
        stats.append(ys)
    return x, aux, tuple(stats)


def _scan_prefill(params: dict, cfg: ArchConfig, x, ctx: Ctx):
    cache = []
    for gi, ((pat, n), gp) in enumerate(zip(cfg.pattern_groups,
                                            params["groups"])):
        def body(h, pslice, _pat=pat, _gi=gi):
            pslice = ctx.gather("groups", _gi, pslice)
            entries = {}
            for i, bt in enumerate(_pat):
                h, c = blocks.block_prefill(pslice[f"s{i}"], bt, h, ctx)
                entries[f"s{i}"] = c
            return h, entries
        x, gc = jax.lax.scan(body, x, gp)
        cache.append(gc)
    return x, tuple(cache)


def _scan_decode(params: dict, cfg: ArchConfig, x1, cache, pos, ctx: Ctx):
    new_cache = []
    for gi, ((pat, n), gp, gc) in enumerate(zip(cfg.pattern_groups,
                                                params["groups"], cache)):
        def body(h, xs, _pat=pat, _gi=gi):
            pslice, cslice = xs
            pslice = ctx.gather("groups", _gi, pslice)
            entries = {}
            for i, bt in enumerate(_pat):
                h, c = blocks.block_decode(pslice[f"s{i}"], bt, h,
                                           cslice[f"s{i}"], pos, ctx)
                entries[f"s{i}"] = c
            return h, entries
        x1, ngc = jax.lax.scan(body, x1, (gp, gc))
        new_cache.append(ngc)
    return x1, tuple(new_cache)


# ---------------------------------------------------------------------------
# embeddings / memory / logits
# ---------------------------------------------------------------------------

def _embed(params: dict, cfg: ArchConfig, tokens: jax.Array,
           positions: jax.Array) -> jax.Array:
    x = params["embed"][tokens].astype(COMPUTE_DTYPE)
    x = x * jnp.asarray(cfg.d_model ** 0.5, COMPUTE_DTYPE)
    if cfg.is_encdec:   # whisper decoder: absolute sinusoidal positions
        pe = sinusoidal_positions(int(positions.shape[-1]), cfg.d_model) \
            if positions.ndim == 1 else None
        if pe is not None:
            x = x + pe.astype(COMPUTE_DTYPE)
    return x


def _decode_embed(params: dict, cfg: ArchConfig, token: jax.Array,
                  pos: jax.Array) -> jax.Array:
    x = params["embed"][token][:, None, :].astype(COMPUTE_DTYPE)
    x = x * jnp.asarray(cfg.d_model ** 0.5, COMPUTE_DTYPE)
    if cfg.is_encdec:
        d = cfg.d_model
        inv = 1.0 / (10_000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        ang = pos.astype(jnp.float32) * inv
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])
        x = x + pe.astype(COMPUTE_DTYPE)
    return x


def _memory(params: dict, cfg: ArchConfig, batch: dict,
            param_gather=None) -> jax.Array | None:
    if cfg.family == "vlm":
        return (batch["patches"].astype(COMPUTE_DTYPE)
                @ params["mproj"]).astype(COMPUTE_DTYPE)
    if cfg.is_encdec:
        return encode(params, cfg, batch["frames"], param_gather)
    return None


def encode(params: dict, cfg: ArchConfig, frames: jax.Array,
           param_gather=None) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, M, d)."""
    m = frames.shape[1]
    x = frames.astype(COMPUTE_DTYPE)
    x = x + sinusoidal_positions(m, cfg.d_model).astype(COMPUTE_DTYPE)
    ctx = Ctx(cfg=cfg, positions=jnp.arange(m), param_gather=param_gather)

    def body(h, pslice):
        pslice = ctx.gather("enc", 0, pslice)
        h, _ = blocks.block_train(pslice, "bidir", h, ctx)
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc"]["blocks"])
    return rms_norm(x, params["enc"]["norm"], cfg.norm_eps)


def logits_of(params: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Logits over the PADDED vocab (sharded over 'model'); padded
    columns are masked to -inf so softmax/argmax ignore them."""
    w = params.get("lm_head", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x, w)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype),
                           logits)
    return logits


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _forward(params: dict, cfg: ArchConfig, batch: dict, remat: bool,
             param_gather) -> tuple[jax.Array, jax.Array, tuple]:
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s)
    ctx = Ctx(cfg=cfg, positions=positions, param_gather=param_gather,
              memory=_memory(params, cfg, batch, param_gather))
    x = _embed(params, cfg, tokens, positions)
    x, aux, stats = _scan_train(params, cfg, x, ctx, remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_of(params, cfg, x), aux, stats


def forward(params: dict, cfg: ArchConfig, batch: dict, remat: bool = True,
            param_gather=None) -> tuple[jax.Array, jax.Array]:
    """batch["tokens"]: (B, S) int32 -> (logits (B,S,V), aux)."""
    logits, aux, _ = _forward(params, cfg, batch, remat, param_gather)
    return logits, aux


def has_routing_stats(cfg: ArchConfig) -> bool:
    """True if the model has sigmoid-routed MoE blocks, whose routing
    stats ``train_loss`` returns under ``"moe"``."""
    return any(bt == "mla_moe" for pat, _ in cfg.pattern_groups
               for bt in pat)


def train_loss(params: dict, cfg: ArchConfig, batch: dict,
               remat: bool = True, param_gather=None
               ) -> tuple[jax.Array, dict]:
    """batch["tokens"]: (B, S+1) -> next-token cross-entropy (+ MoE aux);
    with sigmoid-routed MoE blocks the metrics carry their routing stats
    (``_scan_train``) under ``"moe"``."""
    tokens = batch["tokens"]
    inp = {**batch, "tokens": tokens[:, :-1]}
    tgt = tokens[:, 1:]
    logits, aux, stats = _forward(params, cfg, inp, remat, param_gather)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tl = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - tl.astype(jnp.float32))
    loss = ce + cfg.aux_coef * aux
    metrics = {"ce": ce, "aux": aux}
    if has_routing_stats(cfg):
        metrics["moe"] = stats
    return loss, metrics


def prefill(params: dict, cfg: ArchConfig, batch: dict, param_gather=None
            ) -> tuple[jax.Array, tuple]:
    """Build the decode cache; returns (last-position logits, cache)."""
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s)
    ctx = Ctx(cfg=cfg, positions=positions, param_gather=param_gather,
              memory=_memory(params, cfg, batch, param_gather))
    x = _embed(params, cfg, tokens, positions)
    x, cache = _scan_prefill(params, cfg, x, ctx)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return logits_of(params, cfg, x)[:, 0], cache


def decode_step(params: dict, cfg: ArchConfig, token: jax.Array,
                pos: jax.Array, cache: tuple,
                seq_axes: tuple | None = None, param_gather=None
                ) -> tuple[jax.Array, tuple]:
    """token: (B,) int32, pos: scalar int32 -> (logits (B,V), cache)."""
    ctx = Ctx(cfg=cfg, seq_axes=seq_axes, param_gather=param_gather)
    x1 = _decode_embed(params, cfg, token, pos)
    x1, cache = _scan_decode(params, cfg, x1, cache, pos, ctx)
    x1 = rms_norm(x1, params["final_norm"], cfg.norm_eps)
    return logits_of(params, cfg, x1)[:, 0], cache


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, batch: int, seq_len: int) -> tuple:
    """Global-shape cache pytree (stacked per pattern group)."""
    out = []
    for pat, n in cfg.pattern_groups:
        g = {}
        for i, bt in enumerate(pat):
            entry = blocks.cache_entry_shape(bt, cfg, batch, seq_len)
            g[f"s{i}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype),
                entry)
        out.append(g)
    return tuple(out)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    sds = jax.ShapeDtypeStruct
    b, s = shape.global_batch, shape.seq_len
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = sds((b, cfg.frontend_tokens, cfg.d_model),
                               COMPUTE_DTYPE)
    if cfg.is_encdec:
        extra["frames"] = sds((b, cfg.frontend_tokens, cfg.d_model),
                              COMPUTE_DTYPE)
    if shape.kind == "train":
        return {"tokens": sds((b, s + 1), jnp.int32), **extra}
    if shape.kind == "prefill":
        return {"tokens": sds((b, s), jnp.int32), **extra}
    # decode: one new token against a seq_len cache
    return {"token": sds((b,), jnp.int32),
            "pos": sds((), jnp.int32),
            "cache": cache_specs(cfg, b, s)}


# ---------------------------------------------------------------------------
# parameter counting (for MODEL_FLOPS = 6*N*D)
# ---------------------------------------------------------------------------

def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0))
    total = expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if any(getattr(k, "key", None) == "experts" for k in path):
            expert += n
    if active_only and cfg.n_experts:
        total -= expert * (1 - cfg.top_k / cfg.n_experts)
    return int(total)
