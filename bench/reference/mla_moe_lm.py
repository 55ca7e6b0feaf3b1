"""Plain float32 reference of the MLA + sigmoid-routed MoE training cell.

One dense MLA layer and then MLA MoE layers (DeepSeek-V3's block, as
``moonlight-16b-a3b`` has them), their next-token cross-entropy with the
sequence-wise balance term, global-norm clipping, AdamW and the load
rule of the selection bias, written out in ``jax.numpy`` at ``highest``
matmul precision with no kernels, no sharding, no cache and no grouped
product: every held expert runs on every token, times its gate, which is
zero where the token did not choose it. Data parallelism and the INC wire
format are ``dense_lm``'s: each rank's gradient, rounded half to even to
the fixed point, summed over the ranks.

The layer, as the configuration states it:

  MLA      q = h W_q split per head into q_nope and q_pe; [c, k_pe] =
           h W_kv_a, c RMS-normed; [k_nope, v] = c W_kv_b per head; rotary
           on q_pe and on k_pe, which every head shares; softmax of
           [q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope), causal;
           W_o over the heads' values.
  MoE      s = sigmoid(h W_r) over all router outputs; gates of the k
           chosen g_i = scale s_i / sum s_j; y = shared SwiGLU(h) + sum of
           g_i SwiGLU_i(h) over the chosen experts held here.
  balance  alpha x the mean over sequences of sum_i f_i P_i (f_i: E/(k S)
           x the slots routed to i; P_i: the mean of s_i / sum_j s_j).
  bias     b += gamma sign(mean load - load) after each step, loads
           counted over the whole batch; never touched by AdamW.

It draws weights and tokens again from the seeds, by a copy of the
program's initializer (experts by their global index) and Zipf token
generator, and follows the program's conventions where they depart from
the published model (``departures`` in the configuration). One thing it
takes from the program: which experts each token chose, per checked step
and layer (``ids``). Near ties at the top-k boundary flip on bfloat16
rounding, and with random weights one flip moves an expert's gradient by
a whole token's share. Everything else it computes itself: scores, gates,
the balance term, loads and the bias. It also makes its own choice on its
own scores and bias, and reports the share of token slots where that
choice differs from the program's (``route_mismatch``).

``control=True`` rounds every matmul operand to float8 e4m3, as
``dense_lm`` does.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from reference.dense_lm import (SAT_MAX, _mm, _rms, _rope, _wire_add,
                                _wire_peak, _wire_round, adamw, delta_norms,
                                leaf_names, leaf_norms, lr_at)


# -- initializer: the program's draws, in its order ----------------------

def _normal(key, shape, fan_in):
    w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    return w.astype(jnp.bfloat16)


def _swiglu_init(key, d, ff):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w1": _normal(k1, (d, ff), d), "w3": _normal(k2, (d, ff), d),
            "w2": _normal(k3, (ff, d), ff)}


def _init_block(key, a: dict, moe: bool):
    d, h = a["hidden_size"], a["num_attention_heads"]
    rank, nope = a["kv_lora_rank"], a["qk_nope_head_dim"]
    rope, vd = a["qk_rope_head_dim"], a["v_head_dim"]
    ks = jax.random.split(key, 3)
    ka = jax.random.split(ks[0], 4)
    p = {"n1": jnp.zeros((d,), jnp.float32),
         "n2": jnp.zeros((d,), jnp.float32),
         "attn": {"wq": _normal(ka[0], (d, h, nope + rope), d),
                  "wkv_a": _normal(ka[1], (d, rank + rope), d),
                  "kv_norm": jnp.zeros((rank,), jnp.float32),
                  "wkv_b": _normal(ka[2], (rank, h, nope + vd), rank),
                  "wo": _normal(ka[3], (h, vd, d), h * vd)}}
    if not moe:
        p["mlp"] = _swiglu_init(ks[1], d, a["intermediate_size"])
        return p
    ff, held = a["moe_intermediate_size"], a["n_routed_experts"]
    e = held * a["expert_parallel"]
    kr, ke, ksh = jax.random.split(ks[1], 3)
    k1, k2, k3 = jax.random.split(ke, 3)
    ids = a["expert_rank"] * held + jnp.arange(held)

    def experts(k, shape, fan_in):
        return jax.vmap(lambda i: _normal(jax.random.fold_in(k, i), shape,
                                          fan_in))(ids)
    p["moe"] = {"router": _normal(kr, (d, e), d).astype(jnp.float32),
                "router_bias": jnp.zeros((e,), jnp.float32),
                "experts": {"w1": experts(k1, (d, ff), d),
                            "w3": experts(k2, (d, ff), d),
                            "w2": experts(k3, (ff, d), ff)},
                "shared": _swiglu_init(ksh, d, a["n_shared_experts"] * ff)}
    return p


def init_params(seed: int, a: dict) -> dict:
    """The initial weights in the type they are served in, as nested dicts
    named like the program's: group 0 the dense layers, group 1 the MoE
    layers, each stacked over its layers."""
    keys = jax.random.split(jax.random.key(seed), 8)
    d, v = a["hidden_size"], a["vocab_size"]
    n_dense = a["first_k_dense_replace"]
    groups = []
    for gi, (n, moe) in enumerate(((n_dense, False),
                                   (a["num_hidden_layers"] - n_dense, True))):
        kg = jax.random.fold_in(keys[2], gi * 16)
        lkeys = jax.vmap(lambda i: jax.random.fold_in(kg, i))(jnp.arange(n))
        groups.append({"s0": jax.vmap(
            lambda k, _m=moe: _init_block(k, a, _m))(lkeys)})
    return {"embed": _normal(keys[0], (v, d), d),
            "lm_head": _normal(keys[1], (v, d), d),
            "final_norm": jnp.zeros((d,), jnp.float32),
            "groups": tuple(groups)}


def tokens(seed: int, step: int, batch: int, seq: int, vocab: int,
           zipf_a: float):
    """The Zipf token rows of one step: (batch, seq + 1) int32."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -zipf_a
    cdf = jnp.asarray((np.cumsum(w) / w.sum()).astype(np.float32))
    key = jax.random.fold_in(jax.random.key(seed), step)
    u = jax.random.uniform(key, (batch, seq + 1), jnp.float32)
    ids = jnp.searchsorted(cdf, u, side="right")
    return jnp.minimum(ids, vocab - 1).astype(jnp.int32)


# -- forward ---------------------------------------------------------------

def _attention(q, k, v, q_chunk, control):
    """Causal softmax attention, one query chunk at a time (each chunk
    recomputed in the backward pass); q/k and v head sizes may differ."""
    b, s, h, hq = q.shape

    @jax.checkpoint
    def chunk(qc, start):
        sc = _mm("bqhk,bshk->bhqs", qc, k, control) * hq ** -0.5
        qpos = start + jnp.arange(qc.shape[1])
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        return _mm("bhqs,bshk->bqhk", jax.nn.softmax(sc, axis=-1), v,
                   control)

    n = s // q_chunk
    qs = q.reshape(b, n, q_chunk, h, hq).swapaxes(0, 1)
    _, out = jax.lax.scan(lambda c, xs: (c, chunk(*xs)), None,
                          (qs, jnp.arange(n) * q_chunk))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def _mla(h, p, a, q_chunk, control):
    nope, rope = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    theta = a["rope_theta"]
    q = _mm("bsd,dhk->bshk", h, p["wq"], control)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = _mm("bsd,dc->bsc", h, p["wkv_a"], control)
    c = _rms(kv[..., :-rope], p["kv_norm"], a["latent_norm_eps"])
    k_pe = _rope(kv[..., -rope:][:, :, None, :], theta)
    kvb = _mm("bsc,chk->bshk", c, p["wkv_b"], control)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe, kvb.shape[:3] + (rope,))], -1)
    o = _attention(q, k, kvb[..., nope:], q_chunk, control)
    return _mm("bshk,hkd->bsd", o, p["wo"], control)


def _swiglu(h, m, control):
    g = jax.nn.silu(_mm("bsd,df->bsf", h, m["w1"], control)) \
        * _mm("bsd,df->bsf", h, m["w3"], control)
    return _mm("bsf,fd->bsd", g, m["w2"], control)


def _moe(h, p, ids, a, control):
    """h (b,s,d), ids (b,s,k) the program's choice -> (y, balance term,
    slots where this reference's own choice differs)."""
    k, held = a["num_experts_per_tok"], a["n_routed_experts"]
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", h, p["router"], control))
    e = scores.shape[-1]
    _, own = jax.lax.top_k(scores + p["router_bias"], k)
    differ = jnp.sum(~jnp.any(ids[..., :, None] == own[..., None, :], -1))
    sel = jnp.take_along_axis(scores, ids, axis=-1)
    gates = a["routed_scaling_factor"] * sel / jnp.sum(sel, -1,
                                                       keepdims=True)
    local = ids - a["expert_rank"] * held
    # gate of each held expert per token: zero where it was not chosen
    g_held = jnp.sum(jnp.where(local[..., None] == jnp.arange(held),
                               gates[..., None], 0.0), axis=-2)

    @jax.checkpoint
    def expert(acc, xs):
        w, g = xs
        return acc + g[..., None] * _swiglu(h, w, control), None

    y, _ = jax.lax.scan(expert, _swiglu(h, p["shared"], control),
                        (p["experts"], jnp.moveaxis(g_held, -1, 0)))
    s = scores.shape[1]
    f = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), (1, 2)) \
        * (e / (k * s))
    prob = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    return y, jnp.mean(jnp.sum(f * prob, -1)), differ


def _block(x, p, a, q_chunk, control, ids=None):
    eps = a["rms_norm_eps"]
    x = x + _mla(_rms(x, p["n1"], eps), p["attn"], a, q_chunk, control)
    h = _rms(x, p["n2"], eps)
    if ids is None:
        return x + _swiglu(h, p["mlp"], control), 0.0, 0
    y, bal, differ = _moe(h, p["moe"], ids, a, control)
    return x + y, bal, differ


def loss_fn(w: dict, toks, ids, a: dict, q_chunk: int, row_chunk: int,
            control: bool = False):
    """Mean next-token cross-entropy of (batch, seq + 1) token rows plus
    the balance terms; ids (layers, batch, seq, k) of the MoE layers.
    Returns (loss, slots where the own choice differs)."""
    inp, tgt = toks[:, :-1], toks[:, 1:]
    x = w["embed"][inp] * jnp.float32(a["hidden_size"] ** 0.5)
    dense = jax.checkpoint(partial(_block, a=a, q_chunk=q_chunk,
                                   control=control))
    x, _ = jax.lax.scan(lambda h, p: (dense(h, p)[0], None), x,
                        w["groups"][0]["s0"])
    moe = jax.checkpoint(partial(_block, a=a, q_chunk=q_chunk,
                                 control=control))

    def moe_layer(h, xs):
        p, layer_ids = xs
        h, bal, differ = moe(h, p, ids=layer_ids)
        return h, (bal, differ)

    x, (bal, differ) = jax.lax.scan(moe_layer, x,
                                    (w["groups"][1]["s0"], ids))
    x = _rms(x, w["final_norm"], a["rms_norm_eps"])
    d = x.shape[-1]
    xs = x.reshape(-1, row_chunk, d)
    ts = tgt.reshape(-1, row_chunk)

    @jax.checkpoint
    def ce(xc, tc):
        logits = _mm("td,vd->tv", xc, w["lm_head"], control)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - tl)

    _, sums = jax.lax.scan(lambda c, xs_: (c, ce(*xs_)), None, (xs, ts))
    loss = jnp.sum(sums) / tgt.size + a["aux_coef"] * jnp.sum(bal)
    return loss, jnp.sum(differ)


# -- the selection bias ------------------------------------------------------

def bias_step(b, ids, n_experts: int, rate: float):
    """b (layers, E) after one step of the load rule; ids (layers, ...)
    of every token of the step."""
    load = jax.vmap(lambda i: jnp.bincount(i.reshape(-1), length=n_experts)
                    )(ids).astype(jnp.float32)
    return b + rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)


# -- the reference run ---------------------------------------------------

def run(a: dict, o: dict, *, weight_seed: int, data_seed: int, batch: int,
        seq: int, micro: int, wire_precision: int, ids, steps: int = 3,
        q_chunk: int = 512, row_chunk: int = 512, zipf_a: float = 1.0,
        control: bool = False) -> dict:
    """``steps`` training steps from the seeds, ``micro`` rows per
    data-parallel rank; ``ids[s]`` is the program's choice at step ``s``,
    (batch, MoE layers, seq, k). Returns each step's loss, the per-leaf
    norms of the first clipped gradient, the per-leaf norms of the
    parameters' change after the last step, and ``route_mismatch``."""
    lossgrad = jax.jit(jax.value_and_grad(partial(
        loss_fn, a=a, q_chunk=min(q_chunk, seq),
        row_chunk=min(row_chunk, micro * seq), control=control),
        has_aux=True))
    f32 = jax.jit(lambda t: jax.tree.map(lambda l: l.astype(jnp.float32), t))
    w = f32(init_params(weight_seed, a))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    step_fn = jax.jit(partial(adamw, o=o), donate_argnums=(0, 1, 2))
    scale_fn = jax.jit(lambda g, c: jax.tree.map(lambda l: l * c, g),
                       donate_argnums=(0,))
    n_router = a["n_routed_experts"] * a["expert_parallel"]
    wire = jnp.float32(10.0 ** wire_precision)
    losses, g0, differ, slots = [], None, 0, 0
    for s in range(steps):
        rows = tokens(data_seed, s, batch, seq, a["vocab_size"], zipf_a)
        step_ids = jnp.moveaxis(jnp.asarray(ids[s]), 1, 0)  # (L,B,S,k)
        n = batch // micro
        loss, g = 0.0, None
        for i in range(n):
            sl = slice(i * micro, (i + 1) * micro)
            (li, di), gi = lossgrad(w, rows[sl], step_ids[:, sl])
            loss += float(li)
            differ += int(di)
            if float(_wire_peak(gi, wire)) > SAT_MAX:
                raise RuntimeError("a gradient element leaves the fixed-"
                                   "point range; the reference does not "
                                   "model the fp32 fallback")
            g = (_wire_round(gi, wire) if g is None
                 else _wire_add(g, gi, wire))
            del gi
        slots += int(step_ids.size)
        g = scale_fn(g, jnp.float32(1.0) / wire / n)
        gnorm = float(np.sqrt(sum(x * x for x in leaf_norms(g))))
        g = scale_fn(g, jnp.float32(min(1.0, o["grad_clip"]
                                        / max(gnorm, 1e-12))))
        if s == 0:
            g0 = leaf_norms(g)
        losses.append(loss / n)
        bias = bias_step(w["groups"][1]["s0"]["moe"]["router_bias"],
                         step_ids, n_router, a["bias_update_rate"])
        w, m, v = step_fn(w, m, v, g, jnp.float32(lr_at(o, s)),
                          jnp.float32(s + 1))
        # the bias moves by its rule alone, not by AdamW's weight decay
        w["groups"][1]["s0"]["moe"]["router_bias"] = bias
        del g
    del m, v
    w0 = f32(init_params(weight_seed, a))
    delta = delta_norms(w, w0)
    return {"losses": losses, "grad_norms": g0, "delta_norms": delta,
            "route_mismatch": differ / max(slots, 1),
            "leaves": leaf_names(w)}
