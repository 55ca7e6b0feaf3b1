"""Plain float32 reference of the data-parallel training cells.

A dense pre-norm decoder (the configuration's widths), its next-token
cross-entropy, global-norm clipping and AdamW, written out in
``jax.numpy`` at ``highest`` matmul precision with no kernels, no
sharding and no collectives. Data parallelism is the mean over the
global batch, each data-parallel rank's gradient (the mean over its own
rows) put through the INC wire format the configuration states before
the ranks are summed: rounded half to even to the fixed point at
10**precision, so that an element under half a step is zero, as it is
on the ring. Adam turns such a zero into no update at all, where a tiny
non-zero gradient would move the weight by a whole step.

It imports nothing of the program and takes nothing the program made.
Weights and tokens are drawn again from the same seeds by a copy of the
program's initializer and uniform token generator (the same
``jax.random`` calls in the same order), so that both start from one
point. The block follows the program's conventions where they depart
from the published model (listed under ``departures`` in the
configuration): RMSNorm scaled by ``1 + w``, rotary over the whole head,
no q/k/v bias, embeddings scaled by ``sqrt(d)``.

``control=True`` runs the same steps with every matmul operand rounded
to float8 e4m3 under a per-tensor scale (gradients straight through):
the step below the bfloat16 that the configuration states, used to show
that the comparison fails it.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# -- initializer: the program's draws, in its order ----------------------

def _normal(key, shape, fan_in):
    w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    return w.astype(jnp.bfloat16)


def _init_layer(key, a: dict):
    d, h, kv, hd, ff = (a["hidden_size"], a["num_attention_heads"],
                        a["num_key_value_heads"], a["head_dim"],
                        a["intermediate_size"])
    ks = jax.random.split(key, 3)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    return {
        "n1": jnp.zeros((d,), jnp.float32),
        "n2": jnp.zeros((d,), jnp.float32),
        "attn": {"wq": _normal(ka[0], (d, h, hd), d),
                 "wk": _normal(ka[1], (d, kv, hd), d),
                 "wv": _normal(ka[2], (d, kv, hd), d),
                 "wo": _normal(ka[3], (h, hd, d), h * hd)},
        "mlp": {"w1": _normal(km[0], (d, ff), d),
                "w3": _normal(km[1], (d, ff), d),
                "w2": _normal(km[2], (ff, d), ff)},
    }


def init_params(seed: int, a: dict) -> dict:
    """The initial weights in the type they are served in (bf16 matrices,
    fp32 norm scales), as nested dicts named like the program's."""
    keys = jax.random.split(jax.random.key(seed), 8)
    d, v, n = a["hidden_size"], a["vocab_size"], a["num_hidden_layers"]
    lkey = jax.random.fold_in(keys[2], 0)
    lkeys = jax.vmap(lambda i: jax.random.fold_in(lkey, i))(jnp.arange(n))
    return {"embed": _normal(keys[0], (v, d), d),
            "lm_head": _normal(keys[1], (v, d), d),
            "final_norm": jnp.zeros((d,), jnp.float32),
            "groups": ({"s0": jax.vmap(lambda k: _init_layer(k, a))(lkeys)},)}


def tokens(seed: int, step: int, batch: int, seq: int, vocab: int):
    """The uniform token rows of one step: (batch, seq + 1) int32."""
    key = jax.random.fold_in(jax.random.key(seed), step)
    return jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)


# -- forward ---------------------------------------------------------------

def _f8(x):
    """Round to float8 e4m3 under a per-tensor scale, back to float32;
    the gradient passes the rounding straight through, as in float8
    training with higher-precision gradients."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / F8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, x, w, control):
    if control:
        x, w = _f8(x), _f8(w)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, q_chunk, control):
    """Causal softmax attention, one query chunk at a time (each chunk
    recomputed in the backward pass, so only its inputs are kept)."""
    b, s, h, hd = q.shape

    @jax.checkpoint
    def chunk(qc, start):
        sc = _mm("bqhk,bshk->bhqs", qc, k, control) * hd ** -0.5
        qpos = start + jnp.arange(qc.shape[1])
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return _mm("bhqs,bshk->bqhk", w, v, control)

    n = s // q_chunk
    qs = q.reshape(b, n, q_chunk, h, hd).swapaxes(0, 1)
    _, out = jax.lax.scan(lambda c, xs: (c, chunk(*xs)), None,
                          (qs, jnp.arange(n) * q_chunk))
    return out.swapaxes(0, 1).reshape(b, s, h, hd)


def _layer(x, p, a, q_chunk, control):
    eps, theta = a["rms_norm_eps"], a["rope_theta"]
    h = _rms(x, p["n1"], eps)
    q = _rope(_mm("bsd,dhk->bshk", h, p["attn"]["wq"], control), theta)
    k = _rope(_mm("bsd,dhk->bshk", h, p["attn"]["wk"], control), theta)
    v = _mm("bsd,dhk->bshk", h, p["attn"]["wv"], control)
    o = _attention(q, k, v, q_chunk, control)
    x = x + _mm("bshk,hkd->bsd", o, p["attn"]["wo"], control)
    h = _rms(x, p["n2"], eps)
    m = p["mlp"]
    g = jax.nn.silu(_mm("bsd,df->bsf", h, m["w1"], control)) \
        * _mm("bsd,df->bsf", h, m["w3"], control)
    return x + _mm("bsf,fd->bsd", g, m["w2"], control)


def loss_fn(w: dict, toks, a: dict, q_chunk: int, row_chunk: int,
            control: bool = False):
    """Mean next-token cross-entropy of (batch, seq + 1) token rows."""
    inp, tgt = toks[:, :-1], toks[:, 1:]
    x = w["embed"][inp] * jnp.float32(a["hidden_size"] ** 0.5)
    layer = jax.checkpoint(partial(_layer, a=a, q_chunk=q_chunk,
                                   control=control))
    x, _ = jax.lax.scan(lambda h, p: (layer(h, p), None), x,
                        w["groups"][0]["s0"])
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
    return jnp.sum(sums) / tgt.size


# -- optimizer ---------------------------------------------------------------

def lr_at(o: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    s = float(step)
    if s < o["warmup_steps"]:
        return o["lr"] * s / max(o["warmup_steps"], 1)
    prog = min(max((s - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    cos = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (
        1 + np.cos(np.pi * prog))
    return o["lr"] * cos


def adamw(w, m, v, g, lr, t, o: dict):
    """One AdamW step on every leaf at learning rate ``lr`` and step
    count ``t`` (from 1); weight decay on leaves of two or more dimensions
    (the layer-stacked norm scales among them)."""
    b1, b2 = o["b1"], o["b2"]

    def one(wl, ml, vl, gl):
        ml = b1 * ml + (1 - b1) * gl
        vl = b2 * vl + (1 - b2) * jnp.square(gl)
        upd = (ml / (1 - b1 ** t)) / (jnp.sqrt(vl / (1 - b2 ** t)) + o["eps"])
        if wl.ndim >= 2:
            upd = upd + o["weight_decay"] * wl
        return wl - lr * upd, ml, vl

    out = jax.tree.map(one, w, m, v, g)
    return jax.tree.transpose(jax.tree.structure(w),
                              jax.tree.structure((0, 0, 0)), out)


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda l: jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)))), tree)


@jax.jit
def _delta_norms(x, y):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), x, y)


def leaf_norms(tree) -> list[float]:
    """Euclidean norm of every leaf, in tree order."""
    return [float(x) for x in jax.tree.leaves(_norms(tree))]


def delta_norms(x, y) -> list[float]:
    """Per-leaf norm of ``x - y``, in tree order."""
    return [float(z) for z in jax.tree.leaves(_delta_norms(x, y))]


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


# -- the reference run ---------------------------------------------------

SAT_MAX = 2.0 ** 31 - 2


@partial(jax.jit, donate_argnums=(0,))
def _wire_round(g, scale):
    """The first rank's gradient, rounded to the fixed point, in its own
    buffer."""
    return jax.tree.map(lambda x: jnp.round(x * scale), g)


@partial(jax.jit, donate_argnums=(0,))
def _wire_add(acc, g, scale):
    """Add one rank's gradient, rounded to the fixed point, to the sum."""
    return jax.tree.map(lambda a, x: a + jnp.round(x * scale), acc, g)


@jax.jit
def _wire_peak(g, scale):
    return jnp.max(jnp.stack([jnp.max(jnp.abs(x)) for x in
                              jax.tree.leaves(g)])) * scale


def run(a: dict, o: dict, *, weight_seed: int, data_seed: int, batch: int,
        seq: int, micro: int, wire_precision: int, steps: int = 3,
        q_chunk: int = 512, row_chunk: int = 512,
        control: bool = False) -> dict:
    """``steps`` training steps from the seeds, ``micro`` rows per
    data-parallel rank. Returns each step's loss, the per-leaf norms of
    the first clipped gradient, and the per-leaf norms of the parameters'
    change after the last step."""
    lossgrad = jax.jit(jax.value_and_grad(partial(
        loss_fn, a=a, q_chunk=min(q_chunk, seq),
        row_chunk=min(row_chunk, micro * seq), control=control)))
    f32 = jax.jit(lambda t: jax.tree.map(lambda l: l.astype(jnp.float32), t))
    w = f32(init_params(weight_seed, a))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    step_fn = jax.jit(partial(adamw, o=o), donate_argnums=(0, 1, 2))
    scale_fn = jax.jit(lambda g, c: jax.tree.map(lambda l: l * c, g),
                       donate_argnums=(0,))
    wire = jnp.float32(10.0 ** wire_precision)
    losses, g0 = [], None
    for s in range(steps):
        rows = tokens(data_seed, s, batch, seq, a["vocab_size"])
        n = batch // micro
        # the gradients are summed in place: the weights, Adam's two
        # moments and one gradient are what the chip holds between passes
        loss, g = 0.0, None
        for i in range(n):
            li, gi = lossgrad(w, rows[i * micro:(i + 1) * micro])
            loss += float(li)
            # the ring's overflow fallback never fires at these sizes;
            # where it would, the sum below is not the program's
            if float(_wire_peak(gi, wire)) > SAT_MAX:
                raise RuntimeError("a gradient element leaves the fixed-"
                                   "point range; the reference does not "
                                   "model the fp32 fallback")
            g = (_wire_round(gi, wire) if g is None
                 else _wire_add(g, gi, wire))
            del gi
        g = scale_fn(g, jnp.float32(1.0) / wire / n)
        gnorm = float(np.sqrt(sum(x * x for x in leaf_norms(g))))
        g = scale_fn(g, jnp.float32(min(1.0, o["grad_clip"]
                                        / max(gnorm, 1e-12))))
        if s == 0:
            g0 = leaf_norms(g)
        losses.append(loss / n)
        w, m, v = step_fn(w, m, v, g, jnp.float32(lr_at(o, s)),
                          jnp.float32(s + 1))
        del g
    del m, v
    w0 = f32(init_params(weight_seed, a))
    delta = delta_norms(w, w0)
    return {"losses": losses, "grad_norms": g0, "delta_norms": delta,
            "leaves": leaf_names(w)}
