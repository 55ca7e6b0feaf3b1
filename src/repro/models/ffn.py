"""Feed-forward layers: SwiGLU, GELU MLP, and two MoE layers.

``moe_apply`` is grok's softmax top-k layer with capacity dropping. Its
dispatch is sort-based (static shapes, TPU-friendly): tokens are
replicated top_k times, sorted by expert id, scattered into a per-expert
capacity buffer (overflow tokens dropped — standard GShard semantics), run
through a grouped einsum, and gathered back with router weights. Expert
weights are sharded over the 'model' mesh axis on the d_ff dim (expert
tensor parallelism), so activations stay data-parallel-local and GSPMD
inserts the usual Megatron-style partial-sum all-reduce after w2; every
chip holds a slice of every expert.

``sigmoid_moe`` is DeepSeek-V3's layer (sigmoid scores, a selection bias,
shared experts, no dropped token) for expert-parallel shares: the layer
is told which experts it holds, routes over all of them, and computes
only its own experts' part of the result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.common import dense_init

F32 = jnp.float32


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

def init_swiglu(key, d: int, ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w1": dense_init(k1, (d, ff)),
            "w3": dense_init(k2, (d, ff)),
            "w2": dense_init(k3, (ff, d), fan_in=ff)}


def swiglu(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    return h @ p["w2"]


def init_gelu_mlp(key, d: int, ff: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, (d, ff)), "b1": jnp.zeros((ff,), jnp.float32),
            "w2": dense_init(k2, (ff, d), fan_in=ff),
            "b2": jnp.zeros((d,), jnp.float32)}


def gelu_mlp(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.gelu((x @ p["w1"] + p["b1"]).astype(x.dtype))
    return (h @ p["w2"] + p["b2"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def init_moe(key, d: int, ff: int, n_experts: int) -> dict:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, (d, n_experts)).astype(jnp.float32),
        "experts": {
            "w1": dense_init(k1, (n_experts, d, ff), fan_in=d),
            "w3": dense_init(k2, (n_experts, d, ff), fan_in=d),
            "w2": dense_init(k3, (n_experts, ff, d), fan_in=ff),
        },
    }


def moe_capacity(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = int(n_tokens * top_k / n_experts * capacity_factor)
    return max(8, -(-c // 8) * 8)      # round up to a multiple of 8


def moe_apply(p: dict, x: jax.Array, *, top_k: int,
              capacity_factor: float) -> tuple[jax.Array, jax.Array]:
    """grok's softmax top-k layer with capacity dropping: every chip holds
    a d_ff slice of every expert (no all-to-all, since no chip holds an
    expert whole). For a share of whole experts, as expert parallelism
    places them, see ``sigmoid_moe``.

    x: (B,S,d) -> (y (B,S,d), aux load-balance loss scalar)."""
    b, s, d = x.shape
    e = p["router"].shape[-1]
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ p["router"])          # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)      # (T,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # Switch-style aux loss: E * sum_e f_e * p_e
    counts = jnp.sum(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32),
                     axis=(0, 1))                            # (E,)
    f = counts / (t * top_k)
    pbar = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pbar)

    # sort-based dispatch
    cap = moe_capacity(t, e, top_k, capacity_factor)
    e_flat = expert_ids.reshape(-1)                          # (T*k,)
    g_flat = gate_vals.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t), top_k)
    order = jnp.argsort(e_flat)
    e_s, g_s, tok_s = e_flat[order], g_flat[order], tok_flat[order]
    # rank of each routed token within its expert
    start = jnp.cumsum(jnp.bincount(e_s, length=e)) - jnp.bincount(e_s,
                                                                   length=e)
    rank = jnp.arange(t * top_k) - start[e_s]
    dest = jnp.where(rank < cap, e_s * cap + rank, e * cap)  # overflow -> bin

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xf[tok_s])
    buf = buf[:-1].reshape(e, cap, d)

    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["experts"]["w1"]))
         * jnp.einsum("ecd,edf->ecf", buf, p["experts"]["w3"]))
    out = jnp.einsum("ecf,efd->ecd", h, p["experts"]["w2"])

    out_flat = out.reshape(e * cap, d)
    out_flat = jnp.concatenate([out_flat, jnp.zeros((1, d), x.dtype)], 0)
    y_s = out_flat[dest] * g_s[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok_s].add(y_s)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# sigmoid-routed MoE with shared experts (DeepSeek-V3 noaux_tc, one group)
# ---------------------------------------------------------------------------
# s = sigmoid(h W_r) over all router outputs (float32); the top k are chosen
# on s + b, where the bias b carries no gradient and is moved by the train
# step from the experts' loads; gates g_i = scale * s_i / sum_topk s_j;
# y = shared(h) + sum over the chosen experts held here of g_i SwiGLU_i(h).
# Every routed row is computed: the held experts' rows are sorted by expert
# and cut into tiles of ``tile`` rows, each tile one expert's, and a loop
# runs as many tiles as the rows need (padding only in each expert's last
# tile). The static bound of that loop is what no routing can exceed.

MOE_TILE_ROWS = 512      # rows of one tile: full MXU passes on TPU

def init_sigmoid_moe(key, d: int, ff: int, n_experts: int, held: int,
                     offset: int, n_shared: int) -> dict:
    """Experts are drawn by their global index, so a share holds exactly
    the experts [offset, offset + held) of the uncut layer."""
    kr, ke, ks = jax.random.split(key, 3)
    k1, k2, k3 = jax.random.split(ke, 3)
    ids = offset + jnp.arange(held)

    def experts(k, shape, fan_in):
        return jax.vmap(lambda e: dense_init(jax.random.fold_in(k, e), shape,
                                             fan_in=fan_in))(ids)
    p = {"router": dense_init(kr, (d, n_experts)).astype(F32),
         "router_bias": jnp.zeros((n_experts,), F32),
         "experts": {"w1": experts(k1, (d, ff), d),
                     "w3": experts(k2, (d, ff), d),
                     "w2": experts(k3, (ff, d), ff)}}
    if n_shared:
        p["shared"] = init_swiglu(ks, d, n_shared * ff)
    return p


def sigmoid_route(p: dict, xf: jax.Array, *, top_k: int, scaling: float):
    """xf: (T,d) -> scores (T,E) f32, chosen ids (T,k) int32, gates (T,k)."""
    scores = jax.nn.sigmoid(jnp.dot(xf.astype(F32), p["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    pick = jax.lax.stop_gradient(scores + p["router_bias"])
    _, ids = jax.lax.top_k(pick, top_k)
    sel = jnp.take_along_axis(scores, ids, axis=-1)
    return scores, ids, scaling * sel / jnp.sum(sel, -1, keepdims=True)


def seq_balance(scores: jax.Array, ids: jax.Array) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance term, before its coefficient:
    the mean over sequences of sum_i f_i P_i, f_i = E / (k S) x the slots
    of the sequence routed to i, P_i = the mean over its tokens of s_i over
    sum_j s_j. scores (B,S,E), ids (B,S,k)."""
    _, s, e = scores.shape
    k = ids.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(ids, e, dtype=F32), axis=(1, 2))
    f = counts * (e / (k * s))
    prob = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(f * prob, axis=-1))


def held_tiles(ids: jax.Array, *, held: int, offset: int, tile: int):
    """Sort the token slots (T*k, slot = token * k + j) routed to experts
    [offset, offset + held) by expert, and cut each expert's rows into
    tiles. Returns the slot order, per tile (expert, first sorted row, end
    of that expert's rows), the number of tiles in use and the rows of
    each held expert."""
    t, k = ids.shape
    local = ids.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    n_tile = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(n_tile)
    row_end = jnp.cumsum(counts)
    max_tiles = -(-(t * min(k, held)) // tile) + held
    j = jnp.arange(max_tiles, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(tile_end, j, side="right"), held - 1)
    first = row_end[e] - counts[e] + (j - (tile_end[e] - n_tile[e])) * tile
    tiles = jnp.stack([e, first, row_end[e]], axis=-1).astype(jnp.int32)
    return order, tiles, tile_end[-1].astype(jnp.int32), counts


def _tile(order, gates, tiles, j, tile: int, k: int):
    e, first, end = tiles[j, 0], tiles[j, 1], tiles[j, 2]
    pos = first + jnp.arange(tile, dtype=jnp.int32)
    valid = pos < end
    slot = order[jnp.minimum(pos, order.shape[0] - 1)]
    g = jnp.where(valid, gates[slot], 0.0)
    return e, slot, slot // k, g, valid


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def grouped_swiglu(tile: int, k: int, x, w1, w3, w2, gates, order, tiles,
                   n_tiles):
    """sum over held slots of gate x SwiGLU_expert(x[token]), scattered
    back to tokens: x (T,d), w1/w3 (held,d,ff), w2 (held,ff,d), gates
    (T*k,) by slot; the tiles come from ``held_tiles``."""
    def body(j, y):
        e, _, tok, g, _ = _tile(order, gates, tiles, j, tile, k)
        xt = x[tok]
        a = jnp.dot(xt, w1[e], preferred_element_type=F32)
        b = jnp.dot(xt, w3[e], preferred_element_type=F32)
        h = (jax.nn.silu(a) * b).astype(x.dtype)
        out = jnp.dot(h, w2[e], preferred_element_type=F32)
        return y.at[tok].add(out * g[:, None])
    y = jax.lax.fori_loop(0, n_tiles, body, jnp.zeros(x.shape, F32))
    return y.astype(x.dtype)


def _grouped_swiglu_fwd(tile, k, x, w1, w3, w2, gates, order, tiles,
                        n_tiles):
    y = grouped_swiglu(tile, k, x, w1, w3, w2, gates, order, tiles, n_tiles)
    return y, (x, w1, w3, w2, gates, order, tiles, n_tiles)


def _grouped_swiglu_bwd(tile, k, res, dy):
    """The tiles again, each recomputing its SwiGLU: cotangents of x, the
    held experts' weights and the gates (padding rows carry gate 0, so
    they add nothing)."""
    x, w1, w3, w2, gates, order, tiles, n_tiles = res
    bf = x.dtype

    def body(j, c):
        dx, dw1, dw3, dw2, dg = c
        e, slot, tok, g, valid = _tile(order, gates, tiles, j, tile, k)
        xt, w1e, w3e, w2e = x[tok], w1[e], w3[e], w2[e]
        a = jnp.dot(xt, w1e, preferred_element_type=F32)
        b = jnp.dot(xt, w3e, preferred_element_type=F32)
        sig = jax.nn.sigmoid(a)
        h = (a * sig * b).astype(bf)
        dyt = dy[tok].astype(F32)
        out = jnp.dot(h, w2e, preferred_element_type=F32)
        dg = dg.at[slot].add(jnp.where(valid, jnp.sum(out * dyt, -1), 0.0))
        dyg = (dyt * g[:, None]).astype(bf)
        dw2 = dw2.at[e].add(jnp.dot(h.T, dyg, preferred_element_type=F32))
        dh = jnp.dot(dyg, w2e.T, preferred_element_type=F32)
        da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(bf)
        db = (dh * a * sig).astype(bf)
        dx = dx.at[tok].add(jnp.dot(da, w1e.T, preferred_element_type=F32)
                            + jnp.dot(db, w3e.T, preferred_element_type=F32))
        dw1 = dw1.at[e].add(jnp.dot(xt.T, da, preferred_element_type=F32))
        dw3 = dw3.at[e].add(jnp.dot(xt.T, db, preferred_element_type=F32))
        return dx, dw1, dw3, dw2, dg

    zeros = lambda a: jnp.zeros(a.shape, F32)  # noqa: E731
    dx, dw1, dw3, dw2, dg = jax.lax.fori_loop(
        0, n_tiles, body, (zeros(x), zeros(w1), zeros(w3), zeros(w2),
                           zeros(gates)))
    return (dx.astype(bf), dw1.astype(w1.dtype), dw3.astype(w3.dtype),
            dw2.astype(w2.dtype), dg.astype(gates.dtype), None, None, None)


grouped_swiglu.defvjp(_grouped_swiglu_fwd, _grouped_swiglu_bwd)


def sigmoid_moe(p: dict, x: jax.Array, *, top_k: int, scaling: float,
                held: int, offset: int, tile: int = MOE_TILE_ROWS
                ) -> tuple[jax.Array, jax.Array, dict]:
    """x: (B,S,d) -> (y, balance term, stats). Stats: ``load`` (E,) slots
    routed to each router output, ``rows`` (3,) [slots routed, slots on
    held experts, rows the grouped product computed], ``held_max`` rows
    of the fullest held expert, ``ids`` (B,S,k) the chosen experts."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    e = p["router"].shape[-1]
    with jax.named_scope("moe.route"):
        scores, ids, gates = sigmoid_route(p, xf, top_k=top_k,
                                           scaling=scaling)
        aux = seq_balance(scores.reshape(b, s, e), ids.reshape(b, s, top_k))
        order, tiles, n_tiles, counts = held_tiles(ids, held=held,
                                                   offset=offset, tile=tile)
    with jax.named_scope("moe.experts"):
        y = grouped_swiglu(tile, top_k, xf, p["experts"]["w1"],
                           p["experts"]["w3"], p["experts"]["w2"],
                           gates.reshape(-1), order, tiles, n_tiles)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(p["shared"], xf)
    stats = {"load": jnp.bincount(ids.reshape(-1), length=e).astype(jnp.int32),
             "rows": jnp.stack([jnp.int32(ids.size), jnp.sum(counts),
                                n_tiles * tile]).astype(jnp.int32),
             "held_max": jnp.max(counts),
             "ids": ids.reshape(b, s, top_k).astype(jnp.int32)}
    return y.reshape(b, s, d), aux, stats
