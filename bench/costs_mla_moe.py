"""Training FLOPs of the MLA + sigmoid-routed MoE cells, from their shapes.

The yardstick's own counts, as ``costs.py`` is for the dense decoder: 6
FLOPs per matmul weight a token meets (forward and backward), and causal
MLA attention's scores (q/k width nope + rope) and weighted sum (v width)
over half of the positions. A token meets every weight outside the routed
experts; a routed expert's weights are met once per routed row that landed
on an expert held here, counted as the program routed them, not as an
average. Recomputation and padding rows are not counted.
"""
from __future__ import annotations


def mla_matmul_params(a: dict) -> int:
    """W_q, W_kv_a, W_kv_b and W_o of one MLA layer."""
    d, h = a["hidden_size"], a["num_attention_heads"]
    nope, rope = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    rank, vd = a["kv_lora_rank"], a["v_head_dim"]
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + vd) + h * vd * d)


def expert_matmul_params(a: dict) -> int:
    """The three matrices of one routed expert (a routed row's weights)."""
    return 3 * a["hidden_size"] * a["moe_intermediate_size"]


def dense_matmul_params(a: dict) -> int:
    """Weights every token meets: MLA in every layer, the dense layers'
    SwiGLU, the MoE layers' shared experts and router, and the LM head
    (the embedding lookup is a gather)."""
    d = a["hidden_size"]
    n_dense = a["first_k_dense_replace"]
    n_moe = a["num_hidden_layers"] - n_dense
    router = d * a["n_routed_experts"] * a["expert_parallel"]
    shared = a["n_shared_experts"] * expert_matmul_params(a)
    return (a["num_hidden_layers"] * mla_matmul_params(a)
            + n_dense * 3 * d * a["intermediate_size"]
            + n_moe * (shared + router) + a["vocab_size"] * d)


def attention_flops_per_token(a: dict, seq: int) -> float:
    """Causal MLA scores and weighted sum, forward and backward: per layer
    2 x heads x (qk + v widths) x seq / 2 forward, times 3."""
    h = a["num_attention_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    return 3.0 * a["num_hidden_layers"] * h * (qk + a["v_head_dim"]) * seq


def step_flops(a: dict, seq: int, tokens: int, held_rows: int) -> float:
    """FLOPs that ``tokens`` tokens of context ``seq`` need, with
    ``held_rows`` routed rows landing on the experts held here."""
    per_token = 6.0 * dense_matmul_params(a) \
        + attention_flops_per_token(a, seq)
    return per_token * tokens + 6.0 * expert_matmul_params(a) * held_rows


def mean_flops_per_token(a: dict, seq: int) -> float:
    """FLOPs a token needs when its k routed rows in each MoE layer fall
    on the experts in proportion, held / router width of them here."""
    n_moe = a["num_hidden_layers"] - a["first_k_dense_replace"]
    rows = n_moe * a["num_experts_per_tok"] / a["expert_parallel"]
    return step_flops(a, seq, 1, 0) + 6.0 * expert_matmul_params(a) * rows
