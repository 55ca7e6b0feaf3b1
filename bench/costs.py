"""Operations and bytes the benchmark's work needs, from its shapes alone.

These are the yardstick's own counts, fixed whatever the implementation
does: a kernel that moves more than this, or a step that recomputes, is
charged the difference as lost roofline share, never credited with it.
"""
from __future__ import annotations

# Map.addTo of one fp32 element into an int32 register: read the fp32
# value, read and write the 32-bit register
ADDTO_BYTES_PER_ELEM = 4 + 4 + 4
# Map.get of one element as the Get reply: read the int32 register, write
# the fp32 value and its one-byte overflow flag
READ_BYTES_PER_ELEM = 4 + 4 + 1


# one entry of a saturating int32 scatter-add stream: read its index and
# value, read and write the register it names
SCATTER_BYTES_PER_ENTRY = 4 + 4 + 4 + 4


def fused_addto_bytes(elems: int) -> int:
    """HBM bytes one fused quantize + saturating addTo must move."""
    return ADDTO_BYTES_PER_ELEM * int(elems)


def read_bytes(elems: int) -> int:
    """HBM bytes one fused gather + dequantize reply must move."""
    return READ_BYTES_PER_ELEM * int(elems)


def scatter_addto_bytes(entries: int) -> int:
    """HBM bytes a saturating scatter-add of ``entries`` must move."""
    return SCATTER_BYTES_PER_ENTRY * int(entries)


def agtr_call_bytes(elems: int) -> int:
    """HBM bytes one SyncAgtr call (addTo plus its Get reply) must move."""
    return (ADDTO_BYTES_PER_ELEM + READ_BYTES_PER_ELEM) * int(elems)


def dense_matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix multiplication once per token in a
    dense decoder: q, k, v and output projections, the gated MLP's three
    matrices, and the LM head. The embedding lookup is a gather."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    attn = d * hd * (2 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def dense_params(cfg: dict) -> int:
    """All parameters of the dense decoder as the program holds it: the
    matmul weights, the embedding table and one RMSNorm scale before
    attention, one before the MLP and one at the end."""
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return dense_matmul_params(cfg) + cfg["vocab_size"] * d + norms


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Training FLOPs per token that the forward and backward passes
    require: 6 per matmul weight, plus causal attention's scores and
    weighted sum (4 * seq * d per layer forward for full attention, half
    of it under the causal mask, times 3 for forward and backward).
    Recomputation is not counted."""
    d_attn = cfg["num_attention_heads"] * cfg["head_dim"]
    attn = 6 * cfg["num_hidden_layers"] * seq * d_attn
    return 6.0 * dense_matmul_params(cfg) + float(attn)
