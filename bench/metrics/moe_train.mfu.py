"""Model FLOP utilization of the MoE training window: the FLOPs of the
window's tokens and of the routed rows that landed on the experts held
here (``inc_moe_held_rows_total``, bench/costs_mla_moe.py, no
recomputation, no padding) per second, over the chips' published bf16
peak."""
from costs_mla_moe import step_flops
from readings import counter


def read(rec):
    w, peaks = rec["window"], rec.get("peaks")
    held = counter(rec, "inc_moe_held_rows_total")
    if not peaks or not w["steps"] or held is None:
        return None
    flops = step_flops(w["arch"], w["seq"], w["tokens"], held)
    return 100.0 * flops / w["seconds"] / (
        rec["chips"] * peaks["bf16_flops_per_s"])
