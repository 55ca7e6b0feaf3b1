"""Model FLOP utilization of the training window: tokens per second
times the FLOPs a token needs (bench/costs.py, no recomputation) over
the chips' published bf16 peak."""
from costs import model_flops_per_token


def read(rec):
    w, peaks = rec["window"], rec.get("peaks")
    if not peaks or not w["steps"]:
        return None
    tok_s = w["tokens"] / w["seconds"]
    flops = model_flops_per_token(w["arch"], w["seq"])
    return 100.0 * tok_s * flops / (rec["chips"] * peaks["bf16_flops_per_s"])
