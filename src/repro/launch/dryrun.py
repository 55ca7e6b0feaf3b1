import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract the roofline terms.

The three lines above MUST run before any other import (jax locks the
device count on first init); 512 placeholder host devices back both the
single-pod (16,16) and multi-pod (2,16,16) meshes. The dry-run is CPU-only
tooling, so it pins jax to the CPU: on a machine with a chip it must not
take the chip from the process that runs there.

Usage:
    python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k \
        --mesh multi --inc-mode netrpc [--json out.json]

Exit code 0 iff lower+compile succeeded. Prints memory_analysis (proves the
cell fits) and cost_analysis (feeds §Roofline), plus parsed collective
bytes.
"""
import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_kind: str, inc_mode: str,
             precision: int = 8, n_micro: int | None = None,
             flash: bool = False, pad_heads: int = 0,
             qgather: bool = False, pad_kv: int = 0) -> dict:
    if flash:
        os.environ["REPRO_FLASH_ATTN"] = "1"
    if qgather:
        os.environ["REPRO_QUANTIZED_GATHER"] = "1"
    from dataclasses import replace as _replace

    from repro.configs.base import get_arch, SHAPES, shape_applicable
    from repro.core.inc_agg import IncAggConfig
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh
    from repro.models import api
    from repro.optim.adamw import AdamWConfig
    from repro.roofline import analysis
    from repro.roofline.flash_model import flash_traffic_bytes

    cfg = get_arch(arch)
    if pad_heads:
        # sharding-equivalence padding (zero heads + grad mask in prod):
        # shapes-only measurement
        kv = pad_kv or cfg.n_kv_heads
        assert pad_heads % kv == 0, (pad_heads, kv)
        cfg = _replace(cfg, n_heads=pad_heads, n_kv_heads=kv)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "why": why}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    inc = IncAggConfig(mode=inc_mode, precision=precision)
    t0 = time.time()
    if shape.kind == "train":
        prog = steps.build_train_step(
            cfg, shape, mesh, inc=inc,
            opt_cfg=AdamWConfig(), n_micro=n_micro)
    else:
        prog = steps.build_serve_step(cfg, shape, mesh)
    lowered = prog.lower()
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0))
    print("memory_analysis:", ma)
    cost = dict(compiled.cost_analysis() or {})
    print("cost_analysis: flops=%.3e bytes=%.3e"
          % (cost.get("flops", 0), cost.get("bytes accessed", 0)))

    extra = 0.0
    scopes = ()
    if flash:
        scopes = ("flash_attention",)
        extra = flash_traffic_bytes(
            cfg, shape, n_micro=prog.meta.get("n_micro") or 1,
            n_dp=prog.meta["n_dp"], n_model=prog.meta["n_model"])
    roof = analysis.analyze(compiled, skip_scopes=scopes,
                            extra_hbm_bytes=extra)
    n_chips = 512 if mesh_kind == "multi" else 256
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mf = analysis.model_flops(api.count_params(cfg),
                              api.count_params(cfg, active_only=True),
                              shape.kind, tokens)
    s = roof.summary()
    s.update({
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "inc_mode": inc_mode, "status": "ok",
        "flash": flash, "pad_heads": pad_heads, "qgather": qgather,
        "kind": shape.kind, "mode": prog.meta["mode"],
        "n_micro": prog.meta.get("n_micro"),
        "chips": n_chips,
        "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "memory": mem,
        "bytes_per_device": mem.get("argument_size_in_bytes", 0)
        + mem.get("temp_size_in_bytes", 0),
        "model_flops_per_dev": mf / n_chips,
        "useful_ratio": (mf / n_chips) / max(roof.flops, 1.0),
        "model_compute_s": mf / n_chips / analysis.PEAK_FLOPS,
    })
    s["roofline_fraction"] = s["model_compute_s"] / max(
        s["compute_s"], s["memory_s"], s["collective_s"], 1e-30)
    return s


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--inc-mode", default="netrpc")
    ap.add_argument("--precision", type=int, default=8)
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--pad-heads", type=int, default=0)
    ap.add_argument("--pad-kv", type=int, default=0)
    ap.add_argument("--qgather", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    try:
        res = run_cell(args.arch, args.shape, args.mesh, args.inc_mode,
                       args.precision, args.n_micro, args.flash,
                       args.pad_heads, args.qgather, args.pad_kv)
    except Exception as e:
        traceback.print_exc()
        res = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "inc_mode": args.inc_mode, "status": "error",
               "error": f"{type(e).__name__}: {e}"}
    print("DRYRUN_RESULT " + json.dumps(res))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
    return 0 if res["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
