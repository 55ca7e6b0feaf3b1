"""Sweep driver: run every (arch x shape x mesh) dry-run cell in a
subprocess (each needs a fresh jax with 512 forced host devices) and
aggregate results into experiments/dryrun/*.json.

    PYTHONPATH=src python -m repro.launch.dryrun_all \
        [--mesh single multi] [--arch ...] [--shape ...] [--jobs 4]
        [--inc-mode netrpc] [--tag baseline]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ARCHS = (
    "moonlight-16b-a3b", "grok-1-314b", "gemma3-27b", "phi4-mini-3.8b",
    "stablelm-1.6b", "qwen2.5-3b", "llama-3.2-vision-90b",
    "recurrentgemma-9b", "mamba2-780m", "whisper-medium",
)
SHAPES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def run_one(arch: str, shape: str, mesh: str, inc_mode: str, outdir: Path,
            timeout: int, extra: list[str]) -> dict:
    out = outdir / f"{arch}__{shape}__{mesh}__{inc_mode}.json"
    if out.exists():
        res = json.loads(out.read_text())
        if res.get("status") in ("ok", "skipped"):
            return res
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--inc-mode", inc_mode,
           "--json", str(out)] + extra
    t0 = time.time()
    try:
        p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=timeout)
        if not out.exists():
            res = {"arch": arch, "shape": shape, "mesh": mesh,
                   "inc_mode": inc_mode, "status": "error",
                   "error": (p.stderr or p.stdout)[-2000:]}
            out.write_text(json.dumps(res, indent=2))
        res = json.loads(out.read_text())
    except subprocess.TimeoutExpired:
        res = {"arch": arch, "shape": shape, "mesh": mesh,
               "inc_mode": inc_mode, "status": "timeout",
               "wall_s": time.time() - t0}
        out.write_text(json.dumps(res, indent=2))
    res["wall_s"] = round(time.time() - t0, 1)
    return res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=list(ARCHS))
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--mesh", nargs="*", default=["single", "multi"])
    ap.add_argument("--inc-mode", default="netrpc")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--n-micro", type=int, default=None)
    args = ap.parse_args()
    outdir = Path(args.outdir)
    outdir.mkdir(parents=True, exist_ok=True)
    extra = []
    if args.n_micro:
        extra += ["--n-micro", str(args.n_micro)]

    cells = [(a, s, m) for a in args.arch for s in args.shape
             for m in args.mesh]
    results = []
    with ThreadPoolExecutor(max_workers=args.jobs) as ex:
        futs = {ex.submit(run_one, a, s, m, args.inc_mode, outdir,
                          args.timeout, extra): (a, s, m)
                for a, s, m in cells}
        for fut in futs:
            pass
        done = 0
        for fut, key in futs.items():
            res = fut.result()
            done += 1
            print(f"[{done}/{len(cells)}] {key[0]:22s} {key[1]:12s} "
                  f"{key[2]:6s} -> {res['status']:8s} "
                  f"({res.get('wall_s', 0):.0f}s compile "
                  f"{res.get('compile_s', '-')}s)", flush=True)
            results.append(res)

    bad = [r for r in results if r["status"] not in ("ok", "skipped")]
    print(f"\n{len(results) - len(bad)}/{len(results)} cells ok/skipped; "
          f"{len(bad)} failed")
    for r in bad:
        print("FAILED:", r["arch"], r["shape"], r["mesh"],
              str(r.get("error", ""))[:200])
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
