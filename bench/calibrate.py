#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 [--faults half_batch]

Training cells: the cell's step is built and compiled once. For every
seed its state is drawn afresh, the checked steps run through the
window's own call and feed, the state is freed and the plain reference
follows; the compared numbers are printed as the program's reading. For
each control seed the reference runs again with float8 matmul operands
(``dense_lm`` with ``control=True``) and is compared with the float32
reference in the same way. Each fault (``bench/faults.py``) is planted
around the compiled step and read on the control seeds.

Serve cells: for each control seed, the chunks the cell draws are
rounded to bfloat16 before the fixed point (the control in the
program's place) and compared with the integer oracle element for
element.

One JSON line per reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as harness  # noqa: E402


def calibrate_train(ctx, seeds, control_seeds, faults) -> None:
    import faults as planted
    driver = harness.load_module(BENCH / "drivers" / "dp_train.py")
    conf, tr = ctx.config, ctx.traffic

    def reading(trainer, ws, ds):
        trainer.reset(ws, ds)
        got = trainer.checked_steps(tr["checked_steps"])
        trainer.free_state()
        return got

    trainer = driver.Trainer(ctx, conf, tr)
    refs = {}
    for seed in seeds:
        ws, ds = harness.seed_words(seed)[:2]
        got = reading(trainer, ws, ds)
        ref = refs[seed] = driver.reference(conf, tr, ws, ds)
        print(json.dumps({"seed": seed, "reading": "program",
                          **driver.readings(got, ref),
                          "losses": got["losses"],
                          "ref_losses": ref["losses"],
                          "grad_norms": got["grad_norms"],
                          "ref_grad_norms": ref["grad_norms"],
                          "delta_norms": got["delta_norms"],
                          "ref_delta_norms": ref["delta_norms"],
                          "leaves": ref["leaves"]}), flush=True)
    for seed in control_seeds:
        ws, ds = harness.seed_words(seed)[:2]
        ref = refs.get(seed) or driver.reference(conf, tr, ws, ds)
        ctl = driver.reference(conf, tr, ws, ds, control=True)
        print(json.dumps({"seed": seed, "reading": "control",
                          **driver.readings(ctl, ref),
                          "losses": ctl["losses"]}), flush=True)
    for name in faults:
        fn = trainer.prog.fn
        trainer.prog.fn = planted.wrap_step(fn, name)
        for seed in control_seeds:
            ws, ds = harness.seed_words(seed)[:2]
            got = reading(trainer, ws, ds)
            ref = refs.get(seed) or driver.reference(conf, tr, ws, ds)
            print(json.dumps({"seed": seed, "reading": f"fault:{name}",
                              **driver.readings(got, ref)}),
                  flush=True)
        trainer.prog.fn = fn
    trainer.telemetry.finish()


def calibrate_serve(ctx, control_seeds) -> None:
    import numpy as np

    import jax.numpy as jnp
    from reference import agtr_oracle as oracle
    driver = harness.load_module(BENCH / "drivers" / "agtr_closed_loop.py")
    conf, tr = ctx.config, ctx.traffic
    scale = 10 ** conf["service"]["precision"]
    for seed in control_seeds:
        words = harness.seed_words(seed)
        pools = driver.make_pools(words[0], tr["workers"], tr["pool"],
                                  tr["elems"], tr["chunk_std"])
        mism = total = 0
        for pool in pools:
            for chunk in pool:
                want = oracle.reply_f32(np.asarray(chunk), scale)
                low = np.asarray(chunk.astype(jnp.bfloat16)
                                 .astype(jnp.float32))
                mism += oracle.mismatches(oracle.reply_f32(low, scale), want)
                total += want.size
        print(json.dumps({"seed": seed, "reading": "control",
                          "reply_elems_mismatched": mism,
                          "reply_elems_compared": total}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    args = argparse.Namespace(workload=a.workload, seed=0, seconds=0.0,
                              trace=0, rehearse=a.rehearse)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == a.workload)
    ctx = harness.Ctx(args, spec, cell)
    harness.check_machine(ctx)
    harness.configure_jax(ctx)
    if ctx.traffic["kind"] == "dp_train":
        calibrate_train(ctx, ints(a.seeds), ints(a.control_seeds),
                        [f for f in a.faults.split(",") if f])
    else:
        calibrate_serve(ctx, ints(a.control_seeds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
