#!/usr/bin/env python3
"""Readings that set the limits of an MoE training cell's comparison, in
one process (``bench/calibrate.py`` for the ``dp_train_moe`` driver).

    python3 bench/calibrate_moe.py --workload <cell> --seeds 1,2,...,6 \
        --control-seeds 1,2 [--faults half_batch,expert_dropped]

The cell's step is built and compiled once. For every seed its state is
drawn afresh, the checked steps run through the window's own call and
feed, the state is freed and the plain reference follows, given the
program's expert choice; the compared numbers are printed as the
program's reading. For each control seed the reference runs again with
float8 matmul operands (``mla_moe_lm`` with ``control=True``), given the
same choice, and is compared with the float32 reference in the same way.
Each fault (``bench/faults.py`` around the compiled step, or
``bench/faults_moe.py`` in the expert layer, whose step is built again)
is read on the control seeds.

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


def calibrate(ctx, seeds, control_seeds, fault_names) -> None:
    import faults
    import faults_moe
    driver = harness.load_module(BENCH / "drivers" / "dp_train_moe.py")
    conf, tr = ctx.config, ctx.traffic

    def reading(trainer, ws, ds):
        trainer.reset(ws, ds)
        got = trainer.checked_steps(tr["checked_steps"])
        trainer.free_state()
        return got

    def show(seed, kind, got, ref, **extra):
        print(json.dumps({"seed": seed, "reading": kind,
                          **driver.readings(got, ref), **extra}), flush=True)

    trainer = driver.Trainer(ctx, conf, tr)
    got_of, refs = {}, {}
    for seed in sorted(set(seeds) | set(control_seeds)):
        ws, ds = harness.seed_words(seed)[:2]
        got = got_of[seed] = reading(trainer, ws, ds)
        ref = refs[seed] = driver.reference(conf, tr, ws, ds, got["ids"])
        if seed in seeds:
            show(seed, "program", got, ref, losses=got["losses"],
                 ref_losses=ref["losses"], grad_norms=got["grad_norms"],
                 ref_grad_norms=ref["grad_norms"], leaves=ref["leaves"])
    for seed in control_seeds:
        ws, ds = harness.seed_words(seed)[:2]
        ctl = driver.reference(conf, tr, ws, ds, got_of[seed]["ids"],
                               control=True)
        # the control stands in the program's place; its route reading
        # is its own choice against the program's
        ctl = dict(ctl, ids=got_of[seed]["ids"])
        show(seed, "control", ctl, dict(refs[seed],
                                        route_mismatch=ctl["route_mismatch"]),
             losses=ctl["losses"])
    for name in fault_names:
        if name in faults_moe.FAULTS:
            undo = faults_moe.install(name)
            trainer.telemetry.finish()
            trainer = driver.Trainer(ctx, conf, tr)
        else:
            fn = trainer.prog.fn
            trainer.prog.fn = faults.wrap_step(fn, name)
        for seed in control_seeds:
            ws, ds = harness.seed_words(seed)[:2]
            got = reading(trainer, ws, ds)
            ref = driver.reference(conf, tr, ws, ds, got["ids"])
            show(seed, f"fault:{name}", got, ref)
        if name in faults_moe.FAULTS:
            undo()
            trainer.telemetry.finish()
            trainer = driver.Trainer(ctx, conf, tr)
        else:
            trainer.prog.fn = fn
    trainer.telemetry.finish()


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
    calibrate(ctx, ints(a.seeds), ints(a.control_seeds),
              [f for f in a.faults.split(",") if f])
    return 0


if __name__ == "__main__":
    sys.exit(main())
