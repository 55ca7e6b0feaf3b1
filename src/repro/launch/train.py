"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --inc-mode netrpc --steps 200 --reduced --seq 128 --batch 8

--reduced runs the tiny same-family config on the host devices (CPU smoke /
examples); without it the full config requires a real TPU pod slice. The
loop integrates: deterministic data pipeline, the INC-aggregated train
step, CntFwd elastic quorum (straggler mitigation: --quorum < 1.0 lets a
step commit on a partial aggregation), and checkpoint/restart with the
step-parity exactly-once gate.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.store import CheckpointStore
from repro.configs.base import ArchConfig, ShapeConfig, get_arch
from repro.core.inc_agg import IncAggConfig
from repro.data import pipeline
from repro.kernels.backend import configure_compile_cache
from repro.launch import steps
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim.adamw import AdamWConfig


def train_loop(*, arch: str | ArchConfig, inc_mode: str, steps_n: int,
               seq: int, batch: int, reduced: bool, precision: int = 8,
               ckpt_dir: str | None = None, ckpt_every: int = 50,
               resume: bool = True, model_axis: int = 2,
               data_kind: str = "bigram", log_every: int = 10,
               n_micro: int = 1, quorum: float = 1.0, seed: int = 0) -> dict:
    """Train ``steps_n`` steps of ``arch`` (a registered name, or an
    ArchConfig such as a depth-cut variant of one) and return the losses,
    the final state and the INC telemetry summary. ``seed`` draws the
    initial weights and the data."""
    cfg = get_arch(arch) if isinstance(arch, str) else arch
    if reduced:
        cfg = cfg.reduced()
    if not reduced and len(jax.devices()) >= 256:
        mesh = make_production_mesh()
    else:
        mesh = make_host_mesh(model=min(model_axis, len(jax.devices())))
    shape = ShapeConfig("cli_train", seq_len=seq, global_batch=batch,
                        kind="train")
    inc = IncAggConfig(mode=inc_mode, precision=precision)
    opt_cfg = AdamWConfig(warmup_steps=max(steps_n // 20, 5),
                          total_steps=steps_n)
    prog = steps.build_train_step(cfg, shape, mesh, inc=inc,
                                  opt_cfg=opt_cfg, n_micro=n_micro)
    params, opt = steps.init_state(prog, cfg, jax.random.key(seed))

    store = CheckpointStore(ckpt_dir) if ckpt_dir else None
    start = 0
    if store and resume and store.latest_step() is not None:
        start = store.latest_step() + 1
        state = store.restore(store.latest_step(),
                              {"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        print(f"resumed from step {start - 1}")

    dcfg = pipeline.DataConfig(vocab=cfg.vocab, batch=batch, seq_len=seq,
                               seed=seed, kind=data_kind)
    # metric + agreement channels on the async INC runtime (typed schema
    # services, launch/steps.py): per-step pushes and commit votes enqueue
    # through the generated stubs and return; the scheduler coalesces them
    # into drained batches off the hot path (no N=1 INC call per step)
    telemetry = steps.TrainTelemetry(n_workers=prog.meta["n_dp"],
                                     quorum=quorum, app_prefix="train")
    losses = []
    ran = 0
    t0 = time.time()
    for s in range(start, steps_n):
        if store and store.already_applied(s):
            continue      # exactly-once: this step is a "retransmission"
        b = pipeline.make_batch(dcfg, s)
        b = pipeline.add_modality_stubs(b, cfg, batch)
        params, opt, m = prog.fn(params, opt, b, jnp.int32(s))
        losses.append(steps.read_step(m, prog.meta))
        ran += 1
        telemetry.push({"loss_sum": losses[-1], "steps": 1,
                        "gnorm_sum": float(m["gnorm"])})
        # one commit vote per dp rank; CntFwd forwards exactly one quorum
        # notification per step once >= quorum * n_dp votes landed
        for _ in range(prog.meta["n_dp"]):
            telemetry.vote(s)
        if s % log_every == 0 or s == steps_n - 1:
            dt = time.time() - t0
            print(f"step {s:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(m['gnorm']):.3f} lr {float(m['lr']):.2e} "
                  f"({dt:.1f}s)")
        if store and s and s % ckpt_every == 0:
            store.save(s, {"params": params, "opt": opt})
    if store:
        store.save(steps_n - 1, {"params": params, "opt": opt})
        store.wait()
    inc = telemetry.finish()
    if ran:
        sched = inc["scheduling"].get("train-metrics", {})
        print(f"inc telemetry: steps={inc['metrics'].get('steps', 0):.0f} "
              f"mean_loss={inc['metrics'].get('loss_sum', 0.0) / ran:.4f} "
              f"commits={inc['commits']}/{ran} "
              f"mean_drained_batch={sched.get('mean_drained_batch', 0)}")
    return {"losses": losses, "params": params, "opt": opt,
            "inc_telemetry": inc,
            "entropy_floor": (pipeline.bigram_entropy(dcfg)
                              if data_kind == "bigram" else None)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--inc-mode", default="netrpc")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--precision", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data", default="bigram")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--quorum", type=float, default=1.0)
    args = ap.parse_args()
    configure_compile_cache()
    out = train_loop(arch=args.arch, inc_mode=args.inc_mode,
                     steps_n=args.steps, seq=args.seq, batch=args.batch,
                     reduced=args.reduced, precision=args.precision,
                     ckpt_dir=args.ckpt_dir, data_kind=args.data,
                     n_micro=args.n_micro, quorum=args.quorum)
    ls = out["losses"]
    print(f"final loss {ls[-1]:.4f} (first {ls[0]:.4f}); "
          f"entropy floor {out['entropy_floor']}")


if __name__ == "__main__":
    main()
