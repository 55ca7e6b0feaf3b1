"""Data-parallel training with INC gradient aggregation.

Set-up builds one compiled step with its state through the program's own
entry points (``steps.build_train_step`` with the configuration's
``IncAggConfig``, ``steps.init_state``), a ``TrainTelemetry`` on the INC
runtime, and the per-step sequence of ``launch/train.py:train_loop``:
``pipeline.make_batch`` -> ``prog.fn`` -> loss read back -> telemetry
push and one commit vote per data-parallel rank. The loop is copied
because ``train_loop`` cannot hand its warm-up and its window apart.

The first ``checked_steps`` steps run in set-up through that same
sequence (the first one compiles) and are what the check compares: each
step's loss, the per-leaf norms of the first gradient as AdamW got it
(its first moment over ``1 - b1``), and the per-leaf norms of the
parameters' change after the checked steps. The window then goes on
stepping the same object for ``--seconds``. Once it has closed and the
peak memory is read, the program's state is freed and the plain float32
reference (``bench/reference/dense_lm.py``) runs the checked steps from
the same seeds.
"""
from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

import jax
import jax.numpy as jnp

ARCH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_hidden_layers",
             "vocab_size", "rope_theta", "rms_norm_eps")


def arch_of(conf: dict) -> dict:
    return {k: conf[k] for k in ARCH_KEYS}


def program_config(conf: dict, rehearse: bool):
    """The program's ArchConfig of the configuration: its registered
    model with the depth cut, whose widths must equal the file's."""
    from repro.configs.base import get_arch
    a = arch_of(conf)
    n = a["num_hidden_layers"]
    cfg = replace(get_arch(conf["program_arch"]), n_layers=n,
                  pattern_groups=((("global",), n),))
    if rehearse:
        cfg = replace(cfg, d_model=a["hidden_size"],
                      d_ff=a["intermediate_size"],
                      n_heads=a["num_attention_heads"],
                      n_kv_heads=a["num_key_value_heads"],
                      head_dim=a["head_dim"], vocab=a["vocab_size"])
    got = (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
           cfg.vocab, cfg.rope_theta, cfg.norm_eps, cfg.qkv_bias,
           cfg.tie_embeddings, cfg.vocab_padded)
    want = (a["hidden_size"], a["intermediate_size"],
            a["num_attention_heads"], a["num_key_value_heads"],
            a["head_dim"], a["vocab_size"], float(a["rope_theta"]),
            a["rms_norm_eps"], False, conf["tie_word_embeddings"],
            a["vocab_size"])
    if got != want:
        raise RuntimeError(f"program config {got} differs from {want}")
    return cfg


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(
        lambda l: jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)))),
        tree)


def leaf_norms(tree) -> list[float]:
    return [float(x) for x in jax.tree.leaves(_leaf_norms(tree))]


def relative_gaps(got: list[float], want: list[float],
                  keep: list[bool] | None = None) -> list[float]:
    """|got - want| per leaf, over the larger of the reference leaf's
    norm and the median reference leaf norm (some leaves are all but
    zero)."""
    keep = keep or [True] * len(want)
    med = float(np.median([w for w, k in zip(want, keep) if k]))
    return [abs(g - w) / max(w, med) if k else 0.0
            for g, w, k in zip(got, want, keep)]


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """The readings of the program against the reference's, each under
    its limit; only the numbers that ``limits`` names are compared (see
    PERF.md for why a reading can go uncompared). Leaves whose reference
    gradient is under a thousandth of the median leaf's move under Adam
    by round-off alone and are left out of the change."""
    med_g = float(np.median(ref["grad_norms"]))
    moving = [g >= 1e-3 * med_g for g in ref["grad_norms"]]
    vals = {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                   ref["losses"])),
        "grad_norm_gap": max(relative_gaps(prog["grad_norms"],
                                           ref["grad_norms"])),
        "update_norm_gap": max(relative_gaps(prog["delta_norms"],
                                             ref["delta_norms"], moving)),
    }
    return {k: {"value": vals[k], "limit": v} for k, v in limits.items()}


def readings(prog: dict, ref: dict) -> dict:
    """Every number ``compare`` can compare, with no limit applied."""
    return {k: v["value"] for k, v in compare(prog, ref, dict.fromkeys(
        ("loss_gap", "grad_norm_gap", "update_norm_gap"))).items()}


class Trainer:
    """The compiled step, its state and the per-step sequence of
    ``train_loop``, built once in set-up and driven by the window."""

    def __init__(self, ctx, conf, tr):
        from jax.sharding import AxisType, Mesh

        from repro.configs.base import ShapeConfig
        from repro.core.inc_agg import IncAggConfig
        from repro.data import pipeline
        from repro.launch import steps
        from repro.optim.adamw import AdamWConfig

        self.ctx, self.conf, self.tr = ctx, conf, tr
        self.pipeline, self.steps = pipeline, steps
        self.cfg = program_config(conf, ctx.rehearse)
        self.batch, self.seq = tr["global_batch"], tr["seq"]
        mesh = Mesh(np.array(ctx.devices).reshape(ctx.chips, 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        shape = ShapeConfig(ctx.name, seq_len=self.seq,
                            global_batch=self.batch, kind="train")
        self.opt_cfg = AdamWConfig(**conf["optimizer"])
        self.prog = steps.build_train_step(
            self.cfg, shape, mesh, inc=IncAggConfig(**conf["inc"]),
            opt_cfg=self.opt_cfg)
        self.n_dp = self.prog.meta["n_dp"]
        self.telemetry = steps.TrainTelemetry(n_workers=self.n_dp,
                                              app_prefix="train")
        self.reset(ctx.seeds[0], ctx.seeds[1])

    def reset(self, weight_seed: int, data_seed: int) -> None:
        """Fresh state and data stream from the seeds, same program."""
        if getattr(self, "params", None) is not None:
            self.free_state()
        self.weight_seed, self.data_seed = weight_seed, data_seed
        self.params, self.opt = self.steps.init_state(
            self.prog, self.cfg, jax.random.key(weight_seed))
        t0 = time.perf_counter()
        self.w0 = [np.asarray(leaf) for leaf in jax.tree.leaves(self.params)]
        self.check_s = time.perf_counter() - t0
        self.dcfg = self.pipeline.DataConfig(
            vocab=self.cfg.vocab, batch=self.batch, seq_len=self.seq,
            seed=data_seed, kind="uniform")
        self.s = 0
        self.losses: list[float] = []

    def step(self) -> float:
        """One step of ``train_loop``'s sequence."""
        ctx, s = self.ctx, self.s
        with ctx.span("bench.batch"):
            b = self.pipeline.make_batch(self.dcfg, s)
            b = self.pipeline.add_modality_stubs(b, self.cfg, self.batch)
        with ctx.span("bench.step"):
            self.params, self.opt, m = self.prog.fn(self.params, self.opt,
                                                    b, jnp.int32(s))
            loss = float(m["loss"])
        self.telemetry.push({"loss_sum": loss, "steps": 1,
                             "gnorm_sum": float(m["gnorm"])})
        for _ in range(self.n_dp):
            self.telemetry.vote(s)
        self.losses.append(loss)
        self.s += 1
        return loss

    def delta_norms(self) -> list[float]:
        """Per-leaf norm of the float32 master's change since the initial
        weights, on the host: a second draw of the weights inside another
        jitted program may round some bf16 weights differently on the
        TPU, and a device copy would raise the peak the cell reports."""
        return [float(np.linalg.norm(np.asarray(m, np.float64)
                                     - w.astype(np.float64)))
                for m, w in zip(jax.tree.leaves(self.opt["master"]),
                                self.w0)]

    def checked_steps(self, n: int) -> dict:
        """Run the first ``n`` steps and take the check's readings. The
        seconds the readings take (copies to the host, norms) are the
        benchmark's own and are kept in ``check_s``."""
        grad_norms = None
        for i in range(n):
            self.step()
            if i == 0:
                t0 = time.perf_counter()
                b1 = self.opt_cfg.b1
                grad_norms = [x / (1 - b1) for x in leaf_norms(self.opt["m"])]
                self.check_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        delta = self.delta_norms()
        self.w0 = None
        self.check_s += time.perf_counter() - t0
        return {"losses": list(self.losses), "grad_norms": grad_norms,
                "delta_norms": delta}

    def free_state(self) -> None:
        for leaf in jax.tree.leaves((self.params, self.opt)):
            leaf.delete()
        self.params = self.opt = None


def reference(conf: dict, tr: dict, weight_seed: int, data_seed: int,
              control: bool = False) -> dict:
    """The plain reference's readings of the checked steps."""
    from reference import dense_lm
    return dense_lm.run(
        arch_of(conf), conf["optimizer"], weight_seed=weight_seed,
        data_seed=data_seed, batch=tr["global_batch"], seq=tr["seq"],
        micro=tr["rows_per_rank"], wire_precision=conf["inc"]["precision"],
        steps=tr["checked_steps"],
        q_chunk=tr["ref_q_chunk"], row_chunk=tr["ref_row_chunk"],
        control=control)


def run(ctx) -> dict:
    conf, tr = ctx.config, ctx.traffic
    trainer = Trainer(ctx, conf, tr)
    checked = trainer.checked_steps(tr["checked_steps"])
    # set-up is the system's: the check's own readings are left out
    setup_s = time.perf_counter() - ctx.t_start - trainer.check_s

    seconds = min(ctx.seconds, tr.get("trace_seconds", ctx.seconds)) \
        if ctx.trace else ctx.seconds
    if ctx.trace:
        from repro import obs
        obs.reset()
        obs.enable(metrics=True)
        ctx.start_trace()
    c0 = ctx.compiles
    s0 = trainer.s
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        trainer.step()
    window_s = time.perf_counter() - t0
    window_compiles = ctx.compiles - c0
    steps_done = trainer.s - s0
    snap = trace = None
    if ctx.trace:
        snap = obs.registry().snapshot()
        obs.disable()
        trace = dict(ctx.stop_trace(), window_s=window_s)
    mem = ctx.memory_peak()
    summary = trainer.telemetry.finish()
    window_losses = trainer.losses[s0:]
    failed = int(sum(not np.isfinite(x) for x in window_losses))
    total_steps = trainer.s
    trainer.free_state()
    del trainer

    ref = reference(conf, tr, ctx.seeds[0], ctx.seeds[1])
    checks = compare(checked, ref, tr["limits"])
    checks["telemetry_commits_missing"] = {
        "value": total_steps - summary["commits"], "limit": 0}
    checks["telemetry_steps_missing"] = {
        "value": total_steps - round(summary["metrics"].get("steps", 0)),
        "limit": 0}
    checks["window_steps_failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    tokens = steps_done * tr["global_batch"] * tr["seq"]
    e2e = {"setup_s": setup_s}
    if steps_done:
        e2e["train_tokens_per_s"] = tokens / window_s
    return {
        "correct": correct, "attempted": steps_done, "failed": failed,
        "checks": checks, "e2e": e2e, "memory_peak_bytes": mem,
        "window": {"seconds": window_s, "steps": steps_done,
                   "tokens": tokens, "seq": tr["seq"],
                   "arch": arch_of(conf)},
        "window_compiles": window_compiles, "obs": snap, "trace": trace,
    }
