"""Data-parallel training of an MLA + sigmoid-routed MoE model (one
expert share) with INC gradient aggregation.

Set-up, window and check follow ``dp_train``: one compiled step with its
state through the program's own entry points (``steps.build_train_step``
with the configuration's ``IncAggConfig``, ``steps.init_state``), a
``TrainTelemetry`` on the INC runtime, the per-step sequence of
``launch/train.py:train_loop`` with its loss read through
``steps.read_step`` (which also counts the routing when the obs registry
is on), and the first ``checked_steps`` steps run in set-up through that
same sequence. Those steps also hand over, to the host, which experts
every token chose (the step's ``moe_ids``): the plain reference
(``bench/reference/mla_moe_lm.py``) takes that choice and computes the
rest itself. The compared numbers are ``dp_train``'s three and
``route_mismatch``, the share of token slots where the reference's own
choice differs from the program's.

The configuration file holds the chip's share of an expert-parallel
deployment: ``n_routed_experts`` experts held of a router
``expert_parallel`` times as wide, the vocabulary's slice, and the depth.
"""
from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

import jax
import jax.numpy as jnp

from drivers.dp_train import compare, leaf_norms

ARCH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "num_hidden_layers", "first_k_dense_replace", "vocab_size",
             "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
             "expert_parallel", "expert_rank", "routed_scaling_factor",
             "rope_theta", "rms_norm_eps", "latent_norm_eps", "aux_coef",
             "bias_update_rate")
COMPARED = ("loss_gap", "grad_norm_gap", "update_norm_gap")


def arch_of(conf: dict) -> dict:
    return {k: conf[k] for k in ARCH_KEYS}


def program_config(conf: dict, rehearse: bool):
    """The program's ArchConfig of the configuration: its registered model
    cut to the file's depth, expert share and vocabulary slice, whose
    widths must equal the file's."""
    from repro.configs.base import get_arch
    from repro.models.attention import MLA_LATENT_EPS
    a = arch_of(conf)
    n, dense = a["num_hidden_layers"], a["first_k_dense_replace"]
    cfg = replace(get_arch(conf["program_arch"]), n_layers=n,
                  pattern_groups=((("mla",), dense), (("mla_moe",), n - dense)),
                  vocab=a["vocab_size"],
                  n_experts=a["n_routed_experts"] * a["expert_parallel"],
                  expert_parallel=a["expert_parallel"],
                  expert_rank=a["expert_rank"])
    if rehearse:
        cfg = replace(cfg, d_model=a["hidden_size"],
                      dense_d_ff=a["intermediate_size"],
                      d_ff=a["moe_intermediate_size"],
                      n_heads=a["num_attention_heads"],
                      n_kv_heads=a["num_key_value_heads"],
                      kv_lora_rank=a["kv_lora_rank"],
                      qk_nope_head_dim=a["qk_nope_head_dim"],
                      qk_rope_head_dim=a["qk_rope_head_dim"],
                      v_head_dim=a["v_head_dim"],
                      top_k=a["num_experts_per_tok"])
    got = (cfg.d_model, cfg.dense_d_ff, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads,
           cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
           cfg.v_head_dim, cfg.vocab_padded, cfg.experts_held,
           cfg.n_shared_experts, cfg.top_k, cfg.routed_scaling,
           cfg.rope_theta, cfg.norm_eps, MLA_LATENT_EPS, cfg.aux_coef,
           cfg.bias_update_rate, cfg.tie_embeddings)
    want = (a["hidden_size"], a["intermediate_size"],
            a["moe_intermediate_size"], a["num_attention_heads"],
            a["num_key_value_heads"], a["kv_lora_rank"],
            a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"],
            a["vocab_size"], a["n_routed_experts"], a["n_shared_experts"],
            a["num_experts_per_tok"], a["routed_scaling_factor"],
            float(a["rope_theta"]), a["rms_norm_eps"], a["latent_norm_eps"],
            a["aux_coef"], a["bias_update_rate"],
            conf["tie_word_embeddings"])
    if got != want:
        raise RuntimeError(f"program config {got} differs from {want}")
    routing = (conf["scoring_func"], conf["topk_method"], conf["n_group"],
               conf["norm_topk_prob"])
    if routing != ("sigmoid", "noaux_tc", 1, True):
        raise RuntimeError(f"routing {routing} is not the mla_moe block's")
    return cfg


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
            seed=data_seed, kind="zipf", zipf_a=self.tr["zipf_a"])
        self.s = 0
        self.losses: list[float] = []
        self.last = None

    def step(self) -> float:
        """One step of ``train_loop``'s sequence."""
        ctx, s = self.ctx, self.s
        with ctx.span("bench.batch"):
            b = self.pipeline.make_batch(self.dcfg, s)
        with ctx.span("bench.step"):
            self.params, self.opt, m = self.prog.fn(self.params, self.opt,
                                                    b, jnp.int32(s))
            loss = self.steps.read_step(m, self.prog.meta)
        self.telemetry.push({"loss_sum": loss, "steps": 1,
                             "gnorm_sum": float(m["gnorm"])})
        for _ in range(self.n_dp):
            self.telemetry.vote(s)
        self.losses.append(loss)
        self.last = m
        self.s += 1
        return loss

    def delta_norms(self) -> list[float]:
        """Per-leaf norm of the float32 master's change since the initial
        weights, on the host (as ``dp_train``)."""
        return [float(np.linalg.norm(np.asarray(m, np.float64)
                                     - w.astype(np.float64)))
                for m, w in zip(jax.tree.leaves(self.opt["master"]),
                                self.w0)]

    def checked_steps(self, n: int) -> dict:
        """Run the first ``n`` steps and take the check's readings, with
        each step's expert choice. The seconds the readings take are the
        benchmark's own and are kept in ``check_s``."""
        grad_norms, ids = None, []
        for i in range(n):
            self.step()
            t0 = time.perf_counter()
            ids.append(np.asarray(self.last["moe_ids"]))
            if i == 0:
                b1 = self.opt_cfg.b1
                grad_norms = [x / (1 - b1) for x in leaf_norms(self.opt["m"])]
            self.check_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        delta = self.delta_norms()
        self.w0 = None
        self.check_s += time.perf_counter() - t0
        return {"losses": list(self.losses), "grad_norms": grad_norms,
                "delta_norms": delta, "ids": ids}

    def free_state(self) -> None:
        for leaf in jax.tree.leaves((self.params, self.opt, self.last)):
            leaf.delete()
        self.params = self.opt = self.last = None


def reference(conf: dict, tr: dict, weight_seed: int, data_seed: int, ids,
              control: bool = False) -> dict:
    """The plain reference's readings of the checked steps, given the
    program's expert choice ``ids`` of each."""
    from reference import mla_moe_lm
    return mla_moe_lm.run(
        arch_of(conf), conf["optimizer"], weight_seed=weight_seed,
        data_seed=data_seed, batch=tr["global_batch"], seq=tr["seq"],
        micro=tr["rows_per_rank"], wire_precision=conf["inc"]["precision"],
        ids=ids, steps=tr["checked_steps"], q_chunk=tr["ref_q_chunk"],
        row_chunk=tr["ref_row_chunk"], zipf_a=tr["zipf_a"], control=control)


def checks_of(prog: dict, ref: dict, limits: dict) -> dict:
    """``dp_train``'s comparison, and ``route_mismatch``, each under its
    limit; as in ``compare``, only the numbers ``limits`` names."""
    out = compare(prog, ref, {k: v for k, v in limits.items()
                              if k in COMPARED})
    if "route_mismatch" in limits:
        out["route_mismatch"] = {"value": ref["route_mismatch"],
                                 "limit": limits["route_mismatch"]}
    return out


def readings(prog: dict, ref: dict) -> dict:
    """Every number ``checks_of`` compares, with no limit applied."""
    return {k: v["value"] for k, v in checks_of(
        prog, ref, dict.fromkeys(COMPARED + ("route_mismatch",))).items()}


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

    ref = reference(conf, tr, ctx.seeds[0], ctx.seeds[1], checked["ids"])
    checks = checks_of(checked, ref, tr["limits"])
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
