"""Train / prefill / decode step builders — the INC data plane wired into
the model zoo.

Every step is a single jit(shard_map(...)) that is MANUAL over the data-
parallel mesh axes (("pod","data") or ("data",)) and AUTO over 'model'
(GSPMD tensor parallelism). The paper's SyncAgtr pipeline is the gradient
aggregation path:

  zero1  params bf16 replicated over dp; local grads accumulate over
         microbatches; each leaf is INC reduce-scattered along its scatter
         dim (quantize -> per-hop saturating Map.addTo ring -> dequant +
         overflow fallback); AdamW updates this rank's fp32 chunk (ZeRO-1);
         the updated leaf is rebuilt by the INC all-gather.
  fsdp   params stored dp-scattered (grok-314b, llama-90b); each layer's
         leaves are gathered inside the scan via a custom_vjp whose
         BACKWARD is the INC reduce-scatter — the paper's technique runs
         inside backprop, per layer, overlappable with compute. The
         optimizer consumes the already-scattered grads; no re-gather of
         the full model ever materializes.

Serve steps use plain gathers (no gradient stream); decode is either
batch-sharded (cache rows per rank) or sequence-sharded (long_500k: the
flash-decoding partial-softmax combine in models/attention.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.api as inc
from repro.api import DrainPolicy, IncFuture, IncRuntime
from repro.obs import hooks as _obs
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import inc_agg
from repro.core.inc_agg import IncAggConfig
from repro.models import api
from repro.optim import adamw
from repro.sharding import rules

SEQ_SHARDED_BLOCKS = ("global", "moe", "selfcross")

# ---------------------------------------------------------------------------
# INC telemetry: the loop's metric + agreement channels on the async runtime
# ---------------------------------------------------------------------------

# fixed-point digits for metric scalars; milli-precision keeps long-run
# accumulated sums far from the int32 saturation sentinels of the register
# path (scaled values must stay << 2**31)
METRIC_PRECISION = 3


def telemetry_service(app: str, local_accum: int = 1):
    """The loop's metric stream as an AsyncAgtr app: per-step scalars ride
    Map.addTo (summed in-network), monitors read them back with Map.get.
    A typed schema class parameterized by AppName (one channel per loop).
    ``local_accum=N`` folds N pushes client-side into one switch-bound
    update (metrics are latency-insensitive, the natural fold target).
    Annotations are assigned explicitly: this module postpones
    annotations, so a closure-parameterized spec inside a decorated class
    body would not resolve."""
    def PushMetrics(self, kvs): ...
    PushMetrics.__annotations__ = {
        "kvs": inc.Agg[inc.STRINTMap](precision=METRIC_PRECISION,
                                      local_accum=local_accum),
        "return": {"msg": inc.Plain}}
    PushMetrics = inc.rpc(request_msg="MetricPush")(PushMetrics)

    def ReadMetrics(self, kvs): ...
    ReadMetrics.__annotations__ = {
        "kvs": inc.ReadMostly[inc.STRINTMap](precision=METRIC_PRECISION)}
    ReadMetrics = inc.rpc(reply_msg="MetricReply")(ReadMetrics)

    cls = type("Telemetry", (), {"PushMetrics": PushMetrics,
                                 "ReadMetrics": ReadMetrics})
    return inc.service(app=app, name="Telemetry")(cls)


# fixed-point digits for gradient elements on the device-resident grad
# channel: micro precision keeps the quantization error below bf16 ULP for
# O(1) gradients while a full dp-group's summed elements stay well under
# the int32 saturation sentinels (n_dp * 1e6 * |g| << 2**31)
GRAD_PRECISION = 6


def gradient_service(app: str):
    """Cross-loop gradient aggregation as a device-resident SyncAgtr app:
    flat fp32 gradient blocks ride Map.addTo (summed in-network through
    the fused quantize+saturating-add Pallas kernel on the DeviceSegment
    register file), and every push's Get reply is the running sum as a
    device-resident fp32 jax array (fused gather+dequantize) — gradients
    flow back into the train step without a host round trip. clear="copy"
    makes each aggregation round independent (the reply is the backup)."""
    @inc.service(app=app, name="GradAggregate")
    class GradAggregate:
        @inc.rpc(request_msg="GradPush")
        def PushGrads(self, grads: inc.Agg[inc.FPArray](
                precision=GRAD_PRECISION, clear="copy", device=True)
                ) -> {"grads": inc.Get[inc.FPArray]}: ...
    return GradAggregate


def agreement_service(threshold: int, app: str):
    """Step-commit quorum as an Agreement app: the threshold-th worker vote
    for a step key forwards exactly one commit notification (CntFwd)."""
    @inc.service(app=app, name="StepAgreement")
    class StepAgreement:
        @inc.rpc(cnt_fwd=inc.CntFwd(to="ALL", threshold=threshold,
                                    key="CommitVote.kvs"))
        def CommitStep(self, kvs: inc.STRINTMap) -> {"msg": inc.Plain}: ...
    return StepAgreement


class TrainTelemetry:
    """Metric + agreement channels for the train/serve loops, batched.

    The hot path calls push()/vote(), which enqueue on the async runtime
    through the typed stubs and return immediately: the scheduler
    coalesces many steps' worth of metric pushes into one drained
    pipeline batch (no N=1 INC call ever runs on the step path). read()
    resolves its ReadMetrics future in place; the query rides the same
    channel queue, so FIFO order keeps reads consistent with every push
    issued before them.
    """

    def __init__(self, runtime: IncRuntime | None = None, *,
                 n_workers: int = 1, quorum: float = 1.0,
                 app_prefix: str = "train", grad_slots: int = 0,
                 local_accum: int = 1):
        # telemetry is latency-insensitive: a generous time trigger lets
        # many steps' pushes coalesce into each drained batch (reads still
        # see everything — the inline ReadMetrics call flushes first).
        # local_accum=N goes further: N metric pushes fold client-side
        # into ONE switch-bound update before they even join the queue
        # (reads stay consistent — the promote-before-read barrier flushes
        # open folds first).
        self.rt = runtime or IncRuntime(policy=DrainPolicy(
            max_batch=64, max_delay=0.25, eager_window=False))
        self._own_rt = runtime is None
        self.threshold = max(1, int(round(quorum * n_workers)))
        self.rt.server.register("CommitStep", self._on_commit)
        self.metrics = self.rt.make_stub(
            telemetry_service(f"{app_prefix}-metrics",
                              local_accum=local_accum))
        self.agree = self.rt.make_stub(
            agreement_service(self.threshold, f"{app_prefix}-agree"))
        # device-resident gradient channel (opt-in by capacity): pushes
        # quantize/aggregate on device, replies are fp32 jax arrays
        self.grads = None
        if grad_slots:
            self.grads = self.rt.make_stub(
                gradient_service(f"{app_prefix}-grads"), n_slots=grad_slots)
        self._names: set[str] = set()
        # O(1) vote accounting: CntFwd invokes the CommitStep handler
        # exactly once per quorum, inside the (plane-serialized) pipeline
        # pass — so counting there needs no retained futures. Only the
        # most recent vote future is kept: per-channel resolution is FIFO,
        # so once it resolves, every earlier vote's pipeline pass (and its
        # handler-side count) has completed.
        self._commits = 0
        self._last_vote: IncFuture | None = None

    def _on_commit(self, req: dict) -> dict:
        self._commits += 1
        if _obs.METRICS:
            inc.metrics().counter("train_commits_total").inc()
        return {"msg": "commit"}

    def push(self, scalars: dict[str, float]) -> IncFuture:
        """Accumulate metric scalars in-network; returns the push future."""
        self._names.update(scalars)
        kvs = {k: float(v) for k, v in scalars.items()}
        if _obs.METRICS:
            inc.metrics().counter("train_metric_pushes_total").inc()
        return self.metrics.PushMetrics(kvs=kvs)

    def vote(self, step: int) -> IncFuture:
        """Cast this worker's commit vote for ``step``; the future's reply
        is non-empty iff this vote completed the quorum."""
        if _obs.METRICS:
            inc.metrics().counter("train_votes_total").inc()
        f = self.agree.CommitStep(kvs={f"step-{step}": 1})
        self._last_vote = f
        return f

    def push_grads(self, flat_grad) -> IncFuture:
        """Accumulate one flat fp32 gradient block in-network (device
        lane); the reply's ``grads`` is the aggregated block as a
        device-resident fp32 jax array, cleared for the next round."""
        if self.grads is None:
            raise RuntimeError("TrainTelemetry built without grad_slots; "
                               "pass grad_slots=<flat gradient length>")
        if _obs.METRICS:
            reg = inc.metrics()
            n = int(getattr(flat_grad, "size", len(flat_grad)))
            reg.counter("train_grad_pushes_total").inc()
            reg.counter("train_grad_elems_total").inc(n)
            reg.histogram("train_grad_block_elems",
                          buckets=_obs._N).observe(n)
        return self.grads.PushGrads(grads=flat_grad)

    def aggregate_gradients(self, grads):
        """Aggregate a gradient pytree through the device channel: leaves
        flatten into one fp32 block, one PushGrads round-trips it through
        the fused quantize -> Map.addTo -> dequantize path, and the reply
        splits back into the tree — every array stays a jax array, so the
        result feeds a train step's optimizer without leaving the device.
        Quantization is GRAD_PRECISION fixed-point (the SyncAgtr wire
        format), so values round to 1e-6 like the in-network ring would."""
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])
        out = self.push_grads(flat).result()["grads"]
        parts, pos = [], 0
        for l in leaves:
            n = int(l.size)
            parts.append(out[pos:pos + n].reshape(l.shape))
            pos += n
        return jax.tree_util.tree_unflatten(treedef, parts)

    def read(self, names=None) -> dict[str, float]:
        """Read accumulated metrics (queued pushes execute first: the
        read rides the same channel queue, and result() demand-flushes)."""
        keys = {k: 0 for k in (names or sorted(self._names))}
        if not keys:
            return {}
        out = self.metrics.ReadMetrics(kvs=keys).result()
        return {k: float(v) for k, v in out.get("kvs", {}).items()}

    def commits(self) -> int:
        """Quorum notifications among the votes cast so far (waits for the
        last vote, which implies every earlier one resolved)."""
        if self._last_vote is not None:
            self._last_vote.exception()     # block until resolved
        return self._commits

    def finish(self) -> dict:
        """Flush, summarize, and (if owned) stop the runtime. With obs
        metrics enabled the summary carries the full ``repro.obs/v1``
        snapshot (per-channel latency quantiles, registry metrics)."""
        summary = {"metrics": self.read(),
                   "commits": self.commits(),
                   "scheduling": self.rt.scheduling_report()}
        if _obs.METRICS:
            summary["obs"] = self.rt.metrics_snapshot()
        if self._own_rt:
            self.rt.close()
        return summary


# ---------------------------------------------------------------------------
# scatter-dim bookkeeping
# ---------------------------------------------------------------------------

def scatter_dims_tree(params_shapes, n_dp: int, n_model: int):
    """Pytree of ints matching params: the dp-scatter dim per leaf, -1 if
    the leaf has none (small norms/biases -> psum + replicated opt state)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    vals = []
    for path, leaf in flat:
        t = rules.tp_dim(path, leaf.shape, n_model)
        f = rules.fsdp_dim(path, leaf.shape, n_dp, t)
        vals.append(-1 if f is None else f)
    return jax.tree_util.tree_unflatten(treedef, vals)


def _with_dp_dim(spec: P, dim: int, dp_axes: tuple[str, ...]) -> P:
    entries = list(spec) + [None] * (dim + 1 - len(spec))
    entries[dim] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    return P(*entries)


def opt_specs(pspecs, dims, dp_axes):
    """Optimizer-state partition specs: param spec + dp sharding on the
    scatter dim (full-shape fp32 master/m/v, globally sharded)."""
    return jax.tree.map(
        lambda s, d: _with_dp_dim(s, d, dp_axes) if d >= 0 else s,
        pspecs, dims, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# FSDP gather (custom_vjp: backward = the INC reduce-scatter)
# ---------------------------------------------------------------------------

def _make_gather(dim: int, dp_axes: tuple[str, ...], inc: IncAggConfig):
    @jax.custom_vjp
    def g(x):
        return inc_agg.all_gather_dim(x, dim, dp_axes, inc)

    def fwd(x):
        return g(x), None

    def bwd(_, ct):
        return (inc_agg.reduce_scatter_dim(ct, dim, dp_axes, inc),)

    g.defvjp(fwd, bwd)
    return g


def make_param_gather(dims: dict, dp_axes, inc: IncAggConfig) -> Callable:
    """Hook for Ctx.param_gather: gathers one layer-slice of stacked params
    (the slice has lost the stack dim, so scatter dims shift by -1)."""
    def hook(scope: str, gi: int, pslice):
        dtree = (dims["groups"][gi] if scope == "groups"
                 else dims["enc"]["blocks"])
        def one(leaf, d):
            if d < 1:      # -1: not scattered; 0 impossible (stack dim)
                return leaf
            return _make_gather(d - 1, dp_axes, inc)(leaf)
        return jax.tree.map(one, pslice, dtree)
    return hook


def gather_unstacked(params: dict, dims: dict, dp_axes,
                     inc: IncAggConfig) -> dict:
    """Gather the non-stacked leaves (embed, lm_head, final_norm, ...)."""
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_d = jax.tree_util.tree_flatten(
        dims, is_leaf=lambda x: isinstance(x, int))[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for (path, leaf), d in zip(flat_p, flat_d):
        if d >= 0 and not rules._is_stacked(path):
            leaf = _make_gather(d, dp_axes, inc)(leaf)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# microbatching
# ---------------------------------------------------------------------------

def default_n_micro(cfg: ArchConfig, shape: ShapeConfig, n_dp: int,
                    budget_bytes: float = 2e9) -> int:
    """Pick n_micro so per-device remat boundary memory fits the budget."""
    local_b = max(shape.global_batch // n_dp, 1)
    per_layer = local_b * shape.seq_len * cfg.d_model * 2
    total = per_layer * (cfg.n_layers + cfg.enc_layers)
    n = 1
    while total / n > budget_bytes and n < local_b:
        n *= 2
    return n


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

@dataclass
class Program:
    """A lowered-able step: fn is jit-wrapped with shardings attached."""
    fn: Any
    arg_specs: tuple              # ShapeDtypeStructs (global shapes)
    mesh: Any
    meta: dict

    def lower(self):
        return self.fn.lower(*self.arg_specs)


def _batch_specs(cfg: ArchConfig, shape: ShapeConfig, dp) -> dict:
    sp = {"tokens": P(dp)}
    if cfg.family == "vlm":
        sp["patches"] = P(dp)
    if cfg.is_encdec:
        sp["frames"] = P(dp)
    return sp


def build_train_step(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                     inc: IncAggConfig, opt_cfg: adamw.AdamWConfig,
                     n_micro: int | None = None, mode: str | None = None,
                     donate: bool = True) -> Program:
    mode = mode or rules.mode_for(cfg.name)
    manual = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    axes = rules.MeshAxes(data=manual)
    n_dp, n_model = axes.sizes(mesh)
    if n_micro is None:
        n_micro = default_n_micro(cfg, shape, n_dp)
    local_b = shape.global_batch // n_dp
    assert local_b % n_micro == 0, (local_b, n_micro)

    params_shapes = jax.eval_shape(partial(api.init_params, cfg=cfg),
                                   jax.random.key(0))
    dims = scatter_dims_tree(params_shapes, n_dp, n_model)
    pspecs = rules.param_specs(params_shapes, axes, mesh, mode)
    ospecs = opt_specs(pspecs, dims, manual)
    bspecs = _batch_specs(cfg, shape, manual)
    dp_spec = manual if len(manual) > 1 else manual[0]

    p_manual = rules.manual_specs(pspecs, manual)
    o_manual = rules.manual_specs(ospecs, manual)

    hook = (make_param_gather(dims, manual, inc) if mode == "fsdp" else None)
    routing = _Routing(cfg, params_shapes) if api.has_routing_stats(cfg) \
        else None

    def loss_fn(p, mb):
        if mode == "fsdp":
            p = gather_unstacked(p, dims, manual, inc)
        loss, metrics = api.train_loss(p, cfg, mb, remat=True,
                                       param_gather=hook)
        return loss, metrics

    def body(params, opt, batch, step_idx):
        # ---- local grads over microbatches -------------------------------
        mb_batch = jax.tree.map(
            lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                *x.shape[1:]), batch)
        g0 = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), params)

        def micro(carry, mb):
            gacc, lacc = carry
            (loss, met), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            return (gacc, lacc + loss), (met["moe"] if routing else None)

        (grads, loss_sum), moe = jax.lax.scan(micro, (g0, 0.0), mb_batch)
        inv = 1.0 / (n_micro * n_dp)
        loss = jax.lax.psum(loss_sum / n_micro, manual) / n_dp
        if routing:
            moe = routing.totals(moe, manual)

        # ---- INC aggregation over dp (SyncAgtr) ---------------------------
        flat_g = jax.tree_util.tree_flatten(grads)[0]
        flat_d = jax.tree_util.tree_flatten(
            dims, is_leaf=lambda x: isinstance(x, int))[0]
        treedef = jax.tree_util.tree_structure(grads)
        agg = []
        for i, (g, d) in enumerate(zip(flat_g, flat_d)):
            if routing and i in routing.bias_sites:
                agg.append(None)             # state: no gradient stream
            elif mode == "fsdp" and d >= 0:
                agg.append(g * inv)          # scattered+summed in backward
            elif d >= 0:
                agg.append(inc_agg.reduce_scatter_dim(g, d, manual, inc)
                           * inv)
            else:
                agg.append(jax.lax.psum(g, manual) * inv)
        # ---- clip ---------------------------------------------------------
        sq_scat = sum(jnp.sum(jnp.square(g))
                      for g, d in zip(agg, flat_d) if d >= 0)
        sq_repl = sum(jnp.sum(jnp.square(g))
                      for g, d in zip(agg, flat_d) if d < 0 and g is not None)
        gnorm = jnp.sqrt(jax.lax.psum(sq_scat, manual) + sq_repl)
        factor = adamw.clip_factor(gnorm, opt_cfg.grad_clip)
        lr = adamw.schedule(opt_cfg, step_idx)

        # ---- AdamW on scattered chunks + param rebuild --------------------
        flat_m = jax.tree_util.tree_flatten(opt["master"])[0]
        flat_mm = jax.tree_util.tree_flatten(opt["m"])[0]
        flat_vv = jax.tree_util.tree_flatten(opt["v"])[0]
        flat_p = jax.tree_util.tree_flatten(params)[0]
        new_p, new_m, new_mm, new_vv = [], [], [], []
        for i, (g, d, ms, m1, v1, pl) in enumerate(zip(
                agg, flat_d, flat_m, flat_mm, flat_vv, flat_p)):
            if g is None:
                # the selection bias: one step of the load rule, no AdamW,
                # no weight decay, no clipping
                b = routing.bias_step(ms, moe["load_by_site"], i)
                new_p.append(b.astype(pl.dtype))
                new_m.append(b)
                new_mm.append(m1)
                new_vv.append(v1)
                continue
            st = adamw.adamw_leaf({"master": ms, "m": m1, "v": v1},
                                  g * factor, lr=lr, cfg=opt_cfg,
                                  step=step_idx, wd_on=adamw.decay_mask(ms))
            upd = st["master"].astype(pl.dtype)
            if d >= 0 and mode == "zero1":
                upd = inc_agg.all_gather_dim(upd, d, manual, inc)
            new_p.append(upd)
            new_m.append(st["master"])
            new_mm.append(st["m"])
            new_vv.append(st["v"])
        unf = partial(jax.tree_util.tree_unflatten, treedef)
        metrics = {"loss": loss, "gnorm": gnorm, "lr": lr}
        if routing:
            metrics.update({k: moe[k] for k in _Routing.OUTPUTS})
        return unf(new_p), {"master": unf(new_m), "m": unf(new_mm),
                            "v": unf(new_vv)}, metrics

    metric_specs = {"loss": P(), "gnorm": P(), "lr": P()}
    if routing:
        metric_specs.update(moe_load=P(), moe_rows=P(), moe_held_max=P(),
                            moe_ids=P(dp_spec))
    step = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_manual, {"master": o_manual, "m": o_manual,
                             "v": o_manual}, bspecs, P()),
        out_specs=(p_manual, {"master": o_manual, "m": o_manual,
                              "v": o_manual}, metric_specs),
        axis_names=set(manual), check_vma=False)

    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
    o_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                           is_leaf=lambda x: isinstance(x, P))
    b_shard = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}

    jitted = jax.jit(step,
                     in_shardings=(p_shard,
                                   {"master": o_shard, "m": o_shard,
                                    "v": o_shard}, b_shard, None),
                     donate_argnums=(0, 1) if donate else ())

    def opt_shapes(ps):
        f32 = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), ps)
        return {"master": f32, "m": f32, "v": f32}

    arg_specs = (
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=s), params_shapes, p_shard),
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=s), opt_shapes(params_shapes),
            {"master": o_shard, "m": o_shard, "v": o_shard}),
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=b_shard[k])
         for k, v in api.input_specs(cfg, shape).items()},
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    meta = {"mode": mode, "n_micro": n_micro, "n_dp": n_dp,
            "n_model": n_model, "manual": manual, "kind": "train",
            "param_shardings": p_shard, "opt_shardings": o_shard,
            "params_shapes": params_shapes, "dims": dims}
    if routing:
        meta["moe_layers"] = routing.layers
        meta["moe_load_elems"] = len(routing.layers) * cfg.n_experts
    return Program(fn=jitted, arg_specs=arg_specs, mesh=mesh, meta=meta)


class _Routing:
    """The train step's share of sigmoid-routed MoE blocks: their routing
    stats summed over microbatches and data-parallel ranks, and the
    selection bias moved by DeepSeek-V3's load rule,
    ``b += rate * sign(mean load - load)``, from the loads of every rank
    summed exactly through the INC integer ring."""

    OUTPUTS = ("moe_load", "moe_rows", "moe_held_max", "moe_ids")

    def __init__(self, cfg: ArchConfig, params_shapes):
        self.rate = cfg.bias_update_rate
        # (group, slot) of each MoE block, in stats order, and the model
        # layer of each of its repeats
        self.sites, self.layers, first = [], [], 0
        for gi, (pat, n) in enumerate(cfg.pattern_groups):
            for si, bt in enumerate(pat):
                if bt == "mla_moe":
                    self.sites.append((gi, f"s{si}"))
                    self.layers += [first + r * len(pat) + si
                                    for r in range(n)]
            first += n * len(pat)
        flat = jax.tree_util.tree_flatten_with_path(params_shapes)[0]
        # flat leaf index of each selection bias -> its (group, slot)
        self.bias_sites = {i: (path[1].idx, path[2].key)
                           for i, (path, _) in enumerate(flat)
                           if rules.is_state_leaf(path)}

    def totals(self, moe, manual) -> dict:
        """Per-microbatch stats (leaves (n_micro, n_rep, ...)) -> the
        step's outputs, layers concatenated in ``self.layers`` order."""
        per = [moe[gi][slot] for gi, slot in self.sites]
        cat = lambda k: jnp.concatenate([p[k] for p in per], 1)  # noqa: E731
        load = jnp.sum(cat("load"), 0)
        rows = jnp.sum(cat("rows"), 0)
        held_max = jnp.sum(cat("held_max"), 0)
        ids = cat("ids")                     # (n_micro, L, mb, S, k)
        n_micro, n_layers, mb = ids.shape[:3]
        ids = jnp.moveaxis(ids, 2, 1).reshape(n_micro * mb, n_layers,
                                              *ids.shape[3:])
        load = inc_agg.sum_counts(load, manual)
        by_site, at = {}, 0
        for (gi, slot), p in zip(self.sites, per):
            n = p["load"].shape[1]
            by_site[(gi, slot)] = load[at:at + n]
            at += n
        return {"moe_load": load, "load_by_site": by_site,
                "moe_rows": jax.lax.psum(rows, manual),
                "moe_held_max": jax.lax.psum(held_max, manual),
                "moe_ids": ids}

    def bias_step(self, b, load_by_site: dict, leaf: int) -> jax.Array:
        load = load_by_site[self.bias_sites[leaf]].astype(jnp.float32)
        return b + self.rate * jnp.sign(
            jnp.mean(load, axis=-1, keepdims=True) - load)


def read_step(m: dict, meta: dict) -> float:
    """The step's loss as a float. With the obs registry on, the step's
    MoE routing outputs come back in the same transfer and are counted:
    ``inc_moe_routed_rows_total``, ``inc_moe_held_rows_total``,
    ``inc_moe_computed_rows_total`` (padding included) and
    ``inc_moe_held_max_rows_total`` (the fullest held expert's rows) per
    model layer, and the elements of the integer load aggregation,
    ``inc_agg_int_elems_total{what="moe_load"}``."""
    if not (_obs.METRICS and "moe_rows" in m):
        return float(m["loss"])
    loss, rows, held_max = jax.device_get((m["loss"], m["moe_rows"],
                                           m["moe_held_max"]))
    reg = inc.metrics()
    for layer, r, h in zip(meta["moe_layers"], rows, held_max):
        lab = str(layer)
        reg.counter("inc_moe_routed_rows_total", layer=lab).inc(int(r[0]))
        reg.counter("inc_moe_held_rows_total", layer=lab).inc(int(r[1]))
        reg.counter("inc_moe_computed_rows_total", layer=lab).inc(int(r[2]))
        reg.counter("inc_moe_held_max_rows_total", layer=lab).inc(int(h))
    reg.counter("inc_agg_int_elems_total", what="moe_load").inc(
        meta["moe_load_elems"])
    return float(loss)


def init_state(program: Program, cfg: ArchConfig, rng=None):
    """Materialize params + optimizer state with the program's shardings
    (smoke scale / real-TPU; the dry-run never calls this)."""
    rng = rng if rng is not None else jax.random.key(0)
    p_shard = program.meta["param_shardings"]
    o_shard = program.meta["opt_shardings"]
    params = jax.jit(partial(api.init_params, cfg=cfg),
                     out_shardings=p_shard)(rng)
    master = jax.jit(lambda p: jax.tree.map(
        lambda l: l.astype(jnp.float32), p), out_shardings=o_shard)(params)
    zeros = jax.jit(lambda p: jax.tree.map(
        lambda l: jnp.zeros(l.shape, jnp.float32), p),
        out_shardings=o_shard)(params)
    zeros2 = jax.jit(lambda p: jax.tree.map(
        lambda l: jnp.zeros(l.shape, jnp.float32), p),
        out_shardings=o_shard)(params)
    return params, {"master": master, "m": zeros, "v": zeros2}


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def _cache_manual_specs(cfg: ArchConfig, shape: ShapeConfig, dp,
                        seq_sharded: bool, n_model: int = 1):
    """PartitionSpecs for the cache pytree: manual (dp) placement plus
    tensor-parallel sharding of the KV heads over 'model' (falling back to
    the head_dim when the kv-head count doesn\'t divide — a 32k cache per
    device otherwise dwarfs HBM). Returns (shapes, manual_specs,
    full_specs)."""
    cspecs = api.cache_specs(cfg, shape.global_batch, shape.seq_len)

    def specs_for(path, leaf):
        gi = path[0].idx
        si = int(str(getattr(path[1], "key", "s0"))[1:])
        key = str(getattr(path[2], "key", ""))
        bt = cfg.pattern_groups[gi][0][si]
        if not seq_sharded:
            manual = [None, dp] + [None] * (len(leaf.shape) - 2)
        elif bt in SEQ_SHARDED_BLOCKS and key in ("k", "v"):
            manual = [None, None, dp] + [None] * (len(leaf.shape) - 3)
        else:
            manual = [None] * len(leaf.shape)
        full = list(manual)
        if n_model > 1:
            if key in ("k", "v", "mk", "mv"):
                # (n, B, S, KV, hd): kv heads (3) else head_dim (4)
                if leaf.shape[3] % n_model == 0 and leaf.shape[3] >= n_model:
                    full[3] = "model"
                elif leaf.shape[4] % n_model == 0:
                    full[4] = "model"
            elif key == "state" and len(leaf.shape) >= 3 \
                    and leaf.shape[2] % n_model == 0 \
                    and leaf.shape[2] >= n_model:
                full[2] = "model"        # ssd heads / rglru width
        return P(*manual), P(*full)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cspecs)
    pairs = [specs_for(p, l) for p, l in flat]
    unf = partial(jax.tree_util.tree_unflatten, treedef)
    return cspecs, unf([a for a, _ in pairs]), unf([b for _, b in pairs])


def build_serve_step(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                     mode: str | None = None) -> Program:
    """Decode (one token, KV cache of seq_len) or prefill, per shape.kind."""
    mode = mode or rules.mode_for(cfg.name)
    manual = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    axes = rules.MeshAxes(data=manual)
    n_dp, n_model = axes.sizes(mesh)
    dp = manual if len(manual) > 1 else manual[0]

    params_shapes = jax.eval_shape(partial(api.init_params, cfg=cfg),
                                   jax.random.key(0))
    dims = scatter_dims_tree(params_shapes, n_dp, n_model)
    pspecs = rules.param_specs(params_shapes, axes, mesh, mode)
    p_manual = rules.manual_specs(pspecs, manual)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
    # serving gathers params with plain collectives (no gradient stream);
    # REPRO_QUANTIZED_GATHER=1 swaps in the int8 block-quantized gather
    # (halves per-token param-stream bytes for FSDP-stored models)
    import os as _os
    q8 = _os.environ.get("REPRO_QUANTIZED_GATHER") == "1"
    serve_inc = IncAggConfig(mode="xla-psum")

    def _serve_gather_leaf(leaf, d):
        if d < 0:
            return leaf
        if q8:
            return inc_agg.all_gather_dim_q8(leaf, d, manual)
        return inc_agg.all_gather_dim(leaf, d, manual, serve_inc)

    def hook_fn(scope, gi, pslice):
        dtree = (dims["groups"][gi] if scope == "groups"
                 else dims["enc"]["blocks"])
        return jax.tree.map(
            lambda l, d: _serve_gather_leaf(l, d - 1) if d >= 1 else l,
            pslice, dtree)

    hook = hook_fn if mode == "fsdp" else None

    def prep(p):
        if mode != "fsdp":
            return p
        flat_p = jax.tree_util.tree_flatten_with_path(p)[0]
        flat_d = jax.tree_util.tree_flatten(
            dims, is_leaf=lambda x: isinstance(x, int))[0]
        treedef = jax.tree_util.tree_structure(p)
        out = []
        for (path, leaf), d in zip(flat_p, flat_d):
            if d >= 0 and not rules._is_stacked(path):
                leaf = _serve_gather_leaf(leaf, d)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    if shape.kind == "prefill":
        bspecs = _batch_specs(cfg, shape, manual)

        def body(params, batch):
            return api.prefill(prep(params), cfg, batch, param_gather=hook)

        _, cache_manual, _ = _cache_manual_specs(cfg, shape, dp, False,
                                                 n_model)
        step = jax.shard_map(body, mesh=mesh,
                             in_specs=(p_manual, bspecs),
                             out_specs=(P(dp), cache_manual),
                             axis_names=set(manual), check_vma=False)
        b_shard = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        arg_specs = (
            jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=s), params_shapes, p_shard),
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=b_shard[k])
             for k, v in api.input_specs(cfg, shape).items()},
        )
        meta = {"mode": mode, "kind": "prefill", "n_dp": n_dp,
                "manual": manual, "param_shardings": p_shard}
        return Program(fn=jitted, arg_specs=arg_specs, mesh=mesh, meta=meta)

    # ---- decode -----------------------------------------------------------
    seq_sharded = shape.global_batch % n_dp != 0
    seq_axes = manual if seq_sharded else None
    cspecs, cache_manual, cache_full = _cache_manual_specs(
        cfg, shape, dp, seq_sharded, n_model)
    tok_spec = P() if seq_sharded else P(dp)

    def body(params, token, pos, cache):
        return api.decode_step(prep(params), cfg, token, pos, cache,
                               seq_axes=seq_axes, param_gather=hook)

    step = jax.shard_map(body, mesh=mesh,
                         in_specs=(p_manual, tok_spec, P(), cache_manual),
                         out_specs=(tok_spec, cache_manual),
                         axis_names=set(manual), check_vma=False)

    def cache_shard(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    c_shard = cache_shard(cache_full)
    jitted = jax.jit(step,
                     in_shardings=(p_shard, NamedSharding(mesh, tok_spec),
                                   None, c_shard),
                     donate_argnums=(3,))
    arg_specs = (
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=s), params_shapes, p_shard),
        jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=s), cspecs, c_shard),
    )
    meta = {"mode": mode, "kind": "decode", "n_dp": n_dp, "manual": manual,
            "seq_sharded": seq_sharded, "param_shardings": p_shard,
            "cache_shardings": c_shard}
    return Program(fn=jitted, arg_specs=arg_specs, mesh=mesh, meta=meta)
