"""The MLA and sigmoid-routed MoE layers of the program against the plain
reference (bench/reference/mla_moe_lm.py), at a small size on the CPU,
in float32 on seeded weights; the expert share, the selection bias and
the exact integer sum of the loads through the INC ring."""
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

from benchutil import BENCH, ROOT, child_env

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from reference import mla_moe_lm as R  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import ffn  # noqa: E402

D, H, RANK, NOPE, ROPE, VD = 64, 4, 32, 16, 8, 16
E, K, FF, SCALE = 16, 6, 32, 2.446
A = {"qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "rope_theta": 5e4,
     "latent_norm_eps": 1e-6, "num_experts_per_tok": K,
     "routed_scaling_factor": SCALE, "expert_rank": 0}


def f32(tree):
    return jax.tree.map(lambda l: l.astype(jnp.float32), tree)


def x_of(seed, s, b=2):
    return jax.random.normal(jax.random.key(seed), (b, s, D), jnp.float32)


def moe_params(held, offset, seed=5):
    return f32(ffn.init_sigmoid_moe(jax.random.key(seed), D, FF, E, held,
                                    offset, n_shared=2))


def program_moe(p, x, held, offset, tile=8):
    return ffn.sigmoid_moe(p, x, top_k=K, scaling=SCALE, held=held,
                           offset=offset, tile=tile)


def reference_moe(p, x, ids, held, rank=0):
    return R._moe(x, p, ids, {**A, "n_routed_experts": held,
                              "expert_rank": rank}, False)


@pytest.mark.parametrize("seq", [32, 4096])
def test_mla_forward_matches_the_reference(seq):
    """Below 2,048 positions one masked product, above it the chunked
    causal attention with rematerialised chunks."""
    p = f32(attn.init_mla(jax.random.key(1), D, H, RANK, NOPE, ROPE, VD))
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.key(2), (RANK,))
    x = x_of(3, seq, b=1)
    got = attn.mla_train(p, x, nope=NOPE, rope=ROPE, theta=5e4,
                         positions=jnp.arange(seq))
    want = R._mla(x, p, A, min(seq, 512), False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("bias", ["zero", "moves_the_choice"])
def test_moe_layer_matches_the_reference(bias):
    """Sigmoid scores, selection on scores plus bias with gates from the
    scores alone, 2.446 x the renormalised gates, two shared experts; the
    output and the gradients of the held experts, router and input."""
    p = moe_params(held=E, offset=0)
    x = x_of(4, 24)
    if bias == "moves_the_choice":
        p["router_bias"] = jnp.linspace(-0.3, 0.3, E)
    y, aux, st = program_moe(p, x, E, 0)
    y_ref, aux_ref, differ = reference_moe(p, x, st["ids"], E)
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(aux, aux_ref, rtol=1e-5)
    assert int(differ) == 0
    plain = program_moe({**p, "router_bias": jnp.zeros(E)}, x, E, 0)[2]
    assert (bias == "zero") == bool(jnp.all(plain["ids"] == st["ids"]))

    r = jax.random.normal(jax.random.key(9), y.shape)
    ids = st["ids"]

    def loss(fn):
        return lambda p, x: jnp.sum(fn(p, x) * r)
    g = jax.grad(loss(lambda p, x: program_moe(p, x, E, 0)[0]),
                 argnums=(0, 1))(p, x)
    g_ref = jax.grad(loss(lambda p, x: reference_moe(p, x, ids, E)[0]),
                     argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("tile", [4, 64])
def test_no_token_is_dropped_when_every_token_picks_one_expert(tile):
    """A bias that sends every token to the first held expert: all of its
    rows are computed, in as many tiles as they need."""
    p = moe_params(held=4, offset=4)
    p["router_bias"] = jnp.zeros(E).at[4].set(10.0)
    x = x_of(6, 40)
    y, _, st = program_moe(p, x, 4, 4, tile=tile)
    ids = np.asarray(st["ids"])
    assert np.all(np.any(ids == 4, axis=-1))
    held = int(np.sum((ids >= 4) & (ids < 8)))
    routed, on_held, computed = (int(v) for v in st["rows"])
    assert (routed, on_held) == (ids.size, held)
    assert int(st["held_max"]) == x.shape[0] * x.shape[1]
    counts = np.bincount(ids.reshape(-1), minlength=E)[4:8]
    assert computed == int(np.sum(-(-counts // tile))) * tile
    y_ref, _, _ = reference_moe(p, x, st["ids"], 4, rank=1)
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 experts each, drawn by global index: each holds
    the uncut layer's experts, and their held-expert parts, with the
    shared experts counted once, add up to the uncut reference layer."""
    full = moe_params(held=E, offset=0)
    x = x_of(7, 32)
    ids = program_moe(full, x, E, 0)[2]["ids"]
    shared = ffn.swiglu(full["shared"], x.reshape(-1, D)).reshape(x.shape)
    total = shared
    for rank in range(4):
        share = moe_params(held=4, offset=4 * rank)
        for name in ("w1", "w3", "w2"):
            np.testing.assert_array_equal(
                share["experts"][name],
                full["experts"][name][4 * rank:4 * rank + 4])
        y, _, st = program_moe(share, x, 4, 4 * rank)
        np.testing.assert_array_equal(st["ids"], ids)
        total = total + (y - shared)
    y_ref, _, _ = reference_moe(full, x, ids, E)
    np.testing.assert_allclose(total, y_ref, rtol=2e-4, atol=5e-5)


def test_the_step_moves_the_bias_by_the_load_rule_alone():
    """One train step of a small moonlight: the selection bias moves by
    gamma x sign(mean load - load) of the step's own loads, exactly, and
    AdamW's moments of it stay zero."""
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import ShapeConfig, get_arch
    from repro.core.inc_agg import IncAggConfig
    from repro.data import pipeline
    from repro.launch import steps
    from repro.optim.adamw import AdamWConfig
    cfg = replace(get_arch("moonlight-16b-a3b").reduced(), n_experts=8,
                  expert_parallel=2)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    prog = steps.build_train_step(
        cfg, ShapeConfig("t", 32, 2, "train"), mesh,
        inc=IncAggConfig(mode="netrpc", precision=8),
        opt_cfg=AdamWConfig(warmup_steps=1))
    params, opt = steps.init_state(prog, cfg, jax.random.key(1))
    batch = pipeline.make_batch(pipeline.DataConfig(
        vocab=cfg.vocab, batch=2, seq_len=32, seed=2, kind="zipf"), 0)
    b0 = np.asarray(params["groups"][1]["s0"]["moe"]["router_bias"])
    params, opt, m = prog.fn(params, opt, batch, jnp.int32(1))
    ids = np.asarray(m["moe_ids"])                     # (B, L, S, k)
    load = np.stack([np.bincount(ids[:, i].reshape(-1), minlength=8)
                     for i in range(ids.shape[1])])
    np.testing.assert_array_equal(np.asarray(m["moe_load"]), load)
    want = b0 + cfg.bias_update_rate * np.sign(
        load.mean(-1, keepdims=True) - load)
    got = params["groups"][1]["s0"]["moe"]["router_bias"]
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(opt["master"]["groups"][1]["s0"]["moe"]["router_bias"]),
        np.asarray(got))
    for moment in ("m", "v"):
        assert not np.any(np.asarray(
            opt[moment]["groups"][1]["s0"]["moe"]["router_bias"]))


def test_loads_sum_exactly_over_four_devices_through_the_int_ring():
    proc = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "sum_counts_4dev.py")],
        cwd=ROOT, env=child_env(4), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SUM_COUNTS_EXACT" in proc.stdout


def test_layers_name_their_scopes():
    """The spans the device trace shows the layers by."""
    p = f32(attn.init_mla(jax.random.key(1), D, H, RANK, NOPE, ROPE, VD))
    x = x_of(3, 16, b=1)
    txt = jax.jit(lambda p, x: attn.mla_train(
        p, x, nope=NOPE, rope=ROPE, theta=5e4,
        positions=jnp.arange(16))).lower(p, x).as_text(debug_info=True)
    assert "mla/" in txt
    txt = jax.jit(lambda p, x: program_moe(p, x, E, 0)[0]).lower(
        moe_params(E, 0), x_of(4, 8)).as_text(debug_info=True)
    for scope in ("moe.route/", "moe.experts/", "moe.shared/"):
        assert scope in txt, scope


def test_read_step_counts_the_routing_only_with_the_registry_on():
    from repro import obs
    from repro.launch import steps
    m = {"loss": jnp.float32(2.5), "moe_rows": jnp.array([[60, 10, 16],
                                                         [60, 7, 8]]),
         "moe_held_max": jnp.array([6, 4])}
    meta = {"moe_layers": [1, 2], "moe_load_elems": 128}
    obs.reset()
    assert steps.read_step(m, meta) == 2.5
    assert not obs.registry().snapshot()["counters"]
    obs.enable(metrics=True)
    try:
        assert steps.read_step(m, meta) == 2.5
        got = obs.registry().snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    assert got == {
        'inc_moe_routed_rows_total{layer="1"}': 60,
        'inc_moe_held_rows_total{layer="1"}': 10,
        'inc_moe_computed_rows_total{layer="1"}': 16,
        'inc_moe_held_max_rows_total{layer="1"}': 6,
        'inc_moe_routed_rows_total{layer="2"}': 60,
        'inc_moe_held_rows_total{layer="2"}': 7,
        'inc_moe_computed_rows_total{layer="2"}': 8,
        'inc_moe_held_max_rows_total{layer="2"}': 4,
        'inc_agg_int_elems_total{what="moe_load"}': 128}
