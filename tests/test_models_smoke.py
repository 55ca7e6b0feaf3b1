"""Per-arch reduced-config smoke: one forward/train step on CPU asserting
output shapes + no NaNs, plus the prefill/decode consistency check (decode
with a prefilled cache must reproduce full-forward logits)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.all import ALL_ARCHS
from repro.configs.base import get_arch
from repro.data import pipeline
from repro.models import api

B, S = 2, 64


def make_batch(cfg, seq=S):
    k = jax.random.key(0)
    batch = {"tokens": jax.random.randint(k, (B, seq + 1), 0, cfg.vocab,
                                          jnp.int32)}
    return pipeline.add_modality_stubs(batch, cfg, B)


@pytest.fixture(scope="module")
def zoo():
    return {name: get_arch(name).reduced() for name in ALL_ARCHS}


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_train_step_shapes_and_finite(zoo, name):
    cfg = zoo[name]
    params = api.init_params(jax.random.key(1), cfg)
    batch = make_batch(cfg)
    loss, metrics = jax.jit(
        lambda p, b: api.train_loss(p, cfg, b))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), name
    logits, aux = jax.jit(
        lambda p, b: api.forward(p, cfg, b))(
        params, {**batch, "tokens": batch["tokens"][:, :-1]})
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), name


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_decode_consistent_with_forward(zoo, name):
    """prefill(tokens[:-1]) + decode(tokens[-1]) == forward(tokens)[-1]."""
    cfg = zoo[name]
    params = api.init_params(jax.random.key(2), cfg)
    batch = make_batch(cfg)
    toks = batch["tokens"][:, :S]           # (B, S)
    full = {**batch, "tokens": toks}
    logits_full, _ = jax.jit(lambda p, b: api.forward(p, cfg, b))(
        params, full)

    pre = {**batch, "tokens": toks[:, :-1]}
    _, cache = jax.jit(lambda p, b: api.prefill(p, cfg, b))(params, pre)
    logits_dec, _ = jax.jit(
        lambda p, t, c: api.decode_step(p, cfg, t, jnp.int32(S - 1), c))(
        params, toks[:, -1], cache)

    a = np.asarray(logits_full[:, -1].astype(jnp.float32))
    b = np.asarray(logits_dec.astype(jnp.float32))
    # bf16 activations: compare top-1 + coarse values
    assert np.array_equal(a.argmax(-1), b.argmax(-1)), name
    np.testing.assert_allclose(a, b, atol=0.5, rtol=0.15)


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_param_count_close_to_label(zoo, name):
    full = get_arch(name)
    n = api.count_params(full)
    label = {"moonlight-16b-a3b": 16e9, "grok-1-314b": 314e9,
             "gemma3-27b": 27e9, "phi4-mini-3.8b": 3.8e9,
             "stablelm-1.6b": 1.6e9, "qwen2.5-3b": 3.1e9,
             "llama-3.2-vision-90b": 88e9, "recurrentgemma-9b": 8.6e9,
             "mamba2-780m": 0.78e9, "whisper-medium": 0.77e9}[name]
    assert abs(n - label) / label < 0.15, (name, n)
