"""Deterministic synthetic data pipeline.

Three generators:
  - `uniform`: i.i.d. tokens — used by benchmarks and the dry-run (shape
    stand-ins only).
  - `zipf`: i.i.d. tokens whose frequencies follow Zipf's law (id i drawn
    with probability proportional to (i + 1) ** -zipf_a), as natural text's
    do: repeated tokens route alike, so MoE loads come out uneven.
  - `bigram`: tokens sampled from a fixed random first-order Markov chain,
    so a model CAN learn it — train_mini's loss must visibly fall toward
    the chain's conditional entropy (paper Fig. 6 analogue validates the
    quantized INC aggregation trains as well as fp32).

Every batch is a pure function of (seed, step): restart-deterministic, which
is what makes the checkpoint/restart exactly-once contract testable — a
re-run step consumes identical data.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class DataConfig:
    vocab: int
    batch: int
    seq_len: int
    seed: int = 0
    kind: str = "bigram"         # "bigram" | "uniform" | "zipf"
    temperature: float = 0.7     # bigram sharpness (lower = more learnable)
    zipf_a: float = 1.0          # zipf exponent


@lru_cache(maxsize=8)
def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    """Cumulative Zipf probabilities of ids 0..vocab-1 (float64 sums,
    float32 table), the same table wherever it is built."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    return (np.cumsum(w) / w.sum()).astype(np.float32)


def _transition_logits(cfg: DataConfig) -> jax.Array:
    k = jax.random.key(cfg.seed ^ 0x5EED)
    return jax.random.normal(k, (cfg.vocab, cfg.vocab)) / cfg.temperature


def make_batch(cfg: DataConfig, step) -> dict:
    """(seed, step) -> {"tokens": (B, S+1) int32}, jit-able."""
    key = jax.random.fold_in(jax.random.key(cfg.seed), step)
    if cfg.kind == "uniform":
        toks = jax.random.randint(key, (cfg.batch, cfg.seq_len + 1), 0,
                                  cfg.vocab, jnp.int32)
        return {"tokens": toks}
    if cfg.kind == "zipf":
        u = jax.random.uniform(key, (cfg.batch, cfg.seq_len + 1), jnp.float32)
        ids = jnp.searchsorted(jnp.asarray(zipf_cdf(cfg.vocab, cfg.zipf_a)),
                               u, side="right")
        return {"tokens": jnp.minimum(ids, cfg.vocab - 1).astype(jnp.int32)}
    trans = _transition_logits(cfg)
    k0, kseq = jax.random.split(key)
    first = jax.random.randint(k0, (cfg.batch,), 0, cfg.vocab, jnp.int32)

    def step_fn(tok, k):
        nxt = jax.random.categorical(k, trans[tok], axis=-1).astype(jnp.int32)
        return nxt, nxt

    keys = jax.random.split(kseq, cfg.seq_len)
    _, rest = jax.lax.scan(step_fn, first, keys)
    toks = jnp.concatenate([first[None, :], rest], axis=0).T
    return {"tokens": toks}


def bigram_entropy(cfg: DataConfig, n: int = 4096) -> float:
    """Reference conditional entropy of the chain (loss floor)."""
    trans = _transition_logits(cfg)
    p = jax.nn.softmax(trans, axis=-1)
    h = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=-1)
    return float(jnp.mean(h))


def add_modality_stubs(batch: dict, arch_cfg, batch_size: int,
                       seed: int = 0) -> dict:
    """Precomputed frame/patch embeddings per the assignment's stub rule."""
    if arch_cfg.family == "vlm":
        k = jax.random.key(seed ^ 0xB1D)
        batch["patches"] = jax.random.normal(
            k, (batch_size, arch_cfg.frontend_tokens, arch_cfg.d_model),
            jnp.bfloat16)
    if arch_cfg.is_encdec:
        k = jax.random.key(seed ^ 0xA1D)
        batch["frames"] = jax.random.normal(
            k, (batch_size, arch_cfg.frontend_tokens, arch_cfg.d_model),
            jnp.bfloat16)
    return batch


def shard_batch(batch: dict, mesh, specs: dict) -> dict:
    """Place host arrays as globally sharded jax.Arrays."""
    from jax.sharding import NamedSharding
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in batch.items()}
