"""Deterministic synthetic data pipeline."""
import numpy as np

from repro.data import pipeline


def cfg(kind="bigram"):
    return pipeline.DataConfig(vocab=64, batch=4, seq_len=16, seed=7,
                               kind=kind)


def test_restart_determinism():
    a = pipeline.make_batch(cfg(), 5)["tokens"]
    b = pipeline.make_batch(cfg(), 5)["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_steps_differ():
    a = pipeline.make_batch(cfg(), 1)["tokens"]
    b = pipeline.make_batch(cfg(), 2)["tokens"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_shapes_and_range():
    t = np.asarray(pipeline.make_batch(cfg(), 0)["tokens"])
    assert t.shape == (4, 17)          # (B, S+1)
    assert t.min() >= 0 and t.max() < 64


def test_bigram_entropy_below_uniform():
    c = cfg()
    h = pipeline.bigram_entropy(c)
    assert 0 < h < np.log(64)          # learnable structure exists


def test_bigram_statistics_match_chain():
    """Empirical next-token distribution tracks the transition matrix."""
    import jax
    import jax.numpy as jnp
    c = pipeline.DataConfig(vocab=8, batch=64, seq_len=64, seed=3,
                            kind="bigram")
    trans = jax.nn.softmax(pipeline._transition_logits(c), axis=-1)
    toks = np.asarray(pipeline.make_batch(c, 0)["tokens"])
    # count transitions from token 0
    pairs = [(a, b) for row in toks for a, b in zip(row[:-1], row[1:])]
    from collections import Counter
    cnt = Counter(b for a, b in pairs if a == 0)
    n = sum(cnt.values())
    if n > 100:
        emp = np.array([cnt.get(i, 0) / n for i in range(8)])
        np.testing.assert_allclose(emp, np.asarray(trans[0]), atol=0.15)


def test_zipf_frequencies_follow_the_law():
    """Id i comes with probability proportional to 1 / (i + 1); every id
    stays in the vocabulary, and the stream is a function of (seed, step)."""
    c = pipeline.DataConfig(vocab=512, batch=8, seq_len=4095, seed=11,
                            kind="zipf", zipf_a=1.0)
    t = np.asarray(pipeline.make_batch(c, 0)["tokens"])
    assert t.shape == (8, 4096) and t.min() >= 0 and t.max() < 512
    np.testing.assert_array_equal(
        t, np.asarray(pipeline.make_batch(c, 0)["tokens"]))
    p = 1.0 / np.arange(1, 513)
    p /= p.sum()
    emp = np.bincount(t.reshape(-1), minlength=512) / t.size
    np.testing.assert_allclose(emp[:4], p[:4], rtol=0.05)
    assert emp[:8].sum() > 0.35            # the head carries the stream
