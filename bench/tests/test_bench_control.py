"""The controls, at a size a test run holds: the plain reference one
precision step below what each configuration states, put in the
program's place, must come out not correct.

Training: float8 e4m3 matmul operands (bf16 stated) against the float32
reference fail at least one of the compared numbers under the toy-size
limits. Serve: gradient chunks rounded to bfloat16 before the fixed point
(fp32 stated) fail the element-exact comparison."""
import json

import numpy as np
import pytest

from benchutil import BENCH

import run  # noqa: E402
from reference import agtr_oracle, dense_lm  # noqa: E402


def _files(cell):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    w = next(w for w in spec["workloads"] if w["name"] == cell)
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    c = json.loads((BENCH.parent / conf["file"]).read_text())
    t = json.loads((BENCH / "traffic" / f"{cell}.json").read_text())
    return {**c, **c["rehearse"]}, {**t, **t["rehearse"]}


@pytest.mark.parametrize("cell", ["train_stablelm_1c"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_training_control_is_not_correct(cell, seed):
    driver = run.load_module(BENCH / "drivers" / "dp_train.py")
    conf, tr = _files(cell)
    ws, ds = run.seed_words(seed)[:2]
    ref = driver.reference(conf, tr, ws, ds)
    ctl = driver.reference(conf, tr, ws, ds, control=True)
    checks = driver.compare(ctl, ref, tr["limits"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_reference_starts_where_the_program_starts():
    """The reference draws the program's initial weights and token rows
    again from the same seeds, bit for bit (compared here, never in a
    run: the reference takes nothing the program made)."""
    import jax
    from repro.data import pipeline
    from repro.models import api
    driver = run.load_module(BENCH / "drivers" / "dp_train.py")
    conf, tr = _files("train_stablelm_1c")
    cfg = driver.program_config(conf, rehearse=True)
    a = driver.arch_of(conf)
    mine = dense_lm.init_params(1234, a)
    theirs = api.init_params(jax.random.key(1234), cfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for x, y in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, batch=2, seq_len=32,
                               seed=77, kind="uniform")
    assert np.array_equal(np.asarray(pipeline.make_batch(dcfg, 5)["tokens"]),
                          np.asarray(dense_lm.tokens(77, 5, 2, 32,
                                                     cfg.vocab)))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_serve_control_is_not_correct(seed):
    rng = np.random.default_rng(seed)
    chunk = rng.standard_normal(4096).astype(np.float32)
    import jax.numpy as jnp
    low = np.asarray(jnp.asarray(chunk).astype(jnp.bfloat16)
                     .astype(jnp.float32))
    want = agtr_oracle.reply_f32(chunk, 10 ** 6)
    assert agtr_oracle.mismatches(want, want) == 0
    assert agtr_oracle.mismatches(agtr_oracle.reply_f32(low, 10 ** 6),
                                  want) > 0
