"""Faults planted under the timed path, to show that ``correct`` fails.

The benchmark's own runs never install one. ``bench/calibrate.py`` reads
the training faults on the chip to set the limits' upper readings, and
``bench/tests`` sees each cell's run come out not correct under each
fault the cell can have:

  answer_altered   serve: one element of every Get reply off by one
                   fixed-point step, where the reply is produced
  state_unchanged  serve: register writes dropped; train: the step
                   returns the state it was given
  half_batch       serve: the second half of every addTo dropped;
                   train: the second half of the batch replaced by the
                   first, so the mean is over half of it

``install(name)`` patches the program in this process and returns a
function that takes the patch out again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def _serve(name):
    from repro.core.inc_map import SwitchMemory
    if name == "answer_altered":
        read = SwitchMemory.read_f32

        def read_f32(self, phys, scale, need_raw=False):
            vals, raw = read(self, phys, scale, need_raw)
            return vals.at[0].add(jnp.float32(1.0) / scale), raw
        return _patch(SwitchMemory, "read_f32", read_f32)
    if name == "state_unchanged":
        undo = [_patch(SwitchMemory, "addto_f32", lambda self, *a: None),
                _patch(SwitchMemory, "addto", lambda self, *a: None)]
        return lambda: [u() for u in undo]
    if name == "half_batch":
        add = SwitchMemory.addto_f32

        def addto_f32(self, phys, fvals, scale):
            k = len(phys) // 2
            return add(self, phys[:k], fvals[:k], scale)
        return _patch(SwitchMemory, "addto_f32", addto_f32)
    raise KeyError(name)


def wrap_step(fn, name):
    """The training step ``fn`` with a fault planted around it."""
    if name == "state_unchanged":
        def step(params, opt, batch, s):
            _, _, metrics = fn(jax.tree.map(jnp.copy, params),
                               jax.tree.map(jnp.copy, opt), batch, s)
            return params, opt, metrics
        return step
    if name == "half_batch":
        def step(params, opt, batch, s):
            t = batch["tokens"]
            h = t.shape[0] // 2
            t = jnp.concatenate([t[:h], t[:h]])
            return fn(params, opt, {**batch, "tokens": t}, s)
        return step
    raise KeyError(name)


def install(name: str, kind: str):
    """Plant fault ``name`` for a cell of ``kind`` ("serve" or "train")."""
    if kind == "serve":
        return _serve(name)
    from repro.launch import steps
    build = steps.build_train_step

    def build_train_step(*a, **kw):
        prog = build(*a, **kw)
        prog.fn = wrap_step(prog.fn, name)
        return prog
    return _patch(steps, "build_train_step", build_train_step)
