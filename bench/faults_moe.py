"""A fault planted in the expert layer of an MoE training cell, to show
that ``correct`` fails (beside ``bench/faults.py``'s training faults):

  expert_dropped  the first held expert's output is dropped: its rows
                  are left out of the grouped product, as if the chip
                  had lost that expert

``install(name)`` patches the program in this process and returns a
function that takes the patch out again.
"""
from __future__ import annotations

import jax.numpy as jnp

FAULTS = ("expert_dropped",)


def install(name: str):
    from repro.models import ffn
    if name != "expert_dropped":
        raise KeyError(name)
    held_tiles = ffn.held_tiles

    def dropped(ids, *, held, offset, tile):
        return held_tiles(jnp.where(ids == offset, -1, ids), held=held,
                          offset=offset, tile=tile)
    ffn.held_tiles = dropped
    return lambda: setattr(ffn, "held_tiles", held_tiles)
