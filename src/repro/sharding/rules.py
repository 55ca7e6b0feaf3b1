"""Logical-to-mesh sharding rules for every parameter leaf.

Two parallelism modes (DESIGN.md §7):

  zero1  params replicated over the data axes (optimizer state ZeRO-1
         sharded as flat chunks by the INC reduce-scatter), TP over 'model'.
  fsdp   params additionally sharded over the data axes on a "fsdp dim"
         (first dim divisible by n_dp, excluding the layer-stack dim and
         the TP dim); gathered per-layer inside the scan, with the INC
         reduce-scatter as the backward path (grok-314b, llama-90b).

TP assignment is name+shape based: heads dims for attention, d_ff for MLPs
and experts, vocab for embeddings, head-groups for SSM, gate blocks for
RG-LRU. A dim is only sharded if its size divides the axis size — e.g.
phi4-mini's 24 heads do not divide 16, so its attention weights stay
replicated over 'model' (documented compute-roofline cost).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

FSDP_ARCHS = ("grok-1-314b", "llama-3.2-vision-90b")


@dataclass(frozen=True)
class MeshAxes:
    data: tuple[str, ...]        # ("pod","data") or ("data",)
    model: str = "model"

    def sizes(self, mesh) -> tuple[int, int]:
        n_dp = 1
        for ax in self.data:
            n_dp *= mesh.shape[ax]
        return n_dp, mesh.shape[self.model]


def _path_names(path) -> list[str]:
    out = []
    for k in path:
        key = getattr(k, "key", None)
        if key is None:
            key = getattr(k, "name", None)
        if key is None and hasattr(k, "idx"):
            key = str(k.idx)
        out.append(str(key))
    return out


def _leaf_name(path) -> str:
    return _path_names(path)[-1]


def _is_stacked(path) -> bool:
    names = _path_names(path)
    return ("groups" in names) or ("blocks" in names)


# TP dim by leaf name, counted from the END of the shape (stacked leaves
# have an extra leading layer dim, so negative indexing is uniform).
_TP_DIM_FROM_END = {
    # attention
    "wq": 2, "wk": 2, "wv": 2,            # (..., d, H, hd) -> H
    "wo": 3,                              # (..., H, hd, d) -> H
    "wkv_b": 2,                           # MLA (..., rank, H, k) -> H
    "bq": 2, "bk": 2, "bv": 2,            # (..., H, hd)    -> H
    # dense mlp
    "w1": 1, "w3": 1,                     # (..., d, ff)    -> ff
    "w2": 2,                              # (..., ff, d)    -> ff
    "b1": 1,
    # ssm
    "w_z": 1, "w_x": 1, "w_dt": 1,        # (..., d, d_inner|H)
    "conv_x": 1, "conv_bx": 1,
    "dt_bias": 1, "A_log": 1, "D": 1,     # (..., H)
    "norm": 1,                            # (..., d_inner)
    "w_out": 2,                           # (..., d_inner|rnn, d)
    # rglru
    "w_in_a": 1, "w_in_b": 1,
    "conv_w": 1, "conv_b": 1,
    "wr": 3, "wi": 3,                     # (..., nb, c, c) -> nb
    "br": 1, "bi": 1, "lam": 1,
    # embeddings
    "embed": 2, "lm_head": 2,             # (V, d) -> V
    "mproj": 1,
}

# expert leaves: under an "experts" subtree the ff dim moves one inward
# (a "shared" subtree is a dense SwiGLU and takes the table above)
_TP_DIM_EXPERTS = {"w1": 1, "w3": 1, "w2": 2}

# leaves that are state and not trained parameters: no gradient stream,
# so never scattered over the data axes (the MoE selection bias, moved by
# the train step from the experts' loads). MLA's "wkv_a" (d, rank + rope)
# and "kv_norm", and the router, are replicated over 'model'.
_NOT_SCATTERED = {"router_bias"}


def tp_dim(path, shape, n_model: int) -> int | None:
    names = _path_names(path)
    name = names[-1]
    if "experts" in names:
        d = _TP_DIM_EXPERTS.get(name)
    else:
        d = _TP_DIM_FROM_END.get(name)
    if d is None or d > len(shape):
        return None
    dim = len(shape) - d
    if shape[dim] % n_model != 0 or shape[dim] < n_model:
        return None
    return dim


def is_state_leaf(path) -> bool:
    """A leaf the optimizer never updates (``_NOT_SCATTERED``)."""
    return _leaf_name(path) in _NOT_SCATTERED


def fsdp_dim(path, shape, n_dp: int, taken: int | None) -> int | None:
    if is_state_leaf(path):
        return None
    start = 1 if _is_stacked(path) else 0
    best = None
    for i in range(start, len(shape)):
        if i == taken:
            continue
        if shape[i] % n_dp == 0 and shape[i] >= n_dp:
            if best is None or shape[i] > shape[best]:
                best = i
    return best


def param_spec(path, leaf, axes: MeshAxes, n_dp: int, n_model: int,
               mode: str) -> P:
    shape = leaf.shape
    entries: list = [None] * len(shape)
    t = tp_dim(path, shape, n_model)
    if t is not None:
        entries[t] = axes.model
    if mode == "fsdp":
        f = fsdp_dim(path, shape, n_dp, t)
        if f is not None:
            entries[f] = axes.data if len(axes.data) > 1 else axes.data[0]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_specs(params_shape, axes: MeshAxes, mesh, mode: str):
    """Pytree of PartitionSpec matching a params (shape) pytree."""
    n_dp, n_model = axes.sizes(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [param_spec(p, l, axes, n_dp, n_model, mode) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(params_shape, axes: MeshAxes, mesh, mode: str):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape, axes, mesh, mode))


def manual_only(spec: P, manual: tuple[str, ...]) -> P:
    """Strip auto-axis entries from a spec (shard_map in_specs see only the
    manual axes; 'model' rides along as auto)."""
    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in manual)
            return kept if kept else None
        return e if e in manual else None
    entries = [keep(e) for e in spec]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def manual_specs(specs_tree, manual: tuple[str, ...]):
    return jax.tree.map(lambda s: manual_only(s, manual), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def mode_for(arch_name: str) -> str:
    return "fsdp" if arch_name in FSDP_ARCHS else "zero1"
