"""Logical-axis sharding rules → PartitionSpecs (MaxText-style).

Every parameter tree has a parallel *axes tree* (same structure, leaves are
tuples of logical names — see ``models.lm.param_axes``).  A ``ShardingRules``
table maps logical names to mesh axes; ``param_pspecs`` applies the table
with divisibility checks (a dim is only sharded if its size divides evenly —
e.g. MQA's single KV head falls back to replication automatically).

Default placement (single-pod ``(data, model)``, multi-pod ``(pod, data,
model)``):

* batch over (pod, data) — DP
* ``heads/mlp/vocab/expert/ssm_*/rnn`` over model — TP/EP
* ``embed`` (weights' d_model dim) over data — FSDP/ZeRO-3 storage
* ``expert_mlp`` (per-expert d_ff) over data — FSDP storage, gathered
  per-layer inside the MoE shard_map
* optimizer state inherits the parameter specs (moments are same-shaped)

These tables are the primary §Perf hillclimb lever: rules are plain data.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


def tree_map_axes(fn, *trees):
    """tree.map treating tuples-of-names as leaves."""
    return jax.tree.map(fn, *trees, is_leaf=lambda x: isinstance(x, tuple))


@dataclass(frozen=True)
class ShardingRules:
    table: Dict[str, MeshAxes] = field(default_factory=dict)

    def lookup(self, name: Optional[str]) -> MeshAxes:
        if name is None:
            return None
        return self.table.get(name)

    def override(self, **kw) -> "ShardingRules":
        t = dict(self.table)
        t.update(kw)
        return ShardingRules(t)


def default_rules(mesh: Mesh) -> ShardingRules:
    dp: Tuple[str, ...] = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp = "data" if "data" in mesh.axis_names else None
    return ShardingRules({
        "batch": dp,
        "vocab": "model",
        "embed": fsdp,          # FSDP storage of the d_model dim
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "kv_lora": None,        # latent attention's compressed KV width
        "mlp": "model",
        "expert": "model",      # EP
        "expert_mlp": fsdp,     # FSDP storage; gathered inside MoE shard_map
        "expert_router": None,
        "ssm_inproj": "model",
        "ssm_conv": "model",
        "ssm_heads": "model",
        "ssm_inner": "model",
        "rnn": "model",
        "layers": None,
        "seq": None,
    })


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def spec_for(shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
             rules: ShardingRules, mesh: Mesh) -> P:
    """PartitionSpec for one array; respects divisibility and never assigns
    the same mesh axis twice."""
    assert len(shape) == len(logical), (shape, logical)
    used: set = set()
    dims = []
    for size, name in zip(shape, logical):
        axes = rules.lookup(name)
        if axes is None:
            dims.append(None)
            continue
        ax_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
        ax_tuple = tuple(a for a in ax_tuple if a in mesh.axis_names and a not in used)
        if not ax_tuple or size % _axis_size(mesh, ax_tuple) != 0:
            dims.append(None)
            continue
        used.update(ax_tuple)
        dims.append(ax_tuple[0] if len(ax_tuple) == 1 else ax_tuple)
    return P(*dims)


def param_pspecs(axes_tree, shapes_tree, rules: ShardingRules, mesh: Mesh):
    """axes_tree: tuples-of-names leaves; shapes_tree: ShapeDtypeStructs (or
    arrays) with matching structure."""
    flat_axes = jax.tree.leaves(axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    flat_shapes, treedef = jax.tree.flatten(shapes_tree)
    assert len(flat_axes) == len(flat_shapes), "axes/param tree mismatch"
    specs = [spec_for(tuple(s.shape), ax, rules, mesh)
             for s, ax in zip(flat_shapes, flat_axes)]
    return jax.tree.unflatten(treedef, specs)


def batch_pspecs(batch_tree, rules: ShardingRules, mesh: Mesh):
    """Shard dim 0 (batch) of every input over the DP axes; replicate rest."""
    dp = rules.lookup("batch")

    def one(x):
        if x.ndim == 0:
            return P()
        if x.shape[0] % _axis_size(mesh, dp) == 0 and _axis_size(mesh, dp) > 1:
            return P(dp if not isinstance(dp, tuple) or len(dp) > 1 else dp[0],
                     *([None] * (x.ndim - 1)))
        return P(*([None] * x.ndim))

    return jax.tree.map(one, batch_tree)


def cache_pspecs(cache_tree, rules: ShardingRules, mesh: Mesh):
    """Decode/prefill caches: keyed by leaf name (k/v/pos_map/conv/state/h)."""
    dp = rules.lookup("batch")
    model = "model" if "model" in mesh.axis_names else None

    def shard_dim(size, axes):
        if axes is None:
            return None
        if size % _axis_size(mesh, axes) != 0 or _axis_size(mesh, axes) == 1:
            return None
        if isinstance(axes, tuple) and len(axes) == 1:
            return axes[0]
        return axes

    def one(path, x):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = p.key
                break
        if name in ("k", "v"):           # (B, Sc, K, hd) [+ leading layers]
            b, kvh, hd = x.ndim - 4, x.ndim - 2, x.ndim - 1
            dims = [None] * x.ndim
            dims[b] = shard_dim(x.shape[b], dp)
            dims[kvh] = shard_dim(x.shape[kvh], model)
            if dims[kvh] is None:
                # MQA / few KV heads: shard head_dim instead (memory parity;
                # GSPMD reduces the contraction with a psum)
                dims[hd] = shard_dim(x.shape[hd], model)
            return P(*dims)
        if name == "pos_map":
            return P(*([None] * x.ndim))
        if name == "conv":               # (B, W, C)
            b, c = x.ndim - 3, x.ndim - 1
            dims = [None] * x.ndim
            dims[b] = shard_dim(x.shape[b], dp)
            dims[c] = shard_dim(x.shape[c], model)
            return P(*dims)
        if name == "state":              # (B, H, P, N)
            b, h = x.ndim - 4, x.ndim - 3
            dims = [None] * x.ndim
            dims[b] = shard_dim(x.shape[b], dp)
            dims[h] = shard_dim(x.shape[h], model)
            return P(*dims)
        if name == "h":                  # (B, R)
            dims = [None] * x.ndim
            dims[x.ndim - 2] = shard_dim(x.shape[x.ndim - 2], dp)
            dims[x.ndim - 1] = shard_dim(x.shape[x.ndim - 1], model)
            return P(*dims)
        return P(*([None] * x.ndim))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
