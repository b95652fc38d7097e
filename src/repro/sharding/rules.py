"""Logical-axis sharding rules (divisibility-aware).

Parameters and activations are annotated with *logical* axis names; a
:class:`ShardingRules` table maps those to physical mesh axes. A mapping is
applied to a tensor dimension only when the dimension size is divisible by the
product of the mapped mesh-axis sizes — otherwise the rule falls back to a
prefix of the mapped axes, and finally to replication (this is what lets e.g.
phi3-medium's 40 heads coexist with TP=16: the head axis falls back and the
row-parallel `embed`-axis sharding of the same weight keeps compute balanced).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Sequence, Tuple, Union

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisMap = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class Packed:
    """A dimension that packs ``blocks`` equal blocks of one logical axis,
    such as a cache's (n_kv_heads * head_dim) axis: it is sharded only
    where each shard holds whole blocks."""
    name: str
    blocks: int


def _as_tuple(v: AxisMap) -> Tuple[str, ...]:
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(v)


# Default production rules: FSDP over `data`, TP/EP over `model`, DP over `pod`.
DEFAULT_RULES: Dict[str, AxisMap] = {
    # ---- parameters -------------------------------------------------
    "vocab": "model",
    "embed": "data",            # FSDP axis (weights gathered per layer)
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "layers": None,             # scan axis
    "ssm_inner": "model",
    "ssm_state": None,
    "ssm_heads": "model",
    "conv_dim": "model",
    "norm": None,
    "pos": None,
    # ---- activations ------------------------------------------------
    "act_batch": ("pod", "data"),
    "act_seq": None,
    # residual stream between blocks: sequence-parallel over `model`
    # (Megatron-SP): the per-layer carries saved by scan-backward shrink by
    # the TP degree; GSPMD inserts the all-gather/reduce-scatter pair.
    "act_res_seq": "model",
    "act_embed": None,
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_mlp": "model",
    "act_vocab": "model",
    "act_experts": "model",
    "act_expert_cap": "data",
    "act_expert_group": ("pod", "data"),
    "act_ssm_inner": "model",
    # ---- decode caches ----------------------------------------------
    "cache_batch": ("pod", "data"),
    "cache_seq": None,
    "cache_kv_heads": "model",
}

# long_500k (global_batch=1): the batch axis cannot be sharded; shard the KV
# cache (and decode activations) along the sequence instead — flash-decode
# style partial-softmax merge is inserted automatically by GSPMD.
LONG_CONTEXT_OVERRIDES: Dict[str, AxisMap] = {
    "act_batch": None,
    "cache_batch": None,
    "cache_seq": ("pod", "data"),
}


@dataclass(frozen=True)
class ShardingRules:
    table: Dict[str, AxisMap] = field(default_factory=lambda: dict(DEFAULT_RULES))
    # logical axes that may shard UNEVENLY (GSPMD pads the last shard).
    # Perf variant for archs whose head counts don't divide the TP degree
    # (phi3-medium: 40 heads over TP=16 -> 3/chip instead of 40/chip
    # replicated); see EXPERIMENTS.md §Perf.
    allow_uneven: Tuple[str, ...] = ()

    def with_overrides(self, **overrides: AxisMap) -> "ShardingRules":
        t = dict(self.table)
        t.update(overrides)
        return ShardingRules(t, self.allow_uneven)

    def with_uneven(self, *axes: str) -> "ShardingRules":
        return ShardingRules(dict(self.table), tuple(axes))

    def for_shape_kind(self, kind: str) -> "ShardingRules":
        if kind == "long_decode":
            return self.with_overrides(**LONG_CONTEXT_OVERRIDES)
        return self

    # ------------------------------------------------------------------
    def spec(self, mesh: Mesh, axes: Sequence[Union[None, str, Packed]],
             shape: Sequence[int]) -> P:
        """PartitionSpec for a tensor with given logical axes and shape."""
        if len(axes) != len(shape):
            raise ValueError(f"axes {axes} do not match shape {shape}")
        mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        used: set = set()
        out = []
        for dim, name in zip(shape, axes):
            if isinstance(name, Packed):
                name, dim = name.name, name.blocks
            entry: AxisMap = self.table.get(name) if name else None
            cand = tuple(a for a in _as_tuple(entry)
                         if a in mesh_sizes and a not in used)
            uneven_ok = name in self.allow_uneven
            # longest prefix that divides the dimension (or, for axes opted
            # into uneven sharding, merely fits: GSPMD pads the last shard)
            while cand:
                prod = int(np.prod([mesh_sizes[a] for a in cand]))
                if dim % prod == 0 or (uneven_ok and dim >= prod):
                    break
                cand = cand[:-1]
            if cand:
                used.update(cand)
                out.append(cand if len(cand) > 1 else cand[0])
            else:
                out.append(None)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, mesh: Mesh, axes: Sequence[Optional[str]],
                 shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(mesh, axes, shape))


def tree_shardings(mesh: Mesh, shapes_tree, axes_tree,
                   rules: Optional[ShardingRules] = None):
    """Map (shape-tree, logical-axes-tree) -> NamedSharding tree."""
    import jax
    rules = rules or ShardingRules()

    def one(sds, axes):
        return rules.sharding(mesh, axes, sds.shape)

    return jax.tree.map(one, shapes_tree, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(e, (str, type(None))) for e in x))
