from repro.sharding.rules import ShardingRules, DEFAULT_RULES, \
    LONG_CONTEXT_OVERRIDES, Packed, tree_shardings
from repro.sharding.partition import lshard, per_shard, use_mesh_rules, \
    active_mesh, active_rules

__all__ = ["ShardingRules", "DEFAULT_RULES", "LONG_CONTEXT_OVERRIDES",
           "Packed", "tree_shardings", "lshard", "per_shard",
           "use_mesh_rules", "active_mesh", "active_rules"]
