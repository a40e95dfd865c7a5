"""Parallelism over device meshes.

The reference's distributed story is kvstore-based data parallelism plus
manual per-layer device placement (SURVEY.md §2.3).  The TPU-native build
gets DP/TP/SP/PP from `jax.sharding` over a Mesh — XLA inserts the
collectives (psum/all-gather/reduce-scatter) and schedules them over ICI.
"""
from .mesh import (
    make_mesh, current_mesh, mesh_scope, data_sharding, replicated_sharding,
    match_partition_rules, shard_parameters, constrain, global_put,
    shard_put, init_distributed, RuleCoverage,
)
from .recipe import ShardingRecipe, parse_recipe
from .ring_attention import ring_attention
from .ulysses import ulysses_attention
from .pipeline import pipeline_apply
from .moe import (moe_ffn, init_moe_params, moe_partition_specs,
                  shard_moe_params, route_top_k, routed_experts, expert_loads)
from .layers import MoEFFN, RoutedExperts, GPipeMLP

__all__ = [
    "make_mesh", "current_mesh", "mesh_scope", "data_sharding",
    "replicated_sharding", "match_partition_rules", "shard_parameters",
    "global_put", "shard_put",
    "constrain", "ring_attention", "ulysses_attention", "init_distributed",
    "pipeline_apply", "moe_ffn", "init_moe_params", "moe_partition_specs",
    "shard_moe_params", "MoEFFN", "GPipeMLP", "RoutedExperts",
    "route_top_k", "routed_experts", "expert_loads",
    "ShardingRecipe", "parse_recipe", "RuleCoverage",
]
