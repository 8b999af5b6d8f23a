"""mxnet_tpu.parallel — device meshes + SPMD training.

TPU-native replacement for the reference's multi-device machinery
(SURVEY.md §2.3): instead of KVStore Comm trees / NCCL rings, a
``jax.sharding.Mesh`` over the chips and GSPMD partitioning.  Data
parallelism = shard the batch axis; tensor/sequence parallelism =
PartitionSpecs on parameters/activations; XLA inserts the all-reduces
over ICI (the reference's gpu_topology.h spanning-tree solver has no
equivalent here — the compiler owns topology).
"""
from .mesh import make_mesh, default_mesh, data_parallel_spec, replicated
from .mesh4d import MeshPlan, Mesh4DTrainer, mesh_plan_from_env
from .trainer import SPMDTrainer
from .ring_attention import (ring_attention, ring_self_attention,
                             ring_flash_attention,
                             ring_flash_self_attention)
from .ulysses import ulysses_attention, ulysses_self_attention
from .pipeline import (gpipe_apply, pipeline_forward,
                       interleaved_apply, pipeline_forward_1f1b,
                       pipeline_forward_interleaved,
                       pipeline_value_and_grad_1f1b, one_f_one_b_apply,
                       one_f_one_b_ticks,
                       interleave_params, interleaved_ticks, gpipe_ticks)
from .moe import (switch_moe, moe_expert_sharding, route_topk,
                  held_experts)

__all__ = ["make_mesh", "default_mesh", "data_parallel_spec", "replicated",
           "MeshPlan", "Mesh4DTrainer", "mesh_plan_from_env",
           "SPMDTrainer", "ring_attention", "ring_self_attention",
           "ring_flash_attention", "ring_flash_self_attention",
           "ulysses_attention", "ulysses_self_attention",
           "gpipe_apply", "pipeline_forward", "switch_moe",
           "interleaved_apply", "pipeline_forward_1f1b",
           "pipeline_forward_interleaved", "pipeline_value_and_grad_1f1b",
           "one_f_one_b_apply", "one_f_one_b_ticks",
           "interleave_params", "interleaved_ticks", "gpipe_ticks",
           "moe_expert_sharding", "route_topk", "held_experts"]
