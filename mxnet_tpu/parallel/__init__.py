"""Parallelism over a device mesh (TPU-native; replaces KVStore/NCCL).

SURVEY.md §2.4/§5.8: all the reference's parallel flavors (and the ones it
lacks: tp/pp/sp/ep/ZeRO) become sharding specifications over one
jax.sharding.Mesh here, with XLA emitting the collectives.
"""
from .mesh import (  # noqa: F401
    AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_PP, AXIS_SP, AXIS_TP, Mesh,
    NamedSharding, PartitionSpec, current_mesh, make_mesh, mesh_scope,
    named_sharding, set_default_mesh)
from .rules import (  # noqa: F401
    ShardingRules, apply_sharding_rules, ep_rules, fsdp_rules,
    megatron_dense_rules)
from .sp import ring_attention, sp_enabled, ulysses_attention  # noqa: F401
from .comm import (collective_summary, comm_report,  # noqa: F401
                   ring_cost_bytes)
from .pp import (PPTrainStep, gpipe, pipeline_grads,  # noqa: F401
                 pipeline_loss, pipeline_loss_and_grads,
                 stack_stage_params)
from .moe import (  # noqa: F401
    all_to_all_tokens, dropless_moe, moe_dispatch_combine, top_k_gating,
    top_k_weights)
from .step import EvalStep, TrainStep  # noqa: F401
