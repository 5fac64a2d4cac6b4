"""thunder_tpu.distributed: data/tensor/sequence parallelism over TPU meshes.

Capability analog of ``thunder/distributed/`` (ddp, fsdp ZeRO2/3, comm
prims, bucketing, checkpointing) designed TPU-first: parallelism is a
sharding of params/batch over a ``jax.sharding.Mesh``; XLA emits and
overlaps the collectives.  Manual collectives remain available as trace
prims (``thunder_tpu.distributed.prims``) for algorithms that need them
(ring attention, expert dispatch).
"""
from thunder_tpu.distributed import prims  # noqa: F401  (registers jax impls)
from thunder_tpu.distributed.api import (
    TrainStep,
    combine_threshold_options,
    ddp,
    fsdp,
    make_train_step,
    tp_fsdp,
)
from thunder_tpu.distributed.checkpoint import (
    StateDictOptions,
    full_state_dict,
    latest_step,
    load_checkpoint,
    save_checkpoint,
)
from thunder_tpu.distributed.moe import ep_gpt_loss, ep_moe_mlp, expert_capacity
from thunder_tpu.distributed.multihost import hybrid_mesh, initialize as initialize_multihost
from thunder_tpu.distributed.pipeline import (
    gpipe,
    place_pipeline_params,
    pp_gpt_loss,
    stack_blocks,
)
from thunder_tpu.distributed.prims import DistributedReduceOps
from thunder_tpu.distributed.ring_attention import ring_attend_shard, ring_attention, ring_self_attention
from thunder_tpu.distributed.sp import sp_gpt_loss
from thunder_tpu.distributed.ulysses import ulysses_attend_shard, ulysses_gpt_loss
from thunder_tpu.distributed.vocab_parallel import tp_fused_linear_ce
from thunder_tpu.distributed.sharding import (
    ShardingRules,
    apply_shardings,
    batch_spec,
    ddp_shardings,
    fsdp_shardings,
    init_sharded,
    kv_cache_spec,
    llama_shardings,
    make_mesh,
)

__all__ = [
    "TrainStep",
    "ddp",
    "fsdp",
    "tp_fsdp",
    "make_train_step",
    "combine_threshold_options",
    "DistributedReduceOps",
    "ShardingRules",
    "apply_shardings",
    "batch_spec",
    "ddp_shardings",
    "fsdp_shardings",
    "init_sharded",
    "kv_cache_spec",
    "llama_shardings",
    "make_mesh",
    "prims",
    "StateDictOptions",
    "full_state_dict",
    "save_checkpoint",
    "load_checkpoint",
    "latest_step",
    "ring_attention",
    "ring_attend_shard",
    "sp_gpt_loss",
    "ulysses_gpt_loss",
    "tp_fused_linear_ce",
    "ulysses_attend_shard",
    "ring_self_attention",
    "ep_moe_mlp",
    "ep_gpt_loss",
    "expert_capacity",
    "gpipe",
    "hybrid_mesh",
    "initialize_multihost",
    "stack_blocks",
    "place_pipeline_params",
    "pp_gpt_loss",
]
