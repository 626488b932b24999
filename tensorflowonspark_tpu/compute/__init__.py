"""TPU compute layer: device mesh, sharded train steps, checkpointing.

This layer replaces the reference's delegation to TensorFlow's distributed
runtime (PS + MultiWorkerMirroredStrategy, SURVEY.md §2.3): data-parallel
and FSDP training are expressed as ``jax.jit`` over a ``Mesh`` with
``NamedSharding``; XLA inserts the collectives (psum over ICI) that NCCL
all-reduce performed in the reference.
"""

from tensorflowonspark_tpu.compute.elastic import (
    ElasticTrainer,
    host_snapshot,
    reshard_state,
)
from tensorflowonspark_tpu.compute.layout import (
    LAYOUT_TABLES,
    SpecLayout,
    get_layout,
    optimizer_state_spec,
    param_shardings,
)
from tensorflowonspark_tpu.compute.mesh import (
    MESH_AXES,
    fit_axis_shapes,
    make_mesh,
    batch_sharding,
    replicated,
)
from tensorflowonspark_tpu.compute.optim import (
    adamw,
    mixed_precision_adamw,
)
from tensorflowonspark_tpu.compute.train import (
    TrainState,
    build_train_step,
    build_eval_step,
    fsdp_shardings,
    shard_state,
    state_shardings,
    zero_update_shardings,
)

__all__ = [
    "LAYOUT_TABLES",
    "MESH_AXES",
    "SpecLayout",
    "get_layout",
    "optimizer_state_spec",
    "param_shardings",
    "ElasticTrainer",
    "host_snapshot",
    "reshard_state",
    "fit_axis_shapes",
    "make_mesh",
    "batch_sharding",
    "replicated",
    "TrainState",
    "build_train_step",
    "build_eval_step",
    "fsdp_shardings",
    "shard_state",
    "state_shardings",
    "zero_update_shardings",
    "adamw",
    "mixed_precision_adamw",
]
