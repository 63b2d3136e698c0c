"""ray_tpu.train: distributed SPMD training on TPU meshes.

The reference's Ray Train (v2) re-designed TPU-first: a controller drives a
gang of per-host worker actors; each worker enters the same jitted SPMD
program over a jax.sharding.Mesh; parallelism strategies (dp/fsdp/tp/sp/ep)
are mesh axes + partition specs (ray_tpu.train.step), not NCCL process
groups.  Reports/checkpoints flow through shared storage with orbax array
payloads.
"""

from ray_tpu.train.checkpoint import (
    Checkpoint,
    CheckpointManager,
    load_pytree,
    save_pytree,
)
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train.context import (
    TrainContext,
    get_checkpoint,
    get_context,
    get_dataset_shard,
    report,
)
from ray_tpu.train.controller import Result, TrainController, TrainingFailedError
from ray_tpu.train.gbdt import LightGBMTrainer, XGBoostTrainer
from ray_tpu.train.torch import TorchConfig, TorchTrainer
from ray_tpu.train.trainer import DataParallelTrainer, JaxTrainer
from ray_tpu.train.worker_group import TrainWorker, WorkerGroup

# The step builders are the only part that needs JAX.  They resolve on first
# use, so that a driver that describes a trainer never imports it: the
# worker that is granted the chip does.
_STEP = ("create_train_state", "data_sharding", "default_optimizer",
         "make_train_step")


def __getattr__(name: str):
    if name not in _STEP:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from ray_tpu.train import step

    value = globals()[name] = getattr(step, name)
    return value


__all__ = [
    "LightGBMTrainer",
    "TorchConfig",
    "TorchTrainer",
    "XGBoostTrainer",
    "Checkpoint", "CheckpointConfig", "CheckpointManager", "DataParallelTrainer",
    "FailureConfig", "JaxTrainer", "Result", "RunConfig", "ScalingConfig",
    "TrainContext", "TrainController", "TrainWorker", "TrainingFailedError",
    "WorkerGroup", "create_train_state", "data_sharding", "default_optimizer",
    "get_checkpoint", "get_context", "get_dataset_shard", "load_pytree",
    "make_train_step", "report", "save_pytree",
]
