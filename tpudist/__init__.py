"""tpudist — a TPU-native distributed training framework.

A from-scratch re-design (JAX / XLA / pjit / shard_map / pallas) of the
capabilities demonstrated by the ``pytorch_distributed_examples`` reference
suite (see SURVEY.md):

* data-parallel training with explicit gradient ``psum`` over ICI
  (the DDP / Horovod ring-allreduce equivalent),
* elastic training: checkpoint / commit / rollback / resume with
  world-size-change hooks (the TorchElastic / Horovod-elastic equivalent),
* micro-batched pipeline model parallelism on a mesh axis
  (the RPC + distributed-autograd ResNet50 pipeline equivalent),
* parameter-server-style hybrid parallelism: a model-axis-sharded embedding
  table feeding data-parallel dense layers (the RemoteModule / HybridModel
  equivalent),
* a runtime layer: mesh construction, multi-host bootstrap, a native (C++)
  rendezvous / coordination store, data sharding, checkpointing, metrics.

The reference's mechanisms (process groups, RPC, RRefs, distributed autograd)
dissolve on TPU: sharding is a compiler annotation and ``jax.grad``
differentiates across devices natively.  What remains — and what this package
provides — are the *capabilities*, re-expressed mesh-first.
"""

from tpudist import data, elastic, models, obs, ops, parallel, runtime, train, utils
from tpudist.runtime.mesh import (
    MeshSpec,
    data_mesh,
    data_model_mesh,
    get_devices,
    make_mesh,
    pipeline_mesh,
)
from tpudist.train.state import TrainState
from tpudist.train.trainer import Trainer, TrainerConfig

__version__ = "0.1.0"

__all__ = [
    "MeshSpec",
    "TrainState",
    "Trainer",
    "TrainerConfig",
    "data",
    "data_mesh",
    "data_model_mesh",
    "elastic",
    "get_devices",
    "make_mesh",
    "models",
    "obs",
    "ops",
    "parallel",
    "pipeline_mesh",
    "runtime",
    "train",
    "utils",
]
