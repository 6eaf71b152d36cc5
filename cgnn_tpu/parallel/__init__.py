"""Distributed layer: data parallelism over a mesh.

TPU-native replacement for the reference's NCCL DDP (SURVEY.md §1
"Distributed layer", §2 parallelism inventory, §5 "Distributed communication
backend"): no process groups, no rendezvous, no gradient buckets — one SPMD
program over ``Mesh(devices, ('data',))`` where XLA emits the ICI/DCN
collectives from ``psum``/``pmean`` inside ``shard_map``. Scaling past one
pod slice adds a DCN axis to the same mesh; the step body is unchanged.
"""

from cgnn_tpu.parallel.mesh import make_mesh, device_count
from cgnn_tpu.parallel.data_parallel import (
    stack_batches,
    empty_batch_like,
    make_parallel_train_step,
    make_parallel_eval_step,
    parallel_batches,
    shard_leading_axis,
    replicate_state,
    fit_data_parallel,
)
from cgnn_tpu.parallel.executor import MeshExecutor

__all__ = [
    "make_mesh",
    "device_count",
    "stack_batches",
    "empty_batch_like",
    "make_parallel_train_step",
    "make_parallel_eval_step",
    "parallel_batches",
    "shard_leading_axis",
    "replicate_state",
    "fit_data_parallel",
    "MeshExecutor",
]
