"""cgnn-tpu: a TPU-native crystal-graph neural network framework.

A ground-up JAX/XLA re-design of the capability surface of the reference
PyTorch/CUDA stack ``CaoAo/CGNN`` (see SURVEY.md — note §0: the reference mount
was empty at survey time, so parity targets come from BASELINE.json and the
reconstructed architecture in SURVEY.md §1-§3).

Layout:
    cgnn_tpu.data      — CIF parsing, periodic neighbor lists, featurization,
                         graph containers, bucketed/padded batching.
    cgnn_tpu.models    — Flax CGCNN model (edge-gated CGConv over dense
                         edge slots; a flat COO body as reference), heads.
    cgnn_tpu.ops       — gathers with declared transposes, segment ops,
                         masked BatchNorm, the in-program neighbor search.
    cgnn_tpu.parallel  — device mesh, data-parallel training over ICI
                         (shard_map + psum).
    cgnn_tpu.train     — training runtime: train state, normalizer,
                         checkpointing (orbax), metrics, loops.
"""

__version__ = "0.1.0"
