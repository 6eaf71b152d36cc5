"""Device-side ops: gathers, segment reductions, masked normalization.

TPU-native replacement for the reference's native kernel surface
(SURVEY.md §2 "Native components" table): ATen gather + per-node reduction
become XLA row gathers and segment ops, and cuDNN BatchNorm becomes an
in-tree masked BatchNorm that keeps padding out of the batch statistics.
"""

from cgnn_tpu.ops.segment import (
    segment_sum,
    segment_mean,
    gather,
    aggregate_edge_messages,
)
from cgnn_tpu.ops.norm import MaskedBatchNorm

__all__ = [
    "segment_sum",
    "segment_mean",
    "gather",
    "aggregate_edge_messages",
    "MaskedBatchNorm",
]
