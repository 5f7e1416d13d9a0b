"""ray_tpu.ops: TPU kernels for the hot ops.

The compute path of the framework is JAX/XLA; these Pallas kernels cover the
ops where hand-tiling beats XLA's default lowering (attention above all —
the reference delegates this tier to NCCL-adjacent GPU libraries; here it is
MXU-tiled Pallas). Every op has an XLA fallback so the same code runs on CPU
(tests) and on the TPU (the cells of `chipbench`) unchanged.
"""

from ray_tpu.ops.flash_attention import flash_attention, mha
from ray_tpu.ops.fused import fused_rmsnorm, softmax_cross_entropy
from ray_tpu.ops.moe import grouped_matmul

__all__ = [
    "flash_attention",
    "mha",
    "grouped_matmul",
    "fused_rmsnorm",
    "softmax_cross_entropy",
]
