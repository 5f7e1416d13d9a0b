"""Operations and bytes one call of a kernel needs, from the call's shapes
alone: the numerator of a kernel's roofline share. `flops.py`'s convention
(a multiply-add is 2 operations); nothing here is measured.

The flash kernels (`ray_tpu/ops/flash_attention.py`) work on `[BH, T, D]`
arrays, causal: a query attends to itself and to what came before it, so a
sequence has `T (T + 1) / 2` (query, key) pairs, and a matmul over the pairs
costs `2 D` operations a pair. The kernels visit whole 128 x 128 tiles and
mask the diagonal ones; the masked half of those tiles is not needed and is
not counted. Bytes are each operand and each result once: what the call
cannot avoid moving between HBM and the chip, whatever its tiling re-reads.
"""

from __future__ import annotations

from typing import Dict, Tuple

# matmuls over the pairs: forward s = q k^T, o = p v; dq recomputes s and
# adds dp = do v^T, dq = ds k; dk/dv recomputes s and dp and adds
# dv = p^T do, dk = ds^T q
_FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
_ROW = 8  # lse and delta are [BH, T, 8] f32, sublane-replicated


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def flash_call(kernel: str, bh: int, seq_len: int, head_dim: int
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call of `kernel` on `bh` (batch x head)
    causal sequences of `seq_len` with heads of `head_dim`; q, k, v, o and
    do are bf16, lse, delta, dq, dk and dv are f32, as the training step
    passes them. The forward is the one that also writes lse."""
    ops = _FLASH_MATMULS[kernel] * 2.0 * causal_pairs(seq_len) * head_dim * bh
    tensor = bh * seq_len * head_dim  # elements of q, k, v, o, do, dq, dk, dv
    row = bh * seq_len * _ROW * 4     # bytes of lse or delta
    bytes_moved = {
        "flash_fwd": 3 * tensor * 2 + tensor * 2 + row,
        "flash_bwd_dq": 4 * tensor * 2 + 2 * row + tensor * 4,
        "flash_bwd_dkv": 4 * tensor * 2 + 2 * row + 2 * tensor * 4,
    }[kernel]
    return ops, float(bytes_moved)


def least_seconds(ops: float, bytes_moved: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip could take for a call, and which peak sets
    it: operations over the bf16 peak or bytes over the HBM peak."""
    compute = ops / peaks["bf16_flops_per_s"]
    memory = bytes_moved / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
