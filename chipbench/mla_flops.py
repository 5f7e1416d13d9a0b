"""Operations and bytes one call of a flash kernel needs where q and k are
one width and v another (latent attention: 128 + 64 rotary against 128),
from the call's shapes alone: `kernel_flops.flash_call` with two widths, by
the same convention (a multiply-add is 2 operations, causal pairs, the
masked half of a diagonal tile not counted, every operand and result once).

Over the pairs the forward makes `s = q k^T` at `Dqk` and `o = p v` at
`Dv`: `2 pairs (Dqk + Dv)` a head; dq makes `s`, `dp = do v^T` at `Dv` and
`dq = ds k` at `Dqk`: `2 pairs (2 Dqk + Dv)`; dk/dv makes `s`, `dp`,
`dv = p^T do` and `dk = ds^T q`: `2 pairs (2 Dqk + 2 Dv)`. The widths are
the published ones: a kernel that pads 192 to 256 lanes inside does no more
of this work, and shows a lower share.
"""

from __future__ import annotations

from typing import Tuple

from chipbench.kernel_flops import causal_pairs

# (matmuls over q and k's width, matmuls over v's) over the pairs
_MATMULS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1), "flash_bwd_dkv": (2, 2)}
_ROW = 8  # lse and delta are [BH, T, 8] f32, sublane-replicated


def flash_call(kernel: str, bh: int, seq_len: int, qk_dim: int, v_dim: int
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call of `kernel` on `bh` (batch x head)
    causal sequences of `seq_len`, q and k `qk_dim` wide and v `v_dim`; q,
    k, v, o and do are bf16, lse, delta, dq, dk and dv are f32, as the
    training step passes them. The forward is the one that also writes
    lse."""
    over_qk, over_v = _MATMULS[kernel]
    ops = 2.0 * causal_pairs(seq_len) * bh * (over_qk * qk_dim + over_v * v_dim)
    qk = bh * seq_len * qk_dim  # elements of q, k, dq, dk
    vo = bh * seq_len * v_dim   # elements of v, o, do, dv
    row = bh * seq_len * _ROW * 4  # bytes of lse or delta
    bytes_moved = {
        "flash_fwd": (2 * qk + 2 * vo) * 2 + row,
        "flash_bwd_dq": (2 * qk + 2 * vo) * 2 + 2 * row + qk * 4,
        "flash_bwd_dkv": (2 * qk + 2 * vo) * 2 + 2 * row + (qk + vo) * 4,
    }[kernel]
    return ops, float(bytes_moved)
