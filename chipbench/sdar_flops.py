"""Operations and parameters of the `sdar` family, from the configuration's
shapes alone. The benchmark's own count, by `flops.py`'s convention: a
multiply-add is 2 operations, the backward pass costs twice the forward,
recomputed work (remat, the flash kernel's backward) is not counted, and the
elementwise passes (norms, rotary positions, the router's softmax, the noise)
are bandwidth and not counted.

A token of a sequence is TWO rows of the stack, the noisy copy and the clean
one: the projections, the router and the held experts are counted for both,
the head for the noisy row alone. Attention is counted as the mask needs it
and no more: a row of block `b` sees the `block * b` clean rows before its
block and the `block` rows of its own half's block, so a head walks
`seq_len + block` pairs for the two rows of a token on average
(`pairs_per_token`; 268.5 M a head for 16,384 tokens under blocks of 4, twice
a causal step's 134.2 M), `2 x 2 x 32 x 128` operations a pair. What the
staircase's diagonal tiles compute above the stairs and mask is not counted.
The routed experts are credited with what this chip computes, as
`keye_vl2_flops.py` does: `experts_per_token x held / n_experts` experts a
row in expectation. The router is whole.

At SDAR-30B-A3B's widths cut to layers 0 to 3, 16 of 128 experts, 18,992 ids
and 16,384-token sequences a token needs 4.600 GFLOP: per forward 301.99 M
in the four layers' attention projections (two rows), 1,074.00 M in the
pairs (268.50 M a layer), 4.19 M in the routers, 75.50 M in the held experts
and 77.79 M in the head, times 3.

`stair_call` is the numerator of the two staircase kernels' roofline shares.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ROWS_PER_TOKEN = 2  # the noisy copy and the clean one
# (matmuls over q and k's width, matmuls over v's) over a tile's pairs, as
# `keye_vl2_flops.py` has them
_MATMULS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
            "flash_bwd_dkv": (2, 2), "flash_bwd_dkv_dq": (3, 2)}
_ROW = 8  # lse and delta are [*, T, 8] float32


def pairs_per_token(seq_len: int, block: int) -> float:
    """The (row, key) pairs a head walks for the two rows of a token, on
    average over a sequence of `seq_len` in blocks of `block`: each row the
    `block * (i // block)` clean rows before its block, `(seq_len - block) /
    2` on average, and its own block's `block` rows."""
    return ROWS_PER_TOKEN * ((seq_len - block) / 2 + block)


def stair_pairs(seq_len: int, block: int) -> int:
    """The pairs one query head walks under the staircase alone: `sum_i
    block * (i // block)` over the `seq_len` queries of one half."""
    blocks = seq_len // block
    return block * block * blocks * (blocks - 1) // 2


def _held(config: Dict[str, Any]) -> int:
    return (config.get("experts_held") or (0, config["n_experts"]))[1]


def attention_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_k`, `W_v` and `W_o` of one layer (no norm)."""
    d, width = config["d_model"], config["d_head"]
    return 2 * d * (config["n_heads"] + config["n_kv_heads"]) * width


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, f, layers = config["d_model"], config["d_ff"], config["n_layers"]
    slots = config["experts_per_token"] * _held(config) / config["n_experts"]
    rows = ROWS_PER_TOKEN
    return {
        "attention_projections": layers * rows * 2 * attention_params(config),
        # s = q k^T and ctx = p v over the pairs: 2 x 2 width a pair
        "pairs": (layers * 4 * config["n_heads"] * config["d_head"]
                  * pairs_per_token(seq_len, config["diffusion_block"])),
        "router": layers * rows * 2 * d * config["n_experts"],
        "experts": layers * rows * slots * 2 * 3 * d * f,
        "head": 2 * d * config["vocab_size"],  # the noisy row alone
    }


def sdar_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def state_params(config: Dict[str, Any]) -> int:
    """All parameters this chip holds (the held experts and the router
    whole, the embedding and the untied head over the vocabulary slice; a
    layer's two norms over the stream and q's and k's a head): what the
    state's bytes follow from."""
    d, f = config["d_model"], config["d_ff"]
    layer = (attention_params(config) + 2 * d + 2 * config["d_head"]
             + d * config["n_experts"] + _held(config) * 3 * d * f)
    return 2 * config["vocab_size"] * d + d + config["n_layers"] * layer


def whole_model_params(published: Dict[str, Any]) -> int:
    """The parameters of the uncut model from the published config.json's
    own keys (`catalog_config`): 30.5 B, the published "30B"."""
    d, width = published["hidden_size"], published["head_dim"]
    layer = (2 * d * (published["num_attention_heads"]
                      + published["num_key_value_heads"]) * width
             + 2 * d + 2 * width + d * published["num_experts"]
             + published["num_experts"] * 3 * d
             * published["moe_intermediate_size"])
    return (2 * published["vocab_size"] * d + d
            + published["num_hidden_layers"] * layer)


def stair_call(kernel: str, sequences: int, heads: int, kv_heads: int,
               seq_len: int, block: int, qk_dim: int, v_dim: int
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call of the flash `kernel` under the
    staircase of block diffusion (`flash_fwd`, `flash_bwd_dkv_dq`,
    `flash_bwd_dq`, `flash_bwd_dkv`; the `pallas_call` is named
    `<kernel>_stair`) on `sequences` sequences of `seq_len` tokens: both
    halves' `heads` query heads, `2 heads` a sequence, against the clean
    half's `kv_heads` key-value heads. Operations: the kernel's matmuls over
    the pairs the staircase leaves, `stair_pairs` a query head; a pair a
    diagonal tile computes and masks is not counted. Bytes: every operand
    and result once, bf16 as the training step passes them: q, o, do and dq
    at `2 seq_len` rows of `heads`, k, v, dk and dv at `seq_len` rows of
    `kv_heads`, lse and delta float32 `[2 heads, seq_len, 8]`."""
    over_qk, over_v = _MATMULS[kernel]
    bh, bkv = sequences * ROWS_PER_TOKEN * heads, sequences * kv_heads
    ops = 2.0 * stair_pairs(seq_len, block) * bh * (
        over_qk * qk_dim + over_v * v_dim)
    q, o = bh * seq_len * qk_dim, bh * seq_len * v_dim  # q, dq; o, do
    k, v = bkv * seq_len * qk_dim, bkv * seq_len * v_dim  # k, dk; v, dv
    row = bh * seq_len * _ROW * 4  # bytes of lse or delta
    read = (q + k + v + o) * 2 + 2 * row  # q, k, v, do, lse, delta
    bytes_moved = {
        "flash_fwd": (q + k + v + o) * 2 + row,  # o and lse out
        "flash_bwd_dq": read + q * 2,
        "flash_bwd_dkv": read + (k + v) * 2,
        "flash_bwd_dkv_dq": read + (q + k + v) * 2,
    }[kernel]
    return ops, float(bytes_moved)
