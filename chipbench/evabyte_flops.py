"""Operations and parameters of the `evabyte` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernels' backward) is not
counted, and the elementwise passes are not counted: the norms, the silu,
the rotary turn, the join of the two partial softmaxes and the chunks'
summaries (6 operations a token and channel: bandwidth).

A query of EVA attention has two kinds of key in one softmax: the tokens of
its own window up to itself, `(window + 1) / 2` on average, and the
summaries of the earlier windows' chunks, `window / chunk` for each of the
`(T / window - 1) / 2` windows before it on average. Each pair costs `2 x 2
x heads x head_dim` operations (`q k^T` and `p v`).

At EvaByte's widths cut to four layers and 8,192-byte sequences a token
needs 5.16 GFLOP: per forward the layers' matmuls 4 x 2 x 202,375,168 =
1,619.0 M (attention's four matrices 67,108,864, the feed-forward
135,266,304), EVA's pairs 4 x 16,384 x (1,024.5 + 192) = 79.7 M, the
eight-byte head 2 x 4096 x 2,560 = 21.0 M: 1,719.7 M: the matmuls 94 %,
EVA's pairs 4.6 %, the head 1.2 %; times 3.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

_ROW = 8  # lse and delta are [BH, T, 8] f32, sublane-replicated


def head_dim(config: Dict[str, Any]) -> int:
    return config["d_model"] // config["n_heads"]


def window_pairs(window: int) -> int:
    """The (query, key) pairs of one window a head: causal."""
    return window * (window + 1) // 2


def stair_pairs(seq_len: int, window: int, chunk: int) -> int:
    """The (query, summary) pairs of one sequence a head: window `w`'s
    `window` queries each see the `window / chunk` summaries of each of the
    `w` windows before it: `sum_w window x (window / chunk) w`."""
    n, per = seq_len // window, window // chunk
    return sum(window * per * w for w in range(n))


def keys_per_query(config: Dict[str, Any], seq_len: int) -> float:
    """The keys of both kinds a query sees, on average."""
    window, chunk = config["eva_window"], config["eva_chunk"]
    if seq_len <= window:
        return (seq_len + 1) / 2
    return (seq_len // window * window_pairs(window)
            + stair_pairs(seq_len, window, chunk)) / seq_len


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part."""
    d, L = config["d_model"], config["n_layers"]
    wide = config["n_heads"] * head_dim(config)
    return {
        "attention_matmuls": L * 2 * 4 * d * wide,
        "feed_forwards": L * 2 * 3 * d * config["d_ff"],
        "eva_pairs": L * 2 * 2 * wide * keys_per_query(config, seq_len),
        "head": 2 * d * config["vocab_size"] * config["n_pred_heads"],
    }


def evabyte_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def state_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: a layer's four attention matrices,
    its feed-forward, its two norms and its two EVA vectors a head; the
    embedding, the eight-byte head and the final norm."""
    d = config["d_model"]
    wide = config["n_heads"] * head_dim(config)
    layer = 4 * d * wide + 3 * d * config["d_ff"] + 2 * d + 2 * wide
    return (config["n_layers"] * layer + config["vocab_size"] * d
            + d * config["vocab_size"] * config["n_pred_heads"] + d)


# matmuls over the pairs: forward s = q k^T and o = p v; the one backward
# kernel makes s and dp = do v^T again and adds dv, dk and dq
_FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dkv_dq": 5}


def flash_call(kernel: str, sequences: int, n_heads: int, rows: int,
               keys: int, pairs: int, dim: int) -> Tuple[float, float]:
    """(operations, bytes) of one call of a flash kernel on `sequences x
    n_heads` rows of `rows` queries against `keys` keys, `pairs` (query,
    key) pairs each, heads `dim` wide. `kernel_flops.py`'s convention: the
    pairs the mask leaves, every operand and result once; q, k, v, o, do
    and the gradients are bf16, lse and delta float32."""
    bh = sequences * n_heads
    ops = _FLASH_MATMULS[kernel] * 2.0 * pairs * dim * bh
    q, k = bh * rows * dim, bh * keys * dim
    lse = bh * rows * _ROW * 4
    bytes_moved = {
        # q, k, v in; o and lse out
        "flash_fwd": (2 * q + 2 * k) * 2 + lse,
        # q, k, v, do, lse and delta in; dq, dk and dv out
        "flash_bwd_dkv_dq": (3 * q + 4 * k) * 2 + 2 * lse,
    }[kernel]
    return ops, float(bytes_moved)


def window_call(kernel: str, config: Dict[str, Any], seq_len: int,
                sequences: int = 1) -> Tuple[float, float]:
    """One call of the window's part: the causal kernel on the windows
    folded into the batch."""
    window = config["eva_window"]
    return flash_call(kernel, sequences * (seq_len // window),
                      config["n_heads"], window, window,
                      window_pairs(window), head_dim(config))


def stair_call(kernel: str, config: Dict[str, Any], seq_len: int,
               sequences: int = 1) -> Tuple[float, float]:
    """One call of the staircase's part: every query against the
    summaries of the earlier windows' chunks."""
    window, chunk = config["eva_window"], config["eva_chunk"]
    return flash_call(kernel, sequences, config["n_heads"], seq_len,
                      seq_len // chunk, stair_pairs(seq_len, window, chunk),
                      head_dim(config))


def summaries_call(kernel: str, config: Dict[str, Any], seq_len: int,
                   sequences: int = 1) -> Tuple[float, float]:
    """(operations, bytes) of one call of `eva_summaries_fwd` or
    `eva_summaries_bwd`. The forward reads k and v once and writes a
    `chunk`-th of each; `phi . k`, its softmax's weighted sums of k and of v:
    6 operations a token and channel. The backward reads k, v and the
    summaries' cotangents and writes dk and dv; the weights again, the
    cotangents spread over the tokens, `dw`, `da`, dk, dv and `d phi`: 16 a
    token and channel (the spreading matmul, `2 x tokens / chunk` a token
    and channel in a block of 2,048 tokens, is the kernel's own device and
    not counted)."""
    elements = sequences * seq_len * config["n_heads"] * head_dim(config)
    chunk = config["eva_chunk"]
    if kernel == "eva_summaries_fwd":
        return 6.0 * elements, float(2 * elements * 2
                                     + 2 * elements // chunk * 2)
    return 16.0 * elements, float(4 * elements * 2
                                  + 2 * elements // chunk * 2)
