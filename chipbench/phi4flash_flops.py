"""Operations and parameters of the `phi4flash` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward, the scan's
chunks made again) is not counted, and the elementwise passes are not
counted: the LayerNorms, the taps, the silus, the pair norm, and the
selective scan itself, `inner x state` multiply-adds and as many `exp` a
token and Mamba layer on the vector unit (81,920 at published widths), as
`nemotron_h_flops.py` leaves the scan's decays out.

A differential layer's pair of softmaxes is two maps a pair: every one of
the `n_heads` query heads has its `q k^T` over `d_head` and its `p V` over
the pair's `2 d_head`, `2 n_heads (d_head + 2 d_head)` operations a (query,
key); a whole causal layer sees `(T + 1) / 2` keys a query, a layer under
the window the band's (`flops.keys_per_query`).

At Phi-4-mini-flash-reasoning's widths cut to the six layers (0, 1, 16, 17,
18, 19), an eighth of the vocabulary and 16,384-token sequences a token
needs 4.96 GFLOP: per forward the layers' matmuls 2 x 632,750,080 =
1,265.5 M (six feed-forwards of 78,643,200; two Mamba mixers of 41,123,840:
in 26,214,400, x 983,040, dt 819,200, out 13,107,200; two attention layers
of 19,660,800; the unit 26,214,400; the cross layer 13,107,200), the two
whole-context layers 2 x 15,360 x 8,192.5 = 251.7 M, the window's 15,360 x
504.0 = 7.7 M, the head 2 x 2560 x 25,008 = 128.0 M. 1,652.9 M: the layers'
matmuls 77 %, attention's pairs 16 %, the head 8 %; times 3.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

_MAMBA = ("mamba1", "mamba1_emit")
_WHOLE = ("diff_attention", "diff_attention_emit", "cross_diff_attention")


def dt_rank(config: Dict[str, Any]) -> int:
    return config.get("mamba1_dt_rank") or -(-config["d_model"] // 16)


def mixer_matmul_params(config: Dict[str, Any], kind: str) -> int:
    """The matrices of one layer's mixer, in elements."""
    d, inner = config["d_model"], config["mamba1_inner"]
    wide = config["n_heads"] * config["d_head"]
    kv = config["n_kv_heads"] * config["d_head"]
    if kind in _MAMBA:
        rank = dt_rank(config)
        return (d * 2 * inner + inner * (rank + 2 * config["mamba1_state"])
                + rank * inner + inner * d)
    if kind == "gmu":
        return 2 * d * inner
    if kind == "cross_diff_attention":
        return 2 * d * wide
    return d * (wide + 2 * kv) + wide * d


def keys_per_query(seq_len: int, window=None) -> float:
    """The keys a query sees, on average: the pairs over the queries."""
    return flash_pairs(seq_len, window) / seq_len


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part."""
    d, kinds = config["d_model"], config["layer_types"]
    pair = 2 * config["n_heads"] * 3 * config["d_head"]
    return {
        "feed_forwards": len(kinds) * 2 * 3 * d * config["d_ff"],
        "mixers": sum(2 * mixer_matmul_params(config, k) for k in kinds),
        "whole_pairs": sum(k in _WHOLE for k in kinds) * pair
        * keys_per_query(seq_len),
        "window_pairs": kinds.count("sliding_diff_attention") * pair
        * keys_per_query(seq_len, config["sliding_window"]),
        "head": 2 * d * config["vocab_size"],
    }


def phi4flash_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def mixer_other_params(config: Dict[str, Any], kind: str) -> int:
    """A mixer's leaves that are no matrix: biases, taps, lambdas, norms."""
    d, inner = config["d_model"], config["mamba1_inner"]
    wide = config["n_heads"] * config["d_head"]
    kv = config["n_kv_heads"] * config["d_head"]
    dh = config["d_head"]
    if kind in _MAMBA:  # taps and their bias, dt's bias, A_log, D
        return (inner * (config["mamba1_conv_taps"] + 1) + inner
                + inner * config["mamba1_state"] + inner)
    if kind == "gmu":
        return 0
    lambdas_and_norm = 4 * dh + 2 * dh
    if kind == "cross_diff_attention":
        return wide + d + lambdas_and_norm
    return wide + 2 * kv + d + lambdas_and_norm


def state_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers with their two
    LayerNorms (a scale and a bias each), the tied embedding's slice and
    the final norm."""
    d = config["d_model"]
    layers = sum(
        mixer_matmul_params(config, k) + mixer_other_params(config, k)
        + 3 * d * config["d_ff"] + 4 * d for k in config["layer_types"])
    return layers + d * config["vocab_size"] + 2 * d


# matmuls over the pairs (over q and k's width, over v's): forward s = q k^T
# and o = p v; dq makes s and dp = do v^T again and adds dq = ds k; dk/dv
# makes s and dp again and adds dv = p^T do and dk = ds^T q
_FLASH_MATMULS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
                  "flash_bwd_dkv": (2, 2)}
_ROW = 8  # lse and delta are [BH, T, 8] f32, sublane-replicated


def flash_pairs(seq_len: int, window: Optional[int] = None) -> int:
    """The (query, key) pairs of one causal sequence a head, whole or
    under the window's band."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def flash_call(kernel: str, sequences: int, n_heads: int, n_kv_heads: int,
               seq_len: int, window: Optional[int], qk_dim: int, v_dim: int
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call of a flash kernel as a differential
    layer makes it: `n_heads` query heads `qk_dim` wide over `n_kv_heads`
    key heads of that width and value heads `v_dim` wide (the pair's width,
    V handed over twice), on `sequences` causal sequences of `seq_len`.
    `kernel_flops.py`'s convention: the pairs the mask leaves, every operand
    and result once; q, k, v, o and do are bf16, lse, delta, dq, dk and dv
    float32."""
    over_qk, over_v = _FLASH_MATMULS[kernel]
    ops = (2.0 * flash_pairs(seq_len, window) * sequences * n_heads
           * (over_qk * qk_dim + over_v * v_dim))
    rows = sequences * seq_len
    q, o = rows * n_heads * qk_dim, rows * n_heads * v_dim
    k, v = rows * n_kv_heads * qk_dim, rows * n_kv_heads * v_dim
    lse = rows * n_heads * _ROW * 4
    bytes_moved = {
        "flash_fwd": (q + k + v + o) * 2 + lse,
        "flash_bwd_dq": (q + k + v + o) * 2 + 2 * lse + q * 4,
        "flash_bwd_dkv": (q + k + v + o) * 2 + 2 * lse + (k + v) * 4,
    }[kernel]
    return ops, float(bytes_moved)
