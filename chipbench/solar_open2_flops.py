"""Operations and parameters of the `solar_open2` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted, and the elementwise passes (norms, convolutions' taps, gates'
sigmoids, decays, the router's scores) and the triangular solve are not
counted.

Only what this chip holds is counted: the `heads_held` heads of every mixer
(for attention the key-value heads they read), the `experts_held` experts
in expectation (`experts_per_token x held / n_experts` experts a token), the
vocabulary slice. The shared expert and the router are whole.

KDA is counted as its chunked form computes it (`ray_tpu/ops/kda.py`), not
as the recurrence would: a head and token, with `C` the chunk and `d` the
head's width, the pair products of k with k and of q with k, `W = T (K
e^G)`, `U = T V` and the scores' product with the fresh values, `2 C d`
each; `W S`, `q S` and the state's update, `2 d d` each: `10 C d + 6 d d`,
180,224 at `C` 64 and `d` 128 (the recurrence itself would take `6 d d`).

At Solar-Open2-250B's widths cut to layers 0 to 3 (GQA, KDA, KDA, KDA), 8 of
64 heads, 8 of 320 experts, 24,576 ids and 8192-token sequences a token
needs 1.560 GFLOP: per forward 108.72 M in the three KDA mixers' products
and 4.33 M in their chunked recurrence, 27.26 M in attention's projections
and gate and 16.78 M in its pairs, 10.49 M in the routers, 25.17 M in the
held experts, 125.83 M in the shared experts and 201.33 M in the head,
519.90 M, times 3.
"""

from __future__ import annotations

from typing import Any, Dict


def _held_heads(config: Dict[str, Any], all_heads: int) -> int:
    return (config.get("heads_held") or (0, all_heads))[1]


def kda_params(config: Dict[str, Any]) -> int:
    """The matmul weights of one KDA mixer over the held heads: `W_q`,
    `W_k`, `W_v`, `W_o`, the two low-rank gates and beta (no taps, vectors
    or norms)."""
    d, width = config["d_model"], config["kda_head_dim"]
    heads = _held_heads(config, config["kda_heads"])
    rank = config.get("kda_gate_rank") or width
    wide = heads * width
    return 4 * d * wide + 2 * (d * rank + rank * wide) + d * heads


def kda_chunked(config: Dict[str, Any]) -> int:
    """Forward operations a token of the chunked recurrence, held heads."""
    width, chunk = config["kda_head_dim"], config["kda_chunk"]
    heads = _held_heads(config, config["kda_heads"])
    return heads * (10 * chunk * width + 6 * width * width)


def attention_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_k`, `W_v`, `W_o` and the elementwise gate of one attention
    layer over the held query heads and the key-value heads they read."""
    d, width = config["d_model"], config["d_head"]
    heads = _held_heads(config, config["n_heads"])
    group = config["n_heads"] // config["n_kv_heads"]
    kv = max(heads // group, 1)
    return d * width * (2 * heads + 2 * kv) + d * heads * width


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, f = config["d_model"], config["d_ff"]
    kinds = config["layer_types"]
    n_kda = sum(kind == "kda" for kind in kinds)
    n_attn = len(kinds) - n_kda
    heads = _held_heads(config, config["n_heads"])
    held = (config.get("experts_held") or (0, config["n_experts"]))[1]
    slots = config["experts_per_token"] * held / config["n_experts"]
    return {
        "kda_matmuls": n_kda * 2 * kda_params(config),
        "kda_chunked": n_kda * kda_chunked(config),
        "attention_matmuls": n_attn * 2 * attention_params(config),
        # q k^T and p v over the causal triangle, (T + 1) / 2 keys a query
        "attention_pairs": n_attn * 2 * 2 * heads * config["d_head"] * (
            seq_len + 1) / 2,
        "router": len(kinds) * 2 * d * config["n_experts"],
        "held_experts": len(kinds) * slots * 2 * 3 * d * f,
        "shared_experts": len(kinds) * 2 * 3 * d * config["d_ff_shared"],
        "head": 2 * d * config["vocab_size"],
    }


def solar_open2_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def state_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds, vectors and norms included."""
    d, width = config["d_model"], config["kda_head_dim"]
    heads = _held_heads(config, config["kda_heads"])
    wide = heads * width
    held = (config.get("experts_held") or (0, config["n_experts"]))[1]
    kda = (kda_params(config) + 3 * config["kda_conv_taps"] * wide
           + wide + heads + wide + width)  # b_g, A_log, dt_bias, the norm
    feed_forward = (3 * d * config["d_ff_shared"] + d * config["n_experts"]
                    + held * 3 * d * config["d_ff"])
    kinds = config["layer_types"]
    n_kda = sum(kind == "kda" for kind in kinds)
    return (n_kda * kda + (len(kinds) - n_kda) * attention_params(config)
            + len(kinds) * (feed_forward + 2 * d)
            + 2 * d * config["vocab_size"] + d)
