"""Operations and parameters of the `kimi_linear` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted, and the elementwise passes (norms, convolutions' taps, gates'
sigmoids, decays, the router's scores) and the triangular solve are not
counted.

Only what this chip holds is counted: the `experts_held` experts in
expectation (`experts_per_token x held / n_experts` experts a token) and the
vocabulary slice. The mixers (all `kda_heads` heads, all `n_heads` heads of
latent attention), the dense feed-forward, the shared expert and the router
are whole.

KDA is counted as its chunked form computes it (`ray_tpu/ops/kda.py`), not
as the recurrence would: a head and token, with `C` the chunk and `dk`, `dv`
the head's widths, the pair products of k with k and of q with k and
`W = T (K e^G)`, `2 C dk` each; `U = T V` and the scores' product with the
fresh values, `2 C dv` each; `W S`, `q S` and the state's update, `2 dk dv`
each: `6 C dk + 4 C dv + 6 dk dv`, 180,224 at `C` 64 and 128 (the recurrence
itself would take `6 dk dv`). The kernels' `[128, 128]` tiles hold two
heads' chunks on the diagonal; the off-diagonal halves are not needed and
not counted.

At Kimi-Linear-48B-A3B's widths cut to published layers 1 to 5 (KDA with the
dense feed-forward, KDA, KDA, latent attention, KDA; 8 of 256 experts, 20,480
ids) and 16,384-token sequences a token needs 2.586 GFLOP: per forward
315.69 M in the four KDA mixers' products and 23.07 M in their chunked
recurrence, 58.23 M in latent attention's projections and 167.78 M in its
pairs, 127.40 M in the dense feed-forward, 4.72 M in the routers, 14.16 M in
the held experts, 56.62 M in the shared experts and 94.37 M in the head,
862.04 M, times 3.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def _rank(config: Dict[str, Any]) -> int:
    return config.get("kda_gate_rank") or config["kda_head_dim"]


def kda_params(config: Dict[str, Any]) -> int:
    """The matmul weights of one KDA mixer: `W_q`, `W_k`, `W_v`, `W_o`, the
    two low-rank gates and beta (no taps, vectors or norms)."""
    d, rank = config["d_model"], _rank(config)
    wide = config["kda_heads"] * config["kda_head_dim"]
    return 4 * d * wide + 2 * (d * rank + rank * wide) + d * config["kda_heads"]


def kda_chunked(heads: int, dk: int, dv: int, chunk: int) -> int:
    """Forward operations a token of the chunked recurrence over `heads`."""
    return heads * (6 * chunk * dk + 4 * chunk * dv + 6 * dk * dv)


def latent_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_kva`, `W_kvb` and `W_o` of one latent-attention layer."""
    d, h, r = config["d_model"], config["n_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
            + h * dv * d)


def _kinds(config: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """(KDA layers, latent-attention layers, dense layers, routed layers)."""
    kinds = config["layer_types"]
    n_kda = sum(kind == "kda" for kind in kinds)
    dense = config["n_dense_layers"]
    return n_kda, len(kinds) - n_kda, dense, len(kinds) - dense


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, f = config["d_model"], config["d_ff"]
    n_kda, n_latent, n_dense, n_routed = _kinds(config)
    held = (config.get("experts_held") or (0, config["n_experts"]))[1]
    slots = config["experts_per_token"] * held / config["n_experts"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    width = config["kda_head_dim"]
    return {
        "kda_matmuls": n_kda * 2 * kda_params(config),
        "kda_chunked": n_kda * kda_chunked(
            config["kda_heads"], width, width, config["kda_chunk"]),
        "latent_matmuls": n_latent * 2 * latent_params(config),
        # q k^T over 192 columns and p v over 128, the causal triangle's
        # (T + 1) / 2 keys a query
        "latent_pairs": n_latent * 2 * config["n_heads"] * (
            qk + config["v_head_dim"]) * (seq_len + 1) / 2,
        "dense_ff": n_dense * 2 * 3 * d * config["d_ff_dense"],
        "router": n_routed * 2 * d * config["n_experts"],
        "held_experts": n_routed * slots * 2 * 3 * d * f,
        "shared_experts": n_routed * 2 * 3 * d * f * config[
            "n_shared_experts"],
        "head": 2 * d * config["vocab_size"],
    }


def flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token: the kept work only."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def state_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds, leaf by leaf, vectors and norms
    included."""
    d, f = config["d_model"], config["d_ff"]
    heads, width = config["kda_heads"], config["kda_head_dim"]
    wide = heads * width
    n_kda, n_latent, n_dense, n_routed = _kinds(config)
    held = (config.get("experts_held") or (0, config["n_experts"]))[1]
    kda = (kda_params(config)
           + 3 * config["kda_conv_taps"] * wide  # q's, k's and v's taps
           + wide + heads + wide + width)  # b_g, A_log, dt_bias, the norm
    latent = latent_params(config) + config["kv_lora_rank"]  # the norm
    routed = (config["n_shared_experts"] * 3 * d * f
              + d * config["n_experts"] + held * 3 * d * f)
    return (n_kda * kda + n_latent * latent
            + n_dense * 3 * d * config["d_ff_dense"] + n_routed * routed
            + len(config["layer_types"]) * 2 * d  # a layer's two norms
            + 2 * d * config["vocab_size"] + d)


def kda_call(kernel: str, heads: int, seq_len: int, dk: int, dv: int,
             chunk: int, states: bool = True) -> Tuple[float, float]:
    """(operations, bytes) of one call of `kda_fwd` or `kda_bwd` on one
    sequence of `seq_len` tokens over `heads` heads: the numerator of the
    kernel's roofline share.

    Operations: `kda_fwd` the chunk's matmuls and the state's step
    (`kda_chunked`). `kda_bwd` makes the chunk again from its blocks and the
    entering states and adds two gradient products a forward product, three
    times the forward's, and the inverse's cotangent `-X^T dX X^T`, two
    `[C, C]` products a chunk (`4 C C` a token and head). The forward
    substitution runs on the vector unit and is not counted.

    Bytes, each operand and result once with its dtype: q, k (bf16, `dk`),
    v (bf16, `dv`), the log decay g (float32, `dk`), beta (float32) and o
    (bf16, `dv`) a token and head, and a chunk's entering state (float32,
    `dk dv`) where the call writes them (`states`: the forward made again
    under remat does, the first forward does not). `kda_bwd` reads those and
    do (bf16) and writes dq, dk, dv (bf16), dg and dbeta (float32)."""
    tokens = heads * seq_len
    inputs = tokens * (2 * dk * 2 + dv * 2 + dk * 4 + 4)
    entering = heads * (seq_len // chunk) * dk * dv * 4
    forward = seq_len * kda_chunked(heads, dk, dv, chunk)
    if kernel == "kda_fwd":
        return float(forward), float(
            inputs + tokens * dv * 2 + (entering if states else 0))
    if kernel != "kda_bwd":
        raise ValueError(f"kda_call of {kernel!r}")
    return (3.0 * forward + tokens * 4.0 * chunk * chunk,
            float(2 * inputs + entering + tokens * dv * 2))
