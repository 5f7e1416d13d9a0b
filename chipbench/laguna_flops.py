"""Operations and parameters of the `laguna` family, from the configuration's
shapes alone. The benchmark's own count, by `flops.py`'s convention: a
multiply-add is 2 operations, the backward pass costs twice the forward,
recomputed work (remat, the flash kernel's backward) is not counted, and the
elementwise passes (norms, rotary positions, gates' sigmoids, the router's
scores) are bandwidth and not counted.

Attention is counted by layer type: the projections and the per-head gate
at the layer's own head count (48 query heads on a full layer, 64 on a
sliding one, 8 key-value heads on both), and the pairs a query really has:
a full layer's causal triangle, `(T + 1) / 2` keys a query, and a sliding
layer's band, `window (window + 1) / 2 + (T - window) window` pairs, 496.03
keys a query at `T` 8192 under the window of 512. No pair a tile computes
and masks is counted. The routed experts are credited with what this chip
computes, as `dsv2_flops.py` does: `experts_per_token x held / n_experts`
experts a token in expectation. The shared expert and the router are whole.

At Laguna-XS.2's widths cut to layers 0 to 4 (one dense), 32 of 256 experts,
12544 ids and 8192-token sequences a token needs 2.405 GFLOP: per forward
345.10 M in the five layers' attention projections and gates, 201.33 M in
the two full layers' pairs, 48.76 M in the three sliding layers', 100.66 M
in the dense feed-forward, 4.19 M in the routers, 25.17 M in the held
experts, 25.17 M in the shared experts and 51.38 M in the head, times 3.

`window_flash_call` is the windowed flash kernels' numerator of a roofline
share: operations over the band's pairs and every operand and result once.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

SLIDING = "sliding_attention"
# (matmuls over q and k's width, matmuls over v's) over a tile's pairs:
# `mla_flops.py`'s, and the one kernel that makes all three gradients from
# one tile (s, dq, dk | dp, dv)
_MATMULS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
            "flash_bwd_dkv": (2, 2), "flash_bwd_dkv_dq": (3, 2)}
_ROW = 8  # lse and delta are [BH, T, 8] f32, sublane-replicated


def band_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs with `key <= query` and `query - key < window` in
    a sequence of `seq_len`: the first `window` queries' triangle, then
    `window` keys a query."""
    if window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def keys_per_query(seq_len: int, window=None) -> float:
    """The keys a query sees on average: the triangle's, or the band's."""
    return band_pairs(seq_len, window or seq_len) / seq_len


def _heads(config: Dict[str, Any], kind: str) -> int:
    return config["n_heads_sliding" if kind == SLIDING else "n_heads"]


def attention_params(config: Dict[str, Any], kind: str) -> int:
    """`W_q`, `W_k`, `W_v`, `W_o` and the per-head gate of one layer of
    `kind` (no norm)."""
    d, hk, width = config["d_model"], config["n_kv_heads"], config["d_head"]
    heads = _heads(config, kind)
    gate = d * heads if config.get("attn_gate") else 0
    return 2 * d * heads * width + 2 * d * hk * width + gate


def _held(config: Dict[str, Any]) -> int:
    return (config.get("experts_held") or (0, config["n_experts"]))[1]


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, width, f = config["d_model"], config["d_head"], config["d_ff"]
    kinds = config["layer_types"]
    dense = config["n_dense_layers"]
    routed = config["n_layers"] - dense
    slots = config["experts_per_token"] * _held(config) / config["n_experts"]
    pairs = {"full_attention": 0.0, SLIDING: 0.0}
    for kind in kinds:  # s = q k^T and ctx = p v: 2 x 2 width a pair, a head
        pairs[kind] += 4 * _heads(config, kind) * width * keys_per_query(
            seq_len, config["sliding_window"] if kind == SLIDING else None)
    return {
        "attention_projections": sum(
            2 * attention_params(config, kind) for kind in kinds),
        "full_attention": pairs["full_attention"],
        "sliding_attention": pairs[SLIDING],
        "dense_ffn": dense * 2 * 3 * d * config["d_ff_dense"],
        "router": routed * 2 * d * config["n_experts"],
        "experts": routed * slots * 2 * 3 * d * f,
        "shared_experts": routed * 2 * 3 * d * config["d_ff_shared"],
        "head": 2 * d * config["vocab_size"],
    }


def laguna_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def laguna_param_count(config: Dict[str, Any]) -> int:
    """All parameters this chip holds (the held experts, the shared expert
    and the router whole, the embedding and the untied head over the
    vocabulary slice): what the state's bytes follow from."""
    d, f = config["d_model"], config["d_ff"]
    n = 2 * config["vocab_size"] * d + d
    for layer, kind in enumerate(config["layer_types"]):
        n += 2 * d + attention_params(config, kind)
        if layer < config["n_dense_layers"]:
            n += 3 * d * config["d_ff_dense"]
        else:
            n += (d * config["n_experts"] + _held(config) * 3 * d * f
                  + 3 * d * config["d_ff_shared"])
    return n


def whole_model_params(published: Dict[str, Any], *, gate: str = "head",
                       heads_per_layer: Sequence[int] = ()) -> int:
    """The parameters of the uncut model from the published config.json's
    own keys (`catalog_config`), for the count that sizes the gate
    (`assumed.attention_gate`): `gate` is "head" (`W_g` `[d, H_l]`),
    "context" (`[d, H_l x head_dim]`) or "none"; `heads_per_layer` takes the
    place of `num_attention_heads_per_layer`."""
    d, width = published["hidden_size"], published["head_dim"]
    hk = published["num_key_value_heads"]
    heads_per_layer = (heads_per_layer
                       or published["num_attention_heads_per_layer"])
    gate_width = {"head": 1, "context": width, "none": 0}[gate]
    expert = 3 * d * published["moe_intermediate_size"]
    n = 2 * published["vocab_size"] * d + d
    for heads, ff in zip(heads_per_layer, published["mlp_layer_types"]):
        n += 2 * d + 2 * d * heads * width + 2 * d * hk * width
        n += d * heads * gate_width
        if ff == "dense":
            n += 3 * d * published["intermediate_size"]
        else:
            n += (d * published["num_experts"]
                  + published["num_experts"] * expert
                  + 3 * d * published["shared_expert_intermediate_size"])
    return n


def window_flash_call(kernel: str, bh: int, seq_len: int, window: int,
                      qk_dim: int, v_dim: int) -> Tuple[float, float]:
    """(operations, bytes) of one call of the windowed `kernel`
    (`flash_fwd`, `flash_bwd_dkv_dq`, `flash_bwd_dq`, `flash_bwd_dkv`; the
    `pallas_call` is named `<kernel>_window`) on `bh` (batch x head)
    sequences of `seq_len` under `window`, q and k `qk_dim` wide and v
    `v_dim`. Operations: the kernel's matmuls over the band's pairs, no
    masked pair counted. Bytes: every operand and result once, all bf16 as
    the training step passes them and the kernels write them, but lse and
    delta, which are float32 `[BH, T, 8]`; the forward is the one that also
    writes lse."""
    over_qk, over_v = _MATMULS[kernel]
    ops = 2.0 * band_pairs(seq_len, window) * bh * (
        over_qk * qk_dim + over_v * v_dim)
    qk = bh * seq_len * qk_dim  # elements of q, k, dq, dk
    vo = bh * seq_len * v_dim   # elements of v, o, do, dv
    row = bh * seq_len * _ROW * 4  # bytes of lse or delta
    read = (2 * qk + 2 * vo) * 2 + 2 * row  # q, k, v, do, lse, delta
    bytes_moved = {
        "flash_fwd": (2 * qk + 2 * vo) * 2 + row,  # q, k, v in; o, lse out
        "flash_bwd_dq": read + qk * 2,
        "flash_bwd_dkv": read + (qk + vo) * 2,
        "flash_bwd_dkv_dq": read + (2 * qk + vo) * 2,
    }[kernel]
    return ops, float(bytes_moved)
