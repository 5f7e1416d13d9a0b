"""Operations and parameters of the `mellum` family, from the configuration's
shapes alone. The benchmark's own count, by `flops.py`'s convention: a
multiply-add is 2 operations, the backward pass costs twice the forward,
recomputed work (remat, the flash kernel's backward) is not counted, and the
elementwise passes (norms, rotary positions, the router's softmax) are
bandwidth and not counted.

Attention is counted by layer type: the same projections on both (32 query
heads over 4 key-value heads of 128), and the pairs a query really has: a
full layer's causal triangle, `(T + 1) / 2` keys a query, and a sliding
layer's band, `window (window + 1) / 2 + (T - window) window` pairs, 992.03
keys a query at `T` 16,384 under the window of 1,024. No pair a tile computes
and masks is counted. Every token goes through its 8 experts of 64 somewhere
on the mesh: the count is the model's, not one chip's, and the cell's rate
is over its four chips.

At Mellum2's widths cut to layers 0 to 3 (one period: three sliding layers
and a full one), whole in everything else, at 16,384-token sequences a token
needs 3.608 GFLOP: per forward 169.87 M in the four layers' attention
projections, 134.23 M in the full layer's pairs, 48.76 M in the three
sliding layers', 1.18 M in the routers, 396.36 M in the 8 experts of each of
the four layers and 452.98 M in the head, 1,203.38 M, times 3.

`flash_call` is the numerator of a flash kernel's roofline share at grouped
heads: operations over the pairs the mask leaves and every operand and
result once, k and v (and dk and dv) at the key-value heads, where the
kernels read and write them since PR 42.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from chipbench.laguna_flops import _MATMULS, _ROW, band_pairs, keys_per_query

SLIDING = "sliding_attention"


def attention_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_k`, `W_v` and `W_o` of one layer of either kind (no norm)."""
    d, width = config["d_model"], config["d_head"]
    return 2 * d * width * (config["n_heads"] + config["n_kv_heads"])


def expert_params(config: Dict[str, Any]) -> int:
    """One expert's gate, up and down."""
    return 3 * config["d_model"] * config["d_ff"]


def layer_params(config: Dict[str, Any]) -> int:
    """One layer whole: attention, the router, all the experts, two norms."""
    d = config["d_model"]
    return (attention_params(config) + d * config["n_experts"]
            + config["n_experts"] * expert_params(config) + 2 * d)


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    width, layers = config["d_head"], config["n_layers"]
    pairs = {"full_attention": 0.0, SLIDING: 0.0}
    for kind in config["layer_types"]:  # s = q k^T and ctx = p v, a head
        pairs[kind] += 4 * config["n_heads"] * width * keys_per_query(
            seq_len, config["sliding_window"] if kind == SLIDING else None)
    return {
        "attention_projections": layers * 2 * attention_params(config),
        "full_attention": pairs["full_attention"],
        "sliding_attention": pairs[SLIDING],
        "router": layers * 2 * config["d_model"] * config["n_experts"],
        "experts": (layers * config["experts_per_token"]
                    * 2 * expert_params(config)),
        "head": 2 * config["d_model"] * config["vocab_size"],
    }


def mellum_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def mellum_param_count(config: Dict[str, Any]) -> int:
    """All parameters of the cut, every expert and the whole vocabulary: what
    the state's bytes over the mesh follow from."""
    d = config["d_model"]
    return (2 * config["vocab_size"] * d + d
            + config["n_layers"] * layer_params(config))


def whole_model_params(published: Dict[str, Any]) -> Tuple[int, int]:
    """(all parameters, those a token goes through) of the uncut model from
    the published config.json's own keys (`catalog_config`): the published
    12B-A2.5B."""
    d, width = published["hidden_size"], published["head_dim"]
    heads = published["num_attention_heads"] + published["num_key_value_heads"]
    expert = 3 * d * published["moe_intermediate_size"]
    outside = 2 * d * width * heads + d * published["num_experts"] + 2 * d
    layers = published["num_hidden_layers"]
    ends = 2 * published["vocab_size"] * d + d
    return (ends + layers * (outside + published["num_experts"] * expert),
            ends + layers * (outside
                             + published["num_experts_per_tok"] * expert))


def flash_call(kernel: str, sequences: int, heads: int, kv_heads: int,
               seq_len: int, window: Optional[int], width: int
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call of the flash `kernel` (`flash_fwd`,
    `flash_bwd_dkv_dq`, `flash_bwd_dq`, `flash_bwd_dkv`; under a window the
    `pallas_call` is named `<kernel>_window`) on `sequences` sequences of
    `seq_len` at `heads` query heads over `kv_heads` key-value heads, all
    `width` wide. Operations: the kernel's matmuls over the pairs the mask
    leaves (the triangle, or the band under `window`), a query head each.
    Bytes: every operand and result once, bf16 as the training step passes
    them and the kernels write them, but lse and delta, which are float32
    `[BH, T, 8]`; q, o, do and dq at the query heads, k, v, dk and dv at
    the key-value heads."""
    over_qk, over_v = _MATMULS[kernel]
    pairs = band_pairs(seq_len, window or seq_len)
    ops = 2.0 * pairs * sequences * heads * (over_qk + over_v) * width
    at_q = sequences * heads * seq_len * width      # elements of q, o, do, dq
    at_kv = sequences * kv_heads * seq_len * width  # of k, v, dk, dv
    row = sequences * heads * seq_len * _ROW * 4    # bytes of lse or delta
    read = (2 * at_q + 2 * at_kv) * 2 + 2 * row     # q, do, k, v, lse, delta
    bytes_moved = {
        "flash_fwd": (2 * at_q + 2 * at_kv) * 2 + row,  # q, k, v; o, lse
        "flash_bwd_dq": read + at_q * 2,
        "flash_bwd_dkv": read + 2 * at_kv * 2,
        "flash_bwd_dkv_dq": read + (at_q + 2 * at_kv) * 2,
    }[kernel]
    return ops, float(bytes_moved)


def exchange_bytes(tokens: int, chips: int, d_model: int,
                   itemsize: int = 2) -> int:
    """Bytes that reach one chip in one pass of the routed layer's exchange
    over `chips` chips of `tokens` tokens each: the other chips' rows of
    `d_model` in the compute dtype (the all-gather; the reduce-scatter moves
    as many the other way)."""
    return (chips - 1) * tokens * d_model * itemsize
