"""Operations and parameters of the `lfm2_moe` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted, and the elementwise passes (the convolution's taps, gates, norms,
the router's sigmoid) are bandwidth and not counted.

The experts are credited with what this chip computes: a token has
`experts_per_token` slots, of which `held / n_experts` fall on a held expert
in expectation, whatever a step's routing makes of it, so a token's experts
cost `experts_per_token x held / n_experts` times one expert's three
products. The router is credited at its whole width.

At LFM2-24B-A2B's widths cut to 5 layers (one dense), 8 of 64 experts and
8192-token sequences a token needs 1.218 GFLOP: per forward 33.55 M in the
dense layer's operator and 144.70 M in its feed-forward, 121.63 M in the
other four operators' projections, 33.56 M in causal attention, 1.05 M in
the routers, 37.75 M in the experts and 33.55 M in the head, times 3.
"""

from __future__ import annotations

from typing import Any, Dict


def _shapes(config: Dict[str, Any]):
    d = config["d_model"]
    h = config["n_heads"]
    hk = config.get("n_kv_heads") or h
    return d, h, hk, d // h


def _held(config: Dict[str, Any]) -> int:
    return (config.get("experts_held") or (0, config["n_experts"]))[1]


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, h, hk, dh = _shapes(config)
    types = config["layer_types"]
    dense = config["n_dense_layers"]
    routed = len(types) - dense
    convs = sum(t == "conv" for t in types)
    attentions = len(types) - convs
    slots = config["experts_per_token"] * _held(config) / config["n_experts"]
    return {
        # in_proj to three streams and out_proj
        "conv_projections": convs * (2 * d * 3 * d + 2 * d * d),
        "attention_projections": attentions * (
            2 * d * (h * dh + 2 * hk * dh) + 2 * h * dh * d),
        # causal: an average query sees (seq_len + 1) / 2 keys
        "attention": attentions * 2 * 2 * h * dh * ((seq_len + 1) / 2),
        "dense_ffn": dense * 2 * 3 * d * config["d_ff_dense"],
        "router": routed * 2 * d * config["n_experts"],
        "experts": routed * slots * 2 * 3 * d * config["d_ff"],
        "head": 2 * d * config["vocab_size"],
    }


def lfm2_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def lfm2_param_count(config: Dict[str, Any]) -> int:
    """All parameters this chip holds (the held experts whole, the tied
    embedding once): what the state's bytes follow from. The selection bias
    is no parameter."""
    d, h, hk, dh = _shapes(config)
    n = config["vocab_size"] * d + d
    for layer, kind in enumerate(config["layer_types"]):
        n += 2 * d  # the two norms
        if kind == "conv":
            n += d * 3 * d + config["conv_taps"] * d + d * d
        else:
            n += d * (h * dh + 2 * hk * dh) + h * dh * d + 2 * dh
        if layer < config["n_dense_layers"]:
            n += 3 * d * config["d_ff_dense"]
        else:
            n += d * config["n_experts"] + _held(config) * 3 * d * config["d_ff"]
    return n
