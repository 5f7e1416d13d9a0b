"""Operations and parameters of the `deepseek_v2` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted, and the elementwise passes (norms, rotary positions, gates, the
router's softmax) are bandwidth and not counted.

Latent attention is counted in its decompressed form, the one training
runs: the query projection, the down projection to the latent and the
shared rotary key, the up projection to every head's keys and values, the
output projection, and over the causal pairs the scores at q and k's width
(`qk_nope_head_dim + qk_rope_head_dim`) and the values at `v_head_dim`.
The routed experts are credited with what this chip computes, as
`lfm2_flops.py` does: `experts_per_token x held / n_experts` experts a
token in expectation. The shared experts and the router are whole.

At DeepSeek-V2-Lite's widths cut to 6 layers (one dense), 8 of 64 experts,
12800 ids and 8192-token sequences a token needs 2.530 GFLOP: per forward
165.15 M in the six layers' attention projections, 251.72 M in the causal
pairs, 134.48 M in the dense feed-forward, 1.31 M in the routers, 64.88 M in
the held experts, 173.02 M in the shared experts and 52.43 M in the head,
times 3.
"""

from __future__ import annotations

from typing import Any, Dict


def _latent(config: Dict[str, Any]):
    return (config["d_model"], config["n_heads"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"])


def _held(config: Dict[str, Any]) -> int:
    return (config.get("experts_held") or (0, config["n_experts"]))[1]


def attention_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_kva`, `W_kvb` and `W_o` of one layer (no norm)."""
    d, h, r, nope, rope, dv = _latent(config)
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
            + h * dv * d)


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, h, r, nope, rope, dv = _latent(config)
    layers, dense = config["n_layers"], config["n_dense_layers"]
    routed = layers - dense
    f = config["d_ff"]
    slots = config["experts_per_token"] * _held(config) / config["n_experts"]
    return {
        "attention_projections": layers * 2 * attention_params(config),
        # causal: an average query sees (seq_len + 1) / 2 keys
        "attention": layers * 2 * h * (nope + rope + dv) * ((seq_len + 1) / 2),
        "dense_ffn": dense * 2 * 3 * d * config["d_ff_dense"],
        "router": routed * 2 * d * config["n_experts"],
        "experts": routed * slots * 2 * 3 * d * f,
        "shared_experts": routed * 2 * 3 * d * config["n_shared_experts"] * f,
        "head": 2 * d * config["vocab_size"],
    }


def dsv2_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def dsv2_param_count(config: Dict[str, Any]) -> int:
    """All parameters this chip holds (the held experts, the shared experts
    and the router whole, the embedding and the untied head over the
    vocabulary slice): what the state's bytes follow from."""
    d, f = config["d_model"], config["d_ff"]
    n = 2 * config["vocab_size"] * d + d
    for layer in range(config["n_layers"]):
        n += 2 * d + attention_params(config) + config["kv_lora_rank"]
        if layer < config["n_dense_layers"]:
            n += 3 * d * config["d_ff_dense"]
        else:
            n += (d * config["n_experts"] + _held(config) * 3 * d * f
                  + 3 * d * config["n_shared_experts"] * f)
    return n
