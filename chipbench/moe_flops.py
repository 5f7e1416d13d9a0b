"""Operations of the `moe_transformer` family, from the configuration's
shapes alone: what one token of training needs, and what one call of the
grouped-matmul kernels needs. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted. Only active parameters count: a token goes through the router and
through `experts_per_token` experts, whatever the number of experts.

At OLMoE-1B-7B's widths with one layer and 4096-token sequences a token
needs 1.072 GFLOP: per forward 33.55 M in the attention projections, 16.78 M
in causal attention, 0.26 M in the router, 100.66 M in 8 experts and
206.05 M in the output head (58 %), times 3.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part; the block's parts are for
    all `n_layers` layers."""
    d, f = config["d_model"], config["d_ff"]
    h = config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    layers = config["n_layers"]
    return {
        "attention_projections": layers * (
            2 * d * (h * dh + 2 * hk * dh) + 2 * h * dh * d),
        # causal: an average query sees (seq_len + 1) / 2 keys
        "attention": layers * 2 * 2 * h * dh * ((seq_len + 1) / 2),
        "router": layers * 2 * d * config["n_experts"],
        "experts": layers * config["experts_per_token"] * 2 * 3 * d * f,
        "head": 2 * d * config["vocab_size"],
    }


def moe_transformer_flops_per_token(config: Dict[str, Any], seq_len: int
                                    ) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def moe_transformer_param_count(config: Dict[str, Any]) -> int:
    """All parameters, experts that a token does not visit included: what
    the state's bytes follow from."""
    d, f = config["d_model"], config["d_ff"]
    h = config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    per_layer = d * (h * dh + 2 * hk * dh) + h * dh * d + 2 * d
    per_layer += d * config["n_experts"] + config["n_experts"] * 3 * d * f
    if config.get("qk_norm"):
        per_layer += h * dh + hk * dh
    n = config["vocab_size"] * d + config["n_layers"] * per_layer + d
    if not config.get("tied_embeddings", True):
        n += d * config["vocab_size"]
    return n


def gmm_call(rows: int, k: int, n: int, experts: int) -> Tuple[float, float]:
    """(operations, bytes) of one grouped matmul over `rows` rows sorted into
    `experts` groups with a [k, n] weight each, whichever of the three
    products it is: `moe_gmm` forward ([rows, k] x [E, k, n]), `moe_gmm` on
    the transposed weights ([rows, n] x [E, n, k]) and `moe_tgmm` (per group
    [rows, k]^T [rows, n]) all cost `2 rows k n` operations. Bytes are what
    the call cannot avoid moving: the rows in and out once ([rows, k] and
    [rows, n]) and the E weights once, all counted as bf16 (`moe_tgmm`
    writes its f32 result, twice those weight bytes; it is compute-bound at
    the cell's shapes either way)."""
    ops = 2.0 * rows * k * n
    bytes_moved = 2.0 * (rows * k + rows * n + experts * k * n)
    return ops, bytes_moved
