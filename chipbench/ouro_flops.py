"""Operations and parameters of the `ouro` family, from the configuration's
shapes alone. The benchmark's own count, by `flops.py`'s convention: a
multiply-add is 2 operations, the backward pass costs twice the forward,
recomputed work (remat, the flash kernel's backward) is not counted, and the
elementwise passes (the four norms a layer, rotary positions, the silu, the
exit distribution and its entropy) are not counted.

The stack is run `loop_steps` times over the same weights: a layer's
operations count once a pass, and the head and the exit gate read every
pass's stream, so they count once a pass too. Parameters count once.

At Ouro-2.6B's widths cut to 8 layers, 4 passes and 16,384-token sequences a
token needs 18.72 GFLOP: per forward a layer application is 2 x 51,380,224 =
102.76 M in its matmuls (attention's four projections 16,777,216 parameters,
SwiGLU's three 34,603,008) and 2 x 2 x 2048 x 16,385 / 2 = 67.11 M in its
causal pairs; 32 applications are 3,288.33 M and 2,147.61 M; the four heads
4 x 2 x 2048 x 49,152 = 805.31 M; the gate 4 x 2 x 2048 = 16,384. 6,241.27 M:
the layers' matmuls 53 %, attention's pairs 34 %, the heads 13 %; times 3.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_matmul_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_k`, `W_v`, `W_o` and SwiGLU's three matrices of one layer."""
    d, width = config["d_model"], config["d_head"]
    heads, kv = config["n_heads"], config["n_kv_heads"]
    return d * width * (2 * heads + 2 * kv) + 3 * d * config["d_ff"]


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the passes."""
    d, passes = config["d_model"], config["loop_steps"]
    applications = passes * config["n_layers"]
    return {
        "layer_matmuls": applications * 2 * layer_matmul_params(config),
        # q k^T and p v over the causal triangle, (T + 1) / 2 keys a query
        "attention_pairs": applications * 2 * 2 * config["n_heads"]
        * config["d_head"] * (seq_len + 1) / 2,
        "heads": passes * 2 * d * config["vocab_size"],
        "exit_gate": passes * 2 * d,
    }


def ouro_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def state_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers with their four norms,
    the embedding, the untied head, the final norm, the gate and its bias."""
    d = config["d_model"]
    layer = layer_matmul_params(config) + 4 * d
    return (config["n_layers"] * layer + 2 * d * config["vocab_size"] + d
            + d + 1)
