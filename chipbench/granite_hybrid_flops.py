"""Operations, bytes and parameters of the `granite_hybrid` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted, and the elementwise passes (norms, the short convolution, gates,
the decays, the four multipliers) are bandwidth and not counted.

Every layer is a mixer and a gated feed-forward of three matrices. A
Mamba-2 mixer is its two projections and the scan as the chunked form
computes it (whole chunks of `ssd_chunk` tokens, the masked half of a
chunk's scores included, as the MXU runs them); attention is its four
projections and the causal pairs.

At granite-4.0-h-micro's widths cut to one period of ten layers (nine
mixers, one attention layer), a quarter of the tied vocabulary and
sequences of 32,768 tokens a token needs 5.30 GFLOP: per forward the ten
feed-forwards 10 x 2 x 3 x 2048 x 8192 = 1,006.6 M, the mixers'
projections 9 x 2 x (2048 x 8512 + 4096 x 2048) = 464.8 M, the causal
pairs 2 x 2 x 32 x 64 x 16,384.5 = 134.2 M, the head 2 x 2048 x 25,088 =
102.8 M, the scans 9 x (2 x 256 x (128 + 4096) + 4 x 4096 x 128) = 38.3 M,
attention's projections 2 x 10,485,760 = 21.0 M: 1,767.7 M, times 3.

`scan_call` gives the re-tiled kernels' operations and bytes a call, part
by part, for `ssd_fwd_roofline.granite.tokens` and
`ssd_bwd_roofline.granite.tokens` (`readers/granite_roofline.py`).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def _mixer(config: Dict[str, Any]):
    H, P = config["mamba_heads"], config["mamba_head_dim"]
    return H, P, config["ssm_groups"], config["ssm_state"]


def _count(config: Dict[str, Any], kind: str) -> int:
    return sum(k == kind for k in config["layer_types"])


def mixer_matmul_params(config: Dict[str, Any]) -> int:
    """`W_in` ([z | x B C | dt]) and `W_out`."""
    d = config["d_model"]
    H, P, G, N = _mixer(config)
    return d * (2 * H * P + 2 * G * N + H) + H * P * d


def mixer_other_params(config: Dict[str, Any]) -> int:
    """The taps and their bias, `dt_bias`, `A_log`, `D`, the gated norm."""
    H, P, G, N = _mixer(config)
    conv = H * P + 2 * G * N
    return (config["mamba_conv_taps"] + 1) * conv + 3 * H + H * P


def attention_params(config: Dict[str, Any]) -> int:
    d, dh = config["d_model"], config["d_head"]
    return d * (config["n_heads"] + 2 * config["n_kv_heads"]) * dh + (
        config["n_heads"] * dh * d)


def feed_forward_params(config: Dict[str, Any]) -> int:
    return 3 * config["d_model"] * config["d_ff"]


def layer_params(config: Dict[str, Any], kind: str) -> int:
    """A layer whole: its mixer, its feed-forward, its two norms."""
    mixer = (attention_params(config) if kind == "attention" else
             mixer_matmul_params(config) + mixer_other_params(config))
    return mixer + feed_forward_params(config) + 2 * config["d_model"]


def state_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers, the tied embedding's
    slice and the final norm."""
    d = config["d_model"]
    return (sum(layer_params(config, k) for k in config["layer_types"])
            + d * config["vocab_size"] + d)


def whole_model_params(config: Dict[str, Any]) -> int:
    """The published model: `published.num_hidden_layers` layers in the
    period this stage holds, the whole tied vocabulary, the final norm."""
    published = config["published"]
    d = config["d_model"]
    periods = published["num_hidden_layers"] // len(config["layer_types"])
    return (periods * sum(layer_params(config, k)
                          for k in config["layer_types"])
            + d * published["vocab_size"] + d)


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    H, P, G, N = _mixer(config)
    Q = config["ssd_chunk"]
    mixers, attention = _count(config, "mamba"), _count(config, "attention")
    return {
        "feed_forwards": len(config["layer_types"]) * 2
        * feed_forward_params(config),
        "mixer_projections": mixers * 2 * mixer_matmul_params(config),
        # causal: an average query sees (seq_len + 1) / 2 keys
        "attention": attention * 2 * 2 * config["n_heads"] * config["d_head"]
        * ((seq_len + 1) / 2),
        "head": 2 * config["d_model"] * config["vocab_size"],
        "scan": mixers * (2.0 * Q * (G * N + H * P) + 2 * 2 * H * P * N),
        "attention_projections": attention * 2 * attention_params(config),
    }


def granite_hybrid_flops_per_token(config: Dict[str, Any],
                                   seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())


def scan_call(kernel: str, config: Dict[str, Any], rows: int, seq_len: int,
              heads_a_tile: int, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one call of `ssd_fwd` or `ssd_bwd` on `rows`
    sequences of `seq_len` tokens with a group's heads taken `heads_a_tile`
    at a time, every operand and result crossing HBM once.

    Operations, whole chunks of `Q = ssd_chunk` tokens as the MXU runs
    them, the masked half included. `ssd_fwd` a token: a tile's scores `C
    B^T` (2 Q N, made again a tile), the masked scores times the inputs (2 Q
    a channel), `C` times the entering state and the state's update (2 N a
    channel each). `ssd_bwd` makes the scores again and has, a channel,
    `mixed^T dy` and `dy (dt x)^T` (2 Q each) and five products with the
    states (`C state`, `dC`, `B dstate`, `dB`, `C^T dcarried`: 2 N each);
    a tile, `dscores B` and `dscores^T C` (2 Q N each).

    Bytes: x, y, dy and dx in `itemsize`, B and C read once a tile (their
    blocks are a group's; a tile's `dB` and `dC` written, `itemsize`), dt
    and cum as columns, and cum as rows, float32 at a tile's `heads_a_tile`
    values a token, the entering states `[N, H P]` float32 a chunk read by
    the backward. The forward's writing of them is NOT counted: under remat
    a mixer's first `ssd_fwd` of a step writes none and the one made again
    for the backward does, both under one name, so the least time is the
    lesser call's and the share reads low, never high."""
    H, P, G, N = _mixer(config)
    Q = config["ssd_chunk"]
    tokens = rows * seq_len
    chunks = rows * -(-seq_len // Q)
    tiles = H // heads_a_tile
    wide = tokens * H * P
    shared = tokens * tiles * N            # B or C, once a tile
    small = tokens * H * 4                 # one of dt, cum as columns or rows
    states = chunks * N * H * P * 4
    if kernel == "ssd_fwd":
        ops = tokens * (tiles * 2.0 * Q * N + H * P * (2.0 * Q + 4 * N))
        moved = 2 * wide * itemsize + 2 * shared * itemsize + 3 * small
    elif kernel == "ssd_bwd":
        ops = tokens * (tiles * 3 * 2.0 * Q * N
                        + H * P * (2 * 2.0 * Q + 5 * 2 * N))
        moved = (3 * wide * itemsize + 4 * shared * itemsize + 6 * small
                 + states)
    else:
        raise ValueError(f"kernel {kernel!r}")
    return ops, float(moved)
