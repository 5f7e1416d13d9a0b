"""Operations, bytes and parameters of the `nemotron_h` family, from the
configuration's shapes alone. The benchmark's own count, by `flops.py`'s
convention: a multiply-add is 2 operations, the backward pass costs twice
the forward, recomputed work (remat, the flash kernel's backward) is not
counted, and the elementwise passes (norms, the short convolution, gates,
the decays, the router's sigmoid) are bandwidth and not counted.

A Mamba-2 mixer is its two projections and the scan as the chunked form
computes it (`scan_parts`: whole chunks of `ssd_chunk` tokens, the masked
half of a chunk's scores included, as the MXU runs them). Attention is its
four projections and the causal pairs. The routed experts are credited with
what this chip computes, `experts_per_token x held / n_experts` experts a
token in expectation, at two matrices an expert (ungated); the shared
expert and the router are whole.

At Nemotron-3-Nano's widths cut to `MEMEM*EME`, 8 of 128 experts, 16384 ids
and 8192-token sequences a token needs 2.153 GFLOP: per forward 309.6 M in
the four mixers' projections and 13.6 M in their scans, 46.8 M in
attention's projections and 67.1 M in its causal pairs, 2.8 M in the
routers, 29.9 M in the held experts, 159.6 M in the shared experts and
88.1 M in the head (717.6 M), times 3.

`scan_parts` also gives the scan's bytes a call, part by part: what each
part must read and write if every operand crosses HBM once, the yardstick
for a kernel that may follow (PERF.md section 7).
"""

from __future__ import annotations

from typing import Any, Dict


def _held(config: Dict[str, Any]) -> int:
    return (config.get("experts_held") or (0, config["n_experts"]))[1]


def _mixer(config: Dict[str, Any]):
    H, P = config["mamba_heads"], config["mamba_head_dim"]
    return H, P, config["ssm_groups"], config["ssm_state"]


def mixer_params(config: Dict[str, Any]) -> int:
    """One Mamba-2 mixer with the norm before it."""
    d = config["d_model"]
    H, P, G, N = _mixer(config)
    conv = H * P + 2 * G * N
    return (d + d * (H * P + conv + H) + config["mamba_conv_taps"] * conv
            + conv + 3 * H + H * P + H * P * d)


def attention_params(config: Dict[str, Any]) -> int:
    """`W_q`, `W_k`, `W_v`, `W_o` and the norm before them."""
    d, dh = config["d_model"], config["d_head"]
    h, hk = config["n_heads"], config["n_kv_heads"]
    return d + d * (h + 2 * hk) * dh + h * dh * d


def routed_params(config: Dict[str, Any]) -> int:
    """The router, the held experts, the shared expert and the norm."""
    d = config["d_model"]
    return (d + d * config["n_experts"] + _held(config) * 2 * d * config["d_ff"]
            + 2 * d * config["d_ff_shared"])


def _count(config: Dict[str, Any], kind: str) -> int:
    return sum(k == kind for k in config["sublayer_types"])


def nemotron_h_param_count(config: Dict[str, Any]) -> int:
    """All parameters this chip holds (the held experts, the shared expert
    and the router whole, the embedding and the untied head over the
    vocabulary slice): what the state's bytes follow from."""
    return (2 * config["vocab_size"] * config["d_model"] + config["d_model"]
            + _count(config, "mamba2") * mixer_params(config)
            + _count(config, "full_attention") * attention_params(config)
            + _count(config, "routed_ff") * routed_params(config))


def scan_parts(config: Dict[str, Any], rows: int, seq_len: int,
               itemsize: int = 2) -> Dict[str, Dict[str, float]]:
    """{part: {"flops", "bytes"}} of one forward call of the scan on `rows`
    sequences of `seq_len` tokens, by `ops/ssd.py`'s three scopes:

    - `ssd_chunk`: a chunk's scores `C B^T` ([Q, Q] a group) and their
      masked product with the inputs ([Q, Q] x [Q, P] a head). It reads x,
      B, C and dt and writes y.
    - `ssd_state`: a chunk's `[P, N]` state a head from its Q inputs and
      B, and the recurrence over the chunks (elementwise). It reads x, B and
      dt and writes a float32 state a chunk.
    - `ssd_out`: the entering state times C for every token. It reads the
      states and C, and reads and writes y.

    Operands in `itemsize` bytes, dt and the states in 4."""
    H, P, G, N = _mixer(config)
    Q = config["ssd_chunk"]
    tokens = rows * seq_len
    chunks = rows * -(-seq_len // Q)
    x = tokens * H * P * itemsize
    bc = tokens * G * N * itemsize
    dt = tokens * H * 4
    states = chunks * H * P * N * 4
    return {
        "ssd_chunk": {"flops": tokens * 2.0 * Q * (G * N + H * P),
                      "bytes": 2.0 * x + 2 * bc + dt},
        "ssd_state": {"flops": tokens * 2.0 * H * P * N,
                      "bytes": float(x + bc + dt + states)},
        "ssd_out": {"flops": tokens * 2.0 * H * P * N,
                    "bytes": float(states + bc + dt + 2 * x)},
    }


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations for one token, by part, over all the layers."""
    d, dh = config["d_model"], config["d_head"]
    h, hk = config["n_heads"], config["n_kv_heads"]
    H, P, G, N = _mixer(config)
    mixers = _count(config, "mamba2")
    attention = _count(config, "full_attention")
    routed = _count(config, "routed_ff")
    slots = config["experts_per_token"] * _held(config) / config["n_experts"]
    scan = sum(part["flops"] for part in scan_parts(config, 1, seq_len).values())
    return {
        "mixer_projections": mixers * 2 * (
            d * (2 * H * P + 2 * G * N + H) + H * P * d),
        "scan": mixers * scan / seq_len,
        "attention_projections": attention * 2 * (
            d * (h + 2 * hk) * dh + h * dh * d),
        # causal: an average query sees (seq_len + 1) / 2 keys
        "attention": attention * 2 * 2 * h * dh * ((seq_len + 1) / 2),
        "router": routed * 2 * d * config["n_experts"],
        "experts": routed * slots * 2 * 2 * d * config["d_ff"],
        "shared_experts": routed * 2 * 2 * d * config["d_ff_shared"],
        "head": 2 * d * config["vocab_size"],
    }


def nemotron_h_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`."""
    return 3.0 * sum(forward_parts(config, seq_len).values())
