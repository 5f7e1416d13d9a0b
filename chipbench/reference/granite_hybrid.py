"""Plain reference of the decoder the `granite_hybrid` family trains:
granite-4.0-h-micro (`model_type` `granitemoehybrid`, dense:
`num_local_experts` 0), one pipeline stage of it. With `RMS_w(x) = x /
sqrt(mean(x^2) + eps) * w` and the four multipliers `embedding_multiplier`
e, `residual_multiplier` r, `attention_multiplier` a and `logits_scaling` s:

- `h_0 = e E[ids]`.
- A layer, two sublayers: `h <- h + r mixer(RMS(h; g_1))`, then `h <- h + r
  (silu(u W_gate) * (u W_up)) W_down` with `u = RMS(h; g_2)`
  (`shared_mlp.input_linear` is `[W_gate | W_up]`).
- `attention` (`layer_types`): `q, k, v = u W_q, u W_k, u W_v` (no bias),
  NO rotary embedding (`position_embedding_type` "nope": the mixers carry
  position), causal softmax of `a q . k` (1/64 at heads of 64, not
  `1/sqrt(64)`), each key-value head serving `heads / kv heads` query heads,
  `W_o`.
- `mamba`, the Mamba-2 mixer (arXiv:2405.21060): `[z | xBC | dt] = u W_in`
  (no bias), `z` of `H P` channels, `xBC` of `H P + 2 G N`, `dt` of `H`.
  `xBC = silu(conv(xBC) + b)`: causal, per channel, `mamba_d_conv` taps,
  `conv_t = sum_i w_i xBC_(t - taps + 1 + i)`, zero before the sequence,
  written as that many shifted adds. Then `x` `[T, H, P]`, `B` and `C`
  `[T, G, N]` (`G` = 1: all 64 heads read one B and one C). `dt =
  softplus(dt + dt_bias)` a head (`time_step_limit` (0, inf): no clamp),
  `A = -exp(A_log)` a head. The state of a head is `[P, N]`, zero before
  the first token:

      S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T,    y_t = S_t C_t + D x_t

  taken TOKEN BY TOKEN, a `lax.scan` step each, never in chunks. Then the
  gate BEFORE the norm, `y = RMS(y * silu(z); g)` over each of the `G`
  groups of `H P / G` channels (one group: all 4096), and `W_out`.
- `logits = RMS(h; g_f) E^T / s` over the stage's ids (the embedding is
  tied); the mean next-token cross-entropy over the positions.

Everything is float32 at the highest matmul precision: no chunked scan, no
kernel, nothing of `ray_tpu/ops/`.

Departures from the published model, written down as the contract asks:
- The stage's share: layers 0 to 9 of 40 (one period: five `mamba`, one
  `attention`, four `mamba`), the first `vocab_size` token ids of 100,352
  (a sliced vocabulary is a smaller vocabulary: the loss is over the
  slice), a final norm so that the stage has a loss.
- The scan over the tokens is cut into blocks of 128 steps, each under
  `jax.checkpoint`: the same steps in the same order, but the gradient
  keeps a state a block and one block's steps, not every token's `[H, P,
  N]` state (2.1 MB a token at published widths).
- Attention's softmax is taken a block of `_QUERY_BLOCK` queries at a time
  over the dense causal mask, each block under `jax.checkpoint`: the same
  rows of the same `[T, T]` softmax, but no `[heads, T, T]` array is held
  (2.1 GB at 4,096 tokens).
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods. A `mamba`
layer has `mixer_norm`, `w_in`, `conv_w` `[taps, channels]`, `conv_b`,
`dt_bias`, `A_log`, `D`, `norm`, `w_out`; an `attention` layer `attn_norm`,
`wq`, `wk`, `wv`, `wo`; both `mlp_norm`, `w_gate`, `w_up`, `w_down`. The
layers' kinds are read from the configuration, not from the tree.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

_SCAN_BLOCK = 128   # steps of the token scan under one `jax.checkpoint`
_QUERY_BLOCK = 512  # queries of the masked softmax under one


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layers(params: Dict[str, Any]):
    """Every layer's weights, first to last, as float32."""
    for segment in params["blocks"]:
        periods = jax.tree.leaves(segment[0])[0].shape[0]
        for period in range(periods):
            for tree in segment:
                yield {k: jnp.asarray(v[period], jnp.float32)
                       for k, v in tree.items()}


def recurrence(x, dt, A, B, C, D):
    """`y` [b, T, H, P] of x [b, T, H, P], dt [b, T, H], A [H], B and C
    [b, T, H, N] (already one a head) and D [H], token by token."""
    b, T, H, P = x.shape
    N = B.shape[-1]

    def step(S, token):
        x_t, dt_t, B_t, C_t = token
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t) + D[:, None] * x_t

    @jax.checkpoint
    def block(S, tokens):
        return jax.lax.scan(step, S, tokens)

    size = _SCAN_BLOCK if T % _SCAN_BLOCK == 0 else T
    by_token = tuple(
        v.swapaxes(0, 1).reshape(T // size, size, *v.shape[:1], *v.shape[2:])
        for v in (x, dt, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((b, H, P, N), jnp.float32), by_token)
    return y.reshape(T, b, H, P).swapaxes(0, 1)


def mixer(u, w, config: Dict[str, Any]):
    """Mamba2(u) of one layer with weights `w`, `u` [b, t, d] normed."""
    H, P = config["mamba_heads"], config["mamba_head_dim"]
    G, N = config["ssm_groups"], config["ssm_state"]
    b, t, _ = u.shape
    inner = H * P
    taps = w["conv_w"].shape[0]
    z, xbc, dt = jnp.split(u @ w["w_in"], (inner, 2 * inner + 2 * G * N), -1)
    conv = w["conv_w"][taps - 1] * xbc  # this token's tap, then the earlier
    for back in range(1, taps):
        earlier = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :t]
        conv = conv + w["conv_w"][taps - 1 - back] * earlier
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs, B, C = jnp.split(xbc, (inner, inner + G * N), -1)
    B = jnp.repeat(B.reshape(b, t, G, N), H // G, axis=2)
    C = jnp.repeat(C.reshape(b, t, G, N), H // G, axis=2)
    y = recurrence(xs.reshape(b, t, H, P), jax.nn.softplus(dt + w["dt_bias"]),
                   -jnp.exp(w["A_log"]), B, C, w["D"])
    gated = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, G, -1)
    y = _rms(gated, w["norm"].reshape(G, -1), config["norm_eps"])
    return y.reshape(b, t, inner) @ w["w_out"]


def attention(u, w, config: Dict[str, Any]):
    """Attn(u) of one layer: a masked softmax at the configuration's scale,
    no rotation, a block of queries at a time."""
    h, hk, dh = config["n_heads"], config["n_kv_heads"], config["d_head"]
    scale = config["attention_multiplier"]
    b, t, _ = u.shape
    q = (u @ w["wq"]).reshape(b, t, h, dh)
    k = jnp.repeat((u @ w["wk"]).reshape(b, t, hk, dh), h // hk, axis=2)
    v = jnp.repeat((u @ w["wv"]).reshape(b, t, hk, dh), h // hk, axis=2)
    size = _QUERY_BLOCK if t % _QUERY_BLOCK == 0 else t
    keys = jnp.arange(t)

    @jax.checkpoint
    def rows(first, q_rows):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) * scale
        seen = keys[None, :] <= (first + jnp.arange(size))[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    blocks = jax.lax.map(
        lambda args: rows(*args),
        (jnp.arange(0, t, size),
         q.reshape(b, t // size, size, h, dh).swapaxes(0, 1)))
    return blocks.swapaxes(0, 1).reshape(b, t, h * dh) @ w["wo"]


def feed_forward(u, w):
    return (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


def loss(params: Dict[str, Any], batch: Dict[str, Any],
         config: Dict[str, Any]):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]`."""
    tokens, targets = batch["tokens"], batch["targets"]
    eps, r = config["norm_eps"], config["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], jnp.float32)
        h = config["embedding_multiplier"] * embed[tokens]
        for kind, w in zip(config["layer_types"], _layers(params)):
            if kind == "mamba":
                h = h + r * mixer(_rms(h, w["mixer_norm"], eps), w, config)
            elif kind == "attention":
                h = h + r * attention(_rms(h, w["attn_norm"], eps), w, config)
            else:
                raise ValueError(f"layer_types names {kind!r}")
            h = h + r * feed_forward(_rms(h, w["mlp_norm"], eps), w)
        h = _rms(h, jnp.asarray(params["final_norm"], jnp.float32), eps)
        logp = jax.nn.log_softmax(
            h @ embed.T / config["logits_scaling"], axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
