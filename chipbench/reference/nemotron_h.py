"""Plain reference of the decoder the `nemotron_h` family trains:
NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type` `nemotron_h`, the published
configuration), one chip's share of it. With `RMS_w(x) = x / sqrt(mean(x^2)
+ eps) * w`, every layer is ONE sublayer `x <- x + f(RMS(x))`, and
`hybrid_override_pattern` says which `f`:

- `M`, the Mamba-2 mixer (arXiv:2405.21060). `[z | xBC | dt] = u W_in` (no
  bias), `z` of `H P` channels, `xBC` of `H P + 2 G N`, `dt` of `H`.
  `xBC = silu(conv(xBC) + b)`: causal, per channel, `conv_kernel` taps,
  `conv_t = sum_i w_i xBC_(t - taps + 1 + i)`, zero before the sequence.
  Then `x` `[T, H, P]`, `B` and `C` `[T, G, N]`; head `h` reads group
  `h // (H / G)`. `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, a head.
  The state of a head is `[P, N]`, zero before the first token:

      h_t = exp(dt_t A) h_(t-1) + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

  taken token by token, a `lax.scan` step each. Then the gate BEFORE the
  norm, `y = GroupRMS(y * silu(z))` over `G` groups of `H P / G` channels
  with one learned scale, and `W_out` (no bias).
- `*`, attention: `q, k, v = u W_q, u W_k, u W_v` (no bias), causal softmax
  at scale `1/sqrt(head width)`, each key-value head serving `heads / kv
  heads` query heads, `W_o`. NO rotary embedding: the mixers carry position.
- `E`, the routed feed-forward. Scores `s = sigmoid(u W_r)` over all
  `n_experts`, in float32; the `experts_per_token` chosen are the largest
  of `s + b` (`b` the selection bias `e_score_correction_bias`, which no
  gradient reaches; `n_group = topk_group = 1`: no group limit); weights
  `p_j = routed_scaling_factor * s_j / (sum of the chosen s + 1e-20)`. An
  expert is ungated, `W_down relu(W_up u)^2`. `f = sum_j p_j Expert_j(u)`
  over the chosen experts *that this chip holds* (`experts_held = [first,
  n]`), a loop over them; what the absent experts would have added is left
  out, and the partial sum goes on to the next layer. Beside it one shared
  expert of the same form and its own width, every token, unweighted.
- A final RMS norm, the untied head, the mean next-token cross-entropy,
  plus `router_aux_loss_coef` times the mean over the routed layers of the
  balance loss `E sum_e f_e P_e` (`f_e` the share of the batch's slots sent
  to expert e, a count that takes no gradient; `P_e` the mean score of e).

Everything is float32 at the highest matmul precision: no chunked scan, no
sort, no grouping, no kernel.

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 128 experts, the first `vocab_size` token
  ids of 131072 (a sliced vocabulary is a smaller vocabulary: the loss is
  over the slice), layers 0 to 8 of 52.
- The balance loss, its coefficient and its form are the configuration's
  `assumed` (the report, as remembered); `P_e` is the mean of the sigmoid
  scores as they are, not divided by their sum over the experts.
- `dt` is not clamped: the `time_step_*` keys are the initialiser's.
- The scan over the tokens is cut into blocks of 128 steps, each under
  `jax.checkpoint`: the same steps in the same order, but the gradient
  keeps a state a block and one block's steps, not every token's `[H, P,
  N]` state (2.1 MB a token and row at published widths).
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods. A mixer has
`mixer_norm`, `w_in`, `conv_w` `[taps, channels]`, `conv_b`, `dt_bias`,
`A_log`, `D`, `norm`, `w_out`; attention `attn_norm`, `wq`, `wk`, `wv`,
`wo`; a routed layer `mlp_norm`, `router`, `w_up` and `w_down` `[n, ...]`,
`ws_up`, `ws_down`. The layers' kinds are read from the configuration, not
from the tree.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.transformer import _rmsnorm

_SCAN_BLOCK = 128  # steps of the token scan under one `jax.checkpoint`


def _layers(params: Dict[str, Any]):
    """Every layer's weights, first to last, as float32."""
    for segment in params["blocks"]:
        periods = jax.tree.leaves(segment[0])[0].shape[0]
        for period in range(periods):
            for tree in segment:
                yield {k: jnp.asarray(v[period], jnp.float32)
                       for k, v in tree.items()}


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def recurrence(x, dt, A, B, C, D):
    """`y` [b, T, H, P] of x [b, T, H, P], dt [b, T, H], A [H], B and C
    [b, T, H, N] (already one a head) and D [H], token by token."""
    b, T, H, P = x.shape
    N = B.shape[-1]

    def step(h, token):
        x_t, dt_t, B_t, C_t = token
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, C_t) + D[:, None] * x_t

    @jax.checkpoint
    def block(h, tokens):
        return jax.lax.scan(step, h, tokens)

    size = _SCAN_BLOCK if T % _SCAN_BLOCK == 0 else T
    by_token = tuple(
        v.swapaxes(0, 1).reshape(T // size, size, *v.shape[:1], *v.shape[2:])
        for v in (x, dt, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((b, H, P, N), jnp.float32), by_token)
    return y.reshape(T, b, H, P).swapaxes(0, 1)


def mixer(x, w, config: Dict[str, Any]):
    """x + Mamba2(RMS(x)) of one layer with weights `w`, x [b, t, d]."""
    H, P = config["mamba_heads"], config["mamba_head_dim"]
    G, N = config["ssm_groups"], config["ssm_state"]
    eps = config["norm_eps"]
    b, t, _ = x.shape
    inner = H * P
    taps = w["conv_w"].shape[0]
    u = _rmsnorm(x, w["mixer_norm"], eps)
    z, xbc, dt = jnp.split(u @ w["w_in"], (inner, 2 * inner + 2 * G * N), -1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][i] * padded[:, i:i + t] for i in range(taps))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs, B, C = jnp.split(xbc, (inner, inner + G * N), -1)
    B = jnp.repeat(B.reshape(b, t, G, N), H // G, axis=2)
    C = jnp.repeat(C.reshape(b, t, G, N), H // G, axis=2)
    y = recurrence(xs.reshape(b, t, H, P), jax.nn.softplus(dt + w["dt_bias"]),
                   -jnp.exp(w["A_log"]), B, C, w["D"])
    gated = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, G, -1)
    y = _rmsnorm(gated, w["norm"].reshape(G, -1), eps).reshape(b, t, inner)
    return x + y @ w["w_out"]


def attention(x, w, config: Dict[str, Any]):
    """x + Attn(RMS(x)) of one layer: a masked softmax, no rotation."""
    h, hk, dh = config["n_heads"], config["n_kv_heads"], config["d_head"]
    b, t, _ = x.shape
    u = _rmsnorm(x, w["attn_norm"], config["norm_eps"])
    q = (u @ w["wq"]).reshape(b, t, h, dh)
    k = jnp.repeat((u @ w["wk"]).reshape(b, t, hk, dh), h // hk, axis=2)
    v = jnp.repeat((u @ w["wv"]).reshape(b, t, hk, dh), h // hk, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores,
                       -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return x + attn.reshape(b, t, h * dh) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], bias=None, best=None):
    """(x + FF(RMS(x)), picked, balance) of one routed layer: `picked`
    [b, t, E] is 1 where a token chose an expert (`best` [b, t, k], where
    given, is the choice; else the largest of `s + bias`), `balance` the
    layer's balance loss before its coefficient."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    u = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    s = jax.nn.sigmoid(u @ w["router"])                       # [b, t, E]
    if best is None:
        biased = s if bias is None else s + jax.lax.stop_gradient(bias)
        best = jnp.argsort(-biased, axis=-1)[..., :top]       # ties: lowest
    picked = jax.nn.one_hot(best, n_experts).sum(axis=-2)     # [b, t, E]
    weights = s * picked
    if config["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True)
                             + config["norm_topk_eps"])
    weights = weights * config["routed_scaling_factor"]
    out = x + _relu2(u @ w["ws_up"]) @ w["ws_down"]           # shared, whole
    for e in range(held):  # the absent experts' terms are left out
        out = out + weights[..., first + e, None] * (
            _relu2(u @ w["w_up"][e]) @ w["w_down"][e])
    share = jax.lax.stop_gradient(picked.sum(axis=(0, 1)) / picked.sum())
    return out, picked, n_experts * jnp.sum(share * s.mean(axis=(0, 1)))


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None, expert_bias=None):
    """(loss, chosen, balance): the loss; which experts each token chose, a
    bool array [routed layers, tokens, n_experts]; and the balance loss
    before its coefficient, the mean over the routed layers.

    `expert_bias` [routed layers, n_experts] is the routers' selection bias
    (zeros where none is given). `expert_index` [routed layers, tokens,
    experts_per_token], where given, takes the place of the reference's own
    choice and nothing else: scores and weights are still the reference's.
    The comparison of gradients hands over the system's choice, so that both
    sides differentiate one routing."""
    top = config["experts_per_token"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen, balance = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        for kind, w in zip(config["sublayer_types"], _layers(params)):
            if kind == "mamba2":
                x = mixer(x, w, config)
            elif kind == "full_attention":
                x = attention(x, w, config)
            else:
                routed = len(chosen)
                x, picked, term = routed_feed_forward(
                    x, w, config,
                    None if expert_bias is None else expert_bias[routed],
                    None if expert_index is None
                    else expert_index[routed].reshape(b, t, top))
                balance.append(term)
                chosen.append(picked.reshape(b * t, -1) > 0)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32),
                     config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["unembed"], jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    balance = jnp.mean(jnp.stack(balance))
    return (ce + config["router_aux_loss_coef"] * balance,
            jnp.stack(chosen), balance)


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None, expert_bias=None):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]`, plus the
    coefficient times the routed layers' mean balance loss."""
    return forward(params, batch, config, expert_index, expert_bias)[0]
