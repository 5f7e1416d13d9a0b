"""Plain reference of the decoder the `solar_open2` family trains:
Solar-Open2-250B (`model_type` `solar_open2`, the published configuration),
one chip's share of it. With `RMS_w(x) = x / sqrt(mean(x^2) + eps) * w`,
layer `l` is

    h = x + Mix_l(RMS_a(x));   y = h + MoE_l(RMS_f(h))

- `Mix_l` on a `gqa_layers` layer, with `u` the normed input: softmax
  attention without rotary positions (`use_rope` false). `q = u W_q`,
  `k = u W_k`, `v = u W_v` over the query heads this chip holds and the
  key-value heads they read (`heads_held = [first, n]` of 64 query heads, 8
  a key-value head; the head counts are read off the weights), heads of
  128; scores `q k^T / sqrt(128)` under the mask `(j <= i)`; `ctx = P v`;
  the gate (`use_gqa_gate`) `sigmoid(u W_g)`, as wide as `ctx`, times
  `ctx`; `Mix = (ctx * gate) W_o`, the partial sum over the held heads.
- `Mix_l` otherwise, Kimi Delta Attention (arXiv:2510.26692) over the held
  heads `h` of width `d = 128`, state `S_h` `[d, d]`, zero before the first
  token:

      q = l2norm(silu(conv(u W_q)))_h    k = l2norm(silu(conv(u W_k)))_h
      v = silu(conv(u W_v))_h            conv: causal, a channel, 4 taps, no bias
      g = -exp(A_log_h) softplus(W_f2 (W_f1 u) + dt_bias)_h    in R^d, <= 0
      beta = 2 sigmoid(u W_b)_h                                 in (0, 2)
      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
      o_t = S_t^T q_t / sqrt(d)
      Mix = [RMS_n(o_t) over each head's d, one scale
             * sigmoid(W_g2 (W_g1 u) + b_g)] W_o

  `l2norm(x) = x / sqrt(sum x^2 + 1e-6)`. The recurrence is taken token by
  token, a `lax.scan` over the sequence (in two levels, blocks of
  `TOKEN_BLOCK` tokens made again in the backward pass: memory, not
  mathematics); never the chunked form the program computes.
- `MoE`: `p = softmax(y W_r)` over all `n_experts` (320) in float32; the
  `experts_per_token` (8) largest; their weights over their sum
  (`norm_topk_prob`), factor 1; `MoE = sum_j p_j E_j(y) + S(y)`, `E` a
  SwiGLU of width 1280, the sum over the chosen experts *that this chip
  holds* (`experts_held = [first, n]`), one after another in a loop (a
  `lax.scan`), and `S` one SwiGLU of width 1280 that every token goes
  through, unweighted. What the absent experts and the absent heads would
  have added is left out, and the partial sum goes on to the next layer.
- A final RMS norm, the untied head, the mean next-token cross-entropy, plus
  `router_aux_loss_coef` times the mean over the layers of `E sum_e f_e P_e`
  (`f_e` the share of the batch's slots sent to expert `e`, a count with no
  gradient; `P_e` the mean of `p_e` over the batch).

Everything is float32 at the highest matmul precision. Attention is the
full softmax under the mask, a block of queries at a time; each layer is
made again in the backward pass (`jax.checkpoint`).

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 64 heads of every mixer, `n` of the 320
  experts, the first `vocab_size` token ids of 196,608 (a sliced vocabulary
  is a smaller vocabulary: the loss is over the slice), layers 0 to 3 of 48.
- The forms the published config.json names and does not spell out (KDA's
  low-rank gates, where a bias sits, the decay's parametrisation, the
  scale, the attention gate's width, the router's score and the balance
  loss) are the configuration's `assumed`, each with its reason there.
- No dropout, no padding mask, no reset of the state inside a sequence:
  sequences are whole documents.

Parameters use the program's layout (`transformer_init` of a stack of
unlike layers): `blocks` is a list of segments, each a list with one tree
per layer of its period, every leaf stacked over the segment's periods. A
layer is KDA where its tree has `kda_q`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.laguna import _swiglu, band_attention
from chipbench.reference.lfm2_moe import _layers
from chipbench.reference.transformer import _rmsnorm

TOKEN_BLOCK = 64  # tokens whose states the recurrence's backward holds


def causal_taps(u, w):
    """`c_t = sum_i w_i u_(t - taps + 1 + i)` a channel of `u` [b, t, C]
    with taps `w` [taps, C], `u` zero before the sequence."""
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i:i + t] for i in range(taps))


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """`o` [b, t, H, d] of the recurrence above, token by token: q, k, v, g
    [b, t, H, d], beta [b, t, H]."""
    b, t, heads, d = q.shape

    def one_token(S, token):
        q_t, k_t, v_t, g_t, beta_t = token
        S = jnp.exp(g_t)[..., None] * S                      # Diag(alpha) S
        seen = jnp.einsum("bhc,bhcv->bhv", k_t, S)           # S^T k
        S = S + jnp.einsum("bhc,bhv->bhcv", k_t,
                           beta_t[..., None] * (v_t - seen))
        return S, jnp.einsum("bhc,bhcv->bhv", q_t, S) / math.sqrt(d)

    @jax.checkpoint
    def one_block(S, block):
        return jax.lax.scan(one_token, S, block)

    size = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    blocks = tuple(
        jnp.moveaxis(x, 1, 0).reshape(t // size, size, *x.shape[:1],
                                      *x.shape[2:])
        for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(one_block, jnp.zeros((b, heads, d, d), jnp.float32),
                        blocks)
    return jnp.moveaxis(o.reshape(t, b, heads, d), 0, 1)


def kda(x, w, config: Dict[str, Any]):
    """x + KDA(RMS_a(x)) of one layer with weights `w`, x [b, t, d], over
    the heads `w` holds."""
    d = config["kda_head_dim"]
    eps = config["norm_eps"]
    b, t, _ = x.shape
    heads = w["kda_q"].shape[-1] // d
    u = _rmsnorm(x, w["kda_norm"], eps)
    q, k, v = (
        jax.nn.silu(causal_taps(u @ w[name], w["kda_conv"][i])).reshape(
            b, t, heads, d)
        for i, name in enumerate(("kda_q", "kda_k", "kda_v")))
    g = -jnp.exp(w["kda_A_log"])[:, None] * jax.nn.softplus(
        (u @ w["kda_f1"]) @ w["kda_f2"] + w["kda_dt_bias"]).reshape(
            b, t, heads, d)
    beta = 2.0 * jax.nn.sigmoid(u @ w["kda_b"])              # [b, t, H]
    o = delta_rule(l2norm(q), l2norm(k), v, g, beta)
    gate = jax.nn.sigmoid((u @ w["kda_g1"]) @ w["kda_g2"] + w["kda_g_bias"])
    o = _rmsnorm(o, w["kda_out_norm"], eps).reshape(b, t, heads * d)
    return x + (o * gate) @ w["kda_o"]


def attention(x, w, config: Dict[str, Any]):
    """x + Attn(RMS_a(x)) of one layer with weights `w`, x [b, t, d], over
    the query and key-value heads `w` holds; no rotary positions."""
    width = config["d_head"]
    b, t, _ = x.shape
    heads, hk = w["wq"].shape[-1] // width, w["wk"].shape[-1] // width
    u = _rmsnorm(x, w["attn_norm"], config["norm_eps"])
    ctx = band_attention((u @ w["wq"]).reshape(b, t, heads, width),
                         (u @ w["wk"]).reshape(b, t, hk, width),
                         (u @ w["wv"]).reshape(b, t, hk, width))
    gate = jax.nn.sigmoid(u @ w["w_gate_attn"])              # [b, t, H width]
    return x + (ctx.reshape(b, t, heads * width) * gate) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], best=None):
    """(x + MoE(RMS_f(x)), picked, balance) of one layer: `picked`
    [b, t, E] is 1 where a token chose an expert (`best` [b, t, k], where
    given, is the choice), `balance` the layer's balance loss over the
    batch, before its coefficient. The routed sum is over the chosen
    experts this chip holds, one expert after another; the shared expert
    is whole."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    p = jax.nn.softmax(y @ w["router"], axis=-1)             # [b, t, E]
    if best is None:
        best = jnp.argsort(-p, axis=-1)[..., :top]           # ties: lowest
    picked = jax.nn.one_hot(best, n_experts).sum(axis=-2)    # [b, t, E]
    chosen = p * picked
    if config["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    chosen = config["routed_scaling_factor"] * chosen
    out = x + _swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"])

    def add_expert(out, expert):  # one held expert on every token
        gate, up, down, weight = expert
        return out + weight[..., None] * _swiglu(y, gate, up, down), None

    out, _ = jax.lax.scan(add_expert, out, (
        w["w_gate"], w["w_up"], w["w_down"],
        jnp.moveaxis(chosen[..., first:first + held], -1, 0)))
    share = jax.lax.stop_gradient(picked.sum(axis=(0, 1))) / picked.sum()
    return out, picked, n_experts * jnp.sum(share * p.mean(axis=(0, 1)))


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None):
    """(loss, chosen, balance): the loss; which experts each token chose, a
    bool array [layers, tokens, n_experts]; and the balance loss before its
    coefficient, the mean over the layers (both information for the
    comparison).

    `expert_index` [layers, tokens, experts_per_token], where given, takes
    the place of the reference's own choice and nothing else: scores and
    weights are still the reference's. The comparison of gradients hands
    over the system's choice, so that both sides differentiate one
    routing."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen, balance = [], 0.0

    @jax.checkpoint
    def layer_fn(x, w, best):
        mix = kda if "kda_q" in w else attention
        return routed_feed_forward(mix(x, w, config), w, config, best)

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        for layer, w in enumerate(_layers(params)):
            best = (None if expert_index is None
                    else expert_index[layer].reshape(b, t, top))
            x, picked, term = layer_fn(x, w, best)
            balance = balance + term
            chosen.append(picked.reshape(b * t, n_experts) > 0)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32),
                     config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["unembed"], jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    balance = balance / len(chosen)
    loss = ce + config["router_aux_loss_coef"] * balance
    return loss, jnp.stack(chosen), balance


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]`, plus the
    coefficient times the layers' mean balance loss."""
    return forward(params, batch, config, expert_index)[0]
