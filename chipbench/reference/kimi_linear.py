"""Plain reference of the decoder the `kimi_linear` family trains:
Kimi-Linear-48B-A3B (`model_type` `kimi_linear`, the published configuration;
arXiv:2510.26692), one chip's share of it. With
`RMS_w(x) = x / sqrt(mean(x^2) + eps) * w` and no bias but the one named,
layer `l` is

    h = x + Mix_l(RMS_a(x));   y = h + FF_l(RMS_f(h))

- `Mix_l` on a `kda_layers` layer, Kimi Delta Attention over `H` heads of
  width `d = 128`, with `u` the normed input and a state `S_h` `[d, d]` a
  head, zero before the first token:

      q = l2norm(silu(conv(u W_q)))_h    k = l2norm(silu(conv(u W_k)))_h
      v = silu(conv(u W_v))_h            conv: causal, a channel, 4 taps, no bias
      g = -exp(A_log_h) softplus(W_f2 (W_f1 u) + dt_bias)_h    in R^d, <= 0
      beta = sigmoid(u W_b)_h                                   in (0, 1)
      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
      o_t = S_t^T q_t / sqrt(d)
      Mix = [RMS_n(o_t) over each head's d, one scale
             * sigmoid(W_g2 (W_g1 u) + b_g)] W_o

  `l2norm(x) = x / sqrt(sum x^2 + 1e-6)`. The recurrence is taken token by
  token, a `lax.scan` over the sequence (`reference/solar_open2.py`
  `delta_rule`, in two levels, blocks of tokens made again in the backward
  pass: memory, not mathematics); never the chunked form the program
  computes.
- `Mix_l` on a `full_attn_layers` layer, multi-head latent attention
  without positions (`mla_use_nope` true; `q_lora_rank` null):
  `q = u W_q`, a head 128 + 64 wide; `[c, k_s] = u W_kva`, `c` the latent
  (512) and `k_s` one key of 64 that all heads share;
  `[k_h, v_h] = RMS_kv(c) W_kvb`, 128 and 128 a head. Nothing is rotated:
  the head's key is `[k_h, k_s]` as the projections leave it. Causal
  softmax of `q_h [k_h, k_s]^T / sqrt(192)`, `o_h = P v_h`,
  `Mix = [o_h] W_o`. The KDA layers carry position.
- `FF_l` for `l < first_k_dense_replace` (1): `W_d(silu(W_g y) * W_u y)`,
  9216 wide.
- `FF_l` otherwise: `s = sigmoid(y W_r)` over all `n_experts` (256) in
  float32; the `experts_per_token` (8) chosen are the largest of `s + b`
  (`b` the selection bias, which no gradient reaches; one group of experts,
  `num_expert_group` 1); their weights are the unbiased scores over their
  sum (`moe_renormalize`) times `routed_scaling_factor` (2.446);
  `FF = sum_j p_j E_j(y) + S(y)`, `E` a SwiGLU of width 1024, the sum over
  the chosen experts *that this chip holds* (`experts_held = [first, n]`),
  one after another in a loop (a `lax.scan`), and `S` one SwiGLU of width
  1024 that every token goes through, unweighted. What the absent experts
  would have added is left out, and the partial sum goes on to the next
  layer.
- A final RMS norm, the untied head, the mean next-token cross-entropy. The
  published configuration names no auxiliary loss: the loss is that alone.

Everything is float32 at the highest matmul precision. Attention is the
full softmax under a causal mask, and that layer is made again in the
backward pass (`jax.checkpoint`: its `[heads, t, t]` scores); the KDA layers
keep what they made, their recurrence its blocks' entering states.

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 256 experts, the first `vocab_size` token ids
  of 163,840 (a sliced vocabulary is a smaller vocabulary: the loss is over
  the slice), published layers 1 to 5 of 27 with the one leading dense
  feed-forward. The mixers are whole: all 32 heads of each.
- The forms the published config.json names and does not spell out (KDA's
  low-rank gates, where a bias sits, the decay's parametrisation, the
  scale, the latent attention's scale without YaRN) are the configuration's
  `assumed`, each with its reason there; the equations are the paper's and
  the published modelling code's as remembered (no network here).
- No dropout, no padding mask, no reset of the state or the taps inside a
  sequence: sequences are whole documents.

Parameters use the program's layout (`transformer_init` of a stack of
unlike layers): `blocks` is a list of segments, each a list with one tree
per layer of its period, every leaf stacked over the segment's periods. A
layer is KDA where its tree has `kda_q`, routed where it has `router`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.laguna import _swiglu
from chipbench.reference.lfm2_moe import _layers
# the recurrence token by token (`delta_rule`: a `lax.scan` over the tokens,
# beta its argument), the causal taps and the unit length are Solar-Open2's
# reference's, which states the same mixer
from chipbench.reference.solar_open2 import causal_taps, delta_rule, l2norm
from chipbench.reference.transformer import _rmsnorm

def kda(x, w, config: Dict[str, Any]):
    """(x + KDA(RMS_a(x)) of one layer with weights `w`, x [b, t, d]; the
    layer's mean beta)."""
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
    beta = jax.nn.sigmoid(u @ w["kda_b"])                    # [b, t, H]
    o = delta_rule(l2norm(q), l2norm(k), v, g, beta)
    gate = jax.nn.sigmoid((u @ w["kda_g1"]) @ w["kda_g2"] + w["kda_g_bias"])
    o = _rmsnorm(o, w["kda_out_norm"], eps).reshape(b, t, heads * d)
    return x + (o * gate) @ w["kda_o"], beta.mean()


def latent_attention(x, w, config: Dict[str, Any]):
    """x + Attn(RMS_a(x)) of one layer with weights `w`, x [b, t, d]: no
    column is rotated."""
    h, r = config["n_heads"], config["kv_lora_rank"]
    nope, shared = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, eps = config["v_head_dim"], config["norm_eps"]
    b, t, _ = x.shape
    y = _rmsnorm(x, w["attn_norm"], eps)
    q = (y @ w["wq"]).reshape(b, t, h, nope + shared)
    down = y @ w["wkv_a"]
    latent, k_s = down[..., :r], down[..., None, r:]
    kv = (_rmsnorm(latent, w["kv_norm"], eps) @ w["wkv_b"]).reshape(
        b, t, h, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_s, (b, t, h, shared))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(nope + shared)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores,
                       -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                      kv[..., nope:])
    return x + attn.reshape(b, t, h * dv) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], best=None, bias=None):
    """(x + FF(RMS_f(x)), own) of one routed layer: `own` [b, t, E] is 1
    where the reference's own rule chooses an expert for a token, the
    largest of the scores plus `bias` [E]. `best` [b, t, k], where given,
    takes that choice's place in the sum and nowhere else. The routed sum
    is over the chosen experts this chip holds, one expert after another;
    the shared expert is whole."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    s = jax.nn.sigmoid(y @ w["router"])                      # [b, t, E]
    biased = s if bias is None else s + bias
    own = jnp.argsort(-biased, axis=-1)[..., :top]           # ties: lowest
    picked = jax.nn.one_hot(                                 # [b, t, E]
        own if best is None else best, n_experts).sum(axis=-2)
    chosen = s * picked                                      # unbiased
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
    return out, jax.nn.one_hot(own, n_experts).sum(axis=-2)


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None, expert_bias=None):
    """(loss, chosen, beta): the loss; which experts the reference's own
    rule chooses for each token at each routed layer, a bool array [routed
    layers, tokens, n_experts]; and the KDA layers' mean beta (both
    information for the comparison).

    `expert_bias` [routed layers, n_experts] is the routers' selection bias
    (zeros where none is given). `expert_index` [routed layers, tokens,
    experts_per_token], where given, takes the place of the reference's own
    choice in the routed sums and nothing else: scores and weights are still
    the reference's, and `chosen` is still its own rule's choice, on the
    stream that the given routing made. The comparison hands over the
    system's choice, so that both sides differentiate one routing and the
    two choices are of one stream."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen, betas = [], []

    def layer_fn(x, w, best, bias):
        beta = None
        if "kda_q" in w:
            x, beta = kda(x, w, config)
        else:
            x = latent_attention(x, w, config)
        if "router" not in w:  # the leading dense feed-forward
            y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
            return x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"]), (
                None, beta)
        x, picked = routed_feed_forward(x, w, config, best, bias)
        return x, (picked, beta)

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        for w in _layers(params):
            routed = len(chosen)
            best = (None if expert_index is None or "router" not in w
                    else expert_index[routed].reshape(b, t, top))
            bias = (None if expert_bias is None or "router" not in w
                    else expert_bias[routed])
            fn = layer_fn if "kda_q" in w else jax.checkpoint(layer_fn)
            x, (picked, beta) = fn(x, w, best, bias)
            if picked is not None:
                chosen.append(picked.reshape(b * t, n_experts) > 0)
            if beta is not None:
                betas.append(beta)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32),
                     config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["unembed"], jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    return loss, jnp.stack(chosen), jnp.stack(betas).mean()


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None, expert_bias=None):
    """Mean cross-entropy of `batch["targets"]` given `batch["tokens"]`."""
    return forward(params, batch, config, expert_index, expert_bias)[0]
