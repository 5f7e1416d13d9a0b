"""Plain reference of the decoder the `laguna` family trains: Laguna-XS.2
(`model_type` `laguna`, the published configuration), one chip's share of
it. With `RMS_w(x) = x / sqrt(mean(x^2) + eps) * w` and no bias anywhere,
layer `l` is

    h = x + Attn_l(RMS_a(x));   y = h + FF_l(RMS_f(h))

- `Attn_l`, by `layer_types[l]`, with `u` the normed input: `q = u W_q`
  `[d, H_l x 128]`, `k = u W_k`, `v = u W_v` `[d, 8 x 128]`; `H_l` is 48 query
  heads on a `full_attention` layer and 64 on a `sliding_attention` one
  (`num_attention_heads_per_layer`), 8 key-value heads on both, a key-value
  head serving `H_l / 8` query heads in a row. Rotary positions by type
  (`rope_parameters`): a full layer turns the first 64 of a head's 128
  columns (`partial_rotary_factor` 0.5) by YaRN's frequencies:
  `f_i = theta^(-2i/64)` with theta 500,000, `g_i = f_i / factor`;
  `dim(r) = 64 ln(L0 / (2 pi r)) / (2 ln theta)` with `L0` the original
  context (4096); `low = max(floor(dim(beta_fast)), 0)`,
  `high = min(ceil(dim(beta_slow)), 63)`;
  `ramp_i = clip((i - low) / (high - low), 0, 1)` for `i` in 0..31;
  `inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)`; cos and sin times
  `attention_factor` 1.4158883. A sliding layer turns all 128 columns by
  `theta^(-2i/128)` with theta 10,000. Scores `q k^T / sqrt(128)` under the
  mask `(j <= i)` and, on a sliding layer, `(i - j < 512)`
  (`sliding_window`); softmax; `ctx = P v`. Gate (`gating`):
  `g = sigmoid(u W_g)`, `W_g` `[d, H_l]`; head `h`'s `ctx` times `g_h`;
  `Attn = ctx W_o`.
- `FF_l` for `mlp_layer_types[l] == "dense"`: `W_d(silu(W_g y) * W_u y)`,
  8192 wide.
- `FF_l` otherwise: `s = sigmoid(y W_r)` over all `n_experts` (256); the
  `experts_per_token` (8) chosen are the largest `s`; their weights are
  `p_j = 2.5 s_j / sum_chosen s`; `FF = sum_j p_j E_j(y) + S(y)`: `E` a
  SwiGLU of width 512, the sum over the chosen experts *that this chip
  holds* (`experts_held = [first, n]`), one after another in a loop (a
  `lax.scan`: one body to compile, not 32), and `S`
  one SwiGLU of width 512 that every token goes through, unweighted. What
  the absent experts would have added is left out, and the partial sum goes
  on to the next layer.
- A final RMS norm, the untied head, the mean next-token cross-entropy, plus
  `router_aux_loss_coef` times the mean over the routed layers of
  `E sum_e f_e P_e` (`f_e` the share of the batch's slots sent to expert
  `e`, a count with no gradient; `P_e` the mean of `s_e` over the batch).

Everything is float32 at the highest matmul precision. Attention is the
full softmax under the mask, computed a block of queries at a time against
all the keys so that the scores of 64 heads fit; each layer and each block
of queries is made again in the backward pass (`jax.checkpoint`: memory,
not mathematics).

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 256 experts, the first `vocab_size` token
  ids of 100352 (a sliced vocabulary is a smaller vocabulary: the loss is
  over the slice), layers 0 to 4 of 40.
- The gate's form, the router's score and normalisation, the balance loss,
  YaRN's ramp and where `attention_factor` enters, which columns turn and
  their half-split pairing, and the window's edge are the configuration's
  `assumed`, each with its reason there: the published config.json names
  none of them, and `transformers` 4.57.6 here has no `laguna` model.
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods: `wq`
`[periods, d, H_l x 128]`, `wk`, `wv` `[periods, d, 1024]`, `w_gate_attn`
`[periods, d, H_l]`, `wo` `[periods, H_l x 128, d]`; the experts' weights
`[periods, n, d, f]`, `router` `[periods, d, n_experts]`, `ws_gate`, `ws_up`
`[periods, d, 512]`, `ws_down` `[periods, 512, d]`; `embed` `[vocab, d]`,
`unembed` `[d, vocab]`. A layer's kind is the configuration's
`layer_types[l]`; its head count is read off `wq`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.lfm2_moe import _layers
from chipbench.reference.transformer import _rmsnorm

QUERY_BLOCK = 256  # queries whose scores against every key are held at once


def rotary_tables(config: Dict[str, Any], kind: str):
    """(inv_freq [turned columns / 2], what cos and sin are multiplied by,
    the turned columns) of a layer of `kind`."""
    head = config["d_head"]
    if kind == "sliding_attention":
        turned = head  # `partial_rotary_factor` 1: the whole head
        i = jnp.arange(turned // 2, dtype=jnp.float32)
        return config["rope_theta_sliding"] ** (-2.0 * i / turned), 1.0, turned
    turned = int(head * config["partial_rotary_factor"])
    theta, scaling = config["rope_theta"], config["rope_scaling"]
    factor, span = scaling["factor"], scaling["original_max_position_embeddings"]

    def dim(turns):
        return turned * math.log(span / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), turned - 1)
    i = jnp.arange(turned // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / turned)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp),
            scaling["attention_factor"], turned)


def _rotate(x, inv_freq, mscale, turned):
    """Half-split rotation by position of the first `turned` columns of
    every head of x [b, t, H, width]; the rest pass."""
    t, half = x.shape[1], turned // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :]
    sin = (jnp.sin(ang) * mscale)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:turned], x[..., turned:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def band_attention(q, k, v, window=None):
    """softmax(q k^T / sqrt(width)) v of q [b, t, H, width] and k, v
    [b, t, Hk, width] under the mask `(j <= i)` and, with `window`,
    `(i - j < window)`, a key-value head repeated to the `H / Hk` query
    heads it serves, a block of `QUERY_BLOCK` queries at a time."""
    b, t, heads, width = q.shape
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(q_blk, first):
        i = first + jnp.arange(q_blk.shape[1])[:, None]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(width)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    # one block where the blocks do not divide the sequence (the tests')
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape(b, t // size, size, heads, width), 1, 0)
    out = jax.lax.map(lambda args: block(*args),
                      (blocks, jnp.arange(0, t, size)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads, v.shape[-1])


def attention(x, w, config: Dict[str, Any], kind: str):
    """x + Attn(RMS_a(x)) of one layer of `kind` with weights `w`,
    x [b, t, d]."""
    hk, width = config["n_kv_heads"], config["d_head"]
    b, t, _ = x.shape
    heads = w["wq"].shape[-1] // width
    tables = rotary_tables(config, kind)
    u = _rmsnorm(x, w["attn_norm"], config["norm_eps"])
    q = _rotate((u @ w["wq"]).reshape(b, t, heads, width), *tables)
    k = _rotate((u @ w["wk"]).reshape(b, t, hk, width), *tables)
    v = (u @ w["wv"]).reshape(b, t, hk, width)
    window = config["sliding_window"] if kind == "sliding_attention" else None
    ctx = band_attention(q, k, v, window)
    gate = jax.nn.sigmoid(u @ w["w_gate_attn"])              # [b, t, H]
    return x + (ctx * gate[..., None]).reshape(b, t, heads * width) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], best=None):
    """(x + FF(RMS_f(x)), picked, balance) of one routed layer: `picked`
    [b, t, E] is 1 where a token chose an expert (`best` [b, t, k], where
    given, is the choice), `balance` the layer's balance loss over the
    batch, before its coefficient. The routed sum is over the chosen
    experts this chip holds, one expert after another; the shared expert
    is whole."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    s = jax.nn.sigmoid(y @ w["router"])                      # [b, t, E]
    if best is None:
        best = jnp.argsort(-s, axis=-1)[..., :top]           # ties: lowest
    picked = jax.nn.one_hot(best, n_experts).sum(axis=-2)    # [b, t, E]
    chosen = s * picked
    p = config["routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)
    out = x + _swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"])

    def add_expert(out, expert):  # one held expert on every token
        gate, up, down, weight = expert
        return out + weight[..., None] * _swiglu(y, gate, up, down), None

    if held:
        out, _ = jax.lax.scan(add_expert, out, (
            w["w_gate"], w["w_up"], w["w_down"],
            jnp.moveaxis(p[..., first:first + held], -1, 0)))
    share = jax.lax.stop_gradient(picked.sum(axis=(0, 1))) / picked.sum()
    return out, picked, n_experts * jnp.sum(share * s.mean(axis=(0, 1)))


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None):
    """(loss, chosen, balance): the loss; which experts each token chose, a
    bool array [routed layers, tokens, n_experts]; and the balance loss
    before its coefficient, the mean over the routed layers (both
    information for the comparison).

    `expert_index` [routed layers, tokens, experts_per_token], where given,
    takes the place of the reference's own choice and nothing else: scores
    and weights are still the reference's. The comparison of gradients hands
    over the system's choice, so that both sides differentiate one
    routing."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen, balance = [], 0.0

    @jax.checkpoint
    def dense_layer(x, w):
        x = attention(x, w, config, config["layer_types"][0])
        y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
        return x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"])

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        for layer, w in enumerate(_layers(params)):
            if layer < config["n_dense_layers"]:
                x = dense_layer(x, w)
                continue
            kind = config["layer_types"][layer]
            best = (None if expert_index is None
                    else expert_index[len(chosen)].reshape(b, t, top))

            @jax.checkpoint
            def routed_layer(x, w, best, kind=kind):
                return routed_feed_forward(
                    attention(x, w, config, kind), w, config, best)

            x, picked, term = routed_layer(x, w, best)
            balance = balance + term
            chosen.append(picked.reshape(b * t, n_experts) > 0)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32),
                     config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["unembed"], jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    balance = balance / max(len(chosen), 1)
    loss = ce + config["router_aux_loss_coef"] * balance
    return loss, jnp.stack(chosen) if chosen else None, balance


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]`, plus the
    coefficient times the routed layers' mean balance loss."""
    return forward(params, batch, config, expert_index)[0]
