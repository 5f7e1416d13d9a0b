"""Plain reference of the decoder the `mellum` family trains:
Mellum2-12B-A2.5B (`model_type` `mellum`, the published configuration), the
whole layer: all 64 experts and the whole vocabulary, since on the four chips
of the cell nothing of a layer is left out. With `RMS_w(x) = x / sqrt(mean(x^2)
+ eps) * w` and no bias anywhere, layer `l` is

    h = x + Attn_l(RMS_a(x));   y = h + FF(RMS_f(h))

- `Attn_l`, by `layer_types[l]`, with `u` the normed input: `q = u W_q`
  `[d, 32 x 128]`, `k = u W_k`, `v = u W_v` `[d, 4 x 128]`: 32 query heads
  over 4 key-value heads on both kinds of layer, a key-value head serving 8
  query heads in a row. Rotary positions by type (`rope_parameters`), over
  all 128 columns of a head, paired half-split (column i with column i + 64).
  A `sliding_attention` layer turns them by `f_i = theta^(-2i/128)`, theta
  500,000 (`rope_type` default). A `full_attention` layer by YaRN's
  frequencies: `g_i = f_i / factor` (16); `dim(r) = 128 ln(L0 / (2 pi r)) /
  (2 ln theta)` with `L0` the original context (8192); `low =
  max(floor(dim(beta_fast)), 0)`, `high = min(ceil(dim(beta_slow)), 127)`
  (beta_fast 32, beta_slow 1); `ramp_i = clip((i - low) / (high - low), 0, 1)`
  for `i` in 0..63; `inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)`; cos and sin
  times `attention_factor` 1.2772588722 (0.1 ln 16 + 1). Scores `q k^T /
  sqrt(128)` under the mask `(j <= i)` and, on a sliding layer, `(i - j <
  1024)` (`sliding_window`); softmax; `Attn = (P v) W_o`.
- `FF`: `s = softmax(y W_r)` over the 64 experts; the 8 chosen
  (`num_experts_per_tok`) are the largest `s`; their weights are `p_j = s_j /
  sum_chosen s` (`norm_topk_prob` true); `FF = sum_j p_j E_j(y)`, `E` a SwiGLU
  of width 896 (`W_d(silu(W_g y) * W_u y)`), over all 64 experts one after
  another in a loop (a `lax.scan`: one body to compile, not 64; in groups of
  16 that the backward pass makes again, so that one group's products are
  held at a time), each on every token with the weight 0 where it was not
  chosen. No shared expert, no dense
  layer (`mlp_layer_types` is `sparse` throughout).
- A final RMS norm, the untied head, the mean next-token cross-entropy, plus
  `router_aux_loss_coef` times the mean over the layers of `E sum_e f_e P_e`
  (`f_e` the share of the batch's slots sent to expert `e`, a count with no
  gradient; `P_e` the mean of `s_e` over the batch).

Everything is float32 at the highest matmul precision. It is computed in
blocks so that it fits: attention a block of queries at a time against all
the keys (`reference/laguna.py`'s `band_attention`), the head a block of
positions at a time over all the sequences, each layer and each block made
again in the backward pass (`jax.checkpoint`: memory, not mathematics). The
blocks run along the positions and never along the sequences, so that the
batch may stay split over the cell's four chips. Nothing of `ray_tpu/ops` is
imported.

Departures from the published model, written down as the contract asks:
- Depth alone: layers 0 to 3 of 28, one whole period (sliding, sliding,
  sliding, full).
- `described_as` names an "MTP head"; the config has no key for one, so it
  is left out, not guessed. The loss is the next token's alone.
- The balance loss's coefficient, the window's edge, the rotary layout, how
  `attention_factor` enters and `max_window_layers: 0` / `use_sliding_window`
  read as "`layer_types` decides" are the configuration's `assumed`, each with
  its reason there.
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods: `wq`, `wo`
`[periods, 2304, 4096]` and its transpose, `wk`, `wv` `[periods, 2304, 512]`,
the experts' `w_gate`, `w_up` `[periods, 64, 2304, 896]`, `w_down`
`[periods, 64, 896, 2304]`, `router` `[periods, 2304, 64]`; `embed`
`[vocab, d]`, `unembed` `[d, vocab]`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.laguna import _rotate, _swiglu, band_attention
from chipbench.reference.lfm2_moe import _layers
from chipbench.reference.transformer import _rmsnorm

HEAD_BLOCK = 512  # positions whose logits over the vocabulary are held at once
EXPERT_GROUP = 16  # experts whose products on every token are held at once


def rotary_tables(config: Dict[str, Any], kind: str):
    """(inv_freq [64], what cos and sin are multiplied by, the turned
    columns: the whole head) of a layer of `kind`."""
    head = config["d_head"]
    i = jnp.arange(head // 2, dtype=jnp.float32)
    if kind == "sliding_attention":
        return config["rope_theta_sliding"] ** (-2.0 * i / head), 1.0, head
    theta, scaling = config["rope_theta"], config["rope_scaling"]
    factor, span = scaling["factor"], scaling["original_max_position_embeddings"]

    def dim(turns):
        return head * math.log(span / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), head - 1)
    f = theta ** (-2.0 * i / head)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp),
            scaling["attention_factor"], head)


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
    return x + ctx.reshape(b, t, heads * width) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], best=None):
    """(x + FF(RMS_f(x)), own, balance) of one layer: `own` [b, t, E] is 1
    where the reference's scores choose an expert for a token, the layer
    computed with `best` [b, t, k] where that is given and with `own`
    where not; `balance` the layer's balance loss over the batch, before
    its coefficient. Every one of the `E` experts runs on every token, one
    expert after another."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    s = jax.nn.softmax(y @ w["router"], axis=-1)             # [b, t, E]
    own = jax.nn.one_hot(jnp.argsort(-s, axis=-1)[..., :top],  # ties: lowest
                         n_experts).sum(axis=-2)             # [b, t, E]
    picked = own if best is None else jax.nn.one_hot(
        best, n_experts).sum(axis=-2)
    chosen = s * picked
    p = chosen / chosen.sum(-1, keepdims=True)               # norm_topk_prob

    def add_expert(out, expert):  # one expert on every token
        gate, up, down, weight = expert
        return out + weight[..., None] * _swiglu(y, gate, up, down), None

    @jax.checkpoint
    def add_group(out, group):  # made again in the backward pass
        return jax.lax.scan(add_expert, out, group)[0], None

    size = EXPERT_GROUP if n_experts % EXPERT_GROUP == 0 else n_experts
    out, _ = jax.lax.scan(add_group, x, jax.tree.map(
        lambda a: a.reshape(n_experts // size, size, *a.shape[1:]),
        (w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(p, -1, 0))))
    share = jax.lax.stop_gradient(picked.sum(axis=(0, 1))) / picked.sum()
    return out, own, n_experts * jnp.sum(share * s.mean(axis=(0, 1)))


def cross_entropy(x, head, targets):
    """The mean over all tokens of `-log softmax(x head)[target]`, x
    [b, t, d], a block of `HEAD_BLOCK` positions of every sequence at a
    time."""
    b, t, d = x.shape
    size = HEAD_BLOCK if t % HEAD_BLOCK == 0 else t

    @jax.checkpoint
    def block(args):
        x_blk, targets_blk = args                            # [b, size, ..]
        logp = jax.nn.log_softmax(x_blk @ head, axis=-1)
        return -jnp.take_along_axis(
            logp, targets_blk[..., None], axis=-1).sum()

    sums = jax.lax.map(block, (
        jnp.moveaxis(x.reshape(b, t // size, size, d), 1, 0),
        jnp.moveaxis(targets.reshape(b, t // size, size), 1, 0)))
    return sums.sum() / (b * t)


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None, layout=None):
    """(loss, chosen, balance): the loss; which experts the reference's own
    scores choose for each token in each layer, a bool array [layers,
    tokens, n_experts]; and the balance loss before its coefficient, the
    mean over the layers (both information for the comparison).

    `expert_index` [layers, tokens, experts_per_token], where given, takes
    the place of the reference's own choice in what is computed and nothing
    else: scores and weights are still the reference's. The comparison of
    gradients hands over the system's choice, so that both sides
    differentiate one routing; `chosen` is then what the reference would
    have chosen in each layer on that layer's own input, which the
    comparison holds the system's choice against.

    `layout`, where the harness gives one, is applied to the stream
    `[b, t, d]` where a layer takes it and hands it on: the identity in
    value, it says where the array lies (the sequences over the cell's
    chips: left to itself the partitioner kept all of them on every chip,
    and the loop over the experts then held 12.7 GB). The reference names
    no device."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen, balance = [], 0.0
    layout = layout or (lambda x: x)
    with jax.default_matmul_precision("highest"):
        x = layout(jnp.asarray(params["embed"], jnp.float32)[tokens])
        for layer, w in enumerate(_layers(params)):
            kind = config["layer_types"][layer]
            best = (None if expert_index is None
                    else expert_index[layer].reshape(b, t, top))

            @jax.checkpoint
            def routed_layer(x, w, best, kind=kind):
                x = layout(attention(layout(x), w, config, kind))
                out, picked, term = routed_feed_forward(x, w, config, best)
                return layout(out), picked, term

            x, picked, term = routed_layer(x, w, best)
            balance = balance + term
            chosen.append(picked.reshape(b * t, n_experts) > 0)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32),
                     config["norm_eps"])
        ce = cross_entropy(
            x, jnp.asarray(params["unembed"], jnp.float32), targets)
    balance = balance / len(chosen)
    loss = ce + config["router_aux_loss_coef"] * balance
    return loss, jnp.stack(chosen), balance


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None, layout=None):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]`, plus the
    coefficient times the layers' mean balance loss."""
    return forward(params, batch, config, expert_index, layout)[0]
