"""Plain reference of the decoder the `lfm2_moe` family trains: LFM2-24B-A2B
(`model_type` `lfm2_moe`, the published configuration and modelling code),
one chip's share of it. With `RMS_w(x) = x / sqrt(mean(x^2) + eps) * w`,
layer `l` is

    x <- x + Op_l(RMS_a(x));   x <- x + FF_l(RMS_f(x))

- `Op` where `layer_types[l]` is `conv`, the gated short convolution:
  `[B, C, X] = split3(y W_in)` (no bias); `u = B * X`;
  `c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t` per channel, `u` zero before
  the sequence's start (`conv_L_cache` 3 taps); `Op = (C * c) W_out`.
- `Op` where it is `full_attention`: `q, k, v = y W_q, y W_k, y W_v`; every
  head of q and of k is RMS-normed over its own width with one learned scale
  for q and one for k, shared by the heads; rotary positions (half-split
  rotation); causal softmax attention at scale `1/sqrt(head width)`, each
  key-value head serving `heads / kv heads` query heads; `W_o`.
- `FF` for `l < n_dense_layers`: `W_2(silu(W_1 y) * W_3 y)`.
- `FF` otherwise: scores `s = sigmoid(y W_r)` over all `n_experts`; the
  `experts_per_token` chosen are the largest of `s + b` (`b` the selection
  bias, which no gradient reaches); their weights are the unbiased scores
  `p_j = s_j / (sum of the chosen s + norm_topk_eps)`;
  `FF = sum_j p_j W2_e(silu(W1_e y) * W3_e y)` over the chosen experts *that
  this chip holds* (`experts_held = [first, n]`: experts `first` to
  `first + n - 1`). What the absent experts would have added is left out,
  and the partial sum goes on to the next layer. No shared expert.
- A final RMS norm, the tied head, the mean next-token cross-entropy. The
  published configuration has no auxiliary loss: the loss is that alone.

Everything is float32 at the highest matmul precision. Every held expert is
applied to every token and the result is masked by the choice: no sort, no
grouping, no kernel. The convolution is an explicit sum over the taps of a
padded array; attention is the full softmax(QK^T/sqrt(d))V under a causal
mask.

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 64 experts, the first `vocab_size` token ids
  of 65536 (a sliced vocabulary is a smaller vocabulary: the loss is over
  the slice), 5 of 40 layers with one of the two leading dense ones.
- `routed_scaling_factor` is 1 and is not applied; `conv_bias` is false.
- The `1e-6` in the weights' denominator, the per-head QK-norm and the tied
  head are the published modelling code's as remembered (no network here);
  `config.json` has no key for them.
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods; the experts'
weights `[periods, n, d, f]`, `router` `[periods, d, n_experts]`, `q_norm`
and `k_norm` `[periods, head width]`, `conv_in` `[periods, d, 3 d]`,
`conv_w` `[periods, taps, d]`, `conv_out` `[periods, d, d]`, `embed`
`[vocab, d]`; a stack of one kind of layer is that one tree, stacked over
the layers. The layers' kinds are read from the configuration, not from the
tree.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.transformer import _rmsnorm, _rope


def _layers(params: Dict[str, Any]):
    """Every layer's weights, first to last, as float32."""
    blocks = params["blocks"]
    if isinstance(blocks, dict):  # a stack of one kind of layer
        blocks = [[blocks]]
    for segment in blocks:
        periods = jax.tree.leaves(segment[0])[0].shape[0]
        for period in range(periods):
            for tree in segment:
                yield {k: jnp.asarray(v[period], jnp.float32)
                       for k, v in tree.items()}


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None, expert_bias=None):
    """(loss, chosen): the loss, and which experts each token chose, as a
    bool array [routed layers, tokens, n_experts] (information for the
    comparison; None without a routed layer).

    `expert_bias` [routed layers, n_experts] is the routers' selection bias
    (zeros where none is given). `expert_index` [routed layers, tokens,
    experts_per_token], where given, takes the place of the reference's own
    choice and nothing else: scores and weights are still the reference's.
    The comparison of gradients hands over the system's choice, so that both
    sides differentiate one routing."""
    d, h = config["d_model"], config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    eps, theta = config["norm_eps"], config["rope_theta"]
    taps = config["conv_taps"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen = []
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], jnp.float32)
        x = embed[tokens]
        mask = jnp.tril(jnp.ones((t, t), bool))
        for layer, w in enumerate(_layers(params)):
            if config["layer_types"][layer] == "conv":
                y = _rmsnorm(x, w["conv_norm"], eps)
                gate_b, gate_c, stream = jnp.split(y @ w["conv_in"], 3, axis=-1)
                u = jnp.pad(gate_b * stream, ((0, 0), (taps - 1, 0), (0, 0)))
                conv = sum(w["conv_w"][i] * u[:, i:i + t] for i in range(taps))
                x = x + (gate_c * conv) @ w["conv_out"]
            else:
                y = _rmsnorm(x, w["attn_norm"], eps)
                q = (y @ w["wq"]).reshape(b, t, h, dh)
                k = (y @ w["wk"]).reshape(b, t, hk, dh)
                v = (y @ w["wv"]).reshape(b, t, hk, dh)
                q = _rope(_rmsnorm(q, w["q_norm"], eps), theta)
                k = _rope(_rmsnorm(k, w["k_norm"], eps), theta)
                k = jnp.repeat(k, h // hk, axis=2)
                v = jnp.repeat(v, h // hk, axis=2)
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
                attn = jnp.einsum(
                    "bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
                x = x + attn.reshape(b, t, h * dh) @ w["wo"]

            y = _rmsnorm(x, w["mlp_norm"], eps)
            if layer < config["n_dense_layers"]:
                x = x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])
                         ) @ w["w_down"]
                continue
            routed = len(chosen)
            s = jax.nn.sigmoid(y @ w["router"])                  # [b, t, E]
            if expert_index is not None:
                best = expert_index[routed].reshape(b, t, top)
            else:
                biased = s if expert_bias is None else s + expert_bias[routed]
                best = jnp.argsort(-biased, axis=-1)[..., :top]  # ties: lowest
            picked = jax.nn.one_hot(best, n_experts).sum(axis=-2)  # [b, t, E]
            weights = s * picked
            if config["norm_topk_prob"]:
                weights = weights / (weights.sum(axis=-1, keepdims=True)
                                     + config["norm_topk_eps"])
            mine = weights[..., first:first + held]              # [b, t, n]
            gate = jnp.einsum("btd,edf->btef", y, w["w_gate"])
            up = jnp.einsum("btd,edf->btef", y, w["w_up"])
            every = jnp.einsum("btef,efd->bted", jax.nn.silu(gate) * up,
                               w["w_down"])
            x = x + jnp.einsum("bted,bte->btd", every, mine)
            chosen.append(picked.reshape(b * t, n_experts) > 0)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32), eps)
        logp = jax.nn.log_softmax(x @ embed.T, axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    return ce, jnp.stack(chosen) if chosen else None


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None, expert_bias=None):
    """Mean cross-entropy of `batch["targets"]` given `batch["tokens"]`."""
    return forward(params, batch, config, expert_index, expert_bias)[0]
