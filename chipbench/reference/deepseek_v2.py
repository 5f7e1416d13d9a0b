"""Plain reference of the decoder the `deepseek_v2` family trains:
DeepSeek-V2-Lite (`model_type` `deepseek_v2`, the published configuration
and modelling code; arXiv:2405.04434), one chip's share of it. With
`RMS_w(x) = x / sqrt(mean(x^2) + eps) * w` and no bias anywhere, layer `l`
is

    x <- x + Attn_l(RMS_a(x));   x <- x + FF_l(RMS_f(x))

- `Attn`, multi-head latent attention, with `y` the normed input and `h`
  heads: `q = y W_q`, each head `[q_nope (128), q_pe (64)]`.
  `[c, k_pe] = y W_kva`: `c` the latent (512), `k_pe` the token's one rotary
  key (64), shared by all heads. `[k_nope, v] = RMS_kv(c) W_kvb` per head
  (128 and 128). Rotary positions on `q_pe` (every head) and `k_pe` with
  YaRN's frequencies: `f_i = theta^(-2i/64)`, `g_i = f_i / factor`;
  `dim(r) = 64 ln(L0 / (2 pi r)) / (2 ln theta)` with `L0` the original
  context (4096); `low = max(floor(dim(beta_fast)), 0)`,
  `high = min(ceil(dim(beta_slow)), 63)`;
  `ramp_i = clip((i - low) / (high - low), 0, 1)` for `i` in 0..31;
  `inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)`; cos and sin are multiplied
  by `m(mscale) / m(mscale_all_dim)` with `m(s) = 0.1 s ln(factor) + 1`
  (1 here: both are 0.707). `q_h = [q_nope_h, q_pe_h]`,
  `k_h = [k_nope_h, k_pe]`, 192 wide. Causal softmax of
  `q_h k_h^T sigma`, `sigma = 192^-0.5 m(mscale_all_dim)^2 = 0.114721`;
  `o_h = P v_h` (128 wide); `Attn = [o_h] W_o`.
- `FF` for `l < first_k_dense_replace`: `W_d(silu(W_g y) * W_u y)`.
- `FF` otherwise: `s = softmax(y W_r)` over all `n_experts`; the
  `experts_per_token` chosen are the largest `s` (greedy top-k, one group);
  their weights are `p_j = s_j`, not renormalised (`norm_topk_prob` false),
  times `routed_scaling_factor` 1;
  `FF = sum_j p_j E_j(y) + S(y)`: `E` a SwiGLU of width 1408, the sum over
  the chosen experts *that this chip holds* (`experts_held = [first, n]`),
  and `S` one SwiGLU of width `n_shared_experts x 1408` that every token
  goes through, unweighted. What the absent experts would have added is
  left out, and the partial sum goes on to the next layer.
- A final RMS norm, the untied head, the mean next-token cross-entropy, plus
  for every routed layer `alpha mean_b sum_e f_be P_be` (`seq_aux`):
  `f_be = count_be n_experts / (experts_per_token T)` with `count_be` how
  often sequence `b` chose expert `e`, `P_be = mean_t s_bte`; summed over
  the layers, as the published code adds each layer's.

Everything is float32 at the highest matmul precision. Every held expert is
applied to every token and the result is masked by the choice: no sort, no
grouping, no kernel; attention is the full softmax under a causal mask.

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 64 experts, the first `vocab_size` token ids
  of 102400 (a sliced vocabulary is a smaller vocabulary: the loss is over
  the slice), 6 of 27 layers with the one leading dense layer.
- The published code rotates interleaved pairs after a fixed permutation of
  a head's rotary columns; the rotation here is the half-split one, which
  is the same function of permuted columns of `W_q` and `W_kva`, and the
  weights are seeded.
- The paper's device-level and communication balance losses are not in the
  published modelling code and are left out. No z-loss.
- `alpha` (`aux_loss_alpha` 0.001), YaRN's formulae and `sigma` are the
  published configuration and code as remembered (no network here).
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods: `wq`
`[periods, d, h x 192]`, `wkv_a` `[periods, d, 512 + 64]`, `kv_norm`
`[periods, 512]`, `wkv_b` `[periods, 512, h x (128 + 128)]` (a head's key
columns, then its value columns), `wo` `[periods, h x 128, d]`; the experts'
weights `[periods, n, d, f]`, `router` `[periods, d, n_experts]`, `ws_gate`,
`ws_up` `[periods, d, 2 f]`, `ws_down` `[periods, 2 f, d]`; `embed`
`[vocab, d]`, `unembed` `[d, vocab]`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.lfm2_moe import _layers
from chipbench.reference.transformer import _rmsnorm


def yarn(config: Dict[str, Any]):
    """(inv_freq [rotary width / 2], what cos and sin are multiplied by, the
    scores' scale sigma) of the configuration."""
    width = config["qk_rope_head_dim"]
    theta = config["rope_theta"]
    scaling = config["rope_scaling"]
    factor, span = scaling["factor"], scaling["original_max_position_embeddings"]

    def dim(turns):
        return width * math.log(span / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), width - 1)
    i = jnp.arange(width // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / width)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    inv_freq = f / factor * ramp + f * (1.0 - ramp)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0

    head = config["qk_nope_head_dim"] + width
    sigma = head ** -0.5 * m(scaling["mscale_all_dim"]) ** 2
    return inv_freq, m(scaling["mscale"]) / m(scaling["mscale_all_dim"]), sigma


def _rotate(x, inv_freq, mscale):
    """Half-split rotation of x [B, T, H, width] by position."""
    t, half = x.shape[1], x.shape[3] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :]
    sin = (jnp.sin(ang) * mscale)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def attention(x, w, config: Dict[str, Any]):
    """x + Attn(RMS_a(x)) of one layer with weights `w`, x [b, t, d]."""
    h, r = config["n_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, eps = config["v_head_dim"], config["norm_eps"]
    b, t, _ = x.shape
    inv_freq, mscale, sigma = yarn(config)
    y = _rmsnorm(x, w["attn_norm"], eps)
    q = (y @ w["wq"]).reshape(b, t, h, nope + rope)
    down = y @ w["wkv_a"]
    latent, k_pe = down[..., :r], down[..., None, r:]
    kv = (_rmsnorm(latent, w["kv_norm"], eps) @ w["wkv_b"]).reshape(
        b, t, h, nope + dv)
    q = jnp.concatenate(
        [q[..., :nope], _rotate(q[..., nope:], inv_freq, mscale)], -1)
    k_pe = jnp.broadcast_to(_rotate(k_pe, inv_freq, mscale), (b, t, h, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sigma
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores,
                       -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                      kv[..., nope:])
    return x + attn.reshape(b, t, h * dv) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], best=None):
    """(x + FF(RMS_f(x)), picked, balance) of one routed layer: `picked`
    [b, t, E] is 1 where a token chose an expert (`best` [b, t, k], where
    given, is the choice), `balance` the layer's per-sequence balance loss
    before `alpha`. The routed sum is over the chosen experts this chip
    holds; the shared experts are whole."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    t = x.shape[1]
    y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    s = jax.nn.softmax(y @ w["router"], axis=-1)             # [b, t, E]
    if best is None:
        best = jnp.argsort(-s, axis=-1)[..., :top]           # ties: lowest
    picked = jax.nn.one_hot(best, n_experts).sum(axis=-2)    # [b, t, E]
    mine = (s * picked)[..., first:first + held]             # [b, t, n]
    gate = jnp.einsum("btd,edf->btef", y, w["w_gate"])
    up = jnp.einsum("btd,edf->btef", y, w["w_up"])
    every = jnp.einsum("btef,efd->bted", jax.nn.silu(gate) * up, w["w_down"])
    out = (x + jnp.einsum("bted,bte->btd", every, mine)
           + _swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"]))
    # per sequence: how often it chose e, times E / (k T), times the mean of
    # its scores of e
    share = jax.lax.stop_gradient(picked.sum(axis=1)) * (n_experts / (top * t))
    return out, picked, jnp.mean(jnp.sum(share * s.mean(axis=1), -1))


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None):
    """(loss, chosen, balance): the loss; which experts each token chose, a
    bool array [routed layers, tokens, n_experts]; and the balance loss
    before `alpha`, summed over the layers (both information for the
    comparison).

    `expert_index` [routed layers, tokens, experts_per_token], where given,
    takes the place of the reference's own choice and nothing else: scores
    and weights are still the reference's. The comparison of gradients hands
    over the system's choice, so that both sides differentiate one
    routing."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    chosen, balance = [], 0.0
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        for layer, w in enumerate(_layers(params)):
            x = attention(x, w, config)
            if layer < config["n_dense_layers"]:
                y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
                x = x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"])
                continue
            best = (None if expert_index is None
                    else expert_index[len(chosen)].reshape(b, t, top))
            x, picked, term = routed_feed_forward(x, w, config, best)
            balance = balance + term
            chosen.append(picked.reshape(b * t, n_experts) > 0)
        x = _rmsnorm(x, jnp.asarray(params["final_norm"], jnp.float32),
                     config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["unembed"], jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    loss = ce + config["router_aux_loss_coef"] * balance
    return loss, jnp.stack(chosen) if chosen else None, balance


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None):
    """Cross-entropy of `batch["targets"]` given `batch["tokens"]`, plus
    `alpha` times the layers' per-sequence balance losses."""
    return forward(params, batch, config, expert_index)[0]
