"""Plain reference of what the `sdar` family trains: SDAR-30B-A3B's decoder
(`model_type` `sdar_moe`, the published configuration; the Qwen3-MoE
lineage's block), one chip's share of it, under block diffusion as BD3-LMs
state it (arXiv:2503.09573) and SDAR (arXiv:2510.06303) adopts it.

For a sequence `x_0` of `L` ids, block length `B`, blocks `b(i) = i // B`:

- Noise. A level `t_b` in (0, 1] for each block; token `i` is masked where
  `m_i = 1`, with probability `t_b(i)`; `x_t[i] = MASK where m_i else
  x_0[i]`. The batch carries the draw as integers, `m_i = noise_i <
  level_b(i)` and `t_b = level_b / 2^24`, so that this file and the program
  read the same mask to the bit.
- The stack runs on `R = 2 L` rows: rows `0 .. L - 1` embed `x_t` (the noisy
  half), rows `L .. 2 L - 1` embed `x_0` (the clean half); row `r` has
  position `p(r) = r mod L`.
- A layer, with `RMS_w(x) = x / sqrt(mean(x^2) + eps) * w` and no bias:
  `u = RMS_a(h)`; `q = rot(RMS_q(u W_q), p)`, `k = rot(RMS_k(u W_k), p)`,
  the norms over each head's 128 columns with one learned scale for all
  heads, rotary positions on all 128 columns (half-split pairs,
  `theta^(-2i/128)`, theta 1e6); `v = u W_v`; a key-value head serves 8
  query heads in a row. Row `r` of half `s` and block `b = p(r) // B`
  attends, in one softmax at scale `1 / sqrt(128)`, to **the clean rows of
  every block before b** and **the rows of its own half in block b** (all
  `B` of them, both directions): BD3-LMs' `block_diagonal |
  offset_block_causal | block_causal`, built here as one explicit `[2 L,
  2 L]` boolean mask from those three rules (`attention_mask`). So a noisy
  row never sees a noisy row of another block or the clean copy of its own
  block, and a clean row never sees a noisy row. `h += o W_o`.
- Then `u = RMS_f(h)`; `p = softmax(u W_r)` over all `n_experts` (128) in
  float32; the `experts_per_token` (8) largest, over their sum
  (`norm_topk_prob`); `h += sum over the chosen experts held here of w_e
  (silu(u W_g,e) * (u W_u,e)) W_d,e` (`experts_held = [first, n]`), one
  expert after another in a loop (a `lax.scan`). What the absent experts
  would have added is left out, and the partial sum goes on to the next
  layer. No shared expert.
- Head on the noisy half only, no shift: `logits_i = RMS(h_i) W_out` over
  the slice's ids predicts `x_0[i]`.
- `loss = 1 / (b L) sum_i m_i / t_b(i) CE(logits_i, x_0[i]) +
  router_aux_loss_coef` times the mean over the layers of `E sum_e f_e P_e`
  over all `2 L` rows (`f_e` the share of the batch's slots sent to expert
  `e`, a count with no gradient; `P_e` the mean of `p_e`).

Everything is float32 at the highest matmul precision. Attention is one
dense softmax under the mask, a block of `QUERY_BLOCK` rows at a time
against all `2 L` keys; each layer and each block of rows is made again in
the backward pass (`jax.checkpoint`: memory, not mathematics).

Departures from the published model, written down as the contract asks:
- The chip's share: `n` of the 128 experts, the first `vocab_size` token
  ids of 151,936 (a sliced vocabulary is a smaller vocabulary: the loss is
  over the slice), layers 0 to 3 of 48.
- The block length (4), the linear schedule with `t_b` uniform a block and
  the weight `1 / t`, the positions repeated over the two halves, no shift,
  the balance loss over both halves, `mask_token_id` (the slice's last id,
  never drawn as data and never a target), the per-head norm on q and k and
  the router's form are the configuration's `assumed`, each with its reason
  there: the published config.json gives none of them.
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of one
kind of layer): `blocks` is one tree, every leaf stacked over the layers:
`wq` `[L, d, 4096]`, `wk`, `wv` `[L, d, 512]`, `q_norm`, `k_norm` `[L, 128]`,
`wo` `[L, 4096, d]`; the experts' weights `[L, n, d, f]`, `router`
`[L, d, n_experts]`; `embed` `[vocab, d]`, `unembed` `[d, vocab]`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.laguna import _swiglu
from chipbench.reference.lfm2_moe import _layers
from chipbench.reference.transformer import _rmsnorm

QUERY_BLOCK = 256  # rows whose scores against every key are held at once
NOISE_LEVELS = 1 << 24  # a block's level is a whole number of these parts


def noise(batch: Dict[str, Any], config: Dict[str, Any]):
    """(x_t [b, L], m [b, L] bool, 1 / t [b, L] float32) of a batch's three
    integer columns: token `i` is masked where `noise_i < level_b(i)`."""
    block = config["diffusion_block"]
    level = jnp.repeat(batch["level"], block, axis=1)
    masked = batch["noise"] < level
    x_t = jnp.where(masked, config["mask_token_id"], batch["tokens"])
    return x_t, masked, NOISE_LEVELS / level.astype(jnp.float32)


def attention_mask(length: int, block: int):
    """[2 L, 2 L] bool over the doubled stream, the noisy half first: row
    `r` sees column `c` where one of BD3-LMs' three rules holds."""
    row = jnp.arange(2 * length)[:, None]
    col = jnp.arange(2 * length)[None, :]
    row_clean, col_clean = row >= length, col >= length
    row_block = (row % length) // block
    col_block = (col % length) // block
    # within a half, a block sees itself, both directions
    block_diagonal = (row_block == col_block) & (row_clean == col_clean)
    # a noisy row sees the clean rows of the blocks strictly before its own
    offset_block_causal = (col_block < row_block) & col_clean & ~row_clean
    # a clean row sees the clean rows of its own block and the ones before
    block_causal = (col_block <= row_block) & col_clean & row_clean
    return block_diagonal | offset_block_causal | block_causal


def _rope_at(x, positions, theta):
    """Rotary positions on x [b, r, H, width], half-split pairs, at
    `positions` [r]."""
    half = x.shape[3] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def masked_attention(q, k, v, mask):
    """softmax(q k^T / sqrt(width)) v of q [b, r, H, width] and k, v
    [b, r, Hk, width] under `mask` [r, r], a key-value head repeated to the
    `H / Hk` query heads it serves, a block of `QUERY_BLOCK` rows at a
    time."""
    b, r, heads, width = q.shape
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)

    @jax.checkpoint
    def rows(q_blk, mask_blk):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(width)
        p = jax.nn.softmax(jnp.where(mask_blk[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    # one block where the blocks do not divide the rows (the tests')
    size = QUERY_BLOCK if r % QUERY_BLOCK == 0 else r
    ctx = jax.lax.map(
        lambda args: rows(*args),
        (jnp.moveaxis(q.reshape(b, r // size, size, heads, width), 1, 0),
         mask.reshape(r // size, size, r)))
    return jnp.moveaxis(ctx, 0, 1).reshape(b, r, heads, width)


def attention(x, w, config: Dict[str, Any]):
    """x + Attn(RMS_a(x)) of one layer with weights `w`, x [b, 2 L, d]."""
    heads, hk, width = config["n_heads"], config["n_kv_heads"], config["d_head"]
    eps, theta = config["norm_eps"], config["rope_theta"]
    b, r, _ = x.shape
    length = r // 2
    positions = jnp.arange(r) % length
    u = _rmsnorm(x, w["attn_norm"], eps)
    q = _rmsnorm((u @ w["wq"]).reshape(b, r, heads, width), w["q_norm"], eps)
    k = _rmsnorm((u @ w["wk"]).reshape(b, r, hk, width), w["k_norm"], eps)
    v = (u @ w["wv"]).reshape(b, r, hk, width)
    ctx = masked_attention(
        _rope_at(q, positions, theta), _rope_at(k, positions, theta), v,
        attention_mask(length, config["diffusion_block"]))
    return x + ctx.reshape(b, r, heads * width) @ w["wo"]


def routed_feed_forward(x, w, config: Dict[str, Any], best=None):
    """(x + MoE(RMS_f(x)), own, balance) of one layer on rows x [b, r, d]:
    `own` [b, r, E] is 1 where the reference's own router chose an expert,
    `balance` the layer's balance loss over the batch, before its
    coefficient. `best` [b, r, k], where given, takes the place of the
    reference's own choice in the sum and in the balance loss's counts, and
    of nothing else. The sum is over the chosen experts this chip holds,
    one expert after another."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    first, held = config.get("experts_held") or (0, n_experts)
    y = _rmsnorm(x, w["mlp_norm"], config["norm_eps"])
    p = jax.nn.softmax(y @ w["router"], axis=-1)              # [b, r, E]
    own = jax.nn.one_hot(
        jnp.argsort(-p, axis=-1)[..., :top], n_experts).sum(axis=-2)
    picked = own if best is None else jax.nn.one_hot(
        best, n_experts).sum(axis=-2)
    chosen = p * picked
    if config["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)

    def add_expert(out, expert):  # one held expert on every row
        gate, up, down, weight = expert
        return out + weight[..., None] * _swiglu(y, gate, up, down), None

    out, _ = jax.lax.scan(add_expert, x, (
        w["w_gate"], w["w_up"], w["w_down"],
        jnp.moveaxis(chosen[..., first:first + held], -1, 0)))
    share = jax.lax.stop_gradient(picked.sum(axis=(0, 1))) / picked.sum()
    return out, own, n_experts * jnp.sum(share * p.mean(axis=(0, 1)))


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any], expert_index=None):
    """(loss, chosen, balance, masked): the loss; which experts the
    reference's own routers chose for each row, a bool array [layers,
    2 b L, n_experts]; the balance loss before its coefficient, the mean
    over the layers; and the positions of `x_t` that hold the mask's id, a
    count (the last three information for the comparison).

    `expert_index` [layers, 2 b L, experts_per_token], where given, takes
    the place of the reference's own choice and nothing else: probabilities
    and weights are still the reference's. The comparison of gradients hands
    over the system's choice, so that both sides differentiate one
    routing."""
    n_experts, top = config["n_experts"], config["experts_per_token"]
    tokens = batch["tokens"]
    b, length = tokens.shape
    x_t, masked, inverse_t = noise(batch, config)
    rows = jnp.concatenate([x_t, tokens], axis=1)             # [b, 2 L]
    chosen, balance = [], 0.0

    @jax.checkpoint
    def layer_fn(x, w, best):
        return routed_feed_forward(attention(x, w, config), w, config, best)

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[rows]
        for layer, w in enumerate(_layers(params)):
            best = (None if expert_index is None
                    else expert_index[layer].reshape(b, 2 * length, top))
            x, own, term = layer_fn(x, w, best)
            balance = balance + term
            chosen.append(own.reshape(2 * b * length, n_experts) > 0)
        x = _rmsnorm(x[:, :length], jnp.asarray(params["final_norm"],
                                                jnp.float32),
                     config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["unembed"], jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        weighted = jnp.where(masked, inverse_t * ce, 0.0).sum() / (b * length)
    balance = balance / len(chosen)
    loss = weighted + config["router_aux_loss_coef"] * balance
    return (loss, jnp.stack(chosen), balance,
            (x_t == config["mask_token_id"]).sum())


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any],
         expert_index=None):
    """The masked positions' cross-entropies of the noisy half weighted by
    `1 / t`, over the sequence's tokens, plus the coefficient times the
    layers' mean balance loss."""
    return forward(params, batch, config, expert_index)[0]
