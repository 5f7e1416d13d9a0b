"""Plain reference of the looped decoder the `ouro` family trains
(Ouro-2.6B, arXiv:2510.25741): a stack of sandwich-normed layers that is run
`loop_steps` times over the same weights, the final norm after every pass,
one head and one exit gate that read every pass's normed stream, and one
loss over the passes weighted by the distribution the gate makes.

With T = `loop_steps`, L layers, tokens `s`, targets `y`, N tokens:

    x(0) = E[s]
    for t = 1..T:   h = x(t-1)
        for l = 1..L:   h = h + n2_l( Attn_l( n1_l(h) ) )
                        h = h + n4_l( W_down_l ( silu(W_gate_l u) * W_up_l u ) ),  u = n3_l(h)
        x(t)   = n_f(h)            the next pass's input; what head and gate read
        ce(t)  = logsumexp(x(t) W_head) - (x(t) W_head)[y]        per token
        a(t)   = x(t) . w_g + b_g ;  lambda(t) = sigmoid(a(t))    per token
    p(1) = lambda(1);  p(t) = lambda(t) prod_{j<t} (1 - lambda(j)), 1 < t < T
    p(T) = prod_{j<T} (1 - lambda(j))
    loss = 1/N sum_i [ sum_t p(t)_i ce(t)_i  -  beta H(p_i) ]
    H(p) = - sum_t p(t) log p(t)

Attn: q, k, v = W_q u, W_k u, W_v u as heads of `d_head`, rotary positions
over the whole head (half-split rotation) at `rope_theta`, the full
softmax(q k^T / sqrt(d_head)) v under a causal mask, W_o; no bias anywhere
but b_g. `n*` are RMS norms with a learned scale. log p(t) is formed from
log-sigmoids, never as the log of a product.

Everything is float32 at the highest matmul precision, a Python loop over
the passes and the layers. A layer application and a pass's head are each
under `jax.checkpoint`, and the head takes a pass's logits in blocks of
tokens: 32 applications' scores (268 MB each at 2,048 tokens and 16 heads)
and four passes' logits (403 MB each) would not fit a chip beside three
float32 copies of the parameters; neither changes a value.

Parameters use the program's layout (`transformer_init`): block weights
stacked on a leading layer axis, `embed` [vocab, d], `unembed` [d, vocab],
`final_norm` [d], the gate `exit_w` [d] and `exit_b` [].
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

LOGIT_BLOCK = 512  # tokens whose logits the head holds at once


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: [B, T, H, Dh]; rotate the two halves of each head by position angles
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer(h, w, config):
    """One layer application: attention and the feed-forward, each between
    a norm on its input and a norm on its output."""
    b, t, _ = h.shape
    heads, hk, dh = config["n_heads"], config["n_kv_heads"], config["d_head"]
    eps, theta = config["norm_eps"], config["rope_theta"]
    u = _rmsnorm(h, w["attn_norm"], eps)
    q = _rope((u @ w["wq"]).reshape(b, t, heads, dh), theta)
    k = _rope((u @ w["wk"]).reshape(b, t, hk, dh), theta)
    v = (u @ w["wv"]).reshape(b, t, hk, dh)
    k = jnp.repeat(k, heads // hk, axis=2)
    v = jnp.repeat(v, heads // hk, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    h = h + _rmsnorm(attn.reshape(b, t, heads * dh) @ w["wo"],
                     w["attn_post_norm"], eps)
    u = _rmsnorm(h, w["mlp_norm"], eps)
    ff = (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]
    return h + _rmsnorm(ff, w["mlp_post_norm"], eps)


def token_cross_entropy(x, head, targets):
    """logsumexp(x W) - (x W)[y] a token, [B, T]: whole logits, a block of
    tokens at a time."""
    b, t, d = x.shape
    rows, ys = x.reshape(b * t, d), targets.reshape(b * t)
    out = []
    for start in range(0, b * t, LOGIT_BLOCK):
        logits = rows[start:start + LOGIT_BLOCK] @ head
        picked = jnp.take_along_axis(
            logits, ys[start:start + LOGIT_BLOCK, None], axis=-1)[:, 0]
        out.append(jax.scipy.special.logsumexp(logits, axis=-1) - picked)
    return jnp.concatenate(out).reshape(b, t)


def exit_distribution(a):
    """(p, log p) [T, ...] from the passes' gate logits `a` [T, ...]."""
    passes = a.shape[0]
    log_p, stayed = [], jnp.zeros_like(a[0])  # sum_{j<t} log(1 - lambda(j))
    for t in range(passes):
        if t < passes - 1:
            log_p.append(jax.nn.log_sigmoid(a[t]) + stayed)
            stayed = stayed + jax.nn.log_sigmoid(-a[t])
        else:
            log_p.append(stayed)  # the last pass takes what is left
    log_p = jnp.stack(log_p)
    return jnp.exp(log_p), log_p


def terms(params: Dict[str, Any], batch: Dict[str, Any],
          config: Dict[str, Any]):
    """(loss, {ut_pass_loss [T], exit_p_mean [T], exit_entropy})."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens, targets = batch["tokens"], batch["targets"]
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        head = f32(params["unembed"])
        one_layer = jax.checkpoint(lambda h, w: layer(h, w, config))
        one_head = jax.checkpoint(
            lambda x, head: token_cross_entropy(x, head, targets))
        x = f32(params["embed"])[tokens]
        ce, gate = [], []
        for _ in range(config["loop_steps"]):
            h = x
            for index in range(config["n_layers"]):
                h = one_layer(h, {k: f32(v[index]) for k, v in blocks.items()})
            x = _rmsnorm(h, f32(params["final_norm"]), config["norm_eps"])
            ce.append(one_head(x, head))
            gate.append(x @ f32(params["exit_w"]) + f32(params["exit_b"]))
        ce = jnp.stack(ce)  # [T, B, S]
        p, log_p = exit_distribution(jnp.stack(gate))
        entropy = -(p * log_p).sum(0)  # [B, S]
        loss = ((p * ce).sum(0)
                - config["exit_entropy_coef"] * entropy).mean()
        return loss, {"ut_pass_loss": ce.mean((1, 2)),
                      "exit_p_mean": p.mean((1, 2)),
                      "exit_entropy": entropy.mean()}


def loss(params: Dict[str, Any], batch: Dict[str, Any],
         config: Dict[str, Any]):
    """The looped model's training loss on `batch["tokens"]` and
    `batch["targets"]`."""
    return terms(params, batch, config)[0]
