"""Plain reference of the stack the `evabyte` family trains (EvaByte; its
attention is EVA, arXiv:2302.04542, "Efficient Attention via Control
Variates"): pre-norm blocks with an RMSNorm whose scale is `1 + g`, rotary
positions over a whole head (half-split pairs), EVA attention, a SwiGLU
feed-forward, a final norm and a head that predicts the next
`num_pred_heads` bytes of every position. Everything is float32 at the
highest matmul precision.

EVA attention as the equations have it, with no kernel, no partial softmax,
no lse and no join: the chunks' summaries by a plain reshape to `[T / chunk,
chunk]` (a softmax over the chunk of `phi . k`, its weighted sums of the
chunk's keys, plus `mu`, and of its values), and for every window of
queries ONE masked softmax over one list of keys, `[the window's own tokens
| the summaries of every earlier window's chunks]`: causal over the first,
all of the second. A window at a time, each under `jax.checkpoint`, so that
the loss and its gradients fit a chip beside the parameters; a layer is
under `jax.checkpoint` too.

The loss is the mean over positions `t` and heads `i` of the cross-entropy
of the logits `h_t W[:, 320 i : 320 (i + 1)]` against byte `t + 1 + i`:
eight slices of the head's matrix, eight cross-entropies.

Parameters use the program's layout (`transformer_init`): `blocks` is one
tree of leaves stacked on a leading layer axis, or (a stack that is walked
layer by layer) a list of segments, each a list with one tree per layer of
its period, stacked over its periods; `embed` [vocab, d], `unembed`
[d, heads x vocab], the norms' `g` (the scale is 1 + g).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    # x: [B, T, H, Dh]; rotate the two halves of each head by position angles
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layers(params: Dict[str, Any]):
    """Every layer's weights, first to last, as float32."""
    blocks = params["blocks"]
    for segment in [[blocks]] if isinstance(blocks, dict) else blocks:
        periods = jax.tree.leaves(segment[0])[0].shape[0]
        for period in range(periods):
            for tree in segment:
                yield {k: jnp.asarray(v[period], jnp.float32)
                       for k, v in tree.items()}


def summaries(k, v, phi, mu, chunk: int):
    """(kS, vS [B, T / chunk, H, D], the chunks' weights w [B, T / chunk,
    chunk, H]) of k, v [B, T, H, D] under phi, mu [H, D]."""
    b, t, h, d = k.shape
    kc = k.reshape(b, t // chunk, chunk, h, d)
    vc = v.reshape(b, t // chunk, chunk, h, d)
    w = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi), axis=2)
    return (jnp.einsum("bnch,bnchd->bnhd", w, kc) + mu,
            jnp.einsum("bnch,bnchd->bnhd", w, vc), w)


def attention(q, k, v, phi, mu, window: int, chunk: int):
    """(o [B, T, H, D]; the share of every query's softmax that lies on the
    summaries [B, T, H]; the chunks' weights) of rotated q, k and v
    [B, T, H, D]: the module's docstring."""
    b, t, h, d = q.shape
    if t <= window:
        window = t
    ks, vs, w = summaries(k, v, phi, mu, chunk)
    per = window // chunk
    causal = jnp.tril(jnp.ones((window, window), bool))

    @jax.checkpoint
    def one_window(q_w, keys, values):
        seen = jnp.concatenate(
            [causal, jnp.ones((window, keys.shape[1] - window), bool)], axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_w, keys) / jnp.sqrt(float(d))
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", p, values),
                p[..., window:].sum(-1).transpose(0, 2, 1))

    outs, remote = [], []
    for n in range(t // window):
        own = slice(n * window, (n + 1) * window)
        o, r = one_window(
            q[:, own],
            jnp.concatenate([k[:, own], ks[:, :per * n]], axis=1),
            jnp.concatenate([v[:, own], vs[:, :per * n]], axis=1))
        outs.append(o)
        remote.append(r)
    return jnp.concatenate(outs, axis=1), jnp.concatenate(remote, axis=1), w


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any]):
    """(the loss, {eva_remote_mass, eva_chunk_entropy}: [layers] each) of
    `batch["tokens"]` [B, T] and `batch["targets"]` [B, T, heads]."""
    d, h = config["d_model"], config["n_heads"]
    dh = d // h
    eps, theta = config["norm_eps"], config["rope_theta"]
    window, chunk = config["eva_window"], config["eva_chunk"]
    heads, vocab = config["n_pred_heads"], config["vocab_size"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape

    @jax.checkpoint
    def layer(x, w):
        y = _rmsnorm(x, w["attn_norm"], eps)
        q = _rope((y @ w["wq"]).reshape(b, t, h, dh), theta)
        k = _rope((y @ w["wk"]).reshape(b, t, h, dh), theta)
        v = (y @ w["wv"]).reshape(b, t, h, dh)
        o, remote, weights = attention(
            q, k, v, w["eva_phi"], w["eva_mu"], window, chunk)
        x = x + o.reshape(b, t, d) @ w["wo"]
        y = _rmsnorm(x, w["mlp_norm"], eps)
        x = x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]
        entropy = -(weights * jnp.log(weights)).sum(axis=2).mean()
        return x, (remote.mean(), entropy)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        readings = []
        for w in _layers(params):
            x, made = layer(x, w)
            readings.append(made)
        x = _rmsnorm(x, f32(params["final_norm"]), eps)
        head = f32(params["unembed"])
        losses = []
        for i in range(heads):  # head i predicts byte t + 1 + i
            logp = jax.nn.log_softmax(
                x @ head[:, vocab * i:vocab * (i + 1)], axis=-1)
            losses.append(-jnp.take_along_axis(
                logp, targets[..., i, None], axis=-1).mean())
        remote, entropy = (jnp.stack(r) for r in zip(*readings))
        return sum(losses) / heads, {
            "eva_remote_mass": jax.lax.stop_gradient(remote),
            "eva_chunk_entropy": jax.lax.stop_gradient(entropy)}


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any]):
    """The mean of the eight heads' cross-entropies."""
    return forward(params, batch, config)[0]
