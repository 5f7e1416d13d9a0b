"""Plain reference of the decoder the `transformer` family trains: pre-norm
blocks with RMSNorm, rotary positions (half-split rotation, as in the
published Mistral code), grouped-query causal attention, a SwiGLU
feed-forward, a final RMSNorm and an output head; the loss is the mean
next-token cross-entropy. Everything is float32 at the highest matmul
precision; attention is the full softmax(QK^T/sqrt(d))V with a causal mask.

Departures from the published model, none of which changes the arithmetic
at the benchmark's shapes: sequences are at most the model's sliding window
(4096), where the window masks nothing; there is no dropout and no bias.

Parameters use the program's layout (`transformer_init`): block weights
stacked on a leading layer axis, `embed` [vocab, d], `unembed` [d, vocab]
when the embeddings are untied.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


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


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any]):
    """Mean cross-entropy of `batch["targets"]` given `batch["tokens"]`."""
    d, h = config["d_model"], config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    eps, theta = config["norm_eps"], config["rope_theta"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        blocks = params["blocks"]
        mask = jnp.tril(jnp.ones((t, t), bool))
        for layer in range(config["n_layers"]):
            w = {k: f32(v[layer]) for k, v in blocks.items()}
            y = _rmsnorm(x, w["attn_norm"], eps)
            q = _rope((y @ w["wq"]).reshape(b, t, h, dh), theta)
            k = _rope((y @ w["wk"]).reshape(b, t, hk, dh), theta)
            v = (y @ w["wv"]).reshape(b, t, hk, dh)
            k = jnp.repeat(k, h // hk, axis=2)
            v = jnp.repeat(v, h // hk, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + attn.reshape(b, t, h * dh) @ w["wo"]
            y = _rmsnorm(x, w["mlp_norm"], eps)
            x = x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]
        x = _rmsnorm(x, f32(params["final_norm"]), eps)
        head = (f32(params["unembed"]) if "unembed" in params
                else f32(params["embed"]).T)
        logp = jax.nn.log_softmax(x @ head, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -picked.mean()
