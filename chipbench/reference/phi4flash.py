"""Plain reference of the decoder the `phi4flash` family trains:
Phi-4-mini-flash-reasoning (`model_type` `phi4flash`; the architecture is
SambaY, arXiv:2507.06607), one pipeline stage's share of it. With `LN(x)` a
LayerNorm with a scale and a bias, every layer is

    x <- x + mixer(LN1(x)),        x <- x + W_down (silu(g) * u),  [g | u] = LN2(x) [W_gate | W_up]

and the layer's name in `layer_types` says which mixer, on `y = LN1(x)`:

- `mamba1` (arXiv:2312.00752). `[u | z] = y W_in`; `u <- silu(conv(u) +
  b)`, causal, a channel, `mamba1_conv_taps` taps, zero before the
  sequence; `[r | B | C] = u W_x`; `dt = softplus(r W_dt + dt_bias)`;
  `A = -exp(A_log)` `[inner, N]`. A channel's state is `[N]`, zero before
  the first token:

      h_t = exp(dt_t A) * h_(t-1) + (dt_t u_t) B_t^T,    s_t = h_t C_t + D u_t

  taken token by token, a `lax.scan` step each; out `(s * silu(z)) W_out`.
  `mamba1_emit` is the same layer, and `s` (before the gate by `z`, with
  the skip `D u`) is the *memory* `m` of the layers after it.
- `sliding_diff_attention`, `diff_attention`, `diff_attention_emit`:
  differential attention (arXiv:2410.05258). `q`, `k`, `v` each with a
  bias, `n_heads` query and `n_kv_heads` key and value heads `d_head` wide,
  no rotary embedding. Heads pair, even with odd: `q1_i = q_(2i)`, `q2_i =
  q_(2i+1)`; `k1_j = k_(2j)`, `k2_j = k_(2j+1)`, `V_j = [v_(2j) |
  v_(2j+1)]`; query pair `i` reads key pair `j = i // (query pairs a key
  pair)`. `a1 = softmax(q1 k1^T / sqrt(d_head)) V`, `a2` from `q2`, `k2`,
  both causal and, under the window, over the token and the
  `sliding_window - 1` before it. `lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam0`, `lam0 = 0.8 - 0.6 exp(-0.3 l)` with `l` the layer's published
  index (`layer_depths`). `o_i = (1 - lam0) RMS(a1_i - lam a2_i)` over the
  pair's `2 d_head` with one learned scale; the pairs side by side, `W_o`
  with a bias. `diff_attention_emit` also hands its `k` and `v` on.
- `gmu`, the gated memory unit: `(silu(y W_in) * m) W_out`.
- `cross_diff_attention`: `q = y W_q + b`; `k` and `v` are the emitting
  layer's (`cross_keys_and_values`); the whole causal context; its own
  lambdas, norm and `W_o`.

One more LayerNorm after the last layer, the head is the embedding
transposed, the loss the mean next-token cross-entropy. Everything is
float32 at the highest matmul precision: no chunked scan, no kernel, no
paired-head layout (the heads are repeated and every pair is its own
softmax).

Departures from the published model, written down as the contract asks:
- The stage's share: the layers `layer_depths` names of 32, the first
  `vocab_size` token ids of 200,064 (a sliced vocabulary is a smaller
  vocabulary: the loss is over the slice).
- The scan over the tokens is cut into blocks of 128 steps and attention's
  queries into blocks of 1,024, each under `jax.checkpoint`, and so is every
  layer: the same operations in the same order, but the gradient keeps a
  state a block and one block's steps, not every token's `[inner, N]` state
  (0.33 MB a token and row at published widths) nor every pair's
  probability.
- No dropout, no padding mask: sequences are whole.

Parameters use the program's layout (`transformer_init` of a stack of unlike
layers): `blocks` is a list of segments, each a list with one tree per layer
of its period, every leaf stacked over the segment's periods. The layers'
kinds are read from the configuration, not from the tree.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

_SCAN_BLOCK = 128   # steps of the token scan under one `jax.checkpoint`
_QUERY_BLOCK = 1024  # queries of one masked softmax


def _layers(params: Dict[str, Any]):
    """Every layer's weights, first to last, as float32."""
    for segment in params["blocks"]:
        periods = jax.tree.leaves(segment[0])[0].shape[0]
        for period in range(periods):
            for tree in segment:
                yield {k: jnp.asarray(v[period], jnp.float32)
                       for k, v in tree.items()}


def layer_norm(x, w, name: str, eps: float):
    centred = x - x.mean(axis=-1, keepdims=True)
    normed = centred / jnp.sqrt((centred ** 2).mean(axis=-1, keepdims=True)
                                + eps)
    return normed * w[name] + w[name + "_bias"]


def recurrence(u, dt, A, B, C, D):
    """`s` [b, T, inner] of u and dt [b, T, inner], A [inner, N], B and C
    [b, T, N] and D [inner], token by token."""
    b, T, inner = u.shape

    def step(h, token):
        u_t, dt_t, B_t, C_t = token
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * u_t)[..., None] * B_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, C_t) + D * u_t

    @jax.checkpoint
    def block(h, tokens):
        return jax.lax.scan(step, h, tokens)

    size = _SCAN_BLOCK if T % _SCAN_BLOCK == 0 else T
    by_token = tuple(
        v.swapaxes(0, 1).reshape(T // size, size, b, v.shape[-1])
        for v in (u, dt, B, C))
    _, s = jax.lax.scan(
        block, jnp.zeros((b, inner, A.shape[-1]), jnp.float32), by_token)
    return s.reshape(T, b, inner).swapaxes(0, 1)


def mamba1(y, w, config: Dict[str, Any]):
    """(Mamba1(y), the scan's output `s`, `s` gated by `z`) of one layer on
    the normed `y`."""
    N = config["mamba1_state"]
    t = y.shape[1]
    taps = w["conv_w"].shape[0]
    u, z = jnp.split(y @ w["w_in"], 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][i] * padded[:, i:i + t] for i in range(taps))
    u = jax.nn.silu(conv + w["conv_b"])
    rank = w["w_dt"].shape[0]
    r, B, C = jnp.split(u @ w["w_x"], (rank, rank + N), axis=-1)
    dt = jax.nn.softplus(r @ w["w_dt"] + w["dt_bias"])
    s = recurrence(u, dt, -jnp.exp(w["A_log"]), B, C, w["D"])
    gated = s * jax.nn.silu(z)
    return gated @ w["w_out"], s, gated


def memory_of(s, gated):
    """What the emitting Mamba layer hands the gated memory units: the
    scan's output, not the one gated by `z`. Under a name of its own: a test
    hands on the other to show what the comparison reads then."""
    return s


def softmaxes(q, k, v, window):
    """Causal `softmax(q k^T / sqrt(width)) v` a head, q and k [b, t, H,
    dk], v [b, t, H, dv]; under `window` a query sees itself and the
    `window - 1` keys before it. A block of queries at a time."""
    t, dk = q.shape[1], q.shape[-1]
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(q_blk, first):
        rows = first + jnp.arange(q_blk.shape[1])
        seen = keys[None, :] <= rows[:, None]
        if window:
            seen = seen & (keys[None, :] > rows[:, None] - window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(float(dk))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    size = min(_QUERY_BLOCK, t)
    return jnp.concatenate(
        [block(q[:, first:first + size], first)
         for first in range(0, t, size)], axis=1)


def keys_and_values(y, w, config: Dict[str, Any]):
    """k and v [b, t, kv heads, d_head] of the normed input `y`."""
    b, t, _ = y.shape
    hk, dh = config["n_kv_heads"], config["d_head"]
    return ((y @ w["wk"] + w["bk"]).reshape(b, t, hk, dh),
            (y @ w["wv"] + w["bv"]).reshape(b, t, hk, dh))


def cross_keys_and_values(emitted, y, config: Dict[str, Any]):
    """What a cross layer attends over: the emitting layer's keys and
    values, not ones made from its own input `y` by the emitter's weights
    (`emitted["w"]`). Under a name of its own: a test makes the other to
    show what the comparison reads then."""
    return emitted["k"], emitted["v"]


def diff_lambda(w, depth: int):
    """(lam, lam0) of a layer at published index `depth`."""
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    return (jnp.exp(jnp.sum(w["lam_q1"] * w["lam_k1"]))
            - jnp.exp(jnp.sum(w["lam_q2"] * w["lam_k2"])) + lam0), lam0


def diff_attention(y, w, config: Dict[str, Any], depth: int, k, v, window):
    """DiffAttn(y) over the keys `k` and values `v` [b, t, kv heads,
    d_head]."""
    b, t, _ = y.shape
    h, hk, dh = config["n_heads"], config["n_kv_heads"], config["d_head"]
    q = (y @ w["wq"] + w["bq"]).reshape(b, t, h, dh)
    group = (h // 2) // (hk // 2)  # query pairs a key pair
    pairs = v.reshape(b, t, hk // 2, 2 * dh)
    a1, a2 = (softmaxes(q[:, :, i::2], jnp.repeat(k[:, :, i::2], group, 2),
                        jnp.repeat(pairs, group, 2), window)
              for i in (0, 1))
    lam, lam0 = diff_lambda(w, depth)
    apart = a1 - lam * a2
    normed = apart / jnp.sqrt((apart ** 2).mean(-1, keepdims=True)
                              + config["norm_eps"]) * w["diff_norm"]
    return ((1.0 - lam0) * normed).reshape(b, t, h * dh) @ w["wo"] + w["bo"]


def feed_forward(x, w, config: Dict[str, Any]):
    y = layer_norm(x, w, "mlp_norm", config["norm_eps"])
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


def layer(x, w, emitted, config: Dict[str, Any], kind: str, depth: int):
    """(the stream after one layer of `kind`, what it emits: {} or the
    memory or the keys and values)."""
    eps, made = config["norm_eps"], {}
    if kind in ("mamba1", "mamba1_emit"):
        y = layer_norm(x, w, "mixer_norm", eps)
        out, s, gated = mamba1(y, w, config)
        if kind == "mamba1_emit":
            made = {"memory": memory_of(s, gated)}
    elif kind == "gmu":
        y = layer_norm(x, w, "gmu_norm", eps)
        out = (jax.nn.silu(y @ w["gmu_in"]) * emitted["memory"]) @ w["gmu_out"]
    else:
        y = layer_norm(x, w, "attn_norm", eps)
        if kind == "cross_diff_attention":
            k, v = cross_keys_and_values(emitted, y, config)
        else:
            k, v = keys_and_values(y, w, config)
        window = (config["sliding_window"]
                  if kind == "sliding_diff_attention" else None)
        out = diff_attention(y, w, config, depth, k, v, window)
        if kind == "diff_attention_emit":
            made = {"k": k, "v": v, "w": w}
    return feed_forward(x + out, w, config), made


def forward(params: Dict[str, Any], batch: Dict[str, Any],
            config: Dict[str, Any]):
    """(the loss, every differential layer's `lam`)."""
    tokens, targets = batch["tokens"], batch["targets"]
    emitted, lams = {}, []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        for kind, depth, w in zip(config["layer_types"],
                                  config["layer_depths"], _layers(params)):
            x, made = jax.checkpoint(
                lambda x, w, emitted, kind=kind, depth=depth: layer(
                    x, w, emitted, config, kind, depth))(x, w, emitted)
            emitted = {**emitted, **made}
            if "attention" in kind:
                lams.append(diff_lambda(w, depth)[0])
        final = {k: jnp.asarray(params[k], jnp.float32)
                 for k in ("final_norm", "final_norm_bias")}
        x = layer_norm(x, final, "final_norm", config["norm_eps"])
        logp = jax.nn.log_softmax(
            x @ jnp.asarray(params["embed"], jnp.float32).T, axis=-1)
        ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    return ce, jnp.stack(lams)


def loss(params: Dict[str, Any], batch: Dict[str, Any],
         config: Dict[str, Any]):
    """Mean cross-entropy of `batch["targets"]` given `batch["tokens"]`."""
    return forward(params, batch, config)[0]
