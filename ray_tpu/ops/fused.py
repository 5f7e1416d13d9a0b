"""Small fused ops: RMSNorm and large-vocab cross entropy.

XLA already fuses most elementwise chains into neighboring matmuls; these
exist for the two spots where explicit control wins: (a) RMSNorm in f32 on
bf16 activations without an f32 round-trip through HBM, (b) cross entropy
that never materializes [B*T, V] probabilities in f32 and multiplies each
chunk's logits once, gradients included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def fused_rmsnorm(x, weight, *, eps: float = 1e-6):
    """RMSNorm with f32 statistics on any-dtype input; output in input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def softmax_cross_entropy(logits, labels, *, ignore_index: int = -100):
    """Token-level CE on [..., V] logits and integer labels.

    Computed as logsumexp - label_logit in f32 without forming probabilities;
    positions equal to ignore_index contribute 0 and are excluded from the
    mean. Returns (mean_loss, valid_token_count).
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    label_safe = jnp.where(labels == ignore_index, 0, labels)
    picked = jnp.take_along_axis(
        lf, label_safe[..., None], axis=-1
    ).squeeze(-1)
    per_tok = lse - picked
    mask = (labels != ignore_index).astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    return (per_tok * mask).sum() / n, n


# tokens a chunk of `lm_head_cross_entropy`: the one name the call and the
# rule of what a rematerialised block keeps (`models/transformer.py`
# `_head_bytes`) take it from
HEAD_CHUNK = 2048


def lm_head_cross_entropy(
    hidden,
    unembed,
    targets,
    *,
    chunk_tokens: int = HEAD_CHUNK,
    ignore_index: int = -100,
    weights=None,
):
    """Fused LM-head + token CE that never materializes [B*T, V] logits.

    `hidden` [B, T, d] (compute dtype) is scanned in token chunks; each chunk
    computes its logits once (one [chunk, d] @ [d, V] matmul) and reduces
    them to logsumexp - label_logit in f32. Under differentiation the same
    pass also forms the chunk's `softmax - onehot` and, from it, the gradient
    to its hidden rows and its f32 share of the gradient to `unembed`
    (`_lm_head_ce_fwd`), so the backward pass multiplies no logits again:
    three [chunk, d] x [d, V]-sized matmuls a chunk, not four. Peak logits
    memory drops from B*T*V*4 bytes (gigabytes at GPT-2 vocab) to
    chunk_tokens*V*4, which is what lets large-vocab models train at large
    batch on one chip. Returns (mean_loss, valid_token_count).

    With `weights` (float32, of `targets`' shape, differentiable) it returns
    (`sum_i weights_i ce_i`, valid_token_count) instead of the mean: the
    first output of `weighted_lm_head_cross_entropy`. Without them the
    traced program is the one it was.
    """
    if weights is None:
        return _lm_head_ce(hidden, unembed, targets, chunk_tokens, ignore_index)
    loss, _ = weighted_lm_head_cross_entropy(
        hidden, unembed, targets, weights, chunk_tokens=chunk_tokens,
        ignore_index=ignore_index)
    return loss, _valid_count(targets, ignore_index)


def weighted_lm_head_cross_entropy(
    hidden,
    unembed,
    targets,
    weights,
    *,
    chunk_tokens: int = HEAD_CHUNK,
    ignore_index: int = -100,
):
    """(`sum_i weights_i ce_i`, `ce` float32 of `targets`' shape): the
    chunked head under a weight a token. `hidden` [..., d] and `targets`,
    `weights` [...] may have any leading shape (several streams over one
    head stacked, their targets tiled: one float32 accumulator for the
    unembedding's gradient however many streams). An ignored target's `ce`
    is 0 whatever its weight.

    Under differentiation the forward folds `weights_i` into `dlogits` as
    `lm_head_cross_entropy` folds `mask / count`, three matmuls a chunk,
    and keeps the chunks' `ce` (four bytes a token) for `d loss / d
    weights_i = ce_i`; no `[tokens, V]` array is held. The second output is
    a reading: no gradient passes through it."""
    return _lm_head_ce_weighted(
        hidden, unembed, targets, weights, chunk_tokens, ignore_index)


def _token_chunks(hidden, targets, chunk_tokens, ignore_index):
    """([chunks, chunk_tokens, d], [chunks, chunk_tokens]); a ragged last
    chunk is padded with ignored positions."""
    d = hidden.shape[-1]
    h = hidden.reshape(-1, d)
    t = targets.reshape(-1)
    pad = (-h.shape[0]) % chunk_tokens
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)], axis=0)
        t = jnp.concatenate(
            [t, jnp.full((pad,), ignore_index, t.dtype)], axis=0
        )
    return h.reshape(-1, chunk_tokens, d), t.reshape(-1, chunk_tokens)


def _own_buffer(x):
    """`x` as an array of its own, not an expression fused into its users.

    Inside the scan a chunk's rows are a dynamic slice of the stacked
    `hidden`, and its `dh` a dynamic update of the stacked result. Left to
    itself XLA fuses the slice into each matmul that reads the rows and the
    update into the matmul that makes `dh`, and the TPU compiler then tiles
    those [chunk, d] x [d, V]-sized matmuls worse: on a v5e the logits and
    dW matmuls ran at 79-80 and 65-70 % of the MXU's peak with the slice
    fused in and at 95-96 and 88-89 % from a buffer (PERF.md section 6,
    PR 28). The copy is chunk_tokens x d elements, a thousandth of the
    matmul's work."""
    return jax.lax.optimization_barrier(x)


@jax.custom_vjp
def _own_cotangent(x):
    """`x` itself, whose cotangent is an array of its own: the half of
    `_own_buffer` that a value's gradient sees, for where the value itself
    is better left to its users' fusions."""
    return x


_own_cotangent.defvjp(lambda x: (x, None), lambda _, g: (_own_buffer(g),))


def _chunk_token_terms(hc, tc, w, ignore_index):
    """One chunk's f32 (logits, logsumexp, one-hot label index, mask,
    every token's loss: 0 at an ignored target)."""
    logits = (hc @ w).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.where(tc == ignore_index, 0, tc)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    mask = (tc != ignore_index).astype(jnp.float32)
    return logits, lse, safe, mask, (lse - picked) * mask


def _chunk_loss_terms(hc, tc, w, ignore_index):
    """One chunk's f32 (logits, logsumexp, one-hot label index, mask,
    summed loss)."""
    *terms, ce = _chunk_token_terms(hc, tc, w, ignore_index)
    return (*terms, ce.sum())


def _chunk_gradients(hc, w, logits, lse, safe, scale):
    """(dh, this chunk's share of dW in float32) from the logits a chunk
    holds: dlogits = (softmax - onehot) * scale() a token, in f32, cast to
    the compute dtype before it feeds the MXU (as autodiff casts it, the
    transpose of the logits' cast to f32). `scale` is called where the
    factor stood when this was written out in `_lm_head_ce_fwd`: the
    traced program is that one, equation for equation."""
    onehot = safe[:, None] == jnp.arange(logits.shape[-1])[None, :]
    dlogits = (
        (jnp.exp(logits - lse[:, None]) - onehot) * scale()[:, None]
    ).astype(hc.dtype)
    dh = jax.lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())))
    dw = jax.lax.dot_general(
        hc, dlogits, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dh, dw


def _valid_count(targets, ignore_index):
    return jnp.maximum(
        (targets != ignore_index).sum().astype(jnp.float32), 1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _lm_head_ce(hidden, unembed, targets, chunk_tokens, ignore_index):
    h, t = _token_chunks(hidden, targets, chunk_tokens, ignore_index)
    w = unembed.astype(hidden.dtype)

    def body(loss_sum, xs):
        hc, tc = xs
        *_, loss = _chunk_loss_terms(_own_buffer(hc), tc, w, ignore_index)
        return loss_sum + loss, None

    loss_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, t))
    count = _valid_count(targets, ignore_index)
    return loss_sum / count, count


def _lm_head_ce_fwd(hidden, unembed, targets, chunk_tokens, ignore_index):
    """The primal's scan, with each chunk's gradients formed from the logits
    it holds: dlogits = (softmax - onehot) * mask / count in f32, cast to
    the compute dtype before it feeds the MXU (as autodiff casts it, the
    transpose of the logits' cast to f32), then dh = dlogits @ w^T as the
    scan's per-chunk output and dW += hc^T @ dlogits, carried in f32."""
    h, t = _token_chunks(hidden, targets, chunk_tokens, ignore_index)
    w = unembed.astype(hidden.dtype)
    count = _valid_count(targets, ignore_index)

    def body(carry, xs):
        loss_sum, dw = carry
        hc, tc = xs
        hc = _own_buffer(hc)  # read by the logits' matmul and by dW's
        logits, lse, safe, mask, loss = _chunk_loss_terms(
            hc, tc, w, ignore_index)
        dh, dw_chunk = _chunk_gradients(
            hc, w, logits, lse, safe, lambda: mask / count)
        dw = dw + dw_chunk
        return (loss_sum + loss, dw), _own_buffer(dh)

    (loss_sum, dw), dh = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros(unembed.shape, jnp.float32)),
        (h, t),
    )
    n = targets.size
    dh = dh.reshape(-1, hidden.shape[-1])[:n].reshape(hidden.shape)
    return (loss_sum / count, count), (dh, dw.astype(unembed.dtype))


def _lm_head_ce_bwd(chunk_tokens, ignore_index, saved, cotangents):
    dh, dw = saved
    g = cotangents[0]  # the count does not depend on hidden or unembed
    return (
        (dh.astype(jnp.float32) * g).astype(dh.dtype),
        (dw.astype(jnp.float32) * g).astype(dw.dtype),
        None,
    )


_lm_head_ce.defvjp(_lm_head_ce_fwd, _lm_head_ce_bwd)


# ------------------------------------------------ the head under weights

def _weight_chunks(weights, chunk_tokens):
    """`weights` as `_token_chunks` lays the tokens out; the padding's
    weights are 0 (its targets are ignored)."""
    w = weights.reshape(-1).astype(jnp.float32)
    pad = (-w.shape[0]) % chunk_tokens
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)], axis=0)
    return w.reshape(-1, chunk_tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _lm_head_ce_weighted(hidden, unembed, targets, weights, chunk_tokens,
                         ignore_index):
    h, t = _token_chunks(hidden, targets, chunk_tokens, ignore_index)
    w = unembed.astype(hidden.dtype)

    def body(loss_sum, xs):
        hc, tc, wc = xs
        *_, ce = _chunk_token_terms(_own_buffer(hc), tc, w, ignore_index)
        return loss_sum + (ce * wc).sum(), ce

    loss_sum, ce = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (h, t, _weight_chunks(weights, chunk_tokens)))
    return loss_sum, ce.reshape(-1)[:targets.size].reshape(targets.shape)


def _lm_head_ce_weighted_fwd(hidden, unembed, targets, weights, chunk_tokens,
                             ignore_index):
    """`_lm_head_ce_fwd` with the token's weight where it has `1 / count`;
    the chunks' `ce` is an output and the residual for the weights'
    gradient."""
    h, t = _token_chunks(hidden, targets, chunk_tokens, ignore_index)
    w = unembed.astype(hidden.dtype)

    def body(carry, xs):
        loss_sum, dw = carry
        hc, tc, wc = xs
        hc = _own_buffer(hc)  # read by the logits' matmul and by dW's
        logits, lse, safe, mask, ce = _chunk_token_terms(
            hc, tc, w, ignore_index)
        dh, dw_chunk = _chunk_gradients(
            hc, w, logits, lse, safe, lambda: mask * wc)
        return (loss_sum + (ce * wc).sum(), dw + dw_chunk), (
            _own_buffer(dh), ce)

    (loss_sum, dw), (dh, ce) = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros(unembed.shape, jnp.float32)),
        (h, t, _weight_chunks(weights, chunk_tokens)),
    )
    n = targets.size
    dh = dh.reshape(-1, hidden.shape[-1])[:n].reshape(hidden.shape)
    ce = ce.reshape(-1)[:n].reshape(targets.shape)
    return (loss_sum, ce), (dh, dw.astype(unembed.dtype), ce, weights)


def _lm_head_ce_weighted_bwd(chunk_tokens, ignore_index, saved, cotangents):
    dh, dw, ce, weights = saved
    g = cotangents[0]  # `ce` is a reading: its cotangent is not taken
    return (
        (dh.astype(jnp.float32) * g).astype(dh.dtype),
        (dw.astype(jnp.float32) * g).astype(dw.dtype),
        None,
        (ce * g).astype(weights.dtype).reshape(weights.shape),
    )


_lm_head_ce_weighted.defvjp(_lm_head_ce_weighted_fwd, _lm_head_ce_weighted_bwd)


# ------------------------------------------- a head of several positions

def multi_head_cross_entropy(hidden, unembed, targets, *,
                             chunk_tokens: int = HEAD_CHUNK,
                             logits_dtype=jnp.float32):
    """The mean over tokens and heads of the cross-entropy of a head that
    predicts several positions a token: `hidden` [B, T, d] (compute dtype),
    `unembed` [d, P V] float32 (head `i`'s columns are `V i .. V (i + 1) -
    1`), `targets` [B, T, P] integers below `V`. A chunk of tokens makes its
    `[chunk, P V]` logits by ONE matmul accumulated in `logits_dtype`
    (float32: `fp32_logits`) and reduces each head's `V` of them to
    logsumexp - label logit; the chunks are a scan under `jax.checkpoint`,
    so no `[B T, P V]` array is held and the unembedding's gradient is
    summed over the chunks in float32. Every target counts: there is no
    ignored index."""
    d = hidden.shape[-1]
    P = targets.shape[-1]
    V = unembed.shape[-1] // P
    h = hidden.reshape(-1, d)
    t = targets.reshape(-1, P)
    n = h.shape[0]
    chunk_tokens = min(chunk_tokens, n)
    if n % chunk_tokens:
        raise ValueError(
            f"{n} tokens are no whole chunks of {chunk_tokens}")

    @jax.checkpoint
    def chunk_loss(hc, tc, w):
        # a checkpointed body is lowered as a function of its own, whose
        # name stacks start anew: the head's scope again, so that a trace
        # finds the chunk's matmuls under it (docs/observability.md)
        with jax.named_scope("lm_head_ce"):
            logits = jnp.dot(
                _own_buffer(hc), w.astype(hc.dtype),
                preferred_element_type=logits_dtype,
            ).astype(jnp.float32).reshape(chunk_tokens, P, V)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, tc[..., None], axis=-1)[..., 0]
            return (lse - picked).sum()

    def body(total, xs):
        return total + chunk_loss(*xs, unembed), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (h.reshape(-1, chunk_tokens, d), t.reshape(-1, chunk_tokens, P)))
    return total / (n * P)
