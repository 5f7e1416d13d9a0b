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


def lm_head_cross_entropy(
    hidden,
    unembed,
    targets,
    *,
    chunk_tokens: int = 2048,
    ignore_index: int = -100,
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
    """
    return _lm_head_ce(hidden, unembed, targets, chunk_tokens, ignore_index)


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


def _chunk_loss_terms(hc, tc, w, ignore_index):
    """One chunk's f32 (logits, logsumexp, one-hot label index, mask,
    summed loss)."""
    logits = (hc @ w).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.where(tc == ignore_index, 0, tc)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    mask = (tc != ignore_index).astype(jnp.float32)
    return logits, lse, safe, mask, ((lse - picked) * mask).sum()


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
        onehot = safe[:, None] == jnp.arange(logits.shape[-1])[None, :]
        dlogits = (
            (jnp.exp(logits - lse[:, None]) - onehot)
            * (mask / count)[:, None]
        ).astype(hc.dtype)
        dh = jax.lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())))
        dw = dw + jax.lax.dot_general(
            hc, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
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
