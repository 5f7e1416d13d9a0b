"""The selective scan of a Mamba-1 mixer (arXiv:2312.00752).

For every channel `c` of `inner` and every one of its `N` states, with a
decay of its own a channel and state:

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_(t-1)[c, n] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] u_t[c]        h before the first token: 0

`B_t` and `C_t` are one vector of `N` a token, shared by the channels. The
decay differs by channel *and* state, so a chunk of tokens is no matmul as
Mamba-2's is (`ops/ssd.py`: one scalar decay a head): the work is `inner N`
multiply-adds and as many `exp` a token on the vector unit, and `T`
dependent steps.

`selective_scan` computes it in float32 (`dt`, `A`, every `exp`, the state
and `y`; `u`, `B` and `C` are read in the dtype they come in and widened a
token at a time) by one of two paths:

- **`"chunked"`**, what a model runs, behind a `custom_vjp`. The sequence is
  cut into chunks of `chunk` tokens (padded with steps of `dt = 0`, which
  leave the state alone). The forward walks the chunks in order, the tokens
  of a chunk in order inside, and keeps each chunk's *entering* state,
  `[T / chunk, b, N, inner]` float32: the one residual beside the inputs.
  The backward walks the chunks from the last to the first with the state's
  cotangent in hand, makes a chunk's steps again from its entering state
  (`jax.vjp` of the chunk: autodiff's residuals are a chunk's `[chunk, b,
  N, inner]`, never the sequence's) and adds `dA` into one accumulator. No
  `[T, inner, N]` array is ever made. The entering states carry the
  `checkpoint_name` `scan_out`, as the model's cast of `y` does: a
  rematerialised block that keeps the name runs no second forward scan. The state lies `[b, N, inner]`: the
  channels along the lanes.
- **`"tokens"`**, the recurrence token by token under one `lax.scan` with
  autodiff through it: the tests' reference. Its gradient keeps every
  token's state.

The scan's state is not reset inside a sequence: rows are whole documents
(ROADMAP Queue 2: a reset at a document's boundary, with R6b and R12).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_F32 = jnp.float32
# tokens a trip of the inner loop: a trip's fusions are launched once for
# this many steps
_UNROLL = 8


def _step(A):
    """One token's step on the state `h` [b, N, inner], `A` [N, inner], in
    the state's dtype (float32: `_F32` where the state is made)."""
    def step(h, token):
        u_t, dt_t, B_t, C_t = (x.astype(h.dtype) for x in token)
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + (dt_t * u_t)[:, None, :] * B_t[:, :, None])
        return h, jnp.sum(h * C_t[:, :, None], axis=1)

    return step


def _chunk(h, tokens, A):
    """(the state after, y [Q, b, inner]) of a chunk's tokens `(u, dt, B,
    C)`, each `[Q, b, ...]`, from the state `h` before it."""
    return jax.lax.scan(_step(A), h, tokens, unroll=_UNROLL)


@jax.custom_vjp
def _chunked(tokens, A):
    return _chunked_fwd(tokens, A)[0]


def _chunked_fwd(tokens, A):
    """`tokens` `(u, dt, B, C)` as `[n, Q, b, ...]`, `A` [N, inner]:
    ((y [n, Q, b, inner], the last state), the residuals)."""
    u = tokens[0]
    h0 = jnp.zeros((u.shape[2], A.shape[0], A.shape[1]), _F32)

    def body(h, chunk):
        after, y = _chunk(h, chunk, A)
        return after, (h, y)

    with jax.named_scope("selective_scan"):
        last, (entering, y) = jax.lax.scan(body, h0, tokens)
    # kept with the scan's output where a rematerialised block keeps that
    # (`models/transformer.py` `_SAVE_ORDER`): no second forward scan
    entering = checkpoint_name(entering, "scan_out")
    return (y, last), (tokens, A, entering)


def _chunked_bwd(residuals, cotangents):
    tokens, A, entering = residuals
    dy, dlast = cotangents

    def body(carry, chunk):
        dh, dA = carry
        h, of_chunk, dy_c = chunk
        _, pull = jax.vjp(_chunk, h, of_chunk, A)
        dh, dtokens, dA_c = pull((dh, dy_c))
        return (dh, dA + dA_c), dtokens

    with jax.named_scope("selective_scan"):
        (_, dA), dtokens = jax.lax.scan(
            body, (dlast, jnp.zeros_like(A)), (entering, tokens, dy),
            reverse=True)
    return dtokens, dA


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _by_chunk(x, chunk: int):
    """`x` [b, T, w] as `[n, chunk, b, w]`, `T` padded with zeros to whole
    chunks."""
    b, T, w = x.shape
    n = -(-T // chunk)
    x = jnp.pad(x, ((0, 0), (0, n * chunk - T), (0, 0)))
    return x.reshape(b, n, chunk, w).transpose(1, 2, 0, 3)


def selective_scan(u, delta, A, B, C, D, *, chunk: int = 128,
                   impl: str = "chunked") -> Tuple[jax.Array, jax.Array]:
    """(y [b, T, inner] float32, the state after the last token [b, inner,
    N] float32) of `u` [b, T, inner], `delta` [b, T, inner] (the step size,
    after its softplus), `A` [inner, N] (negative), `B` and `C` [b, T, N]
    and the skip `D` [inner]. `impl`: "chunked" or "tokens" (above)."""
    b, T, inner = u.shape
    A_t = A.astype(_F32).T  # [N, inner]: the channels along the lanes
    if impl == "tokens":
        by_token = tuple(x.swapaxes(0, 1) for x in (u, delta, B, C))
        last, y = jax.lax.scan(
            _step(A_t), jnp.zeros((b, A_t.shape[0], inner), _F32), by_token)
        y = y.swapaxes(0, 1)
    elif impl == "chunked":
        tokens = tuple(_by_chunk(x, chunk) for x in (u, delta, B, C))
        y, last = _chunked(tokens, A_t)
        y = y.transpose(2, 0, 1, 3).reshape(b, -1, inner)[:, :T]
    else:
        raise ValueError(f"impl {impl!r}: 'chunked' or 'tokens'")
    return y + D.astype(_F32) * u.astype(_F32), last.swapaxes(1, 2)
