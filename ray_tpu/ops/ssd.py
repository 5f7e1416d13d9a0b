"""The selective state-space scan of a Mamba-2 mixer, in its chunked form.

For every head (`P` channels, a state of `[P, N]`), with `a_t = dt_t A`:

    h_t = exp(a_t) h_(t-1) + dt_t x_t B_t^T        h before the first token: 0
    y_t = h_t C_t + D x_t

`B` and `C` come in `G` groups; head `h` reads group `h // (H / G)`. Taken
token by token that is `T` dependent steps. The state-space dual
(arXiv:2405.21060) cuts the sequence into chunks of `Q` tokens and makes
each part a matmul:

- `ssd_chunk`: within a chunk `y_i += sum_(j <= i) (C_i . B_j) exp(cum_i -
  cum_j) dt_j x_j`, `cum` the running sum of `a` inside the chunk: the
  scores `C B^T` of a chunk under the mask of decays `L`, times the inputs.
- `ssd_state`: what a chunk adds to the state by its end, `S_c = sum_j
  exp(cum_Q - cum_j) dt_j x_j B_j^T`, and the `T / Q` steps `h_c =
  exp(cum_Q) h_(c-1) + S_c` over the chunks.
- `ssd_out`: what the state a chunk starts from adds to its tokens,
  `y_i += exp(cum_i) h_(c-1) C_i`.

Operands reach the matmuls in `x`'s dtype (bf16 in training) and accumulate
in float32; `a`, its running sums, every `exp`, the masks and the
recurrence over the chunks' states are float32. The result does not depend
on `Q` beyond rounding. Plain `jax.numpy` under named scopes, and autodiff
through it: there is no kernel here yet (PERF.md section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _heads_of_groups(grouped, heads: int):
    """[..., G, Q, Q'] repeated to the heads that share each group."""
    groups = grouped.shape[-3]
    return grouped if groups == heads else jnp.repeat(
        grouped, heads // groups, axis=-3)


def ssd(x, dt, A, B, C, D, *, chunk: int = 128):
    """`y` [b, T, H, P] of inputs `x` [b, T, H, P], step sizes `dt`
    [b, T, H] (positive: after the softplus), decay rates `A` [H]
    (negative), `B` and `C` [b, T, G, N] with `G` dividing `H`, and the
    skip `D` [H], by the recurrence above in chunks of `chunk` tokens. A `T`
    that is no multiple of the chunk is padded with steps of `dt = 0`,
    which leave the state as it is. `y` is in x's dtype."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    if H % G:
        raise ValueError(f"{G} groups of B and C do not divide {H} heads")
    Q = min(chunk, T)
    pad = (-T) % Q
    dtype = x.dtype
    dt = dt.astype(_F32)
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, B, C))
    n = (T + pad) // Q
    a = (dt * A.astype(_F32)).reshape(b, n, Q, H)
    cum = jnp.cumsum(a, axis=2)                            # [b, n, Q, H]
    # dt rides with the inputs: both parts below take `dt_j x_j`
    xdt = (x.astype(_F32) * dt[..., None]).astype(dtype).reshape(b, n, Q, H, P)
    Bc, Cc = B.reshape(b, n, Q, G, N), C.reshape(b, n, Q, G, N)

    with jax.named_scope("ssd_chunk"):
        scores = jnp.einsum("bniGN,bnjGN->bnGij", Cc, Bc,
                            preferred_element_type=_F32)
        by_head = cum.transpose(0, 1, 3, 2)                # [b, n, H, Q]
        span = by_head[..., :, None] - by_head[..., None, :]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        # masked before the exp: above the diagonal the sum is positive
        decay = jnp.exp(jnp.where(causal, span, -jnp.inf))  # L [b, n, H, i, j]
        mixed = (_heads_of_groups(scores, H) * decay).astype(dtype)
        y = jnp.einsum("bnHij,bnjHP->bniHP", mixed, xdt,
                       preferred_element_type=_F32)

    with jax.named_scope("ssd_state"):
        last = cum[:, :, -1]                               # [b, n, H]
        to_end = jnp.exp(last[:, :, None] - cum)           # [b, n, Q, H]
        decayed = (xdt.astype(_F32) * to_end[..., None]).astype(dtype)
        # head H = (G, R): the heads of a group share its B
        added = jnp.einsum(
            "bnjGRP,bnjGN->bnGRPN", decayed.reshape(b, n, Q, G, H // G, P), Bc,
            preferred_element_type=_F32).reshape(b, n, H, P, N)

        def over_chunks(h, step):
            keep, add = step
            return keep[..., None, None] * h + add, h     # emits h_(c-1)

        _, entering = jax.lax.scan(
            over_chunks, jnp.zeros((b, H, P, N), _F32),
            (jnp.exp(last).swapaxes(0, 1), added.swapaxes(0, 1)))
        entering = entering.swapaxes(0, 1)                 # [b, n, H, P, N]

    with jax.named_scope("ssd_out"):
        carried = jnp.einsum(
            "bniGN,bnGRPN->bniGRP", Cc,
            entering.astype(dtype).reshape(b, n, G, H // G, P, N),
            preferred_element_type=_F32).reshape(b, n, Q, H, P)
        y = y + carried * jnp.exp(cum)[..., None]
        y = y.reshape(b, n * Q, H, P)[:, :T]
        x = x[:, :T]
        return (y + x.astype(_F32) * D.astype(_F32)[:, None]).astype(dtype)
