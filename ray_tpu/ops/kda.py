"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): a linear
attention whose state is a matrix updated by a delta rule under a decay a
channel of the key, in its chunked form and step by step.

For every head (keys `dk` wide, values `dv` wide, a state `S` of `[dk, dv]`),
with `g_t <= 0` the log decay of every key channel and `beta_t` in (0, 2):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = scale S_t^T q_t                     S before the first token: 0

A gated delta rule decays the whole state by one scalar a head and token
(`ops/ssd.py`'s `exp(dt A)` is such a scalar); here every row of `S`, a
channel of the key, has a decay of its own. With `beta > 1` the factor
`I - beta k k^T` has the eigenvalue `1 - beta < 0` along `k`
(`kda_allow_neg_eigval`).

Taken token by token that is `T` dependent steps (`kda_recurrent`, which the
tests and the benchmark's plain reference use). The chunked form (`kda`)
cuts the sequence into chunks of `C` tokens. Inside a chunk let `G_i` be the
running sum of `g` up to and with token `i` (so `0 >= G_1 >= ... >= G_C`, a
vector of `dk`), and write the state after token `i` as the chunk's entering
state decayed plus one rank-one term a token,

    S_i = Diag(e^{G_i}) S_0 + sum_(j <= i) Diag(e^{G_i - G_j}) k_j u_j^T

Putting that into the recurrence gives the `u` by a unit lower triangular
system: with `A = strict_lower[beta_i (k_i e^{G_i}) . (k_j e^{-G_j})]`,

    (I + A) U' = Diag(beta) (V - (K e^G) S_0)   =>   U' = U - W S_0
    T = (I + A)^-1 Diag(beta),   W = T (K e^G),   U = T V

    o_i   = scale [(q_i e^{G_i}) S_0 + sum_(j <= i) (q_i e^{G_i}) . (k_j e^{-G_j}) u'_j]
    S_C   = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T (U - W S_0)

so a chunk is a handful of matmuls and the `T / C` chunk states follow one
another in a `lax.scan`.

**No exponent is ever positive.** A decay a channel makes `e^{-G_j}`
overflow where a scalar decay does not (at `g = -1.6` a token, `-G` passes
float32's 88 inside one chunk of 64), so the pair products are never formed
from `e^{G_i}` and `e^{-G_j}` apart. The chunk is cut into sub-chunks of
`SUB` tokens. For `i` and `j` in different sub-chunks the exponent is split
at the boundary `r` before `i`'s sub-chunk, `(G_i - G_r) + (G_r - G_j)`:
both parts are sums of `g` over tokens between `j` and `i`, so both are
`<= 0`, and the pair products are one matmul of `q e^{G_i - G_r}` against a
copy of the chunk's keys scaled by `e^{G_r - G_j}` for each sub-chunk. For
`i` and `j` in one sub-chunk the difference `G_i - G_j` is formed for every
pair and channel (`[SUB, SUB, dk]`) and summed on the vector unit. Every
other factor (`e^G`, `e^{G_C - G}`, `e^{G_C}`) decays from a point before
to a point after. What underflows is a contribution below float32's least
value; nothing overflows and no guard clamps `g`. `log_decay_min`, the most
negative `G_C` of the call, says how far the decays reach
(`kda_log_decay_min` among the step's readings).

Dtypes: `g`, its running sums, every `exp`, `beta`, the triangular solve and
the chunk states are float32. The matmuls take their operands in `v`'s dtype
(bf16 in training; a float32 factor is multiplied in float32 and rounded
once) and accumulate in float32. The result does not depend on `C` or `SUB`
beyond rounding.

This is `jax.numpy` alone, with autodiff through it, under the scopes
`kda_chunk` (the pair products, the solve, `W` and `U`), `kda_state` (the
scan over the chunks' states) and `kda_out`. No Pallas kernel yet: the
`[.., SUB, SUB, dk]` pair tensor and the scaled copies of the keys cross
HBM, which a kernel would keep in VMEM (ROADMAP Queue 2).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_F32 = jnp.float32
SUB = 16  # tokens a sub-chunk: the pair tensor is [SUB, SUB, dk] a sub-chunk


def _mm(spec: str, a, b, dtype):
    """An einsum of operands rounded to `dtype`, accumulated in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32)


def _pair_products(rows, keys, G, dtype):
    """`P[i, j] = sum_c rows[i, c] keys[j, c] exp(G[i, c] - G[j, c])` for
    `j <= i` inside each chunk, 0 above the diagonal: [b, n, H, C, C]
    float32 of `rows`, `keys` and the running log decays `G`
    [b, n, C, H, dk]. Every exponent is a sum of `g` over tokens between
    `j` and `i` (the module's text)."""
    b, n, C, H, dk = keys.shape
    sub, m = SUB, C // SUB
    shape = (b, n, m, sub, H, dk)
    Gs = G.reshape(shape)
    rows32 = rows.astype(_F32).reshape(shape)
    keys32 = keys.astype(_F32)
    # the boundary before each sub-chunk: the chunk's start, then the last
    # token of the sub-chunk before
    ref = jnp.concatenate(
        [jnp.zeros_like(Gs[:, :, :1, -1]), Gs[:, :, :-1, -1]], axis=2)
    # i and j in different sub-chunks: one matmul a sub-chunk of rows
    # against the chunk's keys scaled to its boundary (the keys at and
    # after the boundary are masked below; their exponent is held at 0)
    to_rows = jnp.exp(Gs - ref[:, :, :, None])
    to_keys = jnp.exp(jnp.minimum(ref[:, :, :, None] - G[:, :, None], 0.0))
    across = _mm("bnaihc,bnajhc->bnhaij", rows32 * to_rows,
                 keys32[:, :, None] * to_keys, dtype)       # [b,n,H,m,sub,C]
    before = (jnp.arange(C)[None, None, :]
              < (jnp.arange(m) * sub)[:, None, None])        # [m, 1, C]
    across = jnp.where(before, across, 0.0).reshape(b, n, H, C, C)
    # i and j in one sub-chunk: the difference itself, pair by pair
    within = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    diff = Gs[:, :, :, :, None] - Gs[:, :, :, None, :]       # [.., i, j, H, dk]
    decay = jnp.exp(jnp.where(within[:, :, None, None], diff, -jnp.inf))
    inside = jnp.einsum(
        "bnaihc,bnaijhc,bnajhc->bnhaij", rows32, decay,
        keys32.reshape(shape))                               # [b,n,H,m,sub,sub]
    # the sub-chunks' blocks onto the chunk's diagonal
    diagonal = jnp.einsum("bnhaij,ac->bnhaicj", inside,
                          jnp.eye(m, dtype=_F32)).reshape(b, n, H, C, C)
    return across + diagonal


def kda(q, k, v, g, beta, *, chunk: int = 64, scale: Optional[float] = None,
        state=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(`o` [b, T, H, dv] in v's dtype, the state after the last token
    [b, H, dk, dv] float32, `log_decay_min`: the most negative running log
    decay at a chunk's end) of queries and keys `q`, `k` [b, T, H, dk],
    values `v` [b, T, H, dv], log decays `g` [b, T, H, dk] (`<= 0`) and
    `beta` [b, T, H], by the recurrence above in chunks of `chunk` tokens.
    `scale` is the output's, `dk ** -0.5` where None; `state` the state
    before the first token, zero where None. A `T` that is no multiple of
    the chunk is padded with tokens of `g = 0` and `beta = 0`, which leave
    the state as it is."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
    dtype = v.dtype
    scale = dk ** -0.5 if scale is None else scale
    C = min(chunk, -(-T // SUB) * SUB)
    if C % SUB:
        raise ValueError(f"a chunk of {C} tokens is not whole sub-chunks "
                         f"of {SUB}")
    pad = (-T) % C
    g, beta = g.astype(_F32), beta.astype(_F32)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (T + pad) // C
    q, k, v, g = (x.reshape(b, n, C, H, x.shape[-1]) for x in (q, k, v, g))
    beta = beta.reshape(b, n, C, H)

    with jax.named_scope("kda_chunk"):
        G = jnp.cumsum(g, axis=2)                            # [b, n, C, H, dk]
        G_end = G[:, :, -1]                                  # [b, n, H, dk]
        k32 = k.astype(_F32)
        strict = jnp.tril(jnp.ones((C, C), bool), -1)
        beta_rows = jnp.moveaxis(beta, 3, 2)                 # [b, n, H, C]
        A = jnp.where(strict, beta_rows[..., None]
                      * _pair_products(k, k, G, dtype), 0.0)
        # T = (I + A)^-1 Diag(beta), by forward substitution in float32
        solved = jax.lax.linalg.triangular_solve(
            A + jnp.eye(C, dtype=_F32),
            beta_rows[..., None, :] * jnp.eye(C, dtype=_F32),
            left_side=True, lower=True, unit_diagonal=True)
        W = _mm("bnhij,bnjhc->bnhic", solved, k32 * jnp.exp(G), dtype)
        U = _mm("bnhij,bnjhv->bnhiv", solved, v, dtype)
        # (q_i e^{G_i}) . (k_j e^{-G_j}), the diagonal included
        scores = scale * _pair_products(q, k, G, dtype)
        q_in = (scale * q.astype(_F32) * jnp.exp(G)).astype(dtype)
        k_end = (k32 * jnp.exp(G_end[:, :, None] - G)).astype(dtype)

    with jax.named_scope("kda_state"):
        def one_chunk(S, chunk_):
            W_c, U_c, k_end_c, decay_c = chunk_
            fresh = U_c - _mm("bhic,bhcv->bhiv", W_c, S, dtype)
            S_next = (decay_c[..., None] * S
                      + _mm("bihc,bhiv->bhcv", k_end_c, fresh, dtype))
            return S_next, (S, fresh)

        if state is None:
            state = jnp.zeros((b, H, dk, dv), _F32)
        of_chunks = tuple(jnp.moveaxis(x, 1, 0) for x in (
            W.astype(dtype), U, k_end, jnp.exp(G_end)))
        last, (entering, fresh) = jax.lax.scan(
            one_chunk, state.astype(_F32), of_chunks)
        entering = jnp.moveaxis(entering, 0, 1)              # [b, n, H, dk, dv]
        fresh = jnp.moveaxis(fresh, 0, 1)                    # [b, n, H, C, dv]

    with jax.named_scope("kda_out"):
        o = (_mm("bnihc,bnhcv->bnhiv", q_in, entering, dtype)
             + _mm("bnhij,bnhjv->bnhiv", scores, fresh, dtype))
    o = jnp.moveaxis(o.astype(dtype), 2, 3).reshape(b, n * C, H, dv)[:, :T]
    return o, last, G_end.min()


def kda_recurrent(q, k, v, g, beta, *, scale: Optional[float] = None,
                  state=None) -> Tuple[jax.Array, jax.Array]:
    """(`o` [b, T, H, dv] float32, the state after the last token) by the
    recurrence itself, one token a step of a `lax.scan`, in float32 at the
    highest matmul precision: what `kda` is tested against."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    q, k, v, g, beta = (jnp.moveaxis(x.astype(_F32), 1, 0)
                        for x in (q, k, v, g, beta))
    hi = jax.lax.Precision.HIGHEST

    def one_token(S, token):
        q_t, k_t, v_t, g_t, beta_t = token
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.einsum("bhc,bhcv->bhv", k_t, S, precision=hi)
        S = S + jnp.einsum("bhc,bhv->bhcv", k_t,
                           beta_t[..., None] * (v_t - seen), precision=hi)
        return S, scale * jnp.einsum("bhc,bhcv->bhv", q_t, S, precision=hi)

    if state is None:
        state = jnp.zeros((b, H, dk, dv), _F32)
    last, o = jax.lax.scan(one_token, state.astype(_F32),
                           (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1), last
