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

Two paths compute that, part by part and at the same dtypes. `kda` takes the
kernels where the step's operators resolve to Pallas (`impl`, as `ssd`, `mha`
and the grouped matmul: the TPU) and the shape tiles (`kda_untiled`), and
says once a shape which it took (`_log_kda`: the path, the kernels' grid,
blocks and VMEM, or why the shape went to `jax.numpy`):

- **`jax.numpy` under the scopes `kda_chunk` (the pair products, the solve,
  `W` and `U`), `kda_state` (the scan over the chunks' states) and
  `kda_out`**, with autodiff through it: the CPU's path, the one of shapes
  that do not tile, and the reference the kernels are tested against. The
  `[.., SUB, SUB, dk]` pair tensors, the scaled copies of the keys, the
  solve (an `InvertDiagBlocksLowerTriangular` custom call) and every one's
  cotangent cross HBM in float32.
- **Two Pallas kernels, `kda_fwd` and `kda_bwd`, behind a `custom_vjp`.**
  The grid is (batch row, heads, chunk), the chunks innermost and in order.
  Every array of a body is `[128, w]` with row `(head, token)`: the chunks
  of the `R = 128 / C` heads that fill 128 rows (a *group*: two heads at
  chunks of 64), so a head's `[C, C]` matrix is its block on the diagonal
  of a `[128, 128]` one and the matmuls are the MXU's whole tile. A grid
  step takes one group with its chunk's whole work, the state-free part
  and the state's step alike, so the scheduler fills the waits of the one
  with the other; a pass is `H / R` times `T / C` steps a layer. (Four
  groups a step, unrolled, were a tenth faster alone and cost the cell 75
  s of set-up: a body is traced an equation at a time, and the call is
  under `jit` so that a model's layers share one trace: PERF.md section 6,
  PR 60.) q, k,
  v, g and o are taken as `[b, T, H w]` in blocks `[C, R w]`, as they
  leave the convolution, lane-dense; beta as rows `[1, 128]`; the heads'
  states live in VMEM scratch across the chunks. In a step: the running
  sums `G` (`log2 C` shifted adds); pairs of different sub-chunks by one
  matmul a boundary, as above; pairs of one sub-chunk with a channel a
  sublane and `(head, token)` a lane, a diagonal `d = i - j` at a time (the
  keys and `G` turned `d` lanes, one `exp` a channel and pair, shared by
  the keys' and the queries' rows, summed down the sublanes), the 16
  diagonals then turned into the matrix by one strided rotation (`_skew`);
  `(I + A)^-1` by forward substitution in float32 on the vector unit
  (`_inverse`), 63 dependent steps that the heads of a step take side by
  side; `W`, `U`, the scores and the state's step on the MXU. From the
  forward rule the kernel also writes each chunk's entering states
  (`[b, n, H, dk, dv]` float32), the backward's one residual beside the
  inputs. `kda_bwd` walks the chunks from the last to the first with the
  states' cotangent in the same scratch, makes the chunk again from its
  blocks and the entering states, and returns dq, dk, dv, dg, dbeta and the
  entering state's cotangent; the inverse's cotangent `-X^T dX X^T` is two
  float32 matmuls at the MXU's full precision. Nothing of shape
  `[.., C, C]` or `[.., SUB, SUB, dk]` and no per-chunk intermediate but
  the entering states reaches HBM. Heads are padded to whole groups with
  heads of `g = 0` and `beta = 0`. `benchmarks/kda_alone.py` times both
  paths alone.

What hands `kda` its q, k and v is the mixer's, not this module's: the
short convolution, its silu and q's and k's unit length a head
(`models/transformer.py` `_kda_mixer`, scope `kda_conv`). By the same two
conditions (the operators resolve to Pallas, the streams tile:
`ops/mamba_passes.py` `conv_untiled`) that pass runs as the kernel pair
`kda_conv_fwd` and `kda_conv_bwd`, `ops/mamba_passes.py`
`causal_conv_silu(..., unit=dk, name="kda_conv")`, a call a stream, each
stream read once and written once; elsewhere as the mixer's `jax.numpy`
lines. `train.kda_conv_calls_kernels` and `train.kda_conv_calls_numpy`
count the calls of each (docs/observability.md; PERF.md section 6, PR 67).

What takes o is the mixer's too: the norm a head under one learned scale and
the sigmoid gate (scope `kda_out` of the mixer, not this module's `kda_out`
of the `jax.numpy` path). The kernels write o as `[b, T, H dv]`, and where
the heads fill whole groups `kda` returns a free reshape of it, so by the
same two conditions (`ops/mamba_passes.py` `norm_untiled`) that pass reads
it where it lies, as the kernel pair `kda_out_norm_fwd` and
`kda_out_norm_bwd` (`group_rmsnorm_gated`); no transpose and no float32
copy stands between the two kernels. `train.kda_out_norm_calls_kernels` and
`train.kda_out_norm_calls_numpy` count the mixers of each (PERF.md section
6, PR 69).
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (
    _DEFAULT_VMEM, _LANES, _MAX_VMEM, _NN, _NT, _TN, _dot, _pallas_call,
    resolve_impl)

logger = logging.getLogger(__name__)

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
        state=None, impl: str = "auto", interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(`o` [b, T, H, dv] in v's dtype, the state after the last token
    [b, H, dk, dv] float32, `log_decay_min`: the most negative running log
    decay at a chunk's end) of queries and keys `q`, `k` [b, T, H, dk],
    values `v` [b, T, H, dv], log decays `g` [b, T, H, dk] (`<= 0`) and
    `beta` [b, T, H], by the recurrence above in chunks of `chunk` tokens.
    `scale` is the output's, `dk ** -0.5` where None; `state` the state
    before the first token, zero where None. A `T` that is no multiple of
    the chunk is padded with tokens of `g = 0` and `beta = 0`, which leave
    the state as it is.

    impl: 'auto' (the kernels on TPU, `jax.numpy` elsewhere) | 'pallas' |
    'xla'; `interpret` runs the kernels in interpret mode, for tests. A
    shape that does not tile (`kda_untiled`) takes `jax.numpy` whatever
    `impl` says."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
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
    if state is None:
        state = jnp.zeros((b, H, dk, dv), _F32)
    state = state.astype(_F32)
    kernels = resolve_impl(impl) == "pallas" or interpret
    untiled = kda_untiled(C, dk, dv, jnp.dtype(v.dtype).itemsize)
    _log_kda(kernels, untiled, b, T + pad, H, dk, dv, C,
             jnp.dtype(v.dtype).name)
    if kernels and not untiled:
        o, last, reach = _kda_kernels(q, k, v, g, beta, state, C, scale,
                                      interpret)
    else:
        o, last, reach = _kda_numpy(q, k, v, g, beta, state, C, scale)
    return o[:, :T], last, reach


def _kda_numpy(q, k, v, g, beta, state, C, scale):
    """The recurrence over whole chunks of `C` tokens in `jax.numpy`."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
    dtype = v.dtype
    n = T // C
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

        of_chunks = tuple(jnp.moveaxis(x, 1, 0) for x in (
            W.astype(dtype), U, k_end, jnp.exp(G_end)))
        last, (entering, fresh) = jax.lax.scan(one_chunk, state, of_chunks)
        entering = jnp.moveaxis(entering, 0, 1)              # [b, n, H, dk, dv]
        fresh = jnp.moveaxis(fresh, 0, 1)                    # [b, n, H, C, dv]

    with jax.named_scope("kda_out"):
        o = (_mm("bnihc,bnhcv->bnhiv", q_in, entering, dtype)
             + _mm("bnhij,bnhjv->bnhiv", scores, fresh, dtype))
    o = jnp.moveaxis(o.astype(dtype), 2, 3).reshape(b, T, H, dv)
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


# ----------------------------------------------------------------- kernels
#
# Every array of a kernel's body is `[128, w]` with row `(head, token)`: the
# chunks of the `R = 128 / C` heads that fill 128 rows, a *group*. A `[C, C]`
# matrix of a head is its block on the diagonal of a `[128, 128]` one, so
# the matmuls are the MXU's full tile. A grid step takes one group: a body
# is traced an equation at a time, and a step's set-up pays for every one.
# Float32 here is `_ACC`, not the module's `_F32`, which a test lowers to
# show what a bf16 part costs.

_ACC = jnp.float32
_ROWS = 128
_TILE = 8    # rows of a float32 register


def kda_vmem_bytes(kernel: str, dk: int, dv: int, itemsize: int) -> int:
    """An estimate of what a grid step of `kda_fwd` or `kda_bwd` holds in
    VMEM: its blocks double-buffered (q, k, g `[128, dk]`, v, o `[128, dv]`
    and their cotangents, the heads' states `[R, dk, dv]` float32 entering,
    leaving and in scratch) and the body's live float32 `[128, dk]`,
    `[128, dv]` and `[128, 128]` arrays."""
    wide, tall = _ROWS * max(dk, dv), dk * dv * 4
    if kernel == "kda_fwd":
        blocks = 4 * wide * itemsize + wide * 4 + 3 * tall
        live = 24 * wide * 4 + 16 * _ROWS * _ROWS * 4
    else:
        blocks = 9 * wide * itemsize + 2 * wide * 4 + 3 * tall
        live = 48 * wide * 4 + 32 * _ROWS * _ROWS * 4
    return 2 * blocks + tall + live


def _vmem_limit(kernel, dk, dv, itemsize) -> int:
    return max(_DEFAULT_VMEM, 2 * kda_vmem_bytes(kernel, dk, dv, itemsize))


def kda_untiled(C: int, dk: int, dv: int, itemsize: int) -> Optional[str]:
    """Why the kernels cannot take chunks of `C` tokens and heads of `dk`
    and `dv`, or None where they can: whole heads' chunks fill the 128 rows
    of a group, `dk` and `dv` are whole tiles of 128 lanes, and a step fits
    VMEM."""
    if _ROWS % C:
        return f"chunks of {C} tokens do not fill {_ROWS} rows"
    for name, size in (("keys", dk), ("values", dv)):
        if size % _LANES:
            return f"{name} of {size} are no multiple of {_LANES} lanes"
    need = _vmem_limit("kda_bwd", dk, dv, itemsize)
    if need > _MAX_VMEM:
        return f"a step of kda_bwd needs {need} bytes of VMEM, over {_MAX_VMEM}"
    return None


@functools.lru_cache(maxsize=None)
def _log_kda(kernels, untiled, b, T, H, dk, dv, C, dtype):
    """One line for each recurrence a process traces, as `ops/ssd.py`'s
    `_log_scan`: which path, and the kernels' grid, blocks and VMEM."""
    shape = f"kda at b {b}, T {T}, H {H}, dk {dk}, dv {dv}, chunk {C}, {dtype}"
    if not kernels:
        logger.info("%s: jax.numpy (kda_chunk, kda_state, kda_out)", shape)
    elif untiled:
        logger.info("%s: jax.numpy (kda_chunk, kda_state, kda_out), because "
                    "%s", shape, untiled)
    else:
        R, item = _ROWS // C, jnp.dtype(dtype).itemsize
        logger.info(
            "%s: kda_fwd and kda_bwd, grid (%d, %d, %d), %d heads a step, "
            "blocks [%d, %d] of q, k, g and [%d, %d] of v, states of "
            "[%d, %d, %d] float32, VMEM %d and %d bytes of limits of %d and "
            "%d", shape, b, -(-H // R), T // C, R, C, R * dk, C, R * dv, R,
            dk, dv, *(f(kernel, dk, dv, item) for f in (
                kda_vmem_bytes, _vmem_limit)
                for kernel in ("kda_fwd", "kda_bwd")))


def _iota(axis):
    return jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _ROWS), axis)


def _rows_of(x, C, token):
    """`[128, w]` whose rows of every head are that head's row `token` of
    `x` `[128, w]`."""
    return jnp.concatenate(
        [jnp.broadcast_to(x[h * C + token:h * C + token + 1],
                          (C, x.shape[1])) for h in range(_ROWS // C)], axis=0)


def _roll(x, shift, axis):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[axis], axis)


def _running_sum(x, C, reverse: bool = False):
    """Every head's running sum down its chunk's `C` rows of `x` `[128, w]`
    (up them, with `reverse`), in `log2 C` shifted additions."""
    token = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) % C
    step = 1
    while step < C:
        if reverse:
            x = x + jnp.where(token + step < C, _roll(x, -step, 0), 0.0)
        else:
            x = x + jnp.where(token >= step, _roll(x, step, 0), 0.0)
        step *= 2
    return x


def _to_row(col):
    """`[1, 128]` of a column `[128, 1]`."""
    return jnp.where(_iota(0) == _iota(1), col, 0.0).sum(axis=0, keepdims=True)


def _to_col(row):
    """`[128, 1]` of a row `[1, 128]`."""
    return jnp.where(_iota(0) == _iota(1), row, 0.0).sum(axis=1, keepdims=True)


def _skew(bands_k, bands_q):
    """(`M_k`, `M_q`) with `M[r, l] = band_(r - l)[r]` for the 16 diagonals
    `0 <= r - l < 16`, of two lists of 16 rows `[1, 128]`, the main
    diagonal first: row `r` of the bands side by side is turned right by
    `r - 15`, a strided rotation. Entries off those diagonals are whatever
    the rotation brings: the caller masks."""
    from jax.experimental.pallas import tpu as pltpu

    bands = jnp.concatenate(
        bands_k[::-1] + bands_q[::-1]
        + [jnp.zeros((_ROWS - 2 * SUB, _ROWS), _ACC)], axis=0)
    # lane e of row r: k's pair (r, r - 15 + e), and q's 16 lanes on
    both = pltpu.roll(bands.T, _ROWS - SUB + 1, 1, stride=1, stride_axis=0)
    return both, _roll(both, -SUB, 1)


def _unskew(M_k, M_q, inside):
    """`_skew`'s inverse on the 16 diagonals inside a sub-chunk: (`bands_k`,
    `bands_q`) `[16, 128]` with `bands[15 - d, r] = M[r, r - d]`. Row `r` is
    turned left by `r - 15`: the tiles of 8 rows by what they share, then a
    bit of `r` at a time (the strided rotation turns right only). q's
    sub-chunk blocks ride 16 lanes right of k's, where k's rows are 0."""
    both = jnp.where(inside, M_k, 0.0) + _roll(
        jnp.where(inside, M_q, 0.0), SUB, 1)
    both = jnp.concatenate(
        [_roll(both[t * _TILE:(t + 1) * _TILE], SUB - 1 - t * _TILE, 1)
         for t in range(_ROWS // _TILE)], axis=0)
    row = _iota(0)
    for bit in (1, 2, 4):
        both = jnp.where((row & bit) != 0, _roll(both, -bit, 1), both)
    bands = both.T
    return bands[:SUB], bands[SUB:2 * SUB]


class _Chunk(NamedTuple):
    """What both kernels make of a step's blocks before the state is read:
    float32 but for the matmuls' operands, which are in v's dtype."""
    k32: jax.Array      # [128, dk] the keys, the queries
    q32: jax.Array
    v: jax.Array        # [128, dv]
    beta: jax.Array     # [1, 128]
    G: jax.Array        # the running log decays
    decay: jax.Array    # e^G
    to_end: jax.Array   # e^{G_C - G}
    kT: jax.Array       # [dk, 128] the same, a channel a row
    qT: jax.Array
    GT: jax.Array
    to_rows: jax.Array  # e^{G - G_r}, r the boundary before the row's sub-chunk
    to_keys: tuple      # e^{min(G_r - G, 0)} for each boundary r but the first
    rows_k: jax.Array   # k and q scaled to their boundary, v's dtype
    rows_q: jax.Array
    keys: tuple         # k scaled to each boundary, v's dtype
    pairs_k: jax.Array  # [128, 128] sum_c k_i k_j e^{G_i - G_j}, j <= i
    pairs_q: jax.Array  # the same of q_i
    X: jax.Array        # (I + A)^-1, A = strict_lower(beta_i pairs_k)
    solved: jax.Array   # X Diag(beta), v's dtype
    k_decayed: jax.Array  # k e^G, v's dtype
    W: jax.Array        # solved (k e^G), v's dtype
    U: jax.Array        # solved v, float32
    scores: jax.Array   # scale pairs_q, v's dtype
    q_in: jax.Array     # scale q e^G, v's dtype
    k_end: jax.Array    # k e^{G_C - G}, v's dtype


class _Masks(NamedTuple):
    """Of a `[128, 128]` matrix of pairs (row `(head, i)`, lane `(head,
    j)`): one head and `j <= i`; the same and `j < i`; one sub-chunk and
    `j <= i`; and for each boundary `a SUB` inside a chunk, `i` in the
    sub-chunk after it and `j` before it."""
    lower: jax.Array
    strict: jax.Array
    inside: jax.Array
    across: tuple


def _masks(C) -> _Masks:
    row, lane = _iota(0), _iota(1)
    lower = (row // C == lane // C) & (lane <= row)
    return _Masks(
        lower, lower & (lane < row),
        lower & (row // SUB == lane // SUB),
        tuple(lower & ((row % C) // SUB == a) & (lane % C < a * SUB)
              for a in range(1, C // SUB)))


def _inverse(A, C):
    """`(I + A)^-1` of `A` `[128, 128]`, strictly lower triangular in each
    head's `[C, C]` block and 0 outside: forward substitution in float32 on
    the vector unit, a column of `A` a step. The heads' inverses lie side
    by side, `[C, (head, column)]`, so that a step is one multiply-subtract
    of the rows below it (from the tile of 8 that holds the next row) for
    all the heads, whose chains the unit takes together."""
    R = _ROWS // C
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, _ROWS), 1)
    token = jax.lax.broadcasted_iota(jnp.int32, (C, _ROWS), 0)
    X = jnp.where(lane % C == token, 1.0, 0.0).astype(_ACC)
    # the lanes of head h and after, for the rows below each tile's start
    # (made at that height: a slice of an iota along the lanes is refused)
    of_head = {lo: [jax.lax.broadcasted_iota(
        jnp.int32, (C - lo, _ROWS), 1) >= h * C for h in range(1, R)]
        for lo in range(0, C, _TILE)}
    for j in range(C - 1):
        lo = (j + 1) // _TILE * _TILE
        done = X[j:j + 1]
        moved = A[lo:C, j:j + 1] * done
        for h in range(1, R):  # head h's column, over its own lanes
            at = h * C + j
            moved = jnp.where(
                of_head[lo][h - 1],
                A[h * C + lo:(h + 1) * C, at:at + 1] * done, moved)
        below = X[lo:] - moved
        X = below if lo == 0 else jnp.concatenate([X[:lo], below], axis=0)
    # each head's block onto the diagonal
    return jnp.concatenate(
        [jnp.where(lane // C == h, X, 0.0) for h in range(R)], axis=0)


def _pairs(kT, qT, GT, rows_k, rows_q, keys, masks: _Masks):
    """(`pairs_k`, `pairs_q`) `[128, 128]`: `sum_c rows[i, c] k[j, c]
    exp(G[i, c] - G[j, c])` for `j <= i` of one head, 0 elsewhere, of the
    keys and of the queries as rows. Pairs of different sub-chunks: one
    matmul a boundary of rows and keys scaled to it. Pairs of one
    sub-chunk: a diagonal at a time, the keys and the sums turned `d`
    lanes, in float32 on the vector unit with a channel a sublane; the 16
    diagonals are then turned into the matrix (`_skew`)."""
    both = jnp.concatenate([rows_k, rows_q], axis=0)           # [256, dk]
    pairs_k = pairs_q = jnp.zeros((_ROWS, _ROWS), _ACC)
    for mask, scaled in zip(masks.across, keys):
        product = _dot(both, scaled, _NT)                      # [256, 128]
        pairs_k = pairs_k + jnp.where(mask, product[:_ROWS], 0.0)
        pairs_q = pairs_q + jnp.where(mask, product[_ROWS:], 0.0)
    bands_k = [(kT * kT).sum(axis=0, keepdims=True)]
    bands_q = [(qT * kT).sum(axis=0, keepdims=True)]
    for d in range(1, SUB):
        # lane (head, i) holds the pair (i, i - d): the exponent is a sum
        # of g over tokens between them, or the lane is masked below
        turned = jnp.exp(jnp.minimum(GT - _roll(GT, d, 1), 0.0)) * _roll(
            kT, d, 1)
        bands_k.append((kT * turned).sum(axis=0, keepdims=True))
        bands_q.append((qT * turned).sum(axis=0, keepdims=True))
    inside_k, inside_q = _skew(bands_k, bands_q)
    return (pairs_k + jnp.where(masks.inside, inside_k, 0.0),
            pairs_q + jnp.where(masks.inside, inside_q, 0.0))


def _chunk(q, k, v, g, beta, C, scale, masks: _Masks) -> _Chunk:
    """A step's chunk from its blocks `[128, w]` and `beta` `[1, 128]`."""
    dtype = v.dtype
    k32, q32 = k.astype(_ACC), q.astype(_ACC)
    G = _running_sum(g, C)
    decay = jnp.exp(G)
    kT, qT, GT = k32.T, q32.T, G.T
    # the boundary before each row's sub-chunk: the chunk's start (0), then
    # the last token of the sub-chunk before
    m = C // SUB
    before = jnp.concatenate(
        [jnp.zeros((SUB, G.shape[1]), _ACC) if s % m == 0 else
         jnp.broadcast_to(G[s * SUB - 1:s * SUB], (SUB, G.shape[1]))
         for s in range(_ROWS // SUB)], axis=0)
    to_rows = jnp.exp(G - before)
    to_keys = tuple(
        jnp.exp(jnp.minimum(_rows_of(G, C, a * SUB - 1) - G, 0.0))
        for a in range(1, m))
    rows_k, rows_q = ((x * to_rows).astype(dtype) for x in (k32, q32))
    keys = tuple((k32 * scaled).astype(dtype) for scaled in to_keys)
    pairs_k, pairs_q = _pairs(kT, qT, GT, rows_k, rows_q, keys, masks)
    X = _inverse(jnp.where(masks.strict, _to_col(beta) * pairs_k, 0.0), C)
    solved = (X * beta).astype(dtype)
    k_decayed = (k32 * decay).astype(dtype)
    to_end = jnp.exp(_rows_of(G, C, C - 1) - G)
    return _Chunk(
        k32, q32, v, beta, G, decay, to_end, kT, qT, GT, to_rows, to_keys,
        rows_k, rows_q, keys, pairs_k, pairs_q, X, solved, k_decayed,
        W=_dot(solved, k_decayed, _NN).astype(dtype),
        U=_dot(solved, v, _NN),
        scores=(scale * pairs_q).astype(dtype),
        q_in=(scale * q32 * decay).astype(dtype),
        k_end=(k32 * to_end).astype(dtype))


def _heads(ref, C):
    """A block `[1, C, R w]` as `[128, w]`, row `(head, token)`."""
    R = _ROWS // C
    w = ref.shape[2] // R
    return jnp.concatenate(
        [ref[0, :, h * w:(h + 1) * w] for h in range(R)], axis=0)


def _to_heads(ref, x, C):
    """`x` `[128, w]` into the block `[1, C, R w]`."""
    w = x.shape[1]
    for h in range(_ROWS // C):
        ref[0, :, h * w:(h + 1) * w] = x[h * C:(h + 1) * C].astype(ref.dtype)


def _head(x, C, h):
    return x[h * C:(h + 1) * C]


def _by_head(f, C):
    return jnp.concatenate([f(h) for h in range(_ROWS // C)], axis=0)


def _fresh(c: _Chunk, low, C):
    """`U - W S` in v's dtype: the values the chunk's tokens write, less
    what the entering states `low` (v's dtype) already hold."""
    return (c.U - _by_head(lambda h: _dot(_head(c.W, C, h), low[h], _NN), C)
            ).astype(c.v.dtype)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, first_ref, o_ref,
                    last_ref, reach_ref, *rest, C: int, scale: float):
    """One chunk of `R` heads: `rest` is the states' scratch `[R, dk, dv]`,
    after the block of entering states where the forward rule asks."""
    from jax.experimental import pallas as pl

    state = rest[-1]
    R = _ROWS // C

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = first_ref[0]

    if len(rest) == 2:
        rest[0][0, 0] = state[...]
    c = _chunk(*(_heads(ref, C) for ref in (q_ref, k_ref, v_ref, g_ref)),
               beta_ref[0, 0, 0], C, scale, _masks(C))
    dtype, dv = c.v.dtype, c.v.shape[1]
    entering = [state[h] for h in range(R)]
    low = [S.astype(dtype) for S in entering]
    fresh = _fresh(c, low, C)
    o = _dot(c.scores, fresh, _NN) + _by_head(
        lambda h: _dot(_head(c.q_in, C, h), low[h], _NN), C)
    _to_heads(o_ref, o, C)
    ends = []
    for h in range(R):
        end = h * C + C - 1
        to_chunk_end = jnp.broadcast_to(
            jnp.exp(c.GT[:, end:end + 1]), (c.GT.shape[0], dv))
        state[h] = to_chunk_end * entering[h] + _dot(
            _head(c.k_end, C, h), _head(fresh, C, h), _TN)
        ends.append(c.G[end:end + 1])
    reach_ref[0, 0, 0] = functools.reduce(jnp.minimum, ends)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _last_chunk():
        last_ref[0] = state[...]


def _dot_f32(a, b, dims):
    """A matmul of float32 operands at the MXU's full precision."""
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=_ACC)


def _pairs_bwd(c: _Chunk, dpairs_k, dpairs_q, masks: _Masks):
    """(`dk`, `dq`, `dG`) `[128, dk]` float32 of `_pairs`' cotangents
    `[128, 128]`, and `dGT` `[dk, 128]`, the part of `dG` that is made a
    channel a row. The boundaries get nothing: a pair's product does not
    depend on where its exponent was split."""
    dtype = c.v.dtype
    drows_k = drows_q = dk = dG = jnp.zeros(c.k32.shape, _ACC)
    for mask, scaled, to_key in zip(masks.across, c.keys, c.to_keys):
        low_k = jnp.where(mask, dpairs_k, 0.0).astype(dtype)
        low_q = jnp.where(mask, dpairs_q, 0.0).astype(dtype)
        drows_k = drows_k + _dot(low_k, scaled, _NN)
        drows_q = drows_q + _dot(low_q, scaled, _NN)
        dkeys = to_key * (
            _dot(low_k, c.rows_k, _TN) + _dot(low_q, c.rows_q, _TN))
        dk = dk + dkeys
        dG = dG - dkeys * c.k32
    drows_k, drows_q = drows_k * c.to_rows, drows_q * c.to_rows
    dk = dk + drows_k
    dG = dG + drows_k * c.k32 + drows_q * c.q32

    bands_k, bands_q = _unskew(dpairs_k, dpairs_q, masks.inside)
    kT, qT, GT = c.kT, c.qT, c.GT
    on_k, on_q = bands_k[SUB - 1:SUB], bands_q[SUB - 1:SUB]
    dkT = 2.0 * on_k * kT + on_q * qT
    dqT = on_q * kT
    dGT = jnp.zeros(GT.shape, _ACC)
    for d in range(1, SUB):
        of_k = bands_k[SUB - 1 - d:SUB - d]
        of_q = bands_q[SUB - 1 - d:SUB - d]
        k_turned = _roll(kT, d, 1)
        decay = jnp.exp(jnp.minimum(GT - _roll(GT, d, 1), 0.0))
        turned = decay * k_turned
        dqT = dqT + of_q * turned
        to_keys = (of_k * kT + of_q * qT) * decay   # of the key d lanes back
        to_exponent = to_keys * k_turned
        dkT = dkT + of_k * turned + _roll(to_keys, -d, 1)
        dGT = dGT + to_exponent - _roll(to_exponent, -d, 1)
    return dk + dkT.T, drows_q + dqT.T, dG, dGT


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, first_ref,
                    entering_ref, do_ref, dlast_ref, dq_ref, dk_ref, dv_ref,
                    dg_ref, dbeta_ref, dfirst_ref, dstate, *, C: int,
                    scale: float):
    """The same chunk's cotangents; the grid walks the chunks backwards,
    `dstate` `[R, dk, dv]` holds the cotangent of the states the chunk
    leaves, and the chunk is made again from its blocks and the states it
    entered with."""
    from jax.experimental import pallas as pl

    del first_ref  # the first chunk's entering state is among `entering`
    R = _ROWS // C

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = dlast_ref[0]

    masks = _masks(C)
    c = _chunk(*(_heads(ref, C) for ref in (q_ref, k_ref, v_ref, g_ref)),
               beta_ref[0, 0, 0], C, scale, masks)
    v, dtype = c.v, c.v.dtype
    do = _heads(do_ref, C)

    def head(x, h):
        return _head(x, C, h)

    def by_head(f):
        return _by_head(f, C)

    entering = [entering_ref[0, 0, h] for h in range(R)]
    leaving = [dstate[h] for h in range(R)]
    low = [S.astype(dtype) for S in entering]
    low_leaving = [S.astype(dtype) for S in leaving]
    fresh = _fresh(c, low, C)

    # o = scores fresh + q_in S
    dq_in = by_head(lambda h: _dot(head(do, h), low[h], _NT))
    dscores = jnp.where(masks.lower, _dot(do, fresh, _NT), 0.0)
    # S' = e^{G_C} S + k_end^T fresh
    dk_end = by_head(lambda h: _dot(head(fresh, h), low_leaving[h], _NT))
    dU = (_dot(c.scores, do, _TN) + by_head(
        lambda h: _dot(head(c.k_end, h), low_leaving[h], _NN))
          ).astype(dtype)
    # fresh = U - W S
    dW = (-by_head(lambda h: _dot(head(dU, h), low[h], _NT))).astype(dtype)
    dGT_end = []
    for h in range(R):
        to_chunk_end = jnp.exp(c.GT[:, h * C + C - 1:h * C + C])  # [dk, 1]
        dstate[h] = (to_chunk_end * leaving[h]
                     + _dot(head(c.q_in, h), head(do, h), _TN)
                     - _dot(head(c.W, h), head(dU, h), _TN))
        dGT_end.append(to_chunk_end * (leaving[h] * entering[h]).sum(
            axis=1, keepdims=True))
    # W = solved (k e^G), U = solved v, solved = X Diag(beta)
    dsolved = jnp.where(
        masks.lower, _dot(dW, c.k_decayed, _NT) + _dot(dU, v, _NT), 0.0)
    dk_decayed = _dot(c.solved, dW, _TN)
    _to_heads(dv_ref, _dot(c.solved, dU, _TN), C)
    dbeta = (dsolved * c.X).sum(axis=0, keepdims=True)
    # X = (I + A)^-1, A = strict_lower(beta_i pairs_k)
    dA = jnp.where(masks.strict, -_dot_f32(
        _dot_f32(c.X, dsolved * c.beta, _TN), c.X, _NT), 0.0)
    dbeta = dbeta + _to_row((dA * c.pairs_k).sum(axis=1, keepdims=True))
    dk, dq, dG, dGT = _pairs_bwd(
        c, dA * _to_col(c.beta), scale * dscores, masks)

    dk_decayed = dk_decayed * c.decay
    dq_in = scale * dq_in * c.decay
    dk_end = dk_end * c.to_end
    to_end = dk_end * c.k32
    dk = dk + dk_decayed + dk_end
    dq = dq + dq_in
    dG = dG + dk_decayed * c.k32 + dq_in * c.q32 - to_end
    # the chunk's last token: G_C, in e^{G_C - G} and in e^{G_C}
    row = jax.lax.broadcasted_iota(jnp.int32, dG.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, dGT.shape, 1)
    for h in range(R):
        end = h * C + C - 1
        dG = dG + jnp.where(
            row == end, head(to_end, h).sum(axis=0, keepdims=True), 0.0)
        dGT = dGT + jnp.where(lane == end, dGT_end[h], 0.0)
    _to_heads(dq_ref, dq, C)
    _to_heads(dk_ref, dk, C)
    _to_heads(dg_ref, _running_sum(dG + dGT.T, C, reverse=True), C)
    dbeta_ref[0, 0, 0] = dbeta

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _first_chunk():
        dfirst_ref[0] = dstate[...]


# how each operand and result of the kernels lies, by its place in the call
_TOKENS, _BETA, _STATE, _STATES, _REACH = range(5)
_INPUTS = (_TOKENS, _TOKENS, _TOKENS, _TOKENS, _BETA, _STATE)


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4, 5, 6, 7, 8))
def _kda_call(kernel, name, operands, kinds, out_shapes, out_kinds, C, scale,
              interpret):
    """`pallas_call` of `kda_fwd` or `kda_bwd` (which walks the chunks
    backwards) over (batch row, group of `R` heads, chunk). Blocks: `[C, R
    w]` of tokens `[b, T, H w]`; beta's row `[1, 128]` of `[b, H / R, n, 1,
    128]`; the heads' states `[R, dk, dv]` of `[b, H, dk, dv]` before the
    first and after the last token and of `[b, n, H, dk, dv]` entering each
    chunk; the reach `[1, dk]` of `[b, H / R, n, 1, dk]`. Under `jit`: a
    model's layers of one shape share one trace of a kernel's body, which
    is most of what a kernel costs a step's set-up."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, v = operands[1], operands[2]
    b, T = k.shape[:2]
    R, n = _ROWS // C, T // C
    _, H, dk, dv = operands[5].shape
    chunk = (lambda c: n - 1 - c) if name == "kda_bwd" else (lambda c: c)

    def spec(a, kind):
        if kind == _TOKENS:
            return pl.BlockSpec((1, C, a.shape[2] // (H // R)),
                                lambda i, h, c: (i, chunk(c), h))
        if kind in (_BETA, _REACH):
            return pl.BlockSpec((1, 1, 1, 1, a.shape[4]),
                                lambda i, h, c: (i, h, chunk(c), 0, 0))
        if kind == _STATE:
            return pl.BlockSpec((1, R, dk, dv), lambda i, h, c: (i, h, 0, 0))
        return pl.BlockSpec((1, 1, R, dk, dv),
                            lambda i, h, c: (i, chunk(c), h, 0, 0))

    return _pallas_call(
        functools.partial(kernel, C=C, scale=scale),
        grid=(b, H // R, n),
        in_specs=[spec(a, kind) for a, kind in zip(operands, kinds)],
        out_specs=[spec(a, kind) for a, kind in zip(out_shapes, out_kinds)],
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((R, dk, dv), _ACC)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                name, dk, dv, jnp.dtype(v.dtype).itemsize)),
        interpret=interpret,
        name=name,
    )(*operands)


def _kda_fwd(q, k, v, g, beta, first, C, scale, interpret,
             with_states: bool = False):
    b, T = k.shape[:2]
    _, H, dk, dv = first.shape
    R, n = _ROWS // C, T // C
    out = [jax.ShapeDtypeStruct(v.shape, v.dtype),
           jax.ShapeDtypeStruct(first.shape, _ACC),
           jax.ShapeDtypeStruct((b, H // R, n, 1, dk), _ACC)]
    if with_states:
        out.append(jax.ShapeDtypeStruct((b, n, H, dk, dv), _ACC))
    return _kda_call(
        _kda_fwd_kernel, "kda_fwd", (q, k, v, g, beta, first), _INPUTS,
        tuple(out), (_TOKENS, _STATE, _REACH, _STATES), C, scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _recurrence(q, k, v, g, beta, first, C, scale, interpret):
    return tuple(_kda_fwd(q, k, v, g, beta, first, C, scale, interpret))


def _recurrence_vjp_fwd(q, k, v, g, beta, first, C, scale, interpret):
    o, last, reach, entering = _kda_fwd(
        q, k, v, g, beta, first, C, scale, interpret, with_states=True)
    return (o, last, reach), (q, k, v, g, beta, first, entering)


def _recurrence_vjp_bwd(C, scale, interpret, res, cotangents):
    q, k, v, g, beta, first, entering = res
    do, dlast, _ = cotangents  # the reach is a reading
    shapes = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta, first))
    return tuple(_kda_call(
        _kda_bwd_kernel, "kda_bwd",
        (q, k, v, g, beta, first, entering, do, dlast),
        (*_INPUTS, _STATES, _TOKENS, _STATE), shapes, _INPUTS, C, scale,
        interpret))


_recurrence.defvjp(_recurrence_vjp_fwd, _recurrence_vjp_bwd)


def _kda_kernels(q, k, v, g, beta, state, C, scale, interpret):
    """The recurrence over whole chunks of `C` tokens by `kda_fwd` and
    `kda_bwd`: the layouts they take, made here. Heads are padded to whole
    groups of `R` with heads of `g = 0` and `beta = 0`."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
    R, n = _ROWS // C, T // C
    more = (-H) % R
    if more:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, more), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, more)))
        state = jnp.pad(state, ((0, 0), (0, more), (0, 0), (0, 0)))
    Hp = H + more
    rows = beta.reshape(b, n, C, Hp // R, R).transpose(0, 3, 1, 4, 2)
    o, last, reach = _recurrence(
        q.reshape(b, T, Hp * dk), k.reshape(b, T, Hp * dk),
        v.reshape(b, T, Hp * dv), g.reshape(b, T, Hp * dk),
        rows.reshape(b, Hp // R, n, 1, _ROWS), state, C, scale, interpret)
    return (o.reshape(b, T, Hp, dv)[:, :, :H], last[:, :H],
            jax.lax.stop_gradient(reach).min())
