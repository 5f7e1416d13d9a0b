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

`selective_scan` computes it in float32 (`dt`, `A`, every `exp`, the state,
its cotangent, `dA` and `y`; `u`, `B` and `C` are read in the dtype they
come in and widened where they are used). The sequence is cut into chunks
of `chunk` tokens (padded with steps of `dt = 0`, which leave the state
alone). The forward walks the chunks in order, the tokens of a chunk in
order inside, and keeps each chunk's *entering* state, `[T / chunk, b, N,
inner]` float32: the one residual beside the inputs. The backward walks the
chunks from the last to the first with the state's cotangent in hand,
makes a chunk's steps again from its entering state and adds `dA` into one
accumulator. No `[T, inner, N]` array is ever made. The entering states
carry the `checkpoint_name` `scan_out`, as the model's cast of `y` does: a
rematerialised block that keeps the name runs no second forward scan. The
state lies `[b, N, inner]`: the channels along the lanes. The skip `D u`
is `jax.numpy`'s on every path, and autodiff's.

Two paths compute the chunked scan, at the same dtypes. `selective_scan`
takes the kernels where the step's operators resolve to Pallas (`impl`, as
`ssd`, `kda`, `mha` and the grouped matmul: the TPU) and the shape tiles
(`selective_scan_untiled`), and says once a shape which it took
(`_log_selective_scan`: the path, the kernels' grid, block and VMEM, or why
the shape went to `jax.numpy`):

- **`jax.numpy` (`"chunked"`; `"xla"`, as `resolve_impl` calls it)** behind
  a `custom_vjp`, two nested `lax.scan`: the CPU's path, the one of shapes
  that do not tile, and the reference the kernels are tested against. Its
  backward takes `jax.vjp` of a chunk: autodiff stacks a chunk's steps
  (three `[chunk, b, N, inner]` float32 arrays) to HBM and reads them back.
- **Two Pallas kernels, `selective_scan_fwd` and `selective_scan_bwd`,
  behind a `custom_vjp`.** The grid is (batch row, block of channels,
  chunk), the chunks innermost and in order. `u`, `dt`, `y` and their
  cotangents are taken as `[b, T, inner]` in blocks `[chunk, block]`, as the
  mixer makes them, with no transposed copy; `B` and `C` (16 values a
  token each) come as one array of columns, `[b, T / chunk, 2 N, chunk]`:
  `B`'s states then `C`'s down the sublanes, a token a lane. A step first
  spreads each token's column over 128 lanes into VMEM scratch (`[chunk,
  2 N, 128]` float32; one slice and one broadcast a token, unrolled: they
  are most of a body's equations, which is why `B` and `C` share them), so
  that inside the loop a token's `B_t` and `C_t` are read by the token's
  index and multiply every tile of 128 channels as they lie. The block's
  state, `[N, block]` float32, lives in VMEM scratch across the chunks of a
  row (zeroed at chunk 0); the
  tokens are walked eight a trip of a `fori_loop`, the state in the loop's
  carry, `dt_t` and `dt_t u_t` as rows spread down the sublanes, `y_t` the
  sum down the sublanes. From the forward rule the kernel also writes each
  chunk's entering state. `selective_scan_bwd` walks the chunks from the
  last to the first with the state's cotangent `[N, block]` in scratch:
  a step makes the chunk's `chunk` states again from its entering state
  into VMEM scratch (`[chunk + 1, N, block]` float32, 8 MB at 128 x 16 x
  1,024), then walks the tokens backward: `dh`, `ddt`, `du` a channel; `dB_t`
  and `dC_t` as sums over the block's channels (whole tiles of 128 lanes
  added first, then one sum along the lanes a token) set into their
  token's lane of `[N, chunk]`; `dA` added into a `[N, block]` accumulator
  that is written at a row's last chunk. `dB` and `dC` are sums over all
  channels and the blocks are a parallel grid axis: a partial a block,
  `[inner / block, b, T / chunk, 2 N, chunk]` float32 (10 MB at the cell's
  shape), summed outside. No `[chunk, b, N, inner]` array of a chunk's
  steps reaches HBM in either direction.
  `benchmarks/selective_scan_alone.py` times both paths alone.

**`"tokens"`** is the recurrence token by token under one `lax.scan` with
autodiff through it: the tests' reference. Its gradient keeps every
token's state.

The scan's state is not reset inside a sequence: rows are whole documents
(ROADMAP Queue 2: a reset at a document's boundary, with R6b and R12).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.flash_attention import (
    _DEFAULT_VMEM, _LANES, _MAX_VMEM, _pallas_call, resolve_impl)

logger = logging.getLogger(__name__)

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
                   impl: str = "auto", interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """(y [b, T, inner] float32, the state after the last token [b, inner,
    N] float32) of `u` [b, T, inner], `delta` [b, T, inner] (the step size,
    after its softplus), `A` [inner, N] (negative), `B` and `C` [b, T, N]
    and the skip `D` [inner].

    impl: 'auto' (the kernels on TPU, `jax.numpy` elsewhere) | 'pallas' |
    'chunked' (`jax.numpy`; 'xla' is `resolve_impl`'s name for it) |
    'tokens' (the recurrence, above); `interpret` runs the kernels in
    interpret mode, for tests. A shape that does not tile
    (`selective_scan_untiled`) takes `jax.numpy` whatever `impl` says."""
    b, T, inner = u.shape
    N = A.shape[1]
    A_t = A.astype(_F32).T  # [N, inner]: the channels along the lanes
    if impl == "tokens":
        by_token = tuple(x.swapaxes(0, 1) for x in (u, delta, B, C))
        last, y = jax.lax.scan(
            _step(A_t), jnp.zeros((b, N, inner), _F32), by_token)
        y = y.swapaxes(0, 1)
    elif impl in ("auto", "pallas", "chunked", "xla"):
        kernels = resolve_impl(impl) == "pallas" or interpret
        untiled = selective_scan_untiled(
            chunk, N, inner, jnp.dtype(u.dtype).itemsize)
        padded = -(-T // chunk) * chunk
        _log_selective_scan(kernels, untiled, b, padded, inner, N, chunk,
                            jnp.dtype(u.dtype).name)
        if kernels and not untiled:
            y, last = _scan_kernels(
                *(jnp.pad(x, ((0, 0), (0, padded - T), (0, 0)))
                  for x in (u, delta.astype(_F32), B, C)),
                A_t, chunk, channel_block(inner), interpret)
            y = y[:, :T]
        else:
            tokens = tuple(_by_chunk(x, chunk) for x in (u, delta, B, C))
            y, last = _chunked(tokens, A_t)
            y = y.transpose(2, 0, 1, 3).reshape(b, -1, inner)[:, :T]
    else:
        raise ValueError(
            f"impl {impl!r}: 'auto', 'pallas', 'chunked' or 'tokens'")
    return y + D.astype(_F32) * u.astype(_F32), last.swapaxes(1, 2)


# ----------------------------------------------------------------- kernels
#
# Float32 here is `_ACC`, not the module's `_F32`, which a test lowers to
# show what the `jax.numpy` scan in bf16 costs.

_ACC = jnp.float32
_TILE = 8     # tokens a trip of a kernel's loop: the rows of a float32 tile
# the most channels a grid step takes (the sweep: PERF.md section 6, PR 62)
_BLOCK = 1024


def channel_block(inner: int) -> int:
    """The channels a grid step takes: the largest multiple of 128 lanes
    that divides `inner` and is no more than `_BLOCK`."""
    return max((w for w in range(_LANES, _BLOCK + 1, _LANES)
                if inner % w == 0), default=0)


def selective_scan_vmem_bytes(kernel: str, Q: int, N: int, block: int,
                              itemsize: int) -> int:
    """An estimate of what a grid step of `selective_scan_fwd` or
    `selective_scan_bwd` holds in VMEM: its blocks double-buffered (u
    `[Q, block]`, dt and y or dy, du and ddt `[Q, block]` float32, B's and
    C's columns and their cotangents `[2 N, Q]`, A, the entering and the
    last state and their cotangents `[N, block]` float32) and its scratch:
    the state `[N, block]`, the columns spread over a tile's lanes `[Q, 2 N,
    128]` and `dt u` or u and du `[Q, block]`, float32, and for the
    backward `dA`'s sum and the chunk's `Q + 1` states."""
    wide, state = Q * block, N * block * 4
    columns, spread = N * Q, 2 * Q * N * _LANES * 4
    if kernel == "selective_scan_fwd":
        blocks = wide * (itemsize + 8) + 2 * columns * itemsize + 3 * state
        return 2 * blocks + state + wide * 4 + spread
    blocks = (wide * (2 * itemsize + 12) + 2 * columns * (itemsize + 4)
              + 4 * state)
    return 2 * blocks + (Q + 3) * state + 2 * wide * 4 + spread


def _vmem_limit(kernel, Q, N, block, itemsize) -> int:
    return max(_DEFAULT_VMEM, 2 * selective_scan_vmem_bytes(
        kernel, Q, N, block, itemsize))


def selective_scan_untiled(Q: int, N: int, inner: int,
                           itemsize: int) -> Optional[str]:
    """Why the kernels cannot take chunks of `Q` tokens, `N` states and
    `inner` channels of `itemsize` bytes, or None where they can: the
    channels whole tiles of 128 lanes, the states and the chunk's tokens
    whole tiles of sublanes (8 of float32, 16 of bf16), and a step of the
    backward within VMEM."""
    if inner % _LANES:
        return f"{inner} channels are no multiple of {_LANES} lanes"
    if N % _TILE:
        return f"{N} states are no multiple of {_TILE} sublanes"
    rows = _TILE * 4 // itemsize
    if Q % rows:
        return f"chunks of {Q} tokens are no multiple of {rows} sublanes"
    need = _vmem_limit("selective_scan_bwd", Q, N, channel_block(inner),
                       itemsize)
    if need > _MAX_VMEM:
        return (f"a step of selective_scan_bwd needs {need} bytes of VMEM, "
                f"over {_MAX_VMEM}")
    return None


@functools.lru_cache(maxsize=None)
def _log_selective_scan(kernels, untiled, b, T, inner, N, Q, dtype):
    """One line for each scan a process traces, as `ops/kda.py`'s
    `_log_kda`: which path, and the kernels' grid, block and VMEM."""
    shape = (f"selective_scan at b {b}, T {T}, inner {inner}, N {N}, "
             f"chunk {Q}, {dtype}")
    if not kernels:
        logger.info("%s: jax.numpy (selective_scan)", shape)
    elif untiled:
        logger.info("%s: jax.numpy (selective_scan), because %s", shape,
                    untiled)
    else:
        block, item = channel_block(inner), jnp.dtype(dtype).itemsize
        logger.info(
            "%s: selective_scan_fwd and selective_scan_bwd, grid (%d, %d, "
            "%d), blocks [%d, %d] of u, dt and y and [%d, %d] of B's and C's "
            "columns, a state of [%d, %d] float32, VMEM %d and %d bytes of "
            "limits of %d and %d", shape, b, inner // block, T // Q, Q, block,
            2 * N, Q, N, block, *(f(k, Q, N, block, item) for f in (
                selective_scan_vmem_bytes, _vmem_limit)
                for k in ("selective_scan_fwd", "selective_scan_bwd")))


def _spread_columns(cols_ref, spread):
    """A token's column of `cols_ref`'s block `[2 N, Q]` (B's states, then
    C's) over 128 lanes, into `spread` `[Q, 2 N, 128]` float32: what a
    token's step multiplies every tile of 128 channels by."""
    cols = cols_ref[0, 0].astype(_ACC)
    rows, Q = cols.shape
    for t in range(Q):
        spread[t] = jnp.broadcast_to(cols[:, t:t + 1], (rows, _LANES))


def _b_and_c(spread, t, width):
    """(`B_t`, `C_t`) `[N, width]` of the spread columns: the tile of 128
    lanes side by side."""
    both = spread[t]
    if width != _LANES:
        both = jnp.concatenate([both] * (width // _LANES), axis=1)
    N = both.shape[0] // 2
    return both[:N], both[N:]


def _after(h, dt_t, x_t, A, B_t):
    """The state after a token: `exp(dt_t A) h + (dt_t u_t) B_t`, `dt_t` and
    `x_t = dt_t u_t` rows `[1, w]`."""
    return jnp.exp(dt_t * A) * h + x_t * B_t


def _folded(x):
    """The sum of `x`'s tiles of 128 lanes, `[N, 128]`: whole registers
    added, before the one sum along the lanes."""
    return functools.reduce(
        jnp.add, [x[:, at:at + _LANES] for at in range(0, x.shape[1], _LANES)])


def _down(x):
    """The sum down the sublanes, `[1, w]`."""
    return jnp.sum(x, axis=0, keepdims=True)


def _rows(rows):
    return jnp.concatenate(rows, axis=0)


def _fwd_kernel(u_ref, dt_ref, a_ref, cols_ref, y_ref, last_ref, *rest):
    """One chunk of one block of channels: `rest` is the scratch (the state
    `[N, block]`, `dt u` `[Q, block]`, B's and C's spread columns), after
    the block of entering states where the forward rule asks."""
    from jax.experimental import pallas as pl

    state, dtu, spread = rest[-3:]
    Q, block = dtu.shape

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    if len(rest) == 4:
        rest[0][0, 0] = state[...]
    A = a_ref[...]
    dtu[...] = dt_ref[0] * u_ref[0].astype(_ACC)
    _spread_columns(cols_ref, spread)

    def trip(g, h):
        at = pl.multiple_of(g * _TILE, _TILE)
        dt, x = dt_ref[0, pl.ds(at, _TILE), :], dtu[pl.ds(at, _TILE), :]
        y = []
        for k in range(_TILE):
            B, C = _b_and_c(spread, at + k, block)
            h = _after(h, dt[k:k + 1], x[k:k + 1], A, B)
            y.append(_down(h * C))
        y_ref[0, pl.ds(at, _TILE), :] = _rows(y)
        return h

    h = jax.lax.fori_loop(0, Q // _TILE, trip, state[...])
    state[...] = h

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _last_chunk():
        last_ref[0] = h


def _bwd_kernel(u_ref, dt_ref, a_ref, cols_ref, entering_ref, dy_ref,
                dlast_ref, du_ref, ddt_ref, da_ref, dcols_ref, dstate,
                da_sum, states, u_wide, du_wide, spread):
    """The same chunk's cotangents; the grid walks the chunks backwards,
    `dstate` `[N, block]` holds the cotangent of the state the chunk
    leaves, `da_sum` `dA`'s sum over the row's chunks so far, and `states`
    `[Q + 1, N, block]` the chunk's states made again: the entering one,
    then the one after each token."""
    from jax.experimental import pallas as pl

    Q, block = u_wide.shape
    N = dstate.shape[0]
    trips = Q // _TILE

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = dlast_ref[0]
        da_sum[...] = jnp.zeros_like(da_sum)

    A = a_ref[...]
    u_wide[...] = u_ref[0].astype(_ACC)
    _spread_columns(cols_ref, spread)
    states[0] = entering_ref[0, 0]

    def again(g, h):
        at = pl.multiple_of(g * _TILE, _TILE)
        dt = dt_ref[0, pl.ds(at, _TILE), :]
        x = dt * u_wide[pl.ds(at, _TILE), :]
        for k in range(_TILE):
            h = _after(h, dt[k:k + 1], x[k:k + 1], A,
                       _b_and_c(spread, at + k, block)[0])
            states[at + k + 1] = h
        return h

    jax.lax.fori_loop(0, trips, again, states[0])
    lane = jax.lax.broadcasted_iota(jnp.int32, (N, Q), 1)

    def trip(i, carry):
        dh, dA, dB, dC = carry
        at = pl.multiple_of((trips - 1 - i) * _TILE, _TILE)
        dt = dt_ref[0, pl.ds(at, _TILE), :]
        u = u_wide[pl.ds(at, _TILE), :]
        dy = dy_ref[0, pl.ds(at, _TILE), :]
        x = dt * u
        through_decay, through_x = [None] * _TILE, [None] * _TILE
        for k in reversed(range(_TILE)):
            t = at + k
            before, after = states[t], states[t + 1]
            B, C = _b_and_c(spread, t, block)
            dh = dh + dy[k:k + 1] * C
            dC = jnp.where(lane == t, jnp.sum(
                _folded(after * dy[k:k + 1]), axis=1, keepdims=True), dC)
            dB = jnp.where(lane == t, jnp.sum(
                _folded(dh * x[k:k + 1]), axis=1, keepdims=True), dB)
            decay = jnp.exp(dt[k:k + 1] * A)
            # the exponent's cotangent: of `dt_t A`, a channel and state
            dexp = dh * before * decay
            through_decay[k] = _down(dexp * A)
            through_x[k] = _down(dh * B)
            dA = dA + dexp * dt[k:k + 1]
            dh = dh * decay
        dx = _rows(through_x)
        du_wide[pl.ds(at, _TILE), :] = dx * dt
        ddt_ref[0, pl.ds(at, _TILE), :] = _rows(through_decay) + dx * u
        return dh, dA, dB, dC

    zeros = jnp.zeros((N, Q), _ACC)
    dh, dA, dB, dC = jax.lax.fori_loop(
        0, trips, trip, (dstate[...], jnp.zeros_like(A), zeros, zeros))
    dstate[...] = dh
    da_sum[...] += dA
    du_ref[0] = du_wide[...].astype(du_ref.dtype)
    dcols_ref[0, 0, 0] = jnp.concatenate([dB, dC], axis=0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _first_chunk():
        da_ref[0] = da_sum[...]


# how each operand and result of the kernels lies, by its place in the call
_TOKENS, _DECAY, _COLUMNS, _STATE, _STATES, _PARTS = range(6)
_INPUTS = (_TOKENS, _TOKENS, _DECAY, _COLUMNS)


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4, 5, 6, 7))
def _scan_call(kernel, name, operands, kinds, out_shapes, out_kinds, block,
               interpret):
    """`pallas_call` of `selective_scan_fwd` or `selective_scan_bwd` (which
    walks the chunks backwards) over (batch row, block of channels, chunk).
    Blocks: `[Q, block]` of tokens `[b, T, inner]`; `[N, block]` of `A`
    `[N, inner]`; a chunk's columns `[2 N, Q]` of `[b, n, 2 N, Q]`; `[N,
    block]` of a row's state `[b, N, inner]` (the last one, its cotangent,
    `dA`'s part) and of a chunk's entering state `[b, n, N, inner]`; a block's
    part of the columns' cotangents `[2 N, Q]` of `[inner / block, b, n,
    2 N, Q]`. Under `jit`: a model's layers of one shape share one trace of
    a kernel's body (`ops/kda.py` `_kda_call`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    u, _, A, cols = operands[:4]
    b, T, inner = u.shape
    N, n, Q = A.shape[0], cols.shape[1], cols.shape[3]
    backward = name == "selective_scan_bwd"
    chunk = (lambda c: n - 1 - c) if backward else (lambda c: c)

    def spec(kind):
        if kind == _TOKENS:
            return pl.BlockSpec((1, Q, block),
                                lambda i, j, c: (i, chunk(c), j))
        if kind == _DECAY:
            return pl.BlockSpec((N, block), lambda i, j, c: (0, j))
        if kind == _COLUMNS:
            return pl.BlockSpec((1, 1, 2 * N, Q),
                                lambda i, j, c: (i, chunk(c), 0, 0))
        if kind == _STATE:
            return pl.BlockSpec((1, N, block), lambda i, j, c: (i, 0, j))
        if kind == _STATES:
            return pl.BlockSpec((1, 1, N, block),
                                lambda i, j, c: (i, chunk(c), 0, j))
        return pl.BlockSpec((1, 1, 1, 2 * N, Q),
                            lambda i, j, c: (j, i, chunk(c), 0, 0))

    state, wide = pltpu.VMEM((N, block), _ACC), pltpu.VMEM((Q, block), _ACC)
    spread = pltpu.VMEM((Q, 2 * N, _LANES), _ACC)
    scratch = [state, wide, spread]
    if backward:
        scratch = [state, state, pltpu.VMEM((Q + 1, N, block), _ACC), wide,
                   wide, spread]
    return _pallas_call(
        kernel,
        grid=(b, inner // block, n),
        in_specs=[spec(kind) for kind in kinds],
        out_specs=[spec(kind) for kind in out_kinds],
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                name, Q, N, block, jnp.dtype(u.dtype).itemsize)),
        interpret=interpret,
        name=name,
    )(*operands)


def _scan_fwd(u, dt, A, cols, block, interpret, with_states: bool = False):
    b, _, inner = u.shape
    N, n = A.shape[0], cols.shape[1]
    out = [jax.ShapeDtypeStruct(u.shape, _ACC),
           jax.ShapeDtypeStruct((b, N, inner), _ACC)]
    if with_states:
        out.append(jax.ShapeDtypeStruct((b, n, N, inner), _ACC))
    with jax.named_scope("selective_scan"):
        return _scan_call(
            _fwd_kernel, "selective_scan_fwd", (u, dt, A, cols), _INPUTS,
            tuple(out), (_TOKENS, _STATE, _STATES)[:len(out)], block,
            interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scan(u, dt, A, cols, block, interpret):
    return tuple(_scan_fwd(u, dt, A, cols, block, interpret))


def _scan_vjp_fwd(u, dt, A, cols, block, interpret):
    y, last, entering = _scan_fwd(u, dt, A, cols, block, interpret,
                                  with_states=True)
    # kept with the scan's output, as the `jax.numpy` path's (above)
    entering = checkpoint_name(entering, "scan_out")
    return (y, last), (u, dt, A, cols, entering)


def _scan_vjp_bwd(block, interpret, res, cotangents):
    u, dt, A, cols, entering = res
    dy, dlast = cotangents
    b, _, inner = u.shape
    shapes = (jax.ShapeDtypeStruct(u.shape, u.dtype),
              jax.ShapeDtypeStruct(dt.shape, _ACC),
              jax.ShapeDtypeStruct((b, *A.shape), _ACC),
              jax.ShapeDtypeStruct((inner // block, *cols.shape), _ACC))
    with jax.named_scope("selective_scan"):
        du, ddt, dA, dcols = _scan_call(
            _bwd_kernel, "selective_scan_bwd",
            (u, dt, A, cols, entering, dy, dlast),
            (*_INPUTS, _STATES, _TOKENS, _STATE), shapes,
            (_TOKENS, _TOKENS, _STATE, _PARTS), block, interpret)
        # the rows' parts of dA and the blocks' parts of dB and dC
        return (du, ddt, dA.sum(axis=0),
                dcols.sum(axis=0).astype(cols.dtype))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _scan_kernels(u, dt, B, C, A, Q, block, interpret):
    """(y [b, T, inner], the last state [b, N, inner]) of whole chunks of
    `Q` tokens by `selective_scan_fwd` and `selective_scan_bwd`: B's and
    C's columns, the one layout the kernels do not take as it comes, are
    made here (`[b, n, 2 N, Q]`, 32 values a token), and autodiff turns
    their cotangent back."""
    b, T, _ = u.shape
    cols = jnp.concatenate([B, C], axis=-1).reshape(
        b, T // Q, Q, 2 * A.shape[0]).swapaxes(2, 3)
    return _scan(u, dt, A, cols, block, interpret)
