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
on `Q` beyond rounding.

Two paths compute that, part by part and at the same dtypes. `ssd` takes
the kernels where the step's operators resolve to Pallas (`impl`, as `mha`
and the grouped matmul: the TPU) and the shapes tile (`scan_untiled`), and
says once a shape which it took (`_log_scan`):

- **`jax.numpy` under the scopes `ssd_chunk`, `ssd_state`, `ssd_out`**, with
  autodiff through it: the CPU's path, the one of shapes that do not tile,
  and the reference the kernels are tested against. Every `[b, n, H, Q, Q]`
  array of it (span, decay, the masked scores and their cotangents)
  crosses HBM.
- **Two Pallas kernels, `ssd_fwd` and `ssd_bwd`, behind a `custom_vjp`.**
  The grid is (batch row, group of `B` and `C`, chunk), the chunks
  innermost and in order; a step is one chunk of the `R = H / G` heads that
  share a group. `x`, `y` and their cotangents are taken as `[b, T, H P]` in
  blocks `[Q, R P]`, `B` and `C` as `[b, T, G N]` in blocks `[Q, N]`: as they
  leave the convolution, lane-dense, with no transposed copy. `dt` and
  `cum` (4 MB together) come as columns `[b, G, T, R]` and `cum` as rows
  `[b, G, R, T]` too, so that a head's decay tile `exp(where(i >= j, cum_i -
  cum_j, -inf))` is a column less a row. `dt x` is formed in the kernel.
  The group's state lives transposed, `[N, R P]` float32, in VMEM scratch
  across the chunks (zeroed at chunk 0), so every per-head factor
  (`dt`, `exp(cum)`, `exp(cum_Q - cum)`, `exp(cum_Q)`, `D`) is a row or a
  `[Q, R P]` array spread over the head's `P` lanes (`_spread`), and the
  products with the state are full width: `C @ state` is `[Q, N] x [N, R P]`,
  the state's update `B^T @ (dt x to_end)` is `[N, Q] x [Q, R P]`. Only
  `mixed_h @ (dt x)_h` is `P` wide; where `P` is under a tile's 128 lanes
  the heads of a tile are taken one at a time against the tile with the
  other heads' lanes zeroed (the MXU's pass is 128 wide either way), so
  no slice, store or concatenation is narrower than 128 lanes. A group
  whose heads do not fit a step's VMEM (`head_tile`: one group of 64 heads
  at chunks of 256 would hold 73 MB in `ssd_bwd`) is taken a TILE of its
  heads a step: the grid's second axis is then (group, tile), every tile
  a group of its own but for `B` and `C`, whose blocks the index maps
  share among a group's tiles (each tile makes the group's `scores`
  again: one `[Q, N] x [N, Q]` product beside a product as large for
  every head of the tile), and `ssd_bwd` writes a tile's `dB` and `dC` in
  float32, summed over the tiles outside. A group that fits is one tile,
  and the call is the one it was. From the forward rule the kernel also
  writes each chunk's entering state (`[b, n, G, N, R P]` float32; tile
  by tile where a group is tiled), the backward's one residual beside the
  inputs. `ssd_bwd` walks the chunks from the last to the first with the
  state's cotangent in the same scratch, makes `scores` and the decay
  tiles again from `B`, `C` and `cum`, and returns `dx`, `dB` and `dC`
  (summed over the group's heads in the step), `d dt` and `d cum` as
  columns and the part of `d cum` that falls out as rows. The tails stay
  `jax.numpy`: `cum`'s running sum, `a = dt A` and `D` spread over its
  head's lanes (autodiff's, outside the `custom_vjp`) and `dD = sum(dy x)`
  in the backward rule. Nothing of shape `[.., Q, Q]` and
  no per-chunk intermediate but the entering states reaches HBM.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (
    _DEFAULT_VMEM, _LANES, _MAX_VMEM, _NN, _NT, _TN, _dot, _pallas_call,
    resolve_impl)
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

_F32 = jnp.float32


def _heads_of_groups(grouped, heads: int):
    """[..., G, Q, Q'] repeated to the heads that share each group."""
    groups = grouped.shape[-3]
    return grouped if groups == heads else jnp.repeat(
        grouped, heads // groups, axis=-3)


def ssd(x, dt, A, B, C, D, *, chunk: int = 128, impl: str = "auto",
        interpret: bool = False):
    """`y` [b, T, H, P] of inputs `x` [b, T, H, P], step sizes `dt`
    [b, T, H] (positive: after the softplus), decay rates `A` [H]
    (negative), `B` and `C` [b, T, G, N] with `G` dividing `H`, and the
    skip `D` [H], by the recurrence above in chunks of `chunk` tokens. A `T`
    that is no multiple of the chunk is padded with steps of `dt = 0`,
    which leave the state as it is. `y` is in x's dtype.

    impl: 'auto' (the kernels on TPU, `jax.numpy` elsewhere) | 'pallas' |
    'xla'; `interpret` runs the kernels in interpret mode, for tests. A
    shape that does not tile (`scan_untiled`) takes `jax.numpy` whatever
    `impl` says."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    if H % G:
        raise ValueError(f"{G} groups of B and C do not divide {H} heads")
    Q = min(chunk, T)
    pad = (-T) % Q
    dt = dt.astype(_F32)
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, B, C))
    kernels = resolve_impl(impl) == "pallas" or interpret
    untiled = scan_untiled(Q, N, H // G, P)
    _log_scan(kernels, untiled, b, T + pad, H, P, G, N, Q,
              jnp.dtype(x.dtype).name)
    A, D = A.astype(_F32), D.astype(_F32)
    if kernels and not untiled:
        tracing.count("train.ssd_calls_kernels")
        return _scan_kernels(x, dt, A, B, C, D, Q, interpret)[:, :T]
    tracing.count("train.ssd_calls_numpy")
    return _scan_numpy(x, dt, A, B, C, D, Q)[:, :T]


def _scan_numpy(x, dt, A, B, C, D, Q):
    """The scan of whole chunks of `Q` tokens in `jax.numpy`."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    n = T // Q
    dtype = x.dtype
    a = (dt * A).reshape(b, n, Q, H)
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
        y = y.reshape(b, T, H, P)
        return (y + x.astype(_F32) * D[:, None]).astype(dtype)


# ----------------------------------------------------------------- kernels

def scan_untiled(Q: int, N: int, R: int, P: int) -> Optional[str]:
    """Why the kernels cannot take chunks of `Q` tokens, a state of `N`, and
    groups of `R` heads of `P` channels, or None where they can: `Q`, `N`
    and `R P` whole tiles of 128 lanes, and `P` a divisor or a multiple of
    128 so that a tile holds whole heads or a head whole tiles."""
    for name, size in (("chunk", Q), ("state", N),
                       ("a group's channels", R * P)):
        if size % _LANES:
            return f"{name} {size} is no multiple of {_LANES}"
    if _LANES % P and P % _LANES:
        return f"heads of {P} neither divide nor fill tiles of {_LANES} lanes"
    return None


def scan_vmem_bytes(kernel: str, Q: int, N: int, RP: int,
                    itemsize: int) -> int:
    """An estimate of what a grid step of `ssd_fwd` or `ssd_bwd` holds in
    VMEM: its blocks, double-buffered (x, y or x, dy, dx `[Q, R P]`; B, C
    and their cotangents `[Q, N]`; the entering state `[N, R P]` float32;
    the columns and rows of dt and cum at a tile's 128 lanes), the state's
    scratch, and the body's live float32 `[Q, R P]` and `[Q, Q]` arrays."""
    wide, narrow, state = Q * RP, Q * N, N * RP * 4
    small = Q * _LANES * 4
    if kernel == "ssd_fwd":
        blocks = (2 * wide + 2 * narrow) * itemsize + state + 3 * small
        live = 6 * wide * 4 + 4 * Q * Q * 4
    else:
        blocks = (3 * wide + 4 * narrow) * itemsize + state + 6 * small
        live = 12 * wide * 4 + 8 * Q * Q * 4
    return 2 * blocks + state + live


def _vmem_limit(kernel, Q, N, RP, itemsize) -> int:
    need = 2 * scan_vmem_bytes(kernel, Q, N, RP, itemsize)
    return min(max(_DEFAULT_VMEM, need), _MAX_VMEM)


# the most a grid step of `ssd_bwd` may hold by `scan_vmem_bytes` before a
# group's heads are taken in tiles: what `_vmem_limit` can still ask twice
# of. One group of 64 heads of 64 at chunks of 256 (73.4 MB whole) then goes
# 32 heads a tile, 38.8 MB: of 4, 8, 16, 32 and 64 a tile the fastest alone
# on the chip (18.0, 15.9, 15.2, 14.8, 15.6 ms forward and backward at
# 32,768 tokens) and but for 64 the least in HBM, a tile's columns of dt
# and cum lying there at 128 lanes (PERF.md section 6, PR 74)
_STEP_VMEM = _MAX_VMEM // 2


def head_tile(Q: int, N: int, R: int, P: int, itemsize: int) -> int:
    """The heads of a group that a grid step takes: all `R` where
    `ssd_bwd`'s estimate (`scan_vmem_bytes`) stands under `_STEP_VMEM`, else
    the most heads that divide `R`, fill whole tiles of 128 lanes and stand
    under it (the fewest such, if none does)."""
    whole = [r for r in range(R, 0, -1)
             if R % r == 0 and not r * P % _LANES]
    return next((r for r in whole if scan_vmem_bytes(
        "ssd_bwd", Q, N, r * P, itemsize) <= _STEP_VMEM), whole[-1])


@functools.lru_cache(maxsize=None)
def _log_scan(kernels, untiled, b, T, H, P, G, N, Q, dtype):
    """One line for each scan a process traces, as `_log_bwd_kernels` and
    `saved_activations` have theirs: which path, and the kernels' grid,
    blocks and VMEM."""
    shape = (f"ssd at b {b}, T {T}, H {H}, P {P}, G {G}, N {N}, chunk {Q}, "
             f"{dtype}")
    if not kernels:
        logger.info("%s: jax.numpy (ssd_chunk, ssd_state, ssd_out)", shape)
    elif untiled:
        logger.info("%s: jax.numpy (ssd_chunk, ssd_state, ssd_out), because "
                    "%s", shape, untiled)
    else:
        item = jnp.dtype(dtype).itemsize
        heads = head_tile(Q, N, H // G, P, item)
        RP = heads * P
        logger.info(
            "%s: ssd_fwd and ssd_bwd, %d heads a tile, grid (%d, %d, %d), "
            "blocks [%d, %d] of x and [%d, %d] of B and C, a state of "
            "[%d, %d] float32, VMEM %d and %d bytes of limits of %d and %d",
            shape, heads, b, H // heads, T // Q, Q, RP,
            Q, N, N, RP, *(f(k, Q, N, RP, item) for f in (
                scan_vmem_bytes, _vmem_limit) for k in ("ssd_fwd", "ssd_bwd")))


def _lane_tiles(R, P):
    """(lanes of a tile of whole heads, heads in it, tiles of `R` heads)."""
    width = max(P, _LANES)
    return width, width // P, R * P // width


def _beside(tiles):
    """Tiles of whole lanes side by side."""
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _spread(cols, P):
    """`[rows, R P]` whose lanes of head `h` hold `cols[h]` (`[rows, 1]`)."""
    rows = cols[0].shape[0]
    width, heads, tiles = _lane_tiles(len(cols), P)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = []
    for t in range(tiles):
        tile = jnp.broadcast_to(cols[t * heads], (rows, width))
        for k in range(1, heads):
            tile = jnp.where(lane >= k * P, cols[t * heads + k], tile)
        out.append(tile)
    return _beside(out)


def _head_lanes(rows, width, heads, P, k):
    """The lanes of a tile's head `k`, `[rows, width]`; None: all of them."""
    if heads == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    return (lane >= k * P) & (lane < (k + 1) * P)


def _of_head(tile, lanes):
    return tile if lanes is None else jnp.where(lanes, tile, 0)


def _head_sums(z, R, P):
    """Each head's sum over its `P` lanes of `z` `[rows, R P]`: `R` columns
    `[rows, 1]`."""
    width, heads, tiles = _lane_tiles(R, P)
    return [
        _of_head(z[:, t * width:(t + 1) * width],
                 _head_lanes(z.shape[0], width, heads, P, k)
                 ).sum(axis=1, keepdims=True)
        for t in range(tiles) for k in range(heads)]


def _columns(v):
    return [v[:, h:h + 1] for h in range(v.shape[1])]


def _chunk_parts(x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, P):
    """What both kernels make of a step's blocks: x, B, C, the columns
    `[Q, R]` of dt and cum and cum's rows `[R, Q]`, `dt x` in x's dtype, the
    group's scores and the causal mask."""
    x, Bm, Cm = x_ref[0], b_ref[0], c_ref[0]
    dtc, cumc, cumr = dtc_ref[0, 0], cumc_ref[0, 0], cumr_ref[0, 0]
    Q = x.shape[0]
    dt_x = _spread(_columns(dtc), P)
    xdt = (x.astype(_F32) * dt_x).astype(x.dtype)
    scores = _dot(Cm, Bm, _NT)                             # [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    return x, Bm, Cm, cumc, cumr, dt_x, xdt, scores, causal


def _decay(cumc, cumr, h, causal):
    """Head `h`'s `L`: masked before the exp, as `_scan_numpy` has it."""
    span = cumc[:, h:h + 1] - cumr[h:h + 1, :]
    return jnp.exp(jnp.where(causal, span, -jnp.inf))


def _ssd_fwd_kernel(x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, d_ref,
                    y_ref, *rest, P: int):
    """One chunk of one group: `rest` is the state's scratch `[N, R P]`,
    after the block of entering states where the forward rule asks."""
    from jax.experimental import pallas as pl

    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    x, Bm, Cm, cumc, cumr, _, xdt, scores, causal = _chunk_parts(
        x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, P)
    Q, dtype = x.shape[0], x.dtype
    R = cumc.shape[1]
    width, heads, tiles = _lane_tiles(R, P)
    entering = state[...]
    if len(rest) == 2:
        rest[0][0, 0, 0] = entering

    within = []                                            # ssd_chunk
    for t in range(tiles):
        tile, acc = xdt[:, t * width:(t + 1) * width], None
        for k in range(heads):
            h = t * heads + k
            mixed = (scores * _decay(cumc, cumr, h, causal)).astype(dtype)
            part = _dot(mixed, _of_head(
                tile, _head_lanes(Q, width, heads, P, k)), _NN)
            acc = part if acc is None else acc + part
        within.append(acc)
    y = _beside(within)
    # ssd_out: what the entering state adds, all the group's heads at once
    y = y + _dot(Cm, entering.astype(dtype), _NN) * _spread(
        _columns(jnp.exp(cumc)), P)
    y_ref[0] = (y + x.astype(_F32) * d_ref[0]).astype(dtype)
    # ssd_state: the state the next chunk enters with
    last = cumc[Q - 1:Q, :]                                # [1, R]
    decayed = (xdt.astype(_F32) * _spread(
        _columns(jnp.exp(last - cumc)), P)).astype(dtype)
    state[...] = (_spread(_columns(jnp.exp(last)), P) * entering
                  + _dot(Bm, decayed, _TN))


def _ssd_bwd_kernel(x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, d_ref,
                    entering_ref, dy_ref, dx_ref, ddtc_ref, dcumc_ref,
                    dcumr_ref, db_ref, dc_ref, dstate, *, P: int):
    """The same chunk's cotangents; the grid walks the chunks backwards and
    `dstate` `[N, R P]` holds the cotangent of the state the chunk leaves."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    x, Bm, Cm, cumc, cumr, dt_x, xdt, scores, causal = _chunk_parts(
        x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, P)
    Q, dtype = x.shape[0], x.dtype
    R = cumc.shape[1]
    width, heads, tiles = _lane_tiles(R, P)
    dy = dy_ref[0]
    g = dy.astype(_F32)
    entering = entering_ref[0, 0, 0]                       # [N, R P] float32
    leaving = dstate[...]
    low_entering, low_leaving = entering.astype(dtype), leaving.astype(dtype)
    last = cumc[Q - 1:Q, :]
    to_end_x = _spread(_columns(jnp.exp(last - cumc)), P)
    ecum_x = _spread(_columns(jnp.exp(cumc)), P)
    elast_x = _spread(_columns(jnp.exp(last)), P)          # [1, R P]
    decayed = xdt.astype(_F32) * to_end_x

    # ssd_out: y += exp(cum) (C @ entering)
    carried = _dot(Cm, low_entering, _NN) * ecum_x
    dcarried = (g * ecum_x).astype(dtype)
    dC = _dot(dcarried, low_entering, _NT)                 # [Q, N]
    # ssd_state: leaving = exp(last) entering + B^T @ decayed
    ddecayed = _dot(Bm, low_leaving, _NN)                  # [Q, R P]
    dB = _dot(decayed.astype(dtype), low_leaving, _NT)     # [Q, N]
    dstate[...] = elast_x * leaving + _dot(Cm, dcarried, _TN)

    # ssd_chunk, a head at a time: y_h += (scores L_h) @ (dt x)_h
    dxdt, dscores, to_rows, to_cols = [], jnp.zeros((Q, Q), _F32), [], []
    for t in range(tiles):
        lo = t * width
        tile, dy_tile, acc = xdt[:, lo:lo + width], dy[:, lo:lo + width], None
        for k in range(heads):
            h = t * heads + k
            decay = _decay(cumc, cumr, h, causal)
            mixed = (scores * decay).astype(dtype)
            dy_h = _of_head(dy_tile, _head_lanes(Q, width, heads, P, k))
            part = _dot(mixed, dy_h, _TN)                  # mixed^T @ dy
            acc = part if acc is None else acc + part
            dmixed = _dot(dy_h, tile, _NT) * decay         # [Q, Q]
            dscores = dscores + dmixed
            dspan = dmixed * scores
            to_rows.append(dspan.sum(axis=1, keepdims=True))    # d cum_i
            to_cols.append(-dspan.sum(axis=0, keepdims=True))   # d cum_j
        dxdt.append(acc)
    dxdt = _beside(dxdt) + ddecayed * to_end_x
    dscores = dscores.astype(dtype)
    dc_ref[0] = (dC + _dot(dscores, Bm, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (dB + _dot(dscores, Cm, _TN)).astype(db_ref.dtype)
    dx_ref[0] = (dxdt * dt_x + g * d_ref[0]).astype(dtype)

    # a token and head: d dt through `dt x`; d cum through exp(cum) and
    # exp(last - cum), with d last on the chunk's last token
    through_end = ddecayed * decayed
    dlast = (through_end.sum(axis=0, keepdims=True)
             + elast_x * (leaving * entering).sum(axis=0, keepdims=True))
    row = jax.lax.broadcasted_iota(jnp.int32, through_end.shape, 0)
    dcum = g * carried - through_end + jnp.where(row == Q - 1, dlast, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, R), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (R, Q), 0)
    ddtc, dcumc = jnp.zeros((Q, R), _F32), jnp.zeros((Q, R), _F32)
    dcumr = jnp.zeros((R, Q), _F32)
    for h, (ddt_h, dcum_h) in enumerate(zip(
            _head_sums(dxdt * x.astype(_F32), R, P), _head_sums(dcum, R, P))):
        ddtc = jnp.where(lane == h, ddt_h, ddtc)
        dcumc = jnp.where(lane == h, dcum_h + to_rows[h], dcumc)
        dcumr = jnp.where(sublane == h, to_cols[h], dcumr)
    ddtc_ref[0, 0], dcumc_ref[0, 0], dcumr_ref[0, 0] = ddtc, dcumc, dcumr


# how each operand and result of the kernels lies, by its place in the call
_TOKENS, _COLUMNS, _ROWS, _SKIP, _STATES, _SHARED = range(6)
_INPUTS = (_TOKENS, _COLUMNS, _COLUMNS, _ROWS, _SHARED, _SHARED, _SKIP)
# the skip's is `jax.numpy`'s; dB and dC are a tile's own
_GRADS = (_TOKENS, _COLUMNS, _COLUMNS, _ROWS, _TOKENS, _TOKENS)


def _scan_call(kernel, name, operands, kinds, out_shapes, out_kinds, P, n,
               tiles, interpret):
    """`pallas_call` of `ssd_fwd` or `ssd_bwd` (which walks the chunks
    backwards) over (batch row, group, chunk); `G` and `R` below are the
    tiles of heads of all the groups and a tile's heads, `tiles` a group.
    Blocks: `[Q, w]` of tokens `[b, T, G w]`, `[Q, N]` of a group's `B` and
    `C`, shared by its `tiles`, `[Q, R]` of columns `[b, G, T, R]`, `[R, Q]`
    of rows `[b, G, R, T]`, a tile's skip `[1, R P]` of `[G, 1, R P]`, a
    chunk's entering state `[N, R P]` of `[b, n, G, N, R P]`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, _, cumc, _, Bm = operands[:5]
    b, T = x.shape[:2]
    G, R = cumc.shape[1], cumc.shape[3]
    N, RP, Q = Bm.shape[2] * tiles // G, R * P, T // n
    chunk = (lambda c: n - 1 - c) if name == "ssd_bwd" else (lambda c: c)
    group = (lambda g: g // tiles) if tiles > 1 else (lambda g: g)

    def spec(a, kind):
        if kind == _SHARED:
            return pl.BlockSpec((1, Q, N),
                                lambda i, g, c: (i, chunk(c), group(g)))
        if kind == _TOKENS:
            return pl.BlockSpec((1, Q, a.shape[2] // G),
                                lambda i, g, c: (i, chunk(c), g))
        if kind == _COLUMNS:
            return pl.BlockSpec((1, 1, Q, R),
                                lambda i, g, c: (i, g, chunk(c), 0))
        if kind == _ROWS:
            return pl.BlockSpec((1, 1, R, Q),
                                lambda i, g, c: (i, g, 0, chunk(c)))
        if kind == _SKIP:
            return pl.BlockSpec((1, 1, RP), lambda i, g, c: (g, 0, 0))
        return pl.BlockSpec((1, 1, 1, N, RP),
                            lambda i, g, c: (i, chunk(c), g, 0, 0))

    return _pallas_call(
        functools.partial(kernel, P=P),
        grid=(b, G, n),
        in_specs=[spec(a, k) for a, k in zip(operands, kinds)],
        out_specs=[spec(a, k) for a, k in zip(out_shapes, out_kinds)],
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((N, RP), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                name, Q, N, RP, jnp.dtype(x.dtype).itemsize)),
        interpret=interpret,
        name=name,
    )(*operands)


def _ssd_fwd(x, dtc, cumc, cumr, Bm, Cm, skip, P, n, tiles, interpret,
             with_states: bool = False):
    b = x.shape[0]
    G, R = cumc.shape[1], cumc.shape[3]
    out = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if with_states:
        out.append(jax.ShapeDtypeStruct(
            (b, n, G, Bm.shape[2] * tiles // G, R * P), _F32))
    got = _scan_call(
        _ssd_fwd_kernel, "ssd_fwd", (x, dtc, cumc, cumr, Bm, Cm, skip),
        _INPUTS, out, (_TOKENS, _STATES), P, n, tiles, interpret)
    return got if with_states else got[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _scan(x, dtc, cumc, cumr, Bm, Cm, skip, P, n, tiles, interpret):
    return _ssd_fwd(x, dtc, cumc, cumr, Bm, Cm, skip, P, n, tiles, interpret)


def _scan_vjp_fwd(x, dtc, cumc, cumr, Bm, Cm, skip, P, n, tiles, interpret):
    y, entering = _ssd_fwd(x, dtc, cumc, cumr, Bm, Cm, skip, P, n, tiles,
                           interpret, with_states=True)
    return y, (x, dtc, cumc, cumr, Bm, Cm, skip, entering)


def _scan_vjp_bwd(P, n, tiles, interpret, res, dy):
    x, dtc, cumc, cumr, Bm, Cm, skip, _ = res
    b, T, GN = Bm.shape
    # a tile's own dB and dC, `[b, T, (G, tiles) N]`, float32 where they
    # are summed: rounded a tile, the sum reads 4e-3 from `jax.numpy`'s
    shapes = [jax.ShapeDtypeStruct(v.shape, v.dtype)
              for v in (x, dtc, cumc, cumr)] + 2 * [jax.ShapeDtypeStruct(
                  (b, T, tiles * GN), _F32 if tiles > 1 else Bm.dtype)]
    grads = _scan_call(
        _ssd_bwd_kernel, "ssd_bwd", (*res, dy), (*_INPUTS, _STATES, _TOKENS),
        shapes, _GRADS, P, n, tiles, interpret)
    if tiles > 1:  # summed over a group's tiles
        N = GN * tiles // cumc.shape[1]
        grads = (*grads[:4], *(
            g.reshape(b, T, -1, tiles, N).sum(axis=3).astype(
                Bm.dtype).reshape(b, T, GN) for g in grads[4:]))
    # left to XLA, which makes this sum in the fusion that makes dy from the
    # gate's backward (PERF.md section 6, PR 39)
    dskip = (dy.astype(_F32) * x.astype(_F32)).sum(axis=(0, 1)).reshape(
        skip.shape)
    return (*grads, dskip)


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _scan_kernels(x, dt, A, B, C, D, Q, interpret,
                  heads: Optional[int] = None):
    """The scan of whole chunks of `Q` tokens by `ssd_fwd` and `ssd_bwd`:
    the layouts they take, made here; `a = dt A` and its running sums are
    `jax.numpy`, and autodiff's. `heads` a tile (`head_tile`'s where None:
    the tests name others) make the kernels' groups, `tiles` of them
    sharing a group's `B` and `C`."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    n = T // Q
    R = heads or head_tile(Q, N, H // G, P, jnp.dtype(x.dtype).itemsize)
    tiles = H // G // R
    cum = jnp.cumsum((dt * A).reshape(b, n, Q, H), axis=2)

    def columns(v):                                        # [b, G tiles, T, R]
        return v.reshape(b, T, H // R, R).transpose(0, 2, 1, 3)

    cumc = columns(cum)
    y = _scan(x.reshape(b, T, H * P), columns(dt), cumc, cumc.swapaxes(2, 3),
              B.reshape(b, T, G * N), C.reshape(b, T, G * N),
              jnp.repeat(D, P).reshape(H // R, 1, R * P), P, n, tiles,
              interpret)
    return y.reshape(b, T, H, P)
