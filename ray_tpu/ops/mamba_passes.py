"""The two bandwidth passes of a Mamba-2 mixer, each as one pass over HBM,
with the first the KDA mixer's short convolutions, and beside the second
the KDA mixer's output norm and gate.

Beside its matmuls and its scan (`ops/ssd.py`) the mixer runs two passes
that multiply no matrix, and whose time is the bytes they move:

- **`causal_conv_silu(x, w, bias)`**: `silu(sum_i w_i x_(t - taps + 1 + i) +
  bias)` a channel of `x` [B, T, C], `w` [taps, C], `x` zero before the
  sequence: the short convolution over x, B and C, handed on as the arrays
  the scan takes (`splits`: the channels' widths, `[B, T, inner]` and twice
  `[B, T, G N]`). With `unit`, each head of `unit` channels of the silu's
  result then over its own length, `out rsqrt(sum out^2 + 1e-6)` in float32:
  the KDA mixer's q and k (`models/transformer.py` `_kda_mixer`, scope
  `kda_conv`: three calls a layer, q's and k's with `unit = kda_head_dim`,
  v's without, no bias, under `name="kda_conv"`; PR 67).
- **`gated_group_rmsnorm(y, z, weight, groups, eps)`**: `GroupRMSNorm(y *
  silu(z))`, the mean square over each of `groups` groups of channels, one
  learned scale a channel.
- **`group_rmsnorm_gated(o, z, bias, weight, groups, eps)`** (PR 69): the
  KDA mixer's own pass behind its recurrence (`ops/kda.py`),
  `RMSNorm(o) * weight * sigmoid(z + bias)`: the norm first, over each of
  `groups` heads, one learned scale `[dk]` shared by the heads, and the gate
  outside the norm, a sigmoid with a bias (`_kda_mixer`, scope `kda_out`).
  Another formula than Mamba-2's and another backward rule, so kernel bodies
  of its own (`_gated_fwd_kernel`, `_gated_bwd_kernel`), chosen by which of
  the two functions a layer calls; the grid, the specs, the blocks, the
  conditions and the log line are the gated norm's (`_norm_call`,
  `norm_blocks`, `norm_untiled`, `_log_pass`, `pass_vmem_bytes`).

Two paths compute each, as `ops/ssd.py`'s: the kernels where the step's
operators resolve to Pallas (`impl`: the TPU) and the shape tiles
(`conv_untiled`, `norm_untiled`), `jax.numpy` under autodiff elsewhere; a
line once a shape says which (`_log_pass`).

- **`jax.numpy`**: the taps as shifted slices of a padded copy, in `x`'s
  dtype; `ops/fused.py`'s `fused_rmsnorm` of the gated product. The CPU's
  path and the kernels' reference. Under autodiff XLA makes an array a tap
  and a padded sum of the four in the backward, and float32 arrays of the
  mixer's width around the norm: 41.6 GB a step through HBM in
  `nemotron3nano.tokens8k` for 11.5 GB of operands and results (PERF.md
  section 6, PR 63).
- **Kernel pairs behind a `custom_vjp`: `mamba_conv_fwd`, `mamba_conv_bwd`,
  `mamba_norm_fwd`, `mamba_norm_bwd`; the convolution's pair under the
  caller's `name`, `kda_conv_fwd` and `kda_conv_bwd` for the KDA mixer;
  `kda_out_norm_fwd` and `kda_out_norm_bwd`.**
  Each reads an operand once and
  writes a result once; every intermediate is float32 in VMEM; the
  residuals are the inputs alone, so the backward makes the pre-activation
  and the groups' statistics again.

  The convolution's grid is (batch row, block of channels, block of
  tokens). A block of tokens takes the 16 rows before it as a second view
  of the same operand (zeros before the sequence): no padded copy. A step
  widens block and rows before it into VMEM scratch and walks it `_ROWS`
  tokens a trip; a trip's taps are sublane rotations (`pltpu.roll`) of its
  rows and the 16 before them. The forward writes each block of channels
  into the one of the `splits`' arrays it belongs to (a block straddles no
  split), so no copy splits them afterwards; an array's block index stands
  still while the grid is outside its channels, so nothing unwritten is
  flushed. The backward's grid is (block of channels, batch row, block of
  tokens from the last to the first): it takes the splits' cotangents as
  they come, makes `pre` again, and `dx_t = sum_k w_(taps - 1 - k) dpre_(t +
  k)` reads the `taps - 1` rows after a trip from the trip before it (a
  carry, and VMEM scratch from block to block). `d w` and `d bias` are
  summed in float32 in one output block a block of channels, eight
  partial rows each, that stays in VMEM across the batch rows and the
  blocks of tokens; the eight are summed outside.

  The unit length (`unit`, a static argument: without it the body is the
  one above to the equation) is one more step behind the silu, on a trip's
  float32 rows in VMEM: a block of channels holds whole heads, each a
  lane tile or several, and a head's sum of squares is a sum over its
  lanes. The backward makes the silu and the length again and takes the
  cotangent through them, `d act = (dout - out sum(dout out)) / length` a
  head, before the silu's slope. A trip takes `_UNIT_ROWS` tokens there
  and not `_ROWS`: the sums over lanes want more rows in flight (PERF.md
  section 6, PR 67). Under autodiff the `jax.numpy` lines kept float32
  arrays of the streams' width round the norm beside the taps' arrays:
  56.1 GB a step through HBM in `kimilinear.tokens16k` for 11.3 GB of
  operands and results.

  The norm's grid is (batch row, block of tokens) over whole rows of
  `inner`; a trip takes `_ROWS` tokens of one group at a time. The product
  `y * silu(z)` is kept in float32 (the `jax.numpy` line rounds it to
  `y`'s dtype before the norm widens it). The backward reads `y`, `z` and
  the cotangent, writes `dy` and `dz` in their dtype and sums `d weight` in
  float32 in one block that stays in VMEM across the grid. No float32
  array of the mixer's width reaches HBM.

  KDA's output norm and gate walk (block of heads, batch row, block of
  tokens): a row holds 32 heads in `kimilinear.tokens16k`, and a trip
  unrolls the heads of its block alone (`_GATED_LANES` channels), a head a
  lane tile or several. The lines it replaces norm o on `[B, T, H, dk]` in
  float32, which this chip tiles with the heads on the sublanes: the
  compiler copied o to and from the layout of `[B, T, H dk]`, twelve copies
  of 268 MB a step there, and rounded the normed o and the gate to bf16
  before their product (PERF.md section 6, PR 69). The kernels read o and
  the gate's pre-activation as `kda` and the matmul leave them, `[B, T, H
  dk]`, hold the statistics, the sigmoid and the product in float32 and
  round once; the backward makes them again, writes `do` and `dz` and sums
  `d weight` (over tokens, and over the heads outside) and `d bias` in
  sixteen float32 partial rows a block of heads, which stay in VMEM while
  the grid walks that block's tokens.
  `benchmarks/mamba_passes_alone.py` times every pass's two paths alone.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (
    _DEFAULT_VMEM, _LANES, _MAX_VMEM, _pallas_call, resolve_impl)
from ray_tpu.ops.fused import fused_rmsnorm

logger = logging.getLogger(__name__)

_F32 = jnp.float32
_TILE = 8    # the rows of a float32 tile: a partial sum's rows, a carry's
_HALO = 16   # the rows before a block of tokens: a tile of bf16 sublanes
# the most tokens and channels a grid step of the convolution takes, the
# most tokens one of the norm, and the tokens a trip of a kernel's loop
# (the sweep: PERF.md section 6, PR 63)
_CONV_TOKENS = 1024
_CONV_CHANNELS = 512
_NORM_TOKENS = 256
_ROWS = 32
# a trip's tokens where heads go to unit length: a head's sums over its lanes
# want more rows in flight (the sweep: PERF.md section 6, PR 67)
_UNIT_ROWS = 128
_UNIT_EPS = 1e-6  # under a head's unit length, as the model's `_unit_length`
# the most tokens and channels a grid step of KDA's output norm takes, and the
# tokens a trip (the sweep: PERF.md section 6, PR 69)
_GATED_TOKENS = 2048
_GATED_LANES = 512
_GATED_ROWS = 64


def _largest(size: int, step: int, most: int) -> int:
    """The largest multiple of `step` that divides `size` and is no more
    than `most`, or 0."""
    return max((b for b in range(step, min(size, most) + 1, step)
                if size % b == 0), default=0)


def _silu_and_slope(v):
    """(`silu(v)`, `silu'(v)`) in `v`'s dtype."""
    s = jax.nn.sigmoid(v)
    return v * s, s * (1.0 + v * (1.0 - s))


def _by_head(unit, fn, *arrays):
    """`fn` of each head's `unit` lanes of `arrays` `[n, w]`, the heads' results
    side by side again: whole lane tiles cut and joined."""
    return jnp.concatenate(
        [fn(*(a[:, at:at + unit] for a in arrays))
         for at in range(0, arrays[0].shape[1], unit)], axis=1)


def _inverse_length(act):
    """`1 / length` of a head `[n, unit]`, `[n, 1]`: `models/transformer.py`
    `_unit_length`'s arithmetic, float32."""
    return jax.lax.rsqrt(
        jnp.sum(act * act, axis=-1, keepdims=True) + _UNIT_EPS)


def _unit_length(act):
    """A head `[n, unit]` over its own length."""
    return act * _inverse_length(act)


def _unit_length_slope(act, dout):
    """`d act` of a head's `_unit_length(act)` under the cotangent `dout`:
    `(dout - out sum(dout out)) / length`."""
    scale = _inverse_length(act)
    out = act * scale
    return scale * (dout - out * jnp.sum(dout * out, axis=-1, keepdims=True))


def _folded(v):
    """`[8, w]`: the sum of `v`'s tiles of 8 rows, whole registers added."""
    return functools.reduce(
        jnp.add, [v[at:at + _TILE] for at in range(0, v.shape[0], _TILE)])


# ------------------------------------------------------------ convolution

def causal_conv_silu(x, w, bias=None, *, splits: Optional[Sequence[int]] = None,
                     unit: int = 0, name: str = "mamba_conv",
                     impl: str = "auto", interpret: bool = False):
    """`silu(sum_i w_i x_(t - taps + 1 + i) + bias)` of `x` [B, T, C], `w`
    [taps, C] and `bias` [C] or None, in `x`'s dtype: one array, or with
    `splits` (widths that sum to C) a tuple of the arrays of those channels.
    With `unit`, each head of `unit` channels of the silu's result over its
    own length, `out rsqrt(sum out^2 + 1e-6)` in float32 (KDA's q and k,
    `models/transformer.py` `_kda_mixer`). `name` is the caller's: the
    kernels are `<name>_fwd` and `<name>_bwd`, under the scope `name`.

    impl: 'auto' (the kernels on TPU, `jax.numpy` elsewhere) | 'pallas' |
    'xla'; `interpret` runs the kernels in interpret mode, for tests. A
    shape that does not tile (`conv_untiled`) takes `jax.numpy` whatever
    `impl` says."""
    B, T, C = x.shape
    widths = tuple(splits) if splits is not None else (C,)
    if sum(widths) != C:
        raise ValueError(f"splits {widths} do not sum to {C} channels")
    kernels = resolve_impl(impl) == "pallas" or interpret
    untiled = conv_untiled(w.shape[0], widths, T, unit)
    _log_pass(name, kernels, untiled, (B, T, C),
              (w.shape[0], widths, unit), jnp.dtype(x.dtype).name)
    if kernels and not untiled:
        if bias is None:
            bias = jnp.zeros((C,), _F32)
        out = _conv(x, w, bias, name, widths, conv_blocks(T, widths, unit),
                    unit, interpret)
    else:
        out = _conv_numpy(x, w, bias, widths, unit)
    return out if splits is not None else out[0]


def _conv_numpy(x, w, bias, widths, unit=0):
    """The shifted multiply-adds in `x`'s dtype, as `models/transformer.py`
    `_causal_taps` has them, the bias, the silu, the heads' unit length as
    its `_unit_length` has it, and the split."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    pre = sum(w[i] * jax.lax.dynamic_slice_in_dim(padded, i, x.shape[1], 1)
              for i in range(taps))
    if bias is not None:
        pre = pre + bias.astype(x.dtype)
    out = jax.nn.silu(pre)
    if unit:
        heads = out.astype(_F32).reshape(*out.shape[:2], -1, unit)
        out = _unit_length(heads).astype(out.dtype).reshape(out.shape)
    ends = [sum(widths[:k + 1]) for k in range(len(widths) - 1)]
    return tuple(jnp.split(out, ends, axis=-1))


def conv_blocks(T: int, widths: Sequence[int],
                unit: int = 0) -> Tuple[int, int, int]:
    """(tokens a grid step, channels a grid step, tokens a trip) of the
    convolution's kernels: a block of channels divides every split's
    width, and holds whole heads of `unit`."""
    tokens = _largest(T, _HALO, _CONV_TOKENS)
    return (tokens,
            _largest(math.gcd(*widths), unit or _LANES, _CONV_CHANNELS),
            _largest(tokens, _HALO, _UNIT_ROWS if unit else _ROWS))


def conv_untiled(taps: int, widths: Sequence[int], T: Optional[int] = None,
                 unit: int = 0) -> Optional[str]:
    """Why the convolution's kernels cannot take `taps` taps and splits of
    `widths` channels (and rows of `T` tokens, where they are known; heads
    of `unit` channels), or None where they can: every split whole tiles of
    128 lanes, a head whole tiles too and within a block of channels, the
    taps within the rows a block takes before it, and the tokens whole
    blocks of 16 rows (a tile of bf16 sublanes)."""
    for width in widths:
        if width % _LANES:
            return f"{width} channels are no multiple of {_LANES} lanes"
    if unit % _LANES or unit > _CONV_CHANNELS or math.gcd(*widths) % (
            unit or _LANES):
        return (f"heads of {unit} channels are no whole tiles of {_LANES} "
                f"lanes within a block of {_CONV_CHANNELS}")
    if not 1 < taps <= _TILE + 1:
        return f"{taps} taps: a block takes {_TILE} rows of the one beside it"
    if T is not None and T % _HALO:
        return f"{T} tokens are no multiple of {_HALO} rows"
    return None


def pass_vmem_bytes(kernel: str, tokens: int, width: int, itemsize: int,
                    arrays: int = 1) -> int:
    """An estimate of what a grid step of a kernel of this module holds in
    VMEM: its blocks `[tokens, width]` double-buffered (the convolution's
    backward takes a cotangent an array of `arrays`, and the forward writes
    as many) and its float32 scratch of the same shape."""
    block = tokens * width
    blocks, scratch = {  # by the name's end: the pass, whoever named it
        "conv_fwd": (1 + arrays, 1),
        "conv_bwd": (2 + arrays, 2),
        "norm_fwd": (3, 0),
        "norm_bwd": (5, 0),
    }["_".join(kernel.rsplit("_", 2)[-2:])]
    return 2 * blocks * block * itemsize + scratch * (block + _HALO * width) * 4


def _vmem_limit(kernel, tokens, width, itemsize, arrays=1) -> int:
    need = 2 * pass_vmem_bytes(kernel, tokens, width, itemsize, arrays)
    return min(max(_DEFAULT_VMEM, need), _MAX_VMEM)


@functools.lru_cache(maxsize=None)
def _log_pass(name, kernels, untiled, shape, rest, dtype):
    """One line for each pass and shape a process traces, as `ops/ssd.py`'s
    `_log_scan`: which path, and the kernels' grid, blocks and VMEM. `name`
    is the kernels' (`<name>_fwd`, `<name>_bwd`); `rest` is a convolution's
    (taps, splits, unit) or a norm's (groups,)."""
    B, T, C = shape
    said = {"mamba_conv": "causal_conv_silu",
            "mamba_norm": "gated_group_rmsnorm"}.get(name, name)
    said = f"{said} at B {B}, T {T}, C {C}, {dtype}"
    item = jnp.dtype(dtype).itemsize
    if not kernels:
        logger.info("%s: jax.numpy", said)
    elif untiled:
        logger.info("%s: jax.numpy, because %s", said, untiled)
    elif len(rest) == 3:
        taps, widths, unit = rest
        tokens, channels, rows = conv_blocks(T, widths, unit)
        logger.info(
            "%s: %s_fwd and %s_bwd, %d taps, splits %s%s, grid "
            "(%d, %d, %d), blocks [%d, %d] after [%d, %d], %d tokens a trip, "
            "VMEM %d and %d bytes", said, name, name, taps, list(widths),
            f", unit length a head of {unit}" if unit else "",
            B, C // channels, T // tokens, tokens, channels, _HALO, channels,
            rows, *(pass_vmem_bytes(k, tokens, channels, item, len(widths))
                    for k in ("conv_fwd", "conv_bwd")))
    else:
        groups, = rest
        tokens, rows, lanes = _norm_blocks_of(name, T, C, groups)
        grid = (B, T // tokens)
        if name != "mamba_norm":
            grid = (C // lanes, *grid)
        logger.info(
            "%s: %s_fwd and %s_bwd, %d groups of %d, grid %s, blocks "
            "[%d, %d], %d tokens a trip, VMEM %d and %d bytes", said, name,
            name, groups, C // groups, grid, tokens, lanes, rows,
            *(pass_vmem_bytes(k, tokens, lanes, item)
              for k in ("norm_fwd", "norm_bwd")))


def _widen(x_ref, before_ref, wide, first):
    """The block's rows after the 16 before them (zeros where the block is
    the sequence's first), float32, into `wide` `[16 + tokens, channels]`."""
    before = before_ref[0].astype(_F32)
    wide[:_HALO] = jnp.where(first, jnp.zeros_like(before), before)
    wide[_HALO:] = x_ref[0].astype(_F32)


def _shifted(rows, taps):
    """`rows` `[16 + n, w]` (a trip's tokens after the 16 before them) as
    the `taps` arrays `[n, w]` a trip's taps multiply: the tokens `taps -
    1 - i` before each, for `i` up to `taps`."""
    from jax.experimental.pallas import tpu as pltpu

    return [(pltpu.roll(rows, taps - 1 - i, 0) if i < taps - 1 else rows)
            [_HALO:] for i in range(taps)]


def _pre_activation(shifted, w, bias):
    return sum(x * w[i:i + 1] for i, x in enumerate(shifted)) + bias


def _its_split(refs, bounds, block, do):
    """`do(ref)` for the one of `refs`, an array a split, whose blocks of
    channels (`bounds`) hold the grid's `block`."""
    from jax.experimental import pallas as pl

    if len(refs) == 1:
        return do(refs[0])
    for ref, (lo, hi) in zip(refs, bounds):
        pl.when((block >= lo) & (block < hi))(functools.partial(do, ref))


def _conv_fwd_kernel(x_ref, before_ref, w_ref, bias_ref, *rest, bounds, rows,
                     unit):
    """One block of tokens of one block of channels: `rest` is an output a
    split, then the scratch `wide`."""
    from jax.experimental import pallas as pl

    outs, wide = rest[:-1], rest[-1]
    tokens = x_ref.shape[1]
    taps = w_ref.shape[0]
    _widen(x_ref, before_ref, wide, pl.program_id(2) == 0)
    w, bias = w_ref[...], bias_ref[...]
    block = pl.program_id(1)

    def trip(i, _):
        at = pl.multiple_of(i * rows, rows)
        pre = _pre_activation(
            _shifted(wide[pl.ds(at, _HALO + rows), :], taps), w, bias)
        out = _silu_and_slope(pre)[0]
        if unit:
            out = _by_head(unit, _unit_length, out)

        def write(out_ref):
            out_ref[0, pl.ds(at, rows), :] = out.astype(out_ref.dtype)

        _its_split(outs, bounds, block, write)
        return 0

    jax.lax.fori_loop(0, tokens // rows, trip, 0)


def _conv_bwd_kernel(x_ref, before_ref, w_ref, bias_ref, *rest, bounds, rows,
                     unit):
    """The same block's cotangents; the grid walks the blocks of tokens
    backwards. `rest` is a cotangent a split, then `dx`'s block and the
    sums' (`[taps + 1, 8, channels]`: eight partial rows a tap of `d w`,
    then `d bias`'s), then the scratch: `wide`, the block's cotangent and
    `dpre`'s first rows of the block after this one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    douts = rest[:len(bounds)]
    dx_ref, sums_ref, wide, dout, after_ref = rest[len(bounds):]
    tokens = x_ref.shape[1]
    taps = w_ref.shape[0]
    step = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _first_of_the_channels():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(step == 0)
    def _last_block():
        after_ref[...] = jnp.zeros_like(after_ref)

    _widen(x_ref, before_ref, wide, step == pl.num_programs(2) - 1)

    def read(dout_ref):
        dout[...] = dout_ref[0].astype(_F32)

    _its_split(douts, bounds, pl.program_id(0), read)
    w, bias = w_ref[...], bias_ref[...]
    trips = tokens // rows

    def trip(i, after):
        at = pl.multiple_of((trips - 1 - i) * rows, rows)
        shifted = _shifted(wide[pl.ds(at, _HALO + rows), :], taps)
        pre = _pre_activation(shifted, w, bias)
        dact = dout[pl.ds(at, rows), :]
        act, slope = _silu_and_slope(pre)
        if unit:
            dact = _by_head(unit, _unit_length_slope, act, dact)
        dpre = dact * slope
        # dx_t = sum_k w_(taps - 1 - k) dpre_(t + k): the rows after these
        both = jnp.concatenate([dpre, after], axis=0)
        dx = dpre * w[taps - 1:taps]
        for k in range(1, taps):
            dx = dx + (pltpu.roll(both, rows + _TILE - k, 0)[:rows]
                       * w[taps - 1 - k:taps - k])
        dx_ref[0, pl.ds(at, rows), :] = dx.astype(dx_ref.dtype)
        for tap, x in enumerate(shifted):
            sums_ref[tap] += _folded(dpre * x)
        sums_ref[taps] += _folded(dpre)
        return dpre[:_TILE]

    after_ref[...] = jax.lax.fori_loop(0, trips, trip, after_ref[...])


def _split_bounds(widths, channels):
    """(first, one past the last) block of channels of each split."""
    ends = [sum(widths[:k + 1]) // channels for k in range(len(widths))]
    return tuple(zip([0] + ends[:-1], ends))


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7, 8))
def _conv_call(name, x, w, bias, douts, widths, blocks, unit, interpret):
    """`pallas_call` of `<caller's name>_fwd` (no `douts`: an array a split)
    or of `<caller's name>_bwd` (a cotangent a split: `dx` and the sums of
    `d w` and `d bias`), under the caller's name as a scope. The forward's grid is (batch row, block of channels, block
    of tokens), the backward's (block of channels, batch row, block of
    tokens from the last). A split's block stands at its first block until
    the grid reaches its channels and at its last once it has left them.
    Under `jit`: a model's layers of one shape share one trace of a
    kernel's body (`ops/selective_scan.py` `_scan_call`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, C = x.shape
    tokens, channels, rows = blocks
    taps, n = w.shape[0], T // tokens
    backward = name.endswith("_bwd")
    bounds = _split_bounds(widths, channels)

    def of(step):
        return (lambda a, b, c: step(b, a, n - 1 - c)) if backward else step

    def tokens_of(step):
        return pl.BlockSpec((1, tokens, channels), of(step))

    def per_channel(*lead):
        return pl.BlockSpec((*lead, channels),
                            of(lambda i, j, t: (*(0,) * len(lead), j)))

    def split(lo, hi):
        # the backward walks t from n - 1 down: its first block is the last
        early, late = (n - 1, 0) if backward else (0, n - 1)
        return tokens_of(lambda i, j, t: (
            i, jnp.where(j < lo, early, jnp.where(j >= hi, late, t)),
            jnp.clip(j, lo, hi - 1) - lo))

    main = tokens_of(lambda i, j, t: (i, t, j))
    before = pl.BlockSpec(
        (1, _HALO, channels),
        of(lambda i, j, t: (i, jnp.maximum(t * (tokens // _HALO) - 1, 0), j)))
    splits = [split(lo, hi) for lo, hi in bounds]
    split_shapes = [jax.ShapeDtypeStruct((B, T, width), x.dtype)
                    for width in widths]
    wide = pltpu.VMEM((_HALO + tokens, channels), _F32)
    if backward:
        kernel, out_specs = _conv_bwd_kernel, [main, per_channel(taps + 1, _TILE)]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                     jax.ShapeDtypeStruct((taps + 1, _TILE, C), _F32)]
        scratch = [wide, pltpu.VMEM((tokens, channels), _F32),
                   pltpu.VMEM((_TILE, channels), _F32)]
    else:
        kernel, out_specs, out_shape, scratch = (
            _conv_fwd_kernel, splits, split_shapes, [wide])
    with jax.named_scope(name[:-len("_bwd")]):
        return _pallas_call(
            functools.partial(kernel, rows=rows, bounds=bounds, unit=unit),
            grid=(C // channels, B, n) if backward else (B, C // channels, n),
            in_specs=[main, before, per_channel(taps), per_channel(1),
                      *splits[:len(douts)]],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(
                    "conv" + name[-len("_bwd"):], tokens, channels,
                    jnp.dtype(x.dtype).itemsize, len(widths))),
            interpret=interpret,
            name=name,
        )(x, x, w.astype(_F32), bias.astype(_F32).reshape(1, C), *douts)


def _conv_fwd(x, w, bias, name, widths, blocks, unit, interpret):
    return tuple(_conv_call(
        name + "_fwd", x, w, bias, (), widths, blocks, unit, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _conv(x, w, bias, name, widths, blocks, unit, interpret):
    return _conv_fwd(x, w, bias, name, widths, blocks, unit, interpret)


def _conv_vjp_fwd(x, w, bias, *static):
    return _conv_fwd(x, w, bias, *static), (x, w, bias)


def _conv_vjp_bwd(name, widths, blocks, unit, interpret, res, douts):
    x, w, bias = res
    dx, sums = _conv_call(name + "_bwd", x, w, bias, tuple(douts), widths,
                          blocks, unit, interpret)
    sums = sums.sum(axis=1)  # the eight partial rows
    return dx, sums[:-1].astype(w.dtype), sums[-1].astype(bias.dtype)


_conv.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


# ------------------------------------------------------------- gated norm

def gated_group_rmsnorm(y, z, weight, groups: int, eps: float = 1e-6, *,
                        impl: str = "auto", interpret: bool = False):
    """`GroupRMSNorm(y * silu(z))` of `y` and `z` [B, T, inner] and `weight`
    [inner]: the mean square over each of `groups` groups of channels in
    float32, the result in `y`'s dtype. `impl` and `interpret` as
    `causal_conv_silu`'s; `norm_untiled` says which shapes the kernels
    take."""
    B, T, inner = y.shape
    kernels = resolve_impl(impl) == "pallas" or interpret
    untiled = norm_untiled(inner, groups, T)
    _log_pass("mamba_norm", kernels, untiled, (B, T, inner), (groups,),
              jnp.dtype(y.dtype).name)
    if kernels and not untiled:
        return _norm(y, z, weight, groups, eps, norm_blocks(T), interpret)
    gated = (y * jax.nn.silu(z)).reshape(B, T, groups, inner // groups)
    return fused_rmsnorm(gated, weight.reshape(groups, inner // groups),
                         eps=eps).reshape(B, T, inner)


def norm_blocks(T: int, most: int = 0, trip: int = 0) -> Tuple[int, int]:
    """(tokens a grid step, tokens a trip) of a norm's kernels: the gated
    norm's, or under `most` and `trip` tokens."""
    tokens = _largest(T, _HALO, most or _NORM_TOKENS)
    return tokens, _largest(tokens, _HALO, trip or _ROWS)


def _norm_blocks_of(name, T, inner, groups) -> Tuple[int, int, int]:
    """(tokens a grid step, tokens a trip, channels a grid step) of the
    kernels `<name>_fwd` and `<name>_bwd`: `mamba_norm_*` take whole rows."""
    if name == "mamba_norm":
        return (*norm_blocks(T), inner)
    return gated_blocks(T, inner, groups)


def norm_untiled(inner: int, groups: int, T: Optional[int] = None,
                 name: str = "mamba_norm") -> Optional[str]:
    """Why the norm's kernels `<name>_fwd` and `<name>_bwd` cannot take
    `groups` groups of `inner` channels (and rows of `T` tokens, where they
    are known), or None where they can: a group whole tiles of 128 lanes,
    the tokens whole blocks of 16 rows, and a step of the backward within
    VMEM."""
    if inner % groups or inner // groups % _LANES:
        return (f"{groups} groups of {inner} channels are no multiple of "
                f"{_LANES} lanes each")
    if T is not None and T % _HALO:
        return f"{T} tokens are no multiple of {_HALO} rows"
    tokens, _, lanes = _norm_blocks_of(
        name, _HALO if T is None else T, inner, groups)
    need = 2 * pass_vmem_bytes("norm_bwd", tokens, lanes, 4)
    if need > _MAX_VMEM:
        return (f"a step of {name}_bwd needs {need} bytes of VMEM, over "
                f"{_MAX_VMEM}")
    return None


def _group_norm(y, z, eps):
    """(the normed product, `1 / rms`, `silu(z)`, its slope) of a trip's
    tokens of one group, float32."""
    act, slope = _silu_and_slope(z)
    gated = y * act
    scale = jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return gated * scale, scale, act, slope


def _norm_fwd_kernel(y_ref, z_ref, w_ref, out_ref, *, groups, eps, rows):
    from jax.experimental import pallas as pl

    tokens, inner = y_ref.shape[1:]
    width = inner // groups

    def trip(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for g in range(groups):
            lanes = pl.ds(g * width, width)
            normed = _group_norm(y_ref[0, at, lanes].astype(_F32),
                                 z_ref[0, at, lanes].astype(_F32), eps)[0]
            out_ref[0, at, lanes] = (normed * w_ref[:, lanes]).astype(
                out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tokens // rows, trip, 0)


def _norm_bwd_kernel(y_ref, z_ref, w_ref, dout_ref, dy_ref, dz_ref, sums_ref,
                     *, groups, eps, rows):
    """`sums_ref` `[8, inner]`: eight partial rows of `d weight`, in VMEM
    across the whole grid."""
    from jax.experimental import pallas as pl

    tokens, inner = y_ref.shape[1:]
    width = inner // groups

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _first_step():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def trip(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for g in range(groups):
            lanes = pl.ds(g * width, width)
            y = y_ref[0, at, lanes].astype(_F32)
            normed, scale, act, slope = _group_norm(
                y, z_ref[0, at, lanes].astype(_F32), eps)
            dout = dout_ref[0, at, lanes].astype(_F32)
            sums_ref[:, lanes] += _folded(dout * normed)
            dnormed = dout * w_ref[:, lanes]
            dgated = scale * (dnormed - normed * jnp.mean(
                dnormed * normed, axis=-1, keepdims=True))
            dy_ref[0, at, lanes] = (dgated * act).astype(dy_ref.dtype)
            dz_ref[0, at, lanes] = (dgated * y * slope).astype(dz_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tokens // rows, trip, 0)


def _norm_call(name, kernel, operands, outs, blocks, interpret):
    """`pallas_call` of a norm's kernel `<scope>_fwd` or `<scope>_bwd`
    (`mamba_norm_*`, `kda_out_norm_*`) under its scope, over (batch row,
    block of tokens): blocks `[tokens, inner]` of the `[B, T, inner]`
    arrays, a parameter whole as a row, and the parameters' partial rows.
    With a third of `blocks`, `lanes`, over (block of `lanes` channels,
    batch row, block of tokens): blocks `[tokens, lanes]`, a row of `inner`
    channels by its `lanes`, so that the partial rows of a block of
    channels stay in VMEM while the grid walks its tokens, and a narrower
    row whole."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, inner = operands[0].shape
    tokens, rows, lanes = (*blocks, 0)[:3]
    grid = (B, T // tokens)
    if lanes:
        grid = (inner // lanes, *grid)

    def at(step):
        """`step(batch row, block of tokens, block of lanes)` of the grid's
        indices."""
        if lanes:
            return lambda j, i, t: step(i, t, j)
        return lambda i, t: step(i, t, 0)

    def spec(a):
        if a.ndim == 3:
            return pl.BlockSpec((1, tokens, lanes or inner),
                                at(lambda i, t, j: (i, t, j)))
        if lanes and a.shape[1] == inner:
            return pl.BlockSpec((a.shape[0], lanes),
                                at(lambda i, t, j: (0, j)))
        return pl.BlockSpec(a.shape, at(lambda i, t, j: (0, 0)))

    backward = name.endswith("_bwd")
    with jax.named_scope(name[:-len("_bwd")]):
        return _pallas_call(
            functools.partial(kernel, rows=rows),
            grid=grid,
            in_specs=[spec(a) for a in operands],
            out_specs=[spec(a) for a in outs],
            out_shape=outs,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    *(("parallel",) if lanes else ()),
                    *("arbitrary" if backward else "parallel",) * 2),
                vmem_limit_bytes=_vmem_limit(
                    name, tokens, lanes or inner,
                    jnp.dtype(operands[0].dtype).itemsize)),
            interpret=interpret,
            name=name,
        )(*operands)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _norm_fwd(y, z, weight, groups, eps, blocks, interpret):
    return _norm_call(
        "mamba_norm_fwd",
        functools.partial(_norm_fwd_kernel, groups=groups, eps=eps),
        (y, z, weight.astype(_F32).reshape(1, -1)),
        [jax.ShapeDtypeStruct(y.shape, y.dtype)], blocks, interpret)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm(y, z, weight, groups, eps, blocks, interpret):
    return _norm_fwd(y, z, weight, groups, eps, blocks, interpret)


def _norm_vjp_fwd(y, z, weight, groups, eps, blocks, interpret):
    return _norm_fwd(y, z, weight, groups, eps, blocks, interpret), (
        y, z, weight)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _norm_bwd(y, z, weight, dout, groups, eps, blocks, interpret):
    dy, dz, sums = _norm_call(
        "mamba_norm_bwd",
        functools.partial(_norm_bwd_kernel, groups=groups, eps=eps),
        (y, z, weight.astype(_F32).reshape(1, -1), dout),
        [jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct(z.shape, z.dtype),
         jax.ShapeDtypeStruct((_TILE, y.shape[-1]), _F32)],
        blocks, interpret)
    return dy, dz, sums.sum(axis=0).astype(weight.dtype)


def _norm_vjp_bwd(groups, eps, blocks, interpret, res, dout):
    return _norm_bwd(*res, dout, groups, eps, blocks, interpret)


_norm.defvjp(_norm_vjp_fwd, _norm_vjp_bwd)


# --------------------------------------------- KDA's output norm and gate

def group_rmsnorm_gated(o, z, bias, weight, groups: int, eps: float = 1e-6, *,
                        name: str = "kda_out_norm", impl: str = "auto",
                        interpret: bool = False):
    """`RMSNorm(o) * weight * sigmoid(z + bias)` of `o` and `z` [B, T, inner],
    `bias` [inner] and `weight` [inner // groups]: the mean square over each
    of `groups` heads of channels in float32, one learned scale a channel of
    a head, the same for every head, the result in `o`'s dtype (the KDA
    mixer's output norm and gate, `models/transformer.py` `_kda_mixer`: `z`
    the gate's second product as the matmul leaves it). The kernels are
    `<name>_fwd` and `<name>_bwd`, under the scope `name`. `impl` and
    `interpret` as `causal_conv_silu`'s; `norm_untiled` says which shapes
    the kernels take, and the rest take the mixer's `jax.numpy` lines."""
    B, T, inner = o.shape
    kernels = resolve_impl(impl) == "pallas" or interpret
    untiled = norm_untiled(inner, groups, T, name)
    _log_pass(name, kernels, untiled, (B, T, inner), (groups,),
              jnp.dtype(o.dtype).name)
    if kernels and not untiled:
        return _gated(o, z, bias, weight, name, eps,
                      gated_blocks(T, inner, groups), interpret)
    normed = fused_rmsnorm(o.reshape(B, T, groups, inner // groups), weight,
                           eps=eps).reshape(B, T, inner)
    return normed * jax.nn.sigmoid(
        z.astype(_F32) + bias.astype(_F32)).astype(o.dtype)


def gated_blocks(T: int, inner: int, groups: int) -> Tuple[int, int, int]:
    """(tokens a grid step, tokens a trip, channels a grid step) of
    `group_rmsnorm_gated`'s kernels: a block of channels holds whole heads,
    one at the least."""
    head = inner // groups
    return (*norm_blocks(T, _GATED_TOKENS, _GATED_ROWS),
            _largest(inner, head, max(head, _GATED_LANES)))


def _head_norm(o, z, bias, eps):
    """(the normed head, `1 / rms`, the gate `sigmoid(z + bias)`) of a
    trip's tokens of one head, float32."""
    scale = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * scale, scale, jax.nn.sigmoid(z + bias)


def _gated_fwd_kernel(o_ref, z_ref, bias_ref, w_ref, out_ref, *, eps, rows):
    """One block of tokens of one block of heads: `w_ref` `[1, head]`, the
    one scale of every head."""
    from jax.experimental import pallas as pl

    tokens, lanes = o_ref.shape[1:]
    width = w_ref.shape[1]

    def trip(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for head in range(lanes // width):
            lane = pl.ds(head * width, width)
            normed, _, gate = _head_norm(
                o_ref[0, at, lane].astype(_F32),
                z_ref[0, at, lane].astype(_F32), bias_ref[:, lane], eps)
            out_ref[0, at, lane] = (normed * w_ref[...] * gate).astype(
                out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tokens // rows, trip, 0)


def _gated_bwd_kernel(o_ref, z_ref, bias_ref, w_ref, dout_ref, do_ref, dz_ref,
                      sums_ref, *, eps, rows):
    """`sums_ref` `[16, lanes]`: eight partial rows of `d weight` a channel
    of each head of the block, then eight of `d bias`, in VMEM across the
    batch rows and the blocks of tokens."""
    from jax.experimental import pallas as pl

    tokens, lanes = o_ref.shape[1:]
    width = w_ref.shape[1]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _first_of_the_heads():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def trip(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for head in range(lanes // width):
            lane = pl.ds(head * width, width)
            normed, scale, gate = _head_norm(
                o_ref[0, at, lane].astype(_F32),
                z_ref[0, at, lane].astype(_F32), bias_ref[:, lane], eps)
            # the cotangents of `normed * weight` and of `normed`
            dscaled = dout_ref[0, at, lane].astype(_F32) * gate
            dnormed = dscaled * w_ref[...]
            along = dnormed * normed
            dz = along * (1.0 - gate)  # `dout normed weight gate (1 - gate)`
            sums_ref[:_TILE, lane] += _folded(dscaled * normed)
            sums_ref[_TILE:, lane] += _folded(dz)
            do_ref[0, at, lane] = (scale * (dnormed - normed * jnp.mean(
                along, axis=-1, keepdims=True))).astype(do_ref.dtype)
            dz_ref[0, at, lane] = dz.astype(dz_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tokens // rows, trip, 0)


def _rows_of(bias, weight):
    return bias.astype(_F32).reshape(1, -1), weight.astype(_F32).reshape(1, -1)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _gated_fwd(o, z, bias, weight, name, eps, blocks, interpret):
    return _norm_call(
        name + "_fwd", functools.partial(_gated_fwd_kernel, eps=eps),
        (o, z, *_rows_of(bias, weight)),
        [jax.ShapeDtypeStruct(o.shape, o.dtype)], blocks, interpret)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gated(o, z, bias, weight, name, eps, blocks, interpret):
    return _gated_fwd(o, z, bias, weight, name, eps, blocks, interpret)


def _gated_vjp_fwd(o, z, bias, weight, *static):
    return _gated_fwd(o, z, bias, weight, *static), (o, z, bias, weight)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _gated_bwd(o, z, bias, weight, dout, name, eps, blocks, interpret):
    do, dz, sums = _norm_call(
        name + "_bwd", functools.partial(_gated_bwd_kernel, eps=eps),
        (o, z, *_rows_of(bias, weight), dout),
        [jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(z.shape, z.dtype),
         jax.ShapeDtypeStruct((2 * _TILE, o.shape[-1]), _F32)],
        blocks, interpret)
    dweight, dbias = sums[:_TILE], sums[_TILE:]
    return (do, dz, dbias.sum(axis=0).astype(bias.dtype),
            dweight.reshape(-1, weight.shape[0]).sum(axis=0).astype(
                weight.dtype))


def _gated_vjp_bwd(name, eps, blocks, interpret, res, dout):
    return _gated_bwd(*res, dout, name, eps, blocks, interpret)


_gated.defvjp(_gated_vjp_fwd, _gated_vjp_bwd)
