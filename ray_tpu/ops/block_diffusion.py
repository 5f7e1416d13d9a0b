"""Block-diffusion attention (BD3-LMs, arXiv:2503.09573, as SDAR,
arXiv:2510.06303, trains with it): a noisy and a clean copy of every
sequence through one stack, side by side.

The stream holds `R = 2 L` rows for `L` tokens: rows `0 .. L - 1` are the
noisy half (`x_t`), rows `L .. 2 L - 1` the clean half (`x_0`), and row `r`
stands at position `r mod L` in block `(r mod L) // block`. Row `r` of half
`s` and block `b` attends, in ONE softmax at `scale`, to

    the clean rows of every block before b       (BD3-LMs' offset_block_causal
                                                  for a noisy row, block_causal
                                                  less the diagonal for a clean one)
    the rows of its own half in block b          (block_diagonal: all `block`
                                                  of them, both directions)

so a noisy row never sees a noisy row of another block or the clean copy of
its own block, and a clean row never sees a noisy row.

`block_diffusion_attention` walks exactly those pairs, in three parts, each
under a `jax.named_scope` of its own, forward and backward:

- `bd_stair`: both halves' queries against the *clean* keys and values
  under the flash kernels' staircase at steps of one block
  (`flash_attention_lse(stair=(block, block))`: query `i` sees the first
  `block * (i // block)` keys, the strictly earlier blocks). The two halves
  are folded into the query heads, half-major inside a key-value head's
  group (`2 H / Hk` query heads a key-value head, no copy of k or v; dk and
  dv are summed over the group in the kernel as a group's always are), so
  the call is `[B, L, 2 H, D]` against `[B, L, Hk, D]`: `L^2 / 2` pairs a
  query head over `2 H` of them, `L^2` a head for `L` tokens. Rows of block
  0 see no key: o 0, lse -inf.
- `bd_own_block`: a row against the `block` rows of its own half and block,
  `[2 L / block, block, block]` scores a head, a softmax over the `block`
  scores in float32, and the weighted sum of the block's values.
- `bd_join`: `o = (e^lseS oS + e^lseO oO) / (e^lseS + e^lseO)` in float32 by
  the larger lse; the own block's is finite (a row sees itself), so `lseS =
  -inf` gives `oO`.

Two paths compute the last two, as `ops/mamba_passes.py`'s passes: the
kernels where the operators resolve to Pallas (`impl`: the TPU) and the shape
tiles (`own_join_untiled`), `jax.numpy` under autodiff elsewhere; a line once
a shape says which (`_log_own_join`).

- **`jax.numpy`** (`own_block_part`, `own_block_and_join`, under the scopes
  `bd_own_block` and `bd_join`): two small matmuls a block and key-value
  head (a tile of `block` columns leaves the MXU idle, and still beats the
  same sums by hand), and `ops/eva.py`'s `_join`, a chunk of `ROWS_AT_ONCE`
  rows at a time (`lax.map`), each chunk made again in the backward
  (`jax.checkpoint`): under autodiff the float32 values a head and row are
  seven or so of q's shape, and are a chunk's and never the stream's. The
  CPU's path and the kernels' reference; the staircase's o and lse reach it
  unfolded (`unfold_halves`). 12.5 % of `sdar.tokens16k`'s busy time with
  the folds, for 8 of a token's 16,388 pairs a head (PERF.md section 6, PR
  70).
- **The kernel pair `bd_own_join_fwd` and `bd_own_join_bwd`** behind a
  `custom_vjp` (`_own_join`, under the scope `bd_own_join`; PR 71), by the
  row: a grid over (batch row and key-value head, half, tile of the half's
  rows). A step takes its group's `g = H / Hk` query heads of q and oS, the
  key-value head's k and v rows of the same half and tile, and the group's
  rows of lseS, and writes o; a trip of the body takes 128 rows and a head:
  `q k^T` `[128, 128]` on the MXU (bf16 operands, float32 sum) masked to the
  diagonal blocks of `block` rows, the row's max, `exp`, sum and lse in
  float32, the weights float32 into `p v` by their three bf16 parts
  (`_wide_dot`: v is exact in float32, so that is the MXU's full precision),
  and the join by the two parts' shares (`join_shares`) in float32. The
  backward makes the scores, the weights and the shares again from the
  operands (the residuals are the operands: nothing new is kept) and writes
  dq, doS, dlseS and, summed over the group's heads inside the body, dk and
  dv. Every float32 value a head and row is VMEM's; there is no loop over
  chunks and no `jax.checkpoint`. (The backward's call stands inside a
  `while` of one trip, `_own_join_vjp_bwd`: the TPU compiler dies on the
  cell's comparison without it.)

  Every array crosses the kernels' boundary as its neighbour leaves or takes
  it (`attention_by_kernels`, `_own_join_call`): q with the halves folded
  into the heads and the heads into the batch, `[B 2 H, L, D]`, made once
  for the staircase's kernels and these; oS and lseS where `flash_fwd_stair`
  writes them, `[B 2 H, L, D]` and `[B 2 H, L]` (a block the `g` heads of
  one half's group: `fold_halves`' arithmetic in the index map; lse's rows
  lie on the lanes, and a row becomes a column by a masked sum in the body);
  k and v `[B Hk, 2 L, D]`; o `[B, 2 L, H D]`, lane-dense as `wo`'s product
  reads it. The backward writes doS and dlseS where `flash_bwd_dkv_dq_stair`
  reads them and dq beside that kernel's, so the two are added as they lie
  before the one transpose back. No `unfold_halves`, no transpose of o or
  of its cotangent, and no rank-4 array with the heads on the sublanes
  stands between the two pairs of kernels.
  `benchmarks/bd_own_join_alone.py` times both paths alone.

No `[2 L, 2 L]` tensor is made on the Pallas path. The XLA path
(`impl="xla"`, every platform but the TPU) builds the staircase's scores
whole under its mask. A bit mask as data (`ops/sparse_attention.py`'s form)
would walk the causal tiles of a `2 L x 2 L` square, a third more pairs.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.eva import _join, _partial_xla
from ray_tpu.ops.flash_attention import (
    _BIG_NEG, _DEFAULT_VMEM, _LANES, _MAX_VMEM, _NN, _NT, _TN, _dot,
    _flash_lse, _pallas_call, _to_kernels, flash_attention_lse, resolve_impl)

logger = logging.getLogger(__name__)

_F32 = jnp.float32
ROWS_AT_ONCE = 2048  # rows whose own block and join are in flight at once
# the rows of a trip of the kernels' bodies, a tile of the MXU's, and the most
# rows a grid step takes (the sweep: PERF.md section 6, PR 71)
_TRIP = 128
_OWN_JOIN_ROWS = 1024


def fold_halves(x, kv_heads: int):
    """`x` [B, 2 L, H, D], the two halves one after the other, as
    [B, L, 2 H, D]: the halves folded into the heads, half-major inside each
    key-value head's group, so that query head `j (2 g) + s g + i` is head
    `j g + i` of half `s` and reads key-value head `j` (`g = H / kv_heads`)."""
    B, R, H, D = x.shape
    g = H // kv_heads
    x = x.reshape(B, 2, R // 2, kv_heads, g, D)
    return x.transpose(0, 2, 3, 1, 4, 5).reshape(B, R // 2, 2 * H, D)


def unfold_halves(x, kv_heads: int):
    """`fold_halves`'s inverse on [B, L, 2 H, ...]: [B, 2 L, H, ...]."""
    B, L, H2 = x.shape[:3]
    g = H2 // 2 // kv_heads
    x = x.reshape(B, L, kv_heads, 2, g, *x.shape[3:])
    x = jnp.moveaxis(x, 3, 1)  # [B, 2, L, Hk, g, ...]
    return x.reshape(B, 2 * L, H2 // 2, *x.shape[5:])


def stair_part(q, k, v, block: int, scale: float, pallas: bool,
               keep_ctx: bool = False, interpret: bool = False):
    """(oS [B, 2 L, H, D], lseS [B, 2 L, H] float32): every row of both
    halves against the clean rows of the blocks before its own."""
    B, R, H, D = q.shape
    L, Hk = R // 2, k.shape[2]
    with jax.named_scope("bd_stair"):
        qf = fold_halves(q, Hk)
        kc, vc = k[:, L:], v[:, L:]
        if pallas:
            o, lse = flash_attention_lse(
                qf, kc, vc, scale=scale, stair=(block, block),
                keep_ctx=keep_ctx, interpret=interpret)
        else:
            rep = 2 * H // Hk
            seen = block * (jnp.arange(L) // block)
            o, lse = _partial_xla(
                qf, jnp.repeat(kc, rep, axis=2), jnp.repeat(vc, rep, axis=2),
                jnp.arange(L)[None, :] < seen[:, None], scale)
        return unfold_halves(o, Hk), unfold_halves(lse, Hk)


def own_block_part(q, k, v, block: int, scale: float):
    """(oO [n, H, D] float32, lseO [n, H] float32) of `n` rows that are
    whole blocks, q [n, H, D] and k, v [n, Hk, D]: every row against the
    `block` rows of its own block, both directions. Two small matmuls a
    block and key-value head, `[block g, D] x [D, block]` and back (`g = H /
    Hk`): the MXU idles at a width of `block`, but it broadcasts a block's
    keys over its queries and heads for nothing, where the multiply-and-sum
    by hand made every key's copy at q's shape in float32 (9.2 % of
    `sdar.tokens16k`'s busy time against 38.5 for the staircase's kernels:
    PERF.md section 6, PR 70). Scores, softmax and sums in float32; the
    weights stay float32 into the second matmul (a contraction over `block`
    keys costs nothing at any precision, and a row's own block carries most
    of an early row's softmax: rounded to bf16 there the tests' tiny cell
    read a loss 3.4e-4 off the reference's where this reads 1.2e-5)."""
    n, H, D = q.shape
    Hk = k.shape[1]
    g = H // Hk
    with jax.named_scope("bd_own_block"):
        q5 = q.reshape(n // block, block, Hk, g, D)
        k4, v4 = (x.reshape(n // block, block, Hk, D) for x in (k, v))
        s = jnp.einsum("nqhgd,nkhd->nqhgk", q5, k4,
                       preferred_element_type=_F32) * scale
        m = jax.lax.stop_gradient(s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m)
        total = p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("nqhgk,nkhd->nqhgd", p / total, v4.astype(_F32),
                       precision=jax.lax.Precision.HIGHEST)
        lse = (m + jnp.log(total))[..., 0]
        return o.reshape(n, H, D), lse.reshape(n, H)


def _row_chunk(rows: int, block: int) -> int:
    """The rows `own_block_and_join` takes at once: whole blocks that divide
    the stream, `ROWS_AT_ONCE` at most where such a number divides it."""
    chunk = math.gcd(rows, ROWS_AT_ONCE)
    return chunk if chunk % block == 0 else rows


def own_block_and_join(q, k, v, o_s, lse_s, block: int, scale: float):
    """o [B, 2 L, H, D] in q's dtype: the own block's partial softmax
    (`own_block_part`) joined with the staircase's `(o_s, lse_s)`, a chunk of
    rows at a time (`lax.map` over chunks of whole blocks, each under
    `jax.checkpoint`): the float32 values a head and row that the part and
    its backward make, seven or so of q's shape, are a chunk's and never the
    stream's (0.5 GB each at 32,768 rows of 32 heads of 128, which the
    compiler's plan for a v5e did not fit)."""
    B, R, H, D = q.shape
    chunk = _row_chunk(R, block)

    def fold(x):
        return x.reshape(B * R // chunk, chunk, *x.shape[2:])

    @jax.checkpoint
    def of_chunk(rows):
        q, k, v, o_s, lse_s = rows
        o_o, lse_o = own_block_part(q, k, v, block, scale)
        with jax.named_scope("bd_join"):
            return _join(o_o, lse_o, o_s, lse_s)[0].astype(q.dtype)

    o = jax.lax.map(of_chunk, tuple(map(fold, (q, k, v, o_s, lse_s))))
    return o.reshape(B, R, H, D)


# ----------------------------------- the own block and the join, kernels

def own_join_vmem_bytes(kernel: str, tile: int, group: int, D: int,
                        itemsize: int) -> int:
    """An estimate of what a grid step of `bd_own_join_fwd` or
    `bd_own_join_bwd` holds in VMEM: its blocks double-buffered (q's, oS's
    and o's of `group` heads, with the backward their three cotangents
    less one; k's, v's and with the backward theirs; lseS's and its
    cotangent's rows in float32) and a trip's float32 tiles."""
    wide, narrow = (3, 2) if kernel.endswith("fwd") else (5, 4)
    rows = 1 if kernel.endswith("fwd") else 2
    blocks = tile * D * (wide * group + narrow) * itemsize
    blocks += rows * max(group, 8) * tile * 4
    return 2 * blocks + 8 * _TRIP * max(_TRIP, D) * 4


def own_join_tile(L: int, group: int, D: int, itemsize: int) -> int:
    """The rows of a half a grid step of the kernels takes: the most whole
    trips of 128, `_OWN_JOIN_ROWS` at most, that divide `L` and whose
    backward step fits VMEM; 0 where none does."""
    return max((tile for tile in range(_TRIP, min(L, _OWN_JOIN_ROWS) + 1,
                                       _TRIP)
                if L % tile == 0 and 2 * own_join_vmem_bytes(
                    "bwd", tile, group, D, itemsize) <= _MAX_VMEM),
               default=0)


def own_join_untiled(L: int, H: int, Hk: int, D: int, block: int,
                     itemsize: int) -> Optional[str]:
    """Why the kernels `bd_own_join_fwd` and `bd_own_join_bwd` cannot take
    halves of `L` rows of `H` heads of `D` over `Hk` key-value heads under
    blocks of `block` rows, or None where they can: a head whole tiles of
    128 lanes, a trip's 128 rows whole blocks, a half whole trips, and a
    step of the backward within VMEM."""
    if H % Hk:
        return f"{H} query heads do not divide among {Hk} key-value heads"
    if D % _LANES:
        return f"heads of {D} are no whole tiles of {_LANES} lanes"
    if block < 1 or _TRIP % block:
        return f"blocks of {block} rows do not divide a trip's {_TRIP}"
    if L % _TRIP:
        return f"halves of {L} rows are no whole trips of {_TRIP}"
    if not own_join_tile(L, H // Hk, D, itemsize):
        need = 2 * own_join_vmem_bytes("bwd", _TRIP, H // Hk, D, itemsize)
        return (f"a trip of bd_own_join_bwd over a group of {H // Hk} heads "
                f"of {D} needs {need} bytes of VMEM, over {_MAX_VMEM}")
    return None


@functools.lru_cache(maxsize=None)
def _log_own_join(kernels, untiled, shape, block, dtype):
    """One line for each shape a process traces, as `ops/mamba_passes.py`'s
    `_log_pass`: which path, and the kernels' grid, blocks and VMEM."""
    B, L, H, Hk, D = shape
    said = (f"block diffusion's own block and join at B {B}, 2 x {L} rows, "
            f"{H} heads of {D} over {Hk}, blocks of {block}, {dtype}")
    if not kernels:
        logger.info("%s: jax.numpy", said)
    elif untiled:
        logger.info("%s: jax.numpy, because %s", said, untiled)
    else:
        item, g = jnp.dtype(dtype).itemsize, H // Hk
        tile = own_join_tile(L, g, D, item)
        logger.info(
            "%s: bd_own_join_fwd and bd_own_join_bwd, grid (%d, 2, %d), "
            "blocks [%d, %d, %d] of q and oS, [%d, %d] of k and v, [%d, %d] "
            "of o, %d rows a trip, VMEM %d and %d bytes", said, B * Hk,
            L // tile, g, tile, D, tile, D, tile, g * D, _TRIP,
            *(own_join_vmem_bytes(k, tile, g, D, item)
              for k in ("fwd", "bwd")))


def _wide_dot(a, b, dims):
    """float32 `a` times `b` on the MXU at `a`'s full precision. bf16 `b` is
    exact in float32, so the products of `a`'s three bf16 parts (its
    rounding, the rest's, and the rest's of that: 24 bits) are all there
    is, three passes; float32 `b` takes the compiler's most."""
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            a, b.astype(_F32), dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=_F32)
    total, rest = None, a
    for _ in range(3):
        part = rest.astype(b.dtype)
        rest = rest - part.astype(_F32)
        product = _dot(part, b, dims)
        total = product if total is None else total + product
    return total


def join_shares(lse_l, lse_r):
    """(the left part's share, the right part's) of two partial softmaxes
    joined, `e^lse / (e^lse_l + e^lse_r)`, float32: `o = share_l o_l +
    share_r o_r` is `ops/eva.py` `_join`'s o. The left's is the sigmoid of
    the lse's difference, which is the division by the larger lse in one
    exponential; one of the two lse is finite, and the other's -inf gives
    shares of 0 and 1."""
    share_l = jax.nn.sigmoid(lse_l - lse_r)
    return share_l, 1.0 - share_l


def _own_rows(q, k, own, scale):
    """(p `[n, n]`, lse `[n, 1]`), float32: a trip's rows of one head
    against the trip's keys where `own` says they share a block, the
    softmax's weights (a row's sum to 1) and the log of its sum."""
    s = jnp.where(own, _dot(q, k, _NT) * scale, _BIG_NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    total = p.sum(axis=-1, keepdims=True)
    return p / total, m + jnp.log(total)


def _own_mask(block: int):
    """`[128, 128]` bool: row and column of a trip in one block (`block` a
    power of two, as every divisor of 128 is)."""
    shift = block.bit_length() - 1
    rows = jax.lax.broadcasted_iota(jnp.int32, (_TRIP, _TRIP), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (_TRIP, _TRIP), 1)
    return (rows >> shift) == (cols >> shift), rows == cols


def _column(row, diagonal):
    """`[1, n]` along the lanes as `[n, 1]` down the sublanes."""
    return jnp.sum(jnp.where(diagonal, row, 0.0), axis=-1, keepdims=True)


def _own_join_fwd_kernel(q_ref, k_ref, v_ref, os_ref, lse_ref, o_ref, *,
                         block, scale):
    """One tile of rows of one half and one key-value head: `q_ref`,
    `os_ref` `[g, tile, D]` its group's heads, `k_ref`, `v_ref`
    `[1, tile, D]`, `lse_ref` `[1, g, tile]`, `o_ref` `[1, tile, g D]`."""
    from jax.experimental import pallas as pl

    group, tile, D = q_ref.shape
    own, diagonal = _own_mask(block)

    def trip(i, _):
        at = pl.ds(pl.multiple_of(i * _TRIP, _TRIP), _TRIP)
        k, v = k_ref[0, at, :], v_ref[0, at, :]
        for h in range(group):
            p, lse_o = _own_rows(q_ref[h, at, :], k, own, scale)
            share_s, share_o = join_shares(
                _column(lse_ref[0, h:h + 1, at], diagonal), lse_o)
            o_ref[0, at, h * D:(h + 1) * D] = (
                share_s * os_ref[h, at, :].astype(_F32)
                + share_o * _wide_dot(p, v, _NN)).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tile // _TRIP, trip, 0)


def _own_join_bwd_kernel(q_ref, k_ref, v_ref, os_ref, lse_ref, do_ref,
                         dq_ref, dk_ref, dv_ref, dos_ref, dlse_ref, *,
                         block, scale):
    """The forward's tile with o's cotangent `do_ref` `[1, tile, g D]`: the
    scores, both weights and the shares made again; `dq_ref`, `dos_ref`
    `[g, tile, D]`, `dlse_ref` `[1, g, tile]`, and `dk_ref`, `dv_ref`
    `[1, tile, D]` summed over the group's heads. A backward product
    carries its forward's precision: ds rounds to the operands' dtype into
    dq and dk as q and k are in `q k^T`, the weights go float32 into dv as
    into `p v`; `do v^T` is exact as it is."""
    from jax.experimental import pallas as pl

    group, tile, D = q_ref.shape
    own, diagonal = _own_mask(block)

    def trip(i, _):
        at = pl.ds(pl.multiple_of(i * _TRIP, _TRIP), _TRIP)
        k, v = k_ref[0, at, :], v_ref[0, at, :]
        dk = dv = jnp.zeros((_TRIP, D), _F32)
        for h in range(group):
            q, do = q_ref[h, at, :], do_ref[0, at, h * D:(h + 1) * D]
            p, lse_o = _own_rows(q, k, own, scale)
            share_s, share_o = join_shares(
                _column(lse_ref[0, h:h + 1, at], diagonal), lse_o)
            do32 = do.astype(_F32)
            # o = share_s oS + share_o oO, share_s = sigmoid(lseS - lseO):
            # `do . oO` is the row's sum of `p (do v^T)`, so oO is not made
            along = _dot(do, v, _NT)
            own_dot = jnp.sum(p * along, axis=-1, keepdims=True)
            dlse = share_s * share_o * (jnp.sum(
                do32 * os_ref[h, at, :].astype(_F32), axis=-1,
                keepdims=True) - own_dot)
            ds = (p * (share_o * (along - own_dot) - dlse) * scale).astype(
                q.dtype)
            dq_ref[h, at, :] = _dot(ds, k, _NN).astype(dq_ref.dtype)
            dk = dk + _dot(ds, q, _TN)
            dv = dv + _wide_dot(share_o * p, do, _TN)
            dos_ref[h, at, :] = (share_s * do32).astype(dos_ref.dtype)
            dlse_ref[0, h:h + 1, at] = jnp.sum(
                jnp.where(diagonal, dlse, 0.0), axis=0, keepdims=True)
        dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, at, :] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tile // _TRIP, trip, 0)


def _own_join_call(name, kernel, operands, outs, kinds, batch, tile,
                   interpret):
    """`pallas_call` of `bd_own_join_fwd` or `bd_own_join_bwd` under the
    scope `bd_own_join`, over (batch row and key-value head, half, tile of
    the half's rows). Every array lies as its neighbour leaves or takes it,
    and `kinds` says which of four each of `operands` and `outs` is:
    "heads", `[B 2 H, L, D]` with the halves folded into the heads
    (`fold_halves`) and the heads into the batch, as the staircase's kernels
    read q and write oS, a block the `g` heads of one half's group; "lse",
    `[B 2 Hk, g, L]` float32, the rows on the lanes, as they write lse;
    "keys", `[B Hk, 2 L, D]`, k and v, a block the tile of the half's rows;
    and "rows", `[B, 2 L, H D]`, o as `wo`'s product reads it, a block the
    group's `g D` lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k = operands[:2]
    (_, L, D), kv_rows = q.shape, k.shape[0]
    group = q.shape[0] // (2 * kv_rows)
    steps = L // tile
    kv_heads = kv_rows // batch
    specs = {
        "heads": pl.BlockSpec((group, tile, D),
                              lambda bj, s, i: (2 * bj + s, i, 0)),
        "lse": pl.BlockSpec((1, group, tile),
                            lambda bj, s, i: (2 * bj + s, 0, i)),
        "keys": pl.BlockSpec((1, tile, D),
                             lambda bj, s, i: (bj, s * steps + i, 0)),
        "rows": pl.BlockSpec(
            (1, tile, group * D),
            lambda bj, s, i: (bj // kv_heads, s * steps + i, bj % kv_heads)),
    }
    need = 2 * own_join_vmem_bytes(name, tile, group, D,
                                   jnp.dtype(q.dtype).itemsize)
    with jax.named_scope("bd_own_join"):
        return _pallas_call(
            kernel,
            grid=(kv_rows, 2, steps),
            in_specs=[specs[kind] for kind in kinds[:len(operands)]],
            out_specs=[specs[kind] for kind in kinds[len(operands):]],
            out_shape=outs,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3,
                vmem_limit_bytes=min(max(_DEFAULT_VMEM, need), _MAX_VMEM)),
            interpret=interpret,
            name=name,
        )(*operands)


_INPUTS = ("heads", "keys", "keys", "heads", "lse")  # q, k, v, oS, lseS


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _own_join_fwd(q, k, v, o_s, lse_s, batch, block, scale, tile, interpret):
    return _own_join_call(
        "bd_own_join_fwd",
        functools.partial(_own_join_fwd_kernel, block=block, scale=scale),
        (q, k, v, o_s, lse_s),
        [jax.ShapeDtypeStruct(
            (batch, k.shape[1], q.shape[0] // (2 * batch) * q.shape[2]),
            q.dtype)],
        (*_INPUTS, "rows"), batch, tile, interpret)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _own_join(q, k, v, o_s, lse_s, batch, block, scale, tile, interpret):
    """o `[B, 2 L, H D]` of q and oS `[B 2 H, L, D]`, k and v `[B Hk, 2 L,
    D]` and lseS `[B 2 Hk, g, L]` float32 (`_own_join_call` says how each
    lies): the own block's partial softmax joined with the staircase's, by
    the kernels. The backward's residuals are the operands."""
    return _own_join_fwd(q, k, v, o_s, lse_s, batch, block, scale, tile,
                         interpret)


def _own_join_vjp_fwd(q, k, v, o_s, lse_s, *static):
    return _own_join_fwd(q, k, v, o_s, lse_s, *static), (q, k, v, o_s, lse_s)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _own_join_bwd(q, k, v, o_s, lse_s, do, batch, block, scale, tile,
                  interpret):
    return tuple(_own_join_call(
        "bd_own_join_bwd",
        functools.partial(_own_join_bwd_kernel, block=block, scale=scale),
        (q, k, v, o_s, lse_s, do),
        [jax.ShapeDtypeStruct(a.shape, a.dtype)
         for a in (q, k, v, o_s, lse_s)],
        (*_INPUTS, "rows", *_INPUTS), batch, tile, interpret))


def _own_join_vjp_bwd(batch, block, scale, tile, interpret, res, do):
    """`_own_join_bwd` inside a `while` of one trip whose count the compiler
    cannot see (`lse == lse` somewhere: false for a NaN alone, and then the
    second trip computes the same). Without the loop the TPU compiler's
    memory-space assignment dies on the comparison's program of
    `sdar.tokens16k` (one sequence of 4,096 tokens, the layers' backward
    scanned): SIGSEGV in `BestFitRepacker::Finish` behind "We did not find a
    place for our sliced allocation", on the chip and compiled for a
    described v5e alike, whatever the kernels' tile, VMEM limit, operands'
    memory space or neighbours (PERF.md section 6, PR 71, has the twenty
    forms tried). The kernel's results leave as the loop's state."""
    lse_s = res[4]
    trips = 1 + (lse_s[0, 0, 0] != lse_s[0, 0, 0]).astype(jnp.int32)

    def trip(state):
        return state[0] + 1, _own_join_bwd(
            *res, do, batch, block, scale, tile, interpret)

    return jax.lax.while_loop(
        lambda state: state[0] < trips, trip,
        (jnp.int32(0), tuple(jnp.zeros(a.shape, a.dtype) for a in res)))[1]


_own_join.defvjp(_own_join_vjp_fwd, _own_join_vjp_bwd)


def attention_by_kernels(q, k, v, block: int, scale: float, keep_ctx: bool,
                         interpret: bool):
    """`block_diffusion_attention` where the own block and the join tile
    (`own_join_untiled`): the staircase's kernels and `_own_join`'s, with no
    copy between them. q's halves are folded into its heads and the heads
    into the batch once (`fold_halves`, `ops/flash_attention.py`
    `_to_kernels`), k's and v's heads into the batch; both pairs of kernels
    read those, `flash_fwd_stair` writes oS and lseS where
    `bd_own_join_fwd` reads them, `bd_own_join_bwd` writes their cotangents
    where `flash_bwd_dkv_dq_stair` reads them, and q's two cotangents are
    added as they lie, before the one transpose back."""
    B, R, H, D = q.shape
    L = R // 2
    group = H // k.shape[2]
    with jax.named_scope("bd_stair"):
        qf, kf, vf, _ = _to_kernels(fold_halves(q, k.shape[2]), k, v)
        o_s, lse_s = _flash_lse(
            qf, kf[:, L:], vf[:, L:], False, scale, None, None, interpret,
            keep_ctx, None, (block, block), 1)
    # lse's rows by the group, `[B 2 Hk, g, L]`: a reshape that moves nothing
    o = _own_join(qf, kf, vf, o_s, lse_s.reshape(-1, group, L), B, block,
                  scale,
                  own_join_tile(L, group, D, jnp.dtype(q.dtype).itemsize),
                  interpret)
    return o.reshape(B, R, H, D)


def block_diffusion_attention(q, k, v, *, block: int, impl: str = "auto",
                              keep_ctx: bool = False,
                              interpret: bool = False) -> jax.Array:
    """o [B, 2 L, H, D] in q's dtype: block-diffusion attention of q
    [B, 2 L, H, D] and k, v [B, 2 L, Hk, D] (already rotated, both halves at
    positions `0 .. L - 1`), the noisy half first; the module's docstring has
    the rule and the parts. With `keep_ctx` the kernels' residuals are named
    `attn_ctx` (`jax.ad_checkpoint`). `interpret` runs the Pallas path in
    interpret mode, for tests."""
    B, R, H, D = q.shape
    if k.shape != v.shape or k.shape[:2] != (B, R) or H % k.shape[2]:
        raise ValueError(
            f"block-diffusion attention takes k and v of one shape at heads "
            f"that divide q's: q {q.shape}, k {k.shape}, v {v.shape}")
    if block < 1 or R % (2 * block):
        raise ValueError(
            f"{R} rows are not two halves of whole blocks of {block}")
    pallas = interpret or resolve_impl(impl) == "pallas"
    scale = 1.0 / math.sqrt(D)
    untiled = own_join_untiled(R // 2, H, k.shape[2], D, block,
                               jnp.dtype(q.dtype).itemsize)
    _log_own_join(pallas, untiled, (B, R // 2, H, k.shape[2], D), block,
                  jnp.dtype(q.dtype).name)
    if pallas and not untiled:
        return attention_by_kernels(q, k, v, block, scale, keep_ctx,
                                    interpret)
    o_s, lse_s = stair_part(q, k, v, block, scale, pallas, keep_ctx,
                            interpret)
    if keep_ctx and not pallas:  # the kernels name their own residuals
        o_s, lse_s = (checkpoint_name(x, "attn_ctx") for x in (o_s, lse_s))
    return own_block_and_join(q, k, v, o_s, lse_s, block, scale)


def dense_mask(L: int, block: int):
    """[2 L, 2 L] bool, the rule as one mask over the doubled stream (the
    noisy half first): what the parts above walk, for tests and for the
    operation counts. Row r sees column c where c is clean and in a block
    before r's, or c is of r's half and block."""
    pos = jnp.arange(2 * L) % L
    half = jnp.arange(2 * L) // L
    blk = pos // block
    earlier_clean = jnp.logical_and(half[None, :] == 1,
                                    blk[None, :] < blk[:, None])
    own = jnp.logical_and(half[None, :] == half[:, None],
                          blk[None, :] == blk[:, None])
    return jnp.logical_or(earlier_clean, own)


def pairs_per_token(seq_len: int, block: int) -> float:
    """The (query, key) pairs a head walks for each of a sequence's
    `seq_len` tokens: both of its rows against the clean rows of the earlier
    blocks, `block * (i // block)` each, and against their own block's
    `block` rows: `seq_len - block + 2 block` on average, `seq_len + block`."""
    return float(seq_len + block)
