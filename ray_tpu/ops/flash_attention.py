"""Blocked (flash) attention as a Pallas TPU kernel.

Online-softmax attention tiled for the MXU: the forward's grid walks
(batch*heads, q-block, k-block) with the k dimension innermost; running
max/denominator and the output accumulator live in VMEM scratch that
persists across the k steps and is flushed on the last one.

Dispatch: `mha(impl="auto")` picks this kernel when JAX reports a TPU and an
XLA einsum implementation on any other platform (tests run the kernel in
interpret mode on tiny shapes via `flash_attention(..., interpret=True)`).

Backward pass uses recompute (custom_vjp re-derives the tile softmax from
q, k and the saved lse), trading FLOPs for the O(T^2) memory XLA would
otherwise materialize. Its residuals are q, k, v, o and lse; with `keep_ctx`
the forward rule names o and lse `attn_ctx` (`jax.ad_checkpoint`), so that a
caller under `jax.checkpoint` whose policy keeps that name does not run the
forward kernel a second time for them.

The backward is one kernel, `flash_bwd_dkv_dq`, wherever it fits: it walks
(batch*heads, k-block, q-block) with q innermost, makes a tile's s, p, dp and
ds once and takes dv, dk and dq from them, five matmuls a tile. dk and dv
are summed over the q steps of a k tile in VMEM scratch; dq is summed over
k tiles, a whole column of the grid apart, so its f32 sum holds all the q
tiles of the (batch, head) row. The row leaves by one of two exits
(`FlashTiles.exit`), which `flash_tiles` takes from the shape alone. "block":
the output's block is the whole row too (8.4 MB of f32 and a 4.2 MB block,
which the pipeline keeps twice, at T 8192, D 192), a tile of the sum is
rounded into it when complete and the pipeline writes the row back while the
next computes: every shape whose cheapest tile has room beside such blocks,
1.5 to 1.8 % the faster at T 4096 and level at T 8192 (the chip, PR 49:
ROADMAP D23 has the sweep). "tile": where it has
not, the output lies in HBM whole (`pl.ANY`) with no block in VMEM, and a
completed tile of the sum is rounded into a staged tile and copied to its
rows by the kernel's own DMA, once, so that the sums alone stay:
`keyevl2.tokens16k`'s layer, T 16,384 at 8 query heads a key-value head,
25.2 MB of sums where the blocks would be 25.2 MB more. The tile exit is
offered at every width (PR 75). Mosaic takes no DMA to rows that are no
whole lanes, so a gradient 64 or 192 wide leaves 128 or 256 wide: the
staged tile and the output are `_whole_lanes` of the width, the flush
rounds the sum into the first columns (the rest zero, written at the row's
first step), and the caller slices the width back; the plan pays for the
padded columns by their bytes (`_padded_exit_bytes`), and at whole lanes
every array, tile and program is the one it was. Under a group, dk and dv
that together fill no more than a tile of lanes (64 and 64) are the columns
of one sum, one staged tile and one output `[B Hk, S, 128]`
(`_share_lanes`: half the VMEM of two sums half empty; the MXU places dv's
product in the upper columns through operands `_at_lanes` pads), which the
caller splits: `phi4flash.tokens16k`'s paired heads (64 / 128 under a group
of 2 at T 16,384) and `granite4hmicro.longctx`'s (64 / 64 under a group of
4 at T 32,768) fit so. `flash_bwd_kernels`
decides whether either fits VMEM beside some tile and logs the choice once;
where neither does, `flash_bwd_dq` (k innermost) and `flash_bwd_dkv` run as
before, each making the tile for itself, seven matmuls between them (no
cell's shape since PR 75).

Grouped-query attention makes no copy of k or v. They reach every kernel at
their own heads, `[B*Hk, S, D]` and `[B*Hk, S, Dv]`, and with heads folded
batch-major the index maps name the key-value row of query row `bh`:
`bh // group`, `group = H / Hk`. The forward's and dq's grids, bodies and
values are those of the kernels fed repeated k and v. dk and dv leave at
`[B*Hk, S, ...]`, summed over a group's heads in f32 in VMEM and rounded
once: `flash_bwd_dkv` walks the group's heads inside a key column, grid
`(B*Hk, key tiles, group * q steps)`, with the key tile's sums it has;
`flash_bwd_dkv_dq` takes the group's head as a grid dimension of its own,
`(B*Hk, group, key tiles, q steps)`, so that a head's dq row is complete when
its key walk ends, and holds the key-value head's whole dk and dv, `S` rows
each, as it holds the head's dq (8.4 MB of f32 and 4.2 MB of blocks twice
more at S 8192, D 128 in bf16, whatever the group; `flash_tiles(group=)`
prices them), and they leave by dq's exit: as the row's blocks, or a key
tile at a time by DMA under the group's last head. With as many key heads
as query heads every grid, spec, scratch and body is the statement it was.

Where v lies (`v_heads`). The model holds v and dv as `[B, S, Hk, Dv]`,
which come from and go to a matmul as `[B, S, Hk Dv]`. Every kernel takes v
(and hands back dv) either with its heads folded into the batch by a
transpose, `[B Hk, S, Dv]`, or where it lies, `[B, S, Hk Dv]`, a reshape
that moves nothing, with `v_heads = Hk` beside it: the grid keeps its
(batch, head) rows, v's block is still `(1, tile, Dv)`, and the index map of
row `bh` names batch row `bh // v_heads`, the tile, and column block
`bh % v_heads` (`_head_block`). The folded layout is `v_heads` 1: the maps
and the programs the ones they were, not a second path. A block's last
dimension must be whole tiles of 128 lanes, so only a v whole tiles wide can
stay where it lies, and the entries leave it there where q and k are whole
tiles wide too and v has as many heads as q (`_to_kernels` has the chip's
readings behind both conditions). q, k and o are always folded: they come
from or go to a pass over `[B, T, H, D]` (a rotary turn, a norm, a join by
head), which the compiler tiles with the heads on the sublanes, so
`[B, T, H D]` is a copy of its own on the chip, while their transposes
mostly ride inside that pass's fusion (PERF.md section 6, PR 65, has the
chip's readings of all four pairs in place, and of this). lse and delta are
`[B H, T, 8]` float32. The shape decides, the backward's log line says what
it decided, and `ops/sparse_attention.py` folds for its own kernels' sake
and hands these theirs folded.

The four `pallas_call`s are named `flash_fwd` (with or without the lse
output), `flash_bwd_dkv_dq`, `flash_bwd_dq` and `flash_bwd_dkv`: the names a
profiler trace and the compiled HLO show, and the ones the benchmark's
per-kernel metrics read (docs/observability.md, "Device scopes"). Under a
window they are `flash_fwd_window`, `flash_bwd_dkv_dq_window`,
`flash_bwd_dq_window` and `flash_bwd_dkv_window`, which the same prefixes
match and a metric of their own can tell apart.

With `window=w` (and `causal`) key `j` counts for query `i` where `j <= i` and
`i - j < w`: a token sees itself and the `w - 1` before it, a band under the
diagonal. `window=None`, or a window no shorter than the sequence, is the
causal kernel: the same names, tiles, index maps and bodies.

A mask that is data (`_flash_fwd(mask=)` and the three backward calls;
`ops/sparse_attention.py` is the caller): a bit a (query, key) pair, set
where the pair counts, in an int8 array `[B, key tiles, T, block_k / 8]`.
Bit `b` of column `c` of key tile `j` is key `j * block_k + b * (block_k / 8)
+ c`: eight bit planes of `block_k / 8` columns (`_pack_bits` and
`_unpack_bits` are the rule, in the kernels and in `jax.numpy` alike), so a
kernel's tile of it is one block whatever the walk, an eighth of a byte a
pair, and at a key tile of 1,024 a plane is 128 lanes of the scores. Its
key tile is every kernel's. The walk is the causal one, tile for tile; every
body, bare or masked by position, also takes the tile's choice (`_pairs_mask`)
into its two selects, and the batch row of query row `bh` is `bh // heads`.
The calls are named `flash_fwd_sparse`, `flash_bwd_dkv_dq_sparse`,
`flash_bwd_dq_sparse` and `flash_bwd_dkv_sparse`. Without a mask every call
is the statement it was: same names, tiles, index maps and bodies.

A second entry, `flash_attention_lse`, returns `(o, lse)` with a
`custom_vjp` that takes lse's cotangent (`delta - dlse` in place of `delta`;
the kernels are the same), so that partial softmaxes over disjoint sets of
keys can be joined outside: `ops/eva.py` is the caller. It also takes a
*staircase* (`stair=(span, per)`, no `causal`): query `i` sees the first
`per * (i // span)` keys, the `per` keys of every span of queries before its
own. `_tile_kind` gives a tile a body where its last query sees its first
key and a mask where its first query does not see its last; `flash_tiles`
weighs the tiles that divide a span of queries and a span's keys, which are
whole or empty; the index maps clamp a step with no body to the row's
(column's) nearest tile that has one. A row that sees no key leaves o 0 and
lse -inf. A span finer than any tile (`ops/block_diffusion.py`: steps of a
block of 4 queries and 4 keys, `stair=(4, 4)`, at a group of 16 query heads
a key-value head) divides none: every tile the stairs cross, the diagonal's,
takes the in-tile mask (`k_pos < per * (q_pos // span)`), the tiles under it
the bare body and the ones above it none, the walk, the tile and the VMEM
the causal kernels' at that shape. The calls are named `flash_fwd_stair`, `flash_bwd_dkv_dq_stair`,
`flash_bwd_dq_stair` and `flash_bwd_dkv_stair`. `flash_attention`, the entry
that returns o alone, is the program it was.

The tile program, the same in all four kernels:

- Tiles come from the shape. `flash_tiles(kernel, T, S, D, dtype)` (with
  `v_dim=` where v is narrower or wider than q and k) returns `block_q` and
  `block_k` for one kernel: multiples of 128 that do not exceed
  the sequence (the sequence itself when it is shorter than 128), chosen to
  make the sum of two costs least: what every grid step costs whatever it
  holds, and the work of the tiles that have a body, of which the part above
  the causal diagonal, and under a window the part left of the band, is
  wasted (`window=`: the tiles with a body are the band's, the grid is the
  band's too, and the tiles that divide the sequence are weighed). It also
  returns the estimate of the VMEM the tile needs and the
  `vmem_limit_bytes` handed to Mosaic (the default 16 MiB where that is
  enough), the grid steps a (batch, head) row makes and the share of them
  that have a body, and what the row's grid costs by the sweeps'
  constants. `flash_attention(block_q=, block_k=)` force a tile,
  for tests; `None` is the shape's choice.
- Operands reach the MXU in the input's dtype. q, k, v and do go to
  `dot_general` as loaded, p and ds are cast to that dtype for the second
  matmuls, and every dot accumulates in f32. m, l, lse, delta, the
  accumulators and every exp are f32. An f32 input is fed as f32: the kernel
  never rounds below what it was given.
- A tile is masked only where the mask decides something: where the causal
  diagonal crosses it, or where it hangs over the end of a sequence (there
  the padded rows, which may hold NaN, are also zeroed before they reach a
  dot). A tile wholly under the diagonal and wholly inside the sequences
  takes a body with no iota, compare or select. A tile wholly above the
  diagonal has no body, and its grid step fetches nothing: the index maps
  clamp the walked index to the nearest tile of that row (column) that has
  one, so the step names the block already in VMEM. Under a window a tile
  the band's lower edge crosses masks `query - key < window`, and a tile
  wholly inside the band takes the bare body.
- Under a window the grid is the band's. A q row's walk over k (forward,
  dq) is `band_k` steps long, the most key tiles any row's band crosses,
  and step `j` of row `qi` is key tile `_first_k_with_body(qi) + j`; a key
  column's walk over q (dk/dv, all three gradients) is `band_q` steps from
  `_first_q_with_body(ki)`. No tile left of the band or below it is walked.
  A row (column) whose band crosses fewer tiles, the first rows, the last
  columns, a ragged end, has trailing steps past its last tile: they have
  no body, and the index maps clamp them to that tile, so they fetch
  nothing. The sums are zeroed at the walk's first step and flushed at its
  last; the one-kernel backward zeroes a q tile's rows of dq at the first
  key column whose band reaches them and rounds them out at the last.
- Outputs leave in the input's dtype: o, and dq, dk, dv, which the flush
  rounds once from the f32 accumulator (dk and dv once a key-value head:
  the sum over a group's query heads is part of that accumulator). lse and
  delta are f32 `[BH, T, 8]`.
- Two widths. q, k, dq and dk are `D` wide, v, o, do, dv and the forward's
  accumulator `Dv` wide (latent attention: 128 + 64 rotary against 128).
  A block's last dimension is the array's whole width, so 192 goes to the
  MXU as it is, with no zero column in HBM; in VMEM it fills two tiles of
  128 lanes, which is what `_vmem_bytes` counts. (The one exception is a
  row-long gradient of `flash_bwd_dkv_dq` that leaves a tile at a time: a
  DMA's rows are whole lanes, above.) With `Dv == D` every shape, tile and
  body is the one-width kernel's.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

_BIG_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    """One MXU matmul: the operands as they are, accumulated in f32.
    Operands narrower than f32 take a single pass whatever
    `jax_default_matmul_precision` says: their products are exact in f32,
    and Mosaic refuses "highest" for them. f32 operands follow the
    config."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32
    )


def _pallas_call(kernel, *, name: str, **params):
    """`pl.pallas_call(kernel, name=name, **params)` whose application to
    its operands is a span `pallas.trace` with the attribute `kernel`
    (docs/observability.md, "The train path"): every kernel of `ops/` is
    called through here. A kernel is applied while the program around it is
    traced, so the span runs then and never on a step's path: its row
    counts the kernel call sites traced, and its seconds (the kernel's body
    traced to a jaxpr) lie inside `jax.trace`'s. Applied outside any `jit`
    the span holds the kernel's lowering, compilation and run too."""
    from jax.experimental import pallas as pl

    call = pl.pallas_call(kernel, name=name, **params)

    def apply(*operands):
        with tracing.span("pallas.trace", kernel=name):
            return call(*operands)

    return apply


# ------------------------------------------------------------------- tiles

_LANES = 128
_DEFAULT_VMEM = 16 << 20  # Mosaic's scoped limit when none is given
_MAX_VMEM = 96 << 20  # of the v5e's 128 MiB
_MAX_BLOCK = 1024
# [bq, bk] f32 tiles a body holds at once (s/p, and dp, ds and a transposed
# copy in the backward), and [bq, bk] copies in the input's dtype (p; p, ds).
_LIVE_TILES = {"flash_fwd": (2, 1), "flash_bwd_dq": (4, 1),
               "flash_bwd_dkv": (4, 2), "flash_bwd_dkv_dq": (4, 2)}


class FlashTiles(NamedTuple):
    """One kernel's tile for one shape, and what follows from it."""
    block_q: int
    block_k: int
    grid_steps: int  # of one (batch, head) row, as the grid is walked
    active_share: float  # share of those steps whose tile has a body
    vmem_bytes: int  # estimate of what the tile needs
    vmem_limit_bytes: int  # what Mosaic is told it may use
    cost_us: float  # of a (batch, head) row's grid, by `_COST_US`
    # how a row-long gradient leaves `flash_bwd_dkv_dq`: "block", an output
    # block of the whole row beside its f32 sum, or "tile", the sum alone,
    # rounded out a tile at a time by the kernel's own DMA
    exit: str = "block"


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _whole_lanes(width: int) -> int:
    """`width` rounded up to whole tiles of 128 lanes: what a row of it
    fills in VMEM, and the least a DMA to its rows may be."""
    return _cdiv(width, _LANES) * _LANES


def _share_lanes(D: int, Dv: int, group: int) -> bool:
    """Whether `flash_bwd_dkv_dq` by tile holds dk's and dv's row-long sums
    as the columns of one: under a group (where they are row-long), at
    widths that together fill no more than a tile of lanes (64 and 64),
    where each alone would fill one half empty."""
    return group > 1 and D + Dv <= _LANES


def _active_tiles(T, S, block_q, block_k, causal, window=None,
                  stair=None) -> int:
    """Tiles with a body: all of them, or under causal those that hold a
    (query, key) pair with key <= query, and with a window only those of
    them that hold one with query - key < window: the band's. Under a
    staircase (`stair`) those that hold a key the tile's last query sees."""
    num_q, num_k = _cdiv(T, block_q), _cdiv(S, block_k)
    if stair is not None:
        return sum(
            min(num_k, _cdiv(_stair_keys((qi + 1) * block_q - 1, stair),
                             block_k))
            for qi in range(num_q))
    if not causal:
        return num_q * num_k
    if window is None:
        return sum(
            min(num_k, _cdiv((qi + 1) * block_q, block_k))
            for qi in range(num_q)
        )
    return sum(last - first + 1 for first, last in _band_rows(
        T, S, block_q, block_k, window))


def _band_rows(T, S, block_q, block_k, window):
    """(first, last) key tile with a body of every q row under a window:
    from the tile that holds the first key the row's first query sees to the
    one that holds its last query's own position (`_first_k_with_body`,
    `_last_k_with_body`, inside the array). A row past every key it could
    see (more queries than keys) has `first > last`."""
    num_q, num_k = _cdiv(T, block_q), _cdiv(S, block_k)
    return [(max(qi * block_q - window + 1, 0) // block_k,
             min(num_k - 1, ((qi + 1) * block_q - 1) // block_k))
            for qi in range(num_q)]


def _band_cols(T, S, block_q, block_k, window):
    """(first, last) q tile with a body of every key column under a window
    (`_first_q_with_body`, `_last_q_with_body`): from the tile that holds
    the column's first key's own query to the one that holds the last query
    that sees its last key."""
    num_q, num_k = _cdiv(T, block_q), _cdiv(S, block_k)
    return [(ki * block_k // block_q,
             min(num_q - 1, ((ki + 1) * block_k + window - 2) // block_q))
            for ki in range(num_k)]


def _stair_keys(query, stair):
    """The keys query `query` sees under the staircase `stair` = (span,
    per): the first `per * (query // span)`, the `per` keys of every span of
    queries before its own. A Python number or a traced one."""
    span, per = stair
    return per * (query // span)


_K_INNERMOST = ("flash_fwd", "flash_bwd_dq")  # the others walk q innermost


def _inner_steps(kernel, T, S, block_q, block_k, window=None) -> int:
    """Steps of `kernel`'s innermost grid dimension: every key tile of a q
    row (forward, dq) or every q tile of a key column (dk/dv, all three
    gradients), and under a window the most of them that the band crosses
    in any row (column)."""
    k_inner = kernel in _K_INNERMOST
    if window is None:
        return _cdiv(S, block_k) if k_inner else _cdiv(T, block_q)
    spans = (_band_rows if k_inner else _band_cols)(
        T, S, block_q, block_k, window)
    return max(1, max(last - first + 1 for first, last in spans))


def _vmem_bytes(kernel, block_q, block_k, D, itemsize, Dv=None,
                T=None, S=None, group: int = 1, sparse: bool = False,
                by_tile: bool = False) -> int:
    """Blocks in flight (double-buffered), scratch and the body's live
    [bq, bk] tiles, for q and k `D` wide and v `Dv` wide (`D` where None).
    A VMEM row is whole tiles of 128 lanes whatever the width is. The one
    kernel that makes all three gradients also holds a (batch, head) row's
    whole dq, `T` rows in whole q tiles: its f32 sum and its block; and
    where `group` query heads share a key-value head, that head's whole dk
    and dv, `S` rows in whole key tiles, in place of one key tile's.
    `by_tile`: the row-long gradients have no block, their sums alone stay
    and a tile of each is staged for the DMA that takes it out; dk and dv
    that share a tile of lanes (`_share_lanes`) have one sum between them."""
    qk = _whole_lanes(D)
    vo = qk if Dv is None else _whole_lanes(Dv)
    q_qk, q_vo = block_q * qk, block_q * vo  # elements: q, dq; o, do
    k_qk, k_vo = block_k * qk, block_k * vo  # k, dk; v, dv
    row = block_q * _LANES * 4  # an lse/delta block, or m or l
    if kernel == "flash_fwd":
        blocks = (q_qk + q_vo + k_qk + k_vo) * itemsize + row
        scratch = q_vo * 4 + 2 * row
    elif kernel == "flash_bwd_dq":
        blocks = (2 * q_qk + q_vo + k_qk + k_vo) * itemsize + 2 * row
        scratch = q_qk * 4
    else:
        blocks = (q_qk + q_vo + 2 * k_qk + 2 * k_vo) * itemsize + 2 * row
        k_sums = k_qk + k_vo  # a key tile of dk's and dv's sums: or of one
        if by_tile and _share_lanes(D, D if Dv is None else Dv, group):
            k_sums = block_k * _LANES
        scratch = k_sums * 4
        if kernel == "flash_bwd_dkv_dq":
            dq_row = _cdiv(T, block_q) * q_qk
            scratch += dq_row * 4
            if group > 1:  # dk and dv leave and are summed by the row too
                more = (_cdiv(S, block_k) - 1) * k_sums
                scratch += more * 4
            if not by_tile:
                blocks += dq_row * itemsize
                if group > 1:
                    blocks += more * itemsize
            else:  # dq's staged tile; dk's and dv's in place of their blocks
                scratch += q_qk * itemsize
                if group > 1:
                    blocks -= (k_qk + k_vo) * itemsize
                    scratch += k_sums * itemsize
    f32_tiles, dtype_tiles = _LIVE_TILES[kernel]
    live = block_q * block_k * (4 * f32_tiles + itemsize * dtype_tiles)
    if sparse:  # the mask's block, a bit a pair, and its tile as 32 bits
        blocks += block_q * block_k // 8
        live += block_q * block_k * 4
    return 2 * blocks + scratch + live


def _block_candidates(seq: int, whole: bool = False):
    """The tiles a sequence of `seq` may take along one side. `whole` keeps
    those that divide it, where some do: a tile that hangs over the
    sequence's end sanitises its padded rows in every masked body, for
    which `_COST_US` has no term (under a window that read 1.4 to 2.2 ms a
    backward call at BH 128, T 8192 where 640 and 768 were chosen for
    512 x 1024: PERF.md section 6, PR 40)."""
    if seq < _LANES:
        return [seq]
    every = list(range(_LANES, min(seq, _MAX_BLOCK) + 1, _LANES))
    dividing = [b for b in every if seq % b == 0]
    return dividing if whole and dividing else every


# What a tile costs, in microseconds, as the sweep on one v5e found it
# ({128, 256, 512, 1024}^2 at BH 128, T 4096, D 128 and at BH 384, T 1024,
# D 64, bf16, causal; PERF.md section 6, PR 26): a grid step whatever it
# holds; 1,024 query rows of a tile that has a body (the per-row work that
# does not grow with the tile's width: cross-lane max and sum, the rescale
# of m, l and the accumulator); 2**20 (query, key) pairs of such a tile
# (the matmuls, and the exp and its neighbours over [bq, bk]).
_COST_US = {
    "flash_fwd": (0.20, 2.75, 2.25),
    "flash_bwd_dq": (0.29, 0.0, 5.8),
    "flash_bwd_dkv": (0.29, 0.7, 5.35),
    # {512, 1024}^2 with 512 x 1024 and 1024 x 512 at BH 128, T 4096, D 128
    # (within 2 %), and at BH 64, T 8192, 192 / 128 through `_pairs_factor`
    # (within 1 %): 8.0 where the two kernels it stands for cost 11.15
    # (PERF.md section 6, PR 35)
    "flash_bwd_dkv_dq": (0.29, 0.0, 8.0),
}


# The matmuls over a tile's pairs, by the width each walks: (over q and k's
# width, over v's). Forward s = q k^T | o = p v; dq: s, dq = ds k | dp = do
# v^T; dk/dv: s, dk = ds^T q | dp, dv = p^T do; all three gradients from one
# tile: s, dq, dk | dp, dv.
_PAIR_MATMULS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
                 "flash_bwd_dkv": (2, 2), "flash_bwd_dkv_dq": (3, 2)}


# A byte of HBM traffic, in microseconds: a v5e's 819 GB/s.
_HBM_US_A_BYTE = 1 / 819e3


def _padded_exit_bytes(rows_q: int, rows_k: int, D: int, Dv: int,
                       itemsize: int, group: int = 1) -> int:
    """What the tile-at-a-time exit of a (batch, head) row moves in HBM
    that the row's blocks would not. Mosaic takes no DMA to rows that are
    no whole lanes ("Slice shape along dimension 2 must be aligned to
    tiling (128)"), so a row-long gradient narrower than its lanes leaves
    at `_whole_lanes` of its width, the columns past it zero, and a slice
    after the call reads that and writes the width: twice the padded
    array. dq of `rows_q` rows always; dk and dv of `rows_k` where a group
    makes them row-long, a `group`-th of each to a query head's row.
    Nothing at widths of whole lanes, whose arrays are the ones they were."""
    def padded(rows, width):
        lanes = _whole_lanes(width)
        return 2 * rows * lanes * itemsize if lanes != width else 0

    moved = padded(rows_q, D)
    if _share_lanes(D, Dv, group):  # one array of dk and dv, split after
        moved += 2 * rows_k * _LANES * itemsize // group
    elif group > 1:
        moved += (padded(rows_k, D) + padded(rows_k, Dv)) // group
    return moved


def _pairs_factor(kernel: str, D: int, Dv: int) -> float:
    """What a pair costs with q and k `D` wide, over what it costs at `Dv`
    all round, where `_COST_US` was measured: the MXU takes a width in
    passes of 128 lanes, so 192 costs two, and one only of a kernel's
    matmuls walk q and k's width. 1 at equal widths. The sweep at BH 64,
    T 8192, bf16, causal on one v5e (PERF.md section 6, PR 34) read 1.49,
    1.70 and 1.52 for (192, 128) over (128, 128) where this gives 1.5,
    1.67 and 1.5, and the tile it leads to, 1024 x 1024, was the fastest
    of {256, 512, 1024}^2 in all three kernels."""
    over_qk, over_v = _PAIR_MATMULS[kernel]
    passes_qk, passes_v = _cdiv(D, _LANES), _cdiv(Dv, _LANES)
    return (over_qk * passes_qk + over_v * passes_v) / (
        (over_qk + over_v) * passes_v)


def flash_tiles(kernel: str, T: int, S: int, D: int, dtype, *,
                causal: bool = True, block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                v_dim: Optional[int] = None,
                window: Optional[int] = None, group: int = 1,
                sparse: bool = False, stair=None) -> FlashTiles:
    """The tile of `kernel` (`flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`,
    `flash_bwd_dkv_dq`) for q of [*, T, D] and k of [*, S, D] and v of
    [*, S, v_dim] (`D` where None) in `dtype`, `group` query heads to a
    key-value head. Pure: the shape decides, nothing is asked of a device.
    Among the tiles that fit VMEM it takes
    the one whose grid costs least by `_COST_US`: small tiles pay in grid
    steps, large ones in pairs above the causal diagonal that a diagonal
    tile computes and masks. What `flash_bwd_dkv_dq` must fit depends on
    how its row-long gradients leave (`FlashTiles.exit`; the module's
    docstring): the row's blocks if the cheapest tile has room beside
    them, else the sums alone. A forced `block_q` or `block_k` is taken as
    given (cut to the sequence) and the other is chosen. With `window`
    (causal, `query - key < window`) the tiles with a body are the band's
    and so is the grid (`_inner_steps`): a large tile pays in pairs on both
    sides of the band, a small one in steps, and one whose band crosses
    fewer tiles in some rows (columns) than in others in the trailing steps
    of those. Under a staircase (`stair` = (span, per), not causal: query
    `i` sees the first `per * (i // span)` keys) the tiles with a body are
    the staircase's, and of the tiles those are weighed that divide a span
    of queries and a span's keys, where some do: such a tile is whole or
    empty, and no body masks a pair. Where none does (a span finer than
    128: block diffusion's steps of 4) every candidate is weighed, the
    diagonal's tiles are masked ones, and the choice comes out the causal
    walk's: at T = S = 16,384, D 128 and a group of 16, 1024 x 1024 forward
    and 1024 x 768 for the one backward kernel with the sums alone held
    (46.0 MB of VMEM, `keyevl2.tokens16k`'s at a group of 8)."""
    itemsize = jnp.dtype(dtype).itemsize
    Dv = D if v_dim is None else v_dim
    step_us, rows_us, pairs_us = _COST_US[kernel]
    pairs_us = pairs_us * _pairs_factor(kernel, D, Dv)

    def plan(bq, bk, exit):
        outer = _cdiv(T, bq) if kernel in _K_INNERMOST else _cdiv(S, bk)
        steps = outer * _inner_steps(kernel, T, S, bq, bk, window)
        active = _active_tiles(T, S, bq, bk, causal, window, stair)
        vmem = _vmem_bytes(kernel, bq, bk, D, itemsize, Dv, T, S, group,
                           sparse, by_tile=exit == "tile")
        cost = steps * step_us + active * (
            rows_us * bq / 1024 + pairs_us * bq * bk / 2 ** 20)
        if exit == "tile":
            cost += _HBM_US_A_BYTE * _padded_exit_bytes(
                _cdiv(T, bq) * bq, _cdiv(S, bk) * bk, D, Dv, itemsize, group)
        return FlashTiles(bq, bk, steps, active / steps, vmem,
                          max(_DEFAULT_VMEM, 2 * vmem), cost, exit)

    whole = window is not None
    qs = [min(block_q, T)] if block_q else _block_candidates(T, whole)
    ks = [min(block_k, S)] if block_k else _block_candidates(S, whole)
    if stair is not None:
        qs = [b for b in qs if stair[0] % b == 0] or qs
        ks = [b for b in ks if stair[1] % b == 0] or ks

    def fitting(exit):
        plans = [plan(bq, bk, exit) for bq in qs for bk in ks]
        return [p for p in plans if p.vmem_limit_bytes <= _MAX_VMEM]

    # The cheapest tile that fits, and of the exits that have room for it
    # the row's blocks: the program of every shape whose cheapest tile had
    # room beside them, 1.5 to 1.8 % the faster at T 4096 (PERF.md section 6,
    # PR 49). The sums alone at every width: one of no whole lanes leaves
    # padded to them (`_padded_exit_bytes`), which its plan pays for.
    fits = fitting("block")
    if kernel == "flash_bwd_dkv_dq":
        fits += fitting("tile")
    return min(fits or [plan(qs[0], ks[0], "block")],
               key=lambda p: (p.cost_us, p.exit == "tile"))


def flash_bwd_kernels(T: int, S: int, D: int, dtype, *, causal: bool = True,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      v_dim: Optional[int] = None,
                      window: Optional[int] = None,
                      group: int = 1, sparse: bool = False,
                      stair=None) -> Tuple[str, ...]:
    """The kernels of one backward, for the shapes `flash_tiles` takes:
    `("flash_bwd_dkv_dq",)`, all three gradients from one pass over the
    score tiles, or `("flash_bwd_dq", "flash_bwd_dkv")`, which make every
    tile twice and hold one q tile's dq at a time. Pure, as `flash_tiles`
    is. The one kernel holds a (batch, head) row's whole dq in VMEM, `T`
    rows of f32, and with `group` query heads to a key-value head that
    head's whole dk and dv, `S` rows each, summed over the group (8.4 MB
    each at 8192 rows of 128): it is taken where that fits beside some
    tile, and the tile it leaves room for does not cost more in grid steps
    than the second pass saves. With the row's blocks beside the sums
    (`FlashTiles.exit` "block"; in bf16, causal, self-attention) that is T
    up to about 21k at q and k 192 wide and 44k at 64, 14k and 8k under a
    group; with the sums alone ("tile") 88k at 128 and at 64, 44k at 192,
    and under a group 29k, or 45k where dk and dv share a tile of lanes.
    Under a window its grid
    comes to a q tile's dq only through a key column whose band reaches it:
    where some q tile lies past every key it could see (more queries than
    keys) the two kernels run, whose dq walks every q row."""
    def tiles(kernel):
        return flash_tiles(kernel, T, S, D, dtype, causal=causal,
                           block_q=block_q, block_k=block_k, v_dim=v_dim,
                           window=window, group=group, sparse=sparse,
                           stair=stair)

    one, two = tiles("flash_bwd_dkv_dq"), ("flash_bwd_dq", "flash_bwd_dkv")
    if window is not None and any(first > last for first, last in _band_rows(
            T, S, one.block_q, one.block_k, window)):
        return two
    if (one.vmem_limit_bytes <= _MAX_VMEM
            and one.cost_us <= sum(tiles(kernel).cost_us for kernel in two)):
        return ("flash_bwd_dkv_dq",)
    return two


# ----------------------------------------------------------------- kernels

def _tile_kind(qi, ki, *, block_q, block_k, num_q, num_k, causal,
               seq_q, seq_k, window=None, stair=None):
    """(has_body, needs_mask) of grid tile (qi, ki): Python bools where the
    shape settles it, traced scalars where the grid position does.
    `seq_q=None` says padded q rows need no care (the forward: a row's
    output depends on that row alone, and padded rows are never written).
    `window`: a pair counts where `query - key < window` too, and the tile
    is the band grid's logical one, which a short row's (column's) trailing
    steps carry past the array's last: those have no body. `stair`
    (`_stair_keys`): the tile has a body where its last query sees its
    first key, and a mask where its first query does not see its last."""
    has_body, needs_mask = True, False
    if stair is not None:
        has_body = ki * block_k < _stair_keys((qi + 1) * block_q - 1, stair)
        needs_mask = (ki + 1) * block_k > _stair_keys(qi * block_q, stair)
    if causal:
        # some key of the tile is at or before some query of it
        has_body = (qi + 1) * block_q > ki * block_k
        # and some key of it is after some query of it
        needs_mask = (ki + 1) * block_k - 1 > qi * block_q
    if window is not None:
        # its last key is inside its first query's window: the nearest pair
        has_body = jnp.logical_and(
            has_body, qi * block_q - ((ki + 1) * block_k - 1) < window)
        # and its first key is outside its last query's: the farthest pair
        needs_mask = jnp.logical_or(
            needs_mask, (qi + 1) * block_q - 1 - ki * block_k >= window)
        has_body = jnp.logical_and(
            has_body, jnp.logical_and(qi < num_q, ki < num_k))
    if seq_k % block_k:
        needs_mask = jnp.logical_or(needs_mask, ki == num_k - 1)
    if seq_q is not None and seq_q % block_q:
        needs_mask = jnp.logical_or(needs_mask, qi == num_q - 1)
    return has_body, needs_mask


def _run_tile(body, has_body, needs_mask):
    """Run `body(masked)` for this grid step: not at all, masked or bare."""
    from jax.experimental import pallas as pl

    if needs_mask is False:
        if has_body is True:
            body(False)
        else:
            pl.when(has_body)(lambda: body(False))
        return
    pl.when(jnp.logical_and(has_body, needs_mask))(lambda: body(True))
    pl.when(jnp.logical_and(has_body, jnp.logical_not(needs_mask)))(
        lambda: body(False))


def _tile_mask(qi, ki, *, block_q, block_k, causal, seq_q, seq_k,
               window=None, stair=None):
    """[bq, bk] bool: the pair is inside both sequences and, under causal,
    the key is not after the query, nor `window` or more before it. Only
    the terms the shape leaves open."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    terms = []
    if seq_k % block_k:
        terms.append(k_pos < seq_k)  # padding keys past the true length
    if seq_q is not None and seq_q % block_q:
        terms.append(q_pos < seq_q)
    if causal:
        terms.append(q_pos >= k_pos)
    if window is not None:
        terms.append(q_pos - k_pos < window)
    if stair is not None:
        terms.append(k_pos < _stair_keys(q_pos, stair))
    return functools.reduce(jnp.logical_and, terms)


def _pack_bits(keep):
    """[..., rows, tile] bool as [..., rows, tile / 8] int8, a data mask's
    tile: bit `b` of column `c` is pair `b * (tile / 8) + c`."""
    width = keep.shape[-1] // 8
    plane = jax.lax.broadcasted_iota(
        jnp.int32, keep.shape, keep.ndim - 1) // width
    # bit 7 as -128: the sum is the int8's value, nothing to wrap
    bits = jnp.where(keep, jnp.where(
        plane == 7, -128, jnp.left_shift(1, plane)), 0)
    return sum(bits[..., b * width:(b + 1) * width]
               for b in range(8)).astype(jnp.int8)


def _unpack_bits(bits):
    """`_pack_bits`'s inverse: [..., rows, tile / 8] int8 as
    [..., rows, tile] bool, a plane after another along the columns."""
    bits = bits.astype(jnp.int32)
    return jnp.concatenate(
        [bits & (1 << b) for b in range(8)], axis=-1) != 0


def _pairs_mask(masked, mask_ref, qi, ki, **shape):
    """The [bq, bk] bool mask of a tile's body, or None where it has none:
    `_tile_mask`'s terms where the positions decide something (`masked`),
    and under a data mask (`mask_ref`: the tile's bits, set where the pair
    counts) that tile's choice as well, in every body."""
    mask = _tile_mask(qi, ki, **shape) if masked else None
    if mask_ref is None:
        return mask
    chosen = _unpack_bits(mask_ref[0, 0])
    return chosen if mask is None else jnp.logical_and(mask, chosen)


def _with_mask(kernel, operands: int):
    """`kernel` for a `pallas_call` whose operand after the first
    `operands` is the data mask's tile: it reaches the body as `mask_ref`."""
    def masked_kernel(*refs):
        return kernel(*refs[:operands], *refs[operands + 1:],
                      mask_ref=refs[operands])

    return masked_kernel


def _rows_valid(i, block, seq):
    """[block, 1] bool: which rows of tile `i` lie inside the sequence."""
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return row < seq


def _attn_fwd_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    acc_ref, m_ref, l_ref,  # VMEM scratch, persistent over the k grid dim
    *, block_q: int, block_k: int, num_q: int, num_k: int, steps: int,
    scale: float, causal: bool, seq_k: int, window: Optional[int] = None,
    mask_ref=None, stair=None,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = step = pl.program_id(2)  # of `steps`: the row's walk over k
    if window is not None:
        ki = _first_k_with_body(qi, block_q, block_k, window) + step
    # seq_q=None: padded q rows need no care here (see _tile_kind)
    shape = dict(block_q=block_q, block_k=block_k, causal=causal,
                 seq_q=None, seq_k=seq_k, window=window, stair=stair)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _BIG_NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body(masked):
        q = q_ref[0]  # [bq, D]
        k = k_ref[0]  # [bk, D]
        v = v_ref[0]  # [bk, Dv]
        s = _dot(q, k, _NT) * scale  # [bq, bk]
        mask = _pairs_mask(masked, mask_ref, qi, ki, **shape)
        if mask is not None:
            s = jnp.where(mask, s, _BIG_NEG)

        m_prev = m_ref[...]  # [bq, 128] (lane-replicated)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)  # [bq, 128]
        p = jnp.exp(s - m_new[:, :1])  # [bq, bk]
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_prev * alpha + jnp.broadcast_to(
            p.sum(axis=-1, keepdims=True), l_prev.shape
        )
        m_ref[...] = m_new
        if masked and seq_k % block_k:
            # Padded K/V rows may be NaN-filled; p is 0 there but 0*NaN=NaN.
            v = jnp.where(_rows_valid(ki, block_k, seq_k), v, 0.0)
        pv = _dot(p.astype(v.dtype), v, _NN)  # [bq, Dv]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    # Tiles strictly above the diagonal contribute nothing and have no
    # body; nor has a step past the last tile of a row's band.
    _run_tile(_body, *_tile_kind(qi, ki, num_q=num_q, num_k=num_k, **shape))

    @pl.when(step == steps - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _attn_fwd_kernel_lse(
    q_ref, k_ref, v_ref,
    o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, steps: int, **tile,
):
    """Forward that additionally writes LSE = m + log(l) per q row — the
    residual the tiled backward needs to re-derive tile softmax without
    another online-max pass."""
    from jax.experimental import pallas as pl

    _attn_fwd_kernel(
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
        steps=steps, **tile,
    )
    step = pl.program_id(2)

    @pl.when(step == steps - 1)
    def _flush_lse():
        # Per-q-row scalars must live on sublanes; the block's minor dim
        # must be 128-divisible OR equal the array dim, so an 8-wide
        # replicated minor axis is the cheapest legal layout (16x less HBM
        # than jax's own 128-wide l/m residuals).
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[0] = lse[:, :8]


def _last_k_with_body(qi, block_q, block_k):
    """Index of the last k tile that has a body in q row `qi` (causal)."""
    return jax.lax.div((qi + 1) * block_q - 1, block_k)


def _first_k_with_body(qi, block_q, block_k, window):
    """Index of the first k tile that has a body in q row `qi` under a
    window: the one that holds the first key the row's first query sees."""
    return jax.lax.div(jnp.maximum(qi * block_q - window + 1, 0), block_k)


def _first_q_with_body(ki, block_q, block_k, num_q):
    """Index of the first q tile that has a body in k column `ki` (causal),
    kept inside the array for a column that has none."""
    return jnp.minimum(jax.lax.div(ki * block_k, block_q), num_q - 1)


def _last_q_with_body(ki, block_q, block_k, num_q, window):
    """Index of the last q tile that has a body in k column `ki` under a
    window: the one that holds the last query that sees the column's last
    key, kept inside the array."""
    return jnp.minimum(
        jax.lax.div((ki + 1) * block_k + window - 2, block_q), num_q - 1)


def _compiler_params(tiles: FlashTiles, inner=("parallel", "arbitrary")):
    """`inner`: the two grid dimensions inside a (batch, head) row. The
    last carries a kernel's sums; the one before it too where dq is summed
    over the k tiles it walks."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", *inner),
        vmem_limit_bytes=tiles.vmem_limit_bytes,
    )


def _grid(kernel, q, k, v, causal, block_q, block_k, window=None,
          mask=None, stair=None, v_heads: int = 1):
    """(tiles, q tiles, k tiles, steps of the innermost grid dimension) of
    `kernel` for q of [BH, T, D], k of [BHk, S, D] and v of [BHk, S, Dv], or
    of [B, S, v_heads Dv] where it lies; `block_q`, `block_k` force a tile
    or are None."""
    T, S = q.shape[1], k.shape[1]
    tiles = flash_tiles(kernel, T, S, q.shape[2], q.dtype, causal=causal,
                        block_q=block_q, block_k=block_k,
                        v_dim=v.shape[2] // v_heads,
                        window=window, group=q.shape[0] // k.shape[0],
                        sparse=mask is not None, stair=stair)
    return (tiles, _cdiv(T, tiles.block_q), _cdiv(S, tiles.block_k),
            _inner_steps(kernel, T, S, tiles.block_q, tiles.block_k, window))


def _kernel_name(kernel: str, window, mask=None, stair=None) -> str:
    """The `pallas_call`'s name: the kernel's, with a window
    `<kernel>_window`, under a data mask `<kernel>_sparse` and under a
    staircase `<kernel>_stair`, which a trace and the compiled HLO tell
    apart."""
    if mask is not None:
        return kernel + "_sparse"
    if stair is not None:
        return kernel + "_stair"
    return kernel if window is None else kernel + "_window"


def _head_block(row, tile, heads: int = 1):
    """The block of (batch, head) row `row`'s tile `tile` in an array
    [B, rows, heads * D] walked in blocks `D` wide: the batch row, the tile
    and, as the column block, the head. `heads` 1 is the layout with the
    heads folded into the batch, [B H, rows, D], and the map it always had."""
    if heads == 1:
        return (row, tile, 0)
    return (jax.lax.div(row, heads), tile, jax.lax.rem(row, heads))


def _q_block(bh, qi, ki):
    return (bh, qi, 0)


def _k_block_under_q(causal, block_q, block_k, window=None, num_k=None,
                     group: int = 1, stair=None, heads: int = 1):
    """Index map of K and V where k is walked innermost (forward, dq): under
    causal a step past the row's last tile with a body names that tile, the
    block already in VMEM, and fetches nothing. Under a window the walk
    starts at the row's first tile with a body (the band grid), and its
    last may be the array's (`num_k`) before it is the diagonal's. With
    `group` query heads to a key-value head, heads folded batch-major, the
    key-value row of query row `bh` is `bh // group`. Under a staircase a
    step past the row's last tile with a body names that tile (the first,
    for a row that has none). `heads`: v's map where v lies `[B, S, heads
    Dv]` (`_head_block`)."""
    def k_block(bh, qi, ki):
        if stair is not None:
            seen = _stair_keys((qi + 1) * block_q - 1, stair)
            ki = jnp.minimum(ki, jnp.maximum(
                jax.lax.div(seen + block_k - 1, block_k) - 1, 0))
        if window is not None:
            ki = jnp.minimum(
                ki + _first_k_with_body(qi, block_q, block_k, window),
                num_k - 1)
        if causal:
            ki = jnp.minimum(ki, _last_k_with_body(qi, block_q, block_k))
        return _head_block(bh if group == 1 else bh // group, ki, heads)

    return k_block


def _q_block_under_k(causal, block_q, block_k, num_q, window=None,
                     stair=None):
    """Index map of q, do, lse and delta where q is walked innermost (dk/dv,
    all three gradients): under causal a step before the column's first tile
    with a body names that tile, which is then there when the walk reaches
    it. Under a window the walk starts at that tile (the band grid), and a
    step past the column's last tile with a body names that one. Under a
    staircase the column's first tile with a body holds the first query of
    the span after its first key's (the last tile, for a column that has
    none)."""
    def q_block(bh, ki, qi):
        if stair is not None:
            span, per = stair
            first = (jax.lax.div(ki * block_k, per) + 1) * span
            qi = jnp.maximum(qi, jnp.minimum(
                jax.lax.div(first, block_q), num_q - 1))
        if window is not None:
            qi = jnp.minimum(
                qi + _first_q_with_body(ki, block_q, block_k, num_q),
                _last_q_with_body(ki, block_q, block_k, num_q, window))
        elif causal:
            qi = jnp.maximum(
                qi, _first_q_with_body(ki, block_q, block_k, num_q))
        return (bh, qi, 0)

    return q_block


def _mask_key_tile(mask, block_k):
    """A data mask is `[B, key tiles, T, block_k / 8]` int8, a bit a pair
    (`_pack_bits`): a key tile's bits lie together, so that a kernel's tile
    of it is one block whatever the walk. Its tile is every kernel's key
    tile."""
    if mask is None:
        return block_k
    tile = 8 * mask.shape[3]
    if block_k not in (None, tile):
        raise ValueError(
            f"a data mask in key tiles of {tile} cannot be walked in tiles "
            f"of {block_k}")
    return tile


def _mask_spec_under_q(mask, BH, block_q, k_block):
    """The data mask's block where k is walked innermost: the tile of
    `k_block`'s key tile and the q row, of the batch row of `bh`."""
    from jax.experimental import pallas as pl

    heads = BH // mask.shape[0]

    def mask_block(bh, qi, ki):
        return (bh // heads, k_block(bh, qi, ki)[1], qi, 0)

    return pl.BlockSpec((1, 1, block_q, mask.shape[3]), mask_block)


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k, interpret,
               with_lse: bool = False, window=None, mask=None, stair=None,
               v_heads: int = 1):
    """o [BH, T, Dv], or (o, lse [BH, T, 8]), of q [BH, T, D], k
    [BHk, S, D] and v [BHk, S, Dv], or v where it lies, [B, S, v_heads Dv]:
    a (batch, head) row of the grid then reads its head's columns of v's
    batch row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S, Dv = k.shape[1], v.shape[2] // v_heads
    block_k = _mask_key_tile(mask, block_k)
    tiles, num_q, num_k, steps = _grid(
        "flash_fwd", q, k, v, causal, block_q, block_k, window, mask, stair,
        v_heads)
    block_q, block_k = tiles.block_q, tiles.block_k

    kernel = functools.partial(
        _attn_fwd_kernel_lse if with_lse else _attn_fwd_kernel,
        stair=stair,
        block_q=block_q,
        block_k=block_k,
        num_q=num_q,
        num_k=num_k,
        steps=steps,
        scale=scale,
        causal=causal,
        seq_k=S,
        window=window,
    )

    k_block, v_block = (
        _k_block_under_q(causal, block_q, block_k, window, num_k,
                         group=BH // k.shape[0], stair=stair, heads=n)
        for n in (1, v_heads))
    out_shape = jax.ShapeDtypeStruct((BH, T, Dv), q.dtype)
    out_specs = pl.BlockSpec((1, block_q, Dv), _q_block)
    if with_lse:
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((BH, T, 8), jnp.float32),
        ]
        out_specs = [out_specs, pl.BlockSpec((1, block_q, 8), _q_block)]
    in_specs = [
        pl.BlockSpec((1, block_q, D), _q_block),
        pl.BlockSpec((1, block_k, D), k_block),
        pl.BlockSpec((1, block_k, Dv), v_block),
    ]
    operands = (q, k, v)
    if mask is not None:
        kernel = _with_mask(kernel, 3)
        in_specs.append(_mask_spec_under_q(mask, BH, block_q, k_block))
        operands += (mask,)
    return _pallas_call(
        kernel,
        grid=(BH, num_q, steps),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(tiles),
        interpret=interpret,
        name=_kernel_name("flash_fwd", window, mask, stair),
    )(*operands)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, keep_ctx,
           window, v_heads):
    """o of q, k and v as `_flash_fwd` takes them: the residuals of its
    backward are q, k, v as they were handed over, o and lse `[BH, T]`."""
    return _flash_fwd(
        q, k, v, causal=causal, scale=scale, window=window, v_heads=v_heads,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   keep_ctx, window, v_heads):
    o, lse = _flash_fwd(
        q, k, v, causal=causal, scale=scale, window=window, v_heads=v_heads,
        block_q=block_q, block_k=block_k, interpret=interpret, with_lse=True,
    )
    if keep_ctx:
        # Named where the backward takes them, so that a rematerialised
        # block that keeps `attn_ctx` does not run the kernel again for its
        # residuals (a name on the layer's output would keep a copy and
        # still run it); q, k and v are made again from the block's input.
        # lse is kept as its one column: [BH, T, 8] float32 fills 8 of a
        # tile's 128 lanes and lies in HBM at 16 times its size.
        o = checkpoint_name(o, "attn_ctx")
        lse = checkpoint_name(lse[..., 0], "attn_ctx")
    return o, (q, k, v, o, lse)


def _exit_said(kernel: str, tiles: FlashTiles, D: int, Dv: int,
               group: int = 1) -> str:
    """What a log line says of how `flash_bwd_dkv_dq`'s row-long gradients
    leave VMEM (`FlashTiles.exit`), and by tile which of them (dq; under a
    group dk and dv) leave padded to whole lanes, at which width, or as
    the columns of one array (`_share_lanes`); nothing of another kernel."""
    if kernel != "flash_bwd_dkv_dq":
        return ""
    if tiles.exit != "tile":
        return ", out by the row's blocks"
    said = ", out a tile at a time by DMA, the row's f32 sums alone held"
    shared = _share_lanes(D, Dv, group)
    alone = [("dq", D)] + [("dk", D), ("dv", Dv)] * (group > 1 and not shared)
    padded = ", ".join(
        "%s %d as %d" % (name, width, _whole_lanes(width))
        for name, width in alone if width != _whole_lanes(width))
    if padded:
        said += ", padded to whole lanes: " + padded
    if shared:
        said += ", dk %d and dv %d wide as the columns of one array of %d" % (
            D, Dv, _LANES)
    return said


@functools.lru_cache(maxsize=None)
def _log_bwd_kernels(kernels, T, S, D, Dv, dtype, causal, block_q, block_k,
                     window=None, group=1, stair=None, v_heads=1):
    """One line for each backward a process traces, as `saved_activations`
    has one for what it keeps: where v lies (`v_heads`), which kernels, at
    which tile and VMEM, and how many query heads read a key-value head
    through the index maps. Under
    a window also the forward's tile, and of every kernel the steps of its
    grid, the band's, and the share of them that have a body: the rest are
    the trailing steps of rows (columns) whose band crosses fewer tiles."""
    def tiles(kernel):
        return flash_tiles(kernel, T, S, D, dtype, causal=causal,
                           block_q=block_q, block_k=block_k, v_dim=Dv,
                           window=window, group=group, stair=stair)

    heads = ("no group" if group == 1 else
             f"{group} query heads a key-value head by index map")
    if stair is not None:
        heads += ", under a staircase of %d keys a span of %d queries" % (
            stair[1], stair[0])
    lie = ("folded by transpose: D %d, Dv %d" % (D, Dv) if v_heads == 1 else
           "v and dv where the model holds them, [B, S, H Dv], H %d; q, k "
           "and o folded" % v_heads)
    for kernel in kernels:
        t = tiles(kernel)
        logger.info(
            "flash backward at T %d, S %d, D %d, Dv %d, %s, %s: %s, tile %d "
            "x %d, VMEM %d bytes of a limit of %d, %s%s", T, S, D, Dv, dtype,
            lie, kernel, t.block_q, t.block_k, t.vmem_bytes,
            t.vmem_limit_bytes, heads, _exit_said(kernel, t, D, Dv, group))
    if window is None:
        return
    for kernel in ("flash_fwd", *kernels):
        t = tiles(kernel)
        logger.info(
            "flash window %d at T %d, D %d, Dv %d, %s: %s, tile %d x %d, "
            "%d grid steps a row, %.1f %% of them with a body", window, T, D,
            Dv, dtype, _kernel_name(kernel, window), t.block_q, t.block_k,
            t.grid_steps, 100 * t.active_share)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, keep_ctx,
                   window, v_heads, res, do):
    """Tiled FlashAttention-2 backward, re-deriving each softmax tile from
    (q, k, lse) — nothing O(T·S) ever touches HBM (the previous recompute
    path materialized full f32 score matrices through XLA, which both OOMed
    large batches and made the step bandwidth-bound). One kernel makes the
    tile once for dq, dk and dv where `flash_bwd_kernels` says a row's
    sums fit VMEM; two kernels (dq; dk/dv) make it once each where not."""
    q, k, v, o, lse = res
    BH, T, _ = q.shape
    if keep_ctx:
        lse = jnp.broadcast_to(lse[..., None], (BH, T, 8))
    return _flash_backward(q, k, v, o, lse, do, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, window=window,
                           v_heads=v_heads)


def _flash_backward(q, k, v, o, lse, do, *, causal, scale, block_q, block_k,
                    interpret, window, dlse=None, stair=None, v_heads=1):
    """(dq, dk, dv) from the residuals and `do`, dv as v lies (`v_heads`),
    lse as `[BH, T, 8]`. With
    `dlse` [BH, T] float32, lse's own cotangent (`_flash_lse`): a pair's
    `ds = p (dp - delta)` gains `p dlse`, so `delta` becomes `delta -
    dlse`, and the kernels are the ones they were."""
    BH, T, _ = q.shape
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    if dlse is not None:
        delta = delta - dlse
    # Same sublane-aligned [BH, T, 8] layout as lse.
    delta = jnp.broadcast_to(delta[..., None], (BH, T, 8))
    shape = (T, k.shape[1], q.shape[2])
    Dv, group = v.shape[2] // v_heads, BH // k.shape[0]
    kernels = flash_bwd_kernels(*shape, q.dtype, causal=causal,
                                block_q=block_q, block_k=block_k,
                                v_dim=Dv, window=window, group=group,
                                stair=stair)
    _log_bwd_kernels(kernels, *shape, Dv, jnp.dtype(q.dtype).name,
                     causal, block_q, block_k, window=window, group=group,
                     stair=stair, v_heads=v_heads)
    tile = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k,
                interpret=interpret, window=window, stair=stair,
                v_heads=v_heads)
    if kernels == ("flash_bwd_dkv_dq",):
        return _flash_bwd_dkv(q, k, v, do, lse, delta, with_dq=True, **tile)
    dq = _flash_bwd_dq(q, k, v, do, lse, delta, **tile)
    dk, dv = _flash_bwd_dkv(q, k, v, do, lse, delta, **tile)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11)
)
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret, keep_ctx,
               window, stair, v_heads):
    """(o [BH, T, Dv], lse [BH, T] float32): `_flash` that also hands out
    every row's log of its softmax's sum, which carries a cotangent of its
    own, so that two partial softmaxes can be joined outside
    (`ops/eva.py`). A row that sees no key (the first span of a staircase)
    has o 0 and lse near `_BIG_NEG`."""
    return _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret, keep_ctx, window, stair, v_heads)[0]


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   keep_ctx, window, stair, v_heads):
    o, lse = _flash_fwd(
        q, k, v, causal=causal, scale=scale, window=window, stair=stair,
        block_q=block_q, block_k=block_k, interpret=interpret, with_lse=True,
        v_heads=v_heads,
    )
    lse = lse[..., 0]  # one column, as `_flash_vjp_fwd` keeps it
    if keep_ctx:
        o = checkpoint_name(o, "attn_ctx")
        lse = checkpoint_name(lse, "attn_ctx")
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, keep_ctx,
                   window, stair, v_heads, res, cotangents):
    q, k, v, o, lse = res
    do, dlse = cotangents
    BH, T, _ = q.shape
    return _flash_backward(
        q, k, v, o, jnp.broadcast_to(lse[..., None], (BH, T, 8)), do,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, dlse=dlse.astype(jnp.float32),
        stair=stair, v_heads=v_heads)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *,
              masked, block_q, block_k, scale, causal, seq_q, seq_k,
              window=None, mask_ref=None, stair=None):
    """Shared per-tile computation of both backward kernels: load, sanitize
    padded rows (masked tiles only: no other tile has any), re-derive the
    softmax tile. Returns (q, k, do, p, ds), p and ds in the input's dtype.

    Sanitizing at load matters: pallas pads partial blocks with arbitrary
    (possibly NaN) data, and a NaN anywhere in a dot input poisons the whole
    contraction even where the weight is 0."""
    q = q_ref[0]  # [bq, D]
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]  # [bk, Dv]
    do = do_ref[0]  # [bq, Dv]
    lse = lse_ref[0][:, :1]  # [bq, 1] (lane-replicated input)
    delta = delta_ref[0][:, :1]  # [bq, 1]
    if masked and seq_q % block_q:
        qvalid = _rows_valid(qi, block_q, seq_q)
        q = jnp.where(qvalid, q, 0.0)
        do = jnp.where(qvalid, do, 0.0)
        lse = jnp.where(qvalid, lse, 0.0)
        delta = jnp.where(qvalid, delta, 0.0)
    if masked and seq_k % block_k:
        kvalid = _rows_valid(ki, block_k, seq_k)
        k = jnp.where(kvalid, k, 0.0)
        v = jnp.where(kvalid, v, 0.0)
    s = _dot(q, k, _NT) * scale  # [bq, bk]
    p = jnp.exp(s - lse)  # [bq, bk]
    dp = _dot(do, v, _NT)  # [bq, bk]
    ds = p * (dp - delta) * scale  # [bq, bk]
    mask = _pairs_mask(
        masked, mask_ref, qi, ki, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k, window=window, stair=stair)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
        # Explicit where: p=0 times a NaN dp entry would still poison the dot.
        ds = jnp.where(mask, ds, 0.0)
    return q, k, do, p.astype(q.dtype), ds.astype(q.dtype)


def _attn_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    acc_ref,
    *, block_q: int, block_k: int, num_q: int, num_k: int, steps: int,
    scale: float, causal: bool, seq_q: int, seq_k: int,
    window: Optional[int] = None, mask_ref=None, stair=None,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = step = pl.program_id(2)  # of `steps`: the row's walk over k
    if window is not None:
        ki = _first_k_with_body(qi, block_q, block_k, window) + step
    shape = dict(block_q=block_q, block_k=block_k, causal=causal,
                 seq_q=seq_q, seq_k=seq_k, window=window, stair=stair)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body(masked):
        _, k, _, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            masked=masked, scale=scale, mask_ref=mask_ref, **shape,
        )
        acc_ref[...] += _dot(ds, k, _NN)  # [bq, D]

    _run_tile(_body, *_tile_kind(qi, ki, num_q=num_q, num_k=num_k, **shape))

    @pl.when(step == steps - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    *outputs_and_sums,
    block_q: int, block_k: int, num_q: int, num_k: int, steps: int,
    scale: float, causal: bool, seq_q: int, seq_k: int, with_dq: bool = False,
    window: Optional[int] = None, group: int = 1, by_tile: bool = False,
    shared: bool = False, mask_ref=None, stair=None,
):
    """dk and dv of k tile `ki`, summed over the q tiles the grid walks
    innermost. `with_dq` (`flash_bwd_dkv_dq`): dq too, from the same p and
    ds. Its f32 sum `dq_acc_ref` holds every q tile of the (batch, head)
    row, because the k tiles that add to one q tile's rows are a whole
    column of the grid apart; tile `qi`'s rows are zeroed in the first
    column (under a window: the first whose band reaches them), summed over
    `ki` in ascending order as `_attn_bwd_dq_kernel` sums them, and rounded
    once in the last: into the row's dq block, or out (`by_tile`, below).

    With `group` query heads to a key-value head dk and dv are summed over
    the group's heads too, in f32, and rounded once. Without dq the column's
    walk runs over the group's heads, `group * steps` long, and the sums
    are a key tile's as they were. With dq the group's head is a grid
    dimension outside the key tiles (a head's dq row is done when its key
    walk ends), so the sums and the output blocks hold the key-value head's
    whole row, as dq's do: tile `ki`'s rows are zeroed at its first step
    under the group's first head and rounded out at its last under the
    group's last.

    `by_tile` (with dq): a row-long gradient has no block in VMEM. Its
    output is the whole array where it lies (`pl.ANY`), and the flush that
    would have rounded a tile of the sum into the row's block rounds it
    into a staged tile and copies that to its rows (`_round_out`): dq
    always, dk and dv where a group makes them row-long.

    `shared` (by tile, under a group, `_share_lanes`): dk and dv are together
    no wider than a tile of lanes, where each alone would fill one. Their
    sums are the columns of one, `[S, 128]`: dk the first `D`, dv the `Dv`
    after them, a key tile's products made there by the MXU, which takes q
    and do as `[bq, 128]` operands zero outside those columns (`_at_lanes`);
    one staged tile, one output `[B Hk, S, 128]`, which the caller splits."""
    from jax.experimental import pallas as pl

    if shared:  # dq's staged tile, dk's and dv's one, semaphores
        dkv_ref, dq_ref, dkv_acc_ref, dq_acc_ref, *staged = outputs_and_sums
        kv_sums = (dkv_acc_ref,)
    elif with_dq:  # `by_tile`: and dq's staged tile, dk's and dv's, semaphores
        (dk_ref, dv_ref, dq_ref,
         dk_acc_ref, dv_acc_ref, dq_acc_ref, *staged) = outputs_and_sums
        kv_sums = (dk_acc_ref, dv_acc_ref)
    else:
        dk_ref, dv_ref, *kv_sums = outputs_and_sums
        dk_acc_ref, dv_acc_ref = kv_sums
    sems = staged.pop() if by_tile else None
    acc, out = ..., 0  # tile `ki` in dk's and dv's sums, and in their blocks
    if group == 1 or not with_dq:
        ki = pl.program_id(1)
        # of `group * steps`: the column's walk over q, a head after another
        qi = step = walk = pl.program_id(2)
        if group > 1:
            qi = step = jax.lax.rem(walk, steps)
    else:  # grid (key-value row, head of its group, ki, step)
        head, ki = pl.program_id(1), pl.program_id(2)
        qi = step = pl.program_id(3)
        walk = head * steps + step  # of tile `ki`'s sums
        acc = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        out = (0, acc)
    if window is not None:
        qi = _first_q_with_body(ki, block_q, block_k, num_q) + step
    if by_tile:  # the rows of the arrays that the flushes copy to
        bh = bkv = pl.program_id(0)
        if group > 1:
            bh = bkv * group + head
        # A staged tile is whole lanes wide, its sum the gradient's width:
        # the columns past it leave as zeros, written at the row's first
        # step and never again (`_round_out` writes the sum's columns).
        sums = (dq_acc_ref, *kv_sums)
        padded = [stage_ref for stage_ref, acc_ref in zip(staged, sums)
                  if stage_ref.shape[1] != acc_ref.shape[1]]
        if padded:
            at_row_start = functools.reduce(jnp.logical_and, [
                pl.program_id(i) == 0 for i in range(1, 3 + (group > 1))])

            @pl.when(at_row_start)
            def _init_padding():
                for stage_ref in padded:
                    stage_ref[...] = jnp.zeros_like(stage_ref)
    shape = dict(block_q=block_q, block_k=block_k, causal=causal,
                 seq_q=seq_q, seq_k=seq_k, window=window, stair=stair)

    @pl.when(walk == 0)
    def _init():
        for acc_ref in kv_sums:
            acc_ref[acc] = jnp.zeros(
                (block_k, acc_ref.shape[1]), acc_ref.dtype)

    if with_dq:
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        first_col = ki == 0  # of those that add to this q tile's dq
        if window is not None:  # a short column's walk runs past `num_q`
            first_col = jnp.logical_and(qi < num_q, ki == _first_k_with_body(
                qi, block_q, block_k, window))

        @pl.when(first_col)
        def _init_dq():
            dq_acc_ref[rows, :] = jnp.zeros(
                (block_q, dq_acc_ref.shape[1]), dq_acc_ref.dtype)

    def _body(masked):
        q, k, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            masked=masked, scale=scale, mask_ref=mask_ref, **shape,
        )
        if shared:  # [bk, lanes]: dk in the first D columns, dv after them
            dkv_acc_ref[acc] += (
                _dot(ds, _at_lanes(q, 0), _TN)
                + _dot(p, _at_lanes(do, q.shape[1]), _TN))
        else:
            dv_acc_ref[acc] += _dot(p, do, _TN)  # [bk, Dv]
            dk_acc_ref[acc] += _dot(ds, q, _TN)  # [bk, D]
        if with_dq:
            dq_acc_ref[rows, :] += _dot(ds, k, _NN)  # [bq, D]

    # Only q tiles at/below the diagonal, and inside the band, see this k
    # tile.
    _run_tile(_body, *_tile_kind(qi, ki, num_q=num_q, num_k=num_k, **shape))

    @pl.when(walk == group * steps - 1)
    def _flush():
        if shared:
            _round_out((dkv_acc_ref[acc], staged[1], dkv_ref.at[bkv, acc],
                        sems.at[1]))
            return
        if by_tile and group > 1:
            _round_out(
                (dk_acc_ref[acc], staged[1], dk_ref.at[bkv, acc], sems.at[1]),
                (dv_acc_ref[acc], staged[2], dv_ref.at[bkv, acc], sems.at[2]))
            return
        dk_ref[out] = dk_acc_ref[acc].astype(dk_ref.dtype)
        dv_ref[out] = dv_acc_ref[acc].astype(dv_ref.dtype)

    if with_dq:
        last_col = ki == num_k - 1
        if window is not None:
            last_col = jnp.logical_and(qi < num_q, ki == jnp.minimum(
                _last_k_with_body(qi, block_q, block_k), num_k - 1))

        @pl.when(last_col)
        def _flush_dq():
            if by_tile:
                _round_out((dq_acc_ref[rows, :], staged[0],
                            dq_ref.at[bh, rows], sems.at[0]))
                return
            dq_ref[0, rows, :] = dq_acc_ref[rows, :].astype(dq_ref.dtype)


def _at_lanes(x, first: int):
    """`x` [rows, width] as [rows, 128]: its columns at lanes `first`
    onward, zero elsewhere. As an operand of a matmul it puts the product's
    columns there at no more passes of the MXU, whose array is 128 wide."""
    return jnp.pad(x, ((0, 0), (first, _LANES - first - x.shape[1])))


def _round_out(*tiles):
    """Each (a tile of an f32 sum, its staging tile in the output's dtype,
    the output's rows where they lie, a DMA semaphore): rounded once into
    the staging tile and copied out by the kernel's own DMA. Every copy is
    started, then every one waited for: the staging tiles are free again.
    The staging tile and the rows are whole lanes wide; a sum narrower than
    that is rounded into their first columns, the rest as they were."""
    from jax.experimental.pallas import tpu as pltpu

    copies = []
    for total, stage_ref, rows_ref, sem in tiles:
        if total.shape[1] == stage_ref.shape[1]:
            stage_ref[...] = total.astype(stage_ref.dtype)
        else:
            stage_ref[:, :total.shape[1]] = total.astype(stage_ref.dtype)
        copies.append(pltpu.make_async_copy(stage_ref, rows_ref, sem))
        copies[-1].start()
    for copy in copies:
        copy.wait()


def _flash_bwd_dq(q, k, v, do, lse, delta, *, causal, scale,
                  block_q, block_k, interpret, window=None, mask=None,
                  stair=None, v_heads: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S, Dv = k.shape[1], v.shape[2] // v_heads
    block_k = _mask_key_tile(mask, block_k)
    tiles, num_q, num_k, steps = _grid(
        "flash_bwd_dq", q, k, v, causal, block_q, block_k, window, mask,
        stair, v_heads)
    block_q, block_k = tiles.block_q, tiles.block_k
    kernel = functools.partial(
        _attn_bwd_dq_kernel,
        stair=stair,
        block_q=block_q, block_k=block_k, num_q=num_q, num_k=num_k,
        steps=steps, scale=scale, causal=causal, seq_q=T, seq_k=S,
        window=window,
    )

    k_block, v_block = (
        _k_block_under_q(causal, block_q, block_k, window, num_k,
                         group=BH // k.shape[0], stair=stair, heads=n)
        for n in (1, v_heads))
    in_specs = [
        pl.BlockSpec((1, block_q, D), _q_block),
        pl.BlockSpec((1, block_k, D), k_block),
        pl.BlockSpec((1, block_k, Dv), v_block),
        pl.BlockSpec((1, block_q, Dv), _q_block),
        pl.BlockSpec((1, block_q, 8), _q_block),
        pl.BlockSpec((1, block_q, 8), _q_block),
    ]
    operands = (q, k, v, do, lse, delta)
    if mask is not None:
        kernel = _with_mask(kernel, 6)
        in_specs.append(_mask_spec_under_q(mask, BH, block_q, k_block))
        operands += (mask,)
    return _pallas_call(
        kernel,
        grid=(BH, num_q, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), _q_block),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(tiles),
        interpret=interpret,
        name=_kernel_name("flash_bwd_dq", window, mask, stair),
    )(*operands)


def _flash_bwd_dkv(q, k, v, do, lse, delta, *, causal, scale,
                   block_q, block_k, interpret, with_dq: bool = False,
                   window=None, mask=None, by_tile: bool = False,
                   stair=None, v_heads: int = 1):
    """(dk, dv), or with `with_dq` the kernel `flash_bwd_dkv_dq` and
    (dq, dk, dv): one more output, whose block is a (batch, head) row's
    whole dq, fetched nowhere and written back when the row is done, and
    one more f32 sum of that size. Where `flash_tiles` found no room for
    such blocks (`FlashTiles.exit` "tile"; `by_tile` forces it, for tests)
    the row-long outputs have none: they lie in HBM, and the kernel copies
    each completed tile out of a staged one.

    k and v of [BHk, S, ...] with `group = BH // BHk` query rows to each:
    dk and dv leave at [BHk, S, ...], summed over the group in VMEM. The
    index maps below are written for `(bh, ki, qi)`; `of_grid` reads those
    from the grid's coordinates: `(BHk, key tiles, group * steps)` without
    dq, `(BHk, group, key tiles, steps)` with it, where dk's and dv's
    blocks and sums are the key-value row's whole `S` rows too. A group of
    one is the grid `(BH, key tiles, steps)` and the maps as they are, and
    there v may lie `[B, S, v_heads Dv]`: dv leaves so too, its key tile at
    its head's columns (`_head_block`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S, Dv = k.shape[1], v.shape[2] // v_heads
    group = BH // k.shape[0]
    if group > 1 and v_heads > 1:
        raise ValueError("v lies where the model holds it only with no group")
    name = "flash_bwd_dkv_dq" if with_dq else "flash_bwd_dkv"
    block_k = _mask_key_tile(mask, block_k)
    tiles, num_q, num_k, steps = _grid(
        name, q, k, v, causal, block_q, block_k, window, mask, stair, v_heads)
    block_q, block_k = tiles.block_q, tiles.block_k
    by_tile = with_dq and (by_tile or tiles.exit == "tile")
    shared = by_tile and _share_lanes(D, Dv, group)
    kernel = functools.partial(
        _attn_bwd_dkv_kernel,
        stair=stair,
        block_q=block_q, block_k=block_k, num_q=num_q, num_k=num_k,
        steps=steps, scale=scale, causal=causal, seq_q=T, seq_k=S,
        with_dq=with_dq, window=window, group=group, by_tile=by_tile,
        shared=shared,
    )

    q_block = _q_block_under_k(causal, block_q, block_k, num_q, window,
                               stair)

    def k_block(bh, ki, qi):
        return (bh, ki, 0)

    def row_block(bh, ki, qi):
        return (bh, 0, 0)

    grid, of_grid = (BH, num_k, steps), lambda index_map: index_map
    inner = ("parallel", "arbitrary")
    dk_block, dk_rows, k_rows = k_block, block_k, S  # a block's, the array's
    if group > 1 and with_dq:
        grid = (BH // group, group, num_k, steps)
        dk_rows = k_rows = num_k * block_k  # S in whole key tiles

        def of_grid(index_map):
            return lambda bkv, head, ki, qi: index_map(
                bkv * group + head, ki, qi)

        def k_block(bkv, head, ki, qi):
            return (bkv, ki, 0)

        def dk_block(bkv, head, ki, qi):
            return (bkv, 0, 0)
    elif group > 1:
        grid = (BH // group, num_k, group * steps)

        def of_grid(index_map):
            return lambda bkv, ki, walk: index_map(
                bkv * group + walk // steps, ki, walk % steps)

    v_block, dv_block = k_block, dk_block
    if v_heads > 1:  # no group: the grid is (bh, ki, qi), dv's block as v's
        def v_block(bh, ki, qi):
            return _head_block(bh, ki, v_heads)

        dv_block = v_block

    in_specs = [
        pl.BlockSpec((1, block_q, D), of_grid(q_block)),
        pl.BlockSpec((1, block_k, D), k_block),
        pl.BlockSpec((1, block_k, Dv), v_block),
        pl.BlockSpec((1, block_q, Dv), of_grid(q_block)),
        pl.BlockSpec((1, block_q, 8), of_grid(q_block)),
        pl.BlockSpec((1, block_q, 8), of_grid(q_block)),
    ]
    operands = (q, k, v, do, lse, delta)
    if mask is not None:  # the tile of the key tile and of q's own q tile
        heads = BH // mask.shape[0]

        def mask_block(bh, ki, qi):
            return (bh // heads, ki, q_block(bh, ki, qi)[1], 0)

        kernel = _with_mask(kernel, 6)
        in_specs.append(pl.BlockSpec((1, 1, block_q, mask.shape[3]),
                                     of_grid(mask_block)))
        operands += (mask,)
    out_specs = [
        pl.BlockSpec((1, dk_rows, D), dk_block),
        pl.BlockSpec((1, dk_rows, Dv), dv_block),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH // group, k_rows, D), k.dtype),
        jax.ShapeDtypeStruct((v.shape[0], k_rows, v.shape[2]), v.dtype),
    ]
    scratch_shapes = [
        pltpu.VMEM((dk_rows, D), jnp.float32),
        pltpu.VMEM((dk_rows, Dv), jnp.float32),
    ]
    if shared:  # dk and dv the columns of one output and one sum
        out_specs, scratch_shapes = out_specs[:1], [
            pltpu.VMEM((dk_rows, _LANES), jnp.float32)]
        out_shape = [jax.ShapeDtypeStruct(
            (BH // group, k_rows, _LANES), k.dtype)]
    if with_dq:
        rows = num_q * block_q  # T in whole q tiles: every step's are there
        out_specs.append(pl.BlockSpec((1, rows, D), of_grid(row_block)))
        out_shape.append(jax.ShapeDtypeStruct((BH, rows, D), q.dtype))
        scratch_shapes.append(pltpu.VMEM((rows, D), jnp.float32))
        # dq is summed over k tiles too, dk and dv over a group's heads
        inner = ("arbitrary",) * (len(grid) - 1)
    if by_tile:
        # The row-long outputs lie in HBM, whole lanes wide (a DMA takes no
        # less), with a staged tile of each: at a width of whole lanes the
        # array it was, at another its columns first and zeros after.
        def out_by_tile(i, tile_rows):
            lanes = _whole_lanes(out_shape[i].shape[2])
            out_specs[i] = pl.BlockSpec(memory_space=pl.ANY)
            out_shape[i] = jax.ShapeDtypeStruct(
                (*out_shape[i].shape[:2], lanes), out_shape[i].dtype)
            scratch_shapes.append(
                pltpu.VMEM((tile_rows, lanes), out_shape[i].dtype))

        out_by_tile(-1, block_q)  # dq, then under a group dk and dv
        if group > 1:
            for i in range(len(out_specs) - 1):
                out_by_tile(i, block_k)
        scratch_shapes.append(pltpu.SemaphoreType.DMA((3,)))
    out = _pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=_compiler_params(tiles, inner),
        interpret=interpret,
        name=_kernel_name(name, window, mask, stair),
    )(*operands)
    if not with_dq:
        return out
    if shared:
        dkv, dq = out
        dk, dv = dkv[..., :D], dkv[..., D:]
    else:
        dk, dv, dq = out
    # each as its operand is: the rows past the sequence's and, by tile,
    # the columns past the width go
    return tuple(
        grad if grad.shape == like.shape
        else grad[:, :like.shape[1], :like.shape[2]]
        for grad, like in zip((dq, dk, dv), (q, k, v)))


def _xla_attention_bhtd(q, k, v, *, causal, scale, window=None):
    """Plain attention on [BH, T, D]: what `mha(impl="xla")` runs, the
    path of every platform but the TPU and the reference the kernels are
    tested against. `window`: with `causal`, `query - key < window` too."""
    s = jnp.einsum(
        "btd,bsd->bts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        T, S = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        if window is not None:
            mask = jnp.logical_and(
                mask, jnp.arange(T)[:, None] - jnp.arange(S)[None, :] < window)
        s = jnp.where(mask[None], s, _BIG_NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32)).astype(q.dtype)


def _to_kernels(q, k, v):
    """(q, k, v, v_heads) as the kernels take q, k of [B, T, H, D] and v of
    [B, S, Hk, Dv].

    q and k go with their heads folded into the batch, batch-major, by a
    transpose, `[B H, T, D]` beside `[B Hk, S, D]` (query row `bh` reads
    key-value row `bh // (H / Hk)`), and o comes back so. q and k come from
    a rotary turn or a norm by head and o goes to a gate or a join by head:
    passes over `[B, T, H, D]`, which the compiler lays out with the heads
    on a tile's sublanes, and which write and read the folded layout from
    inside their fusions at little cost, where `[B, T, H D]`, tokens on the
    sublanes, is a copy of its own and more (PERF.md section 6, PR 65: all
    four in place cost `evabyte.tokens8k` 5.3 % of its rate). v comes from
    a matmul and dv goes to one, as `[B, S, Hk Dv]`, which v's index map
    addresses as it lies: with heads whole tiles of 128 lanes wide and as
    many of them as q has, v goes where it lies, a reshape that moves
    nothing, `v_heads = Hk`, and dv comes back there; elsewhere it is folded
    like the rest, `v_heads` 1. (Under a group v is a `group`-th of q's
    bytes and so is its transpose, while each of the group's heads reads
    v's rows at the stride: the chip read nothing for it in
    `mistral7b.tokens4k` and -0.05 % in `lagunaxs2.tokens8k`, so those keep
    the programs they had. q's and k's width is asked beside v's although
    only v's block needs whole tiles: at 192 beside a v of 128,
    `dsv2lite.tokens8k`, whose v is a slice of a projection
    `[B, T, H, 256]`, a pass over rank 4 again, the chip read -0.87 % with
    v in place.) The shape decides. Counted as the call is
    traced, a count a call site: `train.flash_calls_in_place` where v stays
    where it lies, `train.flash_calls_folded` where it does not
    (docs/observability.md)."""
    H, D, Hk, Dv = *q.shape[2:], k.shape[2], v.shape[3]
    if H % Hk:
        raise ValueError(
            f"{H} query heads do not divide among {Hk} key-value heads")

    def folded(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, *x.shape[1::2])

    if D % _LANES == 0 and Dv % _LANES == 0 and H == Hk:
        tracing.count("train.flash_calls_in_place")
        return folded(q), folded(k), v.reshape(*v.shape[:2], Hk * Dv), Hk
    tracing.count("train.flash_calls_folded")
    return folded(q), folded(k), folded(v), 1


def flash_attention(
    q, k, v,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    keep_ctx: bool = False,
    window: Optional[int] = None,
):
    """Flash attention on q, k of [B, T, H, D] and v of [B, T, H, Dv], which
    gives [B, T, H, Dv] (grouped-query: H_kv may divide H). `scale` is
    `1 / sqrt(D)` where the caller has none of its own. `window`, with
    `causal`: key `j` counts for query `i` where `j <= i` and
    `i - j < window` (a token sees itself and the `window - 1` before it);
    a window no shorter than the sequence is the causal kernel.

    `block_q` and `block_k` force every kernel's tile; `None` lets
    `flash_tiles` choose each kernel's from the shape. `keep_ctx` names the
    backward's residuals o and lse `attn_ctx` (`jax.ad_checkpoint`), for a
    caller under `jax.checkpoint` whose policy keeps that name.

    With heads whole tiles of 128 lanes wide, as many of v as of q, the
    kernels read v where the model holds it, `[B, S, H Dv]`, and write dv
    there (`_to_kernels`): no transpose of either, forward, backward or
    made again."""
    B, T, H, D = q.shape
    window = _band(window, causal, k.shape[1])
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qf, kf, vf, v_heads = _to_kernels(q, k, v)
    of = _flash(qf, kf, vf, causal, scale, block_q, block_k, interpret,
                keep_ctx, window, v_heads)
    return of.reshape(B, H, T, -1).transpose(0, 2, 1, 3)


def flash_attention_lse(
    q, k, v,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    keep_ctx: bool = False,
    window: Optional[int] = None,
    stair: Optional[Tuple[int, int]] = None,
):
    """`flash_attention` that also returns the rows' lse: (o [B, T, H, Dv],
    lse [B, T, H] float32, the log of each row's sum of `exp(scale q . k)`
    over the keys it sees), both differentiable: the backward takes lse's
    cotangent into `delta`. Two such partial softmaxes over disjoint keys
    join into the whole one, `o = (e^lse1 o1 + e^lse2 o2) / (e^lse1 +
    e^lse2)`.

    `stair=(span, per)`, without `causal` or `window`: query `i` sees the
    first `per * (i // span)` keys of `k`, the `per` keys of every span of
    queries before its own, a staircase; the `pallas_call`s are named
    `flash_fwd_stair`, `flash_bwd_dkv_dq_stair` (`flash_bwd_dq_stair`,
    `flash_bwd_dkv_stair`). A row that sees no key has o 0 and lse -inf."""
    B, T, H, D = q.shape
    window = _band(window, causal, k.shape[1])
    if stair is not None and (causal or window is not None):
        raise ValueError("a staircase is its own mask: no causal, no window")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qf, kf, vf, v_heads = _to_kernels(q, k, v)
    of, lse = _flash_lse(qf, kf, vf, causal, scale, block_q, block_k,
                         interpret, keep_ctx, window,
                         None if stair is None else tuple(map(int, stair)),
                         v_heads)
    lse = jnp.where(lse > 0.5 * _BIG_NEG, lse, -jnp.inf)
    return (of.reshape(B, H, T, -1).transpose(0, 2, 1, 3),
            lse.reshape(B, H, T).transpose(0, 2, 1))


def _band(window: Optional[int], causal: bool, seq_k: int) -> Optional[int]:
    """`window` as the kernels take it: None where it hides nothing (no
    key lies `seq_k` or more before a query)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(
            f"a window of {window} needs causal attention and at least the "
            "token itself")
    return None if window >= seq_k else int(window)


def resolve_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU, 'xla' elsewhere, by the platform JAX
    reports. A backend that fails to start raises here: answering 'xla'
    instead would train on the einsum path and call it the flash step."""
    if impl == "auto":
        return "pallas" if jax.devices()[0].platform == "tpu" else "xla"
    return impl


def mha(q, k, v, *, causal: bool = False, scale: Optional[float] = None,
        impl: str = "auto", keep_ctx: bool = False,
        window: Optional[int] = None):
    """Multi-head attention dispatch on q, k of [B, T, H, D] and v of
    [B, T, H, Dv]: [B, T, H, Dv].

    impl: 'auto' (pallas on TPU, XLA elsewhere) | 'pallas' | 'xla'.
    `keep_ctx` and `window` are `flash_attention`'s; plain attention names
    its output.
    """
    impl = resolve_impl(impl)
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               keep_ctx=keep_ctx, window=window)
    window = _band(window, causal, k.shape[1])
    B, T, H, D = q.shape
    Hk, Dv = k.shape[2], v.shape[3]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], Dv)
    of = _xla_attention_bhtd(qf, kf, vf, causal=causal, scale=scale,
                             window=window)
    out = of.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)
    # kept, it saves the output alone: this backward needs its scores again
    return checkpoint_name(out, "attn_ctx") if keep_ctx else out
