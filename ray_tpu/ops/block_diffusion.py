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
  `[2 L / block, block, block]` scores a head, in `jax.numpy`: two small
  matmuls a block and key-value head (a tile of `block` columns leaves the
  MXU idle, and still beats the same sums by hand), a softmax over the
  `block` scores in float32, and the weighted sum of the block's values.
- `bd_join`: `o = (e^lseS oS + e^lseO oO) / (e^lseS + e^lseO)` in float32 by
  the larger lse; the own block's is finite (a row sees itself), so `lseS =
  -inf` gives `oO`. The own block and the join run a chunk of `ROWS_AT_ONCE`
  rows at a time, each chunk made again in the backward: their float32
  values a head and row are never the whole stream's.

No `[2 L, 2 L]` tensor is made on the Pallas path. The XLA path
(`impl="xla"`, every platform but the TPU) builds the staircase's scores
whole under its mask. A bit mask as data (`ops/sparse_attention.py`'s form)
would walk the causal tiles of a `2 L x 2 L` square, a third more pairs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.eva import _join, _partial_xla
from ray_tpu.ops.flash_attention import flash_attention_lse, resolve_impl

_F32 = jnp.float32
ROWS_AT_ONCE = 2048  # rows whose own block and join are in flight at once


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
