"""Attention over the keys a learned indexer selects (DeepSeek sparse
attention, as DeepSeek-V3.2-Exp describes it): every query keeps the `topk`
earlier keys of largest *index score* and the softmax runs over those alone.

With `q_idx` [B, T, Hi, Di] (the indexer's heads), `k_idx` [B, T, Di] (one
key for all of them) and `w_idx` [B, T, Hi] (a weight a head and query):

    I[t, s]  = sum_j w_idx[t, j] relu(q_idx[t, j] . k_idx[s])       s <= t
    S_t      = the min(t + 1, topk) keys s <= t of largest I[t, s]
               (a tie at the edge goes to the lower index, as
               `jax.lax.top_k`)
    out_t    = softmax_{s in S_t}(q_t . k_s scale) v
    L_I      = mean_t KL(pbar_t || softmax_{s in S_t} I[t, s])

`pbar_t` is the attention's probabilities on `S_t` summed over the query
heads and divided by their number, *detached*. The selection passes no
gradient: `out`'s reaches q, k and v alone, `L_I`'s reaches `q_idx`, `k_idx`
and `w_idx` alone (`dL_I / dI = softmax_S(I) - pbar` on `S_t`). The index
score, the comparison with the row's threshold and both softmaxes are
float32 (the matmuls take their operands as given and accumulate in f32).

`sparse_attention` is the one entry point. `impl="xla"` is blocks of queries
in `jax.numpy` with `jax.lax.top_k`, differentiated by JAX: the CPU's path
and the kernels' reference. `impl="pallas"` is the chip's:

- `index_select` (a kernel of that name): a block of queries' scores
  against every earlier key in VMEM as sortable integers, never in HBM; the
  row's threshold, the `min(t + 1, topk)`-th largest, by bisection over the
  32 bits; the keys above it and the lowest-indexed of those equal to it,
  by a running count; out go the mask, a bit a pair (int8
  `[B, key tiles, T, tile / 8]`, a key tile's eight bit planes together:
  `ops/flash_attention.py` states the layout, `_mask_of` and `_keep_of`
  pack and unpack a whole one), the row's `logsumexp` of the selected
  scores and the number selected. No `[T, T]` float array exists.
- the flash kernels under that mask (`ops/flash_attention.py`, `mask=`):
  the dense causal walk with the mask's tile beside the causal term,
  `flash_fwd_sparse` and `flash_bwd_dkv_dq_sparse`, one pass over the score
  tiles for dq, dk and dv (at T 16,384 and 8 query heads a key-value head
  with the row-long gradients leaving VMEM a tile at a time; the planner
  falls back to `flash_bwd_dq_sparse` and `flash_bwd_dkv_sparse` where not
  even their f32 sums fit). A seeded indexer scatters its choice over
  the whole prefix, so no tile is empty and none is skipped; a gather of
  2,048 rows a query is not a TPU program at 8 query heads a key-value head.
- `index_loss` (a kernel of that name): a tile of queries by keys at a
  time, the causal tiles alone, every head's probabilities from q, k and
  the kept lse summed over the heads in VMEM, against the index scores made
  again; the rows' KL and, where the backward will want them, the indexer's
  three gradients by hand (`softmax_S(I) - pbar` on the kept pairs, through
  the relu and the weights). They are made in the forward (they need what
  the forward has) and handed on by the backward rule.

Kept (`keep_ctx`), `attn_ctx` names what the backward takes from the forward:
o, lse as one column, the indexer's three gradients and the mask's bits
(2 KB a token at 16,384 keys). A rematerialised block that keeps the name
runs `flash_fwd_sparse`, `index_loss` and `index_select` once a layer: the
selection is a function of the forward's inputs and passes no gradient.

The kernels take a sequence that is a multiple of 128; any other shape
takes the `jax.numpy` path whatever `impl` says (one line in the log).
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.flash_attention import (
    _DEFAULT_VMEM, _NN, _NT, _TN, _dot, _pallas_call, _flash_bwd_dkv,
    _flash_bwd_dq, _flash_fwd, _pack_bits, _pairs_mask, _unpack_bits,
    _exit_said, flash_bwd_kernels, flash_tiles, resolve_impl)

logger = logging.getLogger(__name__)

# what cuts the selection and `pbar` off from every gradient in the
# `jax.numpy` path, under a name of its own: a test takes it away to show
# what the comparison with the reference reads then
_detached = jax.lax.stop_gradient
_INT_MIN = -2 ** 31
_QUERY_BLOCK = 256  # queries whose scores against every key are held at once


def _rows_a_block(T: int) -> int:
    """The queries of a `jax.numpy` block: the largest divisor of `T` that
    is no more than `_QUERY_BLOCK`."""
    return next(r for r in range(min(T, _QUERY_BLOCK), 0, -1) if T % r == 0)


def keys_kept(seq_len: int, topk: Optional[int]) -> int:
    """(query, key) pairs that causal attention under `topk` keeps in a
    sequence of `seq_len`: `sum_t min(t + 1, topk)`."""
    if not topk or topk >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


# ------------------------------------------------------ jax.numpy, by blocks

def index_scores(q_idx, k_idx, w_idx):
    """I [B, R, S] float32 of a block of `R` queries against all `S` keys:
    `q_idx` [B, R, Hi, Di], `k_idx` [B, S, Di], `w_idx` [B, R, Hi]. The
    heads are added one after another from zero, as the kernel adds them."""
    z = jnp.einsum("brhd,bsd->bhrs", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    w = w_idx.astype(jnp.float32)
    scores = jnp.zeros(z.shape[:1] + z.shape[2:], jnp.float32)
    for j in range(z.shape[1]):
        # `jax.nn.relu`: no gradient at 0, as `index_loss` takes it
        scores = scores + w[:, :, j, None] * jax.nn.relu(z[:, j])
    return scores


def select_keys(scores, first: int, topk: int):
    """[B, R, S] bool: the keys rows `first..first + R - 1` keep, by
    `scores` [B, R, S] float32: of the keys `s <= t` the `min(t + 1, topk)`
    of largest score, a tie at the edge to the lower index. The threshold
    is `jax.lax.top_k`'s last value; what is above it is kept, and of what
    equals it the first few that fill the row."""
    R, S = scores.shape[1:]
    t = first + jnp.arange(R)[:, None]
    causal = jnp.arange(S)[None, :] <= t
    # -0.0 as 0.0: `top_k` ranks the two apart and `==` does not (a sum of
    # products from zero, as `index_scores` makes it, is never -0.0)
    scores = jnp.where(scores == 0, 0.0, scores)
    scores = jnp.where(causal, scores, -jnp.inf)
    keep_n = jnp.minimum(t + 1, topk)  # [R, 1]
    edge = jax.lax.top_k(scores, min(topk, S))[0][..., -1:]
    above = scores > edge
    equal = jnp.logical_and(scores == edge, causal)
    room = keep_n - above.sum(-1, keepdims=True)
    return jnp.logical_or(
        above, jnp.logical_and(equal, jnp.cumsum(equal, axis=-1) <= room))


def _by_blocks(x, rows):
    """[B, T, ...] as [T / rows, B, rows, ...]."""
    B, T = x.shape[:2]
    return jnp.moveaxis(x.reshape(B, T // rows, rows, *x.shape[2:]), 1, 0)


def _from_blocks(x):
    """[blocks, B, rows, ...] as [B, blocks x rows, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _kl_rows(pbar, scores, keep):
    """KL(pbar || softmax_keep(scores)) of every row, [B, R]: `pbar` and
    `scores` [B, R, S] float32, `keep` [B, R, S] bool."""
    log_q = scores - jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1, keepdims=True)
    counts = jnp.logical_and(keep, pbar > 0)
    terms = pbar * (jnp.log(jnp.where(counts, pbar, 1.0)) - log_q)
    return jnp.where(counts, terms, 0.0).sum(-1)


def _sparse_xla(q, k, v, q_idx, k_idx, w_idx, topk, scale, keep_ctx):
    """`sparse_attention` in `jax.numpy`, a block of queries at a time,
    each made again in the backward (`jax.checkpoint`). Kept, `attn_ctx`
    saves the output alone: this backward needs its scores again."""
    B, T, H, D = q.shape
    group = H // k.shape[2]
    rows = _rows_a_block(T)
    kf = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=2).astype(jnp.float32)

    @jax.checkpoint
    def block(q_blk, qi_blk, w_blk, first):
        with jax.named_scope("indexer"):
            with jax.named_scope("index_scores"):
                scores = index_scores(qi_blk, k_idx, w_blk)
            with jax.named_scope("index_select"):
                keep = select_keys(_detached(scores), first, topk)
        with jax.named_scope("attention"):
            s = jnp.einsum("brhd,bshd->bhrs", q_blk.astype(jnp.float32),
                           kf) * scale
            p = jax.nn.softmax(
                jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
            out = jnp.einsum("bhrs,bshd->brhd", p, vf).astype(q.dtype)
        with jax.named_scope("index_loss"):
            pbar = _detached(p).mean(axis=1)
            kl = _kl_rows(pbar, scores, keep)
        kept = keep.sum(-1) - jnp.minimum(
            first + jnp.arange(rows) + 1, topk)[None]
        return out, kl, kept, keep.astype(jnp.int8)

    out, kl, kept, keep = jax.lax.map(
        lambda args: block(*args),
        (_by_blocks(q, rows), _by_blocks(q_idx, rows),
         _by_blocks(w_idx, rows), jnp.arange(0, T, rows)))
    out = _from_blocks(out)
    if keep_ctx:
        out = checkpoint_name(out, "attn_ctx")
    return (out, _from_blocks(kl).mean(),
            _from_blocks(kept).astype(jnp.float32), _from_blocks(keep))


# ------------------------------------------------------------ index_select

def _sortable(x):
    """float32 as int32 in the floats' order (and back: it is its own
    inverse on the bits)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _unsortable(keys):
    return jax.lax.bitcast_convert_type(
        keys ^ ((keys >> 31) & 0x7FFFFFFF), jnp.float32)


def _index_select_kernel(q_ref, kt_ref, w_ref, tri_ref,  # inputs
                         mask_ref, lse_ref, kept_ref,  # outputs
                         keys_ref,  # VMEM: the block's scores, sortable
                         *, block_q: int, chunk: int, chunks: int, topk: int,
                         heads: int):
    """One block of `block_q` queries against every key before its end.
    `q_ref` [1, Hi, bq, Di], `kt_ref` [1, chunks, Di, chunk] (the keys
    transposed, a chunk of columns together), `w_ref` [1, bq, Hi] float32,
    `tri_ref` [chunk, chunk] (1 where row <= column: a running count is a
    product with it). `mask_ref` [1, chunks, bq, chunk / 8] int8 (a bit a
    key), `lse_ref` and `kept_ref` [1, bq, 8] float32."""
    from jax.experimental import pallas as pl

    first = pl.program_id(1) * block_q
    # the chunks that hold a key at or before the block's last query
    walked = jnp.minimum((first + block_q + chunk - 1) // chunk, chunks)
    row = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk), 1)
    w = w_ref[0]  # [bq, Hi]

    def score(c, carry):
        kt = kt_ref[0, c]  # [Di, chunk]
        scores = jnp.zeros((block_q, chunk), jnp.float32)
        for j in range(heads):
            z = _dot(q_ref[0, j], kt, _NN)  # [bq, chunk]
            scores = scores + w[:, j:j + 1] * jnp.maximum(z, 0.0)
        keys_ref[c] = jnp.where(c * chunk + col <= row, _sortable(scores),
                                _INT_MIN)
        return carry

    jax.lax.fori_loop(0, walked, score, 0)

    def over_chunks(of_keys, init):
        """`init` and `of_keys(chunk's keys)` [bq, 1] added up."""
        return jax.lax.fori_loop(
            0, walked, lambda c, acc: acc + of_keys(keys_ref[c]), init)

    def count(hit):
        return jnp.sum(jnp.where(hit, 1.0, 0.0), axis=-1, keepdims=True)

    zeros = jnp.zeros((block_q, 1), jnp.float32)
    keep_n = jnp.minimum(row[:, :1] + 1, topk).astype(jnp.float32)

    # the largest threshold that at least `keep_n` keys reach, a bit at a
    # time from the top, in the order of the keys taken as unsigned
    def bisect(i, tau):
        cand = tau | jnp.left_shift(jnp.int32(1), 31 - i)
        reach = over_chunks(lambda keys: count(keys >= (cand ^ _INT_MIN)),
                            zeros)
        return jnp.where(reach >= keep_n, cand, tau)

    tau = jax.lax.fori_loop(
        0, 32, bisect, jnp.zeros((block_q, 1), jnp.int32)) ^ _INT_MIN
    room = keep_n - over_chunks(lambda keys: count(keys > tau), zeros)
    top = jax.lax.fori_loop(
        0, walked, lambda c, m: jnp.maximum(
            m, jnp.max(keys_ref[c], axis=-1, keepdims=True)),
        jnp.full((block_q, 1), _INT_MIN, jnp.int32))
    top = _unsortable(top)

    def emit(c, carry):
        seen, total, kept = carry
        keys = keys_ref[c]
        equal = keys == tau
        before = _dot(jnp.where(equal, 1.0, 0.0).astype(tri_ref.dtype),
                      tri_ref[...], _NN) + seen
        keep = jnp.logical_or(keys > tau,
                              jnp.logical_and(equal, before <= room))
        mask_ref[0, c] = _pack_bits(keep)
        weight = jnp.where(keep, jnp.exp(_unsortable(keys) - top), 0.0)
        return (seen + count(equal),
                total + jnp.sum(weight, axis=-1, keepdims=True),
                kept + count(keep))

    _, total, kept = jax.lax.fori_loop(0, walked, emit, (zeros, zeros, zeros))

    def clear(c, carry):
        mask_ref[0, c] = jnp.zeros((block_q, chunk // 8), jnp.int8)
        return carry

    jax.lax.fori_loop(walked, chunks, clear, 0)
    lse_ref[0] = jnp.broadcast_to(top + jnp.log(total), (block_q, 8))
    kept_ref[0] = jnp.broadcast_to(kept - keep_n, (block_q, 8))


def _select_block(T: int) -> int:
    """The queries a step of `index_select` holds: 256, whose scores against
    16,384 keys are 16 MB of VMEM, or the sequence where it is shorter."""
    return next((b for b in (256, 128) if T % b == 0), T)


def index_select(q_idx, k_idx, w_idx, *, topk: int, chunk: int,
                 interpret: bool = False):
    """(mask [B, S / chunk, T, chunk / 8] int8, a bit a key, lse_idx [B, T]
    float32, the rows' `kept - min(t + 1, topk)` [B, T] float32) by the
    kernel: T a multiple of 128 and of `chunk`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Hi, Di = q_idx.shape
    chunks = T // chunk
    block_q = _select_block(T)
    qh = q_idx.transpose(0, 2, 1, 3)  # [B, Hi, T, Di]
    kt = k_idx.reshape(B, chunks, chunk, Di).transpose(0, 1, 3, 2)
    tri = (jnp.arange(chunk)[:, None] <= jnp.arange(chunk)[None, :]
           ).astype(jnp.bfloat16)
    vmem = (chunks * block_q * chunk * (4 + 2 / 8)  # keys; the bits, twice
            + 2 * 2 * (Hi * block_q * 128 + T * Di) * q_idx.dtype.itemsize
            + 2 * chunk * chunk * 2 + 16 * block_q * chunk * 4)
    kernel = functools.partial(
        _index_select_kernel, block_q=block_q, chunk=chunk, chunks=chunks,
        topk=topk, heads=Hi)
    row = pl.BlockSpec((1, block_q, 8), lambda b, qi: (b, qi, 0))
    mask, lse_idx, kept = _pallas_call(
        kernel,
        grid=(B, T // block_q),
        in_specs=[
            pl.BlockSpec((1, Hi, block_q, Di), lambda b, qi: (b, 0, qi, 0)),
            pl.BlockSpec((1, chunks, Di, chunk), lambda b, qi: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_q, Hi), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((chunk, chunk), lambda b, qi: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunks, block_q, chunk // 8),
                         lambda b, qi: (b, 0, qi, 0)),
            row, row,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, chunks, T, chunk // 8), jnp.int8),
            jax.ShapeDtypeStruct((B, T, 8), jnp.float32),
            jax.ShapeDtypeStruct((B, T, 8), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((chunks, block_q, chunk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(_DEFAULT_VMEM, int(1.25 * vmem))),
        interpret=interpret,
        name="index_select",
    )(qh, kt, w_idx.astype(jnp.float32), tri)
    return mask, lse_idx[..., 0], kept[..., 0]


# -------------------------------------------------------------- index loss

def _index_loss_kernel(q_ref, k_ref, lse_ref, mask_ref, qi_ref, kt_ref, w_ref,
                       lsei_ref,  # inputs
                       kl_ref, *grads_and_sums,
                       block_q: int, chunk: int, steps: int, heads: int,
                       group: int, index_heads: int, scale: float,
                       with_grads: bool):
    """One tile of `block_q` queries by `chunk` keys, the keys walked
    innermost. `q_ref` [1, H, bq, D], `k_ref` [1, Hk, chunk, D], `lse_ref`
    [1, bq, H] (the attention's), `mask_ref` [1, 1, bq, chunk / 8] int8 (the
    tile's bits, which have the causal term), `qi_ref` [1, Hi, bq, Di],
    `kt_ref` [1, 1, Di, chunk], `w_ref` [1, bq, Hi], `lsei_ref` [1, bq, 8]
    (the rows' logsumexp of the kept index scores). Out: `kl_ref`
    [1, bq, 8], the rows' KL; with `with_grads` the gradient of the rows'
    KL summed by the index queries [1, Hi, bq, Di], by the index key,
    transposed, [1, chunks, Di, chunk] float32 (summed over the q tiles: the
    block stays in VMEM while a sequence is walked) and by the weights
    [1, bq, 128] (head j in lane j)."""
    from jax.experimental import pallas as pl

    if with_grads:
        dq_ref, dkt_ref, dw_ref, kl_acc, dq_acc, dw_acc = grads_and_sums
    else:
        (kl_acc,) = grads_and_sums
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        kl_acc[...] = jnp.zeros_like(kl_acc)
        if with_grads:
            dq_acc[...] = jnp.zeros_like(dq_acc)
            dw_acc[...] = jnp.zeros_like(dw_acc)

    if with_grads:
        @pl.when(jnp.logical_and(qi == 0, ki == 0))
        def _init_dk():
            dkt_ref[...] = jnp.zeros_like(dkt_ref)

    @pl.when(ki * chunk < (qi + 1) * block_q)  # some key is not after
    def _body():
        keep = _pairs_mask(False, mask_ref, qi, ki)
        lse = lse_ref[0]  # [bq, H]
        pbar = jnp.zeros((block_q, chunk), jnp.float32)
        for h in range(heads):
            s = _dot(q_ref[0, h], k_ref[0, h // group], _NT) * scale
            pbar = pbar + jnp.exp(s - lse[:, h:h + 1])
        pbar = jnp.where(keep, pbar * (1.0 / heads), 0.0)
        w = w_ref[0]  # [bq, Hi]
        kt = kt_ref[0, 0]  # [Di, chunk]
        scores = jnp.zeros((block_q, chunk), jnp.float32)
        for j in range(index_heads):
            z = _dot(qi_ref[0, j], kt, _NN)
            scores = scores + w[:, j:j + 1] * jnp.maximum(z, 0.0)
        log_q = scores - lsei_ref[0][:, :1]
        counts = jnp.logical_and(keep, pbar > 0)
        terms = pbar * (jnp.log(jnp.where(counts, pbar, 1.0)) - log_q)
        kl_acc[...] += jnp.sum(jnp.where(counts, terms, 0.0), axis=-1,
                               keepdims=True)
        if not with_grads:
            return
        d_scores = jnp.where(keep, jnp.exp(log_q) - pbar, 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_acc.shape, 1)
        d_w = jnp.zeros(dw_acc.shape, jnp.float32)
        d_kt = jnp.zeros(kt.shape, jnp.float32)
        for j in range(index_heads):
            q_j = qi_ref[0, j]  # [bq, Di]
            z = _dot(q_j, kt, _NN)  # made again: 16 tiles do not stay
            d_w = d_w + jnp.where(lane == j, jnp.sum(
                d_scores * jnp.maximum(z, 0.0), axis=-1, keepdims=True), 0.0)
            d_z = jnp.where(z > 0, d_scores * w[:, j:j + 1], 0.0).astype(
                q_j.dtype)
            dq_acc[j] += _dot(d_z, kt, _NT)  # [bq, Di]
            d_kt = d_kt + _dot(q_j, d_z, _TN)  # [Di, chunk]
        dw_acc[...] += d_w
        dkt_ref[0, ki] += d_kt

    @pl.when(ki == steps - 1)
    def _flush():
        kl_ref[0] = jnp.broadcast_to(kl_acc[...], (block_q, 8))
        if with_grads:
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
            dw_ref[0] = dw_acc[...]


def index_loss(q, k, lse, mask, q_idx, k_idx, w_idx, lse_idx, scale,
               with_grads: bool, interpret: bool = False):
    """(the rows' KL summed, and with `with_grads` its gradient by `q_idx`,
    `k_idx` and `w_idx`, else None) by the kernel `index_loss`: q
    [B, T, H, D], k [B, T, Hk, D], `lse` [B, H, T] (the attention's,
    float32), `mask` [B, key tiles, T, tile / 8] int8, `lse_idx` [B, T]. A
    tile of 256 queries by the mask's key tile, the causal tiles alone; the
    gradient's matmuls take their left operands rounded to the inputs'
    dtype, as the flash kernels round ds."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    Hk = k.shape[2]
    Hi, Di = q_idx.shape[2:]
    chunks, chunk = mask.shape[1], 8 * mask.shape[3]
    block_q = _select_block(T)
    num_q = T // block_q

    def last(qi):  # the last key tile with a key not after the tile's rows
        return ((qi + 1) * block_q - 1) // chunk

    def row(width):
        return pl.BlockSpec((1, block_q, width), lambda b, qi, ki: (b, qi, 0))

    def heads_rows(heads, width):
        return pl.BlockSpec((1, heads, block_q, width),
                            lambda b, qi, ki: (b, 0, qi, 0))

    in_specs = [
        heads_rows(H, D),
        pl.BlockSpec((1, Hk, chunk, D),
                     lambda b, qi, ki: (b, 0, jnp.minimum(ki, last(qi)), 0)),
        row(H),
        pl.BlockSpec((1, 1, block_q, chunk // 8),
                     lambda b, qi, ki: (b, jnp.minimum(ki, last(qi)), qi, 0)),
        heads_rows(Hi, Di),
        pl.BlockSpec((1, 1, Di, chunk),
                     lambda b, qi, ki: (b, jnp.minimum(ki, last(qi)), 0, 0)),
        row(Hi),
        row(8),
    ]
    out_specs = [row(8)]
    out_shape = [jax.ShapeDtypeStruct((B, T, 8), jnp.float32)]
    scratch = [pltpu.VMEM((block_q, 1), jnp.float32)]
    if with_grads:
        out_specs += [
            heads_rows(Hi, Di),
            pl.BlockSpec((1, chunks, Di, chunk),
                         lambda b, qi, ki: (b, 0, 0, 0)),
            row(128)]
        out_shape += [
            jax.ShapeDtypeStruct((B, Hi, T, Di), q_idx.dtype),
            jax.ShapeDtypeStruct((B, chunks, Di, chunk), jnp.float32),
            jax.ShapeDtypeStruct((B, T, 128), jnp.float32)]
        scratch += [pltpu.VMEM((Hi, block_q, Di), jnp.float32),
                    pltpu.VMEM((block_q, 128), jnp.float32)]
    item = q.dtype.itemsize
    vmem = (2 * (H * block_q * 128 + Hk * chunk * 128 + Hi * block_q * 128
                 + Di * chunk) * item + 2 * block_q * chunk // 8
            + 2 * 4 * block_q * 128 * 4 + 12 * block_q * chunk * 4)
    if with_grads:
        vmem += (2 * T * Di * 4 + 3 * Hi * block_q * 128 * 4
                 + 3 * block_q * 128 * 4)
    kernel = functools.partial(
        _index_loss_kernel, block_q=block_q, chunk=chunk, steps=chunks,
        heads=H, group=H // Hk, index_heads=Hi, scale=scale,
        with_grads=with_grads)
    out = _pallas_call(
        kernel,
        grid=(B, num_q, chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(_DEFAULT_VMEM, int(1.25 * vmem))),
        interpret=interpret,
        name="index_loss",
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      jnp.moveaxis(lse, 1, 2), mask, q_idx.transpose(0, 2, 1, 3),
      k_idx.reshape(B, chunks, chunk, Di).transpose(0, 1, 3, 2),
      w_idx.astype(jnp.float32), jnp.broadcast_to(
          lse_idx[..., None], (B, T, 8)))
    kl = out[0][..., 0].sum()
    if not with_grads:
        return kl, None
    _, d_q, d_kt, d_w = out
    return kl, (d_q.transpose(0, 2, 1, 3),
                d_kt.transpose(0, 1, 3, 2).reshape(B, T, Di),
                d_w[..., :Hi])


# ------------------------------------------------------- the chip's program

def _key_tile(S: int, block_k: Optional[int]) -> Optional[int]:
    """The mask's key tile, which is every flash kernel's: the largest of
    1024, 512, 256 and 128 that divides the keys. Timed alone at T 16,384,
    32 / 4 heads of 128 in bf16 (PERF.md section 6, PR 46), 1024 against
    512: `flash_fwd_sparse` 25.0 ms for 37.3 (the unmasked forward reads
    19.4 for 35.6: a key tile of 512 costs the forward its steps, mask or
    no mask), `flash_bwd_dq_sparse` 23.3 for 24.8, `flash_bwd_dkv_sparse`
    32.7 for 34.1, `index_select` 10.3 for 11.0, `index_loss` level. None:
    the kernels do not take the shape."""
    fits = [c for c in ((block_k,) if block_k else (1024, 512, 256, 128))
            if c % 128 == 0 and S % c == 0]
    return fits[0] if fits else None


@functools.lru_cache(maxsize=None)
def _log_path(T, H, Hk, D, Dv, dtype, topk, tile, block_q):
    """One line for each shape a process traces: the path, the mask's tile
    and bytes, and the flash kernels under the mask with their tiles."""
    if tile is None:
        logger.info(
            "sparse attention at T %d, %d heads over %d, D %d, %s, topk %d: "
            "blocks of queries in jax.numpy, because %d is no multiple of "
            "128", T, H, Hk, D, dtype, topk, T)
        return

    def tiles(kernel):
        return flash_tiles(kernel, T, T, D, dtype, block_q=block_q,
                           block_k=tile, v_dim=Dv, group=H // Hk, sparse=True)

    kernels = ("flash_fwd", *flash_bwd_kernels(
        T, T, D, dtype, block_q=block_q, block_k=tile, v_dim=Dv,
        group=H // Hk, sparse=True))
    logger.info(
        "sparse attention at T %d, %d heads over %d, D %d, Dv %d, %s, "
        "topk %d: selection by the kernel index_select into a mask as bits in "
        "key tiles of %d, %d bytes a sequence, kept with attn_ctx; the dense "
        "causal walk under it by %s; the index loss by the kernel index_loss",
        T, H, Hk, D, Dv, dtype, topk, tile, T * T // 8,
        ", ".join("%s_sparse %d x %d%s" % (
            kernel, t.block_q, t.block_k,
            _exit_said(kernel, t, D, Dv, H // Hk))
            for kernel, t in zip(kernels, map(tiles, kernels))))


def _heads_first(x):
    """[B, T, H, D] as [B H, T, D], heads folded batch-major: what
    `index_loss` reads beside the flash kernels, which take v folded too
    (their `v_heads` 1, the default) whatever the heads' width."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _forward(q, k, v, q_idx, k_idx, w_idx, topk, scale, blocks, interpret,
             with_grads):
    """(o [B H, T, Dv], lse [B H, T, 8], the rows' KL summed, the rows'
    kept keys less what they should keep, the residuals beside o and lse:
    q, k, v heads first, the mask, the indexer's three gradients or
    None)."""
    B, T, H, D = q.shape
    S, Hk, Dv = k.shape[1], k.shape[2], v.shape[3]
    block_q, block_k = blocks
    tile = _key_tile(S, block_k)
    with jax.named_scope("indexer"), jax.named_scope("index_select"):
        mask, lse_idx, kept = index_select(
            q_idx, k_idx, w_idx, topk=topk, chunk=tile, interpret=interpret)
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    with jax.named_scope("attention"):
        o, lse = _flash_fwd(
            qf, kf, vf, causal=True, scale=scale, block_q=block_q,
            block_k=tile, interpret=interpret, with_lse=True, mask=mask)
    with jax.named_scope("index_loss"):
        kl, grads = index_loss(
            q, k, lse[..., 0].reshape(B, H, T), mask, q_idx, k_idx, w_idx,
            lse_idx, scale, with_grads, interpret)
    if with_grads:  # of the rows' mean, in their primals' dtypes
        grads = tuple((g / (B * T)).astype(x.dtype) for g, x in zip(
            grads, (q_idx, k_idx, w_idx)))
    return o, lse, kl / (B * T), kept, (qf, kf, vf, mask, grads)


def _mask_of(keep, tile: int):
    """`keep` [B, T, keys] (nonzero where a query keeps a key) as the data
    mask the kernels take: `[B, key tiles, T, tile / 8]` int8, a bit a pair,
    the last tile's bits past the keys 0."""
    B, T, keys = keep.shape
    keep = jnp.pad(keep != 0, ((0, 0), (0, 0), (0, -keys % tile)))
    return _pack_bits(keep.reshape(B, T, -1, tile)).transpose(0, 2, 1, 3)


def _keep_of(mask, keys: int):
    """`_mask_of`'s inverse: the mask as `[B, T, keys]` int8, 1 where a
    query keeps a key."""
    B, _, T, _ = mask.shape
    keep = _unpack_bits(mask).transpose(0, 2, 1, 3).reshape(B, T, -1)
    return keep[..., :keys].astype(jnp.int8)


def _heads_last(x, batch: int):
    """[B H, T, D] as [B, T, H, D]."""
    return x.reshape(batch, -1, *x.shape[1:]).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _sparse_pallas(q, k, v, q_idx, k_idx, w_idx, topk, scale, blocks,
                   interpret, keep_ctx):
    o, _, loss, kept, (_, _, _, mask, _) = _forward(
        q, k, v, q_idx, k_idx, w_idx, topk, scale, blocks, interpret,
        with_grads=False)
    return _heads_last(o, q.shape[0]), loss, kept, _keep_of(mask, k.shape[1])


def _sparse_pallas_fwd(q, k, v, q_idx, k_idx, w_idx, topk, scale, blocks,
                       interpret, keep_ctx):
    o, lse, loss, kept, (qf, kf, vf, mask, grads) = _forward(
        q, k, v, q_idx, k_idx, w_idx, topk, scale, blocks, interpret,
        with_grads=True)
    if keep_ctx:
        # Named where the backward takes them, as `flash_attention` names o
        # and lse: a rematerialised block that keeps `attn_ctx` runs neither
        # the forward kernel, the index loss nor the selection a second
        # time. The indexer's gradients are 2.2 KB a token and the mask's
        # bits 2 KB at 16,384 keys, beside o's 8 KB.
        o = checkpoint_name(o, "attn_ctx")
        lse = checkpoint_name(lse[..., 0], "attn_ctx")
        mask = checkpoint_name(mask, "attn_ctx")
        grads = tuple(checkpoint_name(g, "attn_ctx") for g in grads)
    return (_heads_last(o, q.shape[0]), loss, kept,
            _keep_of(mask, k.shape[1])), (qf, kf, vf, o, lse, mask, grads)


def _sparse_pallas_bwd(topk, scale, blocks, interpret, keep_ctx, res,
                       cotangents):
    """dq, dk and dv by the flash backward under the mask; the indexer's
    three gradients are the forward's, times the loss's cotangent over the
    rows."""
    qf, kf, vf, o, lse, mask, (d_qi, d_ki, d_w) = res
    d_out, d_loss = cotangents[:2]
    BH, T, D = qf.shape
    B = mask.shape[0]
    H, Hk = BH // B, kf.shape[0] // B
    if keep_ctx:
        lse = jnp.broadcast_to(lse[..., None], (BH, T, 8))
    do = _heads_first(d_out)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (BH, T, 8))
    block_q, tile = blocks[0], 8 * mask.shape[3]
    kernels = flash_bwd_kernels(
        T, kf.shape[1], D, qf.dtype, block_q=block_q, block_k=tile,
        v_dim=vf.shape[2], group=H // Hk, sparse=True)
    tile_args = dict(causal=True, scale=scale, block_q=block_q, block_k=tile,
                     interpret=interpret, mask=mask)
    with jax.named_scope("attention"):
        if kernels == ("flash_bwd_dkv_dq",):
            dq, dk, dv = _flash_bwd_dkv(
                qf, kf, vf, do, lse, delta, with_dq=True, **tile_args)
        else:
            dq = _flash_bwd_dq(qf, kf, vf, do, lse, delta, **tile_args)
            dk, dv = _flash_bwd_dkv(qf, kf, vf, do, lse, delta, **tile_args)
    return (_heads_last(dq, B), _heads_last(dk, B), _heads_last(dv, B),
            *((g * d_loss).astype(g.dtype) for g in (d_qi, d_ki, d_w)))


_sparse_pallas.defvjp(_sparse_pallas_fwd, _sparse_pallas_bwd)


def sparse_attention(q, k, v, q_idx, k_idx, w_idx, *, topk: int,
                     scale: Optional[float] = None, impl: str = "auto",
                     interpret: bool = False, keep_ctx: bool = False,
                     blocks: Tuple[Optional[int], Optional[int]] = (None, None)):
    """(out [B, T, H, Dv], the index loss (a float32 scalar: the mean over
    the B T queries of `KL(pbar || softmax_S(I))`), the rows' `|S_t| -
    min(t + 1, topk)` [B, T] float32: 0 everywhere, the selection
    [B, T, T] int8: 1 where a query keeps a key) of causal attention of
    q [B, T, H, D] over k [B, T, Hk, D] and v [B, T, Hk, Dv] restricted to
    the `topk` keys a query's index scores rank first (`q_idx`
    [B, T, Hi, Di], `k_idx` [B, T, Di], `w_idx` [B, T, Hi]; the module's
    docstring has the equations and where gradients go).

    impl: 'auto' (pallas on TPU, XLA elsewhere) | 'pallas' | 'xla'; a
    sequence that is no multiple of 128 takes 'xla' whatever it says.
    `keep_ctx` names the backward's residuals that are worth their bytes
    `attn_ctx` (`jax.ad_checkpoint`): o, lse, the indexer's gradients and
    the selection's mask as bits.
    `interpret` runs the kernels in interpret mode and `blocks` forces
    their (q, key) tile, for tests."""
    B, T, H, D = q.shape
    if k.shape[1] != T:
        raise ValueError("sparse attention is self-attention: as many keys "
                         f"as queries, not {k.shape[1]} for {T}")
    if H % k.shape[2]:
        raise ValueError(
            f"{H} query heads do not divide among {k.shape[2]} key-value "
            "heads")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if resolve_impl(impl) == "pallas" or interpret:
        tile = _key_tile(T, blocks[1])
        _log_path(T, H, k.shape[2], D, v.shape[3], jnp.dtype(q.dtype).name,
                  int(topk), tile, blocks[0])
        if tile is not None:
            return _sparse_pallas(
                q, k, v, q_idx, k_idx, w_idx, int(topk), float(scale),
                tuple(blocks), interpret, keep_ctx)
    return _sparse_xla(q, k, v, q_idx, k_idx, w_idx, int(topk), float(scale),
                       keep_ctx)
