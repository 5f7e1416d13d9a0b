"""EVA attention (arXiv:2302.04542, "Efficient Attention via Control
Variates", as EvaByte holds it): an exact causal softmax inside a window of
`window` tokens joined, in ONE softmax, with learned summaries of every
earlier window's chunks of `chunk` tokens.

For head `h` with two learned vectors `phi_h`, `mu_h` [D] and chunk `c` =
tokens `chunk c .. chunk c + chunk - 1`:

    a_j  = phi_h . k_j                                 float32
    w_j  = exp(a_j) / sum_{j' in c} exp(a_j')          a softmax over a chunk
    kS_c = sum_j w_j k_j + mu_h        vS_c = sum_j w_j v_j

and for query `i` in window `W(i) = i // window`, `s = 1 / sqrt(D)`:

    L_i = {j : W(j) = W(i), j <= i}                    its window, causal
    R_i = {c : c < (window / chunk) W(i)}              the earlier windows' chunks
    o_i = (sum_L exp(s q_i . k_j) v_j + sum_R exp(s q_i . kS_c) vS_c) / Z_i

with `Z_i` the sum of both kinds of weight. `eva_attention` computes it in
four parts, each under a `jax.named_scope` of its own, forward and backward:

- `eva_summaries`: one pass over k and v, `[B, T, H, D]` in and `[B, T /
  chunk, H, D]` twice out. On the TPU the kernel pair `eva_summaries_fwd`,
  `eva_summaries_bwd` behind a `custom_vjp`: a grid step takes a head's
  block of tokens of k and of v as they lie (`[B, T, H D]`, a head's 128
  lanes), widens them in VMEM, and writes the block's summaries; the
  backward makes `w` again from k, spreads a chunk's cotangents over its
  tokens by one small matmul against a 0/1 matrix (Mosaic has no relayout
  from a row a chunk to a row a token), writes dk and dv once and sums
  `d phi` in float32, a partial row a grid step. No float32 array of k's
  shape reaches HBM. Elsewhere (`impl="xla"`, a shape that does not tile:
  `summaries_untiled`) `jax.numpy` under autodiff.
- `eva_window`: the causal flash kernel on `[B T / window, window, H, D]`,
  the windows folded into the batch: block-diagonal and causal with no new
  mask (`flash_attention_lse`, which also hands out the rows' lse).
- `eva_stair`: all `T` queries against the `T / chunk` summaries under the
  staircase (`flash_attention_lse(stair=)`): window `w` sees the summaries
  of windows `0 .. w - 1`, none of its own; window 0 sees none and has lse
  -inf, o 0.
- `eva_join`: `o = (e^lseL oL + e^lseR oR) / (e^lseL + e^lseR)` in float32,
  by the larger lse, so that `lseR = -inf` gives `oL`.

No `[T, T]` or `[T, T / chunk]` tensor reaches HBM on the Pallas path. The
XLA path builds the scores of a window against its own keys and against the
summaries, whole. A sequence no longer than one window is plain causal
attention: no summaries are made and `phi`, `mu` get no gradient.

Two readings, detached: `eva_remote_mass`, the mean over queries and heads
of the share of a query's softmax that the summaries take, `e^lseR / (e^lseL
+ e^lseR)`, and `eva_chunk_entropy`, the mean entropy of `w` over a chunk
(`log chunk` is mean pooling).
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.flash_attention import (
    _LANES, _NN, _dot, _pallas_call, flash_attention, flash_attention_lse,
    mha, resolve_impl)

logger = logging.getLogger(__name__)

_F32 = jnp.float32
_TOKENS = 2048  # the most tokens a grid step of the summaries' kernels takes


# --------------------------------------------------------------- summaries

def _chunk_weights(k3, phi):
    """`w` [n, chunk, 1] float32 of `k3` [n, chunk, D] float32 under `phi`
    [1, D]: the softmax over a chunk of `phi . k`."""
    a = jnp.sum(k3 * phi, axis=-1, keepdims=True)
    e = jnp.exp(a - jnp.max(a, axis=1, keepdims=True))
    return e / jnp.sum(e, axis=1, keepdims=True)


def _summaries_fwd_kernel(k_ref, v_ref, phi_ref, mu_ref, ks_ref, vs_ref, *,
                          chunk: int):
    tokens, D = k_ref.shape[1:]
    k3 = k_ref[0].astype(_F32).reshape(tokens // chunk, chunk, D)
    v3 = v_ref[0].astype(_F32).reshape(tokens // chunk, chunk, D)
    w = _chunk_weights(k3, phi_ref[0])
    ks_ref[0] = (jnp.sum(w * k3, axis=1) + mu_ref[0]).astype(ks_ref.dtype)
    vs_ref[0] = jnp.sum(w * v3, axis=1).astype(vs_ref.dtype)


def _summaries_bwd_kernel(k_ref, v_ref, phi_ref, dks_ref, dvs_ref,
                          dk_ref, dv_ref, dphi_ref, *, chunk: int):
    tokens, D = k_ref.shape[1:]
    n = tokens // chunk
    k3 = k_ref[0].astype(_F32).reshape(n, chunk, D)
    v3 = v_ref[0].astype(_F32).reshape(n, chunk, D)
    phi = phi_ref[0]
    w = _chunk_weights(k3, phi)
    # a chunk's cotangents on each of its tokens: one matmul against the
    # 0/1 matrix [tokens, n] that has a 1 where the token is the chunk's
    of_chunk = (jax.lax.broadcasted_iota(jnp.int32, (tokens, n), 0) // chunk
                == jax.lax.broadcasted_iota(jnp.int32, (tokens, n), 1)
                ).astype(dks_ref.dtype)
    # `_dot`: narrow operands take one MXU pass whatever the process's
    # matmul precision says (Mosaic refuses "highest" for them)
    dks, dvs = (_dot(of_chunk, ref[0], _NN).reshape(n, chunk, D)
                for ref in (dks_ref, dvs_ref))
    dw = jnp.sum(dks * k3 + dvs * v3, axis=-1, keepdims=True)
    da = w * (dw - jnp.sum(w * dw, axis=1, keepdims=True))
    dk_ref[0] = (w * dks + da * phi).reshape(tokens, D).astype(dk_ref.dtype)
    dv_ref[0] = (w * dvs).reshape(tokens, D).astype(dv_ref.dtype)
    dphi = jnp.sum((da * k3).reshape(tokens, D), axis=0, keepdims=True)
    dphi_ref[0, 0] = jnp.broadcast_to(dphi, dphi_ref.shape[2:])


def _block_tokens(T: int, chunk: int) -> int:
    """The tokens a grid step takes: the most, up to `_TOKENS`, that divide
    `T` into blocks whose summaries are whole float32 tiles of 8 rows; 0
    where there is none but the sequence itself."""
    for tokens in range(min(T, _TOKENS), 0, -1):
        if T % tokens == 0 and tokens % (8 * chunk) == 0:
            return tokens
    return 0


def summaries_untiled(T: int, chunk: int, D: int) -> bool:
    """Whether the kernels do not take the shape: a head narrower than the
    lanes, a chunk that is no whole float32 tile, or no block of tokens."""
    return bool(D % _LANES or chunk % 8 or not _block_tokens(T, chunk))


def _summaries_call(kernel, name, k, v, phi, extra, chunk, backward,
                    interpret):
    """One of the two kernels on k, v `[B, T, H D]`, phi `[H, 1, D]` and
    `extra` (the forward's mu, the backward's two cotangents `[B, T / chunk,
    H D]`): grid (batch row, head, block of tokens)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, wide = k.shape
    H, _, D = phi.shape
    tokens = _block_tokens(T, chunk) or T
    per_token = pl.BlockSpec((1, tokens, D), lambda b, h, t: (b, t, h))
    per_chunk = pl.BlockSpec((1, tokens // chunk, D), lambda b, h, t: (b, t, h))
    per_head = pl.BlockSpec((1, 1, D), lambda b, h, t: (h, 0, 0))
    summary = jax.ShapeDtypeStruct((B, T // chunk, wide), k.dtype)
    if backward:
        in_specs = [per_token, per_token, per_head, per_chunk, per_chunk]
        out_specs = [per_token, per_token,
                     pl.BlockSpec((1, 1, 8, D), lambda b, h, t: (b, t, 0, h))]
        out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                     jax.ShapeDtypeStruct(v.shape, v.dtype),
                     jax.ShapeDtypeStruct((B, T // tokens, 8, wide), _F32)]
    else:
        in_specs = [per_token, per_token, per_head, per_head]
        out_specs, out_shape = [per_chunk, per_chunk], [summary, summary]
    with jax.named_scope("eva_summaries"):
        return _pallas_call(
            functools.partial(kernel, chunk=chunk),
            grid=(B, H, T // tokens),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3,
                # a block of k and of v widened, their products and results
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name=name,
        )(k, v, phi, *extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _summaries_kernels(k, v, phi, mu, chunk, interpret):
    return tuple(_summaries_call(
        _summaries_fwd_kernel, "eva_summaries_fwd", k, v, phi, (mu,), chunk,
        False, interpret))


def _summaries_vjp_fwd(k, v, phi, mu, chunk, interpret):
    return _summaries_kernels(k, v, phi, mu, chunk, interpret), (k, v, phi)


def _summaries_vjp_bwd(chunk, interpret, res, cotangents):
    k, v, phi = res
    dks, dvs = cotangents
    dk, dv, dphi = _summaries_call(
        _summaries_bwd_kernel, "eva_summaries_bwd", k, v, phi, (dks, dvs),
        chunk, True, interpret)
    H, _, D = phi.shape
    with jax.named_scope("eva_summaries"):
        return (dk, dv,
                dphi[:, :, 0].sum(axis=(0, 1)).reshape(H, 1, D),
                dks.astype(_F32).sum(axis=(0, 1)).reshape(H, 1, D))


_summaries_kernels.defvjp(_summaries_vjp_fwd, _summaries_vjp_bwd)


def _summaries_xla(k, v, phi, mu, chunk):
    """The summaries in `jax.numpy`, float32 inside, under autodiff."""
    B, T, H, D = k.shape
    k5 = k.astype(_F32).reshape(B, T // chunk, chunk, H, D)
    v5 = v.astype(_F32).reshape(B, T // chunk, chunk, H, D)
    w = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", k5, phi), axis=2)
    ks = jnp.einsum("bnch,bnchd->bnhd", w, k5) + mu
    vs = jnp.einsum("bnch,bnchd->bnhd", w, v5)
    return ks.astype(k.dtype), vs.astype(v.dtype)


@functools.lru_cache(maxsize=None)
def _log_summaries(path, T, chunk, H, D, dtype):
    logger.info("eva summaries at T %d, chunks of %d, %d heads of %d, %s: %s",
                T, chunk, H, D, dtype, path)


def chunk_summaries(k, v, phi, mu, *, chunk: int, impl: str = "auto",
                    interpret: bool = False):
    """(kS, vS) `[B, T / chunk, H, D]` in k's dtype: the chunks' summaries of
    k, v `[B, T, H, D]` under `phi`, `mu` `[H, D]` float32 (the module's
    docstring). The kernel pair where `impl` resolves to Pallas (or
    `interpret`) and the shape tiles, `jax.numpy` elsewhere."""
    B, T, H, D = k.shape
    if T % chunk:
        raise ValueError(f"chunks of {chunk} do not divide {T} tokens")
    phi, mu = phi.astype(_F32), mu.astype(_F32)
    kernels = interpret or (resolve_impl(impl) == "pallas"
                            and not summaries_untiled(T, chunk, D))
    _log_summaries("the kernels eva_summaries_fwd, eva_summaries_bwd"
                   if kernels else "jax.numpy", T, chunk, H, D,
                   jnp.dtype(k.dtype).name)
    if not kernels:
        with jax.named_scope("eva_summaries"):
            return _summaries_xla(k, v, phi, mu, chunk)
    ks, vs = _summaries_kernels(
        k.reshape(B, T, H * D), v.reshape(B, T, H * D),
        phi.reshape(H, 1, D), mu.reshape(H, 1, D), chunk, interpret)
    return (ks.reshape(B, T // chunk, H, D), vs.reshape(B, T // chunk, H, D))


def chunk_entropy(k, phi, chunk: int):
    """The mean entropy of `w` over the chunks of k `[B, T, H, D]`, a float32
    scalar: a reading, detached."""
    B, T, H, D = k.shape
    a = jnp.einsum("bthd,hd->bth", jax.lax.stop_gradient(k),
                   jax.lax.stop_gradient(phi).astype(k.dtype),
                   preferred_element_type=_F32)
    log_w = jax.nn.log_softmax(a.reshape(B, T // chunk, chunk, H), axis=2)
    return -(jnp.exp(log_w) * log_w).sum(axis=2).mean()


# ------------------------------------------------- the two partial softmaxes

def _partial_xla(q, k, v, mask, scale):
    """(o, lse) of q `[..., T, H, D]` over k, v `[..., S, H, D]` where `mask`
    `[..., T, S]` (broadcast over heads) is set, float32: the XLA path's
    partial softmax. A row that sees no key has o 0 and lse -inf."""
    s = jnp.einsum("...thd,...shd->...hts", q.astype(_F32),
                   k.astype(_F32)) * scale
    s = jnp.where(mask[..., None, :, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("...hts,...shd->...thd", p / jnp.maximum(l, 1e-30),
                   v.astype(_F32))
    lse = jnp.where(l > 0, jnp.where(jnp.isfinite(m), m, 0.0)
                    + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
    return o, jnp.swapaxes(lse[..., 0], -1, -2)


def _window_part(q, k, v, window, scale, pallas, keep_ctx, interpret):
    """(oL `[B, T, H, D]`, lseL `[B, T, H]`): every window's own causal
    softmax, the windows folded into the batch."""
    B, T, H, D = q.shape
    fold = lambda x: x.reshape(B * (T // window), window, H, D)  # noqa: E731
    with jax.named_scope("eva_window"):
        if pallas:
            o, lse = flash_attention_lse(
                fold(q), fold(k), fold(v), causal=True, scale=scale,
                keep_ctx=keep_ctx, interpret=interpret)
        else:
            o, lse = _partial_xla(
                fold(q), fold(k), fold(v),
                jnp.tril(jnp.ones((window, window), bool)), scale)
        return o.reshape(B, T, H, D), lse.reshape(B, T, H)


def _stair_part(q, ks, vs, window, chunk, scale, pallas, keep_ctx, interpret):
    """(oR, lseR): every query against the summaries of the windows before
    its own."""
    B, T, H, D = q.shape
    per = window // chunk
    with jax.named_scope("eva_stair"):
        if pallas:
            return flash_attention_lse(
                q, ks, vs, scale=scale, stair=(window, per),
                keep_ctx=keep_ctx, interpret=interpret)
        seen = per * (jnp.arange(T) // window)
        return _partial_xla(
            q, ks, vs, jnp.arange(T // chunk)[None, :] < seen[:, None], scale)


# what joins the two parts, under a name of its own: a test adds them as two
# softmaxes to show what the comparison reads then
def _join(o_l, lse_l, o_r, lse_r):
    """(o float32, the summaries' share of every row's softmax): the two
    partial softmaxes as one, by the larger lse; the window's is finite (a
    query sees itself), so `lse_r = -inf` gives `o_l` and a share of 0."""
    m = jnp.maximum(lse_l, lse_r)
    w_l, w_r = jnp.exp(lse_l - m), jnp.exp(lse_r - m)
    total = w_l + w_r
    o = (w_l[..., None] * o_l.astype(_F32)
         + w_r[..., None] * o_r.astype(_F32)) / total[..., None]
    return o, w_r / total


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  impl: str = "auto", keep_ctx: bool = False,
                  interpret: bool = False) -> Tuple[jax.Array, dict]:
    """(o `[B, T, H, D]` in q's dtype, {eva_remote_mass, eva_chunk_entropy}):
    EVA attention of q, k, v `[B, T, H, D]` (already rotated) under `phi`,
    `mu` `[H, D]`; the module's docstring has the equations and the parts.
    The summaries are named `eva_summaries` and, with `keep_ctx`, the
    kernels' residuals `attn_ctx` (`jax.ad_checkpoint`). `interpret` runs the Pallas
    path in interpret mode, for tests."""
    B, T, H, D = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"eva attention takes as many key heads as query heads, of one "
            f"width: q {q.shape}, k {k.shape}, v {v.shape}")
    if window % chunk:
        raise ValueError(f"chunks of {chunk} do not divide a window of {window}")
    pallas = interpret or resolve_impl(impl) == "pallas"
    zero = jnp.zeros((), _F32)
    if T <= window:  # one window: nothing earlier to summarise
        with jax.named_scope("eva_window"):
            o = (flash_attention(q, k, v, causal=True, keep_ctx=keep_ctx,
                                 interpret=interpret)
                 if pallas else mha(q, k, v, causal=True, impl="xla",
                                    keep_ctx=keep_ctx))
        return o, {"eva_remote_mass": zero, "eva_chunk_entropy": zero}
    if T % window:
        raise ValueError(
            f"a window of {window} does not divide {T} tokens: a sequence is "
            "whole windows, or no longer than one")
    scale = 1.0 / math.sqrt(D)
    ks, vs = chunk_summaries(k, v, phi, mu, chunk=chunk, impl=impl,
                             interpret=interpret)
    # cheap to keep (a `chunk`-th of k and v) where a rematerialised block's
    # policy has the name: no second pass over k and v
    ks, vs = (checkpoint_name(x, "eva_summaries") for x in (ks, vs))
    o_l, lse_l = _window_part(q, k, v, window, scale, pallas, keep_ctx,
                              interpret)
    o_r, lse_r = _stair_part(q, ks, vs, window, chunk, scale, pallas,
                             keep_ctx, interpret)
    if keep_ctx and not pallas:  # the kernels name their own residuals
        o_l, lse_l, o_r, lse_r = (
            checkpoint_name(x, "attn_ctx") for x in (o_l, lse_l, o_r, lse_r))
    with jax.named_scope("eva_join"):
        o, remote = _join(o_l, lse_l, o_r, lse_r)
        readings = {
            "eva_remote_mass": jax.lax.stop_gradient(remote).mean(),
            "eva_chunk_entropy": chunk_entropy(k, phi, chunk),
        }
    return o.astype(q.dtype), readings
