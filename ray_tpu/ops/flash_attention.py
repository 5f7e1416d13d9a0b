"""Blocked (flash) attention as a Pallas TPU kernel.

Online-softmax attention tiled for the MXU: the grid walks (batch*heads,
q-block, k-block) with the k dimension innermost; running max/denominator and
the output accumulator live in VMEM scratch that persists across the k steps
and is flushed on the last one. f32 accumulation, bf16-friendly inputs.

Dispatch: `mha(impl="auto")` picks this kernel when JAX reports a TPU and an
XLA einsum implementation on any other platform (tests run the kernel in
interpret mode on tiny shapes via `flash_attention(..., interpret=True)`).

Backward pass uses recompute (custom_vjp re-derives the tile softmax),
trading FLOPs for the O(T^2) memory XLA would otherwise materialize.

The three `pallas_call`s are named `flash_fwd` (with or without the lse
output), `flash_bwd_dq` and `flash_bwd_dkv`: the names a profiler trace and
the compiled HLO show, and the ones the benchmark's per-kernel roofline
metrics read (docs/observability.md, "Device scopes").
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_BIG_NEG = -1e30


def _attn_fwd_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    acc_ref, m_ref, l_ref,  # VMEM scratch, persistent over the k grid dim
    *, block_q: int, block_k: int, num_k: int, scale: float, causal: bool,
    seq_q: int, seq_k: int,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _BIG_NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0].astype(jnp.float32)  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < seq_k  # padding keys past the true length
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, _BIG_NEG)

        m_prev = m_ref[...]  # [bq, 128] (lane-replicated)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)  # [bq, 128]
        p = jnp.exp(s - m_new[:, :1])  # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_prev * alpha + jnp.broadcast_to(
            p.sum(axis=-1, keepdims=True), l_prev.shape
        )
        m_ref[...] = m_new
        if seq_k % block_k:
            # Padded K/V rows may be NaN-filled; p is 0 there but 0*NaN=NaN.
            krow = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0
            )
            v = jnp.where(krow < seq_k, v, 0.0)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    if causal:
        # Blocks strictly above the diagonal contribute nothing; skip them.
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _body()
    else:
        _body()

    @pl.when(ki == num_k - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _attn_fwd_kernel_lse(
    q_ref, k_ref, v_ref,
    o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, num_k: int, scale: float, causal: bool,
    seq_q: int, seq_k: int,
):
    """Forward that additionally writes LSE = m + log(l) per q row — the
    residual the tiled backward needs to re-derive tile softmax without
    another online-max pass."""
    from jax.experimental import pallas as pl

    _attn_fwd_kernel(
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
        block_q=block_q, block_k=block_k, num_k=num_k, scale=scale,
        causal=causal, seq_q=seq_q, seq_k=seq_k,
    )
    ki = pl.program_id(2)

    @pl.when(ki == num_k - 1)
    def _flush_lse():
        # Per-q-row scalars must live on sublanes; the block's minor dim
        # must be 128-divisible OR equal the array dim, so an 8-wide
        # replicated minor axis is the cheapest legal layout (16x less HBM
        # than jax's own 128-wide l/m residuals).
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[0] = lse[:, :8]


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k, interpret,
               with_lse: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    num_q = pl.cdiv(T, block_q)
    num_k = pl.cdiv(S, block_k)

    kernel = functools.partial(
        _attn_fwd_kernel_lse if with_lse else _attn_fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        num_k=num_k,
        scale=scale,
        causal=causal,
        seq_q=T,
        seq_k=S,
    )
    out_shape = jax.ShapeDtypeStruct((BH, T, D), q.dtype)
    out_specs = pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0))
    if with_lse:
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((BH, T, 8), jnp.float32),
        ]
        out_specs = [
            out_specs,
            pl.BlockSpec((1, block_q, 8), lambda bh, qi, ki: (bh, qi, 0)),
        ]
    return pl.pallas_call(
        kernel,
        grid=(BH, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, with_lse=True,
    )
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, do):
    """Tiled FlashAttention-2 backward: two pallas kernels (dq; dk/dv), each
    re-deriving its softmax tile from (q, k, lse) — nothing O(T·S) ever
    touches HBM (the previous recompute path materialized full f32 score
    matrices through XLA, which both OOMed large batches and made the step
    bandwidth-bound)."""
    q, k, v, o, lse = res
    BH, T, _ = q.shape
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    # Same sublane-aligned [BH, T, 8] layout as lse.
    delta = jnp.broadcast_to(delta[..., None], (BH, T, 8))
    dq = _flash_bwd_dq(
        q, k, v, do, lse, delta, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dk, dv = _flash_bwd_dkv(
        q, k, v, do, lse, delta, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *,
              block_q, block_k, scale, causal, seq_q, seq_k):
    """Shared per-tile computation of both backward kernels: load + sanitize
    padded rows + re-derive the softmax tile. Returns (q, k, v, do, p, ds).

    Sanitizing at load matters: pallas pads partial blocks with arbitrary
    (possibly NaN) data, and a NaN anywhere in a dot input poisons the whole
    contraction even where the weight is 0."""
    q = q_ref[0].astype(jnp.float32)  # [bq, D]
    k = k_ref[0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0].astype(jnp.float32)  # [bk, D]
    do = do_ref[0].astype(jnp.float32)  # [bq, D]
    lse = lse_ref[0][:, :1]  # [bq, 1] (lane-replicated input)
    delta = delta_ref[0][:, :1]  # [bq, 1]
    if seq_q % block_q:
        qrow = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        qvalid = qrow < seq_q
        q = jnp.where(qvalid, q, 0.0)
        do = jnp.where(qvalid, do, 0.0)
        lse = jnp.where(qvalid, lse, 0.0)
        delta = jnp.where(qvalid, delta, 0.0)
    if seq_k % block_k:
        krow = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        )
        kvalid = krow < seq_k
        k = jnp.where(kvalid, k, 0.0)
        v = jnp.where(kvalid, v, 0.0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [bq, bk]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = (k_pos < seq_k) & (q_pos < seq_q)
    if causal:
        mask &= q_pos >= k_pos
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bk]
    # Explicit where: p=0 times a NaN dp entry would still poison the dot.
    ds = jnp.where(mask, p * (dp - delta) * scale, 0.0)  # [bq, bk]
    return q, k, v, do, p, ds


def _attn_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    acc_ref,
    *, block_q: int, block_k: int, num_k: int, scale: float, causal: bool,
    seq_q: int, seq_k: int,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        _, k, _, _, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            block_q=block_q, block_k=block_k, scale=scale, causal=causal,
            seq_q=seq_q, seq_k=seq_k,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _body()
    else:
        _body()

    @pl.when(ki == num_k - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, block_q: int, block_k: int, num_q: int, scale: float, causal: bool,
    seq_q: int, seq_k: int,
):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _body():
        q, _, _, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            block_q=block_q, block_k=block_k, scale=scale, causal=causal,
            seq_q=seq_q, seq_k=seq_k,
        )
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]

    if causal:
        # Only q blocks at/below the diagonal see this k block.
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _body()
    else:
        _body()

    @pl.when(qi == num_q - 1)
    def _flush():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_dq(q, k, v, do, lse, delta, *, causal, scale,
                  block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    num_q = pl.cdiv(T, block_q)
    num_k = pl.cdiv(S, block_k)
    kernel = functools.partial(
        _attn_bwd_dq_kernel,
        block_q=block_q, block_k=block_k, num_k=num_k, scale=scale,
        causal=causal, seq_q=T, seq_k=S,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)


def _flash_bwd_dkv(q, k, v, do, lse, delta, *, causal, scale,
                   block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    num_q = pl.cdiv(T, block_q)
    num_k = pl.cdiv(S, block_k)
    kernel = functools.partial(
        _attn_bwd_dkv_kernel,
        block_q=block_q, block_k=block_k, num_q=num_q, scale=scale,
        causal=causal, seq_q=T, seq_k=S,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, num_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)


def _xla_attention_bhtd(q, k, v, *, causal, scale):
    """Reference path on [BH, T, D] used for backward + non-TPU fallback."""
    s = jnp.einsum(
        "btd,bsd->bts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        T, S = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None], s, _BIG_NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(
    q, k, v,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Flash attention on [B, T, H, D] inputs (grouped-query: H_kv may divide H)."""
    B, T, H, D = q.shape
    Hk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B, T, H, D] -> [B*H, T, D]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], D)
    of = _flash(qf, kf, vf, causal, scale, block_q, block_k, interpret)
    return of.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def resolve_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU, 'xla' elsewhere, by the platform JAX
    reports. A backend that fails to start raises here: answering 'xla'
    instead would train on the einsum path and call it the flash step."""
    if impl == "auto":
        return "pallas" if jax.devices()[0].platform == "tpu" else "xla"
    return impl


def mha(q, k, v, *, causal: bool = False, scale: Optional[float] = None,
        impl: str = "auto"):
    """Multi-head attention dispatch on [B, T, H, D].

    impl: 'auto' (pallas on TPU, XLA elsewhere) | 'pallas' | 'xla'.
    """
    impl = resolve_impl(impl)
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, scale=scale)
    B, T, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], D)
    of = _xla_attention_bhtd(qf, kf, vf, causal=causal, scale=scale)
    return of.reshape(B, H, T, D).transpose(0, 2, 1, 3)
