"""Ring attention: exact attention over a sequence-sharded axis.

Long-context capability the reference delegates to integrations (SURVEY §5:
Ray itself ships none; vLLM/DeepSpeed examples provide it). Here it is a
first-class primitive: K/V blocks rotate around the `sequence` mesh axis via
`ppermute` while each device keeps its Q shard, accumulating exact softmax
attention with the online (flash-style) max/sum recurrence. Communication
rides ICI neighbor hops — the canonical TPU pattern.

Layout inside shard_map: q, k, v are local shards [B, T_local, H, D] where the
global sequence is sharded over `axis_name` (N devices). Differentiable
(scan + ppermute both have transpose rules); wrap the caller in
jax.checkpoint to trade recompute for memory on long sequences.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

_BIG_NEG = -1e30


def ring_attention(q, k, v, axis_name: str, axis_size: int, causal: bool = False,
                   scale: Optional[float] = None, pvary_axes=None):
    """Exact attention across a ring. Call inside shard_map.

    Args:
      q, k, v: [B, T_local, H, D] local shards (sequence axis sharded).
      axis_name: mesh axis carrying the sequence shards.
      axis_size: static number of devices on that axis (mesh.shape[axis]).
      causal: apply causal masking in GLOBAL sequence positions.
    Returns:
      [B, T_local, H, D] attention output for the local Q block.
    """
    import jax
    import jax.numpy as jnp

    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * T + jnp.arange(T)  # [T] global positions of our queries

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # Mark the accumulators as varying over every manual mesh axis so the
    # scan carry type is stable under shard_map's varying-axes checks.
    axes = tuple(pvary_axes) if pvary_axes else (axis_name,)

    def varying(x):
        return jax.lax.pcast(x, axes, to="varying")

    o0 = varying(jnp.zeros((B, H, T, D), dtype=jnp.float32))
    m0 = varying(jnp.full((B, H, T), _BIG_NEG, dtype=jnp.float32))
    l0 = varying(jnp.zeros((B, H, T), dtype=jnp.float32))

    def step(carry, s):
        o, m, l, k_cur, v_cur = carry
        src = (idx - s) % axis_size  # which shard's K/V we hold this step
        k_pos = src * T + jnp.arange(T)
        # scores: [B, H, T, S]
        scores = jnp.einsum(
            "bthd,bshd->bhts", q.astype(jnp.float32), k_cur.astype(jnp.float32)
        ) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [T, S]
            scores = jnp.where(mask[None, None], scores, _BIG_NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p, v_cur.astype(jnp.float32)
        )
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, m_new, l, k_next, v_next), None

    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T, H, D]


def ring_attention_sharded(q, k, v, mesh, causal: bool = False,
                           seq_axis: str = "sequence",
                           batch_axes=("data", "fsdp"),
                           head_axis: str = "tensor"):
    """Global-view wrapper: q/k/v are [B, T, H, D] jax.Arrays; sequence is
    sharded over `seq_axis`, heads optionally over `head_axis`."""
    import jax
    from jax.sharding import PartitionSpec as P

    present = set(mesh.axis_names)
    b_ax = tuple(a for a in batch_axes if a in present) or None
    h_ax = head_axis if head_axis in present else None
    s_ax = seq_axis if seq_axis in present else None
    if s_ax is None:
        return full_attention(q, k, v, causal=causal)
    spec = P(b_ax, s_ax, h_ax, None)
    axis_size = mesh.shape[s_ax]
    manual_axes = []
    for part in (b_ax, s_ax, h_ax):
        if part is None:
            continue
        if isinstance(part, tuple):
            manual_axes.extend(part)
        else:
            manual_axes.append(part)

    fn = functools.partial(
        ring_attention,
        axis_name=s_ax,
        axis_size=axis_size,
        causal=causal,
        pvary_axes=tuple(manual_axes),
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ring_attention_on_group(q, k, v, causal: bool = False,
                            group_name: str = "default"):
    """Ring attention over an xla collective group's mesh: the shard_map
    program is compiled once per (shape, dtype, causal) and cached on the
    group's MeshCollectives engine, so repeated calls skip retracing
    entirely. q/k/v: [B, T, H, D] with T sharded over the group axis."""
    from ray_tpu.util.collective import get_group_collectives

    eng = get_group_collectives(group_name)
    if eng is None:
        raise ValueError(
            f"group {group_name!r} has no mesh engine (xla backend required)"
        )
    return eng.ring_attention(q, k, v, causal=causal)


def full_attention(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Plain (unsharded) softmax attention; reference for tests and the
    no-sequence-axis fallback. Shapes [B, T, H, D]."""
    import jax.numpy as jnp

    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        S = k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(mask[None, None], scores, _BIG_NEG)
    import jax

    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
