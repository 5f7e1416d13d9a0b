"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

Alternative context-parallel scheme to ring attention: instead of rotating
K/V blocks, one `all_to_all` regathers the full sequence while splitting
heads across the axis, each device runs plain attention on its head subset,
and a second all_to_all restores sequence sharding. Better when
heads >= axis_size and ICI all-to-all bandwidth is plentiful; ring wins on
very long sequences (constant memory) — ship both, pick per workload.
"""

from __future__ import annotations

from typing import Callable, Optional

from ray_tpu.parallel.ring_attention import full_attention


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      attn_fn: Optional[Callable] = None):
    """Call inside shard_map. q/k/v: [B, T_local, H, D], sequence sharded
    over axis_name; H must be divisible by the axis size."""
    import jax

    if attn_fn is None:
        attn_fn = full_attention
    # [B, T/N, H, D] -> [B, T, H/N, D]
    q2 = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k2 = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v2 = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = attn_fn(q2, k2, v2, causal=causal)
    # [B, T, H/N, D] -> [B, T/N, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_on_group(q, k, v, causal: bool = False,
                               group_name: str = "default"):
    """Ulysses attention over an xla collective group's mesh with the
    group's compiled-program cache (see ring_attention_on_group)."""
    from ray_tpu.util.collective import get_group_collectives

    eng = get_group_collectives(group_name)
    if eng is None:
        raise ValueError(
            f"group {group_name!r} has no mesh engine (xla backend required)"
        )
    return eng.ulysses_attention(q, k, v, causal=causal)


def ulysses_attention_sharded(q, k, v, mesh, causal: bool = False,
                              seq_axis: str = "sequence",
                              batch_axes=("data", "fsdp")):
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    present = set(mesh.axis_names)
    if seq_axis not in present:
        return full_attention(q, k, v, causal=causal)
    b_ax = tuple(a for a in batch_axes if a in present) or None
    spec = P(b_ax, seq_axis, None, None)
    fn = functools.partial(ulysses_attention, axis_name=seq_axis, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
