"""Device-mesh construction and sharding-rule helpers.

The TPU-native replacement for the reference's process-group bootstrap
(python/ray/train/torch/config.py:65-147 builds NCCL groups; here parallelism
is expressed as axes of one jax.sharding.Mesh and XLA inserts the collectives
over ICI). Canonical axis names follow the scaling-book convention:

    data      — pure data parallelism (gradient psum)
    fsdp      — data parallelism with sharded params/optimizer (ZeRO-3)
    tensor    — megatron-style tensor parallelism within attention/mlp
    sequence  — context parallelism (ring attention / all-to-all)
    expert    — MoE expert parallelism: a routed layer's experts are cut
                over it, whole experts a device; a batch axis, and where
                the mesh has no fsdp axis the other parameters' as well

Any subset may be present; size-1 axes are free, so one codepath serves
single-chip through multi-pod.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DATA, FSDP, TENSOR, SEQUENCE, EXPERT = "data", "fsdp", "tensor", "sequence", "expert"
CANONICAL_ORDER = (DATA, FSDP, EXPERT, SEQUENCE, TENSOR)
# the axes a batch is split over, in the mesh's order
BATCH_AXES = (DATA, FSDP, EXPERT)


@dataclass
class MeshSpec:
    """Declarative mesh: axis name -> size. One axis may be -1 (inferred).

    Axis order matters on hardware: later axes are placed on
    faster/closer device groups (tensor innermost => tensor-parallel
    collectives ride the shortest ICI hops).
    """

    axes: Dict[str, int] = field(default_factory=dict)

    def resolve(self, num_devices: int) -> Dict[str, int]:
        axes = dict(self.axes)
        unknown = [k for k, v in axes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = math.prod(v for v in axes.values() if v != -1)
        if unknown:
            if num_devices % known:
                raise ValueError(
                    f"cannot infer {unknown[0]}: {num_devices} % {known} != 0"
                )
            axes[unknown[0]] = num_devices // known
        if math.prod(axes.values()) != num_devices:
            raise ValueError(
                f"mesh {axes} does not cover {num_devices} devices"
            )
        return axes


def make_mesh(
    axes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence] = None,
):
    """Build a jax.sharding.Mesh from an axis spec over the given devices
    (defaults to all). `axes=None` -> pure data-parallel mesh.

    Canonical axes (data/fsdp/expert/sequence/tensor) are ALWAYS laid out in
    CANONICAL_ORDER regardless of dict order, so tensor/sequence collectives
    ride the innermost (fastest) ICI groups; non-canonical axis names keep
    their given order, outermost. Pass a pre-shaped `jax.sharding.Mesh`
    directly to downstream APIs if full manual control over device placement
    is needed."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if axes is None:
        axes = {DATA: len(devices)}
    resolved = MeshSpec(dict(axes)).resolve(len(devices))
    # Canonical placement: known axes ordered so tensor/sequence land
    # innermost (fastest ICI); unknown axes keep user order, outermost.
    resolved = dict(sorted(
        resolved.items(),
        key=lambda kv: CANONICAL_ORDER.index(kv[0])
        if kv[0] in CANONICAL_ORDER else -1,
    ))
    names = tuple(resolved.keys())
    shape = tuple(resolved.values())
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def data_parallel_spec(mesh) -> "jax.sharding.PartitionSpec":  # noqa: F821
    from jax.sharding import PartitionSpec as P

    batch_axes = [a for a in BATCH_AXES if a in mesh.axis_names]
    return P(tuple(batch_axes) if batch_axes else None)


def batch_sharding(mesh):
    """NamedSharding for a [batch, ...] input: batch split over data-like axes."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, data_parallel_spec(mesh))


# ---------------------------------------------------------------------------
# Logical-axis sharding rules (t5x/flax style): map parameter pytree paths to
# PartitionSpecs by matching logical axis names.
# ---------------------------------------------------------------------------


@dataclass
class ShardingRules:
    """Rules mapping logical array axes to mesh axes.

    e.g. rules = ShardingRules({"embed": "fsdp", "mlp": "tensor",
                                "heads": "tensor", "batch": ("data", "fsdp")})
    """

    rules: Dict[str, Optional[object]] = field(default_factory=dict)

    def spec(self, logical_axes: Sequence[Optional[str]]):
        """A mesh axis names one dimension of an array: where two logical
        axes of one array map to the same, the first keeps it (a routed
        layer's `experts` take `expert`, so their `embed` stays whole)."""
        from jax.sharding import PartitionSpec as P

        dims = []
        for logical in logical_axes:
            axes = self.rules.get(logical) if logical else None
            dims.append(None if axes in dims else axes)
        return P(*dims)

    def sharding(self, mesh, logical_axes: Sequence[Optional[str]]):
        from jax.sharding import NamedSharding

        return NamedSharding(mesh, self.spec(logical_axes))


# Default rules for transformer-family models: params shard over fsdp+tensor,
# activations over data+sequence.
def default_transformer_rules(mesh) -> ShardingRules:
    names = mesh.axis_names
    has = lambda a: a in names

    def ax(*prefs):
        got = [p for p in prefs if has(p)]
        if not got:
            return None
        return got[0] if len(got) == 1 else tuple(got)

    return ShardingRules(
        {
            "batch": ax(*BATCH_AXES),
            # over `expert` only where it is the mesh's one sharded axis
            "embed": ax(FSDP) or ax(EXPERT),
            "mlp": ax(TENSOR),
            "experts": ax(EXPERT),
            "heads": ax(TENSOR),
            "kv": None,
            "vocab": ax(TENSOR),
            "seq": ax(SEQUENCE),
        }
    )


def expert_axis(mesh) -> Optional[str]:
    """`expert` where `mesh` cuts a routed layer's experts over several
    devices, else None. What runs under `shard_map` over that axis (the
    routed layer's exchange, the head's chunks) is written for a mesh whose
    other axes are single devices, so the axis's size is then the mesh's: a
    second axis of several devices beside it is not mapped yet."""
    if mesh is None or mesh.shape.get(EXPERT, 1) == 1:
        return None
    if mesh.size != mesh.shape[EXPERT]:
        raise NotImplementedError(
            f"a step over the mesh {dict(mesh.shape)}: an `{EXPERT}` axis of "
            "several devices is mapped alone, not with a second axis of "
            "several devices beside it")
    return EXPERT


def shard_pytree(tree, mesh, spec_fn):
    """device_put every leaf with the sharding from spec_fn(path, leaf)."""
    import jax

    def place(path, leaf):
        return jax.device_put(leaf, spec_fn(path, leaf))

    return jax.tree_util.tree_map_with_path(place, tree)


def fsdp_sharding_for_leaf(mesh, leaf):
    """Default ZeRO-3 rule: shard the largest divisible axis over fsdp."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if FSDP not in mesh.axis_names:
        return NamedSharding(mesh, P())
    n = mesh.shape[FSDP]
    shape = getattr(leaf, "shape", ())
    if not shape:
        return NamedSharding(mesh, P())
    # Largest axis divisible by the fsdp size, preferring the first.
    candidates = [i for i, d in enumerate(shape) if d % n == 0 and d >= n]
    if not candidates:
        return NamedSharding(mesh, P())
    axis = max(candidates, key=lambda i: shape[i])
    spec = [None] * len(shape)
    spec[axis] = FSDP
    return NamedSharding(mesh, P(*spec))
