"""ResNet (v1.5) in functional JAX — the Train benchmark model family.

Cells that train it (BENCHMARK.json): `resnet50.ingest` and
`resnet50.resident`, ResNet-50 v1.5 at 256 images of 224x224 a step.
Convs are NHWC (XLA's preferred TPU layout → MXU-tiled); batch norm carries
running stats in the state pytree; bf16 compute with f32 params/stats.

Data parallel: params replicated (or fsdp-sharded), batch split over
data/fsdp axes — handled by make_train_step-style sharding at the trainer
level (ray_tpu.train), not inside the model.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

_STAGES = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
    152: ((3, 8, 36, 3), True),
}


@dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16

    @property
    def stages(self) -> Sequence[int]:
        return _STAGES[self.depth][0]

    @property
    def bottleneck(self) -> bool:
        return _STAGES[self.depth][1]


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * (
        (2.0 / fan_in) ** 0.5
    )


def _bn_init(c):
    return {
        "scale": jnp.ones((c,), jnp.float32),
        "bias": jnp.zeros((c,), jnp.float32),
        "mean": jnp.zeros((c,), jnp.float32),
        "var": jnp.ones((c,), jnp.float32),
    }


def block_layout(cfg: ResNetConfig):
    """Static per-block structure: (stride, cin, base, cout) tuples."""
    layout = []
    cin = cfg.width
    for stage, n_blocks in enumerate(cfg.stages):
        base = cfg.width * (2 ** stage)
        cout = base * (4 if cfg.bottleneck else 1)
        for b in range(n_blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            layout.append((stride, cin, base, cout))
            cin = cout
    return layout


def resnet_init(rng, cfg: ResNetConfig) -> Dict[str, Any]:
    keys = iter(jax.random.split(rng, 2048))
    params: Dict[str, Any] = {
        "stem_conv": _conv_init(next(keys), 7, 7, 3, cfg.width),
        "stem_bn": _bn_init(cfg.width),
        "blocks": [],
    }
    for stride, cin, base, cout in block_layout(cfg):
        blk: Dict[str, Any] = {}
        if cfg.bottleneck:
            blk["conv1"] = _conv_init(next(keys), 1, 1, cin, base)
            blk["bn1"] = _bn_init(base)
            blk["conv2"] = _conv_init(next(keys), 3, 3, base, base)
            blk["bn2"] = _bn_init(base)
            blk["conv3"] = _conv_init(next(keys), 1, 1, base, cout)
            blk["bn3"] = _bn_init(cout)
        else:
            blk["conv1"] = _conv_init(next(keys), 3, 3, cin, base)
            blk["bn1"] = _bn_init(base)
            blk["conv2"] = _conv_init(next(keys), 3, 3, base, cout)
            blk["bn2"] = _bn_init(cout)
        if stride != 1 or cin != cout:
            blk["proj_conv"] = _conv_init(next(keys), 1, 1, cin, cout)
            blk["proj_bn"] = _bn_init(cout)
        params["blocks"].append(blk)
    final_c = block_layout(cfg)[-1][3]
    params["fc_w"] = jax.random.normal(
        next(keys), (final_c, cfg.num_classes), jnp.float32
    ) * (1.0 / final_c) ** 0.5
    params["fc_b"] = jnp.zeros((cfg.num_classes,), jnp.float32)
    return params


# The scopes (`conv`, `bn`, and `stem`, `stage1`..`stage4`, `head` in
# resnet_apply) name the step's device work in a profiler trace
# (docs/observability.md, "Device scopes"); they are metadata only.
@jax.named_scope("conv")
def _conv(x, w, stride=1, dtype=jnp.bfloat16):
    kh = w.shape[0]
    pad = kh // 2
    return jax.lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@jax.named_scope("bn")
def _bn(x, bn, train: bool, momentum=0.9, eps=1e-5):
    """Returns (y, new_stats). In train mode uses batch stats (the psum over
    data axes happens automatically because XLA sees the full sharded batch
    under jit — stats are computed on the global batch).

    Stats accumulate in f32; the normalization itself applies in the compute
    dtype (bf16) with the per-channel affine folded to a single scale+bias —
    ResNet training is HBM-bandwidth-bound on TPU, so activation-sized f32
    intermediates are the thing to avoid."""
    if train:
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=(0, 1, 2))
        var = xf.var(axis=(0, 1, 2))
        new = {
            "scale": bn["scale"], "bias": bn["bias"],
            "mean": momentum * bn["mean"] + (1 - momentum) * mean,
            "var": momentum * bn["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = bn["mean"], bn["var"]
        new = bn
    inv = jax.lax.rsqrt(var + eps)
    scale = (bn["scale"] * inv).astype(x.dtype)
    bias = (bn["bias"] - mean * bn["scale"] * inv).astype(x.dtype)
    return x * scale + bias, new


def resnet_apply(params, images, cfg: ResNetConfig, train: bool = False):
    """[B, H, W, 3] float images -> ([B, num_classes] f32 logits, new_params).

    new_params carries updated BN running stats when train=True (otherwise
    it aliases params).
    """
    dt = cfg.dtype
    new_params = {k: v for k, v in params.items() if k != "blocks"}
    with jax.named_scope("stem"):
        x = _conv(images, params["stem_conv"], stride=2, dtype=dt)
        x, new_params["stem_bn"] = _bn(x, params["stem_bn"], train)
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)],
        )
    new_blocks = []
    stage_of_block = [
        f"stage{i + 1}" for i, n in enumerate(cfg.stages) for _ in range(n)
    ]
    for blk, (stride, _, _, _), stage in zip(
        params["blocks"], block_layout(cfg), stage_of_block
    ):
        with jax.named_scope(stage):
            x, nblk = _block(x, blk, stride, cfg, train)
        new_blocks.append(nblk)
    new_params["blocks"] = new_blocks
    with jax.named_scope("head"):
        x = x.mean(axis=(1, 2)).astype(jnp.float32)  # global average pool
        logits = x @ params["fc_w"] + params["fc_b"]
    return logits, new_params


def _block(x, blk, stride, cfg: ResNetConfig, train: bool):
    """One residual block: (y, the block's params with new BN stats)."""
    dt = cfg.dtype
    nblk: Dict[str, Any] = {}
    shortcut = x
    if "proj_conv" in blk:
        shortcut = _conv(x, blk["proj_conv"], stride=stride, dtype=dt)
        shortcut, nblk["proj_bn"] = _bn(shortcut, blk["proj_bn"], train)
        nblk["proj_conv"] = blk["proj_conv"]
    if cfg.bottleneck:
        y = _conv(x, blk["conv1"], 1, dt)
        y, nblk["bn1"] = _bn(y, blk["bn1"], train)
        y = jax.nn.relu(y)
        y = _conv(y, blk["conv2"], stride, dt)
        y, nblk["bn2"] = _bn(y, blk["bn2"], train)
        y = jax.nn.relu(y)
        y = _conv(y, blk["conv3"], 1, dt)
        y, nblk["bn3"] = _bn(y, blk["bn3"], train)
    else:
        y = _conv(x, blk["conv1"], stride, dt)
        y, nblk["bn1"] = _bn(y, blk["bn1"], train)
        y = jax.nn.relu(y)
        y = _conv(y, blk["conv2"], 1, dt)
        y, nblk["bn2"] = _bn(y, blk["bn2"], train)
    for k in ("conv1", "conv2", "conv3"):
        if k in blk:
            nblk[k] = blk[k]
    return jax.nn.relu(y + shortcut), nblk
