"""Plain reference of ResNet v1.5 (arXiv:1512.03385, with the stride of a
stage's first block on its 3x3 convolution, as in the MLPerf Training
reference): 7x7/2 stem, 3x3/2 max pool, bottleneck blocks of 1x1, 3x3, 1x1
convolutions each followed by batch norm, a projection shortcut where the
shape changes, global average pool and a linear classifier; the loss is the
mean cross-entropy. Training mode: batch norm uses the batch's own mean and
(biased) variance. Everything is float32 at the highest precision.

Parameters use the program's layout (`resnet_init`): NHWC activations, HWIO
kernels, per-block dicts with `conv1..3`, `bn1..3`, `proj_conv`, `proj_bn`.
Input images are uint8 and are scaled to [-1, 1] here, as the family's step
does on the device.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _conv(x, w, stride):
    pad = w.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, jnp.asarray(w, jnp.float32), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def _bn(x, bn, eps=1e-5):
    mean = x.mean(axis=(0, 1, 2))
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * bn["scale"] + bn["bias"]


def loss(params: Dict[str, Any], batch: Dict[str, Any], config: Dict[str, Any]):
    size = config["image_size"]
    with jax.default_matmul_precision("highest"):
        x = batch["image"].reshape(-1, size, size, 3).astype(jnp.float32)
        x = x / 127.5 - 1.0
        x = jax.nn.relu(_bn(_conv(x, params["stem_conv"], 2), params["stem_bn"]))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        first_of_stage = set()
        n = 0
        for stage, blocks in enumerate(config["stage_blocks"]):
            if stage > 0:
                first_of_stage.add(n)
            n += blocks
        for i, blk in enumerate(params["blocks"]):
            stride = 2 if i in first_of_stage else 1
            shortcut = x
            if "proj_conv" in blk:
                shortcut = _bn(_conv(x, blk["proj_conv"], stride), blk["proj_bn"])
            y = jax.nn.relu(_bn(_conv(x, blk["conv1"], 1), blk["bn1"]))
            y = jax.nn.relu(_bn(_conv(y, blk["conv2"], stride), blk["bn2"]))
            y = _bn(_conv(y, blk["conv3"], 1), blk["bn3"])
            x = jax.nn.relu(y + shortcut)
        x = x.mean(axis=(1, 2))
        logits = x @ jnp.asarray(params["fc_w"], jnp.float32) + params["fc_b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        labels = batch["label"].astype(jnp.int32)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
