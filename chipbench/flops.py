"""Operations a model needs for one image or one token of training, from the
configuration's shapes alone, and the chip's peaks. The benchmark's own copy:
the program's arithmetic (`ray_tpu.models.transformer.flops_per_token`) may
change, the yardstick may not.

Convention, for both families: a multiply-add is 2 operations (as in the
chip's quoted peak), the backward pass costs twice the forward, recomputed
work (remat, the flash kernel's backward recompute) is not counted.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))

# ResNet stage depths: (blocks per stage, bottleneck?)
_RESNET_STAGES = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
    152: ((3, 8, 36, 3), True),
}


def peaks_for(device_kind: str, path: str = os.path.join(_HERE, "peaks.json")
              ) -> Dict[str, float]:
    """The published peaks of one chip of this kind. A kind that is not in
    the table is an error, never another chip's numbers."""
    with open(path) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}; add it to "
            f"{path} with its source"
        )
    return table[device_kind]


def resnet_convs(config: Dict[str, Any]) -> List[Tuple[int, int, int, int, int]]:
    """Every convolution of ResNet v1.5 as (out_side, kernel, cin, cout, n):
    the stem, then per block conv1..3 (stride on the 3x3) and the projection
    on a stage's first block."""
    blocks, bottleneck = _RESNET_STAGES[config["depth"]]
    width, side = config["width"], config["image_size"]
    side = side // 2  # 7x7 stride-2 stem
    convs = [(side, 7, 3, width, 1)]
    side = side // 2  # 3x3 stride-2 max pool
    cin = width
    for stage, n_blocks in enumerate(blocks):
        base = width * 2 ** stage
        cout = base * (4 if bottleneck else 1)
        for b in range(n_blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            out = side // stride
            if bottleneck:
                convs += [(side, 1, cin, base, 1), (out, 3, base, base, 1),
                          (out, 1, base, cout, 1)]
            else:
                convs += [(out, 3, cin, base, 1), (out, 3, base, cout, 1)]
            if stride != 1 or cin != cout:
                convs.append((out, 1, cin, cout, 1))
            side, cin = out, cout
    return convs


def resnet_flops_per_image(config: Dict[str, Any]) -> float:
    """Forward and backward operations of the convolutions and the classifier
    for one image. Batch norm, ReLU and pooling are bandwidth, not counted."""
    fwd = sum(2 * side * side * k * k * cin * cout * n
              for side, k, cin, cout, n in resnet_convs(config))
    blocks, bottleneck = _RESNET_STAGES[config["depth"]]
    final = config["width"] * 8 * (4 if bottleneck else 1)
    fwd += 2 * final * config["num_classes"]
    return 3.0 * fwd


def transformer_ff(config: Dict[str, Any]) -> int:
    if config.get("d_ff") is not None:
        return int(config["d_ff"])
    return int(8 * config["d_model"] / 3 + 127) // 128 * 128


def transformer_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations for one token of a sequence of
    `seq_len`: the block matmuls, causal attention credited at the
    (seq_len + 1) / 2 keys an average query sees (the flash kernel skips the
    masked tiles, so crediting seq_len would overcount twofold), and the
    output head."""
    d, f = config["d_model"], transformer_ff(config)
    h = config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    per_layer = 2 * d * (h * dh + 2 * hk * dh) + 2 * h * dh * d + 2 * 3 * d * f
    attn = 2 * 2 * h * dh * ((seq_len + 1) / 2)
    head = 2 * d * config["vocab_size"]
    return 3.0 * (config["n_layers"] * (per_layer + attn) + head)


def transformer_param_count(config: Dict[str, Any]) -> int:
    d, f = config["d_model"], transformer_ff(config)
    h = config["n_heads"]
    hk = config.get("n_kv_heads") or h
    dh = d // h
    per_layer = d * (h * dh + 2 * hk * dh) + h * dh * d + 3 * d * f + 2 * d
    n = config["vocab_size"] * d + config["n_layers"] * per_layer + d
    if not config.get("tied_embeddings", True):
        n += d * config["vocab_size"]
    return n
