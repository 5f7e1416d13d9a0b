#!/usr/bin/env python3
"""The Mamba-2 mixer's two bandwidth passes, and the KDA mixer's short
convolutions and its output norm and gate, alone on the chip:
`ops/mamba_passes.py`'s kernels against its `jax.numpy` paths.

    python3 benchmarks/mamba_passes_alone.py [--shapes cell,probe,granite,kimi,solar] [--passes conv,norm,kda_conv,kda_out_norm] [--paths xla,pallas] [--conv-blocks 512x512x32,...] [--norm-blocks 256x32,...] [--out-norm-blocks 512x1024x32,...] [--seed 0]

At each shape (`cell`: the 2 x 8,192 tokens a mixer of
`nemotron3nano.tokens8k` hands them, the convolution over 6,144 channels of 4
taps split 4,096 / 1,024 / 1,024, the norm over 8 groups of 512, bf16;
`probe`: 2,048 tokens a row; `kimi` and `solar`, the KDA mixer's passes
alone: `kda_conv` (PR 67), a KDA layer's three streams q, k and v as
`kimilinear.tokens16k` and `solaropen2.tokens8k` hand them, `[1, 16384,
4096]` and `[1, 8192, 1024]`, 4 taps each, q's and k's heads of 128 then at
unit length: three calls, as `models/transformer.py` `_kda_mixer` makes
them; `kda_out_norm` (PR 69), the recurrence's o and the gate's
pre-activation at those shapes through `group_rmsnorm_gated`, 32 and 8
heads of 128: one call) and for each pass: the forward alone and the
forward with the backward of every input (`jax.vjp` under one `jit`), the
host's clock over 10 calls after one that compiles; the bytes the
mathematics has to move (each operand read once and each result written
once; with the backward the inputs read again, the cotangents read and the
inputs' written) and the share of HBM's rate that is; and the distance of
the kernels' results and gradients from the `jax.numpy` path's. The kernels
are timed at each `tokens x channels x tokens-a-trip` of `--conv-blocks`
and `tokens x tokens-a-trip` of `--norm-blocks`, and `tokens x channels x
tokens-a-trip` of `--out-norm-blocks` (the module's own choice first, and
marked). Prints one JSON line a measurement and fails without a
TPU: a CPU's time is not a chip's.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import mamba_passes as lib  # noqa: E402

SHAPES = {
    "cell": dict(B=2, T=8192, splits=(4096, 1024, 1024), taps=4, groups=8),
    "probe": dict(B=2, T=2048, splits=(4096, 1024, 1024), taps=4, groups=8),
    # `granite4hmicro.longctx`: one group of B and C, the norm over 4,096
    "granite": dict(B=1, T=32768, splits=(4096, 128, 128), taps=4, groups=1),
    # a KDA layer's streams: `splits` the three calls' widths
    "kimi": dict(B=1, T=16384, splits=(4096,) * 3, taps=4, unit=128),
    "solar": dict(B=1, T=8192, splits=(1024,) * 3, taps=4, unit=128),
}
EPS = 1e-5
HBM_BYTES_PER_S = 819e9  # a v5e's, as `chipbench/peaks.json` has it


def conv_inputs(shape, seed, dtype=jnp.bfloat16):
    """((x, w, bias), a cotangent a split) as the mixer hands them at the
    start of training: `x` at unit scale, the taps at `1 / sqrt(taps)`."""
    B, T, taps, splits = (shape[k] for k in ("B", "T", "taps", "splits"))
    C = sum(splits)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3 + len(splits))
    return ((jax.random.normal(ks[0], (B, T, C)).astype(dtype),
             (jax.random.normal(ks[1], (taps, C)) / taps ** 0.5).astype(dtype),
             0.1 * jax.random.normal(ks[2], (C,))),
            tuple(jax.random.normal(k, (B, T, width)).astype(dtype)
                  for k, width in zip(ks[3:], splits)))


def kda_conv_inputs(shape, seed, dtype=jnp.bfloat16):
    """((q, k, v, the three streams' taps), a cotangent a stream)."""
    (x, w, _), cts = conv_inputs(shape, seed, dtype)
    return ((*jnp.split(x, 3, axis=-1),
             jnp.stack(jnp.split(w, 3, axis=-1))), cts)


def kda_conv(q, k, v, taps, *, unit, path):
    return tuple(
        lib.causal_conv_silu(s, taps[i], unit=unit * (i < 2),
                             name="kda_conv", impl=path)
        for i, s in enumerate((q, k, v)))


def kda_out_norm_inputs(shape, seed, dtype=jnp.bfloat16):
    """((o, the gate's pre-activation, its bias, the heads' one scale), a
    cotangent)."""
    (o, z, weight), ct = norm_inputs(shape, seed, dtype)
    bias = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), (o.shape[-1],))
    return (o, z, bias, weight[:shape["unit"]]), ct


def norm_inputs(shape, seed, dtype=jnp.bfloat16):
    B, T, inner = shape["B"], shape["T"], shape["splits"][0]
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    return ((jax.random.normal(ks[0], (B, T, inner)).astype(dtype),
             jax.random.normal(ks[1], (B, T, inner)).astype(dtype),
             1.0 + 0.1 * jax.random.normal(ks[2], (inner,))),
            jax.random.normal(ks[3], (B, T, inner)).astype(dtype))


def needed_bytes(name, shape, backward: bool, item: int = 2) -> int:
    """The convolution: x read, the splits written; with the backward x
    read again, the cotangents read, dx written. The norm: y and z read,
    the result written; with the backward y, z and the cotangent read, dy
    and dz written. The parameters' few KB are left out."""
    B, T, splits = shape["B"], shape["T"], shape["splits"]
    norm = name in ("norm", "kda_out_norm")  # else a convolution
    width = splits[0] if norm else sum(splits)
    arrays = (3, 5) if norm else (2, 3)
    return (arrays[0] + backward * arrays[1]) * B * T * width * item


def timed(fn, *args, calls=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def blocks(text):
    return [tuple(int(x) for x in one.split("x")) for one in text.split(",")
            if one]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="cell")
    parser.add_argument("--passes", default="conv,norm")
    parser.add_argument("--paths", default="xla,pallas")
    parser.add_argument("--conv-blocks", default="")
    parser.add_argument("--norm-blocks", default="")
    parser.add_argument("--out-norm-blocks", default="")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    # the module's constants a pass's blocks are read from, in the order a
    # `--conv-blocks` or `--norm-blocks` entry names them
    knobs = {"conv": ("_CONV_TOKENS", "_CONV_CHANNELS", "_ROWS"),
             "norm": ("_NORM_TOKENS", "_ROWS"),
             "kda_out_norm": ("_GATED_TOKENS", "_GATED_LANES", "_GATED_ROWS")}
    knobs["kda_conv"] = knobs["conv"]
    inputs = {"conv": conv_inputs, "norm": norm_inputs,
              "kda_conv": kda_conv_inputs,
              "kda_out_norm": kda_out_norm_inputs}
    own = {name: tuple(getattr(lib, k) for k in names)
           for name, names in knobs.items()}
    swept = {"conv": blocks(args.conv_blocks), "norm": blocks(args.norm_blocks),
             "kda_out_norm": blocks(args.out_norm_blocks)}
    swept["kda_conv"] = swept["conv"]

    def take(name, block):
        for knob, value in zip(knobs[name], block):
            setattr(lib, knob, value)

    for shape_name in args.shapes.split(","):
        shape = SHAPES[shape_name]
        for name in args.passes.split(","):
            if name.startswith("kda_") != ("unit" in shape):
                continue  # a KDA layer's shapes take its pass alone
            operands, cts = inputs[name](shape, args.seed)
            runs = [(path, block) for path in args.paths.split(",")
                    for block in ([own[name]] if path != "pallas" else
                                  [own[name]] + [b for b in swept[name]
                                                 if b != own[name]])]
            first = {}
            for path, block in runs:
                take(name, block)
                if name == "conv":
                    def forward(*a, path=path):
                        return lib.causal_conv_silu(
                            *a, splits=shape["splits"], impl=path)
                elif name == "kda_conv":
                    def forward(*a, path=path):
                        return kda_conv(*a, unit=shape["unit"], path=path)
                elif name == "kda_out_norm":
                    def forward(*a, path=path):
                        return lib.group_rmsnorm_gated(
                            *a, shape["splits"][0] // shape["unit"], EPS,
                            impl=path)
                else:
                    def forward(*a, path=path):
                        return lib.gated_group_rmsnorm(
                            *a, shape["groups"], EPS, impl=path)

                def both(cts, *a, forward=forward):
                    out, pull = jax.vjp(forward, *a)
                    return out, pull(cts)

                for use, fn, given in (
                        ("forward", forward, operands),
                        ("forward_backward", both, (cts, *operands))):
                    fn = jax.jit(fn)
                    ms = timed(fn, *given)
                    moved = needed_bytes(name, shape, use != "forward")
                    line = {"shape": shape_name, "pass": name, "use": use,
                            "path": path, "ms_a_call": round(ms, 4),
                            "needed_bytes": moved,
                            "hbm_rate_share": round(
                                moved / (ms * 1e-3) / HBM_BYTES_PER_S, 4)}
                    if path == "pallas":
                        line["blocks"] = "x".join(map(str, block))
                        line["the_module_s_own"] = block == own[name]
                    if use == "forward_backward":
                        got = jax.tree.leaves(fn(*given))
                        line["finite"] = all(
                            bool(jnp.isfinite(x).all()) for x in got)
                        if path == runs[0][0]:
                            first = got
                        else:
                            line["rel_diff_to_" + runs[0][0]] = [
                                round(rel(a, b), 6)
                                for a, b in zip(got, first)]
                    print(json.dumps(
                        {**line, "device": device.device_kind}), flush=True)
            take(name, own[name])


if __name__ == "__main__":
    main()
