#!/usr/bin/env python3
"""The flash kernels under a staircase alone on the chip, at the two cells'
shapes: `ops/flash_attention.py`'s forward and backward with `stair=(span,
per)` (query `i` sees the first `per * (i // span)` keys), folded as the
entries hand them over, with the tile `flash_tiles` chose.

    python3 benchmarks/stair_alone.py [--shapes sdar,eva] [--calls 10] [--seed 0]

`sdar`: `sdar.tokens16k`'s layer, both halves' 64 query heads of 128 at
16,384 queries against the clean half's 4 key-value heads at 16,384 keys,
steps of 4 (a group of 16 a key-value head; every tile on the diagonal takes
the in-tile mask). `eva`: `evabyte.tokens8k`'s, 32 heads at 8,192 queries
against 512 chunk summaries, steps of 2,048 queries and 128 keys (tiles
whole or empty). bf16. For the forward (`_flash_fwd` with lse) and the
backward (`_flash_backward`: delta and the kernels the plan takes): the
kernels and their tiles, ms a call by the host's clock over `--calls` calls
after one that compiles, the operations and bytes the call needs
(`chipbench/sdar_flops.py` `stair_call`, `chipbench/evabyte_flops.py`
`flash_call`), the least time a v5e could take (the larger of operations
over 197 TFLOP/s and bytes over 819 GB/s) and the share of it that is.
Every operand is made in the layout it is timed in. Prints one JSON line a
measurement and fails without a TPU: a CPU's time is not a chip's.
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import evabyte_flops, flops, kernel_flops, sdar_flops  # noqa: E402

# the module: `ray_tpu.ops.flash_attention` by attribute is its function
fa = importlib.import_module("ray_tpu.ops.flash_attention")

D = 128
# BH query rows of T against BHk key-value rows of S, under `stair`
SHAPES = {
    "sdar": dict(BH=64, BHk=4, T=16384, S=16384, stair=(4, 4)),
    "eva": dict(BH=32, BHk=32, T=8192, S=512, stair=(2048, 128)),
}


def counted(name, kernel):
    """(operations, bytes) of one call of `kernel` at the shape `name`."""
    if name == "sdar":
        return sdar_flops.stair_call(kernel, 1, 32, 4, 16384, 4, D, D)
    return evabyte_flops.flash_call(
        kernel, 1, 32, 8192, 512, evabyte_flops.stair_pairs(8192, 2048, 16), D)


def timed(fn, *args, calls):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    peaks = flops.peaks_for(device.device_kind)
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        BH, BHk, T, S, stair = (shape[k] for k in ("BH", "BHk", "T", "S",
                                                    "stair"))
        how = dict(causal=False, window=None, stair=stair, scale=D ** -0.5,
                   block_q=None, block_k=None, interpret=False)
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q = jax.random.normal(ks[0], (BH, T, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (BHk, S, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (BHk, S, D), jnp.bfloat16)
        do = jax.random.normal(ks[3], (BH, T, D), jnp.bfloat16)
        forward = jax.jit(lambda q, k, v: fa._flash_fwd(
            q, k, v, with_lse=True, **how))
        backward = jax.jit(lambda q, k, v, o, lse, do: fa._flash_backward(
            q, k, v, o, lse, do, **how))
        o, lse = forward(q, k, v)
        group = BH // BHk
        kernels = fa.flash_bwd_kernels(T, S, D, jnp.bfloat16, causal=False,
                                       group=group, stair=stair)
        for use, fn, operands, names in (
                ("forward", forward, (q, k, v), ("flash_fwd",)),
                ("backward", backward, (q, k, v, o, lse, do), kernels)):
            ms = timed(fn, *operands, calls=args.calls)
            ops, moved = map(sum, zip(*(counted(name, n) for n in names)))
            least, bound = kernel_flops.least_seconds(ops, moved, peaks)
            tiles = {n: fa.flash_tiles(n, T, S, D, jnp.bfloat16, causal=False,
                                       group=group, stair=stair)
                     for n in names}
            print(json.dumps({
                "shape": name, "use": use, "stair": stair,
                "kernels": {n: {"tile": [t.block_q, t.block_k],
                                "grid_steps_a_row": t.grid_steps,
                                "with_a_body": round(t.active_share, 4),
                                "vmem_bytes": t.vmem_bytes, "exit": t.exit}
                            for n, t in tiles.items()},
                "ms_a_call": round(ms, 4), "operations": ops, "bytes": moved,
                "least_ms": round(1e3 * least, 4), "bound": bound,
                "roofline_percent": round(100 * 1e3 * least / ms, 2),
                "device": device.device_kind}), flush=True)


if __name__ == "__main__":
    main()
