#!/usr/bin/env python3
"""The Mamba-2 scan alone on the chip: `ops/ssd.py`'s kernels at each tile
of a group's heads.

    python3 benchmarks/ssd_alone.py [--shapes granite,nemotron] [--heads 8,16,32,64] [--seed 0]

At each shape (`granite`: the 32,768 tokens, 64 heads of 64 in ONE group of
B and C and chunks of 256 that a mixer of `granite4hmicro.longctx` hands the
scan; `nemotron`: `nemotron3nano.tokens8k`'s 2 x 8,192 tokens, 8 groups of 8
heads, chunks of 128), bf16 in, the step size and the decay over their
initialisers' ranges: the forward alone and the forward with the backward
of all six inputs under `jit`, the host's clock over 10 calls after one that
compiles, at each number of heads a tile of `--heads` that divides a
group's (the module's own choice, `head_tile`, first and marked); the
kernels' distance from the `jax.numpy` scan at a probe of 2,048 tokens
(whose `[b, n, H, Q, Q]` arrays fit), forward and every gradient. Prints one
JSON line a measurement and fails without a TPU: a CPU's time is not a
chip's.
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import ssd as lib  # noqa: E402

SHAPES = {"granite": dict(b=1, T=32768, H=64, P=64, G=1, N=128, Q=256),
          "nemotron": dict(b=2, T=8192, H=64, P=64, G=8, N=128, Q=128)}
PROBE_TOKENS = 2048
DT_RANGE = (1e-3, 0.1)   # `mamba_dt_init`'s, the published initialiser's
NAMES = "x dt A B C D".split()
_F32 = jnp.float32


def inputs(shape, seed, T=None, dtype=jnp.bfloat16):
    b, H, P, G, N = (shape[k] for k in "bHPGN")
    T = T or shape["T"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.exp(jax.random.uniform(
        ks[1], (b, T, H), _F32, *map(math.log, DT_RANGE)))
    return (jax.random.normal(ks[0], (b, T, H, P)).astype(dtype), dt,
            -jax.random.uniform(ks[2], (H,), _F32, 1.0, 16.0),
            jax.random.normal(ks[3], (b, T, G, N)).astype(dtype),
            jax.random.normal(ks[4], (b, T, G, N)).astype(dtype),
            jnp.ones((H,), _F32),
            jax.random.normal(ks[5], (b, T, H, P)))


def timed(fn, *args, calls=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def rel(a, b):
    a, b = a.astype(_F32), b.astype(_F32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def both(forward):
    def fn(weights, *a):
        def loss(*a):
            y = forward(*a)
            return jnp.sum(y.astype(_F32) * weights), y
        return jax.grad(loss, argnums=range(6), has_aux=True)(*a)
    return jax.jit(fn)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="granite,nemotron")
    parser.add_argument("--heads", default="8,16,32,64")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")

    for name in args.shapes.split(","):
        shape = SHAPES[name]
        Q, R = shape["Q"], shape["H"] // shape["G"]
        own = lib.head_tile(Q, shape["N"], R, shape["P"], 2)
        tiles = [own] + [h for h in map(int, args.heads.split(","))
                         if h != own and R % h == 0]
        *operands, weights = inputs(shape, args.seed)
        *probe, probe_weights = inputs(shape, args.seed, PROBE_TOKENS)
        want = both(lambda *a: lib._scan_numpy(*a, Q))(probe_weights, *probe)
        for heads in tiles:
            def forward(*a, heads=heads):
                return lib._scan_kernels(*a, Q, False, heads=heads)

            line = {"shape": name, **shape, "heads_a_tile": heads,
                    "the_module_s_own": heads == own}
            grads, y = both(forward)(probe_weights, *probe)
            line["probe_rel_diff_to_jax_numpy"] = {
                "y": rel(y, want[1]), **{
                    n: rel(a, b) for n, a, b in zip(NAMES, grads, want[0])}}
            line["forward_ms"] = round(timed(jax.jit(forward), *operands), 4)
            line["forward_backward_ms"] = round(
                timed(both(forward), weights, *operands), 4)
            print(json.dumps({**line, "device": device.device_kind}),
                  flush=True)


if __name__ == "__main__":
    main()
