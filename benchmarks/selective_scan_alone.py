#!/usr/bin/env python3
"""Mamba-1's selective scan alone on the chip: `ops/selective_scan.py`'s
kernels against its `jax.numpy` chunked path.

    python3 benchmarks/selective_scan_alone.py [--shapes cell,probe] [--paths chunked,pallas] [--blocks 128,256,512,1024] [--seed 0]

At each shape (`cell`: the 16,384 tokens, 5,120 channels and 16 states a
Mamba-1 layer of `phi4flash.tokens16k` hands the scan, chunks of 128, bf16
in; `probe`: the 4,096 tokens of the benchmark's `scan_rel_err`), with
inputs as the mixer hands them at the start of training (`u`, `B` and `C` at
unit scale in bf16, the step size and the decay over their initialisers'
ranges): the forward alone and the forward with the backward of all six
inputs, each path under `jit`, the host's clock over 10 calls after one that
compiles; the bytes the mathematics has to move (`u`, `dt`, `B`, `C` in and
`y` out; with the backward the entering states written and read, the inputs
read again, `dy` read and `du`, `ddt` written) and the share of HBM's rate
that is; the distance of each path's forward from the recurrence taken token
by token, and of the kernels' gradients from the `jax.numpy` path's. The
kernels are timed at each block of channels of `--blocks` that divides the
shape's (the module's own choice is marked). Prints one JSON line a
measurement and fails without a TPU: a CPU's time is not a chip's.
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

from ray_tpu.ops import selective_scan as lib  # noqa: E402

SHAPES = {"cell": dict(T=16384, inner=5120, N=16, chunk=128),
          "probe": dict(T=4096, inner=5120, N=16, chunk=128)}
HBM_BYTES_PER_S = 819e9  # a v5e's, as `chipbench/peaks.py` has it
DT_RANGE = (1e-3, 0.1)   # `mamba_dt_init`'s, the published initialiser's
NAMES = "u delta A B C D".split()


def inputs(shape, seed, dtype=jnp.bfloat16):
    T, inner, N = (shape[x] for x in ("T", "inner", "N"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.exp(jax.random.uniform(
        ks[3], (1, T, inner), jnp.float32, *map(math.log, DT_RANGE)))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (inner, N))
    return (jax.random.normal(ks[0], (1, T, inner)).astype(dtype), dt, A,
            jax.random.normal(ks[1], (1, T, N)).astype(dtype),
            jax.random.normal(ks[2], (1, T, N)).astype(dtype),
            jnp.ones((inner,), jnp.float32),
            jax.random.normal(ks[4], (1, T, inner)))


def needed_bytes(shape, backward: bool, item: int = 2) -> int:
    """What the scan has to read and write: u, B and C in the compute
    dtype, dt and y in float32; with the backward the entering states
    written and read, the inputs read again, dy read, du and ddt written."""
    T, inner, N, Q = (shape[x] for x in ("T", "inner", "N", "chunk"))
    operands = T * (inner * (item + 4) + 2 * N * item)
    forward = operands + T * inner * 4
    if not backward:
        return forward
    states = T // Q * N * inner * 4
    return forward + 2 * states + operands + T * inner * (4 + item + 4)


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


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="cell,probe")
    parser.add_argument("--paths", default="chunked,pallas")
    parser.add_argument("--blocks", default="128,256,512,1024")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    own = lib._BLOCK

    for name in args.shapes.split(","):
        shape = SHAPES[name]
        *operands, weights = inputs(shape, args.seed)
        by_token = jax.jit(lambda *a: lib.selective_scan(
            *a, impl="tokens")[0])(*operands)
        runs = [(path, block) for path in args.paths.split(",")
                for block in ([own] if path != "pallas" else [
                    int(x) for x in args.blocks.split(",")
                    if shape["inner"] % int(x) == 0])]
        grads = {}
        for path, block in runs:
            lib._BLOCK = block  # what `channel_block` may take a grid step

            def forward(*a, path=path):
                return lib.selective_scan(
                    *a, chunk=shape["chunk"], impl=path)[0]

            def both(weights, *a):
                def loss(*a):
                    y = forward(*a)
                    return jnp.sum(y * weights), y
                return jax.grad(loss, argnums=range(6), has_aux=True)(*a)

            for use, fn, given in (
                    ("forward", forward, operands),
                    ("forward_backward", both, (weights, *operands))):
                fn = jax.jit(fn)
                ms = timed(fn, *given)
                moved = needed_bytes(shape, use != "forward")
                out = fn(*given)
                line = {"shape": name, **shape, "use": use, "path": path,
                        "ms_a_call": round(ms, 4), "needed_bytes": moved,
                        "hbm_rate_share": round(
                            moved / (ms * 1e-3) / HBM_BYTES_PER_S, 4)}
                if path == "pallas":
                    line["block"] = lib.channel_block(shape["inner"])
                    line["the_module_s_own"] = block == own
                if use == "forward":
                    line["rel_err_to_the_recurrence"] = rel(out, by_token)
                else:
                    grads.setdefault(path, out[0])
                    line["finite"] = all(
                        bool(jnp.isfinite(x).all()) for x in out[0])
                    if path != runs[0][0]:
                        line["grad_rel_diff_to_" + runs[0][0]] = {
                            n: rel(a, b) for n, a, b in zip(
                                NAMES, out[0], grads[runs[0][0]])}
                print(json.dumps({**line, "device": device.device_kind}),
                      flush=True)
        lib._BLOCK = own


if __name__ == "__main__":
    main()
