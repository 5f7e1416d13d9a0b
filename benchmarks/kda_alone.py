#!/usr/bin/env python3
"""KDA's recurrence alone on the chip: `ops/kda.py`'s kernels against its
`jax.numpy` form.

    python3 benchmarks/kda_alone.py [--shapes cell,probe,kimi] [--paths xla,pallas] [--seed 0]

At each shape (`cell`: the 8,192 tokens and 8 held heads of 128 a layer of
`solaropen2.tokens8k` hands the recurrence; `probe`: the 2,048 tokens of the
benchmark's `kda_rel_err`; `kimi`: the 16,384 tokens and all 32 heads of a
layer of `kimilinear.tokens16k`, a grid of (1, 16, 256), for which take
`--paths pallas`: the `jax.numpy` form's pair tensors are 4 GB there), with
inputs as a KDA layer hands them at the
start of training (q and k of unit length, log decays of a thousandth to 1.6
nats a token, beta on both sides of 1): the forward alone and the forward
with the backward of all five inputs, each path under `jit`, the host's
clock over 10 calls after one that compiles; the bytes the mathematics has
to move (the operands, the result, the chunks' entering states, and for the
backward the cotangents), and the share of HBM's rate that is; the distance
of each path's forward from the recurrence taken token by token, and of the
kernels' gradients from the `jax.numpy` form's. Prints one JSON line a
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

from ray_tpu.ops.kda import kda, kda_recurrent  # noqa: E402

SHAPES = {"cell": dict(T=8192, H=8, dk=128, dv=128, chunk=64),
          "probe": dict(T=2048, H=8, dk=128, dv=128, chunk=64),
          "kimi": dict(T=16384, H=32, dk=128, dv=128, chunk=64)}
HBM_BYTES_PER_S = 819e9  # a v5e's, as `chipbench/peaks.py` has it


def inputs(shape, seed, dtype=jnp.bfloat16):
    T, H, dk, dv = (shape[x] for x in ("T", "H", "dk", "dv"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)

    def unit(x):
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(dtype)

    rate = jax.random.uniform(ks[3], (H, 1), jnp.float32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(
        ks[4], (1, T, H, dk), jnp.float32, math.log(1e-3), math.log(0.1)))
    return (unit(jax.random.normal(ks[0], (1, T, H, dk))),
            unit(jax.random.normal(ks[1], (1, T, H, dk))),
            jax.random.normal(ks[2], (1, T, H, dv)).astype(dtype),
            -rate * step,
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (1, T, H))),
            jax.random.normal(ks[6], (1, T, H, dv)).astype(dtype))


def needed_bytes(shape, backward: bool, item: int = 2) -> int:
    """What the recurrence has to read and write: q, k, v, o in the compute
    dtype, g and beta in float32; with the backward the entering states
    written and read, the operands read again, do read and the five
    cotangents written."""
    T, H, dk, dv, C = (shape[x] for x in ("T", "H", "dk", "dv", "chunk"))
    operands = T * H * (2 * dk * item + dv * item + dk * 4 + 4)
    forward = operands + T * H * dv * item
    if not backward:
        return forward
    states = T // C * H * dk * dv * 4
    return forward + 2 * states + 2 * operands + T * H * dv * item


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
    parser.add_argument("--paths", default="xla,pallas")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")

    for name in args.shapes.split(","):
        shape = SHAPES[name]
        *operands, weights = inputs(shape, args.seed)
        with jax.default_matmul_precision("highest"):
            by_token = jax.jit(kda_recurrent)(
                *(x.astype(jnp.float32) for x in operands))[0]
        grads = {}
        for path in args.paths.split(","):
            def forward(*a, path=path):
                return kda(*a, chunk=shape["chunk"], impl=path)[0]

            def both(*a):
                def loss(*a):
                    o = forward(*a)
                    return jnp.sum(o.astype(jnp.float32) * weights), o
                return jax.grad(loss, argnums=range(5), has_aux=True)(*a)

            for use, fn in (("forward", forward), ("forward_backward", both)):
                fn = jax.jit(fn)
                ms = timed(fn, *operands)
                moved = needed_bytes(shape, use != "forward")
                out = fn(*operands)
                line = {"shape": name, **shape, "use": use, "path": path,
                        "ms_a_call": round(ms, 4), "needed_bytes": moved,
                        "hbm_rate_share": round(
                            moved / (ms * 1e-3) / HBM_BYTES_PER_S, 4)}
                if use == "forward":
                    line["rel_err_to_the_recurrence"] = rel(out, by_token)
                else:
                    grads[path] = out[0]
                    line["finite"] = all(
                        bool(jnp.isfinite(x).all()) for x in out[0])
                    if len(grads) == 2:
                        line["grad_rel_diff_to_" + next(iter(grads))] = {
                            n: rel(a, b) for n, a, b in zip(
                                "q k v g beta".split(), grads[path],
                                next(iter(grads.values())))}
                print(json.dumps({**line, "device": device.device_kind}),
                      flush=True)


if __name__ == "__main__":
    main()
