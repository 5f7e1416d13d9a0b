#!/usr/bin/env python3
"""`moe_sum` alone on the chip, against the scatter-add it stands in for.

    python3 benchmarks/moe_sum_alone.py [--shapes mellum,dsv2lite] [--tt 256,128] [--skew 1]

At each shape, with a seeded routing whose held rows come out near each of
the shape's `held` counts: the forward's use (each held row times its slot's
float32 weight, summed onto its token) and the backward's (the rows as they
are, of one buffer and of two), the `jax.numpy` path (`_to_tokens` and the
passes around it, as `combine_held` and `dispatch`'s backward ran them
before the kernel) against `sum_held` (the kernel and, before it, the plan
of where the rows lie, as a step runs both), each under `jit`, the host's
clock over 10 calls after one that compiles. And the largest difference between
the two, which is the order of the float32 adds. Prints one JSON line a
measurement and fails without a TPU: a CPU's time is not a chip's.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import moe  # noqa: E402

# buffer rows, tokens, d, k, held of experts, held rows to aim for
SHAPES = {
    "mellum": dict(n=180_224, tokens=65_536, d=2304, k=8, held=16, experts=64,
                   rows=(131_072, 170_000)),
    "dsv2lite": dict(n=33_792, tokens=32_768, d=2048, k=6, held=8, experts=64,
                     rows=(24_576, 33_000)),
    "laguna": dict(n=20_480, tokens=16_384, d=2048, k=8, held=32, experts=256,
                   rows=(16_384,)),
}


def routing(shape, aim, seed, skew=0.0):
    """Slots of a seeded routing whose held experts take about `aim` rows:
    their logits are raised until they do. With `skew` some experts are
    liked more than others, by all the tokens and again by each stretch of
    128 tokens, as a router's are whose neighbouring tokens share a context:
    a standard deviation of `skew` in the logits each way."""
    tokens, k, experts, held = (shape[x] for x in ("tokens", "k", "experts", "held"))
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = jax.random.normal(keys[0], (tokens, experts))
    if skew:
        logits += skew * jax.random.normal(keys[1], (experts,))
        logits += skew * jnp.repeat(jax.random.normal(
            keys[2], (tokens // 128, experts)), 128, axis=0)
    lift = jnp.where(jnp.arange(experts) < held, 1.0, 0.0)
    lo, hi = -4.0, 8.0
    for _ in range(24):
        mid = (lo + hi) / 2
        _, weights, index = moe.route(logits + mid * lift, k)
        if int((index < held).sum()) < aim:
            lo = mid
        else:
            hi = mid
    _, weights, index = moe.route(logits + lo * lift, k)
    slots = moe.sort_slots(index, experts, (0, held))
    return weights, slots


def timed(fn, *args, onto=False, calls=10):
    """Milliseconds a call of `fn` under `jit`. With `onto` the last
    argument is given away to each call and is the next call's: the sum
    goes on in one buffer, as in the layer's loop."""
    fn = jax.jit(fn, donate_argnums=(len(args) - 1,) if onto else ())
    *args, last = args
    if onto:
        last = last + 0  # a buffer of the loop's own to give away
    out = jax.block_until_ready(fn(*args, last))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args, out if onto else last)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="mellum,dsv2lite")
    parser.add_argument("--tt", default="")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--skew", type=float, default=0.0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    forced = [int(t) for t in args.tt.split(",") if t]

    for name in args.shapes.split(","):
        shape = SHAPES[name]
        n, tokens, d, k = (shape[x] for x in ("n", "tokens", "d", "k"))
        for aim in shape["rows"]:
            weights, slots = routing(shape, aim, args.seed, args.skew)
            part = moe._chunk_of(slots, slots.order, 0, n)
            rows = part.group_sizes.sum()
            keys = jax.random.split(jax.random.PRNGKey(args.seed + 1), 3)
            live = (jnp.arange(n) < rows)[:, None]
            ys, dy_a, dy_b = (
                jnp.where(live, jax.random.normal(key, (n, d), jnp.bfloat16),
                          jnp.nan) for key in keys)
            onto = jax.random.normal(keys[0], (tokens, d), jnp.float32)

            def line(use, path, ms, **more):
                print(json.dumps({
                    "shape": name, "held_rows": int(rows), "skew": args.skew,
                    "largest_expert_over_mean": round(float(
                        part.group_sizes.max() / part.group_sizes.mean()), 2),
                    "use": use,
                    "path": path, "ms_a_call": round(ms, 4), **more,
                    "device": device.device_kind}), flush=True)

            # the forward's use, as `combine_held` with `out + chunk` behind it
            def forward_xla(ys, weights, onto):
                weighted = ys.astype(jnp.float32) * weights.reshape(-1)[
                    part.order][:, None]
                chunk = moe._to_tokens(weighted, part.order, rows, tokens, k)
                return onto + chunk.astype(ys.dtype).astype(jnp.float32)

            def backward_xla(*dys):
                dxs = dys[0] if len(dys) == 1 else dys[0] + dys[1]
                return moe._to_tokens(dxs.astype(jnp.float32), part.order,
                                      rows, tokens, k).astype(dxs.dtype)

            def kernel(tt, weighted, sources, with_onto, out_dtype=None):
                tiles = tt and moe.sum_tiles(
                    n, tokens, d, shape["held"], jnp.bfloat16,
                    out_dtype=out_dtype, weighted=weighted, sources=sources,
                    onto=with_onto, tt=tt)

                def call(inverse, sizes, rows, *a):
                    a = list(a)
                    base = a.pop() if with_onto else None
                    w = a.pop() if weighted else None
                    return moe.sum_held(
                        a, inverse, sizes, rows, tokens, weights=w, onto=base,
                        out_dtype=out_dtype, tiles=tiles or None)
                return call

            def exact(weighted):
                """The `jax.numpy` sum in float32, nothing rounded after it."""
                def call(*a):
                    a = [x.astype(jnp.float32) for x in a]
                    if weighted:
                        return moe._to_tokens(
                            a[0] * a[1].reshape(-1)[part.order][:, None],
                            part.order, rows, tokens, k)
                    return moe._to_tokens(sum(a), part.order, rows, tokens, k)
                return jax.jit(call)

            def differs(got, want):
                return {"max_abs_diff": float(jnp.abs(got - want).max()),
                        "finite": bool(jnp.isfinite(got).all())}

            uses = {  # use: (the path before, its arguments, weighted, sources, onto)
                "forward": (forward_xla, (ys, weights, onto), True, 1, True),
                "backward": (backward_xla, (dy_a,), False, 1, False),
                "backward_of_two": (backward_xla, (dy_a, dy_b), False, 2, False),
            }
            for use, (before, operands, weighted, sources, with_onto) in uses.items():
                line(use, "xla", timed(before, *operands, onto=with_onto))
                want = exact(weighted)(*operands[:sources + weighted])
                # where the rows lie is the step's to find out on the device
                # (`_sum_plan`'s passes over [held, k, tokens]): arguments
                # here too, or tracing would work the plan out beforehand
                where = (part.inverse, part.group_sizes, rows)
                for tt in forced or [0]:
                    ms = timed(kernel(tt, weighted, sources, with_onto),
                               *where, *operands, onto=with_onto)
                    got = jax.jit(kernel(
                        tt, weighted, sources, False, jnp.float32))(
                            *where, *operands[:sources + weighted])
                    line(use, "moe_sum", ms, **({"tt": tt} if tt else {}),
                         **differs(got, want))


if __name__ == "__main__":
    main()
