#!/usr/bin/env python3
"""Block diffusion's own block and join alone on the chip, at
`sdar.tokens16k`'s shape: `ops/block_diffusion.py`'s kernel pair
(`bd_own_join_fwd`, `bd_own_join_bwd`) over row tiles, beside the `jax.numpy`
lines they stand for (`own_block_and_join`: a `lax.map` over chunks of 2,048
rows under `jax.checkpoint`).

    python3 benchmarks/bd_own_join_alone.py [--tiles 256,512,1024,2048]
        [--paths pallas,numpy] [--calls 10] [--seed 0] [--rows 16384]

One sequence of `--rows` tokens as twice as many rows, 32 query heads of 128
over 4 key-value heads, blocks of 4, bf16. The kernels take every array as
the staircase's kernels leave or take it (q and oS `[64, L, 128]`, the halves
folded into the heads; lseS `[8, 8, L]` float32; k and v `[4, 2 L, 128]`; o
`[1, 2 L, 4096]`), the `jax.numpy` lines theirs (`[1, 2 L, 32, 128]`); every
operand is made in the layout it is timed in. For the forward and for the
backward alone (the lines': forward and backward, less the forward): ms a
call by the host's clock over `--calls` calls after one that compiles, the
bytes the call has to move (each operand read once, each result written
once), the least time a v5e's 819 GB/s allow and the share of it that is;
then the kernels' largest distance from the lines' o and gradients. Prints
one JSON line a measurement and fails without a TPU: a CPU's time is not a
chip's.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import block_diffusion as bd  # noqa: E402

H, HK, D, BLOCK = 32, 4, 128, 4
HBM_BYTES_A_SECOND = 819e9


def moved_bytes(L: int, backward: bool) -> int:
    """What a call has to move: q, oS and o (with the backward o's
    cotangent, and q's and oS's written), k and v (and theirs), lseS (and
    its), bf16 but for the lse."""
    wide, narrow, rows = (5, 4, 2) if backward else (3, 2, 1)
    return (wide * 2 * H * L * D * 2 + narrow * HK * 2 * L * D * 2
            + rows * 2 * H * L * 4)


def timed(fn, *args, calls):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def unfolded(x, L):
    """`[2 H, L, ...]`, the halves folded into the heads, heads first, as
    the `jax.numpy` lines' `[1, 2 L, H, ...]`."""
    return bd.unfold_halves(jnp.moveaxis(x, 0, 1)[None], HK)


def said(path, use, ms, L, device, **more):
    least = moved_bytes(L, use == "backward") / HBM_BYTES_A_SECOND * 1e3
    print(json.dumps({
        "path": path, "use": use, **more, "ms_a_call": round(ms, 4),
        "bytes": moved_bytes(L, use == "backward"),
        "least_ms": round(least, 4),
        "hbm_percent": round(100 * least / ms, 2),
        "device": device.device_kind}), flush=True)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiles", default="256,512,1024,2048")
    parser.add_argument("--paths", default="pallas,numpy")
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rows", type=int, default=16384)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    L, scale = args.rows, D ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    q = jax.random.normal(ks[0], (2 * H, L, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (HK, 2 * L, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (HK, 2 * L, D), jnp.bfloat16)
    o_s = jax.random.normal(ks[3], (2 * H, L, D), jnp.bfloat16)
    # the staircase's lse, near the own block's; block 0's rows see nothing
    lse_s = jax.random.normal(ks[4], (2 * H, L), jnp.float32).at[
        :, :BLOCK].set(-jnp.inf)
    lse_rows = lse_s.reshape(2 * HK, H // HK, L)  # as the kernels take it
    do = jax.random.normal(ks[5], (1, 2 * L, H * D), jnp.bfloat16)
    paths = args.paths.split(",")
    theirs = None
    if "numpy" in paths:
        rows = (unfolded(q, L), *(jnp.moveaxis(x, 0, 1)[None] for x in (k, v)),
                unfolded(o_s, L), unfolded(lse_s, L))
        do_rows = do.reshape(1, 2 * L, H, D)
        forward = jax.jit(lambda *a: bd.own_block_and_join(*a, BLOCK, scale))
        both = jax.jit(lambda *a: jax.vjp(
            lambda *b: bd.own_block_and_join(*b, BLOCK, scale), *a[:-1]
        )[1](a[-1]))
        ms = timed(forward, *rows, calls=args.calls)
        said("numpy", "forward", ms, L, device)
        said("numpy", "backward",
             timed(both, *rows, do_rows, calls=args.calls) - ms, L, device)
        theirs = (forward(*rows), *both(*rows, do_rows))
    if "pallas" not in paths:
        return
    for tile in map(int, args.tiles.split(",")):
        static = (1, BLOCK, scale, tile, False)
        forward = jax.jit(lambda *a: bd._own_join_fwd(*a, *static))
        backward = jax.jit(lambda *a: bd._own_join_bwd(*a, *static))
        more = dict(tile=tile, vmem_bytes=[
            bd.own_join_vmem_bytes(n, tile, H // HK, D, 2)
            for n in ("fwd", "bwd")])
        operands = (q, k, v, o_s, lse_rows)
        said("pallas", "forward",
             timed(forward, *operands, calls=args.calls), L, device, **more)
        said("pallas", "backward",
             timed(backward, *operands, do, calls=args.calls), L, device,
             **more)
        if theirs is None:
            continue
        dq, dk, dv, do_s, dlse_s = backward(*operands, do)
        ours = (forward(*operands).reshape(1, 2 * L, H, D),
                unfolded(dq, L), *(jnp.moveaxis(x, 0, 1)[None]
                                   for x in (dk, dv)),
                unfolded(do_s, L), unfolded(dlse_s.reshape(2 * H, L), L))
        print(json.dumps({"tile": tile, "largest_distance": {
            name: [float(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)).max()),
                   float(jnp.abs(b.astype(jnp.float32)).max())]
            for name, a, b in zip(("o", "dq", "dk", "dv", "doS", "dlseS"),
                                  ours, theirs)}}), flush=True)


if __name__ == "__main__":
    main()
