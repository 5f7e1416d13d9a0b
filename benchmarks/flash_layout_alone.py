#!/usr/bin/env python3
"""The flash kernels alone on the chip, by where v lies:
`ops/flash_attention.py`'s forward and backward with every array's heads
folded into the batch, `[B H, T, D]` (`v_heads` 1), and with v and dv where
the model holds them, `[B, S, Hk Dv]` (`v_heads = Hk`: what the entries hand
over where v has q's heads, whole tiles wide), and the transposes a folded
call needs timed beside them.

    python3 benchmarks/flash_layout_alone.py [--shapes evabyte-window,...] [--calls 10] [--seed 0] [--tile 1024x512]

At each shape (`evabyte-window`: the four windows of 2,048 of
`evabyte.tokens8k` folded into the batch, 32 heads of 128; `evabyte-stair`:
its 8,192 queries against 512 summaries under the staircase; `ouro`:
`ouro.tokens16k`'s 16 heads at T 16,384; `dsv2lite`: `dsv2lite.tokens8k`'s
16 heads, q and k 192 wide and v 128, 4 x 8,192; and three under a group,
where the entries fold v, timed folded only: `laguna-window` and
`laguna-full`, `lagunaxs2.tokens8k`'s 64 / 8 heads under a window of 512
and 48 / 8 whole, 2 x 8,192, and `mistral`, `mistral7b.tokens4k`'s 32 / 8
at 4 x 4,096; and the heads narrower than a tile of lanes, or wider by half
of one, that long rows send out of `flash_bwd_dkv_dq` a tile at a time
padded to whole lanes (PR 75): `phi4flash`, `phi4flash.tokens16k`'s 40 / 20
paired heads, q and k 64 wide and v 128, at T 16,384, `phi4flash-window`,
the same under its window of 512, `granite`, `granite4hmicro.longctx`'s
32 / 8 heads of 64 at T 32,768, and `kimilinear-mla`,
`kimilinear.tokens16k`'s 32 heads, 192 and 128 wide, at T 16,384; bf16)
and for the forward (`_flash_fwd` with lse) and the backward
(`_flash_backward`: delta and the kernels the plan takes, which the line
names with their tiles and the one kernel's exit; `backward_pair`: the same
with the plan answering `flash_bwd_dq` and `flash_bwd_dkv`, and how far the
plan's gradients lie from theirs; with `--tile`, `backward_forced`: the
plan at that tile of q rows x keys): ms a call by the
host's clock over `--calls` calls after one that compiles, for each way of
lying; the bytes the call's arrays hold (each read or written once) and
the GB/s that is; whether the results are equal bit for bit to the folded
call's; and the transposes a folded call stands between, a pair at a time
(q with dq, k with dk, v with dv, o with do: one array in and one out),
under one `jit`, with the bytes they read and write and their GB/s. Every
operand is made in the layout it is timed in: what a program pays to bring
an array there from the pass before is not in these numbers (PERF.md
section 6, PR 65, has that from the step's trace). Prints one JSON line a
measurement and fails without a TPU: a CPU's time is not a chip's.
"""

import argparse
import functools
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# the module: `ray_tpu.ops.flash_attention` by attribute is its function
fa = importlib.import_module("ray_tpu.ops.flash_attention")

SHAPES = {
    "evabyte-window": dict(B=4, T=2048, S=2048, H=32, Hk=32, causal=True),
    "evabyte-stair": dict(B=1, T=8192, S=512, H=32, Hk=32,
                          stair=(2048, 128)),
    "ouro": dict(B=1, T=16384, S=16384, H=16, Hk=16, causal=True),
    "dsv2lite": dict(B=4, T=8192, S=8192, H=16, Hk=16, D=192, causal=True),
    "laguna-window": dict(B=2, T=8192, S=8192, H=64, Hk=8, causal=True,
                          window=512),
    "laguna-full": dict(B=2, T=8192, S=8192, H=48, Hk=8, causal=True),
    "mistral": dict(B=4, T=4096, S=4096, H=32, Hk=8, causal=True),
    "phi4flash": dict(B=1, T=16384, S=16384, H=40, Hk=20, D=64, causal=True),
    "phi4flash-window": dict(B=1, T=16384, S=16384, H=40, Hk=20, D=64,
                             causal=True, window=512),
    "granite": dict(B=1, T=32768, S=32768, H=32, Hk=8, D=64, Dv=64,
                    causal=True),
    "kimilinear-mla": dict(B=1, T=16384, S=16384, H=32, Hk=32, D=192,
                           causal=True),
}
PAIR = ("flash_bwd_dq", "flash_bwd_dkv")


def the_pair(fn):
    """`fn` traced with the plan answering the two kernels."""
    def call(*operands):
        plan = fa.flash_bwd_kernels
        fa.flash_bwd_kernels = lambda *a, **kw: PAIR
        try:
            return fn(*operands)
        finally:
            fa.flash_bwd_kernels = plan

    return call


def fold(x, heads):
    """[B, T, heads D] as [B heads, T, D]: the transpose a folded call
    makes of every operand."""
    B, T, _ = x.shape
    return x.reshape(B, T, heads, -1).transpose(0, 2, 1, 3).reshape(
        B * heads, T, -1)


def unfold(x, heads):
    """The way back, of every result."""
    BH, T, d = x.shape
    return x.reshape(BH // heads, heads, T, d).transpose(0, 2, 1, 3).reshape(
        BH // heads, T, heads * d)


def timed(fn, *args, calls):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def nbytes(*arrays):
    return sum(x.size * x.dtype.itemsize for x in arrays)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tile", help="q rows x keys, as 1024x512: the "
                        "backward also at this tile, forced")
    args = parser.parse_args()
    forced = args.tile and dict(zip(("block_q", "block_k"),
                                    map(int, args.tile.split("x"))))
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")

    def report(**line):
        print(json.dumps({**line, "device": device.device_kind}), flush=True)

    for name in args.shapes.split(","):
        shape = dict(SHAPES[name])
        B, T, S, H, Hk = (shape.pop(k) for k in ("B", "T", "S", "H", "Hk"))
        D, Dv = shape.pop("D", 128), shape.pop("Dv", 128)
        how = dict(causal=False, window=None, stair=None, scale=D ** -0.5,
                   block_q=None, block_k=None, interpret=False)
        how.update(shape)
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q = jax.random.normal(ks[0], (B, T, H * D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, Hk * D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, Hk * Dv), jnp.bfloat16)
        do = jax.random.normal(ks[3], (B, T, H * Dv), jnp.bfloat16)
        hold = dict(zip("qkvo", (H, Hk, Hk, H)))
        given = dict(zip("qkvo", (q, k, v, do)))
        q_, k_, folded_v, do_ = jax.jit(lambda: tuple(
            fold(x, hold[pair]) for pair, x in given.items()))()
        kept = {}
        for layout, v_, v_heads in (("folded", folded_v, 1),
                                    ("v_in_place", v, Hk))[:1 + (H == Hk)]:
            forward = jax.jit(lambda q, k, v, n=v_heads: fa._flash_fwd(
                q, k, v, with_lse=True, v_heads=n, **how))

            def backward_at(q, k, v, o, lse, do, n=v_heads, **tile):
                return fa._flash_backward(q, k, v, o, lse, do, v_heads=n,
                                          **{**how, **tile})

            backward = jax.jit(backward_at)
            o, lse = forward(q_, k_, v_)
            dq, dk, dv = backward(q_, k_, v_, o, lse, do_)
            kept[layout] = (lse, o, dq, dk,
                            dv if v_heads == 1 else fold(dv, Hk))
            uses = [("forward", forward, (q_, k_, v_),
                     nbytes(q_, k_, v_, o, lse), {})]
            grads = (q_, k_, v_, o, lse, do_)
            moved = nbytes(q_, k_, v_, o, do_, lse, lse, dq, dk, dv)
            planned = dict(T=T, S=S, D=D, dtype=q.dtype, v_dim=Dv,
                           group=H // Hk, causal=how["causal"],
                           window=how["window"], stair=how["stair"])
            # (use, the jitted backward, the tile forced; None: the pair)
            ways = [("backward", backward, {}),
                    ("backward_pair", jax.jit(the_pair(backward_at)), None)]
            if forced:
                ways.append(("backward_forced", jax.jit(functools.partial(
                    backward_at, **forced)), forced))
            for use, fn, tile in ways:
                kernels = PAIR if tile is None else fa.flash_bwd_kernels(
                    **planned, **tile)
                said = {"kernels": {
                    kernel: "%d x %d" % t[:2] + (
                        ", " + t.exit if kernel == "flash_bwd_dkv_dq" else "")
                    for kernel in kernels
                    for t in [fa.flash_tiles(kernel, **planned, **(tile or {}))]}}
                if fn is not backward:  # how far the plan's lie from these
                    said["plan_s_largest_difference"] = {
                        what: float(jnp.abs(a.astype(jnp.float32)
                                            - b.astype(jnp.float32)).max())
                        for what, a, b in zip(("dq", "dk", "dv"),
                                              (dq, dk, dv), fn(*grads))}
                uses.append((use, fn, grads, moved, said))
            for use, fn, operands, moved, said in uses:
                ms = timed(fn, *operands, calls=args.calls)
                report(shape=name, use=use, layout=layout,
                       ms_a_call=round(ms, 4), bytes=moved,
                       gb_per_s=round(moved / ms / 1e6, 1), **said)
            if layout != "folded":
                report(shape=name, use="equals_folded", layout=layout, equal={
                    what: bool(jnp.array_equal(a, b, equal_nan=True))
                    for what, a, b in zip(("lse", "o", "dq", "dk", "dv"),
                                          kept[layout], kept["folded"])})
        for pair, x in given.items():
            there = jax.jit(lambda x, n=hold[pair]: fold(x, n))(x)
            ms = timed(jax.jit(lambda x, y, n=hold[pair]: (
                fold(x, n), unfold(y, n))), x, there, calls=args.calls)
            moved = 2 * nbytes(x, there)
            report(shape=name, use="the_fold_s_transposes", pair=pair,
                   ms_a_call=round(ms, 4), bytes=moved,
                   gb_per_s=round(moved / ms / 1e6, 1))


if __name__ == "__main__":
    main()
