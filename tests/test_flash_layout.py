"""Where the flash kernels' v lies (`ops/flash_attention.py`, `v_heads`,
`_head_block`, `_to_kernels`): every kernel takes v (and hands back dv)
either with its heads folded into the batch by a transpose, one head a batch
row, or as the model holds it, `[B, S, H Dv]`, a head a column block of v's
index map. The kernels make the same tiles in the same order on the same
values wherever v lies: results and gradients are equal bit for bit
(interpret mode). With heads whole tiles of 128 lanes wide, as many of v as
of q, the entries hand v over where it lies and take dv back there, q, k
and o folded; at other widths, and under a group, they fold everything,
the programs they were."""

import functools
import importlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.util import tracing

fa = importlib.import_module("ray_tpu.ops.flash_attention")

B, D = 2, 128


def inputs(T, H, Hk, *, S=None, D=D, Dv=None, dtype=jnp.float32, seed=0):
    """q, k, v, and cotangents of o and lse."""
    S, Dv = S or T, Dv or D
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (B, T, H, D), dtype),
            jax.random.normal(ks[1], (B, S, Hk, D), dtype),
            jax.random.normal(ks[2], (B, S, Hk, Dv), dtype),
            jax.random.normal(ks[3], (B, T, H, Dv), dtype),
            jax.random.normal(ks[4], (B, T, H), jnp.float32))


def folded_call(q, k, v, *, lse=False, causal=False, block_q=None,
                block_k=None, keep_ctx=False, window=None, stair=None):
    """The `custom_vjp`s on q, k, v `[B, T, H, D]` with every array's heads
    folded into the batch by a transpose, `v_heads` 1: the call as every
    shape made it before."""
    b, T, H, d = q.shape
    S, Dv = k.shape[1], v.shape[3]
    how = (causal, d ** -0.5, block_q, block_k, True, keep_ctx,
           fa._band(window, causal, S))
    operands = [x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])
                for x in (q, k, v)]
    if lse:
        of, rows = fa._flash_lse(*operands, *how, stair, 1)
        rows = jnp.where(rows > 0.5 * fa._BIG_NEG, rows, -jnp.inf)
    else:
        of = fa._flash(*operands, *how, 1)
    o = of.reshape(b, H, T, Dv).transpose(0, 2, 1, 3)
    return (o, rows.reshape(b, H, T).transpose(0, 2, 1)) if lse else o


def both(call, operands, lse=False, **how):
    """((outputs, gradients) of `call`, (the same) of the folded call),
    under one `jit` each, with the cotangents of `inputs`."""
    q, k, v, do, dlse = operands

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return out, vjp((do.astype(out[0].dtype), dlse) if lse
                        else do.astype(out.dtype))

    return (jax.jit(lambda: run(functools.partial(call, **how)))(),
            jax.jit(lambda: run(functools.partial(
                folded_call, lse=lse, **how)))())


def assert_same_bits(ours, theirs):
    ours, theirs = jax.tree.leaves(ours), jax.tree.leaves(theirs)
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert all(np.isfinite(np.asarray(a, np.float32)).all()
               for a in ours[-3:])  # dq, dk, dv


def steer(backward, monkeypatch):
    """`chosen`: the backward `flash_bwd_kernels` takes (the one kernel, by
    the row's blocks); `two`: `flash_bwd_dq` and `flash_bwd_dkv`; `by_tile`:
    the one kernel whose row-long gradients leave a tile at a time by DMA
    (dq; dv leaves by its blocks where v lies in place)."""
    if backward == "two":
        monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: (
            "flash_bwd_dq", "flash_bwd_dkv"))
    if backward == "by_tile":
        monkeypatch.setattr(fa, "_flash_bwd_dkv", functools.partial(
            fa._flash_bwd_dkv, by_tile=True))


# (T, H, Hk, the call's options): whole and ragged last tiles, one tile and
# several, a window, o and lse kept; under a group the entry folds v too
CASES = {
    "causal": (256, 2, 2, dict(causal=True, block_q=128, block_k=128)),
    "causal-keep": (256, 4, 4, dict(causal=True, keep_ctx=True)),
    "whole": (256, 2, 2, dict(block_q=128, block_k=256)),
    "window": (384, 2, 2, dict(causal=True, window=128, block_q=128,
                               block_k=128)),
    "window-keep": (384, 2, 2, dict(causal=True, window=200, block_q=128,
                                    block_k=128, keep_ctx=True)),
    "ragged": (320, 2, 2, dict(causal=True, block_q=128, block_k=128)),
    "causal-group4": (256, 4, 1, dict(causal=True, block_q=128, block_k=128)),
    "causal-group8-keep": (256, 8, 1, dict(causal=True, keep_ctx=True)),
}


@pytest.mark.parametrize("backward", ["chosen", "two", "by_tile"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_entry_is_the_folded_call_bit_for_bit(case, backward, monkeypatch):
    """`flash_attention` at heads of 128: v where it lies where it has q's
    heads, the rest folded."""
    T, H, Hk, how = CASES[case]
    steer(backward, monkeypatch)
    assert_same_bits(*both(functools.partial(
        fa.flash_attention, interpret=True), inputs(T, H, Hk), **how))


LSE_CASES = {
    "causal": (256, 256, 2, 2, dict(causal=True, block_q=128, block_k=128)),
    "window": (384, 384, 2, 2, dict(causal=True, window=128, block_q=128,
                                    block_k=128)),
    "whole-keep": (256, 128, 2, 2, dict(keep_ctx=True)),
    # 128 keys a span of 256 queries: the first span sees none
    "stair": (512, 256, 2, 2, dict(stair=(256, 128))),
    "stair-masked": (512, 256, 4, 4, dict(
        stair=(256, 128), block_q=128, block_k=256)),
    "stair-group4": (512, 256, 4, 1, dict(stair=(256, 128))),
}


@pytest.mark.parametrize("backward", ["chosen", "two", "by_tile"])
@pytest.mark.parametrize("case", list(LSE_CASES))
def test_with_lse_too(case, backward, monkeypatch):
    """`flash_attention_lse`, with a cotangent on lse."""
    T, S, H, Hk, how = LSE_CASES[case]
    steer(backward, monkeypatch)
    (out, grads), theirs = both(
        functools.partial(fa.flash_attention_lse, interpret=True),
        inputs(T, H, Hk, S=S), lse=True, **how)
    if "stair" in how:  # the rows of the first span see no key
        assert np.isneginf(out[1][:, :256]).all()
        assert np.isfinite(out[1][:, 256:]).all()
    assert_same_bits((out, grads), theirs)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=str)
def test_two_widths_of_whole_tiles_and_bf16(dtype):
    """q and k 128 wide, v 256: both whole tiles, so v where it lies."""
    operands = inputs(256, 2, 2, Dv=256, dtype=dtype)
    before = tracing.counters().get("train.flash_calls_in_place", 0)
    assert_same_bits(*both(functools.partial(
        fa.flash_attention, interpret=True), operands, causal=True,
        block_q=128, block_k=128))
    assert tracing.counters()["train.flash_calls_in_place"] == before + 1


def test_mha_takes_v_where_it_lies(monkeypatch):
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    assert_same_bits(*both(
        functools.partial(fa.mha, impl="pallas"), inputs(256, 4, 4),
        causal=True, window=128, keep_ctx=True))


# ------------------------------------------------------- the traced program

def transposes(jaxpr, rank=4):
    """Operand shapes of every `transpose` of a rank-`rank` array in
    `jaxpr` and the jaxprs its equations hold, the kernels' bodies apart."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if (eqn.primitive.name == "transpose"
                and eqn.invars[0].aval.ndim == rank):
            found.append(eqn.invars[0].aval.shape)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += transposes(sub, rank)
    return found


def traced(entry, widths, H=4, Hk=4, T=256, remat=False, **how):
    """(the jaxpr of `value_and_grad` of `entry` at heads of `widths` = (D,
    Dv), the counters' rise over the trace)."""
    d, dv = widths
    q = jax.ShapeDtypeStruct((B, T, H, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, T, Hk, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B, T, Hk, dv), jnp.bfloat16)

    def loss(q, k, v):
        out = entry(q, k, v, interpret=True, keep_ctx=True, **how)
        return sum(x.astype(jnp.float32).sum() for x in jax.tree.leaves(out))

    if remat:  # a block that keeps `attn_ctx`, as the model's do
        loss = jax.checkpoint(
            loss, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_ctx"))
    names = ("train.flash_calls_in_place", "train.flash_calls_folded")
    before = [tracing.counters().get(name, 0) for name in names]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v)
    return jaxpr.jaxpr, tuple(
        tracing.counters().get(name, 0) - was
        for name, was in zip(names, before))


ENTRIES = {
    "flash_attention": (fa.flash_attention, dict(causal=True)),
    "windowed": (fa.flash_attention, dict(causal=True, window=128)),
    "flash_attention_lse": (fa.flash_attention_lse, dict(causal=True)),
    "stair": (fa.flash_attention_lse, dict(stair=(128, 64))),
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_heads_of_128_leave_v_and_dv_where_they_lie(entry, remat):
    """No transpose of v or dv, forward, backward or made again: q, k in, o
    out, do in, dq, dk out are the six left (a remat that keeps `attn_ctx`
    makes q's and k's again)."""
    fn, how = ENTRIES[entry]
    jaxpr, (in_place, were_folded) = traced(fn, (128, 128), remat=remat, **how)
    shapes = transposes(jaxpr)
    assert (in_place, were_folded) == (1, 0)
    assert len(shapes) == 6 + 2 * remat
    assert all(shape in ((B, 256, 4, 128), (B, 4, 256, 128))
               for shape in shapes)


@pytest.mark.parametrize("widths", [(128, 128), (64, 128)], ids=str)
def test_under_a_group_everything_folds_as_it_did(widths):
    """`mistral7b.tokens4k`, `lagunaxs2.tokens8k`, `phi4flash.tokens16k`: v
    a `group`-th of q."""
    jaxpr, (in_place, were_folded) = traced(
        fa.flash_attention, widths, Hk=2, causal=True)
    assert len(transposes(jaxpr)) == 8
    assert (in_place, were_folded) == (0, 1)


@pytest.mark.parametrize("widths", [(64, 64), (64, 128), (192, 128)], ids=str)
@pytest.mark.parametrize("entry", ["flash_attention", "flash_attention_lse"])
def test_other_widths_fold_by_transpose_as_they_did(entry, widths):
    """Heads of 64 (`lfm2moe.tokens8k`) and 192 beside 128
    (`dsv2lite.tokens8k`: -0.87 % with v in place): q, k, v in, o out, and
    in the backward do in and dq, dk, dv out."""
    fn, how = ENTRIES[entry]
    jaxpr, (in_place, were_folded) = traced(fn, widths, **how)
    assert len(transposes(jaxpr)) == 8
    assert (in_place, were_folded) == (0, 1)


def test_the_backward_s_line_says_how_the_arrays_lie(caplog):
    fa._log_bwd_kernels.cache_clear()
    with caplog.at_level(logging.INFO, logger=fa.logger.name):
        traced(fa.flash_attention, (128, 128), causal=True)
        traced(fa.flash_attention, (64, 64), causal=True)
    lines = [r.getMessage() for r in caplog.records
             if "flash backward" in r.getMessage()]
    assert len(lines) == 2
    assert ("D 128, Dv 128, bfloat16, v and dv where the model holds them, "
            "[B, S, H Dv], H 4; q, k and o folded: flash_bwd_dkv_dq, tile"
            ) in lines[0]
    assert ("D 64, Dv 64, bfloat16, folded by transpose: D 64, Dv 64: "
            "flash_bwd_dkv_dq, tile") in lines[1]
    assert lines[0].endswith(", no group, out by the row's blocks")
