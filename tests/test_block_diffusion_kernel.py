"""Block diffusion's own block and join as the kernel pair `bd_own_join_fwd`
and `bd_own_join_bwd` (`ray_tpu/ops/block_diffusion.py`), on the CPU in
interpret mode: the pair against the `jax.numpy` lines (`own_block_and_join`),
o and the gradients of q, k, v, oS and lseS, over dtypes, groups, blocks and
row tiles; block 0's rows, whose staircase saw nothing; which shapes the
kernels take (`own_join_untiled`) and what a layer does and counts where they
do not; what crosses the kernels' boundary and at what width; the bodies'
equation counts. `tests/test_sdar.py` has the whole attention and the leak
probe by the kernels, `tests/test_kernel_compile.py` the pair compiled for a
described v5e at the cell's shape."""

import functools
import logging

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as model
from ray_tpu.ops import block_diffusion as bd
from ray_tpu.util import tracing

L, D = 256, 128
NAMES = ("o", "dq", "dk", "dv", "doS", "dlseS")


def to_heads(x):
    """`[B, L, heads, ...]` as the kernels take it: `[B heads, L, ...]`."""
    return jnp.moveaxis(x, 2, 1).reshape(-1, x.shape[1], *x.shape[3:])


def by_kernels(q, k, v, o_s, lse_s, block, scale, tile):
    """`own_block_and_join`'s arguments through the kernels: the arrays laid
    as `attention_by_kernels` hands them over, o as the lines give it."""
    B, R, H, _ = q.shape
    Hk = k.shape[2]
    o = bd._own_join(
        to_heads(bd.fold_halves(q, Hk)), to_heads(k), to_heads(v),
        to_heads(bd.fold_halves(o_s, Hk)),
        to_heads(bd.fold_halves(lse_s[..., None], Hk)).reshape(
            -1, H // Hk, R // 2), B, block, scale, tile, True)
    return o.reshape(q.shape)


def inputs(B, group, Hk, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    H = group * Hk
    q, o_s, ct = (jax.random.normal(key, (B, 2 * L, H, D)).astype(dtype)
                  for key in (ks[0], ks[3], ks[5]))
    k, v = (jax.random.normal(key, (B, 2 * L, Hk, D)).astype(dtype)
            for key in ks[1:3])
    # near the own block's lse, so that both parts carry weight
    lse_s = 2.0 + jax.random.normal(ks[4], (B, 2 * L, H))
    return (q, k, v, o_s, lse_s), ct


def o_and_gradients(fn, args, ct):
    o, pull = jax.vjp(fn, *args)
    return dict(zip(NAMES, (o, *pull(ct))))


# (dtype, batch, heads a group, key-value heads, block, rows a grid step)
CASES = {
    "f32-g1-b2-t128": (jnp.float32, 1, 1, 2, 2, 128),
    "f32-g2-b4-t256": (jnp.float32, 2, 2, 2, 4, 256),
    "f32-g8-b16-t128": (jnp.float32, 1, 8, 1, 16, 128),
    "bf16-g1-b16-t256": (jnp.bfloat16, 1, 1, 2, 16, 256),
    "bf16-g2-b2-t128": (jnp.bfloat16, 1, 2, 1, 2, 128),
    "bf16-g8-b4-t256": (jnp.bfloat16, 1, 8, 1, 4, 256),
}
_made = {}


def both_paths(case):
    if case not in _made:
        dtype, B, group, Hk, block, tile = CASES[case]
        args, ct = inputs(B, group, Hk, dtype)
        scale = D ** -0.5
        lines = functools.partial(bd.own_block_and_join, block=block,
                                  scale=scale)
        _made[case] = {
            "kernels": o_and_gradients(functools.partial(
                by_kernels, block=block, scale=scale, tile=tile), args, ct),
            "lines": o_and_gradients(lines, args, ct),
            # the lines on the same values in float32: what bf16 rounds from
            "exact": o_and_gradients(lines, jax.tree.map(
                lambda x: x.astype(jnp.float32), args),
                ct.astype(jnp.float32))}
    return _made[case]


@pytest.mark.parametrize("what", NAMES)
@pytest.mark.parametrize("case", list(CASES))
def test_the_pair_is_the_lines(case, what):
    """o and every gradient: in float32 to 1e-5 of the largest value; in bf16
    no further from the lines in float32 than the lines' own rounding takes
    them (ds rounds once more into dq and dk, as the operands of `q k^T`
    are: three times the lines' distance bounds it)."""
    made = both_paths(case)
    ours, theirs, exact = (made[path][what].astype(jnp.float32)
                           for path in ("kernels", "lines", "exact"))
    assert ours.shape == theirs.shape
    assert made["kernels"][what].dtype == made["lines"][what].dtype
    largest = float(jnp.abs(exact).max())
    if CASES[case][0] == jnp.float32:
        assert float(jnp.abs(ours - theirs).max()) < 1e-5 * largest
    else:
        rounding = float(jnp.abs(theirs - exact).max())
        assert float(jnp.abs(ours - exact).max()) <= 3 * rounding + 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rows_whose_staircase_saw_nothing(dtype):
    """Block 0's rows of both halves, lseS -inf as `flash_attention_lse`
    hands it out: o is the own block's, every gradient is finite, and the
    staircase's o and lse take no gradient there."""
    (q, k, v, o_s, lse_s), ct = inputs(1, 2, 2, dtype, seed=1)
    block = 4
    blind = (jnp.arange(2 * L) % L) < block
    lse_s = jnp.where(blind[None, :, None], -jnp.inf, lse_s)
    made = o_and_gradients(functools.partial(
        by_kernels, block=block, scale=D ** -0.5, tile=128),
        (q, k, v, o_s, lse_s), ct)
    own, _ = bd.own_block_part(q[0], k[0], v[0], block, D ** -0.5)
    tolerance = 1e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(made["o"][0].astype(jnp.float32) - own)[blind].max()
                 ) < tolerance
    for name in NAMES:
        assert bool(jnp.isfinite(made[name].astype(jnp.float32)).all()), name
    assert bool(jnp.all(made["doS"][0][blind] == 0))
    assert bool(jnp.all(made["dlseS"][0][blind] == 0))
    assert bool(jnp.any(made["doS"][0][~blind] != 0))
    # the kernels' own sentinel, as `_flash_lse` leaves it, reads the same
    near = o_and_gradients(functools.partial(
        by_kernels, block=block, scale=D ** -0.5, tile=128),
        (q, k, v, o_s, jnp.maximum(lse_s, -1e30)), ct)
    for name in NAMES:
        assert bool(jnp.all(near[name] == made[name])), name


# ------------------------------------------------- which shapes they take

@pytest.mark.parametrize("shape,reason", [
    ((16384, 32, 4, 128, 4, 2), None),  # `sdar.tokens16k`
    ((256, 4, 2, 128, 16, 4), None),
    ((256, 6, 4, 128, 4, 2), "6 query heads do not divide among 4"),
    ((256, 4, 2, 64, 4, 2), "heads of 64 are no whole tiles of 128 lanes"),
    ((256, 4, 2, 128, 3, 2), "blocks of 3 rows do not divide a trip's 128"),
    ((256, 4, 2, 128, 256, 2), "blocks of 256 rows do not divide"),
    ((200, 4, 2, 128, 4, 2), "halves of 200 rows are no whole trips of 128"),
    ((256, 512, 1, 128, 4, 4), "bytes of VMEM, over 100663296"),
])
def test_which_shapes_the_kernels_take(shape, reason):
    said = bd.own_join_untiled(*shape)
    if reason is None:
        assert said is None
    else:
        assert reason in said


def test_the_rows_a_grid_step_takes():
    """The most whole trips that divide a half and fit VMEM: the cell's
    1,024 of 16,384, all of a short half, fewer under a wide group."""
    assert bd.own_join_tile(16384, 8, 128, 2) == 1024
    assert bd.own_join_tile(384, 8, 128, 2) == 384
    assert bd.own_join_tile(1280, 8, 128, 2) == 640
    assert bd.own_join_tile(16384, 32, 128, 2) == 512
    assert bd.own_join_tile(256, 512, 128, 4) == 0
    assert 2 * bd.own_join_vmem_bytes("bwd", 1024, 8, 128, 2) < 96 << 20
    assert bd.own_join_vmem_bytes("fwd", 1024, 8, 128, 2) < (
        bd.own_join_vmem_bytes("bwd", 1024, 8, 128, 2))


def tiny(**over):
    return TransformerConfig(**{**dict(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
        d_head=128, d_ff=16, max_seq_len=128, qk_norm="head",
        tied_embeddings=False, dtype=jnp.float32, attention_impl="pallas",
        layer_types=("block_diffusion_attention",),
        objective="block_diffusion", diffusion_block=4, mask_token_id=63),
        **over})


@pytest.mark.parametrize("over,kernels", [
    (dict(), True),
    (dict(d_head=8), False),  # a head no whole lane tile
    (dict(max_seq_len=64), False),  # a half no whole trip
    (dict(attention_impl="xla"), False),  # the operators not Pallas's
])
def test_a_layer_counts_the_path_it_took(over, kernels, caplog):
    """Traced, not run: where the operators resolve to Pallas and the shape
    tiles the layer's trace holds the pair and counts one call by the
    kernels; elsewhere the `jax.numpy` lines and one by them; the step's
    log line says both."""
    cfg = tiny(**over)
    params = jax.eval_shape(
        lambda: model.transformer_init(jax.random.PRNGKey(0), cfg))
    rows = jnp.zeros((1, 2 * cfg.max_seq_len), jnp.int32)
    positions = jnp.tile(jnp.arange(cfg.max_seq_len), 2)[None]
    before = tracing.counters()
    bd._log_own_join.cache_clear()
    with caplog.at_level(logging.INFO, logger=bd.__name__):
        text = str(jax.make_jaxpr(lambda p: jax.grad(
            lambda p: model.transformer_hidden(
                p, rows, cfg, positions=positions).sum())(p))(params))
    after = tracing.counters()
    count = lambda name: after.get(name, 0) - before.get(name, 0)  # noqa: E731
    assert count("train.bd_own_join_calls_kernels") == int(kernels)
    assert count("train.bd_own_join_calls_numpy") == int(not kernels)
    assert ("bd_own_join_fwd" in text) == kernels
    assert ("bd_own_join_bwd" in text) == kernels
    said = model._calls_said(before)
    assert said == (
        "; block diffusion's own blocks and joins: %d calls by the kernels "
        "bd_own_join_fwd and bd_own_join_bwd, %d by jax.numpy" % (
            int(kernels), int(not kernels)))
    lines = [r.getMessage() for r in caplog.records
             if "own block and join" in r.getMessage()]
    assert len(lines) == 1
    if kernels:
        assert lines[0] == (
            "block diffusion's own block and join at B 1, 2 x 128 rows, 2 "
            "heads of 128 over 1, blocks of 4, float32: bd_own_join_fwd and "
            "bd_own_join_bwd, grid (1, 2, 1), blocks [2, 128, 128] of q and "
            "oS, [128, 128] of k and v, [128, 256] of o, 128 rows a trip, "
            "VMEM %d and %d bytes" % tuple(
                bd.own_join_vmem_bytes(k, 128, 2, 128, 4)
                for k in ("fwd", "bwd")))
    elif cfg.attention_impl == "xla":
        assert lines[0].endswith("float32: jax.numpy")
    else:
        assert ": jax.numpy, because " in lines[0]


# ----------------------------------------- what crosses the kernels' edge

def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _calls(dtype, group=2, Hk=2, block=4, tile=128):
    """{a kernel's name: its `pallas_call` equation} of the pair's trace."""
    args, ct = inputs(1, group, Hk, dtype)

    def both(*args):
        out, pull = jax.vjp(functools.partial(
            by_kernels, block=block, scale=D ** -0.5, tile=tile), *args)
        return out, pull(ct)

    return {e.params["name"]: e
            for e in _equations(jax.make_jaxpr(both)(*args).jaxpr)
            if e.primitive.name == "pallas_call"}


def test_the_kernels_hold_float32_and_write_none_of_q_s_width():
    """Under bf16 inputs, from the kernels' own jaxprs: what they read and
    write at q's width is bf16 and lies as the staircase's kernels and `wo`
    take it; the one float32 array either way is the lse's rows; the
    backward takes the forward's operands and o's cotangent, no result of
    the forward; inside, every exponential, logarithm and sum along a row
    is float32."""
    calls = _calls(jnp.bfloat16)
    assert set(calls) == {"bd_own_join_fwd", "bd_own_join_bwd"}
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    heads, keys = (bf16, (8, L, D)), (bf16, (2, 2 * L, D))
    lse, rows = (f32, (4, 2, L)), (bf16, (1, 2 * L, 4 * D))

    def of(variables):
        return [(v.aval.dtype, v.aval.shape) for v in variables]

    operands = [heads, keys, keys, heads, lse]
    assert of(calls["bd_own_join_fwd"].invars) == operands
    assert of(calls["bd_own_join_fwd"].outvars) == [rows]
    assert of(calls["bd_own_join_bwd"].invars) == [*operands, rows]
    assert of(calls["bd_own_join_bwd"].outvars) == operands
    made = {id(v) for v in calls["bd_own_join_fwd"].outvars}
    assert not any(id(v) in made for v in calls["bd_own_join_bwd"].invars)
    for name, call in calls.items():
        inner = list(_equations(call.params["jaxpr"]))
        for kind in ("exp", "log", "reduce_sum", "reduce_max", "div"):
            found = [e for e in inner if e.primitive.name == kind]
            assert found, (name, kind)
            assert all(e.outvars[0].aval.dtype == f32 for e in found), (
                name, kind)
        # the weights go into `p v` and its transpose by their bf16 parts,
        # three passes; `q k^T`, its two transposes and `do v^T` take one
        dots = [e for e in inner if e.primitive.name == "dot_general"]
        assert len(dots) == 2 * (4 if name.endswith("fwd") else 7)
        assert all(e.invars[0].aval.dtype == bf16 for e in dots)
        assert all(e.outvars[0].aval.dtype == f32 for e in dots)


# the equations of a body: a head's, by the heads a group (the loop over a
# tile's trips is one `scan`, whatever the tile)
BODIES = {
    ("bd_own_join_fwd", 1): 60, ("bd_own_join_bwd", 1): 96,
    ("bd_own_join_fwd", 8): 396, ("bd_own_join_bwd", 8): 649,
}


@pytest.mark.parametrize("kernel,group", sorted(BODIES))
def test_a_body_traces_to_the_equations_it_had(kernel, group):
    """A body is traced an equation at a time while the step is
    (`pallas_trace_s`): the cell's group of 8 stays under a thousand
    either way, and a tile of more rows adds none."""
    for tile in (128, 256):
        call = _calls(jnp.bfloat16, group=group, Hk=1, tile=tile)[kernel]
        assert len(list(_equations(call.params["jaxpr"]))) == BODIES[
            kernel, group]
    assert BODIES[kernel, group] < 1000
