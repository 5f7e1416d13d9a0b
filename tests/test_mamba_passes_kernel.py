"""The Mamba-2 mixer's bandwidth passes as Pallas kernels
(`ray_tpu/ops/mamba_passes.py`: `mamba_conv_fwd`, `mamba_conv_bwd`,
`mamba_norm_fwd`, `mamba_norm_bwd`) in interpret mode, against the
`jax.numpy` lines they replace on the chip (`_causal_taps` with the bias and
the silu; `fused_rmsnorm` of the gated product): results and every gradient
over several blocks of tokens and of channels, the splits' edges and a
group's, bf16 and float32; which shapes tile, and the line that says which
path a shape took; the dtypes the kernels read, compute and write in, from
their own jaxprs. And the same pair under the KDA mixer's name with each
head of the silu's result at unit length (`kda_conv_fwd`, `kda_conv_bwd`; PR
67), against `_kda_mixer`'s `jax.numpy` lines; and the KDA mixer's output
norm and gate (`group_rmsnorm_gated`: `kda_out_norm_fwd`,
`kda_out_norm_bwd`; PR 69) against the mixer's lines for them."""

import functools
import hashlib
import logging

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.transformer import _causal_taps, _unit_length
from ray_tpu.ops import mamba_passes as passes
from ray_tpu.ops.fused import fused_rmsnorm

EPS = 1e-5
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])


@pytest.fixture
def blocks(monkeypatch):
    """Sets the most a grid step and a trip take, so that a small shape has
    several of each."""
    def set_(conv_tokens=32, conv_channels=128, norm_tokens=32, rows=16,
             unit_rows=16, gated=(32, 256, 16)):
        for knob, value in zip(("_GATED_TOKENS", "_GATED_LANES",
                                "_GATED_ROWS"), gated):
            monkeypatch.setattr(passes, knob, value)
        monkeypatch.setattr(passes, "_UNIT_ROWS", unit_rows)
        monkeypatch.setattr(passes, "_CONV_TOKENS", conv_tokens)
        monkeypatch.setattr(passes, "_CONV_CHANNELS", conv_channels)
        monkeypatch.setattr(passes, "_NORM_TOKENS", norm_tokens)
        monkeypatch.setattr(passes, "_ROWS", rows)
    return set_


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2)))


def conv_lines(x, w, bias, splits):
    """The mixer's lines before PR 63."""
    pre = _causal_taps(x, w)
    if bias is not None:
        pre = pre + bias.astype(x.dtype)
    ends = [sum(splits[:k + 1]) for k in range(len(splits) - 1)]
    return tuple(jnp.split(jax.nn.silu(pre), ends, axis=-1))


def norm_lines(y, z, weight, groups):
    B, T, inner = y.shape
    gated = (y * jax.nn.silu(z)).reshape(B, T, groups, inner // groups)
    return fused_rmsnorm(gated, weight.reshape(groups, inner // groups),
                         eps=EPS).reshape(B, T, inner)


def out_and_grads(f, args, cotangents):
    """`f(*args)` and its cotangents' pull onto every argument that is
    there; one compiled program."""
    def run(cotangents, *args):
        out, pull = jax.vjp(f, *args)
        return out, pull(cotangents)

    return jax.jit(run)(cotangents, *args)


def conv_inputs(B, T, splits, taps, dtype, bias=True, seed=0):
    C = sum(splits)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3 + len(splits))
    args = (jax.random.normal(ks[0], (B, T, C)).astype(dtype),
            (jax.random.normal(ks[1], (taps, C)) / taps ** 0.5).astype(dtype))
    if bias:
        args += (0.3 * jax.random.normal(ks[2], (C,)),)
    return args, tuple(jax.random.normal(k, (B, T, width)).astype(dtype)
                       for k, width in zip(ks[3:], splits))


def norm_inputs(B, T, inner, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ((jax.random.normal(ks[0], (B, T, inner)).astype(dtype),
             jax.random.normal(ks[1], (B, T, inner)).astype(dtype),
             1.0 + 0.2 * jax.random.normal(ks[2], (inner,))),
            jax.random.normal(ks[3], (B, T, inner)).astype(dtype))


# (B, T, splits, taps, bias, tokens and channels a grid step)
CONV_SHAPES = [
    # three blocks of tokens and four of channels in three splits
    (2, 96, (256, 128, 128), 4, True, 32, 128),
    # the cell's widths: twelve blocks of channels, split at 4,096 / 5,120
    (1, 32, (4096, 1024, 1024), 4, True, 16, 512),
    # one block of tokens, two trips; two splits of two blocks each
    (2, 32, (256, 256), 4, True, 32, 128),
    # no bias; three taps; a block of channels as wide as a split
    (2, 64, (256, 256), 3, False, 16, 256),
    # nine taps: all of a tile's rows before a block
    (1, 48, (128,), 9, True, 16, 128),
]


@DTYPES
@pytest.mark.parametrize("B,T,splits,taps,bias,tokens,channels", CONV_SHAPES)
def test_the_convolution_s_kernels_are_the_numpy_lines(
        B, T, splits, taps, bias, tokens, channels, dtype, blocks):
    """float32: the two differ by the order of four products' sum. bf16:
    the lines round every product and sum to bf16, the kernels keep them in
    float32 and round the result once."""
    blocks(conv_tokens=tokens, conv_channels=channels)
    args, cotangents = conv_inputs(B, T, splits, taps, dtype, bias)
    assert passes.conv_blocks(T, splits)[:2] == (tokens, channels)
    out, grads = out_and_grads(functools.partial(
        passes.causal_conv_silu, splits=splits, interpret=True),
        args, cotangents)
    want_out, want = out_and_grads(
        lambda *a: conv_lines(*a, *([] if bias else [None]), splits),
        args, cotangents)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert len(out) == len(splits)
    for ours, theirs, width in zip(out, want_out, splits):
        assert ours.dtype == dtype and ours.shape == (B, T, width)
        assert rel(ours, theirs) < tol
    for name, ours, theirs, arg in zip(("x", "w", "bias"), grads, want, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert rel(ours, theirs) < tol, name


def test_one_result_without_splits_and_zeros_before_the_sequence(blocks):
    blocks()
    (x, w, bias), _ = conv_inputs(2, 64, (256,), 4, jnp.float32)
    out = passes.causal_conv_silu(x, w, bias, interpret=True)
    assert out.shape == x.shape
    # the first token sees its own tap alone, the second two
    first = jax.nn.silu(w[3] * x[:, 0] + bias)
    second = jax.nn.silu(w[3] * x[:, 1] + w[2] * x[:, 0] + bias)
    assert rel(out[:, 0], first) < 1e-6 and rel(out[:, 1], second) < 1e-6
    # a token at a block's edge reads the three before it, across the edge
    at = passes.conv_blocks(64, (256,))[0]
    assert at == 32
    edge = jax.nn.silu(sum(w[i] * x[:, at - 3 + i] for i in range(4)) + bias)
    assert rel(out[:, at], edge) < 1e-6
    with pytest.raises(ValueError, match="do not sum"):
        passes.causal_conv_silu(x, w, bias, splits=(128, 64))


def test_bf16_kernels_are_nearer_float32_than_the_lines(blocks):
    """The kernels round less than the lines they replace, never more."""
    blocks()
    args, cotangents = conv_inputs(2, 64, (256, 128), 4, jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    exact, _ = out_and_grads(
        lambda *a: conv_lines(*a, (256, 128)), wide,
        tuple(c.astype(jnp.float32) for c in cotangents))
    ours = passes.causal_conv_silu(*args, splits=(256, 128), interpret=True)
    theirs = conv_lines(*args, (256, 128))
    for got, lines, true in zip(ours, theirs, exact):
        assert rel(got, true) <= rel(lines, true) < 1e-2
    (y, z, weight), _ = norm_inputs(2, 32, 256, jnp.bfloat16)
    true = norm_lines(y.astype(jnp.float32), z.astype(jnp.float32), weight, 2)
    got = passes.gated_group_rmsnorm(y, z, weight, 2, EPS, interpret=True)
    assert rel(got, true) <= rel(norm_lines(y, z, weight, 2), true) < 1e-2


def test_the_module_s_numpy_path_is_the_mixer_s_lines_to_the_bit():
    """`_conv_numpy` writes `_causal_taps`' sum again (`ops/` imports
    nothing of `models/`): the same values and gradients, bit for bit."""
    args, cotangents = conv_inputs(2, 40, (96, 32), 4, jnp.bfloat16)
    got = out_and_grads(functools.partial(
        passes.causal_conv_silu, splits=(96, 32), impl="xla"),
        args, cotangents)
    want = out_and_grads(lambda *a: conv_lines(*a, (96, 32)), args, cotangents)
    for ours, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert ours.dtype == theirs.dtype and bool((ours == theirs).all())
    args, cotangent = norm_inputs(2, 40, 96, jnp.bfloat16)
    got = out_and_grads(lambda *a: passes.gated_group_rmsnorm(
        *a, 3, EPS, impl="xla"), args, cotangent)
    want = out_and_grads(lambda *a: norm_lines(*a, 3), args, cotangent)
    for ours, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert ours.dtype == theirs.dtype and bool((ours == theirs).all())


# (B, T, inner, groups, tokens a grid step)
NORM_SHAPES = [
    (2, 96, 1024, 2, 32),   # three blocks of tokens; a group's edge at 512
    (1, 32, 4096, 8, 16),   # the cell's row: 8 groups of 512
    (2, 32, 256, 2, 32),    # groups of one tile of lanes; two trips
    (2, 48, 384, 1, 16),    # one group of three tiles
]


@DTYPES
@pytest.mark.parametrize("B,T,inner,groups,tokens", NORM_SHAPES)
def test_the_norm_s_kernels_are_the_numpy_lines(
        B, T, inner, groups, tokens, dtype, blocks):
    """bf16: the lines round `y * silu(z)` to bf16 before the norm widens
    it, the kernels keep the float32 product."""
    blocks(norm_tokens=tokens)
    args, cotangent = norm_inputs(B, T, inner, dtype)
    assert passes.norm_blocks(T) == (tokens, 16)
    out, grads = out_and_grads(lambda *a: passes.gated_group_rmsnorm(
        *a, groups, EPS, interpret=True), args, cotangent)
    want_out, want = out_and_grads(
        lambda *a: norm_lines(*a, groups), args, cotangent)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert out.dtype == dtype and out.shape == (B, T, inner)
    assert rel(out, want_out) < tol
    for name, ours, theirs, arg in zip(("y", "z", "weight"), grads, want, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert rel(ours, theirs) < tol, name


def test_a_group_s_statistics_are_its_own(blocks):
    """Scaling one group's `y` leaves the normed group as it was (but for
    `eps`) and the other group to the bit."""
    blocks()
    (y, z, weight), _ = norm_inputs(1, 32, 1024, jnp.float32)
    run = functools.partial(passes.gated_group_rmsnorm, groups=2, eps=1e-12,
                            interpret=True)
    out = run(y, z, weight)
    scaled = run(y.at[..., :512].multiply(64.0), z, weight)
    assert bool((scaled[..., 512:] == out[..., 512:]).all())
    assert rel(scaled[..., :512], out[..., :512]) < 1e-5


@pytest.mark.parametrize("T,taps,widths,why", [
    (64, 4, (4096, 1024, 1024), None),
    (8192, 4, (4096, 1024, 1024), None),       # the cell's
    (64, 4, (32, 16, 16), "32 channels are no multiple of 128"),  # the tests' toy
    (64, 4, (256, 64), "64 channels are no multiple of 128"),
    (40, 4, (256,), "40 tokens are no multiple of 16"),
    (64, 10, (256,), "10 taps"),
    (64, 1, (256,), "1 taps"),
])
def test_which_shapes_the_convolution_s_kernels_take(T, taps, widths, why):
    said = passes.conv_untiled(taps, widths, T)
    assert (said is None) if why is None else (why in said)


@pytest.mark.parametrize("T,inner,groups,why", [
    (64, 1024, 2, None),
    (8192, 4096, 8, None),                     # the cell's
    (64, 32, 2, "2 groups of 32 channels"),    # the tests' toy
    (64, 768, 4, "4 groups of 768 channels"),  # groups of 192 lanes
    (64, 1024, 3, "3 groups of 1024"),
    (24, 1024, 2, "24 tokens are no multiple of 16"),
    (1024, 1 << 16, 2, "bytes of VMEM"),
])
def test_which_shapes_the_norm_s_kernels_take(T, inner, groups, why):
    said = passes.norm_untiled(inner, groups, T)
    assert (said is None) if why is None else (why in said)


def test_a_shape_that_does_not_tile_takes_numpy_and_says_so(caplog):
    args, _ = conv_inputs(2, 40, (96, 32), 4, jnp.float32)
    passes._log_pass.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.mamba_passes"):
        conv = functools.partial(
            passes.causal_conv_silu, splits=(96, 32), impl="pallas")
        assert "pallas_call" not in str(jax.make_jaxpr(conv)(*args))
        out = conv(*args)  # runs: no kernel on a CPU
        (y, z, weight), _ = norm_inputs(2, 40, 96, jnp.float32)
        norm = functools.partial(
            passes.gated_group_rmsnorm, groups=3, eps=EPS, impl="pallas")
        assert "pallas_call" not in str(jax.make_jaxpr(norm)(y, z, weight))
        normed = norm(y, z, weight)
    for ours, theirs in zip(out, conv_lines(*args, (96, 32))):
        assert bool((ours == theirs).all())
    assert bool((normed == norm_lines(y, z, weight, 3)).all())
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 2  # once a shape, however often it is traced
    assert lines[0].startswith("causal_conv_silu at B 2, T 40, C 128, float32")
    assert "jax.numpy, because 96 channels are no multiple" in lines[0]
    assert lines[1].startswith("gated_group_rmsnorm at B 2, T 40, C 96")
    assert "jax.numpy, because 3 groups of 96 channels" in lines[1]


def test_a_shape_that_tiles_says_which_kernels_and_at_what_size(
        caplog, blocks):
    blocks(conv_tokens=32, conv_channels=128, norm_tokens=64, rows=16)
    args, _ = conv_inputs(2, 64, (256, 128, 128), 4, jnp.bfloat16)
    (y, z, weight), _ = norm_inputs(2, 64, 1024, jnp.bfloat16)
    passes._log_pass.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.mamba_passes"):
        conv = functools.partial(passes.causal_conv_silu,
                                 splits=(256, 128, 128))
        assert "pallas_call" in str(jax.make_jaxpr(
            functools.partial(conv, interpret=True))(*args))
        assert "pallas_call" not in str(jax.make_jaxpr(conv)(*args))  # "auto"
        norm = functools.partial(passes.gated_group_rmsnorm, groups=2, eps=EPS)
        assert "pallas_call" in str(jax.make_jaxpr(
            functools.partial(norm, interpret=True))(y, z, weight))
    conv_line, numpy_line, norm_line = [r.getMessage() for r in caplog.records]
    assert "mamba_conv_fwd and mamba_conv_bwd, 4 taps, splits [256, 128, " \
        "128], grid (2, 4, 2), blocks [32, 128] after [16, 128]" in conv_line
    fwd, bwd = (passes.pass_vmem_bytes(k, 32, 128, 2, 3)
                for k in ("mamba_conv_fwd", "mamba_conv_bwd"))
    assert f"16 tokens a trip, VMEM {fwd} and {bwd} bytes" in conv_line
    assert numpy_line.endswith("bfloat16: jax.numpy")
    assert "mamba_norm_fwd and mamba_norm_bwd, 2 groups of 512, grid (2, 1), " \
        "blocks [64, 1024], 16 tokens a trip" in norm_line


# ------------------------------------ the KDA mixer's short convolutions

def kda_lines(x, w, unit):
    """`_kda_mixer`'s `jax.numpy` lines for one stream: the taps, the silu
    and, for q and k, each head of `unit` channels at unit length."""
    out = jax.nn.silu(_causal_taps(x, w))
    if unit:
        B, T, C = out.shape
        out = _unit_length(out.reshape(B, T, C // unit, unit)).reshape(B, T, C)
    return out


def kda_conv(x, w, unit, **how):
    return passes.causal_conv_silu(x, w, unit=unit, name="kda_conv", **how)


# (T, H dk, tokens and channels a grid step, tokens a trip)
KDA_SHAPES = [
    # `solaropen2.tokens8k`'s width: three blocks of tokens (zeros before
    # the first, the 16 rows of the one before for the others), two of
    # channels, two trips a block
    (96, 1024, 32, 512, 16),
    # `kimilinear.tokens16k`'s: eight blocks of channels of four heads
    (32, 4096, 32, 512, 32),
]


@DTYPES
@pytest.mark.parametrize("unit", [0, 128], ids=["v", "q_and_k"])
@pytest.mark.parametrize("T,wide,tokens,channels,rows", KDA_SHAPES)
def test_kda_s_convolution_kernels_are_the_mixer_s_lines(
        T, wide, tokens, channels, rows, unit, dtype, blocks):
    """Forward, `dx` and the taps' gradient. float32: the order of the sums
    apart. bf16: the lines round every product, the taps' sum and the
    silu's result to bf16, the kernels keep them in float32 into the norm
    and round what they write once."""
    blocks(conv_tokens=tokens, conv_channels=channels, rows=rows,
           unit_rows=rows)
    (x, w), (ct,) = conv_inputs(2, T, (wide,), 4, dtype, bias=False)
    x = x.at[0, :, 128:256].set(0)  # a head of all zeros: the `eps` alone
    assert passes.conv_blocks(T, (wide,), unit) == (tokens, channels, rows)
    out, grads = out_and_grads(
        lambda x, w: kda_conv(x, w, unit, interpret=True), (x, w), ct)
    want_out, want = out_and_grads(
        lambda x, w: kda_lines(x, w, unit), (x, w), ct)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == dtype and out.shape == x.shape
    assert rel(out, want_out) < tol
    assert not out[0, :, 128:256].any()
    for name, ours, theirs, arg in zip(("x", "w"), grads, want, (x, w)):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert bool(jnp.isfinite(ours.astype(jnp.float32)).all()), name
        assert rel(ours, theirs) < tol, name
    # the zero head's `dx`: `dout / sqrt(eps)` through the taps, as the lines'
    assert rel(grads[0][0, :, 128:256], want[0][0, :, 128:256]) < tol


def test_kda_s_numpy_path_is_the_mixer_s_lines_to_the_bit():
    (x, w), _ = conv_inputs(2, 40, (256,), 4, jnp.bfloat16, bias=False)
    for unit in (0, 128):
        assert bool((kda_conv(x, w, unit, impl="xla")
                     == kda_lines(x, w, unit)).all())


def test_a_stream_without_the_unit_length_runs_the_mamba_mixer_s_body(blocks):
    """v's kernels under KDA's name are the Mamba-2 mixer's to the equation
    (its bias at zero); q's and k's add a head's sums and its rsqrt."""
    blocks(conv_channels=256)
    (x, w), (ct,) = conv_inputs(2, 64, (256,), 4, jnp.bfloat16, bias=False)

    def bodies(**how):
        def both(x, w):
            out, pull = jax.vjp(functools.partial(
                passes.causal_conv_silu, interpret=True, **how), x, w)
            return out, pull(ct)
        return {e.params["name"]: e.params["jaxpr"] for e in _equations(
            jax.make_jaxpr(both)(x, w).jaxpr)
            if e.primitive.name == "pallas_call"}

    mamba, v, q = bodies(), bodies(name="kda_conv"), bodies(
        name="kda_conv", unit=128)
    assert set(v) == set(q) == {"kda_conv_fwd", "kda_conv_bwd"}
    for kind in ("fwd", "bwd"):
        assert str(v["kda_conv_" + kind]) == str(mamba["mamba_conv_" + kind])
        norms = [e.primitive.name for e in _equations(q["kda_conv_" + kind])
                 if e.primitive.name in ("rsqrt", "reduce_sum")]
        # two heads a block of 256 channels; the backward sums `dout out` too
        assert norms.count("rsqrt") == 2
        assert norms.count("reduce_sum") == (2 if kind == "fwd" else 4)
        assert not [e for e in _equations(v["kda_conv_" + kind])
                    if e.primitive.name in ("rsqrt", "reduce_sum")]


def test_kda_s_shapes_tile_and_heads_that_do_not_take_the_numpy_lines(caplog):
    """Both cells' streams tile and fit VMEM; a head that is no whole lane
    tile, or that straddles the splits, takes `jax.numpy` and says why."""
    for T, wide in ((16384, 4096), (8192, 1024)):
        assert passes.conv_untiled(4, (wide,), T, 128) is None
        tokens, channels, rows = passes.conv_blocks(T, (wide,), 128)
        assert (tokens, channels, rows) == (1024, 512, 128)
        assert passes.conv_blocks(T, (wide,))[2] == 32  # v: the Mamba mixer's
        for kernel in ("kda_conv_fwd", "kda_conv_bwd"):
            need = passes.pass_vmem_bytes(kernel, tokens, channels, 2)
            assert need == passes.pass_vmem_bytes(
                kernel.replace("kda", "mamba"), tokens, channels, 2)
            assert need < passes._vmem_limit(
                kernel, tokens, channels, 2) <= 96 << 20
    assert "64 channels are no whole tiles" in passes.conv_untiled(
        4, (1024,), 8192, 64)
    assert "heads of 256" in passes.conv_untiled(4, (384,), 8192, 256)
    assert passes.conv_untiled(4, (1024,), 8192, 256) is None
    (x, w), _ = conv_inputs(2, 32, (128,), 4, jnp.float32, bias=False)
    passes._log_pass.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.mamba_passes"):
        out = kda_conv(x, w, 64, impl="pallas")
        assert "pallas_call" in str(jax.make_jaxpr(functools.partial(
            kda_conv, unit=128, interpret=True))(x, w))
    assert bool((out == kda_lines(x, w, 64)).all())
    numpy_line, kernels_line = [r.getMessage() for r in caplog.records]
    assert numpy_line == (
        "kda_conv at B 2, T 32, C 128, float32: jax.numpy, because heads of "
        "64 channels are no whole tiles of 128 lanes within a block of 512")
    assert kernels_line.startswith(
        "kda_conv at B 2, T 32, C 128, float32: kda_conv_fwd and "
        "kda_conv_bwd, 4 taps, splits [128], unit length a head of 128, "
        "grid (2, 1, 1), blocks [32, 128] after [16, 128], 32 tokens a trip")


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_kernels_compute_in_float32_and_write_no_wide_float32(blocks):
    """Under bf16 inputs, from the kernels' own jaxprs: every result of the
    arrays' width is bf16, the float32 results are the parameters' partial
    sums (eight rows a tap, eight of `d weight`); inside, every logistic,
    rsqrt and product is float32; the residuals are the inputs alone."""
    blocks()
    conv_args, conv_cts = conv_inputs(2, 64, (256, 128, 128), 4, jnp.bfloat16)
    norm_args, norm_ct = norm_inputs(2, 64, 1024, jnp.bfloat16)

    def both(conv_args, norm_args):
        def loss(conv_args, norm_args):
            outs = passes.causal_conv_silu(
                *conv_args, splits=(256, 128, 128), interpret=True)
            normed = passes.gated_group_rmsnorm(
                *norm_args, 2, EPS, interpret=True)
            return sum((o.astype(jnp.float32) * c).sum()
                       for o, c in zip((*outs, normed), (*conv_cts, norm_ct)))
        return jax.grad(loss, argnums=(0, 1))(conv_args, norm_args)

    eqns = list(_equations(jax.make_jaxpr(both)(conv_args, norm_args).jaxpr))
    calls = {e.params["name"]: e for e in eqns
             if e.primitive.name == "pallas_call"}
    assert set(calls) == {"mamba_conv_fwd", "mamba_conv_bwd",
                          "mamba_norm_fwd", "mamba_norm_bwd"}
    results = {name: [(v.aval.dtype, v.aval.shape) for v in call.outvars]
               for name, call in calls.items()}
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    assert results["mamba_conv_fwd"] == [
        (bf16, (2, 64, 256)), (bf16, (2, 64, 128)), (bf16, (2, 64, 128))]
    assert results["mamba_conv_bwd"] == [
        (bf16, (2, 64, 512)), (f32, (5, 8, 512))]
    assert results["mamba_norm_fwd"] == [(bf16, (2, 64, 1024))]
    assert results["mamba_norm_bwd"] == [
        (bf16, (2, 64, 1024)), (bf16, (2, 64, 1024)), (f32, (8, 1024))]
    # what a backward takes: the forward's inputs and the cotangents, no
    # result of the forward
    made = {id(v) for call in calls.values() for v in call.outvars}
    for name in ("mamba_conv_bwd", "mamba_norm_bwd"):
        assert not any(id(v) in made for v in calls[name].invars)
    for name, call in calls.items():
        inner = list(_equations(call.params["jaxpr"]))
        for kind in ("logistic", "rsqrt", "mul", "add", "reduce_sum"):
            found = [e for e in inner if e.primitive.name == kind
                     and e.outvars[0].aval.shape]
            if kind in ("rsqrt", "reduce_sum"):  # the groups' mean squares
                assert bool(found) == name.startswith("mamba_norm")
            else:
                assert found, (name, kind)
            assert all(e.outvars[0].aval.dtype == f32 for e in found), (
                name, kind)


def test_the_cell_s_shapes_tile_and_fit_vmem():
    """`nemotron3nano.tokens8k`: 8,192 tokens a row, the convolution over
    6,144 channels split 4,096 / 1,024 / 1,024, 8 groups of 512: every
    kernel's estimate stays inside the limit it asks of Mosaic, and that
    inside what a v5e has."""
    widths = (4096, 1024, 1024)
    assert passes.conv_untiled(4, widths, 8192) is None
    assert passes.norm_untiled(4096, 8, 8192) is None
    tokens, channels, rows = passes.conv_blocks(8192, widths)
    assert 4096 % channels == 0 and 1024 % channels == 0 and tokens % rows == 0
    for kernel in ("mamba_conv_fwd", "mamba_conv_bwd"):
        need = passes.pass_vmem_bytes(kernel, tokens, channels, 2, 3)
        assert need < passes._vmem_limit(kernel, tokens, channels, 2, 3) <= 96 << 20
    tokens, rows = passes.norm_blocks(8192)
    assert tokens % rows == 0
    for kernel in ("mamba_norm_fwd", "mamba_norm_bwd"):
        need = passes.pass_vmem_bytes(kernel, tokens, 4096, 2)
        assert need < passes._vmem_limit(kernel, tokens, 4096, 2) <= 96 << 20


# ----------------------------------- the KDA mixer's output norm and gate

def gated_lines(o, z, bias, weight, heads):
    """`_kda_mixer`'s `jax.numpy` lines: the gate rounded to `o`'s dtype,
    the norm a head on `[B, T, H, dk]`, rounded, and their product."""
    B, T, inner = o.shape
    gate = jax.nn.sigmoid(z.astype(jnp.float32) + bias).astype(o.dtype)
    normed = fused_rmsnorm(o.reshape(B, T, heads, inner // heads), weight,
                           eps=EPS)
    return normed.reshape(B, T, inner) * gate


def gated_inputs(B, T, heads, dtype, dk=128, seed=0):
    """((o, the gate's pre-activation, its bias, the heads' one scale), a
    cotangent)."""
    (o, z, weight), ct = norm_inputs(B, T, heads * dk, dtype, seed)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 7), (heads * dk,))
    return (o, z, bias, weight[:dk]), ct


def gated(*args, heads, **how):
    return passes.group_rmsnorm_gated(*args, heads, EPS, **how)


# (B, T, heads, tokens, channels a grid step)
GATED_SHAPES = [
    (2, 96, 4, 32, 256),    # three blocks of tokens, two of two heads each
    (1, 32, 32, 16, 1024),  # `kimilinear.tokens16k`'s row: four blocks of 8
    (1, 32, 8, 32, 1024),   # `solaropen2.tokens8k`'s: one block, two trips
    (2, 48, 3, 16, 128),    # a head a block: three partial rows of `d weight`
]


@DTYPES
@pytest.mark.parametrize("B,T,heads,tokens,lanes", GATED_SHAPES)
def test_kda_s_output_norm_kernels_are_the_mixer_s_lines(
        B, T, heads, tokens, lanes, dtype, blocks):
    """The result, `do`, `dz`, `d bias` and `d weight`. float32: the order
    of the sums apart. bf16: the lines round the normed o and the gate to
    bf16 and multiply in bf16, the kernels round their product once."""
    blocks(gated=(tokens, lanes, 16))
    args, ct = gated_inputs(B, T, heads, dtype)
    assert passes.gated_blocks(T, heads * 128, heads) == (tokens, 16, lanes)
    out, grads = out_and_grads(
        functools.partial(gated, heads=heads, interpret=True), args, ct)
    want_out, want = out_and_grads(
        lambda *a: gated_lines(*a, heads), args, ct)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert out.dtype == dtype and out.shape == (B, T, heads * 128)
    assert rel(out, want_out) < tol
    for name, ours, theirs, arg in zip(
            ("o", "z", "bias", "weight"), grads, want, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert rel(ours, theirs) < tol, name


def test_bf16_output_norm_kernels_are_nearer_float32_than_the_lines(blocks):
    blocks()
    args, ct = gated_inputs(2, 64, 4, jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    true = out_and_grads(lambda *a: gated_lines(*a, 4), wide,
                         ct.astype(jnp.float32))
    ours = out_and_grads(
        functools.partial(gated, heads=4, interpret=True), args, ct)
    theirs = out_and_grads(lambda *a: gated_lines(*a, 4), args, ct)
    for got, lines, exact in zip(*(jax.tree.leaves(t)
                                   for t in (ours, theirs, true))):
        assert rel(got, exact) <= rel(lines, exact) < 1e-2


def test_a_head_s_statistics_are_its_own_under_the_one_scale(blocks):
    """Scaling one head's o leaves its normed values as they were (but for
    `eps`) and every other head to the bit; the scale is one `[dk]` row, the
    same for every head, and its gradient the sum of the heads'."""
    blocks()
    (o, z, bias, weight), ct = gated_inputs(1, 32, 4, jnp.float32)
    run = functools.partial(passes.group_rmsnorm_gated, groups=4, eps=1e-12,
                            interpret=True)
    out = run(o, z, bias, weight)
    scaled = run(o.at[..., 128:256].multiply(64.0), z, bias, weight)
    assert rel(scaled[..., 128:256], out[..., 128:256]) < 1e-5
    for head in (0, 2, 3):
        lanes = slice(128 * head, 128 * head + 128)
        assert bool((scaled[..., lanes] == out[..., lanes]).all())
    # the heads as four rows of a model of one head: the same values, and
    # `d weight` the one gradient of all four
    rows = [a.reshape(1, 32, 4, 128).transpose(2, 0, 1, 3).reshape(4, 32, 128)
            for a in (o, z, ct)]
    by_head, pull = jax.vjp(
        lambda w: jnp.stack([passes.group_rmsnorm_gated(
            rows[0][h:h + 1], rows[1][h:h + 1], bias[128 * h:128 * h + 128],
            w, 1, 1e-12, interpret=True)[0] for h in range(4)]), weight)
    assert rel(out.reshape(32, 4, 128).transpose(1, 0, 2), by_head) < 1e-6
    dweight = jax.grad(lambda w: (run(o, z, bias, w) * ct).sum())(weight)
    assert dweight.shape == (128,)
    assert rel(dweight, pull(rows[2])[0]) < 1e-5


@pytest.mark.parametrize("T,inner,heads,why", [
    (16384, 4096, 32, None),                   # `kimilinear.tokens16k`'s
    (8192, 1024, 8, None),                     # `solaropen2.tokens8k`'s
    (64, 32, 2, "2 groups of 32 channels"),    # the tests' toy
    (64, 512, 8, "8 groups of 512 channels"),  # heads of 64 lanes
    (40, 512, 4, "40 tokens are no multiple of 16"),
    (64, 1 << 16, 2, "a step of kda_out_norm_bwd needs"),
])
def test_which_shapes_the_output_norm_s_kernels_take(T, inner, heads, why):
    said = passes.norm_untiled(inner, heads, T, "kda_out_norm")
    assert (said is None) if why is None else (why in said)
    if why is None:  # both cells' blocks, four heads a trip, fit VMEM
        tokens, rows, lanes = passes.gated_blocks(T, inner, heads)
        assert (tokens, rows, lanes) == (2048, 64, 512)
        for kernel in ("kda_out_norm_fwd", "kda_out_norm_bwd"):
            need = passes.pass_vmem_bytes(kernel, tokens, lanes, 2)
            assert need == passes.pass_vmem_bytes(
                kernel.replace("kda_out", "mamba"), tokens, lanes, 2)
            assert need < passes._vmem_limit(
                kernel, tokens, lanes, 2) <= 96 << 20


def test_the_output_norm_says_which_path_a_shape_took(caplog, blocks):
    blocks(gated=(32, 256, 16))
    (o, z, bias, weight), _ = gated_inputs(2, 64, 4, jnp.bfloat16)
    passes._log_pass.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.mamba_passes"):
        assert "pallas_call" in str(jax.make_jaxpr(functools.partial(
            gated, heads=4, interpret=True))(o, z, bias, weight))
        auto = functools.partial(gated, heads=4)
        assert "pallas_call" not in str(jax.make_jaxpr(auto)(
            o, z, bias, weight))
        assert bool((auto(o, z, bias, weight)
                     == gated_lines(o, z, bias, weight, 4)).all())
        narrow = functools.partial(gated, heads=8, impl="pallas")
        assert "pallas_call" not in str(jax.make_jaxpr(narrow)(
            o, z, bias, weight[:64]))
        assert bool((narrow(o, z, bias, weight[:64])  # runs: no kernel
                     == gated_lines(o, z, bias, weight[:64], 8)).all())
    kernels_line, numpy_line, narrow_line = [
        r.getMessage() for r in caplog.records]
    fwd, bwd = (passes.pass_vmem_bytes(k, 32, 256, 2)
                for k in ("kda_out_norm_fwd", "kda_out_norm_bwd"))
    assert kernels_line == (
        "kda_out_norm at B 2, T 64, C 512, bfloat16: kda_out_norm_fwd and "
        "kda_out_norm_bwd, 4 groups of 128, grid (2, 2, 2), blocks [32, 256], "
        f"16 tokens a trip, VMEM {fwd} and {bwd} bytes")
    assert numpy_line == (
        "kda_out_norm at B 2, T 64, C 512, bfloat16: jax.numpy")
    assert narrow_line == (
        "kda_out_norm at B 2, T 64, C 512, bfloat16: jax.numpy, because 8 "
        "groups of 512 channels are no multiple of 128 lanes each")


def _calls(f, *args):
    """{a kernel's name: its `pallas_call` equation} of `f`'s trace."""
    return {e.params["name"]: e
            for e in _equations(jax.make_jaxpr(f)(*args).jaxpr)
            if e.primitive.name == "pallas_call"}


def test_the_output_norm_s_kernels_hold_float32_and_write_none_of_it(blocks):
    """Under bf16 inputs, from the kernels' own jaxprs: what they read and
    write at the mixer's width is bf16, the float32 operands are the bias's
    and the scale's rows and the float32 result the sixteen partial rows of
    `d weight` and `d bias`; inside, every logistic, rsqrt, product and sum
    is float32; the residuals are the inputs alone."""
    blocks()
    args, ct = gated_inputs(2, 64, 4, jnp.bfloat16)

    def both(*args):
        out, pull = jax.vjp(
            functools.partial(gated, heads=4, interpret=True), *args)
        return out, pull(ct)

    calls = _calls(both, *args)
    assert set(calls) == {"kda_out_norm_fwd", "kda_out_norm_bwd"}
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    wide, row, head = (bf16, (2, 64, 512)), (f32, (1, 512)), (f32, (1, 128))

    def of(variables):
        return [(v.aval.dtype, v.aval.shape) for v in variables]

    assert of(calls["kda_out_norm_fwd"].invars) == [wide, wide, row, head]
    assert of(calls["kda_out_norm_fwd"].outvars) == [wide]
    assert of(calls["kda_out_norm_bwd"].invars) == [
        wide, wide, row, head, wide]
    assert of(calls["kda_out_norm_bwd"].outvars) == [
        wide, wide, (f32, (16, 512))]
    made = {id(v) for v in calls["kda_out_norm_fwd"].outvars}
    assert not any(id(v) in made for v in calls["kda_out_norm_bwd"].invars)
    for name, call in calls.items():
        inner = list(_equations(call.params["jaxpr"]))
        for kind in ("logistic", "rsqrt", "mul", "add", "reduce_sum"):
            found = [e for e in inner if e.primitive.name == kind
                     and e.outvars[0].aval.shape]
            assert found, (name, kind)
            assert all(e.outvars[0].aval.dtype == f32 for e in found), (
                name, kind)


# `str` of the kernels' jaxprs and grid mappings at the parent of PR 69
# (commit f570df7), sha256: equations and digest
MAMBA_NORM_BODIES = {
    "mamba_norm_fwd": (48, "ad398d3553a97650"),
    "mamba_norm_bwd": (107, "3da6c4e6c4f33002"),
}


@pytest.mark.parametrize("kernel", sorted(MAMBA_NORM_BODIES))
def test_the_mamba_mixer_s_norm_traces_to_the_equations_it_had(kernel):
    """`gated_group_rmsnorm`'s kernels share `_norm_call` with KDA's and keep
    their bodies, their grid of two axes and their blocks of whole rows."""
    (y, z, weight), ct = norm_inputs(2, 64, 1024, jnp.bfloat16)

    def both(y, z, weight, ct):
        out, pull = jax.vjp(lambda *a: passes.gated_group_rmsnorm(
            *a, 2, 1e-5, interpret=True), y, z, weight)
        return out, pull(ct)

    call = _calls(both, y, z, weight, ct)[kernel]
    text = str(call.params["jaxpr"]) + str(call.params["grid_mapping"])
    equations, digest = MAMBA_NORM_BODIES[kernel]
    assert len(list(_equations(call.params["jaxpr"]))) == equations
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
