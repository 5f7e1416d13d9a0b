"""The selective scan's Pallas kernels (`ray_tpu/ops/selective_scan.py`:
`selective_scan_fwd`, `selective_scan_bwd`) in interpret mode, on shapes
that tile: against the `jax.numpy` chunked path they replace on the chip and
against the recurrence taken token by token, `y`, the last state and the
gradients of all six inputs under a loss that weighs every token and the
last state, as cases of one test (a ragged tail, two batch rows, two blocks
of channels, bf16 with a float32 step size); the dtypes the kernels compute
in, read from their own jaxprs; and which path a shape takes, with the line
that says so. A file of its own beside `tests/test_selective_scan.py`, as
the other scans' kernels have theirs: each shape is three programs to
compile."""

import functools
import logging

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import selective_scan as lib

NAMES = "u delta A B C D".split()
N = 8


def draw(seed, b, T, inner, dtype=jnp.float32):
    """The scan's six inputs as a mixer hands them, and the weights of a
    loss on every token's output and on the last state."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    u = jax.random.normal(k[0], (b, T, inner)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, T, inner)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[2], (inner, N)))
    B = jax.random.normal(k[3], (b, T, N)).astype(dtype)
    C = jax.random.normal(k[4], (b, T, N)).astype(dtype)
    D = jax.random.normal(k[5], (inner,))
    weights = (jax.random.normal(k[6], (b, T, inner)),
               jax.random.normal(k[7], (b, inner, N)))
    return (u, dt, A, B, C, D), weights


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / (jnp.sum(b ** 2) + 1e-30)))


@functools.lru_cache(maxsize=None)
def everything(path, chunk):
    """`y`, the last state and the six gradients of one path, as one
    program a shape."""
    fn = {"kernels": functools.partial(lib.selective_scan, interpret=True),
          "numpy": functools.partial(lib.selective_scan, impl="chunked"),
          "recurrence": functools.partial(lib.selective_scan, impl="tokens"),
          }[path]

    def loss(weights, *args):
        y, last = fn(*args, chunk=chunk)
        return (y * weights[0]).sum() + (last * weights[1]).sum(), (y, last)

    return jax.jit(jax.value_and_grad(
        loss, argnums=range(1, 7), has_aux=True))


# (batch rows, tokens, channels, dtype): chunks of 16 tokens; 1,280 channels
# are two blocks of 640, so that dB and dC are summed over blocks. Every
# case's loss weighs the last state too: its cotangent is not zero
KERNEL_CASES = {
    "two_chunks": (1, 32, 256, jnp.float32),
    # 37 tokens are padded to three chunks with steps of dt = 0
    "a_ragged_tail": (1, 37, 256, jnp.float32),
    "two_batch_rows": (2, 32, 256, jnp.float32),
    "two_blocks_of_channels": (1, 32, 1280, jnp.float32),
    "bfloat16_and_all_of_them": (2, 37, 1280, jnp.bfloat16),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_are_the_numpy_path_and_the_recurrence(case):
    """All three widen `u`, `B` and `C` a token at a time and compute in
    float32: they differ by the order of `dB`'s and `dC`'s sums and, with
    bf16, by where a cotangent of `u`, `B` or `C` is rounded."""
    b, T, inner, dtype = KERNEL_CASES[case]
    assert lib.channel_block(inner) == (256 if inner == 256 else 640)
    args, weights = draw(len(case), b, T, inner, dtype)
    (_, (y, last)), grads = everything("kernels", 16)(weights, *args)
    (_, (y_np, last_np)), grads_np = everything("numpy", 16)(weights, *args)
    (_, (y_rec, last_rec)), grads_rec = everything("recurrence", 16)(
        weights, *args)
    assert y.dtype == last.dtype == jnp.float32
    assert y.shape == (b, T, inner) and last.shape == (b, inner, N)
    for ours, theirs in ((y, y_np), (y, y_rec), (last, last_np),
                         (last, last_rec)):
        assert rel(ours, theirs) < 2e-6
    for name, ours, theirs, by_token, arg in zip(
            NAMES, grads, grads_np, grads_rec, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert bool(jnp.isfinite(ours).all()), name
        close = 2e-5 if arg.dtype == jnp.float32 else 4e-3
        assert rel(ours, theirs) < close, name
        assert rel(ours, by_token) < close, name


# (chunk, N, inner, itemsize) -> the reason's words, or None where the
# kernels take it
UNTILED = [((128, 16, 5120, 2), None), ((16, 8, 256, 4), None),
           ((16, 16, 128, 2), None), ((8, 8, 640, 4), None),
           ((128, 16, 5100, 2), "5100 channels"),
           ((16, 8, 64, 4), "64 channels"),
           ((128, 4, 5120, 2), "4 states"),
           ((8, 16, 5120, 2), "no multiple of 16 sublanes"),
           ((12, 16, 5120, 4), "no multiple of 8 sublanes"),
           ((8192, 16, 5120, 2), "VMEM")]


@pytest.mark.parametrize("shape,why", UNTILED)
def test_which_shapes_the_kernels_take(shape, why):
    reason = lib.selective_scan_untiled(*shape)
    assert (reason is None) if why is None else (why in reason)


def test_a_shape_that_does_not_tile_takes_numpy_and_says_why(caplog):
    """64 channels are no whole lanes: `impl='pallas'` and `interpret` take
    the `jax.numpy` path all the same, one line a shape says so, and a
    shape that tiles names its kernels, grid, block and VMEM."""
    lib._log_selective_scan.cache_clear()
    args, _ = draw(3, 1, 24, 64)
    with caplog.at_level(logging.INFO, logger=lib.logger.name):
        y, last = lib.selective_scan(*args, chunk=8, impl="pallas",
                                     interpret=True)
        lib.selective_scan(*args, chunk=8, impl="pallas", interpret=True)
        lib.selective_scan(*args, chunk=8)  # "auto": the CPU's path
        jax.eval_shape(
            functools.partial(lib.selective_scan, chunk=128, interpret=True),
            *draw(3, 1, 300, 2048, jnp.bfloat16)[0])
    y_ref, last_ref = lib.selective_scan(*args, impl="tokens")
    assert rel(y, y_ref) < 2e-6 and rel(last, last_ref) < 2e-6
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 3
    assert lines[0].endswith(
        "jax.numpy (selective_scan), because 64 channels are no multiple of "
        "128 lanes")
    assert lines[1] == ("selective_scan at b 1, T 24, inner 64, N 8, chunk "
                        "8, float32: jax.numpy (selective_scan)")
    assert ("selective_scan at b 1, T 384, inner 2048, N 8, chunk 128, "
            "bfloat16: selective_scan_fwd and selective_scan_bwd, grid "
            "(1, 2, 3), blocks [128, 1024] of u") in lines[2]
    assert "VMEM" in lines[2]
    lib._log_selective_scan.cache_clear()


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_kernels_compute_in_the_stated_dtypes():
    """The configuration's dtypes, read from the kernels' own jaxprs under
    bf16 `u`, `B` and `C`: every `exp` is float32, and nothing but a read
    of a block, a cast and a write holds a bf16 value: no bf16 product,
    sum or state. `dt`, `A`, the states and their cotangents, `y`, `dA` and
    the parts of `dB` and `dC` cross HBM in float32; `u`, `B`, `C` and
    `du` in bf16."""
    args, _ = draw(0, 1, 32, 256, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: sum(
        x.sum() for x in lib.selective_scan(*a, chunk=16, interpret=True)),
        argnums=range(6)))(*args)
    calls = {e.params["name"]: e for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert set(calls) == {"selective_scan_fwd", "selective_scan_bwd"}
    low, wide = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    for name, call in calls.items():
        inner = list(_equations(call.params["jaxpr"]))
        exps = [e for e in inner if e.primitive.name == "exp"]
        assert exps and all(e.invars[0].aval.dtype == wide for e in exps)
        for e in inner:
            if e.primitive.name in ("get", "swap", "convert_element_type"):
                continue
            held = {getattr(v.aval, "dtype", None)
                    for v in (*e.invars, *e.outvars)}
            assert low not in held, (name, e.primitive.name)
        kinds = [v.aval.dtype for v in call.invars]
        assert kinds[:4] == [low, wide, wide, low]
        assert all(kind == wide for kind in kinds[4:])
        outs = [v.aval.dtype for v in call.outvars]
        assert outs == ([wide] * 3 if name == "selective_scan_fwd"
                        else [low] + [wide] * 3)
