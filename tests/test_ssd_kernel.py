"""The Mamba-2 scan's Pallas kernels (`ray_tpu/ops/ssd.py`: `ssd_fwd`,
`ssd_bwd`) in interpret mode, on shapes that tile: against the `jax.numpy`
scan they replace on the chip and against the recurrence taken token by
token, `y` and the gradients of all six inputs; the dtypes the kernels
compute in, read from their own jaxprs; what a wrong dtype or a mask in the
wrong place does; and which path a shape takes, with the line that says so.
"""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd as scan
from ray_tpu.ops.ssd import ssd
from tiny_models import equations as _equations, ssd_by_token, y_and_grads

NAMES = "x dt A B C D".split()
kernels = functools.partial(ssd, interpret=True)


def inputs(T, H, P, G, N=128, rows=1, seed=0, dtype=jnp.float32):
    """x, dt, A, B, C, D; x, B and C in `dtype`, the rest float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (rows, T, H, P)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (rows, T, H)) - 1.0),
            -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (rows, T, G, N)).astype(dtype),
            (0.1 * jax.random.normal(ks[4], (rows, T, G, N))).astype(dtype),
            jax.random.normal(ks[5], (H,)))


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2)))


# (T, H, P, G): a group's channels `(H / G) P` 128 and 512, G 1 and 2; one,
# two and five chunks and a last chunk that is padded; a head a tile
SHAPES = [
    (128, 2, 64, 1),    # one chunk, one group of 128 channels
    (256, 4, 64, 2),    # two chunks, two groups of 128
    (640, 2, 64, 1),    # five chunks
    (300, 4, 64, 2),    # the last chunk is padded from 44 tokens
    (256, 8, 64, 1),    # a group of 512 channels
    (200, 16, 64, 2),   # two groups of 512, padded
    (256, 2, 128, 2),   # heads of a whole tile, a group a head
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,H,P,G", SHAPES)
def test_the_kernels_are_the_numpy_scan_forward_and_backward(T, H, P, G, dtype):
    """float32: the two paths differ by the order of their sums. bf16: both
    round the same operands at the same places (`dt x`, the masked scores,
    the decayed inputs, the entering state), so they stay as close as two
    orders of summing bf16 products are."""
    args = inputs(T, H, P, G, dtype=dtype)
    with jax.default_matmul_precision("highest"):
        y, grads = y_and_grads(kernels, args)
        want_y, want = y_and_grads(ssd, args)
    assert y.dtype == dtype and y.shape == args[0].shape
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    assert rel(y, want_y) < tol
    for name, ours, theirs, arg in zip(NAMES, grads, want, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        # dA is one sum a head over every token, of terms of both signs
        assert rel(ours, theirs) < (100 * tol if name == "A" else tol), name


@pytest.mark.parametrize("T,H,P,G", [SHAPES[1], SHAPES[3], SHAPES[4], SHAPES[6]])
def test_the_kernels_are_the_recurrence_forward_and_backward(T, H, P, G):
    args = inputs(T, H, P, G, seed=1)
    with jax.default_matmul_precision("highest"):
        y, grads = y_and_grads(kernels, args)
        want_y, want = y_and_grads(ssd_by_token, args)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    for name, ours, theirs in zip(NAMES, grads, want):
        scale = float(jnp.abs(theirs).max())
        np.testing.assert_allclose(
            ours / scale, theirs / scale,
            atol=2e-3 if name == "A" else 5e-5, err_msg=name)


def test_bf16_operands_lose_nothing_of_the_decays():
    """Against the float32 recurrence on the same rounded inputs what is
    left is the operands' rounding, a few parts in a thousand: 256 tokens
    of decay keep their float32."""
    args = inputs(256, 4, 64, 2, seed=3, dtype=jnp.bfloat16)
    y = kernels(*args)
    assert y.dtype == jnp.bfloat16
    assert rel(y, ssd_by_token(*args)) < 1e-2


def test_the_kernels_compute_in_the_stated_dtypes():
    """`assumed.dtype` of the configuration, read from the kernels' own
    jaxprs under bf16 inputs: every matmul takes bf16 operands and gives
    float32; every exp, and the select that masks before it, is float32;
    the state carried from chunk to chunk, the entering states handed to
    the backward, and dt and cum with their cotangents are float32; the
    running sum of `dt A` outside the kernels is float32."""
    args = inputs(256, 4, 64, 2, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: jax.value_and_grad(
        lambda *a: kernels(*a).astype(jnp.float32).sum(),
        argnums=range(6))(*a))(*args)
    eqns = list(_equations(jaxpr.jaxpr))
    calls = {e.params["name"]: e for e in eqns if e.primitive.name == "pallas_call"}
    assert set(calls) == {"ssd_fwd", "ssd_bwd"}
    sums = [e for e in eqns if e.primitive.name == "cumsum"]
    assert sums and all(e.invars[0].aval.dtype == jnp.float32 for e in sums)
    for name, call in calls.items():
        inner = list(_equations(call.params["jaxpr"]))
        dots = [e for e in inner if e.primitive.name == "dot_general"]
        # two heads a group: scores, a product a head, `C @ state`, the
        # state's update | three more a head, and five with the states
        assert len(dots) == (2 + 2 + 1 if name == "ssd_fwd" else 12)
        for dot in dots:
            assert {v.aval.dtype for v in dot.invars} == {jnp.dtype(jnp.bfloat16)}
            assert dot.outvars[0].aval.dtype == jnp.float32
        exps = [e for e in inner if e.primitive.name == "exp"]
        assert len(exps) == 2 + 3  # a head's decays; to cum, to the end, of the chunk
        assert all(e.invars[0].aval.dtype == jnp.float32 for e in exps)
        # the mask is a select on float32 whose result an exp takes
        made_by = {id(v): e for e in inner for v in e.outvars}
        masks = [made_by.get(id(e.invars[0])) for e in exps]
        assert sum(m is not None and "where" in str(m.params.get("name", ""))
                   for m in masks) == 2
        scratch = call.params["jaxpr"].invars[-1].aval
        assert scratch.dtype == jnp.float32 and scratch.shape == (128, 128)
        small = [v.aval for v in call.params["jaxpr"].invars
                 if 8 not in v.aval.shape and 2 in v.aval.shape[-2:]]
        assert small and all(a.dtype == jnp.float32 for a in small)
    fwd_out = [v.aval for v in calls["ssd_fwd"].outvars]
    assert [a.dtype for a in fwd_out] == [jnp.bfloat16, jnp.float32]
    assert fwd_out[1].shape == (1, 2, 2, 128, 128)  # [b, n, G, N, R P]


def test_a_bf16_sum_of_decays_or_a_mask_after_the_exp_is_seen(monkeypatch):
    """The twin of `test_a_dropped_skip_or_a_bf16_sum_of_decays_is_seen` on
    the kernels' path: running sums of `dt A` rounded to bf16 show as a
    hundred times the float32 path's distance from the recurrence; a mask
    applied after the exp meets `exp` of a positive sum above the
    diagonal (over 88 within a chunk at these decays), `inf * 0`, and the
    result is not a number."""
    args = inputs(256, 4, 64, 2, seed=5)
    want = ssd_by_token(*args)

    def error():
        with jax.default_matmul_precision("highest"):
            return rel(kernels(*args), want)

    assert error() < 1e-5
    real_cumsum = jnp.cumsum
    monkeypatch.setattr(jnp, "cumsum", lambda a, **kw: real_cumsum(
        a.astype(jnp.bfloat16), **kw).astype(a.dtype))
    assert error() > 1e-3
    monkeypatch.setattr(jnp, "cumsum", real_cumsum)

    def mask_after(cumc, cumr, h, causal):
        # the mask as a number, 1 at and under the diagonal: `i - j + 1`
        # held to [0, 1] (a product with the boolean is compiled to a select)
        Q = causal.shape[0]
        i, j = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), d) for d in (0, 1))
        under = jnp.clip(i - j + 1, 0, 1).astype(jnp.float32)
        return jnp.exp(cumc[:, h:h + 1] - cumr[h:h + 1, :]) * under

    monkeypatch.setattr(scan, "_decay", mask_after)
    jax.clear_caches()  # a kernel's trace is kept by its function
    assert not bool(jnp.isfinite(kernels(*args)).all())
    jax.clear_caches()


@pytest.mark.parametrize("T,H,P,G,N,chunk,why", [
    (64, 4, 8, 2, 16, 16, "chunk 16 is no multiple of 128"),       # the tests' toy
    (256, 4, 64, 2, 64, 128, "state 64 is no multiple of 128"),
    (256, 3, 64, 1, 128, 128, "a group's channels 192 is no multiple"),
    (256, 8, 48, 1, 128, 128, "heads of 48 neither divide nor fill"),
    (100, 2, 64, 1, 128, 128, "chunk 100 is no multiple of 128"),  # T under a chunk
])
def test_a_shape_that_does_not_tile_takes_numpy_and_says_so(
        T, H, P, G, N, chunk, why, caplog):
    args = inputs(T, H, P, G, N=N, seed=2)
    scan._log_scan.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.ssd"):
        jaxpr = jax.make_jaxpr(
            lambda *a: ssd(*a, chunk=chunk, impl="pallas"))(*args)
        y = ssd(*args, chunk=chunk, impl="pallas")  # runs: no kernel on a CPU
    assert "pallas_call" not in str(jaxpr)
    np.testing.assert_allclose(y, ssd(*args, chunk=chunk, impl="xla"))
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1  # once a shape, however often it is traced
    assert "jax.numpy (ssd_chunk, ssd_state, ssd_out), because" in lines[0]
    assert why in lines[0] and f"T {T + (-T) % min(chunk, T)}, H {H}" in lines[0]


def test_a_shape_that_tiles_says_which_kernels_and_at_what_size(caplog):
    args = inputs(256, 16, 64, 2)
    scan._log_scan.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.ssd"):
        assert "pallas_call" in str(jax.make_jaxpr(kernels)(*args))
        assert "pallas_call" not in str(jax.make_jaxpr(ssd)(*args))  # "auto"
    kernel_line, numpy_line = [r.getMessage() for r in caplog.records]
    assert "ssd_fwd and ssd_bwd, 8 heads a tile, grid (1, 2, 2)" in kernel_line
    assert "blocks [128, 512] of x and [128, 128] of B and C" in kernel_line
    assert "a state of [128, 512] float32" in kernel_line
    fwd, bwd = (scan.scan_vmem_bytes(k, 128, 128, 512, 4)
                for k in ("ssd_fwd", "ssd_bwd"))
    assert f"VMEM {fwd} and {bwd} bytes" in kernel_line
    assert numpy_line.endswith("float32: jax.numpy (ssd_chunk, ssd_state, ssd_out)")


def test_the_cell_s_shape_tiles_and_fits_vmem():
    """`nemotron3nano.tokens8k`: chunks of 128, a state of 128, groups of
    8 heads of 64; both kernels' estimates stay inside the limits they
    ask of Mosaic, and those inside what a v5e has."""
    assert scan.scan_untiled(128, 128, 8, 64) is None
    for kernel in ("ssd_fwd", "ssd_bwd"):
        need = scan.scan_vmem_bytes(kernel, 128, 128, 512, 2)
        limit = scan._vmem_limit(kernel, 128, 128, 512, 2)
        assert need < limit <= 96 << 20
    with pytest.raises(ValueError, match="groups"):
        kernels(*inputs(128, 4, 64, 3))


# ------------------------------------------------ a tile of a group's heads

# (T, H, P, G, chunk, heads a tile): 8 and 2 tiles a group (one tile is
# `SHAPES`' above), one group and eight, chunks of 128 and 256, heads that
# share a lane tile and heads of one
TILED = [
    (256, 16, 64, 1, 128, 2),    # one group in 8 tiles of 2 heads
    (512, 16, 64, 1, 256, 8),    # chunks of 256, 2 tiles of 8 heads
    (256, 32, 64, 8, 128, 2),    # eight groups, 2 tiles each
    (256, 8, 128, 1, 128, 1),    # heads of a whole lane tile, a head a tile
]


@pytest.mark.parametrize("T,H,P,G,chunk,heads", TILED)
def test_a_tile_of_a_group_s_heads_is_the_numpy_scan(T, H, P, G, chunk, heads):
    """The kernels with a group's heads taken `heads` at a time against
    the `jax.numpy` scan: `y` and every cotangent, `dB` and `dC` summed over
    a group's tiles in float32."""
    args = inputs(T, H, P, G)

    def tiled(x, dt, A, B, C, D):
        return scan._scan_kernels(
            x, dt.astype(jnp.float32), A, B, C, D, chunk, True, heads=heads)

    def plain(x, dt, A, B, C, D):
        return scan._scan_numpy(x, dt.astype(jnp.float32), A, B, C, D, chunk)

    with jax.default_matmul_precision("highest"):
        y, grads = y_and_grads(tiled, args)
        want_y, want = y_and_grads(plain, args)
    assert rel(y, want_y) < 2e-5
    for name, ours, theirs, arg in zip(NAMES, grads, want, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert rel(ours, theirs) < (2e-3 if name == "A" else 2e-5), name


def test_the_tiles_share_a_group_s_b_and_c_and_sum_their_cotangents():
    """Read from the jaxpr at bf16: with two tiles a group the kernels'
    groups are the tiles, `B` and `C` stay `[b, T, G N]`, `ssd_bwd` writes a
    tile's `dB` and `dC` in float32 and the sum over the tiles is taken
    outside; with one tile the call has no such sum and writes them in the
    operands' dtype."""
    args = inputs(256, 16, 64, 2, dtype=jnp.bfloat16)

    def calls(heads):
        jaxpr = jax.make_jaxpr(lambda *a: jax.grad(
            lambda *a: scan._scan_kernels(
                *a, 128, True, heads=heads).astype(jnp.float32).sum(),
            argnums=(3, 4))(*a))(*args)
        return {e.params["name"]: e for e in _equations(jaxpr.jaxpr)
                if e.primitive.name == "pallas_call"}

    tiled, whole = calls(4), calls(8)
    for made, tiles, dtype in ((tiled, 2, jnp.float32), (whole, 1, jnp.bfloat16)):
        x, dtc, _, _, Bm = (v.aval for v in made["ssd_bwd"].invars[:5])
        assert x.shape == (1, 256, 1024) and Bm.shape == (1, 256, 256)
        assert dtc.shape == (1, 2 * tiles, 256, 8 // tiles)
        dB, dC = (v.aval for v in made["ssd_bwd"].outvars[4:])
        assert dB.shape == dC.shape == (1, 256, 2 * tiles * 128)
        assert dB.dtype == dC.dtype == dtype
        states = made["ssd_fwd"].outvars[1].aval
        assert states.shape == (1, 2, 2 * tiles, 128, 1024 // (2 * tiles))


def test_the_tile_is_chosen_by_what_the_backward_would_hold():
    """`head_tile`: all of a group's heads where `ssd_bwd`'s estimate
    stands under half of what a kernel may ask Mosaic for, else the most
    that do. Nemotron's group of 8 heads at chunks of 128 is one tile
    (6.3 MB); one group of 64 heads at chunks of 256 would hold 73.4 MB and
    goes 32 heads a tile, 38.8 MB, inside a limit of 77.6 MB of a v5e's
    128; the cell's shape tiles."""
    assert scan.head_tile(128, 128, 8, 64, 2) == 8
    assert scan.scan_vmem_bytes("ssd_bwd", 128, 128, 512, 2) == 6291456
    assert scan.scan_untiled(256, 128, 64, 64) is None
    assert scan.scan_vmem_bytes("ssd_bwd", 256, 128, 4096, 2) == 73400320
    assert scan.head_tile(256, 128, 64, 64, 2) == 32
    for kernel, need in (("ssd_fwd", 22020096), ("ssd_bwd", 38797312)):
        assert scan.scan_vmem_bytes(kernel, 256, 128, 2048, 2) == need
        assert need <= scan._STEP_VMEM == 48 << 20
        assert scan._vmem_limit(kernel, 256, 128, 2048, 2) == 2 * need
    # chunks of 512, heads of a whole tile, a lone head
    assert scan.head_tile(512, 128, 64, 64, 2) == 16
    assert scan.head_tile(128, 128, 4, 128, 2) == 4
    assert scan.head_tile(128, 128, 1, 128, 2) == 1


def test_the_log_line_says_the_head_tile(caplog):
    scan._log_scan.cache_clear()
    with caplog.at_level(logging.INFO, logger="ray_tpu.ops.ssd"):
        scan._log_scan(True, None, 1, 32768, 64, 64, 1, 128, 256, "bfloat16")
    (line,) = [r.getMessage() for r in caplog.records]
    assert "ssd_fwd and ssd_bwd, 32 heads a tile, grid (1, 2, 128)" in line
    assert "blocks [256, 2048] of x and [256, 128] of B and C" in line
    assert "a state of [128, 2048] float32" in line

