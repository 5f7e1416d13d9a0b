"""Regression tests for the pallas flash-attention backward, the chunked
LM-head cross entropy, and the Arrow tensor-column extension (all on the CPU
interpreter / CPU arrays — gradient parity against XLA reference math)."""

import numpy as np
import pytest

from ray_tpu.testing import force_cpu_mesh

force_cpu_mesh(8)  # before first backend use, like every jax-facing test

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
import jax.numpy as jnp  # noqa: E402

import importlib  # noqa: E402

import chip_smoke  # noqa: E402
from ray_tpu.ops.flash_attention import (  # noqa: E402
    _xla_attention_bhtd,
    flash_attention,
    flash_tiles,
    mha,
)
# the module: `ray_tpu.ops.flash_attention` is the function of that name
fa = importlib.import_module("ray_tpu.ops.flash_attention")
from ray_tpu.ops.fused import (  # noqa: E402
    HEAD_CHUNK,
    lm_head_cross_entropy,
    softmax_cross_entropy,
    weighted_lm_head_cross_entropy,
)


def _ref_mha(q, k, v, causal):
    import math

    B, T, H, D = q.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    of = _xla_attention_bhtd(
        qf, kf, vf, causal=causal, scale=1.0 / math.sqrt(D)
    )
    return of.reshape(B, H, T, D).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [128, 192])  # 192 exercises block padding
def test_flash_backward_matches_xla(causal, seq):
    q = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 2, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (2, seq, 2, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (2, seq, 2, 64), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=True).sum()

    def g(q, k, v):
        return _ref_mha(q, k, v, causal).sum()

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gg):
        np.testing.assert_allclose(a, b, atol=2e-4)


# The tile program. Tiles are forced small, so that one grid holds every
# kind of tile at once: wholly under the diagonal (no mask), crossed by it,
# wholly above it (no body, and the index maps clamp), and hanging over the
# end of a sequence that no block divides.
TILE_CASES = [
    # T, S, block_q, block_k, causal
    (320, 320, 64, 128, True),   # block_q < block_k, ragged k
    (200, 200, 128, 64, True),   # block_q > block_k, ragged q and k
    (200, 136, 64, 64, True),    # T > S: the last q rows see every key
    (136, 200, 64, 64, True),    # T < S: whole k columns have no body
    (200, 136, 128, 64, False),  # no diagonal: only the edges are masked
    (256, 256, 64, 128, False),  # nothing is masked at all
    (256, 256, 128, 128, True),  # interior, diagonal and skipped, no edge
]


def _tile_program_case(T, S, block_q, block_k, causal, dtype, heads=(2, 2),
                       widths=(32, 32)):
    """out, dq, dk, dv of the kernel (interpret mode) and of mha(impl="xla")
    under one random cotangent; q and k `widths[0]` wide, v `widths[1]`."""
    H, Hk = heads
    D, Dv = widths
    ks = jax.random.split(jax.random.PRNGKey(T + S), 4)
    q = jax.random.normal(ks[0], (1, T, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, S, Hk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, S, Hk, Dv), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[3], (1, T, H, Dv), jnp.float32)

    def run(attn):
        def scalar(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * w).sum(), out

        (_, out), grads = jax.value_and_grad(
            scalar, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True))
    want = run(lambda q, k, v: mha(q, k, v, causal=causal, impl="xla"))
    return got, want, (q, k, v)


def _rel_err(a, b):
    """max|a-b| / max|b|, as chip_smoke.check_flash_against_xla has it."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("T,S,block_q,block_k,causal", TILE_CASES)
def test_flash_tile_program_f32_matches_xla(T, S, block_q, block_k, causal):
    """f32 in, f32 all the way: today's f32 tolerances, which a kernel that
    rounded its operands or p to bf16 (2e-3 or worse) would not meet."""
    got, want, _ = _tile_program_case(
        T, S, block_q, block_k, causal, jnp.float32)
    np.testing.assert_allclose(got[0], want[0], atol=3e-5)
    for a, b in zip(got[1:], want[1:]):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, atol=2e-4)


@pytest.mark.parametrize("T,S,block_q,block_k,causal", TILE_CASES)
def test_flash_tile_program_bf16_matches_xla(T, S, block_q, block_k, causal):
    """bf16 operands on the MXU, p and ds rounded to bf16 for the second
    matmuls: inside the chip smoke's KERNEL_TOLERANCE, and the gradients
    come back in the input's dtype."""
    got, want, _ = _tile_program_case(
        T, S, block_q, block_k, causal, jnp.bfloat16)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        assert _rel_err(a, b) <= chip_smoke.KERNEL_TOLERANCE


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_tile_program_gqa(dtype):
    """Four query heads a key head, tiles forced small."""
    got, want, (q, k, v) = _tile_program_case(
        200, 200, 64, 128, True, dtype, heads=(4, 1))
    for a, b, x in zip(got[1:], want[1:], (q, k, v)):
        assert a.shape == x.shape and a.dtype == x.dtype
    tol = 2e-4 if dtype == jnp.float32 else chip_smoke.KERNEL_TOLERANCE
    for a, b in zip(got, want):
        assert _rel_err(a, b) <= tol


# The tile function: pure arithmetic on shapes, no device. The last kernel
# is the whole backward in one, which `flash_bwd_kernels` takes where a
# (batch, head) row's dq fits VMEM, and the two before it where not.
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dkv_dq")
ONE, TWO = KERNELS[3:], KERNELS[1:3]


def test_flash_default_tiles_are_the_shapes_choice():
    """`block_q=None` is `flash_tiles`' answer: forcing that answer changes
    no bit of the forward or of the gradients."""
    T, D = 192, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, T, 2, D), jnp.float32) for kk in ks)

    def grads(**blocks):
        return jax.value_and_grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True, **blocks
        ).sum(), argnums=(0, 1, 2))(q, k, v)

    tiles = {flash_tiles(kern, T, T, D, jnp.float32)[:2] for kern in KERNELS}
    assert tiles == {(128, 128)}  # one answer here, so it can be forced
    for a, b in zip(jax.tree.leaves(grads()),
                    jax.tree.leaves(grads(block_q=128, block_k=128))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kernel", KERNELS[:3])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_tiles_are_legal_for_any_shape(kernel, dtype):
    for T, S, D in [(4096, 4096, 128), (1024, 1024, 64), (96, 96, 64),
                    (192, 192, 64), (1000, 520, 64), (128, 4096, 128),
                    (32768, 32768, 128), (8192, 8192, 256), (64, 300, 32)]:
        for causal in (True, False):
            t = flash_tiles(kernel, T, S, D, dtype, causal=causal)
            for block, seq in ((t.block_q, T), (t.block_k, S)):
                assert block <= seq
                assert block % 128 == 0 or block == seq
            assert t.vmem_bytes < t.vmem_limit_bytes <= 96 << 20
            assert t.grid_steps == (
                -(-T // t.block_q) * -(-S // t.block_k))
            assert 0 < t.active_share <= 1
            assert causal or t.active_share == 1


@pytest.mark.parametrize("kernel,T,D,tile,steps,active", [
    # the two Mistral cells: BH 128 (64 a chip), T 4096, D 128
    ("flash_fwd", 4096, 128, (1024, 1024), 16, 10 / 16),
    ("flash_bwd_dq", 4096, 128, (1024, 1024), 16, 10 / 16),
    ("flash_bwd_dkv", 4096, 128, (1024, 1024), 16, 10 / 16),
    ("flash_bwd_dkv_dq", 4096, 128, (1024, 1024), 16, 10 / 16),
    # chip_smoke's GPT-2-small: T 1024, D 64
    ("flash_fwd", 1024, 64, (1024, 1024), 1, 1.0),
    ("flash_bwd_dq", 1024, 64, (512, 512), 4, 3 / 4),
    ("flash_bwd_dkv", 1024, 64, (512, 512), 4, 3 / 4),
    ("flash_bwd_dkv_dq", 1024, 64, (512, 512), 4, 3 / 4),
])
def test_flash_tiles_of_the_measured_shapes(kernel, T, D, tile, steps, active):
    t = flash_tiles(kernel, T, T, D, jnp.bfloat16)
    assert (t.block_q, t.block_k) == tile
    assert (t.grid_steps, t.active_share) == (steps, active)
    # what the kernel was before: 128 x 128, 1,024 steps a row at T 4096
    old = flash_tiles(kernel, T, T, D, jnp.bfloat16, block_q=128, block_k=128)
    n = T // 128
    assert old.grid_steps == n * n
    assert old.active_share == (n * (n + 1) // 2) / (n * n)
    assert old.vmem_limit_bytes == 16 << 20  # the default is enough there


def test_flash_tiles_forced_blocks_are_cut_to_the_sequence():
    t = flash_tiles("flash_fwd", 96, 200, 64, jnp.float32,
                    block_q=128, block_k=64)
    assert (t.block_q, t.block_k) == (96, 64)
    t = flash_tiles("flash_bwd_dkv", 4096, 4096, 128, jnp.bfloat16,
                    block_q=256)
    assert t.block_q == 256 and t.block_k % 128 == 0


@pytest.mark.parametrize("T,S,block_q,block_k", [
    (320, 320, 64, 128), (200, 136, 128, 64), (136, 200, 64, 64),
    (4096, 4096, 512, 1024), (1024, 1024, 1024, 256),
])
def test_flash_causal_tile_bookkeeping_matches_the_mask(T, S, block_q, block_k):
    """Which tiles have a body, which need the mask, how many there are and
    where the index maps' clamps point: all against the mask itself."""
    allowed = np.arange(T)[:, None] >= np.arange(S)[None, :]
    num_q, num_k = -(-T // block_q), -(-S // block_k)
    tile = lambda qi, ki: allowed[qi * block_q:(qi + 1) * block_q,
                                  ki * block_k:(ki + 1) * block_k]
    body = np.array([[tile(qi, ki).any() for ki in range(num_k)]
                     for qi in range(num_q)])
    assert fa._active_tiles(T, S, block_q, block_k, True) == body.sum()
    assert fa._active_tiles(T, S, block_q, block_k, False) == body.size
    for qi in range(num_q):
        for ki in range(num_k):
            has_body, needs_mask = fa._tile_kind(
                qi, ki, block_q=block_q, block_k=block_k, num_q=num_q,
                num_k=num_k, causal=True, seq_q=T, seq_k=S)
            assert bool(has_body) == body[qi, ki]
            full = tile(qi, ki).shape == (block_q, block_k)
            if body[qi, ki]:  # a bare body only where the mask is all true
                assert bool(needs_mask) == (not (full and tile(qi, ki).all()))
        last = min(int(fa._last_k_with_body(qi, block_q, block_k)), num_k - 1)
        assert last == np.flatnonzero(body[qi]).max()
    for ki in range(num_k):
        first = int(fa._first_q_with_body(ki, block_q, block_k, num_q))
        rows = np.flatnonzero(body[:, ki])
        assert first == (rows.min() if rows.size else num_q - 1)


# The whole backward in one kernel (PR 35): dq, dk and dv from one pass over
# the score tiles. Against the two kernels it stands for, tile for tile:
# the same p and ds, the same dots, dq summed over k tiles in the same
# order, so no bit differs.
ONE_KERNEL_CASES = [
    # T, S, block_q, block_k, causal, (D, Dv), dtype
    *[(*case, (32, 32), dtype) for case, dtype in zip(
        TILE_CASES, [jnp.float32, jnp.bfloat16] * 4)],
    (200, 200, 64, 128, True, (192, 128), jnp.bfloat16),   # latent attention's
    (256, 256, 128, 64, True, (192, 128), jnp.float32),    # two widths
    (320, 320, 128, 128, True, (64, 64), jnp.bfloat16),    # heads of 64
    (72, 72, None, None, True, (24, 16), jnp.float32),     # one tile, T < 128
]


@pytest.mark.parametrize("T,S,block_q,block_k,causal,widths,dtype",
                         ONE_KERNEL_CASES)
def test_flash_one_backward_kernel_is_the_two_bit_for_bit(
        T, S, block_q, block_k, causal, widths, dtype):
    D, Dv = widths
    ks = jax.random.split(jax.random.PRNGKey(T + S + D), 4)
    q, k, v, do = (
        jax.random.normal(kk, (2, n, w), jnp.float32).astype(dtype)
        for kk, n, w in zip(ks, (T, S, S, T), (D, D, Dv, Dv)))
    tile = dict(causal=causal, scale=D ** -0.5, block_q=block_q,
                block_k=block_k, interpret=True)
    o, lse = fa._flash_fwd(q, k, v, with_lse=True, **tile)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (2, T, 8))
    args = (q, k, v, do, lse, delta)
    two = (fa._flash_bwd_dq(*args, **tile), *fa._flash_bwd_dkv(*args, **tile))
    one = fa._flash_bwd_dkv(*args, with_dq=True, **tile)
    for got, want, x in zip(one, two, (q, k, v)):
        assert got.shape == x.shape and got.dtype == x.dtype
        np.testing.assert_array_equal(got, want)


def _pallas_calls(jaxpr):
    import re

    return set(re.findall(r"name=(flash_\w+)", str(jaxpr)))


@pytest.mark.parametrize("T,S,block_q,block_k,causal,widths,heads,dtype", [
    (200, 200, 64, 128, True, (192, 128), (2, 2), jnp.float32),
    (256, 256, 128, 64, True, (192, 128), (2, 2), jnp.bfloat16),
    (200, 136, 128, 64, False, (192, 128), (2, 2), jnp.float32),  # T != S
    (320, 320, 64, 128, True, (64, 64), (4, 1), jnp.bfloat16),    # GQA 4:1
    (320, 320, 128, 64, True, (64, 64), (4, 1), jnp.float32),
])
def test_flash_one_backward_kernel_matches_xla(
        T, S, block_q, block_k, causal, widths, heads, dtype):
    """Through `flash_attention`, which takes the one kernel here: two
    widths, heads of 64 under grouped queries, `block_q != block_k` both
    ways, at the tolerances the two kernels were held to."""
    got, want, (q, k, v) = _tile_program_case(
        T, S, block_q, block_k, causal, dtype, heads=heads, widths=widths)
    for a, b, x in zip(got[1:], want[1:], (q, k, v)):
        assert a.shape == x.shape and a.dtype == x.dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[0], want[0], atol=3e-5)
        for a, b in zip(got[1:], want[1:]):
            np.testing.assert_allclose(a, b, atol=2e-4)
    else:
        for a, b in zip(got, want):
            assert _rel_err(a, b) <= chip_smoke.KERNEL_TOLERANCE
    grad = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True).sum(), argnums=(0, 1, 2))
    assert _pallas_calls(jax.make_jaxpr(grad)(q, k, v)) == {
        "flash_fwd", "flash_bwd_dkv_dq"}


def test_flash_backward_takes_the_two_kernels_where_it_is_told_to(monkeypatch):
    """The same gradients whichever way `flash_bwd_kernels` answers."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 200, 2, 32), jnp.float32) for kk in ks)
    grad = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True).sum(),
        argnums=(0, 1, 2))
    one = grad(q, k, v)
    monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: TWO)
    assert _pallas_calls(jax.make_jaxpr(grad)(q, k, v)) == {"flash_fwd", *TWO}
    for a, b in zip(one, grad(q, k, v)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("T,D,Dv,dtype,kernels,tile", [
    # the token cells: every one takes the one kernel, at its parts' tile
    (8192, 192, 128, jnp.bfloat16, ONE, (1024, 1024)),  # dsv2lite.tokens8k
    (8192, 64, 64, jnp.bfloat16, ONE, (1024, 1024)),    # lfm2moe.tokens8k
    (4096, 128, 128, jnp.bfloat16, ONE, (1024, 1024)),  # mistral7b.*, olmoe.*
    (1024, 64, 64, jnp.bfloat16, ONE, (512, 512)),      # chip_smoke.py
    # longer rows leave the tile less room beside the row's blocks (1024 x
    # 512: at BH 8, 34.4 ms for the two kernels' 47.3, PERF.md section 6,
    # PR 35); since PR 75 rows of 192 leave a tile at a time too, padded to
    # 256 columns, and the sums alone leave the cheapest tile its room
    (16384, 192, 128, jnp.bfloat16, ONE, (1024, 1024)),
    # a row's dq with its block (its f32 sum, and the block twice) is 8 T
    # lanes(D) bytes in bf16: 67 MB at T 32768, D 192, where the limit of
    # 96 MiB allows an estimate of 48; the sum alone is 33.6 MB
    (32768, 192, 128, jnp.bfloat16, ONE, (512, 896)),
    # at widths of whole lanes (PR 49) the sum alone is 4 T lanes(D) bytes:
    # the cheapest tile again where the row's block left room for 768 x
    # 768, and the one kernel where it left room for none
    (32768, 128, 128, jnp.bfloat16, ONE, (1024, 1024)),
    (46080, 128, 128, jnp.bfloat16, ONE, (1024, 896)),
    (65536, 128, 128, jnp.bfloat16, ONE, (512, 1024)),
    (32768, 128, 128, jnp.float32, ONE, (1024, 896)),
    (98304, 128, 128, jnp.bfloat16, TWO, None),  # 50 MB of sum
    # heads of 64 fill a tile of lanes in VMEM as heads of 128 do, and
    # leave padded to it: the same tiles
    (65536, 64, 64, jnp.bfloat16, ONE, (512, 1024)),
    (98304, 64, 64, jnp.bfloat16, TWO, None),
    # where the row's blocks left room for tiles so small that their grid
    # steps cost more than the second pass at 1024 x 1024 does, the sums
    # alone leave room for 1024 x 768
    (22528, 192, 128, jnp.bfloat16, ONE, (1024, 768)),
    # and here they do not
    (90112, 128, 128, jnp.bfloat16, TWO, (256, 384)),
])
def test_flash_backward_kernels_of_a_shape(T, D, Dv, dtype, kernels, tile):
    assert fa.flash_bwd_kernels(T, T, D, dtype, v_dim=Dv) == kernels
    one = flash_tiles("flash_bwd_dkv_dq", T, T, D, dtype, v_dim=Dv)
    fits = one.vmem_limit_bytes <= fa._MAX_VMEM
    assert fits == (tile is not None)
    if fits:
        assert (one.block_q, one.block_k) == tile
        # the row's blocks wherever the cheapest tile has room beside them
        assert (one.exit == "block") == (T < 16384)
    two = sum(flash_tiles(kernel, T, T, D, dtype, v_dim=Dv).cost_us
              for kernel in TWO)
    assert (kernels == ONE) == (fits and one.cost_us <= two)


def test_flash_backward_plan_never_passes_the_vmem_limit():
    """Whatever the shape, every kernel the plan names has a tile that fits,
    and the forced tiles of the tests decide nothing about the answer."""
    for T, S, D, Dv in [(4096, 4096, 128, 128), (8192, 8192, 192, 128),
                        (96, 96, 64, 64), (1000, 520, 64, 32),
                        (128, 4096, 128, 128), (24576, 24576, 256, 256),
                        (65536, 65536, 64, 64), (131072, 131072, 192, 128),
                        (64, 300, 32, 32)]:
        for dtype in (jnp.bfloat16, jnp.float32):
            for causal in (True, False):
                kernels = fa.flash_bwd_kernels(T, S, D, dtype, causal=causal,
                                               v_dim=Dv)
                assert kernels in (ONE, TWO)
                for kernel in kernels:
                    t = flash_tiles(kernel, T, S, D, dtype, causal=causal,
                                    v_dim=Dv)
                    assert t.vmem_bytes < t.vmem_limit_bytes <= fa._MAX_VMEM
    assert fa.flash_bwd_kernels(200, 136, 32, jnp.float32, block_q=64,
                                block_k=128) == ONE
    # the cells' shapes with a group of query heads to a key-value head:
    # the one kernel, which then holds that head's whole dk and dv too
    # (16.8 MB more at T 8192, D 128, whatever the group), at the tile it
    # takes without a group
    for T, D in [(8192, 128), (4096, 128), (8192, 64), (4096, 64)]:
        for window in (None, 512):
            alone = flash_tiles("flash_bwd_dkv_dq", T, T, D, jnp.bfloat16,
                                window=window)
            for group in (4, 6, 8, 16):
                shape = dict(window=window, group=group)
                assert fa.flash_bwd_kernels(
                    T, T, D, jnp.bfloat16, **shape) == ONE
                t = flash_tiles("flash_bwd_dkv_dq", T, T, D, jnp.bfloat16,
                                **shape)
                assert t[:4] == alone[:4] and t.cost_us == alone.cost_us
                assert t.vmem_bytes < t.vmem_limit_bytes <= fa._MAX_VMEM
                rows = (T // t.block_k - 1) * t.block_k * 2 * 128
                assert t.vmem_bytes - alone.vmem_bytes == rows * (2 * 2 + 4)
                for kernel in ("flash_fwd", *TWO):  # a key tile's, as ever
                    assert flash_tiles(kernel, T, T, D, jnp.bfloat16,
                                       **shape) == flash_tiles(
                        kernel, T, T, D, jnp.bfloat16, window=window)
    assert flash_tiles("flash_bwd_dkv_dq", 8192, 8192, 128, jnp.bfloat16,
                       group=6).vmem_limit_bytes == fa._MAX_VMEM
    assert fa._pairs_factor("flash_bwd_dkv_dq", 192, 128) == 1.6  # 8 / 5
    assert fa._pairs_factor("flash_bwd_dkv_dq", 64, 64) == 1.0


def test_lm_head_ce_matches_dense():
    B, T, d, V = 2, 96, 32, 257  # deliberately non-multiples of the chunk
    hidden = jax.random.normal(jax.random.PRNGKey(0), (B, T, d), jnp.float32)
    unembed = jax.random.normal(jax.random.PRNGKey(1), (d, V), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)

    def chunked(h, w):
        loss, _ = lm_head_cross_entropy(h, w, targets, chunk_tokens=64)
        return loss

    def dense(h, w):
        logits = (h @ w).astype(jnp.float32)
        loss, _ = softmax_cross_entropy(logits, targets)
        return loss

    lc = chunked(hidden, unembed)
    ld = dense(hidden, unembed)
    np.testing.assert_allclose(lc, ld, rtol=1e-5)
    gc = jax.grad(chunked, argnums=(0, 1))(hidden, unembed)
    gd = jax.grad(dense, argnums=(0, 1))(hidden, unembed)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(a, b, atol=1e-4)


def _parent_lm_head_ce(hidden, unembed, targets, *, chunk_tokens,
                       ignore_index=-100):
    """`lm_head_cross_entropy` as it stood before its `custom_vjp`: autodiff
    through a scan of checkpointed chunks. Kept as the reference for the
    roundings of the hand-written gradients (the logits' f32 cotangent cast
    to the compute dtype before the two gradient matmuls)."""
    B, T, d = hidden.shape
    n = B * T
    h = hidden.reshape(n, d)
    t = targets.reshape(n)
    pad = (-n) % chunk_tokens
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)], axis=0)
        t = jnp.concatenate(
            [t, jnp.full((pad,), ignore_index, t.dtype)], axis=0)
    h = h.reshape(-1, chunk_tokens, d)
    t = t.reshape(-1, chunk_tokens)

    @jax.checkpoint
    def chunk_loss(hc, tc):
        logits = (hc @ unembed.astype(hc.dtype)).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.where(tc == ignore_index, 0, tc)
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        mask = (tc != ignore_index).astype(jnp.float32)
        return ((lse - picked) * mask).sum(), mask.sum()

    def body(carry, xs):
        ls, ns = chunk_loss(*xs)
        return (carry[0] + ls, carry[1] + ns), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (h, t))
    return loss_sum / jnp.maximum(count, 1.0)


def _ce_inputs(dtype=jnp.float32, V=257, B=2):
    T, d = 96, 32
    hidden = jax.random.normal(jax.random.PRNGKey(0), (B, T, d), dtype)
    unembed = jax.random.normal(jax.random.PRNGKey(1), (d, V), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
    return hidden, unembed, targets


def _chunked(h, w, targets, chunk_tokens=80):  # 192 rows: a ragged last chunk
    return lm_head_cross_entropy(h, w, targets, chunk_tokens=chunk_tokens)[0]


def _dense(h, w, targets):
    return softmax_cross_entropy((h @ w).astype(jnp.float32), targets)[0]


def _case_f32_ragged_last_chunk():
    h, w, t = _ce_inputs()
    return (lambda h, w: _chunked(h, w, t), lambda h, w: _dense(h, w, t),
            (h, w), dict(loss_rtol=1e-5, atol=1e-4))


def _case_bf16_hidden_against_the_parent():
    h, w, t = _ce_inputs(jnp.bfloat16)
    return (lambda h, w: _chunked(h, w, t),
            lambda h, w: _parent_lm_head_ce(h, w, t, chunk_tokens=80),
            (h, w), dict(loss_rtol=1e-6, rel=1e-2))


def _case_cotangent_not_one():
    h, w, t = _ce_inputs()

    def around(loss):
        return lambda h, w: (3.0 * loss(h, w, t)
                             + (h ** 2).mean() + (w ** 2).mean())

    return around(_chunked), around(_dense), (h, w), dict(
        loss_rtol=1e-5, atol=3e-4)


def _case_tied_embeddings():
    h, w, t = _ce_inputs()
    return (lambda h, embed: _chunked(h, embed.T, t),
            lambda h, embed: _dense(h, embed.T, t),
            (h, w.T), dict(loss_rtol=1e-5, atol=1e-4))


def _case_every_target_ignored():
    h, w, t = _ce_inputs()
    t = jnp.full_like(t, -100)
    return (lambda h, w: _chunked(h, w, t), lambda h, w: 0.0 * _dense(h, w, t),
            (h, w), dict(loss_rtol=0, atol=0))


def _sharded(axes, hidden_spec, unembed_spec):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import make_mesh

    h, w, t = _ce_inputs(V=256, B=4)
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n])
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    loss = lambda h, w: _chunked(h, w, t, chunk_tokens=64)
    return (loss, loss, (h, w), dict(loss_rtol=1e-6, atol=1e-5),
            (put(h, hidden_spec), put(w, unembed_spec)))


def _case_mesh_fsdp_4():  # Mistral's: tokens over the batch, unembed over d
    return _sharded({"fsdp": 4}, ("fsdp",), ("fsdp", None))


def _case_mesh_tensor_2():  # the vocabulary sharded
    return _sharded({"tensor": 2}, (), (None, "tensor"))


_CE_BACKWARD_CASES = [
    _case_f32_ragged_last_chunk, _case_bf16_hidden_against_the_parent,
    _case_cotangent_not_one, _case_tied_embeddings,
    _case_every_target_ignored, _case_mesh_fsdp_4, _case_mesh_tensor_2,
]


@pytest.mark.parametrize(
    "case", _CE_BACKWARD_CASES, ids=lambda f: f.__name__[len("_case_"):])
def test_lm_head_ce_backward(case):
    """The gradients `lm_head_cross_entropy` forms in its forward pass are
    those of the reference, under jit. A mesh case hands the jitted function
    sharded arguments and holds it to its own single-device result."""
    new, ref, args, tol, *placed = case()
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
    ln, gn = grad(new)(*(placed[0] if placed else args))
    lr, gr = grad(ref)(*args)
    np.testing.assert_allclose(ln, lr, rtol=tol["loss_rtol"])
    for a, b in zip(gn, gr):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        if "rel" in tol:
            assert np.linalg.norm(a - b) <= tol["rel"] * np.linalg.norm(b)
        else:
            np.testing.assert_allclose(a, b, atol=tol["atol"])


def test_lm_head_ce_undifferentiated_holds_no_weight_gradient():
    """Evaluation runs the scan without the gradient work: the lowered
    program has no f32 [d, V] array, which under `jax.grad` is the carried
    gradient to `unembed`. (Lowered, not compiled: the CPU's compiler widens
    a bf16 matmul's operands to f32 itself.)"""
    h, w, t = _ce_inputs(jnp.bfloat16)
    w = w.astype(jnp.bfloat16)
    carried = f"tensor<{w.shape[0]}x{w.shape[1]}xf32>"
    loss = lambda h, w: _chunked(h, w, t)
    text = lambda f: jax.jit(f).lower(h, w).as_text()
    assert carried in text(jax.grad(loss, argnums=1))
    assert carried not in text(loss)
    assert "stablehlo.while" in text(loss)  # still chunk by chunk


def test_lm_head_ce_ignore_index():
    hidden = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 16), jnp.float32)
    unembed = jax.random.normal(jax.random.PRNGKey(1), (16, 33), jnp.float32)
    targets = np.random.RandomState(0).randint(0, 33, (1, 8))
    targets[0, :4] = -100  # masked positions
    loss, n = lm_head_cross_entropy(
        hidden, unembed, jnp.asarray(targets), chunk_tokens=4
    )
    assert float(n) == 4.0
    logits = np.asarray(hidden[0] @ unembed, dtype=np.float64)
    lse = np.log(np.exp(logits).sum(-1))
    per = lse - logits[np.arange(8), np.where(targets[0] < 0, 0, targets[0])]
    expect = per[4:].mean()
    np.testing.assert_allclose(float(loss), expect, rtol=1e-5)


# ------------------------------------------- the head under weights a token

def _weights_like(targets, key=3):
    return jax.random.uniform(
        jax.random.PRNGKey(key), targets.shape, jnp.float32, 0.1, 2.0)


def _weighted_whole_logits(h, w, wt, targets, ignore_index=-100):
    """`sum_i w_i ce_i` by whole logits, in h's dtype as the head rounds."""
    logits = (h @ w.astype(h.dtype)).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.where(targets == ignore_index, 0, targets)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return ((lse - picked) * (targets != ignore_index) * wt).sum()


def _weighted_case_plain():
    h, w, t = _ce_inputs()
    return h, w, _weights_like(t), t, 80


def _weighted_case_ignored_targets():
    h, w, t = _ce_inputs()
    t = t.at[:, ::3].set(-100)
    return h, w, _weights_like(t), t, 80


def _weighted_case_stacked_streams():
    """Four streams over one head, targets tiled: `[4, B, T, d]`."""
    h, w, t = _ce_inputs()
    h = jnp.stack([h, 2 * h, h + 1, -h])
    t = jnp.broadcast_to(t, (4, *t.shape))
    return h, w, _weights_like(t), t, 64


def _weighted_case_one_chunk():
    h, w, t = _ce_inputs()
    return h, w, _weights_like(t), t, 4096


_WEIGHTED_CASES = [_weighted_case_plain, _weighted_case_ignored_targets,
                   _weighted_case_stacked_streams, _weighted_case_one_chunk]


@pytest.mark.parametrize(
    "case", _WEIGHTED_CASES, ids=lambda f: f.__name__[len("_weighted_case_"):])
def test_weighted_lm_head_ce_against_whole_logits(case):
    """`sum_i w_i ce_i`, and its gradients to the hidden rows, the
    unembedding and the weights (token i's cross-entropy, 0 at an ignored
    target), against whole logits; the second output is every token's
    cross-entropy and the count is the valid targets'."""
    h, w, wt, t, chunk = case()

    def chunked(h, w, wt):
        return lm_head_cross_entropy(
            h, w, t, chunk_tokens=chunk, weights=wt)[0]

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))
    ln, gn = grad(chunked)(h, w, wt)
    lr, gr = grad(lambda h, w, wt: _weighted_whole_logits(h, w, wt, t))(
        h, w, wt)
    np.testing.assert_allclose(ln, lr, rtol=1e-5)
    for a, b in zip(gn, gr):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-5)
    loss, count = lm_head_cross_entropy(h, w, t, chunk_tokens=chunk, weights=wt)
    assert float(count) == float((np.asarray(t) != -100).sum())
    total, ce = weighted_lm_head_cross_entropy(h, w, t, wt, chunk_tokens=chunk)
    assert ce.shape == t.shape and ce.dtype == jnp.float32
    np.testing.assert_allclose(gn[2], ce, rtol=1e-6)  # d loss / d w_i = ce_i
    np.testing.assert_allclose(float((ce * wt).sum()), float(total), rtol=1e-5)
    assert (np.asarray(ce)[np.asarray(t) == -100] == 0).all()


def test_weighted_lm_head_ce_with_uniform_weights_is_the_mean():
    """Weights of `1 / count` give `lm_head_cross_entropy`'s mean, gradients
    and all, in bf16 too (the same roundings: the weight is folded into
    dlogits where `mask / count` is)."""
    h, w, t = _ce_inputs(jnp.bfloat16)
    t = t.at[0, :7].set(-100)
    count = float((np.asarray(t) != -100).sum())
    uniform = jnp.full(t.shape, 1.0 / count, jnp.float32)
    mean = lambda h, w: _chunked(h, w, t)
    weighted = lambda h, w: lm_head_cross_entropy(
        h, w, t, chunk_tokens=80, weights=uniform)[0]
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
    lm, gm = grad(mean)(h, w)
    lw, gw = grad(weighted)(h, w)
    np.testing.assert_allclose(lw, lm, rtol=1e-6)
    for a, b in zip(gw, gm):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)


def test_weighted_lm_head_ce_holds_no_tokens_by_vocabulary_array():
    """Differentiated, the lowered program has the carried f32 [d, V]
    gradient once, the chunk's [chunk, V] logits, and no [tokens, V] array:
    three matmuls a chunk."""
    h, w, t = _ce_inputs(jnp.bfloat16)  # 192 tokens, V 257, chunks of 64
    wt = _weights_like(t)
    f = lambda h, w, wt: lm_head_cross_entropy(
        h, w, t, chunk_tokens=64, weights=wt)[0]
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(h, w, wt).as_text()
    assert "tensor<32x257xf32>" in text and "tensor<64x257xf32>" in text
    assert "192x257" not in text and "3x64x257" not in text
    assert text.count("stablehlo.dot_general") == 3
    assert "stablehlo.while" in text


def test_lm_head_ce_without_weights_traces_the_program_it_was():
    """`weights=None` is today's program: the same jaxpr, forward and
    differentiated, as calling the unweighted `custom_vjp` itself; the
    default chunk is the one name the rule prices the head by."""
    from ray_tpu.ops import fused

    h, w, t = _ce_inputs(jnp.bfloat16)
    public = lambda h, w: lm_head_cross_entropy(h, w, t, weights=None)[0]
    direct = lambda h, w: fused._lm_head_ce(h, w, t, HEAD_CHUNK, -100)[0]
    for wrap in (lambda f: f, lambda f: jax.grad(f, argnums=(0, 1))):
        assert str(jax.make_jaxpr(wrap(public))(h, w)) == str(
            jax.make_jaxpr(wrap(direct))(h, w))
    assert HEAD_CHUNK == 2048
    from ray_tpu.models import transformer

    assert transformer.HEAD_CHUNK is HEAD_CHUNK
    assert not hasattr(transformer, "_HEAD_CHUNK")


def test_tensor_column_roundtrip_through_blocks():
    import pyarrow as pa

    from ray_tpu.data import block as B
    from ray_tpu.data.tensor_extension import (
        is_tensor_type,
        tensor_column_to_numpy,
    )

    imgs = np.random.randint(0, 255, (16, 48), dtype=np.uint8)
    labels = np.arange(16, dtype=np.int64)
    blk = B.batch_to_block({"image": imgs, "label": labels})
    assert is_tensor_type(blk.schema.field("image").type)

    # numpy batch view is the stacked array (zero-copy reshape)
    batch = B.block_to_batch(blk, "numpy")
    np.testing.assert_array_equal(batch["image"], imgs)

    # slicing and concat preserve tensor semantics
    merged = B.concat_blocks([B.slice_block(blk, 0, 4), B.slice_block(blk, 4, 16)])
    np.testing.assert_array_equal(
        tensor_column_to_numpy(merged.column("image")), imgs
    )

    # rows come back as per-row ndarrays
    rows = B.block_to_rows(blk)
    assert isinstance(rows[0]["image"], np.ndarray)
    np.testing.assert_array_equal(rows[3]["image"], imgs[3])

    # rows_to_block stacks uniform ndarray rows back into a tensor column
    blk2 = B.rows_to_block(rows)
    assert is_tensor_type(blk2.schema.field("image").type)
    np.testing.assert_array_equal(
        B.block_to_batch(blk2, "numpy")["image"], imgs
    )


def test_tensor_column_through_object_store(ray_start_regular):
    import ray_tpu
    from ray_tpu.data import block as B

    imgs = np.random.randint(0, 255, (32, 1024), dtype=np.uint8)
    blk = B.batch_to_block({"image": imgs})
    ref = ray_tpu.put(blk)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(
        B.block_to_batch(out, "numpy")["image"], imgs
    )


def test_concat_mixed_tensor_and_ragged_blocks():
    """Blocks whose ndarray rows differ in shape across blocks must still
    concatenate (tensor columns downgrade to plain lists)."""
    from ray_tpu.data import block as B

    uniform = B.rows_to_block(
        [{"x": np.arange(4, dtype=np.int64)} for _ in range(3)]
    )
    other_shape = B.rows_to_block(
        [{"x": np.arange(6, dtype=np.int64)} for _ in range(2)]
    )
    ragged = B.rows_to_block(
        [{"x": np.arange(3, dtype=np.int64)}, {"x": np.arange(5, dtype=np.int64)}]
    )
    out = B.concat_blocks([uniform, other_shape, ragged])
    rows = B.block_to_rows(out)
    assert len(rows) == 7
    assert list(rows[0]["x"]) == [0, 1, 2, 3]
    assert list(rows[4]["x"]) == [0, 1, 2, 3, 4, 5]
    assert list(rows[6]["x"]) == [0, 1, 2, 3, 4]


def test_prefetch_iterator_early_exit_stops_producer():
    import threading
    import time

    from ray_tpu.data.iterator import prefetch_iterator

    cleaned = threading.Event()

    def gen():
        try:
            for i in range(1000):
                yield i
        finally:
            cleaned.set()

    it = prefetch_iterator(gen(), 2)
    assert next(it) == 0
    it.close()  # consumer abandons mid-stream
    # Fill thread must notice and run the generator's finally block.
    deadline = time.monotonic() + 5
    while not cleaned.is_set() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert cleaned.is_set(), "producer thread leaked after early exit"
    assert not any(
        t.name == "batch-prefetch" and t.is_alive()
        for t in threading.enumerate()
    )
