"""Attention under a window beside full attention in one stack, on the CPU
at a tiny size: the flash kernels' band in interpret mode against a masked
softmax (forward and all three gradients), the tile program's counts by
hand, the causal kernels' programs as they were, a layer of each type with
its head count, gate and rotary recipe and the whole loss against the
benchmark's plain reference, the shares of a routed layer against the uncut
layer, the published pattern of 40 layers, and what the new leaves and
widths mean to `param_shardings`, `_layer_widths` and `saved_activations`
(`ray_tpu/ops/flash_attention.py`, `ray_tpu/models/transformer.py`)."""

import dataclasses
import functools
import hashlib
import importlib
import logging
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import laguna as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    param_shardings, saved_activations, segments, transformer_init)
from ray_tpu.ops import moe
from ray_tpu.ops.flash_attention import flash_attention, flash_tiles, mha
from ray_tpu.parallel import make_mesh
from tiny_models import (
    as_reference_config, batch_of, distance, first_layer, init, key,
    one_device, program, value_and_grad)

fa = importlib.import_module("ray_tpu.ops.flash_attention")

# config.json's `rope_parameters.full_attention`, without theta and the share
YARN = {"rope_type": "yarn", "factor": 64, "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 4096,
        "attention_factor": 1.4158883083359672}
PATTERN = ("full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention")
LAGUNA = dict(
    vocab_size=128, d_model=64, n_layers=5, n_heads=4, n_heads_sliding=8,
    n_kv_heads=2, d_head=16, d_ff=32, d_ff_dense=96, d_ff_shared=32,
    max_seq_len=64, norm_eps=1e-6, layer_types=PATTERN + PATTERN[:1],
    n_dense_layers=1, sliding_window=8, attn_gate=True,
    rope_theta=500000.0, rope_theta_sliding=10000.0, partial_rotary_factor=0.5,
    # the ramp lies inside the tiny rotary width at an original context of 16
    rope_scaling=tuple(sorted(
        {**YARN, "original_max_position_embeddings": 16}.items())),
    n_experts=8, experts_per_token=3, experts_held=(2, 2), n_shared_experts=1,
    router_score="sigmoid", norm_topk_prob=True, routed_scaling_factor=2.5,
    router_aux_loss_coef=0.001, router_z_loss_coef=0.0,
    tied_embeddings=False, dtype=jnp.float32,
)


def tiny(**over):
    return TransformerConfig(**{**LAGUNA, **over})


def masked_softmax(q, k, v, window):
    """The band as it is written: `(j <= i) & (i - j < window)`."""
    T, D = q.shape[1], q.shape[3]
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = jnp.where((j <= i) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)


# ------------------------------------------------------ the kernels' band

# (T, window, block_q, block_k, D, Dv): the window smaller than a tile, equal
# to one, no multiple of one, wider than one with unlike tiles, a sequence
# that is no multiple of 128, v narrower than q and k, one key
BANDS = [
    (512, 64, 128, 128, 32, 32), (512, 128, 128, 128, 32, 32),
    (512, 200, 128, 128, 32, 32), (512, 300, 128, 256, 32, 32),
    (512, 256, 256, 128, 32, 32), (400, 130, 128, 128, 32, 32),
    (384, 129, 128, 128, 48, 32), (256, 1, 128, 128, 32, 32),
    (512, 511, 128, 128, 32, 32), (640, 257, None, None, 32, 32),
]


@pytest.mark.parametrize("two_kernels", [False, True], ids=["one", "two"])
@pytest.mark.parametrize("shape", BANDS, ids=lambda s: "x".join(map(str, s)))
def test_windowed_kernels_agree_with_a_masked_softmax(shape, two_kernels,
                                                      monkeypatch):
    T, window, bq, bk, D, Dv = shape
    if two_kernels:
        monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: (
            "flash_bwd_dq", "flash_bwd_dkv"))
    q, k, v, do = (jax.random.normal(key(i), (1, T, heads, width))
                   for i, (heads, width) in enumerate(
                       [(4, D), (2, D), (2, Dv), (4, Dv)]))
    kw = dict(causal=True, window=window, block_q=bq, block_k=bk,
              interpret=True)
    with jax.default_matmul_precision("highest"):
        ours = flash_attention(q, k, v, **kw)
        theirs = masked_softmax(q, k, v, window)
        np.testing.assert_allclose(ours, theirs, rtol=2e-5, atol=2e-6)
        grads = jax.grad(lambda *a: (flash_attention(*a, **kw) * do).sum(),
                         (0, 1, 2))(q, k, v)
        wanted = jax.grad(lambda *a: (masked_softmax(*a, window) * do).sum(),
                          (0, 1, 2))(q, k, v)
    for got, want in zip(grads, wanted):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [256, 300, 10 ** 6])
def test_a_window_no_shorter_than_the_sequence_is_the_causal_kernel(window):
    q, k, v = (jax.random.normal(key(i), (1, 256, 2, 32)) for i in range(3))
    kw = dict(causal=True, interpret=True)

    def text(**more):
        return str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
            *a, **kw, **more).sum(), (0, 1, 2)))(q, k, v))

    assert text(window=window) == text()
    assert "flash_fwd_window" not in text(window=window)
    assert "flash_fwd_window" in text(window=255)
    np.testing.assert_array_equal(
        flash_attention(q, k, v, window=window, **kw),
        flash_attention(q, k, v, **kw))


def test_keep_ctx_names_the_windowed_kernel_s_residuals():
    q, k, v = (jax.random.normal(key(i), (1, 256, 2, 32)) for i in range(3))
    kw = dict(causal=True, window=64, interpret=True, block_q=128, block_k=128)

    def loss(keep):
        return lambda *a: flash_attention(*a, keep_ctx=keep, **kw).sum()

    kept = jax.checkpoint(
        loss(True),
        policy=jax.checkpoint_policies.save_only_these_names("attn_ctx"))
    text = str(jax.make_jaxpr(jax.grad(kept, (0, 1, 2)))(q, k, v))
    assert text.count("name=attn_ctx") == 2  # o and lse
    assert text.count("name=flash_fwd_window") == 1  # not run again
    again = str(jax.make_jaxpr(jax.grad(
        jax.checkpoint(loss(False)), (0, 1, 2)))(q, k, v))
    assert again.count("name=flash_fwd_window") == 2
    with jax.default_matmul_precision("highest"):
        for got, want in zip(jax.grad(kept, (0, 1, 2))(q, k, v),
                             jax.grad(loss(False), (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_windowed_calls_have_names_of_their_own():
    q = jax.ShapeDtypeStruct((1, 512, 2, 32), jnp.float32)

    def names(two, **kw):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
            *a, causal=True, interpret=True, **kw).sum(), (0, 1, 2)))(q, q, q))
        return set(re.findall(r"name=(flash_\w+)", text))

    assert names(False, window=128) == {
        "flash_fwd_window", "flash_bwd_dkv_dq_window"}
    assert names(False) == {"flash_fwd", "flash_bwd_dkv_dq"}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: (
            "flash_bwd_dq", "flash_bwd_dkv"))
        assert names(True, window=128) == {
            "flash_fwd_window", "flash_bwd_dq_window", "flash_bwd_dkv_window"}
    # the live metrics' prefixes still find them
    for prefix in ("^flash_fwd", "^flash_bwd_dkv", "^flash_bwd_dq"):
        assert any(re.match(prefix, name) for name in names(False, window=128)
                   | {"flash_bwd_dq_window"})


def test_a_window_needs_causal_attention():
    q = jnp.zeros((1, 128, 1, 32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=8, interpret=True)
    with pytest.raises(ValueError, match="at least the token"):
        mha(q, q, q, causal=True, window=0, impl="xla")


def test_plain_attention_takes_the_same_mask():
    q, k, v = (jax.random.normal(key(i), (2, 96, heads, 16))
               for i, heads in enumerate([4, 2, 2]))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            mha(q, k, v, causal=True, window=17, impl="xla"),
            masked_softmax(q, k, v, 17), rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(
            mha(q, k, v, causal=True, window=96, impl="xla"),
            mha(q, k, v, causal=True, impl="xla"))


# ------------------------------------------------------- the tile program

def by_hand(T, window, bq, bk):
    """Tiles that hold a pair of the band, every pair looked at."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    band = (j <= i) & (i - j < window)
    return sum(
        bool(band[a:a + bq, b:b + bk].any())
        for a in range(0, T, bq) for b in range(0, T, bk))


@pytest.mark.parametrize("shape,tiles_with_a_body,band_k,band_q", [
    # 16 rows: the diagonal tile and the one before it, but for the first;
    # a column's own q tile and the one after it
    ((8192, 512, 512, 512), 31, 2, 2),
    # 8 rows of 1024: the diagonal tile and half of the one before it
    ((8192, 512, 1024, 1024), 15, 2, 2),
    # 16 rows of 512 against k tiles of 1024: an even row's first query
    # still sees the k tile before, an odd row's sees its own alone; a
    # column's keys are seen from its own two q tiles and the one after
    ((8192, 512, 512, 1024), 23, 2, 3),
    ((1024, 200, 128, 128), 8 + 7 + 6, 3, 3),  # 128 < 200 <= 257: three a row
    # 130 = 128 + 2: a row's first query sees two keys of the tile before the
    # last; the ragged fourth row has three tiles as the third has
    ((400, 130, 128, 128), 1 + 2 + 3 + 3, 3, 3),
])
def test_flash_tiles_counts_the_band_s_tiles(shape, tiles_with_a_body, band_k,
                                             band_q):
    T, window, bq, bk = shape
    num_q, num_k = -(-T // bq), -(-T // bk)
    assert by_hand(T, window, bq, bk) == tiles_with_a_body
    assert fa._active_tiles(T, T, bq, bk, True, window) == tiles_with_a_body
    # the grid is the band's: a row's walk over k is `band_k` steps, a
    # column's over q `band_q`, the most tiles any row (column) crosses
    for kernel, steps in (("flash_fwd", num_q * band_k),
                          ("flash_bwd_dq", num_q * band_k),
                          ("flash_bwd_dkv", num_k * band_q),
                          ("flash_bwd_dkv_dq", num_k * band_q)):
        tiles = flash_tiles(kernel, T, T, 128, jnp.bfloat16, block_q=bq,
                            block_k=bk, window=window)
        assert tiles.grid_steps == steps
        assert tiles.active_share == tiles_with_a_body / steps
        # without the window it is every tile's, as it was
        assert flash_tiles(kernel, T, T, 128, jnp.bfloat16, block_q=bq,
                           block_k=bk).grid_steps == num_q * num_k
    # without the window the count is the triangle's
    assert fa._active_tiles(T, T, bq, bk, True) == by_hand(T, T, bq, bk)


@pytest.mark.parametrize("T,window,bq,bk", [
    (1024, 200, 128, 256), (1024, 200, 256, 128), (1024, 512, 128, 128),
    (400, 130, 128, 128), (400, 130, 384, 128), (384, 1, 128, 128)])
def test_the_grid_s_bodies_and_fetches_are_the_band_s(T, window, bq, bk):
    """What `_tile_kind` gives a body, and what the index maps name, step
    by step of the band grid in both walks: every tile the band crosses is
    visited exactly once, no step outside the band computes, and none names
    a block that no body of its row (column) takes."""
    num_q, num_k = -(-T // bq), -(-T // bk)
    i, j = np.arange(num_q * bq)[:, None], np.arange(num_k * bk)[None, :]
    band = (j <= i) & (i - j < window)  # by tiles, as `_tile_kind` decides

    def crossed(qi, ki):
        return band[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]

    def kind(qi, ki):
        return fa._tile_kind(
            qi, ki, block_q=bq, block_k=bk, num_q=num_q, num_k=num_k,
            causal=True, seq_q=T, seq_k=T, window=window)

    rows = fa._band_rows(T, T, bq, bk, window)
    band_k = fa._inner_steps("flash_fwd", T, T, bq, bk, window)
    assert band_k == fa._inner_steps("flash_bwd_dq", T, T, bq, bk, window)
    assert band_k == max(sum(bool(crossed(qi, ki).any())
                             for ki in range(num_k)) for qi in range(num_q))
    k_block = fa._k_block_under_q(True, bq, bk, window, num_k)
    for qi in range(num_q):  # the forward's and dq's walk: k innermost
        with_body = [ki for ki in range(num_k) if crossed(qi, ki).any()]
        assert rows[qi] == (with_body[0], with_body[-1])
        first = int(fa._first_k_with_body(qi, bq, bk, window))
        assert first == with_body[0]
        visited = []
        for step in range(band_k):
            has_body, needs_mask = kind(qi, first + step)
            if bool(has_body):
                visited.append(first + step)
                assert bool(needs_mask) == (
                    not crossed(qi, first + step).all()
                    or (T % bk and first + step == num_k - 1)
                    or (T % bq and qi == num_q - 1))
            fetched = int(k_block(0, qi, step)[1])
            assert fetched in with_body
            assert fetched == (first + step if bool(has_body)
                               else with_body[-1])
        assert visited == with_body  # each once, in ascending order
    cols = fa._band_cols(T, T, bq, bk, window)
    band_q = fa._inner_steps("flash_bwd_dkv_dq", T, T, bq, bk, window)
    assert band_q == fa._inner_steps("flash_bwd_dkv", T, T, bq, bk, window)
    assert band_q == max(sum(bool(crossed(qi, ki).any())
                             for qi in range(num_q)) for ki in range(num_k))
    q_block = fa._q_block_under_k(True, bq, bk, num_q, window)
    for ki in range(num_k):  # the dk/dv walk: q innermost
        with_body = [qi for qi in range(num_q) if crossed(qi, ki).any()]
        assert cols[ki] == (with_body[0], with_body[-1])
        first = int(fa._first_q_with_body(ki, bq, bk, num_q))
        last = int(fa._last_q_with_body(ki, bq, bk, num_q, window))
        assert (first, last) == (with_body[0], with_body[-1])
        visited = []
        for step in range(band_q):
            has_body, _ = kind(first + step, ki)
            if bool(has_body):
                visited.append(first + step)
            fetched = int(q_block(0, ki, step)[1])
            assert fetched == (first + step if bool(has_body) else last)
        assert visited == with_body


def pallas_calls(fn, *args):
    """{name: grid} of every `pallas_call` that `fn(*args)` traces."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = (
                    eqn.params["grid_mapping"].grid,
                    eqn.params["compiler_params"]["mosaic_tpu"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "flash_bwd_dkv_dq"])
def test_the_grid_handed_to_pallas_call_is_the_band_s(kernel, monkeypatch):
    """(BH, q tiles, key tiles the band crosses) where k is walked
    innermost, (BH, key tiles, q tiles the band crosses) where q is; every
    tile's without a window, as it was."""
    if kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: (
            "flash_bwd_dq", "flash_bwd_dkv"))
    q = jax.ShapeDtypeStruct((2, 1024, 3, 32), jnp.float32)

    def grids(**kw):
        return pallas_calls(jax.grad(lambda *a: flash_attention(
            *a, causal=True, interpret=True, **kw).sum(), (0, 1, 2)), q, q, q)

    # window 200 at 128 x 256: a row crosses 2 key tiles, a column 4 q tiles
    band = grids(window=200, block_q=128, block_k=256)
    inner = 2 if kernel in ("flash_fwd", "flash_bwd_dq") else 4
    outer = 8 if kernel in ("flash_fwd", "flash_bwd_dq") else 4
    grid, params = band[kernel + "_window"]
    assert grid == (6, outer, inner)
    assert params.dimension_semantics == (
        "parallel", "arbitrary" if kernel == "flash_bwd_dkv_dq"
        else "parallel", "arbitrary")
    tiles = flash_tiles(kernel, 1024, 1024, 32, jnp.float32, block_q=128,
                        block_k=256, window=200)
    assert tiles.grid_steps == outer * inner
    assert params.vmem_limit_bytes == tiles.vmem_limit_bytes
    causal = grids(block_q=128, block_k=256)
    assert causal[kernel][0] == (6, outer, 12 - outer)
    # at the shape's own tile too: one step a row where a tile holds the band
    own = grids(window=200)[kernel + "_window"][0]
    assert own[1] * own[2] == flash_tiles(
        kernel, 1024, 1024, 32, jnp.float32, window=200).grid_steps


def walk_the_grid(kernel, T, window, bq, bk, monkeypatch):
    """The kernel's body run step by step over its grid with `pl.when`
    recording what fires: {(outer, step): [names]}, and the logical tile of
    every step that has a body."""
    from jax.experimental import pallas as pl

    num_q, num_k = -(-T // bq), -(-T // bk)
    steps = fa._inner_steps(kernel, T, T, bq, bk, window)
    at, fired = {}, {}

    def when(condition):
        def record(fn):
            if bool(condition):
                fired[at[1], at[2]].append(fn.__name__)
        return record

    monkeypatch.setattr(pl, "program_id", lambda axis: jnp.int32(at[axis]))
    monkeypatch.setattr(pl, "when", when)
    monkeypatch.setattr(pl, "multiple_of", lambda x, m: x)
    shape = dict(block_q=bq, block_k=bk, num_q=num_q, num_k=num_k,
                 steps=steps, scale=1.0, causal=True, seq_k=T, window=window)
    if kernel == "flash_fwd":
        body = functools.partial(fa._attn_fwd_kernel_lse, *[None] * 8, **shape)
    elif kernel == "flash_bwd_dq":
        body = functools.partial(
            fa._attn_bwd_dq_kernel, *[None] * 8, seq_q=T, **shape)
    else:
        with_dq = kernel == "flash_bwd_dkv_dq"
        body = functools.partial(
            fa._attn_bwd_dkv_kernel, *[None] * (12 if with_dq else 10),
            seq_q=T, with_dq=with_dq, **shape)
    k_inner = kernel in ("flash_fwd", "flash_bwd_dq")
    for outer in range(num_q if k_inner else num_k):
        for step in range(steps):
            at.update({1: outer, 2: step})
            fired[outer, step] = []
            body()
    return fired, steps, num_q, num_k


# (T, window, block_q, block_k): the first rows' band is one tile of two, the
# last columns' likewise; unlike tiles; ragged ends; one key; all but one
SHORT_ROWS = [(512, 64, 128, 128), (512, 300, 128, 256), (400, 130, 128, 128),
              (384, 129, 128, 128), (256, 1, 128, 128), (512, 511, 128, 128),
              (1024, 200, 256, 128)]


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "flash_bwd_dkv_dq"])
@pytest.mark.parametrize("shape", SHORT_ROWS,
                         ids=lambda s: "x".join(map(str, s)))
def test_the_sums_are_zeroed_and_flushed_once_a_walk(shape, kernel,
                                                     monkeypatch):
    """`_init` fires at a row's (column's) first step and `_flush` at its
    last, once each, whether or not the band reaches that step; a body runs
    at every tile the band crosses, once; and in the one-kernel backward a q
    tile's dq is zeroed before its first body and rounded out after its
    last, once each."""
    T, window, bq, bk = shape
    fired, steps, num_q, num_k = walk_the_grid(
        kernel, T, window, bq, bk, monkeypatch)
    k_inner = kernel in ("flash_fwd", "flash_bwd_dq")
    spans = (fa._band_rows if k_inner else fa._band_cols)(T, T, bq, bk, window)
    assert steps == max(last - first + 1 for first, last in spans)
    assert steps < (num_k if k_inner else num_q) or window > T - bk
    short = 0
    for outer, (first, last) in enumerate(spans):
        names = [fired[outer, step] for step in range(steps)]
        flat = [name for at_step in names for name in at_step]
        assert flat.count("_init") == 1 and "_init" in names[0]
        assert flat.count("_flush") == 1 and "_flush" in names[-1]
        if kernel == "flash_fwd":
            assert flat.count("_flush_lse") == 1 and flat[-1] == "_flush_lse"
        bodies = [step for step in range(steps) if "<lambda>" in names[step]]
        assert bodies == list(range(last - first + 1))
        assert all(names[step].count("<lambda>") == 1 for step in bodies)
        short += last - first + 1 < steps
    assert short or steps == 1  # some walk ends before the grid's does
    if kernel != "flash_bwd_dkv_dq":
        return
    for qi, (first_k, last_k) in enumerate(
            fa._band_rows(T, T, bq, bk, window)):
        events = [(ki, name) for ki in range(num_k) for step in range(steps)
                  for name in fired[ki, step]
                  if spans[ki][0] + step == qi and name != "_init"
                  and name != "_flush"]
        assert events[0] == (first_k, "_init_dq")
        assert events[-1] == (last_k, "_flush_dq")
        assert [ki for ki, name in events if name == "<lambda>"] == list(
            range(first_k, last_k + 1))  # ascending: dq's sum order
        assert len(events) == last_k - first_k + 3
    # and no step past a column's band touches a row of dq
    assert sum(name in ("_init_dq", "_flush_dq") for names in fired.values()
               for name in names) == 2 * num_q


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_kernel_s_dq_is_the_two_kernels_bit_for_bit(dtype):
    """A band three tiles wide: a q tile's dq is summed over three key
    columns of the one-kernel grid in the order `flash_bwd_dq` walks them."""
    T, window = 1024, 200
    q, k, v, do = (jax.random.normal(key(i), (2, T, 32)).astype(dtype)
                   for i in range(4))
    kw = dict(causal=True, scale=32 ** -0.5, block_q=128, block_k=128,
              interpret=True, window=window)
    assert fa._band_rows(T, T, 128, 128, window)[-1] == (5, 7)
    o, lse = fa._flash_fwd(q, k, v, with_lse=True, **kw)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (2, T, 8))
    dq, dk, dv = fa._flash_bwd_dkv(q, k, v, do, lse, delta, with_dq=True, **kw)
    assert dq.dtype == dtype and bool(jnp.isfinite(dq).all())
    np.testing.assert_array_equal(
        dq, fa._flash_bwd_dq(q, k, v, do, lse, delta, **kw))
    for got, want in zip((dk, dv),
                         fa._flash_bwd_dkv(q, k, v, do, lse, delta, **kw)):
        np.testing.assert_array_equal(got, want)
    assert float(jnp.abs(dq.astype(jnp.float32)).max()) > 0.01


def test_more_queries_than_keys_take_the_two_kernels():
    """A q tile past every key it could see is reached by no column of the
    one-kernel grid; `flash_bwd_dq` walks every q row and gives it zeros."""
    assert fa._band_rows(512, 128, 128, 128, 64) == [
        (0, 0), (0, 0), (1, 0), (2, 0)]
    assert fa.flash_bwd_kernels(512, 128, 32, jnp.float32, window=64) == (
        "flash_bwd_dq", "flash_bwd_dkv")
    assert fa.flash_bwd_kernels(512, 512, 32, jnp.float32, window=64) == (
        "flash_bwd_dkv_dq",)
    q = jax.random.normal(key(0), (1, 512, 2, 32))
    k, v = (jax.random.normal(key(i), (1, 128, 2, 32)) for i in (1, 2))
    kw = dict(causal=True, window=64, interpret=True, block_q=128, block_k=128)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, **kw)
        dq, dk, dv = jax.grad(lambda *a: flash_attention(*a, **kw).sum(),
                              (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(
            out[:, :128], masked_softmax(q[:, :128], k, v, 64),
            rtol=2e-5, atol=2e-6)
    # query 191 sees key 128, which is not there: nothing from 191 on
    assert float(jnp.abs(out[:, 191:]).max()) == 0
    assert float(jnp.abs(dq[:, 191:]).max()) == 0
    # (queries 0 and 190 see one key: no gradient through a softmax of 1)
    assert float(jnp.abs(dq[:, 1:190]).max(axis=(2, 3)).min()) > 0
    assert bool(jnp.isfinite(dk).all()) and bool(jnp.isfinite(dv).all())


def test_the_cost_model_chooses_for_the_band():
    """At the cell's shape the causal kernels take 1024 x 1024; under the
    window the tiles that divide the sequence are weighed on the band's
    grid, where a small tile no longer pays for the steps of the tiles the
    band leaves out. The backward kernels take 512 x 512, two tiles a row
    (column) and 31 of 32 steps with a body, which the sweep on one v5e
    confirmed (PERF.md section 6, PR 41: 11.46 ms a call, the least of
    {256, 512, 1024}^2). The forward takes 512 x 1024: 23 bodies of 32
    steps, an odd row's second step past its band. (The sweep has its
    512 x 512 a tenth faster: every body under a window is a masked one,
    and `_COST_US` has no term for what the mask costs at 1024 keys.)"""
    causal = flash_tiles("flash_fwd", 8192, 8192, 128, jnp.bfloat16)
    assert (causal.block_q, causal.block_k) == (1024, 1024)
    for kernel, tile, bodies in (("flash_fwd", (512, 1024), 23),
                                 ("flash_bwd_dkv_dq", (512, 512), 31),
                                 ("flash_bwd_dq", (512, 512), 31),
                                 ("flash_bwd_dkv", (512, 512), 31)):
        band = flash_tiles(kernel, 8192, 8192, 128, jnp.bfloat16, window=512)
        assert 8192 % band.block_q == 0 and 8192 % band.block_k == 0
        assert (band.block_q, band.block_k) == tile
        assert band.grid_steps == 32  # 128 and 256 on the grid of every tile
        assert band.active_share == bodies / 32
        assert band.cost_us < 0.4 * flash_tiles(
            kernel, 8192, 8192, 128, jnp.bfloat16).cost_us
        # by the constants as they are, at BH 128: ms a call
        assert band.cost_us * 128 / 1e3 == pytest.approx(
            {"flash_fwd": 8.18, "flash_bwd_dkv_dq": 9.12,
             "flash_bwd_dq": 6.94, "flash_bwd_dkv": 7.88}[kernel], abs=0.005)
    assert fa.flash_bwd_kernels(8192, 8192, 128, jnp.bfloat16, window=512) == (
        "flash_bwd_dkv_dq",)
    # a sequence no tile divides keeps every candidate
    assert fa._block_candidates(400, whole=True) == [128, 256, 384]
    assert fa._block_candidates(1024, whole=True) == [128, 256, 512, 1024]
    assert fa._block_candidates(1024) == list(range(128, 1025, 128))


def test_the_backward_logs_the_windowed_tiles_once(caplog):
    fa._log_bwd_kernels.cache_clear()
    q = jnp.ones((1, 512, 1, 32))
    grad = jax.grad(lambda *a: flash_attention(
        *a, causal=True, window=128, interpret=True).sum(), (0, 1, 2))
    with caplog.at_level(logging.INFO, logger=fa.logger.name):
        grad(q, q, q)
        grad(q * 2, q, q)
    lines = [r.getMessage() for r in caplog.records
             if "flash window" in r.getMessage()]
    assert len(lines) == 2  # the forward's and the one backward kernel's
    assert "flash_fwd_window" in lines[0] and "% of them with a body" in lines[0]
    assert "flash_bwd_dkv_dq_window" in lines[1]
    assert "window 128 at T 512" in lines[1]


# the causal kernels' programs as the parent traced them (jax 0.9.0):
# sha256 of the jaxpr of the forward and backward at q, k [1, 384, 2, 128]
# and v [1, 384, 2, 64] in bf16, interpret mode, addresses stripped
PARENT_JAXPRS = {
    (): "813b6907a7d62fce",
    (("block_k", 256), ("block_q", 128)): "53467ddc2dcdfd65",
    (("keep_ctx", True),): "b63b2462e16c5c2f",
}


@pytest.mark.parametrize("options", sorted(PARENT_JAXPRS), ids=str)
def test_without_a_window_the_kernels_jaxprs_are_the_parent_s(options):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jaxprs as jax 0.9.0 prints them")
    x = jax.ShapeDtypeStruct((1, 384, 2, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 384, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               **dict(options)).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, v))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert "window" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        PARENT_JAXPRS[options])


# ------------------------------------------------- the program, the reference

def test_parameter_tree_of_the_cut():
    cfg = tiny()
    assert [(s.periods, [(k.op, k.routed) for k in s.layout])
            for s in segments(cfg)] == [
        (1, [("full_attention", False)]),
        (1, [("sliding_attention", True)] * 3 + [("full_attention", True)])]
    (dense,), period = transformer_init(key(0), cfg)["blocks"]
    assert dense["wq"].shape == (1, 64, 4 * 16)
    assert dense["w_gate_attn"].shape == (1, 64, 4)
    assert dense["w_gate"].shape == (1, 64, 96) and "router" not in dense
    for blk, heads in zip(period, (8, 8, 8, 4)):
        assert blk["wq"].shape == (1, 64, heads * 16)
        assert blk["wo"].shape == (1, heads * 16, 64)
        assert blk["w_gate_attn"].shape == (1, 64, heads)
        assert blk["wk"].shape == blk["wv"].shape == (1, 64, 2 * 16)
        assert blk["w_gate"].shape == (1, 2, 64, 32)
        assert blk["router"].shape == (1, 64, 8)
        assert blk["ws_up"].shape == (1, 64, 32)
    assert "w_gate_attn" in model.own_buffer_weights(dense, cfg.layers[0])
    # without the new fields nothing of a plain stack's tree changes
    plain = transformer_init(key(0), TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2))
    assert sorted(plain["blocks"]) == [
        "attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo", "wq",
        "wv"]


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_a_layer_of_each_type_agrees_with_the_reference(kind):
    """Its head count, its rotary recipe, its mask and its gate."""
    cfg = tiny()
    heads = cfg.heads(kind)
    assert heads == (8 if kind == "sliding_attention" else 4)
    w = {k: v[0] for k, v in model._blocks_init(
        key(3), cfg, model.LayerKind(kind, False), 1).items()}
    x = jax.random.normal(key(4), (2, 40, 64))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    with jax.default_matmul_precision("highest"):
        ours = model._attention_layer(
            x, w, positions, cfg, None, 1, op=model._OPERATORS[kind])
        theirs = reference.attention(x, w, as_reference_config(cfg), kind)
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-5)
        # each part of it decides something, in the layers it belongs to
        config = as_reference_config(cfg)
        no_factor = {**config["rope_scaling"], "attention_factor": 1.0}
        for changed, belongs_to in (
                ({"sliding_window": 9}, "sliding_attention"),
                ({"rope_theta_sliding": 500000.0}, "sliding_attention"),
                ({"partial_rotary_factor": 1.0}, "full_attention"),
                ({"rope_scaling": no_factor}, "full_attention")):
            other = reference.attention(x, w, {**config, **changed}, kind)
            moved = float(jnp.abs(other - theirs).max()) > 1e-3
            assert moved == (kind == belongs_to), (kind, changed)
        ungated = reference.attention(
            x, {**w, "w_gate_attn": w["w_gate_attn"] * 0}, config, kind)
        # a gate of sigmoid(0) halves the context
        assert float(jnp.abs(ungated - theirs).max()) > 1e-3


def test_both_rotary_recipes_by_hand():
    cfg = tiny(rope_scaling=tuple(sorted(YARN.items())), d_head=128, n_heads=1,
               n_heads_sliding=1, n_kv_heads=1)
    theta, share, scaling = model._OPERATORS["full_attention"].rotary(cfg)
    assert (theta, share) == (500000.0, 0.5) and scaling["factor"] == 64
    assert model._OPERATORS["sliding_attention"].rotary(cfg) == (
        10000.0, 1.0, None)
    # 64 turned columns, theta 500000, factor 64 over 4096: dim(r) =
    # 64 ln(4096 / (2 pi r)) / (2 ln 500000) is 5.66 at 64 turns and 15.80 at
    # one, so the ramp runs from pair 5 to pair 16
    assert model.yarn_ramp_bounds(64, theta, scaling) == (5, 16)
    inv_freq, factor = model.rope_frequencies(64, theta, scaling)
    assert factor == pytest.approx(0.1 * math.log(64) + 1)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv_freq[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[16:], plain[16:] / 64, rtol=1e-6)
    ramp = (10 - 5) / 11
    np.testing.assert_allclose(
        inv_freq[10], plain[10] / 64 * ramp + plain[10] * (1 - ramp), rtol=1e-6)
    # the first 64 columns of a head turn, times the factor; the rest pass
    x = jax.random.normal(key(0), (1, 6, 1, 128))
    positions = jnp.arange(6)[None]
    turned = model._rotate(x, positions, theta, share, scaling)
    np.testing.assert_array_equal(turned[..., 64:], x[..., 64:])
    np.testing.assert_allclose(turned[0, 0, 0, :64], factor * x[0, 0, 0, :64],
                               rtol=1e-6)
    angle = 3 * float(inv_freq[2])
    np.testing.assert_allclose(
        turned[0, 3, 0, 2], factor * (x[0, 3, 0, 2] * math.cos(angle)
                                      - x[0, 3, 0, 34] * math.sin(angle)),
        rtol=1e-4, atol=1e-5)
    whole = model._rotate(x, positions, 10000.0)
    np.testing.assert_array_equal(whole, model._rope(x, positions, 10000.0))
    tables = reference.rotary_tables(as_reference_config(cfg), "full_attention")
    np.testing.assert_allclose(reference._rotate(x, *tables), turned,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_program_agrees_with_the_plain_reference(dtype):
    cfg = tiny(dtype=dtype)
    params = init(key(0), cfg)
    batch = batch_of(cfg)
    config = as_reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        (ours, readings), grads = program(cfg, params, batch)
        theirs, wanted = value_and_grad(
            lambda p: reference.loss(p, batch, config,
                                     readings["expert_index"]), params)
    if dtype == jnp.float32:
        assert abs(ours - theirs) / theirs < 1e-6
        assert distance(grads, wanted) < 1e-5
        chosen = jax.jit(
            lambda p: reference.forward(p, batch, config)[1])(params)
        ours_chosen = jax.nn.one_hot(readings["expert_index"], 8).sum(-2) > 0
        assert bool((chosen == ours_chosen).all())
    else:
        assert abs(ours - theirs) / theirs < 2e-3
        assert distance(grads, wanted) < 0.12
    assert readings["expert_load"].shape == (4, 8)
    assert int(readings["dropped_slots"].sum()) == 0
    # the mean over the layers of E sum_e f_e P_e, sigmoid scores: about E / 2
    assert 2.5 < float(readings["aux_loss"]) < 5.5


def test_the_shares_add_up_to_the_uncut_layer(monkeypatch):
    """8 experts held 2 a share: attention under the window, its gate and
    the shared expert are what every share computes alike, and counted once;
    the four shares' routed parts beside them are the uncut reference's
    layer."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    cfg = tiny(n_layers=1, layer_types=("sliding_attention",),
               n_dense_layers=0, experts_held=None)
    w = first_layer(cfg)
    x = jax.random.normal(key(5), (2, 48, 64))
    positions = jnp.broadcast_to(jnp.arange(48), (2, 48))
    config = as_reference_config(cfg)
    names = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        after_attention = reference.attention(
            x, w, config, "sliding_attention")
        whole, _, _ = reference.routed_feed_forward(after_attention, w, config)
        none_held = {**w, **{k: w[k][:0] for k in names}}
        alike = reference.routed_feed_forward(
            after_attention, none_held, {**config, "experts_held": (0, 0)})[0]
        parts = []
        for first in range(0, 8, 2):
            share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
            held = {**w, **{k: w[k][first:first + 2] for k in names}}
            out, readings = model._block(
                x, held, positions, None, share_cfg, cfg.layers[0], None, 1)
            assert int(readings["dropped_slots"]) == 0
            assert readings["expert_load"].shape == (8,)
            theirs, _, _ = reference.routed_feed_forward(
                after_attention, held, {**config, "experts_held": (first, 2)})
            np.testing.assert_allclose(out, theirs, rtol=2e-4, atol=2e-5)
            parts.append(out - alike)  # this share's routed part alone
    np.testing.assert_allclose(alike + sum(parts), whole, rtol=2e-4, atol=5e-5)
    # the shared expert is no small part of it
    assert float(jnp.abs(alike - after_attention).mean()) > 0.02


def test_the_published_pattern_of_forty_layers_builds_and_steps():
    """`layer_types`, `num_attention_heads_per_layer` and `mlp_layer_types`
    as config.json has them, at a tiny width: the cut is a cut of depth. The
    forty build (their state's shapes, no program); the cell's five, a dense
    and a routed layer of each type among them, step."""
    from chipbench import spec

    published = spec.load_cell(
        spec.ROOT, "lagunaxs2.tokens8k")["config"]["catalog_config"]
    kinds = tuple(published["layer_types"])
    assert len(kinds) == published["num_hidden_layers"] == 40
    assert kinds == PATTERN * 10
    cfg = tiny(d_model=32, d_head=8, n_heads=6, n_heads_sliding=8, d_ff=16,
               d_ff_dense=32, d_ff_shared=16, vocab_size=64, n_layers=40,
               layer_types=kinds,
               n_dense_layers=published["mlp_layer_types"].count("dense"))
    layers = cfg.layers
    assert sum(k.op == "full_attention" for k in layers) == 10
    assert sum(k.op == "sliding_attention" for k in layers) == 30
    assert [k.routed for k in layers] == [
        kind == "sparse" for kind in published["mlp_layer_types"]]
    # 48 and 64 heads by layer type, as published, at this test's 6 and 8
    assert [cfg.heads(k.op) * 8 for k in layers] == (
        published["num_attention_heads_per_layer"])
    state = jax.eval_shape(make_train_step(cfg, one_device())[0], key(0))
    trees = [blk for seg in state["params"]["blocks"] for blk in seg]
    assert len(trees) == 40
    assert [blk["wq"].shape[-1] // 8 for blk in trees] == [
        6 if kind == "full_attention" else 8 for kind in kinds]
    cut = dataclasses.replace(
        cfg, n_layers=5, layer_types=kinds[:5], n_dense_layers=1)
    assert set(cut.layers) == set(layers)
    init_state, step, _ = make_train_step(cut, one_device())
    state, out = step(init_state(key(0)), batch_of(cut, rows=1, seq=24))
    assert math.isfinite(float(out["loss"])) and float(out["grad_norm"]) > 0
    assert out["expert_load"].shape == (cut.n_routed_layers, 8) == (4, 8)


def test_a_window_under_a_sequence_axis_is_refused():
    cfg = tiny()
    kind = model.LayerKind("sliding_attention", False)
    w = {k: v[0] for k, v in model._blocks_init(key(0), cfg, kind, 1).items()}
    x = jnp.zeros((1, 16, 64))
    with pytest.raises(NotImplementedError, match="ring attention has no band"):
        model._block(x, w, jnp.zeros((1, 16), jnp.int32), None, cfg, kind,
                     "sequence", 2)


# ------------------------------------------------------- shardings, remat

def test_param_shardings_of_the_new_leaves():
    cfg = tiny()
    mesh = make_mesh({"fsdp": 4, "tensor": 2}, devices=jax.devices()[:8])
    shard = param_shardings(mesh, cfg)
    params = jax.eval_shape(lambda: transformer_init(key(0), cfg))
    assert jax.tree.structure(shard) == jax.tree.structure(params)
    (dense,), period = shard["blocks"]
    for blk in (dense, *period):
        # a column a head: cut along the heads, as wq's columns are
        assert blk["w_gate_attn"].spec == blk["wq"].spec
        assert blk["w_gate_attn"].spec[2] == "tensor"
    plain = param_shardings(mesh, TransformerConfig(n_layers=2))
    assert "w_gate_attn" not in plain["blocks"]


def test_layer_widths_by_hand_for_both_types():
    from chipbench import spec

    cell = spec.load_cell(spec.ROOT, "lagunaxs2.tokens8k")
    cfg = spec.load_code(spec.ROOT, "loops", "laguna").model_config(
        cell["config"])
    d = 2048
    full, sliding = (model.LayerKind("full_attention", True),
                     model.LayerKind("sliding_attention", True))
    widths, params = model._layer_widths(cfg, sliding)
    routed = d * 256 + 32 * 3 * d * 512 + 3 * d * 512
    assert params == 37879808 + routed
    assert widths == {
        "attn_ctx": 64 * 128 + 64 * 2,  # o, and lse as one f32 column a head
        "attn_res": d, "attn_qkv": (64 + 2 * 8) * 128,
        "shared_gate": 512, "shared_up": 512}
    widths, params = model._layer_widths(cfg, full)
    assert params == 29458432 + routed
    assert widths["attn_ctx"] == 48 * 128 + 48 * 2
    assert widths["attn_qkv"] == (48 + 2 * 8) * 128
    widths, params = model._layer_widths(
        cfg, model.LayerKind("full_attention", False))
    assert params == 29458432 + 3 * d * 8192
    assert widths["mlp_gate"] == widths["mlp_up"] == 8192
    # over the five layers, two sequences of 8192: bytes in bf16
    terms = model._terms(cfg, 16384, 4 * 691623936)
    saved = terms.saved_bytes()
    assert saved["attn_ctx"] == 16384 * 2 * (
        2 * (48 * 128 + 96) + 3 * (64 * 128 + 128))
    assert saved["attn_ctx"] / 1e9 == pytest.approx(1.22, abs=0.01)
    # the widest block in its backward is a sliding one: 64 heads' q, k, v
    # as the kernel takes them, and their lse and delta at a tile's lanes
    state = 12 * 691623936
    working = terms.at_once
    assert 3.0e9 < working < 4.2e9
    kept = saved_activations(cfg, 16384, state, 4 * 691623936, int(15.84e9))
    assert list(kept)[:1] in ([], ["attn_ctx"])
    assert list(kept) == list(model._SAVE_ORDER[i] for i in sorted(
        model._SAVE_ORDER.index(name) for name in kept))


def test_flops_count_the_band_and_the_heads_by_type():
    cfg = tiny()
    assert model.keys_per_query(8192, 512) == pytest.approx(496.03, abs=0.005)
    assert model.keys_per_query(8192) == 4096.5
    assert model.keys_per_query(300, 512) == 150.5
    matmul, attn, head = model._fwd_flops_per_token(cfg, 32)
    d, dh = 64, 16
    full = 2 * d * (4 + 2 * 2) * dh + 2 * 4 * dh * d + 2 * d * 4
    sliding = 2 * d * (8 + 2 * 2) * dh + 2 * 8 * dh * d + 2 * d * 8
    routed = 2 * d * 8 + (3 * 2 / 8) * 6 * d * 32 + 6 * d * 32
    assert matmul == 2 * full + 3 * sliding + 6 * d * 96 + 4 * routed
    band = (8 * 9 / 2 + 24 * 8) / 32
    assert attn == 2 * 4 * 4 * dh * 16.5 + 3 * 4 * 8 * dh * band
    assert head == 2 * d * 128
