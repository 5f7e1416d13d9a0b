"""The routed feed-forward's pieces (`ray_tpu/ops/moe.py`) and the model that
uses them, on the CPU: routing, the dropless sort under skew, the grouped
matmul and its two backward products (the XLA path and the Pallas kernels in
interpret mode) against a per-expert einsum, dispatch and combine, the down
projection and the combine as one operation against the two composed, the
two auxiliary losses against hand values, QK-norm, the step's readings, and
how many kernels and row gathers the lowered step holds."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, make_train_step, transformer_init
from ray_tpu.models.transformer import (
    flops_per_token, param_shardings, transformer_loss,
    transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.ops.fused import fused_rmsnorm
from ray_tpu.parallel import make_mesh
from tiny_models import key

TINY = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=64, tied_embeddings=False,
            n_experts=8, experts_per_token=2, qk_norm=True)


# ----------------------------------------------------------------- routing

@pytest.mark.parametrize("k", [1, 2, 8])
def test_route_is_top_k_of_the_float32_softmax(k):
    logits = jax.random.normal(key(k), (50, 16), jnp.bfloat16) * 3
    probs, weights, index = moe.route(logits, k)
    want = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w_want, i_want = jax.lax.top_k(want, k)
    assert probs.dtype == jnp.float32
    np.testing.assert_array_equal(index, i_want)
    np.testing.assert_array_equal(weights, w_want)
    # as they are: the k weights sum to less than 1
    assert float(weights.sum(-1).max()) < 1.0 or k == 16


def test_route_renormalizes_only_when_asked():
    logits = jax.random.normal(key(0), (20, 8))
    _, plain, index = moe.route(logits, 2)
    _, normed, index_n = moe.route(logits, 2, renormalize=True)
    np.testing.assert_array_equal(index, index_n)
    np.testing.assert_allclose(normed.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(normed, plain / plain.sum(-1, keepdims=True),
                               rtol=1e-6)


def skewed_index(case, tokens=24, k=2, n_experts=8):
    if case == "all_to_one":  # every token's first choice is expert 3
        return jnp.stack([jnp.full((tokens,), 3), jnp.arange(tokens) % 3], 1)
    if case == "one_empty":  # no token goes to expert 5
        a = jnp.arange(tokens) % 5
        return jnp.stack([a, (a + 1) % 5 + jnp.where(a == 4, 2, 0)], 1)
    return jax.random.randint(key(7), (tokens, k), 0, n_experts)


@pytest.mark.parametrize("case", ["all_to_one", "one_empty", "random"])
def test_sort_is_stable_and_dropless(case):
    index = skewed_index(case)
    slots = moe.sort_slots(index, 8)
    flat = np.asarray(index).reshape(-1)
    order = np.asarray(slots.order)
    assert int(slots.group_sizes.sum()) == flat.size  # every slot is kept
    np.testing.assert_array_equal(slots.group_sizes, np.bincount(flat, minlength=8))
    np.testing.assert_array_equal(order, np.argsort(flat, kind="stable"))
    np.testing.assert_array_equal(np.asarray(slots.inverse)[order],
                                  np.arange(flat.size))
    if case == "one_empty":
        assert int(slots.group_sizes[5]) == 0


# ---------------------------------------------------------- grouped matmul

def per_expert_einsum(x, w, sizes):
    """Row m times the weight of the group m falls in."""
    group = np.repeat(np.arange(len(sizes)), np.asarray(sizes))
    return jnp.einsum("mk,mkn->mn", x, w[group])


GROUPS = {
    "even": [16, 16, 16, 16],
    "ragged": [5, 0, 37, 22],      # no multiple of any tile, one empty
    "all_in_one": [0, 0, 64, 0],
    "empty_ends": [0, 30, 34, 0],
}
PATHS = {
    "xla": dict(impl="xla"),
    "kernels_tile8": dict(interpret=True, block_rows=8),
    "kernels_tile16": dict(interpret=True, block_rows=16),
    "kernels_own_tile": dict(interpret=True),
}


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("groups", GROUPS)
def test_grouped_matmul_and_both_gradients(groups, path):
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    m, k, n = int(sizes.sum()), 32, 48
    x = jax.random.normal(key(1), (m, k))
    w = jax.random.normal(key(2), (len(sizes), k, n))
    cot = jax.random.normal(key(3), (m, n))

    def system(x, w):
        return moe.grouped_matmul(x, w, sizes, **PATHS[path])

    out, vjp = jax.vjp(system, x, w)
    want, vjp_want = jax.vjp(lambda x, w: per_expert_einsum(x, w, sizes), x, w)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref in zip(vjp(cot), vjp_want(cot)):  # rows', then weights'
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5)
    if "empty" in groups or groups == "ragged":  # an empty group's gradient
        dw = vjp(cot)[1]
        empty = [e for e, s in enumerate(GROUPS[groups]) if s == 0]
        assert float(jnp.abs(dw[jnp.asarray(empty)]).max()) == 0.0


def test_kernels_take_bf16_rows_and_return_f32_weight_gradients():
    sizes = jnp.asarray([20, 44], jnp.int32)
    x = jax.random.normal(key(1), (64, 32), jnp.bfloat16)
    w = jax.random.normal(key(2), (2, 32, 16), jnp.float32)  # master weights

    def loss(x, w):
        return moe.grouped_matmul(
            x, w, sizes, interpret=True, block_rows=16).astype(jnp.float32).sum()

    dx, dw = jax.grad(loss, (0, 1))(x, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    want = jax.grad(lambda x, w: per_expert_einsum(
        x.astype(jnp.float32), w, sizes).sum(), 1)(x, w)
    # f32 accumulation of bf16 products, never rounded to bf16
    np.testing.assert_allclose(dw, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("transpose_w", [False, True])
def test_gmm_multiple_k_and_n_tiles(transpose_w):
    sizes = jnp.asarray([100, 0, 156], jnp.int32)
    x = jax.random.normal(key(1), (256, 256))
    w = jax.random.normal(key(2), (3, 256, 384))
    tiles = moe.GmmTiles(tm=64, tk=128, tn=128, vmem_limit_bytes=16 << 20)
    if transpose_w:
        got = moe.gmm(x, jnp.swapaxes(w, 1, 2), sizes, transpose_w=True,
                      tiles=tiles, interpret=True)
    else:
        got = moe.gmm(x, w, sizes, tiles=tiles, interpret=True)
    np.testing.assert_allclose(got, per_expert_einsum(x, w, sizes),
                               atol=1e-4, rtol=1e-4)
    dy = jax.random.normal(key(3), (256, 384))
    dw = moe.tgmm(x, dy, sizes, tiles=tiles, interpret=True)
    group = np.repeat(np.arange(3), np.asarray(sizes))
    want = jnp.stack([x[group == e].T @ dy[group == e] for e in range(3)])
    np.testing.assert_allclose(dw, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernel", ["moe_gmm", "moe_tgmm"])
def test_gmm_tiles_come_from_the_shape(kernel):
    tiles = moe.gmm_tiles(kernel, 131072, 2048, 1024, 64, jnp.bfloat16,
                          out_dtype=jnp.float32 if kernel == "moe_tgmm" else None)
    # the sweep's rule: K and N whole where VMEM allows, 256 rows a step
    assert (tiles.tm, tiles.tk, tiles.tn) == (256, 2048, 1024)
    assert tiles.vmem_limit_bytes <= 96 << 20
    wide = moe.gmm_tiles(kernel, 131072, 4096, 14336, 8, jnp.bfloat16)
    assert wide.tk == 4096 and 14336 % wide.tn == 0 and wide.tn % 128 == 0
    assert wide.vmem_limit_bytes <= 96 << 20 < 2 * moe._gmm_vmem(
        kernel, 256, 4096, 14336, 2, 2)  # the whole of both does not fit
    small = moe.gmm_tiles(kernel, 40, 32, 48, 4, jnp.float32)
    assert (small.tm, small.tk, small.tn) == (40, 32, 48)  # cut to the shape
    forced = moe.gmm_tiles(kernel, 4096, 256, 256, 8, jnp.bfloat16, tm=64)
    assert forced.tm == 64


# ---------------------------------------------------- dispatch and combine

def test_dispatch_and_combine_round_trip_with_gather_gradients():
    tokens, k, n_experts, d = 12, 2, 4, 8
    index = jax.random.randint(key(4), (tokens, k), 0, n_experts)
    slots = moe.sort_slots(index, n_experts)
    x = jax.random.normal(key(5), (tokens, d))
    weights = jax.random.uniform(key(6), (tokens, k))

    def system(x, weights):
        xs = moe.dispatch(x, slots.order, slots.inverse)
        return moe.combine(xs * 2.0, weights, slots.inverse)

    def plain(x, weights):  # every slot's row is its token's, times 2
        return (2.0 * x[:, None, :] * weights[..., None]).sum(1)

    np.testing.assert_allclose(system(x, weights), plain(x, weights), rtol=1e-6)
    got = jax.grad(lambda x, w: (system(x, w) ** 2).sum(), (0, 1))(x, weights)
    want = jax.grad(lambda x, w: (plain(x, w) ** 2).sum(), (0, 1))(x, weights)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


def test_combine_weights_are_not_renormalized():
    slots = moe.sort_slots(jnp.asarray([[0, 1]]), 2)
    weights = jnp.asarray([[0.3, 0.2]])
    out = moe.combine(jnp.ones((2, 4)), weights, slots.inverse)
    np.testing.assert_allclose(out, 0.5 * jnp.ones((1, 4)), rtol=1e-6)
    one = moe.project_and_combine(  # each expert's weight sums its row to 1
        jnp.ones((2, 3)), jnp.full((2, 3, 4), 1 / 3), weights, slots, impl="xla")
    np.testing.assert_allclose(one, out, rtol=1e-6)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", ["all_to_one", "one_empty", "random"])
def test_project_and_combine_is_the_two_composed_with_all_three_gradients(
        case, path):
    """Value, and the gradients of `hidden`, `w_down` and the router's
    `weights`, which the one operation takes on the hidden side and the
    composed pair (XLA's own transposes, a scatter-add) on the rows'."""
    tokens, k, n_experts, f, d = 24, 2, 8, 32, 48
    slots = moe.sort_slots(skewed_index(case), n_experts)
    hidden = jax.random.normal(key(1), (tokens * k, f))
    w_down = jax.random.normal(key(2), (n_experts, f, d)) / math.sqrt(f)
    weights = jax.random.uniform(key(3), (tokens, k))
    cot = jax.random.normal(key(4), (tokens, d))

    def system(hidden, w_down, weights):
        return moe.project_and_combine(hidden, w_down, weights, slots,
                                       **PATHS[path])

    def composed(hidden, w_down, weights):
        ys = moe.grouped_matmul(hidden, w_down, slots.group_sizes, impl="xla")
        return moe.combine(ys, weights, slots.inverse)

    out, vjp = jax.vjp(system, hidden, w_down, weights)
    want, vjp_want = jax.vjp(composed, hidden, w_down, weights)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    for got, ref in zip(vjp(cot), vjp_want(cot)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    if case == "one_empty":  # an expert of no rows gets a gradient of zeros
        assert float(jnp.abs(vjp(cot)[1][5]).max()) == 0.0


def test_project_and_combine_gives_f32_master_weights_an_f32_gradient():
    tokens, k, n_experts, f, d = 32, 2, 2, 32, 16
    slots = moe.sort_slots(
        jax.random.randint(key(4), (tokens, k), 0, n_experts), n_experts)
    hidden = jax.random.normal(key(1), (tokens * k, f), jnp.bfloat16)
    w_down = jax.random.normal(key(2), (n_experts, f, d), jnp.float32)
    weights = jax.random.uniform(key(3), (tokens, k))

    def loss(hidden, w_down, weights):
        return moe.project_and_combine(
            hidden, w_down, weights, slots, interpret=True,
            block_rows=16).astype(jnp.float32).sum()

    dh, dw_down, dweights = jax.grad(loss, (0, 1, 2))(hidden, w_down, weights)
    assert (dh.dtype, dw_down.dtype, dweights.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.float32)
    # f32 accumulation of bf16 products, never rounded to bf16: the weight is
    # rounded once, onto its bf16 row of `hidden`
    weighted = (weights.reshape(-1)[slots.order][:, None]
                * hidden.astype(jnp.float32)).astype(jnp.bfloat16)
    want = jax.grad(lambda w: per_expert_einsum(
        weighted.astype(jnp.float32), w, slots.group_sizes).sum())(w_down)
    np.testing.assert_allclose(dw_down, want, atol=1e-5, rtol=1e-5)


# ------------------------------------------- a share's rows to their tokens

HELD = (2, 3)  # experts 2, 3 and 4 of 8


def _held_index(case, tokens, k):
    """[tokens, k] experts of 8, no expert twice a token."""
    held = jnp.arange(HELD[0], HELD[0] + HELD[1])
    others = jnp.asarray([0, 1, 5, 6, 7])
    if case == "no_rows":  # no token names a held expert
        return others[(jnp.arange(tokens)[:, None] + jnp.arange(k)) % 5]
    if case == "one_expert_every_row_of_a_tile":  # expert 3 is everyone's first
        return jnp.stack([jnp.full((tokens,), 3), others[jnp.arange(tokens) % 5]], 1)
    scores = jax.random.uniform(key(11), (tokens, 8))
    index = jax.lax.top_k(scores, k)[1]
    # token 0: all its experts are held; token 1: none is
    return index.at[0].set(held[:k]).at[1].set(others[:k])


# case: (tokens, k, d, rows' dtype, the buffer's rows as a share of the held
# rows (None: 16 rows that hold nothing), chunk, tokens a tile)
SUMS = {
    "no_rows": (256, 2, 128, jnp.bfloat16, None, 0, 128),
    "under_a_chunk": (256, 2, 128, jnp.bfloat16, 1.3, 0, 128),
    "a_whole_chunk": (256, 2, 128, jnp.bfloat16, 0.6, 0, 128),
    "the_second_chunk": (256, 2, 128, jnp.bfloat16, 0.6, 1, 128),
    "one_expert_every_row_of_a_tile": (256, 2, 128, jnp.bfloat16, 1.1, 0, 128),
    "three_of_three_held": (256, 3, 128, jnp.bfloat16, 1.3, 0, 128),
    "tokens_no_whole_tile": (200, 2, 128, jnp.bfloat16, 1.3, 0, 128),
    "the_shape_s_own_tile": (256, 2, 256, jnp.bfloat16, 1.3, 0, None),
    "float32_rows": (256, 2, 128, jnp.float32, 1.3, 0, 128),
}


@pytest.mark.parametrize("use", ["combine", "dispatch_gradient"])
@pytest.mark.parametrize("case", SUMS)
def test_moe_sum_is_the_scatter_add_of_the_held_rows(case, use):
    """`moe_sum` in interpret mode against `_to_tokens`: each held row times
    its slot's float32 weight (combine) or two buffers' rows as they are
    (the dispatch's gradient), onto their tokens in float32. Every row
    behind `rows` is NaN, and nothing of it arrives."""
    tokens, k, d, dtype, share, chunk_i, tt = SUMS[case]
    index = _held_index(case, tokens, k)
    slots = moe.sort_slots(index, 8, HELD)
    total = int(slots.group_sizes.sum())
    n = 16 if share is None else int(share * total) // 16 * 16
    order, n_chunks = moe._chunks(slots, n)
    part = moe._chunk_of(slots, order, chunk_i, n)
    rows = part.group_sizes.sum()
    assert int(n_chunks) == (0 if share is None else 1 if share > 1 else 2)
    assert int(rows) == (total if int(n_chunks) < 2 else (n, total - n)[chunk_i])
    live = (jnp.arange(n) < rows)[:, None]
    ys, more = (jnp.where(live, jax.random.normal(key(i), (n, d), dtype),
                          jnp.nan) for i in (1, 2))
    # none of these is a bf16 value, nor is its first bf16 part's remainder
    weights = 1 / 3 + jax.random.uniform(key(3), (tokens, k)) / 1024
    assert float(jnp.abs(
        weights - weights.astype(jnp.bfloat16).astype(jnp.float32)).min()) > 0
    weighted = use == "combine"
    tiles = tt and moe.sum_tiles(n, tokens, d, HELD[1], dtype, tt=tt,
                                 out_dtype=jnp.float32, weighted=weighted,
                                 sources=2 - weighted)
    if case == "one_expert_every_row_of_a_tile":
        # a run of four or five windows in rounds of three: the walk goes
        # on into a second round, and ends inside it
        assert int(part.group_sizes[1]) == tokens and tiles.tt == 4 * tiles.window
        tiles = tiles._replace(windows=3)
    got = moe.sum_held(
        (ys,) if weighted else (ys, more), part.inverse, part.group_sizes,
        rows, tokens, weights=weights if weighted else None,
        out_dtype=jnp.float32, tiles=tiles or None, interpret=True)

    def want(weights):
        rows32 = ys.astype(jnp.float32)
        rows32 = (rows32 * weights.reshape(-1)[part.order][:, None] if weighted
                  else rows32 + more.astype(jnp.float32))
        return moe._to_tokens(rows32, part.order, rows, tokens, k)

    assert got.shape == (tokens, d) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    # the order of a token's k adds is all that may differ
    np.testing.assert_allclose(got, want(weights), rtol=3e-7, atol=3e-7)
    held = np.asarray((index >= HELD[0]) & (index < HELD[0] + HELD[1]))
    if share is None:
        assert not held.any() and float(jnp.abs(got).max()) == 0.0
    elif case not in ("one_expert_every_row_of_a_tile", "three_of_three_held"):
        assert held[0].all() and not held[1].any()
        np.testing.assert_array_equal(got[1], 0.0)
    if weighted and int(rows):  # weights cut to bf16 are another sum
        cut = want(weights.astype(jnp.bfloat16).astype(jnp.float32))
        assert float(jnp.abs(cut - got).max()) > 1e-4


def test_moe_sum_adds_onto_a_float32_sum_in_its_buffer():
    tokens, k, d = 256, 2, 128
    slots = moe.sort_slots(_held_index("random", tokens, k), 8, HELD)
    n = int(slots.group_sizes.sum()) // 16 * 16 + 32
    order, _ = moe._chunks(slots, n)
    part = moe._chunk_of(slots, order, 0, n)
    rows = part.group_sizes.sum()
    ys = jax.random.normal(key(1), (n, d), jnp.bfloat16)
    weights = jax.random.uniform(key(3), (tokens, k))
    onto = jax.random.normal(key(4), (tokens, d))
    got = moe.combine_held(ys, weights, part, rows, kernels=True,
                           interpret=True, onto=onto)
    want = moe.combine_held(ys, weights, part, rows, onto=onto)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    alone = moe.combine_held(ys, weights, part, rows, kernels=True,
                             interpret=True)
    assert alone.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        alone, moe.combine_held(ys, weights, part, rows))


@pytest.mark.parametrize("kind,shape,expected", [
    # mellum2.ep4: 180,224 rows of 16 experts onto 65,536 tokens of 2304;
    # the weighted sum's three passes of the MXU are cheaper at 128 tokens
    ("forward", (180224, 65536, 2304, 16), (128, 23)),
    ("backward", (180224, 65536, 2304, 16), (256, 34)),
    # dsv2lite.tokens8k, lagunaxs2.tokens8k (a run is about 8 rows)
    ("forward", (33792, 32768, 2048, 8), (256, 15)),
    ("backward", (22528, 16384, 2048, 32), (256, 36)),
    ("forward", (64, 40, 128, 3), (40, 5)),  # fewer tokens than a tile
])
def test_sum_tiles_come_from_the_shape(kind, shape, expected):
    n, tokens, d, n_held = shape
    forward = kind == "forward"
    tiles = moe.sum_tiles(n, tokens, d, n_held, jnp.bfloat16,
                          out_dtype=jnp.float32 if forward else None,
                          weighted=forward, sources=1 if forward else 2,
                          onto=forward)
    assert (tiles.tt, tiles.windows) == expected
    assert (tiles.window, tiles.align, tiles.parts) == (32, 16, 3 if forward else 1)
    # a round holds the buffer's even part of a tile, and for every expert
    # the half window and half packed tile a run's ends leave empty; and no
    # fewer windows than the experts and an eighth more
    even = -(-n // -(-tokens // tiles.tt))
    assert tiles.windows == max(-(-(even + 24 * n_held) // 32),
                                n_held + -(-n_held // 8))
    assert tiles.vmem_limit_bytes <= 96 << 20
    # where 256 tokens' windows have no room in VMEM the tile is 128
    wide = moe.sum_tiles(n, tokens, 16 * d, n_held, jnp.bfloat16,
                         weighted=False, sources=2)
    if tokens > 256:
        assert wide.tt == 128 and wide.vmem_limit_bytes > 96 << 20
    assert moe.sum_tiles(n, tokens, d, n_held, jnp.float32).window == 16


def _share_kernels_in_interpret_mode(monkeypatch):
    monkeypatch.setattr(moe, "_kernels", lambda impl, interpret: impl != "xla")
    for name in ("gmm", "tgmm", "sum_held"):
        fn = getattr(moe, name)
        monkeypatch.setattr(moe, name, lambda *a, _fn=fn, **kw: _fn(
            *a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("gated", [True, False])
def test_experts_of_share_with_the_kernels_has_the_reference_s_gradients(
        gated, monkeypatch):
    """Two chunks of held rows through dispatch, the experts and the combine:
    value and every gradient with `moe_sum` (and the grouped matmul's
    kernels) in interpret mode against the `jax.numpy` path's."""
    tokens, k, d, f = 128, 2, 128, 128
    index = _held_index("random", tokens, k)
    slots = moe.sort_slots(index, 8, HELD)
    chunk = int(slots.group_sizes.sum()) // 2 // 8 * 8 + 8
    assert int(moe._chunks(slots, chunk)[1]) == 2
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    x = jax.random.normal(key(1), (tokens, d))
    w_gate = jax.random.normal(key(2), (HELD[1], d, f)) / 8 if gated else None
    w_up = jax.random.normal(key(3), (HELD[1], d, f)) / 8
    w_down = jax.random.normal(key(4), (HELD[1], f, d)) / 8
    weights = jax.random.uniform(key(5), (tokens, k))
    cot = jax.random.normal(key(6), (tokens, d))

    def layer(impl):
        return lambda *a: moe.experts_of_share(
            a[0], w_gate if w_gate is None else a[4], a[1], a[2], a[3], slots,
            chunk=chunk, impl=impl)

    args = (x, w_up, w_down, weights) + ((w_gate,) if gated else ())
    want, pull_want = jax.vjp(layer("xla"), *args)
    _share_kernels_in_interpret_mode(monkeypatch)
    got, pull = jax.vjp(layer("pallas"), *args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for g, r in zip(pull(cot), pull_want(cot)):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)
    none_held = ~np.asarray(
        ((index >= HELD[0]) & (index < HELD[0] + HELD[1])).any(axis=1))
    assert none_held.any()
    np.testing.assert_array_equal(np.asarray(got)[none_held], 0.0)


def test_every_call_site_of_one_use_shares_sum_held_s_trace(monkeypatch):
    """Three layers in a Python loop, each `experts_of_share` differentiated:
    six call sites of `sum_held` and two distinct uses (the forward's
    weighted sum onto the loop's buffer, the backward's of two buffers), so
    the lowered program holds two functions of that name, each traced and
    lowered once. A kernel that every site traced anew cost set-up 0.1 to
    0.25 s a site in every program (PERF.md section 5)."""
    import re

    tokens, k, d, f = 128, 2, 128, 128
    slots = moe.sort_slots(_held_index("random", tokens, k), 8, HELD)
    chunk = int(slots.group_sizes.sum()) // 8 * 8 + 8
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    _share_kernels_in_interpret_mode(monkeypatch)
    ws = [tuple(jax.random.normal(key(3 * i + j), shape) / 8 for j, shape in
                enumerate([(HELD[1], d, f), (HELD[1], d, f), (HELD[1], f, d)]))
          for i in range(3)]
    weights = jax.random.uniform(key(20), (tokens, k))

    def loss(x, ws):
        for w_gate, w_up, w_down in ws:
            x = x + moe.experts_of_share(x, w_gate, w_up, w_down, weights,
                                         slots, chunk=chunk, impl="pallas")
        return x.sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        jax.random.normal(key(21), (tokens, d)), ws).as_text()
    assert len(re.findall(r"func\.func private @_sum_held", text)) == 2
    assert len(re.findall(r"call @_sum_held", text)) == 6


# ------------------------------------------------------------ aux losses

def test_load_balancing_loss_hand_values():
    uniform = jnp.full((6, 4), 0.25)
    assert float(moe.load_balancing_loss(uniform, jnp.asarray([3, 3, 3, 3]))) \
        == pytest.approx(1.0)
    # all slots and all probability on expert 0: E * 1 * 1
    peaked = jnp.asarray([[1.0, 0.0, 0.0, 0.0]] * 5)
    assert float(moe.load_balancing_loss(peaked, jnp.asarray([10, 0, 0, 0]))) \
        == pytest.approx(4.0)
    # f = (3/4, 1/4, 0, 0), P = (0.5, 0.3, 0.1, 0.1): 4 * (0.375 + 0.075)
    probs = jnp.asarray([[0.5, 0.3, 0.1, 0.1]] * 2)
    assert float(moe.load_balancing_loss(probs, jnp.asarray([3, 1, 0, 0]))) \
        == pytest.approx(1.8)


def test_router_z_loss_hand_values():
    # logsumexp of n zeros is log n
    assert float(moe.router_z_loss(jnp.zeros((7, 8)))) == pytest.approx(
        math.log(8) ** 2, rel=1e-6)
    logits = jnp.asarray([[0.0, 0.0], [math.log(3.0), 0.0]])
    want = (math.log(2) ** 2 + math.log(4) ** 2) / 2
    assert float(moe.router_z_loss(logits)) == pytest.approx(want, rel=1e-6)


# ------------------------------------------------------------------- model

def tiny(**over):
    return TransformerConfig(**{**TINY, "dtype": jnp.float32, **over})


def tiny_batch(cfg, rows=3, seq=32, seed=1):
    ids = jax.random.randint(key(seed), (rows, seq + 1), 0, cfg.vocab_size)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


def test_routed_model_parameter_tree_and_shardings():
    cfg = tiny()
    params = transformer_init(key(0), cfg)
    shapes = {k: v.shape for k, v in params["blocks"].items()}
    assert shapes["router"] == (2, 64, 8)
    assert shapes["w_gate"] == shapes["w_up"] == (2, 8, 64, 128)
    assert shapes["w_down"] == (2, 8, 128, 64)
    assert shapes["q_norm"] == (2, 64) and shapes["k_norm"] == (2, 32)
    mesh = make_mesh({"expert": 2, "fsdp": 2}, devices=jax.devices()[:4])
    shard = param_shardings(mesh, cfg)
    assert jax.tree.structure(shard) == jax.tree.structure(params)
    assert shard["blocks"]["w_gate"].spec == (None, "expert", "fsdp", None)
    assert shard["blocks"]["w_down"].spec == (None, "expert", None, "fsdp")


def test_qk_norm_is_an_rmsnorm_of_the_whole_projection():
    cfg, plain = tiny(), tiny(qk_norm=False)
    params = transformer_init(key(0), cfg)
    batch = tiny_batch(cfg)
    # scales of ones still normalise: the loss differs from the un-normed one
    bare = {**params, "blocks": {k: v for k, v in params["blocks"].items()
                                 if k not in ("q_norm", "k_norm")}}
    with_norm = float(transformer_loss(params, batch, cfg))
    assert with_norm != pytest.approx(
        float(transformer_loss(bare, batch, plain)), rel=1e-6)
    # scaling wq is undone by the norm over the whole 64-wide projection
    scaled = {**params, "blocks": {**params["blocks"],
                                   "wq": params["blocks"]["wq"] * 3.0}}
    assert float(transformer_loss(scaled, batch, cfg)) == pytest.approx(
        with_norm, rel=1e-4)
    x = jax.random.normal(key(2), (5, 64))
    np.testing.assert_allclose(
        fused_rmsnorm(x, jnp.ones(64), eps=1e-5),
        x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-5), rtol=1e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_step_readings_and_dropless_load(remat):
    cfg = tiny(remat=remat)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init, step, _ = make_train_step(cfg, mesh)
    state = init(key(0))
    batch = tiny_batch(cfg, rows=4, seq=64)
    state, out = step(state, batch)
    assert set(out) == {"loss", "grad_norm", "aux_loss", "z_loss", "expert_load"}
    assert out["expert_load"].shape == (2, 8)
    # dropless: every layer computes tokens x experts_per_token slots
    np.testing.assert_array_equal(out["expert_load"].sum(-1), [4 * 64 * 2] * 2)
    assert float(out["aux_loss"]) >= 1.0 - 1e-5 and float(out["z_loss"]) > 0
    first = float(out["loss"])
    for _ in range(3):
        state, out = step(state, batch)
    assert float(out["loss"]) < first and math.isfinite(float(out["grad_norm"]))


def test_loss_adds_the_weighted_router_losses():
    cfg = tiny()
    params = transformer_init(key(0), cfg)
    batch = tiny_batch(cfg)
    total, readings = transformer_loss_and_readings(params, batch, cfg)
    bare, _ = transformer_loss_and_readings(
        params, batch, tiny(router_aux_loss_coef=0.0, router_z_loss_coef=0.0))
    assert float(total) == pytest.approx(
        float(bare) + 0.01 * float(readings["aux_loss"])
        + 0.001 * float(readings["z_loss"]), rel=1e-6)
    assert readings["expert_index"].shape == (2, 3 * 32, 2)
    # the router trains: its gradient is not zero
    grads = jax.grad(transformer_loss)(params, batch, cfg)
    assert float(jnp.abs(grads["blocks"]["router"]).max()) > 0


def test_kernels_in_interpret_mode_give_the_model_the_xla_path_s_loss(
        monkeypatch):
    cfg = tiny(n_layers=1)
    params = transformer_init(key(0), cfg)
    batch = tiny_batch(cfg, rows=2, seq=16)
    want, g_want = jax.value_and_grad(transformer_loss)(params, batch, cfg)

    def interpreted(real):
        return lambda *args, impl, **kw: real(
            *args, **kw, interpret=True, block_rows=8)

    for name in ("grouped_matmul", "project_and_combine"):
        monkeypatch.setattr(moe, name, interpreted(getattr(moe, name)))
    got, g_got = jax.value_and_grad(transformer_loss)(params, batch, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)


def test_the_lowered_step_makes_no_expert_rows_again():
    """The routed step with `remat=True`, lowered for a TPU with the kernels:
    per layer eight `moe_gmm` (gate, up and down; gate and up made again;
    three gradients of rows) and three `moe_tgmm`, and five gathers of
    [T k, d] rows (dispatch, combine, dispatch made again; `dy` by `order`
    and dispatch's gradient by `inverse`). A ninth and a sixth mean the down
    projection's rows are a residual again: made twice, gathered twice."""
    cfg = tiny(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256,
               max_seq_len=128, n_experts=4, remat=True,
               attention_impl="pallas")
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init, step, _ = make_train_step(cfg, mesh)
    state = jax.eval_shape(init, key(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = step.trace(state, {"tokens": tokens, "targets": tokens}).lower(
        lowering_platforms=("tpu",)).as_text()
    # the layers are a scan: one body holds a layer's calls
    assert len(re.findall(r'kernel_name = "moe_gmm"', text)) == 8
    assert len(re.findall(r'kernel_name = "moe_tgmm"', text)) == 3
    slot_rows = 2 * 128 * cfg.experts_per_token
    assert len(re.findall(
        rf"stablehlo\.gather[^\n]*-> tensor<{slot_rows}x128x", text)) == 5


# ------------------------------------ the step's account of its routed layers

@pytest.mark.parametrize("rows", [0, 15, 16, 17, 32, 33])
def test_the_host_s_account_walks_the_chunks_the_layer_walks(rows):
    """`layer_steps` (on the host, `numpy`) and `_chunks` (in the step) round
    a layer's held rows to chunks by one expression; the account counts a
    chunk at least, a layer's buffers once even where it held no row."""
    chunk = 16
    slots = moe.Slots(jnp.arange(40), jnp.arange(40),
                      jnp.array([rows // 3, rows - rows // 3]))
    order, in_the_step = moe._chunks(slots, chunk)
    assert order.shape == (48,) and int(in_the_step) == -(-rows // chunk)
    load = np.array([[[rows, 40 - rows, 0, 0]]])  # [S, layers, E]
    sums, of_steps = moe.layer_steps(
        load, np.array([[[rows + 2]]]), np.array([[[2]]]), chunk)
    chunks = max(1, int(in_the_step))
    assert of_steps == [[[chunks], [rows]]]
    assert sums == {
        "moe.layer_steps": 1, "moe.fullest_expert_slots": max(rows, 40 - rows),
        "moe.even_expert_slots": 10.0, "moe.held_slots": rows + 2,
        "moe.dropped_slots": 2, "moe.held_rows": rows,
        "moe.buffer_rows": chunks * chunk,
        "moe.extra_chunk_layer_steps": int(chunks > 1)}
    assert all(type(n) in (int, float) for n in sums.values())


def test_over_an_expert_axis_the_account_takes_the_fullest_device():
    """Two steps of two layers on three devices: every device's buffers are
    paid for, a layer-step's chunks are its fullest device's, and without a
    share there is nothing of one."""
    held = np.array([[[16, 17, 0], [5, 6, 7]], [[33, 1, 1], [16, 16, 16]]])
    load = np.ones((2, 2, 6), np.int32)
    ratio = np.array([[1.5, 1.0], [2.0, 1.5]], np.float32)
    sums, of_steps = moe.layer_steps(
        load, held, np.zeros_like(held), 16, chip_load_max_over_mean=ratio)
    assert of_steps == [[[2, 1], [17, 7]], [[3, 1], [33, 16]]]
    assert sums["moe.extra_chunk_layer_steps"] == 2
    assert sums["moe.buffer_rows"] == 16 * ((1 + 2 + 1) + 3 + (3 + 1 + 1) + 3)
    assert sums["moe.held_rows"] == sums["moe.held_slots"] == held.sum()
    assert sums["moe.chip_load_max_over_mean_sum"] == 1.25 + 1.75
    whole, none = moe.layer_steps(load)
    assert none == [] and whole == {
        "moe.layer_steps": 4, "moe.fullest_expert_slots": 4,
        "moe.even_expert_slots": 4.0}


def test_buffer_rows_is_held_chunk_of_the_rows_routed_together():
    """One recipe for the layer, the step and the rule: a share's, and a
    device's of an `expert` axis for the rows and sequences of all of it
    (`mellum2.ep4`: four chips of 16,384 tokens each, 8 of 64 experts a
    token, PERF.md section 6, PR 50)."""
    numbers = dict(experts_per_token=8, n_experts=64, load_held_even=False)
    assert moe.buffer_rows(4 * 16384, 4, 4, held=64, **numbers) == 180224
    assert moe.buffer_rows(16384, 1, held=8, **numbers) == moe.held_chunk(
        131072, 8, 64, load_held_even=False, sequences=1)
    assert moe.buffer_rows(
        16384, 16, held=8, experts_per_token=8, n_experts=64,
        load_held_even=True) == moe.held_chunk(131072, 8, 64) == 20480


def test_flops_count_active_parameters():
    cfg = TransformerConfig(
        vocab_size=50304, d_model=2048, n_layers=1, n_heads=16, n_kv_heads=16,
        d_ff=1024, n_experts=64, experts_per_token=8, tied_embeddings=False)
    assert flops_per_token(cfg, 4096) == pytest.approx(1.0719e9, rel=1e-4)
    # the experts a token does not visit cost nothing
    more = TransformerConfig(**{**cfg.__dict__, "n_experts": 128})
    assert flops_per_token(more, 4096) - flops_per_token(cfg, 4096) \
        == 3 * 2 * 2048 * 64


def test_pallas_under_a_mesh_of_several_devices_is_refused():
    cfg = tiny(attention_impl="pallas")
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    params = transformer_init(key(0), cfg)
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        jax.eval_shape(
            lambda p, b: transformer_loss(p, b, cfg, mesh=mesh),
            params, tiny_batch(cfg, rows=2))
