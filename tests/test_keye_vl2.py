"""Sparse attention under a learned indexer (Keye-VL-2.0's; DeepSeek sparse
attention) on the CPU at tiny widths in float32: the selection against
`jax.lax.top_k` to the index, the kernels in interpret mode against the
`jax.numpy` blocks (a ragged edge, ties at the edge of a row's keys), where
gradients go and where they do not, the program against the benchmark's
plain reference, the share tied to the model, the published stack of 48
layers, and what the record means to `saved_activations`
(`ray_tpu/ops/sparse_attention.py`, `ray_tpu/ops/flash_attention.py`
`mask=`, `ray_tpu/models/transformer.py` `_SparseAttention`)."""

import collections
import dataclasses
import functools
import importlib
import logging
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import keye_vl2 as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    param_shardings, saved_activations, segments, transformer_init,
    transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.ops import sparse_attention as sparse
from ray_tpu.ops.flash_attention import mha
from ray_tpu.parallel import make_mesh
import tiny_models
from tiny_models import first_layer, init, key, one_device

INDEXER = ("wq_idx", "wk_idx", "w_idx", "k_idx_norm", "k_idx_bias")


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


batch_of = functools.partial(tiny_models.batch_of, seq=48, seed=3)


def tiny(**over):
    """64 wide: 8 query heads of 16 over 2 key-value heads with a per-head
    norm, an indexer of 4 heads of 8 that keeps 16 keys, 4 of 16 experts
    held, 3 a token."""
    values = dict(
        vocab_size=256, d_model=64, n_layers=2, n_heads=8, n_kv_heads=2,
        d_head=16, d_ff=32, max_seq_len=64, attention_impl="xla",
        layer_types=("sparse_attention",) * 2, qk_norm="head",
        index_heads=4, index_head_dim=8, index_topk=16, n_experts=16,
        experts_per_token=3, experts_held=(4, 4), norm_topk_prob=True,
        router_aux_loss_coef=0.001, router_z_loss_coef=0.0,
        tied_embeddings=False, rope_theta=1e7, dtype=jnp.float32)
    values.update(over)
    if "layer_types" not in over:
        values["layer_types"] = ("sparse_attention",) * values["n_layers"]
    return TransformerConfig(**values)


def as_reference_config(cfg):
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.kv_heads, d_head=cfg.head_dim,
        norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        index_heads=cfg.index_heads, index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk, n_experts=cfg.n_experts,
        experts_per_token=cfg.experts_per_token,
        experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
        router_aux_loss_coef=cfg.router_aux_loss_coef)


def operands(seed, B, T, H, Hk, D, Hi, Di, whole=False):
    """(q, k, v, q_idx, k_idx, w_idx) float32; `whole`: the indexer's are
    small whole numbers, so every index score is exact whatever the order
    of its sums, and rows are full of ties."""
    ks = jax.random.split(key(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hk, D))
    v = jax.random.normal(ks[2], (B, T, Hk, D))
    q_idx = jax.random.normal(ks[3], (B, T, Hi, Di))
    k_idx = jax.random.normal(ks[4], (B, T, Di))
    w_idx = jax.random.normal(ks[5], (B, T, Hi))
    if whole:
        q_idx, k_idx, w_idx = (jnp.round(2 * x) for x in (q_idx, k_idx, w_idx))
    return q, k, v, q_idx, k_idx, w_idx


# ---------------------------------------------------------- the selection

@pytest.mark.parametrize("T,topk,first", [(64, 16, 0), (64, 16, 32),
                                          (40, 64, 0), (48, 1, 8)])
@pytest.mark.parametrize("ties", [False, True], ids=["seeded", "ties"])
def test_the_selection_is_top_k_s_to_the_index(T, topk, first, ties):
    """`select_keys` against a mask made from `jax.lax.top_k`'s indices over
    the causal keys: the same keys, a tie at the edge to the lower index,
    and a row of fewer keys than `topk` keeps them all."""
    rows = 16
    scores = jax.random.normal(key(T + topk), (2, rows, T))
    if ties:  # a few values: every row's edge falls among equals (and no
        # -0.0, which `top_k` ranks under 0.0: a sum from zero is never it)
        scores = jnp.round(scores) + 0.0
    keep = sparse.select_keys(scores, first, topk)
    t = first + np.arange(rows)
    causal = np.arange(T)[None, :] <= t[:, None]
    index = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, T))[1]
    theirs = np.zeros((2, rows, T), bool)
    for b in range(2):
        for r in range(rows):
            theirs[b, r, np.asarray(index[b, r])] = True
    theirs &= causal
    np.testing.assert_array_equal(np.asarray(keep), theirs)
    np.testing.assert_array_equal(
        np.asarray(keep.sum(-1)), np.broadcast_to(
            np.minimum(t + 1, topk), (2, rows)))
    assert np.array_equal(np.asarray(reference.kept_mask(index, first, T)),
                          theirs)


@pytest.mark.parametrize("T,topk", [(256, 64), (128, 200), (256, 1)])
def test_index_select_is_select_keys_bit_for_bit(T, topk):
    """The kernel (interpret mode) against `select_keys` on whole numbers,
    where every score is exact and rows are full of ties: the same mask,
    every row's count, the same logsumexp to rounding."""
    _, _, _, q_idx, k_idx, w_idx = operands(T, 2, T, 4, 2, 16, 3, 16, True)
    mask, lse, gap = sparse.index_select(
        q_idx, k_idx, w_idx, topk=topk, chunk=128, interpret=True)
    scores = sparse.index_scores(q_idx, k_idx, w_idx)
    keep = sparse.select_keys(scores, 0, topk)
    np.testing.assert_array_equal(
        np.asarray(sparse._keep_of(mask, T)), np.asarray(keep, np.int8))
    assert float(jnp.abs(gap).max()) == 0
    np.testing.assert_allclose(lse, jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1), rtol=1e-6)
    # ties there were: some row's edge value occurs more than once
    assert len(np.unique(np.asarray(scores[0, -1]))) < T // 2


@pytest.mark.parametrize("tile", [32, 128, 512, 1024])
def test_a_mask_packs_to_bits_and_back(tile):
    """`_mask_of` and `_keep_of` are inverses at every tile the kernels take
    (and the tests' 32), the last tile ragged; bit `b` of column `c` of key
    tile `j` is key `j * tile + b * (tile / 8) + c`, and the bits past the
    keys are 0."""
    keys = 2 * tile + tile // 2 + 3
    keep = jax.random.bernoulli(key(tile), 0.3, (2, 16, keys))
    mask = sparse._mask_of(keep, tile)
    assert mask.shape == (2, 3, 16, tile // 8) and mask.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(sparse._keep_of(mask, keys)), np.asarray(keep, np.int8))
    bits = np.asarray(mask).view(np.uint8)
    for j, b, c in ((0, 0, 0), (1, 7, tile // 8 - 1), (2, 3, 2), (2, 4, 0)):
        at = j * tile + b * (tile // 8) + c
        want = np.asarray(keep[:, :, at]) if at < keys else 0
        np.testing.assert_array_equal((bits[:, j, :, c] >> b) & 1, want)
    assert not np.asarray(sparse._keep_of(mask, 3 * tile))[..., keys:].any()


def test_the_sortable_integer_keeps_the_floats_order():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf])
    keys = sparse._sortable(x)
    assert bool(jnp.all(keys[1:] > keys[:-1]))
    assert int(keys[0]) > sparse._INT_MIN
    np.testing.assert_array_equal(sparse._unsortable(keys), x)


# ------------------------------------------ the kernels against the blocks

def loss_and_grads(args, topk, **how):
    def loss(*a):
        out, index_loss, gap, keep = sparse.sparse_attention(
            *a, topk=topk, **how)
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                          ).reshape(out.shape)
        return (out * weights).sum() + 3.0 * index_loss, (
            out, index_loss, gap, keep)

    return jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*args)


@pytest.mark.parametrize("T,topk,blocks,whole", [
    (256, 64, (None, None), False),   # one tile a row
    (512, 100, (256, 128), True),     # eight tiles, ties at every edge
    (128, 200, (None, None), False),  # no row chooses: topk >= T
], ids=["256", "512-ties", "topk-over-T"])
def test_the_kernels_agree_with_the_blocks(T, topk, blocks, whole):
    """`impl="pallas"` in interpret mode against `impl="xla"`: the output,
    the index loss, the selection, and all six gradients (q, k and v's by
    the flash backward under the mask, the indexer's by hand)."""
    args = operands(0, 2, T, 4, 2, 32, 3, 16, whole)
    (_, (o1, l1, g1, k1)), d1 = loss_and_grads(args, topk, impl="xla")
    (_, (o2, l2, g2, k2)), d2 = loss_and_grads(
        args, topk, impl="pallas", interpret=True, blocks=blocks)
    np.testing.assert_allclose(o2, o1, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(l2, l1, rtol=1e-5)
    assert float(jnp.abs(g1).max()) == float(jnp.abs(g2).max()) == 0
    if whole:  # exact scores: the same keys to the index
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    for ours, theirs in zip(d2, d1):
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("one_kernel", [True, False], ids=["one", "two"])
def test_the_masked_flash_kernels_at_a_ragged_edge(one_kernel):
    """The four flash kernels under a data mask against a masked softmax in
    `jax.numpy`, at a sequence of 80 in tiles of 32 (2.5 tiles: padded rows
    and columns in the last), 2 query heads a key-value head."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    B, T, H, Hk, D, tile = 2, 80, 4, 2, 16, 32
    q, k, v = operands(3, B, T, H, Hk, D, 2, 8)[:3]
    keep = jnp.logical_and(
        jax.random.bernoulli(key(7), 0.4, (B, T, T)),
        jnp.tril(jnp.ones((T, T), bool))) | jnp.eye(T, dtype=bool)
    scale = D ** -0.5

    def reference(q, k, v):
        kf, vf = (jnp.repeat(x, H // Hk, axis=2) for x in (k, v))
        s = jnp.einsum("bthd,bshd->bhts", q, kf) * scale
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, vf)

    do = jax.random.normal(key(8), (B, T, H, D))
    out, grads = jax.jit(lambda q, k, v, do: (lambda out, pull: (
        out, pull(do)))(*jax.vjp(reference, q, k, v)))(q, k, v, do)
    mask = sparse._mask_of(keep, tile)
    assert mask.shape == (B, 3, T, tile // 8) and mask.dtype == jnp.int8
    qf, kf, vf, dof = (sparse._heads_first(x) for x in (q, k, v, do))
    how = dict(causal=True, scale=scale, block_q=tile, block_k=tile,
               interpret=True, mask=mask)
    o, lse = fa._flash_fwd(qf, kf, vf, with_lse=True, **how)
    np.testing.assert_allclose(
        sparse._heads_last(o, B), out, rtol=1e-5, atol=2e-6)
    delta = jnp.broadcast_to(
        (dof * o).sum(-1)[..., None], (B * H, T, 8))
    if one_kernel:
        dq, dk, dv = fa._flash_bwd_dkv(qf, kf, vf, dof, lse, delta,
                                       with_dq=True, **how)
    else:
        dq = fa._flash_bwd_dq(qf, kf, vf, dof, lse, delta, **how)
        dk, dv = fa._flash_bwd_dkv(qf, kf, vf, dof, lse, delta, **how)
    for ours, theirs in zip((dq, dk, dv), grads):
        np.testing.assert_allclose(
            sparse._heads_last(ours, B), theirs, rtol=2e-4, atol=2e-5)


def test_a_sequence_the_kernels_do_not_take_runs_in_jax_numpy(caplog):
    args = operands(0, 1, 80, 4, 2, 16, 2, 8)
    sparse._log_path.cache_clear()
    with caplog.at_level(logging.INFO, logger=sparse.logger.name):
        ours = sparse.sparse_attention(*args, topk=24, impl="pallas")
    theirs = sparse.sparse_attention(*args, topk=24, impl="xla")
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "80 is no multiple of 128" in caplog.records[0].getMessage()


def test_a_topk_no_shorter_than_the_sequence_is_causal_attention():
    args = operands(1, 2, 64, 4, 2, 16, 2, 8)
    q, k, v = args[:3]
    dense = mha(q, k, v, causal=True, impl="xla")
    for how in (dict(impl="xla"), dict(impl="pallas", interpret=True)):
        out, index_loss, gap, keep = jax.jit(functools.partial(
            sparse.sparse_attention, topk=64, **how))(*args)
        np.testing.assert_allclose(out, dense, rtol=1e-5, atol=2e-6)
        assert float(index_loss) > 0 and float(jnp.abs(gap).max()) == 0
        np.testing.assert_array_equal(
            np.asarray(keep[0]), np.tril(np.ones((64, 64), np.int8)))


def test_gradients_go_where_the_equations_send_them():
    """The output's gradient reaches q, k and v alone; the index loss's
    reaches the indexer's three operands alone: both exactly."""
    args = operands(2, 1, 128, 4, 2, 16, 2, 8)
    for how in (dict(impl="xla"), dict(impl="pallas", interpret=True)):
        @jax.jit
        def both(*a):  # one program: the forward once, pulled back twice
            (out, loss), pull = jax.vjp(lambda *a: (lambda o, l, *_: (
                o.sum(), l))(*sparse.sparse_attention(*a, topk=32, **how)), *a)
            return pull((jnp.ones_like(out), jnp.zeros_like(loss))), pull(
                (jnp.zeros_like(out), jnp.ones_like(loss)))

        d_out, d_loss = both(*args)
        for g in d_out[3:] + d_loss[:3]:
            assert float(jnp.abs(g).max()) == 0.0
        for g in d_out[:3] + d_loss[3:]:
            assert float(jnp.abs(g).max()) > 0.0


def test_the_masked_calls_have_names_of_their_own(caplog):
    """`flash_fwd_sparse`, the backward's kernel and `index_select` in the
    jaxpr; no `[T, T]` float array anywhere, and none of a byte a pair (the
    mask is bits, an eighth of that); one log line a shape."""
    args = tuple(jax.ShapeDtypeStruct((1, 2048, *x.shape[2:]), jnp.bfloat16)
                 for x in operands(0, 1, 8, 4, 2, 32, 2, 16))
    sparse._log_path.cache_clear()
    with caplog.at_level(logging.INFO, logger=sparse.logger.name):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: (lambda out, loss, *_: out.astype(jnp.float32).sum()
                        + loss)(*sparse.sparse_attention(
                            *a, topk=64, impl="pallas")),
            argnums=tuple(range(6))))(*args))
    for name in ("index_select", "flash_fwd_sparse", "flash_bwd_dkv_dq_sparse"):
        assert f"name={name}" in text, name
    assert re.search(r"i8\[1,2,2048,128\]", text)
    assert not re.search(r"i8\[1,2,2048,1024\]", text)
    assert not re.search(r"(f32|bf16)\[(\d+,)*2048,2048\]", text)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "index_select" in lines[0]
    assert "flash_fwd_sparse 1024 x 1024" in lines[0]
    assert ("mask as bits in key tiles of 1024, 524288 bytes a sequence, "
            "kept with attn_ctx") in lines[0]


def kernel_calls(jaxpr, counts=None):
    """{name: `pallas_call`s of that name} in `jaxpr` and every jaxpr
    inside it (a program's text prints a repeated inner jaxpr once)."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    kernel_calls(inner, counts)
    return counts


def test_a_block_that_keeps_attn_ctx_selects_once_a_layer():
    """Two layers, each under `jax.checkpoint`: where the policy keeps
    `attn_ctx` the gradient's program holds `index_select` once a layer (the
    forward's: the backward reads the kept bits), `flash_fwd_sparse` and
    `index_loss` too; where nothing is kept, each twice. The same gradients
    to the bit."""
    B, T, H, Hk, D, Hi, Di = 1, 128, 2, 1, 16, 2, 8
    ks = jax.random.split(key(11), 2)
    x = jax.random.normal(ks[0], (B, T, H * D))
    w = 0.3 * jax.random.normal(
        ks[1], (2, H * D, (H + 2 * Hk) * D + Hi * Di + Di + Hi))

    def stack(x, w, names):
        def layer(x, w):
            q, k, v, q_idx, k_idx, w_idx = jnp.split(x @ w, np.cumsum(
                [H * D, Hk * D, Hk * D, Hi * Di, Di]), axis=-1)
            out, index_loss, _, _ = sparse.sparse_attention(
                q.reshape(B, T, H, D), k.reshape(B, T, Hk, D),
                v.reshape(B, T, Hk, D), q_idx.reshape(B, T, Hi, Di), k_idx,
                w_idx, topk=24, impl="pallas", interpret=True,
                keep_ctx="attn_ctx" in names)
            return x + out.reshape(B, T, H * D), index_loss

        layer = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        total = 0.0
        for i in range(2):
            x, index_loss = layer(x, w[i])
            total = total + index_loss
        return jnp.sin(x).sum() + total

    grads, calls = {}, {}
    for names in ((), ("attn_ctx",)):
        grad = jax.grad(functools.partial(stack, names=names), (0, 1))
        found = kernel_calls(jax.make_jaxpr(grad)(x, w).jaxpr)
        calls[names] = [found[kernel] for kernel in (
            "index_select", "flash_fwd_sparse", "index_loss",
            "flash_bwd_dkv_dq_sparse")]
        grads[names] = jax.jit(grad)(x, w)
    assert calls[()] == [4, 4, 4, 2]
    assert calls["attn_ctx",] == [2, 2, 2, 2]
    for ours, theirs in zip(grads["attn_ctx",], grads[()]):
        assert float(jnp.abs(ours).max()) > 0
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


# ------------------------------------------------ the program, the reference

def test_parameter_tree_and_seeded_values():
    cfg = tiny()
    assert [(s.periods, [(k.op, k.routed) for k in s.layout])
            for s in segments(cfg)] == [(2, [("sparse_attention", True)])]
    blocks = init(key(0), cfg)["blocks"]
    assert blocks["wq_idx"].shape == (2, 64, 4 * 8)
    assert blocks["wk_idx"].shape == (2, 64, 8)
    assert blocks["w_idx"].shape == (2, 64, 4)
    assert blocks["k_idx_norm"].shape == blocks["k_idx_bias"].shape == (2, 8)
    assert blocks["q_norm"].shape == (2, 16)
    assert float(blocks["k_idx_bias"].max()) == 0
    kind = cfg.layers[0]
    assert {"wq_idx", "wk_idx", "w_idx"} <= set(
        model.own_buffer_weights(blocks, kind))
    # plain attention's leaves are what a full-attention stack draws: the
    # indexer's are new draws beside them
    plain = init(key(0), dataclasses.replace(
        cfg, layer_types=("full_attention",) * 2))["blocks"]
    assert set(blocks) - set(plain) == set(INDEXER)
    for name, leaf in plain.items():
        np.testing.assert_array_equal(leaf, blocks[name])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_program_agrees_with_the_plain_reference(impl, monkeypatch):
    """Loss and gradients on seeded weights with `topk` 16 under sequences
    of 48, so that two rows in three choose: under the system's choice of
    experts and of keys, and the selections themselves to the index."""
    if impl == "pallas":  # the kernels, interpreted; the experts' as ever
        monkeypatch.setattr(model, "sparse_attention", lambda *a, **kw: (
            sparse.sparse_attention(*a, **{**kw, "impl": "xla",
                                           "interpret": True})))
    cfg = tiny()
    config = as_reference_config(cfg)
    params = init(key(1), cfg)
    # an indexer whose key is not its initial LayerNorm's
    params["blocks"]["k_idx_bias"] = 0.3 * jax.random.normal(key(2), (2, 8))
    batch = batch_of(cfg)
    (loss, readings), grads = jax.jit(jax.value_and_grad(
        transformer_loss_and_readings, has_aux=True), static_argnums=2)(
            params, batch, cfg)
    assert float(readings["index_keys_min_gap"]) == 0
    assert float(readings["index_keys_max_gap"]) == 0
    keep = readings["index_keep"]  # [L, B, T, T]
    assert keep.shape == (2, 2, 48, 48)
    keys = jax.lax.top_k(keep.astype(jnp.int32), 16)[1]
    index = readings["expert_index"]
    theirs, g_ref = jax.jit(jax.value_and_grad(
        lambda p, b, i, k: reference.loss(p, b, config, i, k)))(
            params, batch, index, keys)
    assert float(loss) == pytest.approx(float(theirs), rel=2e-6)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(grads), jax.tree.leaves(g_ref)))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree.leaves(g_ref))
    assert math.sqrt(num / den) < 2e-5
    # the reference's own choices are the system's, in float32
    _, chosen, balance, index_loss, own = jax.jit(
        lambda p, b: reference.forward(p, b, config))(params, batch)
    own_keep = jnp.stack([reference.kept_mask(k, 0, 48) for k in own])
    np.testing.assert_array_equal(np.asarray(keep != 0), np.asarray(own_keep))
    assert float(readings["index_loss"]) == pytest.approx(
        float(index_loss), rel=1e-5)
    assert float(readings["aux_loss"]) == pytest.approx(
        float(balance), rel=1e-5)
    # a sixth of the loss's size: the index loss is no rounding
    assert 0.02 < float(readings["index_loss"]) < 2.0


def test_the_two_detachments_are_exact():
    """Cross-entropy and the balance loss leave every leaf of the indexer
    exactly 0; the index loss leaves every other leaf exactly 0."""
    cfg = tiny()
    params = init(key(1), cfg)
    batch = batch_of(cfg)

    @jax.jit
    def parts(p):  # one program: the forward once, pulled back twice
        def of(p):
            loss, readings = transformer_loss_and_readings(p, batch, cfg)
            return loss - readings["index_loss"], readings["index_loss"]

        _, pull = jax.vjp(of, p)
        return pull((1.0, 0.0))[0], pull((0.0, 1.0))[0]

    rest, of_index = parts(params)
    for name, leaf in rest["blocks"].items():
        norm = float(jnp.abs(leaf).max())
        assert (norm == 0.0) == (name in INDEXER), name
    for name, leaf in of_index["blocks"].items():
        norm = float(jnp.abs(leaf).max())
        assert (norm > 0.0) == (name in INDEXER), name
    for name in ("embed", "unembed", "final_norm"):
        assert float(jnp.abs(of_index[name]).max()) == 0.0


def test_a_topk_over_the_sequence_is_full_attention_with_a_taught_indexer():
    cfg = tiny(index_topk=64)
    dense = dataclasses.replace(cfg, layer_types=("full_attention",) * 2)
    params = init(key(1), cfg)
    batch = batch_of(cfg)
    step = jax.jit(jax.value_and_grad(
        transformer_loss_and_readings, has_aux=True), static_argnums=2)
    (loss, readings), grads = step(params, batch, cfg)
    plain = {**params, "blocks": {k: v for k, v in params["blocks"].items()
                                  if k not in INDEXER}}
    (loss_d, _), grads_d = step(plain, batch, dense)
    assert float(loss - readings["index_loss"]) == pytest.approx(
        float(loss_d), rel=1e-6)
    for name, leaf in grads_d["blocks"].items():
        np.testing.assert_allclose(grads["blocks"][name], leaf, rtol=2e-4,
                                   atol=1e-6)
    for name in ("wq_idx", "wk_idx", "w_idx"):
        assert float(jnp.abs(grads["blocks"][name]).max()) > 0


def test_the_shares_add_up_to_the_uncut_layer(monkeypatch):
    """8 experts held 2 a share: attention under the selection and the
    indexer are what every share computes alike, and counted once; the four
    shares' routed parts beside them are the uncut reference's layer."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    cfg = tiny(n_layers=1, n_experts=8, experts_held=None)
    w = first_layer(cfg)
    x = jax.random.normal(key(5), (2, 48, 64))
    positions = jnp.broadcast_to(jnp.arange(48), (2, 48))
    config = as_reference_config(cfg)
    names = ("w_gate", "w_up", "w_down")
    after_attention, kl, _ = jax.jit(
        lambda x, w: reference.attention(x, w, config))(x, w)
    whole, _, _ = jax.jit(lambda y, w: reference.routed_feed_forward(
        y, w, config))(after_attention, w)
    parts = []
    for first in range(0, 8, 2):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
        held = {**w, **{k: w[k][first:first + 2] for k in names}}
        out, readings = jax.jit(lambda x, held: model._block(
            x, held, positions, None, share_cfg, cfg.layers[0], None, 1))(
                x, held)
        assert int(readings["dropped_slots"]) == 0
        assert float(readings["index_loss"]) == pytest.approx(
            float(kl.mean()), rel=1e-5)
        theirs, _, _ = reference.routed_feed_forward(
            after_attention, held, {**config, "experts_held": (first, 2)})
        np.testing.assert_allclose(out, theirs, rtol=2e-4, atol=2e-5)
        parts.append(out - after_attention)  # this share's routed part alone
    np.testing.assert_allclose(
        after_attention + sum(parts), whole, rtol=2e-4, atol=5e-5)


def test_one_sequence_s_held_rows_get_buffers_past_their_swing(monkeypatch):
    """`keyevl2.tokens16k`: 131,072 slots, 16 of 128 experts held, a step
    of one sequence under a router that only a loss balances: buffers of
    3.25 even shares. More sequences, or a selection bias, keep the slack
    they had, and the layer tells the rule how many sequences it sees."""
    assert moe.held_chunk(
        131072, 16, 128, load_held_even=False, sequences=1) == 53248
    assert moe.held_chunk(
        131072, 16, 128, load_held_even=False, sequences=2) == 22528
    assert moe.held_chunk(131072, 16, 128, load_held_even=False) == 22528
    assert moe.held_chunk(131072, 16, 128, sequences=1) == 20480
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    rule, seen = moe.held_chunk, []

    def recording(*args, **kwargs):
        seen.append((kwargs["load_held_even"], kwargs["sequences"]))
        return rule(*args, **kwargs)

    monkeypatch.setattr(moe, "held_chunk", recording)
    cfg = tiny(n_layers=1)
    w = first_layer(cfg)
    for rows in (1, 2):
        x = jax.random.normal(key(5), (rows, 48, 64))
        positions = jnp.broadcast_to(jnp.arange(48), (rows, 48))
        _, readings = jax.jit(lambda x, w: model._block(
            x, w, positions, None, cfg, cfg.layers[0], None, 1))(x, w)
        assert int(readings["dropped_slots"]) == 0
    assert seen == [(False, 1), (False, 2)]


def test_the_published_stack_of_forty_eight_layers_builds_and_steps():
    """48 alike layers over 128 experts as config.json has them, at a tiny
    width: the cut is a cut of depth and share."""
    from chipbench import spec

    published = spec.load_cell(
        spec.ROOT, "keyevl2.tokens16k")["config"]["catalog_config"]
    sa = published["sa_config"]
    assert published["num_hidden_layers"] == 48
    assert published["mlp_only_layers"] == []
    assert published["decoder_sparse_step"] == 1
    cfg = tiny(d_model=32, d_head=8, n_heads=8, n_kv_heads=1, d_ff=8,
               vocab_size=64, n_layers=48, n_experts=published["num_experts"],
               experts_per_token=published["num_experts_per_tok"],
               experts_held=None, index_heads=sa["indexer_num_heads"],
               index_head_dim=4, index_topk=8)
    assert all(k.op == "sparse_attention" and k.routed for k in cfg.layers)
    init, step, _ = make_train_step(cfg, one_device())
    state = init(key(0))
    assert state["params"]["blocks"]["wq_idx"].shape == (48, 32, 16 * 4)
    assert state["params"]["blocks"]["router"].shape == (48, 32, 128)
    batch = batch_of(cfg, rows=1, seq=24)
    state, out = step(state, batch)
    assert math.isfinite(float(out["loss"])) and float(out["grad_norm"]) > 0
    assert out["expert_load"].shape == (48, 128)
    assert float(out["index_keys_min_gap"]) == 0
    assert float(out["index_keys_max_gap"]) == 0
    assert float(out["index_loss"]) > 0
    assert "index_keep" not in out


def test_sparse_attention_refuses_what_it_cannot_map():
    cfg = tiny()
    kind = cfg.layers[0]
    w = {k: v[0] for k, v in model._blocks_init(key(0), cfg, kind, 1).items()}
    x = jnp.zeros((1, 16, 64))
    with pytest.raises(NotImplementedError, match="sequence axis"):
        model._block(x, w, jnp.zeros((1, 16), jnp.int32), None, cfg, kind,
                     "sequence", 2)
    with pytest.raises(ValueError, match="index_topk"):
        transformer_init(key(0), dataclasses.replace(cfg, index_topk=0))


# ------------------------------------------------------- shardings, remat

def test_param_shardings_of_the_new_leaves():
    cfg = tiny()
    mesh = make_mesh({"fsdp": 4, "tensor": 2}, devices=jax.devices()[:8])
    shard = param_shardings(mesh, cfg)
    params = jax.eval_shape(lambda: transformer_init(key(0), cfg))
    assert jax.tree.structure(shard) == jax.tree.structure(params)
    blk = shard["blocks"]
    # the indexer is whole along its columns: no cut by head
    for name in ("wq_idx", "wk_idx", "w_idx"):
        assert blk[name].spec[2] is None
        assert blk[name].spec[1] == blk["wq"].spec[1]
    plain = param_shardings(mesh, TransformerConfig(n_layers=2))
    assert "wq_idx" not in plain["blocks"]


def test_layer_widths_and_operations_by_hand():
    from chipbench import keye_vl2_flops, spec

    cell = spec.load_cell(spec.ROOT, "keyevl2.tokens16k")
    config = cell["config"]
    cfg = spec.load_code(spec.ROOT, "loops", "keye_vl2").model_config(config)
    d = 2048
    kind = cfg.layers[0]
    widths, params = model._layer_widths(cfg, kind)
    assert params == 18874368 + 2260992 + d * 128 + 16 * 3 * d * 768
    assert widths == {
        # o, lse as one f32 column a head, the indexer's three gradients
        # and the mask's bits: 2 KB a token at 16,384 keys
        "attn_ctx": 32 * 128 + 32 * 2 + 16 * 64 + 64 + 16 * 2 + 16384 // 16,
        "attn_res": d, "attn_qkv": (32 + 2 * 4) * 128}
    # the program's count is the benchmark's
    assert model.flops_per_token(cfg, 16384) == pytest.approx(
        keye_vl2_flops.keye_vl2_flops_per_token(config, 16384), rel=1e-9)
    matmul, pairs = model._OPERATORS["sparse_attention"].flops(cfg, 16384)
    assert matmul == 2 * (18874368 + 2260992)
    assert pairs * 16384 == (31458304 * 2 * 2 * 32 * 128
                             + 134225920 * 2 * 16 * 64)
    assert sparse.keys_kept(16384, 2048) == 31458304
    assert sparse.keys_kept(2048, 2048) == 2048 * 2049 // 2


# the names a rematerialised block keeps in the one-chip token cells of the
# accepted benchmark, at what a v5e offers a program (15.84 GB), which the
# new record and `_qkv` must not move: computed on the parent commit for
# the cells whose stack is scanned; for the three whose segments are one
# period long as PR 54's rule walks them, a layer at a time
KEPT_BY_THE_PARENT = {
    "mistral7b.tokens4k": [],
    "olmoe.tokens4k": ["attn_ctx", "moe_slots", "attn_res", "attn_qkv",
                       "moe_gate", "moe_up"],
    "lfm2moe.tokens8k": ["attn_ctx", "attn_res", "conv_res", "attn_qkv",
                         "conv_in", "mlp_gate", "mlp_up"],
    "dsv2lite.tokens8k": [],
    # on the `jax.numpy` scan's path, which "auto" takes here and no cell
    # runs: its first mixer's moment, with the accumulators of the routed
    # layers behind it waiting for the optimizer (PR 73), fills 15.84 GB
    "nemotron3nano.tokens8k": [],
    "lagunaxs2.tokens8k": ["attn_ctx", "attn_res", "attn_qkv",
                           "shared_gate", "shared_up"],
}


def kept_in(cell_name):
    """The names `saved_activations` chooses for the cell's one-chip step."""
    from chipbench import spec

    cell = spec.load_cell(spec.ROOT, cell_name)
    config, traffic = cell["config"], cell["traffic"]
    cfg = spec.load_code(spec.ROOT, "loops", config["family"]).model_config(
        config)
    shapes = jax.eval_shape(lambda: transformer_init(key(0), cfg))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    tokens = int(traffic["batch_rows"]) * int(traffic["units_per_row"])
    return list(saved_activations(cfg, tokens, 12 * n, 4 * n, int(15.84e9)))


@pytest.mark.parametrize("cell_name", sorted(KEPT_BY_THE_PARENT))
def test_the_other_cells_keep_what_the_parent_kept(cell_name):
    assert kept_in(cell_name) == KEPT_BY_THE_PARENT[cell_name]
