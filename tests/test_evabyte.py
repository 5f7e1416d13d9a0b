"""EVA attention (`ops/eva.py`) and the stack that holds it
(`models/transformer.py`: `eva_attention`, the norms' unit offset, the head
of several positions) at a small size on the CPU: hidden 64, 4 heads of 16,
windows of 16, chunks of 4, sequences of 64 (4 windows), 2 layers, 3
prediction heads. Loss and every gradient leaf against the plain reference
on both paths (the Pallas one in interpret mode); the staircase, the
one-window case and causality by hand; what is refused."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import evabyte as reference
from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as tr
from ray_tpu.ops import eva
from ray_tpu.ops.flash_attention import mha

W, C, T, H, D = 16, 4, 64, 4, 16


def small(**over):
    fields = dict(
        vocab_size=40, d_model=64, n_layers=2, n_heads=H, d_ff=96,
        max_seq_len=T, layer_types=("eva_attention",) * 2, eva_window=W,
        eva_chunk=C, n_pred_heads=3, norm_unit_offset=True, norm_eps=1e-5,
        rope_theta=100000.0, tied_embeddings=False, dtype=jnp.float32,
        attention_impl="xla")
    return TransformerConfig(**{**fields, **over})


def reference_config(cfg):
    return dict(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_layers=cfg.n_layers,
        norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        eva_window=cfg.eva_window, eva_chunk=cfg.eva_chunk,
        n_pred_heads=cfg.n_pred_heads, vocab_size=cfg.vocab_size)


def seeded(cfg, seed=0):
    """Parameters with the norms' `g` moved off zero, so that a scale left
    at 1 shows, and the vectors at a size at which the summaries weigh."""
    params = tr.transformer_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    blocks = params["blocks"]
    for i, name in enumerate(("attn_norm", "mlp_norm")):
        blocks[name] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, i), blocks[name].shape)
    for i, name in enumerate(("eva_phi", "eva_mu")):
        blocks[name] = 0.5 * jax.random.normal(
            jax.random.fold_in(key, 10 + i), blocks[name].shape)
    params["final_norm"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 20), params["final_norm"].shape)
    return params


def batch_of(cfg, rows=2, seq_len=T, seed=3):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, seq_len + cfg.n_pred_heads), 0,
                             cfg.vocab_size)
    tokens, targets = tr.next_ids(ids, cfg.n_pred_heads)
    return {"tokens": tokens, "targets": targets}


def interpreted(monkeypatch):
    """The Pallas path, its kernels in interpret mode."""
    monkeypatch.setattr(tr, "eva_attention", lambda *a, **kw: (
        eva.eva_attention(*a, **{**kw, "impl": "xla", "interpret": True})))


def qkv(seed=0, rows=2, seq_len=T):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (rows, seq_len, H, D)) for key in keys[:3])
    return q, k, v, 0.3 * jax.random.normal(keys[3], (H, D)), (
        0.3 * jax.random.normal(keys[4], (H, D)))


# ------------------------------------------------------- the whole stack

@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_program_agrees_with_the_plain_reference(impl, remat, monkeypatch):
    """Loss and every leaf's gradient on seeded weights, and the two
    readings a layer against the reference's own."""
    if impl == "pallas":
        interpreted(monkeypatch)
    cfg = small(remat=remat)
    params, batch = seeded(cfg), batch_of(cfg)
    saved = {"saved_names": ("attn_ctx", "eva_summaries")} if remat else {}
    (loss, readings), grads = jax.jit(jax.value_and_grad(
        lambda p, b: tr.transformer_loss_and_readings(p, b, cfg, **saved),
        has_aux=True))(params, batch)
    (theirs, read_ref), g_ref = jax.jit(jax.value_and_grad(
        lambda p, b: reference.forward(p, b, reference_config(cfg)),
        has_aux=True))(params, batch)
    assert float(loss) == pytest.approx(float(theirs), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, ours), ref in zip(flat, jax.tree.leaves(g_ref)):
        scale = float(jnp.abs(ref).max())
        assert scale > 0, path  # every leaf learns, eva_phi and eva_mu too
        assert float(jnp.abs(ours - ref).max()) < 2e-4 * scale + 1e-7, path
    for name in ("eva_remote_mass", "eva_chunk_entropy"):
        assert readings[name].shape == (2,)
        np.testing.assert_allclose(readings[name], read_ref[name], rtol=1e-4)
    assert 0.05 < float(readings["eva_remote_mass"].mean()) < 0.6
    assert 0 < float(readings["eva_chunk_entropy"].max()) < math.log(C)


def test_the_vectors_are_no_matmul_weights_and_the_norms_start_at_zero():
    cfg = small()
    params = tr.transformer_init(jax.random.PRNGKey(0), cfg)
    blocks = params["blocks"]
    assert blocks["eva_phi"].shape == blocks["eva_mu"].shape == (2, H, D)
    for name in ("eva_phi", "eva_mu"):  # normal, clipped, times D ** -0.5
        assert float(jnp.abs(blocks[name]).max()) <= D ** -0.5
        assert float(jnp.abs(blocks[name]).max()) > 0
    assert not {"eva_phi", "eva_mu"} & set(
        tr.own_buffer_weights(blocks, cfg.layers[0]))
    for g in (blocks["attn_norm"], blocks["mlp_norm"], params["final_norm"]):
        assert float(jnp.abs(g).max()) == 0
    assert params["unembed"].shape == (64, 3 * 40)


def test_init_std_draws_every_matrix_at_one_deviation():
    cfg = small(d_model=128, d_ff=256, init_std=0.01275)
    params = tr.transformer_init(jax.random.PRNGKey(1), cfg)
    blocks = params["blocks"]
    for leaf in (blocks["wq"], blocks["wo"], blocks["w_down"],
                 params["embed"], params["unembed"]):
        assert float(leaf.std()) == pytest.approx(0.01275, rel=0.05)
    plain = tr.transformer_init(jax.random.PRNGKey(1), small(
        d_model=128, d_ff=256))
    np.testing.assert_array_equal(plain["blocks"]["eva_phi"],
                                  blocks["eva_phi"])


# -------------------------------------------------- the attention by hand

def by_hand(q, k, v, phi, mu, i, b=0, h=0):
    """Query `i`'s output for one row and head, from the equations: its
    window's tokens up to itself and the summaries of the chunks of every
    earlier window, one softmax."""
    q, k, v = (np.asarray(x, np.float64)[b, :, h] for x in (q, k, v))
    phi, mu = (np.asarray(x, np.float64)[h] for x in (phi, mu))
    keys, values = [], []
    for j in range(i // W * W, i + 1):
        keys.append(k[j])
        values.append(v[j])
    for c in range(i // W * (W // C)):
        rows = slice(C * c, C * (c + 1))
        a = k[rows] @ phi
        w = np.exp(a - a.max())
        w /= w.sum()
        keys.append(w @ k[rows] + mu)
        values.append(w @ v[rows])
    s = np.array(keys) @ q[i] / math.sqrt(D)
    p = np.exp(s - s.max())
    return (p / p.sum()) @ np.array(values), len(keys)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_query_s_keys_by_hand(impl):
    """Query 16 has its own token and chunks 0 to 3; query 15 its window's
    16 tokens and no chunk; query 63 its window's 16 and 12 chunks."""
    args = qkv()
    how = dict(interpret=True) if impl == "pallas" else dict(impl="xla")
    o, _ = eva.eva_attention(*args, window=W, chunk=C, **how)
    for i, n_keys in ((15, 16), (16, 1 + 4), (17, 2 + 4), (31, 16 + 4),
                      (32, 1 + 8), (63, 16 + 12)):
        ours, n = by_hand(*args, i)
        assert n == n_keys
        np.testing.assert_allclose(o[0, i, 0], ours, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_staircase_gives_the_first_window_no_key(impl):
    q, k, v, phi, mu = qkv(1)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=C, impl="xla")
    o, lse = eva._stair_part(q, ks, vs, W, C, D ** -0.5, impl == "pallas",
                             False, impl == "pallas")
    assert float(jnp.abs(o[:, :W]).max()) == 0
    assert bool(jnp.all(lse[:, :W] == -jnp.inf))
    assert bool(jnp.all(jnp.isfinite(lse[:, W:])))
    # query 16 sees chunks 0 to 3 and not chunk 4
    s = np.einsum("hd,chd->hc", np.asarray(q[0, 16]),
                  np.asarray(ks[0, :4])) / math.sqrt(D)
    np.testing.assert_allclose(
        lse[0, 16], np.log(np.exp(s).sum(-1)), rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_sequence_no_longer_than_a_window_is_plain_causal_attention(impl):
    q, k, v, phi, mu = qkv(2, seq_len=W)
    how = dict(interpret=True) if impl == "pallas" else dict(impl="xla")
    o, readings = eva.eva_attention(q, k, v, phi, mu, window=W, chunk=C, **how)
    plain = mha(q, k, v, causal=True, impl="xla")
    if impl == "xla":
        np.testing.assert_array_equal(o, plain)
    else:
        np.testing.assert_allclose(o, plain, rtol=1e-5, atol=1e-6)
    assert float(readings["eva_remote_mass"]) == 0
    # shorter than a window too, and the vectors get no gradient
    d_phi, d_mu = jax.grad(lambda phi, mu: eva.eva_attention(
        q[:, :10], k[:, :10], v[:, :10], phi, mu, window=W, chunk=C,
        impl="xla")[0].sum(), argnums=(0, 1))(phi, mu)
    assert float(jnp.abs(d_phi).max()) == float(jnp.abs(d_mu).max()) == 0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_token_reaches_later_windows_through_a_summary_alone(
        impl, monkeypatch):
    """A change to token 21 (window 1, chunk 5) changes no output before it,
    changes its own window's later ones, and reaches windows 2 and 3 only
    through chunk 5's summary: with the summaries held it reaches
    neither."""
    q, k, v, phi, mu = qkv(3)
    how = dict(interpret=True) if impl == "pallas" else dict(impl="xla")

    def run(k, v):
        return eva.eva_attention(q, k, v, phi, mu, window=W, chunk=C,
                                 **how)[0]

    before = run(k, v)
    k2, v2 = k.at[:, 21].add(1.0), v.at[:, 21].add(1.0)
    after = run(k2, v2)
    np.testing.assert_array_equal(before[:, :21], after[:, :21])
    assert float(jnp.abs(before[:, 21:32] - after[:, 21:32]).min()) > 0
    assert float(jnp.abs(before[:, 32:] - after[:, 32:]).max()) > 1e-4
    held = eva.chunk_summaries(k, v, phi, mu, chunk=C, impl="xla")
    monkeypatch.setattr(eva, "chunk_summaries", lambda *a, **kw: held)
    np.testing.assert_array_equal(run(k2, v2)[:, 32:], before[:, 32:])


def test_the_summaries_kernels_agree_with_jax_numpy():
    q, k, v, phi, mu = qkv(4)
    g = jax.random.normal(jax.random.PRNGKey(9), (2, 2, T // C, H, D))

    def loss(how):
        def f(k, v, phi, mu):
            ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=C, **how)
            return (ks * g[0]).sum() + (vs * g[1]).sum(), (ks, vs)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(
            k, v, phi, mu)

    (l_x, out_x), g_x = loss(dict(impl="xla"))
    (l_k, out_k), g_k = loss(dict(interpret=True))
    np.testing.assert_allclose(l_k, l_x, rtol=1e-5)
    for a, b in zip((*out_k, *g_k), (*out_x, *g_x)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ------------------------------------------------------ what is refused

@pytest.mark.parametrize("over,said", [
    (dict(max_seq_len=40), "whole windows"),
    (dict(eva_window=0), "eva_window and eva_chunk"),
    (dict(eva_chunk=5), "whole chunks"),
    (dict(n_kv_heads=2), "key heads"),
    (dict(layer_norm=True), "layer_norm"),
    (dict(layer_types=("eva_attention", "full_attention")),
     "norm_unit_offset"),
])
def test_layers_refuses(over, said):
    with pytest.raises(ValueError, match=said):
        small(**over).layers


def test_a_sequence_of_part_windows_is_refused_where_it_is_traced():
    with pytest.raises(ValueError, match="whole windows"):
        eva.eva_attention(*qkv(seq_len=40), window=W, chunk=C, impl="xla")


def test_a_sequence_axis_is_refused():
    cfg = small()
    params, batch = seeded(cfg), batch_of(cfg)
    with pytest.raises(NotImplementedError, match="sequence axis"):
        tr.transformer_loss_and_readings(
            params, batch, cfg, seq_axis="sequence", seq_size=2)


def test_tied_embeddings_with_several_heads_are_refused():
    with pytest.raises(ValueError, match="n_pred_heads"):
        tr.transformer_init(jax.random.PRNGKey(0), small(tied_embeddings=True))


# ----------------------------------------------------- the head and flops

def test_the_head_of_several_positions_by_hand():
    """Head i's logits are columns 40 i .. 40 (i + 1) - 1 and its target is
    id t + 1 + i; the loss is the mean of the heads' cross-entropies, from
    rows of T + heads ids as from tokens and targets."""
    cfg = small()
    params = seeded(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, T + 3), 0, 40)
    tokens, targets = tr.next_ids(ids, 3)
    assert tokens.shape == (2, T) and targets.shape == (2, T, 3)
    for i in range(3):
        np.testing.assert_array_equal(targets[..., i], ids[:, 1 + i:1 + i + T])
    loss = tr.transformer_loss(params, {"tokens": ids}, cfg)
    same = tr.transformer_loss(
        params, {"tokens": tokens, "targets": targets}, cfg)
    assert float(loss) == float(same)
    hidden = tr.transformer_hidden(params, tokens, cfg)
    logits = (hidden @ params["unembed"]).reshape(2, T, 3, 40)
    logp = jax.nn.log_softmax(logits, axis=-1)
    by_hand = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    assert float(loss) == pytest.approx(float(by_hand), rel=1e-5)


def test_operations_a_token_count_both_kinds_of_key():
    cfg = small()
    assert tr.eva_keys_per_query(T, W, C) == (W + 1) / 2 + 4 * 1.5
    assert tr.eva_keys_per_query(W, W, C) == (W + 1) / 2
    assert tr.eva_keys_per_query(8192, 2048, 16) == 1024.5 + 192
    matmul, attn, head = tr._fwd_flops_per_token(cfg, T)
    assert matmul == 2 * 2 * (4 * 64 * 64 + 3 * 64 * 96)
    assert attn == 2 * 2 * 2 * 64 * ((W + 1) / 2 + 6)
    assert head == 2 * 64 * 3 * 40
