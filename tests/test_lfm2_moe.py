"""A stack of unlike layers, on the CPU at a tiny size: the segments, the gated
short convolution, per-head QK-norm, the sigmoid router with its selection
bias, and a routed layer that holds a share of the experts (LFM2-24B-A2B's
mechanisms, `ray_tpu/models/transformer.py` and `ray_tpu/ops/moe.py`), each
against something written out plainly and the whole against the benchmark's
plain reference."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.reference import lfm2_moe as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    LayerKind, expert_bias_init, param_shardings, segments, transformer_init,
    transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.parallel import make_mesh
from tiny_models import (
    as_reference_config, batch_of, first_layer, init, key, one_device, program,
    value_and_grad)

LFM2 = dict(
    vocab_size=128, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
    d_ff=32, d_ff_dense=96, max_seq_len=64, rope_theta=1e6, norm_eps=1e-5,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    n_dense_layers=1, conv_taps=3, n_experts=8, experts_per_token=2,
    experts_held=(2, 4), norm_topk_prob=True, norm_topk_eps=1e-6,
    router_score="sigmoid", expert_bias=True, qk_norm="head",
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0, dtype=jnp.float32,
)


def tiny(**over):
    return TransformerConfig(**{**LFM2, **over})


def seeded_bias(cfg, seed=5, std=0.1):
    return std * jax.random.normal(key(seed), expert_bias_init(cfg).shape)


# ---------------------------------------------------------------- segments

FULL_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 9 + (
    "full_attention", "conv")


@pytest.mark.parametrize("name,cfg,expected", [
    ("one_kind", TransformerConfig(n_layers=6), [(1, 6)]),
    ("one_kind_routed", TransformerConfig(n_layers=3, n_experts=4), [(1, 3)]),
    ("the_cut", tiny(), [(1, 1), (4, 1)]),
    ("two_periods", tiny(n_layers=9, layer_types=LFM2["layer_types"]
                         + LFM2["layer_types"][1:]), [(1, 1), (4, 2)]),
    ("dense_first_k", tiny(n_layers=4, layer_types=(), n_dense_layers=2),
     [(1, 2), (1, 2)]),
    ("published_40", tiny(n_layers=40, layer_types=FULL_TYPES,
                          n_dense_layers=2), [(1, 2), (38, 1)]),
])
def test_the_stack_is_cut_into_runs_of_whole_periods(name, cfg, expected):
    segs = segments(cfg)
    assert [(len(s.layout), s.periods) for s in segs] == expected
    laid_out = [kind for s in segs for _ in range(s.periods) for kind in s.layout]
    assert tuple(laid_out) == cfg.layers  # every layer once, in order


def test_parameter_tree_and_shardings_of_the_cut():
    cfg = tiny()
    params = transformer_init(key(0), cfg)
    dense, period = params["blocks"]
    assert len(dense) == 1 and len(period) == 4
    assert dense[0]["w_gate"].shape == (1, 64, 96) and "router" not in dense[0]
    assert dense[0]["conv_in"].shape == (1, 64, 192)
    assert dense[0]["conv_w"].shape == (1, 3, 64)
    attention, conv = period[0], period[1]
    assert attention["q_norm"].shape == (1, 16)  # one scale for every head
    assert attention["k_norm"].shape == (1, 16) and "conv_in" not in attention
    assert "wq" not in conv and "q_norm" not in conv
    for blk in period:  # the router keeps its width, the experts are the held
        assert blk["router"].shape == (1, 64, 8)
        assert blk["w_gate"].shape == (1, 4, 64, 32)
        assert blk["w_down"].shape == (1, 4, 32, 64)
    assert expert_bias_init(cfg).shape == (4, 8)
    mesh = make_mesh({"expert": 2, "fsdp": 2}, devices=jax.devices()[:4])
    shard = param_shardings(mesh, cfg)
    assert jax.tree.structure(shard) == jax.tree.structure(params)
    assert shard["blocks"][1][1]["w_gate"].spec == (None, "expert", "fsdp", None)
    assert shard["blocks"][0][0]["conv_in"].spec == (None, "fsdp", None)


def test_one_kind_keeps_its_parameter_tree_and_its_lowered_step():
    """The segments add nothing to a model of one kind of layer: its tree is
    the one stacked dict with the values it had, and its loss lowers to the
    text of one `lax.scan` over that dict written out here."""
    for over in (dict(), dict(n_experts=4, experts_per_token=2, qk_norm=True)):
        cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=3,
                                n_heads=2, d_ff=48, dtype=jnp.float32,
                                remat=True, **over)
        params = transformer_init(key(0), cfg)
        blocks = params["blocks"]
        assert isinstance(blocks, dict) and blocks["wq"].shape == (3, 32, 32)
        k_blk = jax.random.split(key(0), 3)[1]
        ks = jax.random.split(k_blk, 7)
        np.testing.assert_array_equal(
            blocks["wq"],
            jax.random.normal(ks[0], (3, 32, 32), jnp.float32) / np.sqrt(32))
        shape = (3, 4, 48, 32) if over else (3, 48, 32)
        np.testing.assert_array_equal(
            blocks["w_down"],
            jax.random.normal(ks[6], shape, jnp.float32) / np.sqrt(48))
        batch = batch_of(cfg, seq=16)

        def one_scan(params, batch):
            tokens = batch["tokens"]
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
            with jax.named_scope("embed"):
                x = params["embed"].astype(cfg.dtype)[tokens]
            blk_fn = jax.checkpoint(lambda x, blk: model._block(
                x, blk, positions, None, cfg, cfg.layers[0], None, 1,
                sliced=True),
                static_argnums=())
            x, readings = jax.lax.scan(blk_fn, x, params["blocks"])
            with jax.named_scope("final_norm"):
                x = model.fused_rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
            return x, readings

        def through_segments(params, batch):
            return model._hidden_and_readings(params, batch["tokens"], cfg)

        texts = [jax.jit(f).lower(params, batch).as_text()
                 for f in (one_scan, through_segments)]
        assert texts[0].replace("one_scan", "f") == texts[1].replace(
            "through_segments", "f")


# ---------------------------------------------------- the short convolution

def test_short_conv_is_a_depthwise_causal_convolution_between_two_gates():
    cfg = tiny()
    blk = jax.tree.map(lambda x: x[0], transformer_init(key(0), cfg)["blocks"][0][0])
    x = jax.random.normal(key(3), (2, 16, 64))
    y = model.fused_rmsnorm(x, blk["conv_norm"], eps=cfg.norm_eps)
    b, c, xs = jnp.split(y @ blk["conv_in"], 3, axis=-1)
    # [batch, time, channels] with one [taps, 1, 1] filter per channel; two
    # zeros before the sequence make it causal, w[2] weighs the token itself
    conv = jax.lax.conv_general_dilated(
        b * xs, blk["conv_w"][:, None, :], window_strides=(1,),
        padding=[(2, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=64)
    expected = (c * conv) @ blk["conv_out"]
    np.testing.assert_allclose(
        model._short_conv(x, blk, cfg), expected, rtol=2e-5, atol=2e-6)
    # causal: a later token does not move an earlier output
    moved = model._short_conv(x.at[:, 9].add(1.0), blk, cfg)
    np.testing.assert_array_equal(moved[:, :9], model._short_conv(x, blk, cfg)[:, :9])
    assert not np.allclose(moved[:, 9:12], expected[:, 9:12])
    np.testing.assert_allclose(moved[:, 12:], expected[:, 12:], rtol=2e-5, atol=2e-6)


def test_qk_norm_per_head_norms_every_head_over_its_own_width():
    cfg = tiny(n_layers=1, layer_types=(), n_dense_layers=0, n_experts=0,
               expert_bias=False, experts_held=None)
    params = transformer_init(key(0), cfg)
    assert params["blocks"]["q_norm"].shape == (1, 16)
    batch = batch_of(cfg)
    loss = lambda p: float(transformer_loss_and_readings(p, batch, cfg)[0])  # noqa: E731
    # one head's columns of wq scaled: undone by that head's own norm, where
    # a norm over the whole projection would shrink the other heads
    wq = params["blocks"]["wq"]
    scaled = {**params, "blocks": {
        **params["blocks"], "wq": wq.at[..., :16].multiply(5.0)}}
    assert loss(scaled) == pytest.approx(loss(params), rel=1e-5)
    whole = dataclasses.replace(cfg, qk_norm="projection")
    params_w = transformer_init(key(0), whole)
    scaled_w = {**params_w, "blocks": {
        **params_w["blocks"], "wq": wq.at[..., :16].multiply(5.0)}}
    assert float(transformer_loss_and_readings(scaled_w, batch, whole)[0]) != (
        pytest.approx(float(transformer_loss_and_readings(
            params_w, batch, whole)[0]), rel=1e-5))


# ------------------------------------------------------------------ routing

def test_route_is_unchanged_for_a_softmax_router():
    logits = jax.random.normal(key(0), (12, 8)) * 3
    probs, weights, index = moe.route(logits, 2)
    expected = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_array_equal(probs, expected)
    top_w, top_i = jax.lax.top_k(expected, 2)
    np.testing.assert_array_equal(weights, top_w)
    np.testing.assert_array_equal(index, top_i)
    _, renormed, _ = moe.route(logits, 2, True)
    np.testing.assert_array_equal(renormed, top_w / top_w.sum(-1, keepdims=True))


def test_route_sigmoid_chooses_by_biased_score_and_weighs_by_the_score():
    logits = jax.random.normal(key(1), (40, 8)) * 2
    bias = jnp.array([0.0, 0.4, -0.4, 0.0, 0.2, 0.0, -0.2, 0.0])
    scores, weights, index = moe.route(
        logits, 3, True, score="sigmoid", bias=bias, eps=1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    np.testing.assert_allclose(scores, s, rtol=1e-6)
    biased = s + np.asarray(bias, np.float64)
    moved = 0
    for t in range(40):
        chosen = sorted(range(8), key=lambda e: -biased[t, e])[:3]
        assert list(np.asarray(index[t])) == chosen
        moved += chosen != sorted(range(8), key=lambda e: -s[t, e])[:3]
        np.testing.assert_allclose(
            weights[t], s[t, chosen] / (s[t, chosen].sum() + 1e-6), rtol=1e-5)
    assert moved > 5  # the bias decided something
    # not divided: the scores as they are; no bias: the largest scores
    _, raw, _ = moe.route(logits, 3, False, score="sigmoid", bias=bias)
    np.testing.assert_allclose(
        raw, np.take_along_axis(s, np.asarray(index), -1), rtol=1e-6)
    _, _, plain = moe.route(logits, 3, True, score="sigmoid")
    np.testing.assert_array_equal(plain, np.argsort(-s, axis=-1)[:, :3])


def test_bias_takes_no_gradient_and_moves_toward_balance():
    cfg = tiny()
    params = init(key(0), cfg)
    batch = batch_of(cfg)
    bias = seeded_bias(cfg)
    grad = jax.jit(jax.grad(lambda b: transformer_loss_and_readings(
        params, batch, cfg, expert_bias=b)[0]))(bias)
    np.testing.assert_array_equal(grad, jnp.zeros_like(grad))
    load = jnp.array([[9, 1, 5, 5], [5, 5, 5, 5]])
    np.testing.assert_allclose(
        moe.update_expert_bias(jnp.zeros((2, 4)), load, 1e-3),
        [[-1e-3, 1e-3, 0, 0], [0, 0, 0, 0]])
    # in the step: owned by no optimizer, moved by the rate, load evening out
    big = dataclasses.replace(cfg, expert_bias_update_rate=0.02)
    init_state, step, _ = make_train_step(big, one_device(), optax.sgd(0.0))
    state = init_state(key(0))
    state["expert_bias"] = 0.3 * jax.random.normal(key(9), (4, 8))
    n_opt = len(jax.tree.leaves(state["opt"]))
    spread = []
    for _ in range(30):
        before = np.asarray(state["expert_bias"])  # the state is donated
        state, out = step(state, batch)
        assert out["loss"].shape == () and np.isfinite(out["loss"])
        moved = np.abs(np.asarray(state["expert_bias"]) - before)
        assert np.logical_or(moved < 1e-6, np.abs(moved - 0.02) < 1e-6).all()
        spread.append(float(out["expert_load"].astype(jnp.float32).std(-1).mean()))
    assert len(jax.tree.leaves(state["opt"])) == n_opt
    assert out["expert_bias_abs_max"] == pytest.approx(
        float(jnp.abs(state["expert_bias"]).max()))
    assert np.mean(spread[-5:]) < 0.5 * np.mean(spread[:3])


# ---------------------------------------------------------------- the share

def plain_layer(y, w, bias, k, held=None):
    """The routed feed-forward written out: every expert on every token,
    masked by the choice; `held = (first, n)` keeps those experts' terms."""
    s = jax.nn.sigmoid(jnp.dot(y, w["router"], precision="highest"))
    _, best = jax.lax.top_k(s + bias, k)
    picked = jax.nn.one_hot(best, s.shape[-1]).sum(-2)
    p = s * picked
    p = p / (p.sum(-1, keepdims=True) + 1e-6)
    hidden = jax.nn.silu(jnp.einsum("td,edf->tef", y, w["w_gate"])) * jnp.einsum(
        "td,edf->tef", y, w["w_up"])
    every = jnp.einsum("tef,efd->ted", hidden, w["w_down"])
    if held is not None:
        mine = slice(held[0], held[0] + held[1])
        every, p = every[:, mine], p[:, mine]
    return jnp.einsum("ted,te->td", every, p)


def share_of(w, first, n):
    return {**w, **{k: w[k][first:first + n] for k in ("w_gate", "w_up", "w_down")}}


@pytest.fixture
def layer():
    """8 small experts, 3 a token, over 96 tokens."""
    cfg = tiny(n_layers=1, layer_types=("conv",), n_dense_layers=0,
               experts_per_token=3, experts_held=None)
    w = first_layer(cfg)
    y = jax.random.normal(key(5), (2, 48, 64))
    bias = 0.2 * jax.random.normal(key(6), (8,))
    return cfg, w, y, bias


def test_the_shares_add_up_to_the_uncut_layer(layer, monkeypatch):
    """Held 2 a share, the four shares' routed outputs sum to what the
    uncut reference gives for the layer; nothing is computed alike on every
    share (no shared expert), so nothing is counted twice."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)  # chunks that bind at this size
    cfg, w, y, bias = layer
    whole = plain_layer(y.reshape(-1, 64), w, bias, 3)
    parts, held_slots, chunks = [], 0, set()
    for first in range(0, 8, 2):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
        out, readings = model._routed_ffn(
            y, share_of(w, first, 2), share_cfg, bias=bias)
        np.testing.assert_allclose(
            out.reshape(-1, 64),
            plain_layer(y.reshape(-1, 64), w, bias, 3, (first, 2)),
            rtol=1e-4, atol=1e-5)
        assert int(readings["dropped_slots"]) == 0
        assert readings["expert_load"].shape == (8,)  # all the experts
        assert int(readings["expert_load"].sum()) == 96 * 3
        assert int(readings["held_slots"]) == int(
            readings["expert_load"][first:first + 2].sum())
        held_slots += int(readings["held_slots"])
        chunks.add(-(-int(readings["held_slots"]) // moe.held_chunk(288, 2, 8)))
        parts.append(out.reshape(-1, 64))
    assert held_slots == 96 * 3 and chunks == {1, 2}  # one chunk, and more
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    # all the experts held is the layer itself
    out, readings = model._routed_ffn(y, w, cfg, bias=bias)
    np.testing.assert_allclose(out.reshape(-1, 64), whole, rtol=1e-4, atol=1e-5)
    assert "held_slots" not in readings


def test_a_chunk_is_one_and_a_quarter_even_shares_in_whole_tiles():
    assert moe.held_chunk(131072, 8, 64) == 20480
    assert moe.held_chunk(8192, 8, 64) == 1280
    assert moe.held_chunk(256, 4, 16) == 256  # never more than the slots
    assert moe.held_chunk(131072, 64, 64) == 131072


@functools.cache
def the_cut():
    """The cut with its drawn weights, a batch and a bias, and its (loss,
    readings) and gradients at the rows and slack a chunk has: one program
    for the three cases that set theirs against it."""
    cfg = tiny()
    params, batch, bias = init(key(0), cfg), batch_of(cfg), seeded_bias(cfg)
    return cfg, params, batch, bias, program(
        cfg, params, batch, expert_bias=bias)


@pytest.mark.parametrize("slack", [0.01, 0.4, 100.0])
def test_few_rows_a_chunk_or_all_in_one_give_one_loss_and_one_gradient(
        slack, monkeypatch):
    cfg, params, batch, bias, ((loss, readings), grads) = the_cut()
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    monkeypatch.setattr(moe, "_HELD_SLACK", slack)  # 8 rows a chunk ... all
    (loss_2, readings_2), grads_2 = program(
        cfg, params, batch, expert_bias=bias)
    assert float(loss_2) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(readings["dropped_slots"], 0)
    np.testing.assert_array_equal(readings_2["dropped_slots"], 0)
    assert readings["held_slots"].shape == (4,)


@pytest.mark.parametrize("path", ["xla", "interpret"])
def test_rows_behind_the_held_ones_are_selected_away_not_multiplied(path):
    """Poison every row no tile visits: the outputs and all the gradients
    are those of clean buffers, and finite."""
    t, k, d, f, n_experts, first, n = 40, 2, 128, 128, 8, 2, 3
    kw = dict(interpret=True, block_rows=8) if path == "interpret" else dict(impl="xla")
    index = jax.random.randint(key(0), (t, k), 0, n_experts)
    slots = moe.sort_slots(index, n_experts, (first, n))
    rows = slots.group_sizes.sum()
    assert 0 < int(rows) < t * k
    assert int(rows) == int(((index >= first) & (index < first + n)).sum())
    x = jax.random.normal(key(1), (t, d))
    w_gate = jax.random.normal(key(2), (n, d, f)) / 8
    w_down = jax.random.normal(key(3), (n, f, d)) / 8
    weights = jax.random.uniform(key(4), (t, k))
    live = (jnp.arange(t * k) < rows)[:, None]

    def layer_out(x, w_gate, w_down, weights, poison):
        xs = moe.dispatch(x, slots.order, slots.inverse, rows)
        xs = jnp.where(live, xs, poison)
        hidden = moe.grouped_matmul(
            xs, w_gate, slots.group_sizes, tail=True, **kw)
        hidden = jnp.where(live, hidden, poison)  # whatever the buffer held
        return moe.project_and_combine(
            hidden, w_down, weights, slots, rows=rows, **kw)

    def loss(x, w_gate, w_down, weights, poison):
        return (layer_out(x, w_gate, w_down, weights, poison) ** 2).sum()

    args = (x, w_gate, w_down, weights)
    clean = layer_out(*args, 0.0)
    poisoned = layer_out(*args, jnp.nan)
    assert np.isfinite(np.asarray(poisoned)).all()
    np.testing.assert_allclose(poisoned, clean, rtol=1e-6)
    # a token none of whose experts is held gets nothing
    none_held = ~np.asarray(
        ((index >= first) & (index < first + n)).any(axis=1))
    assert none_held.any()
    np.testing.assert_array_equal(np.asarray(clean)[none_held], 0.0)
    g_clean = jax.grad(loss, argnums=(0, 1, 2, 3))(*args, 0.0)
    g_poisoned = jax.grad(loss, argnums=(0, 1, 2, 3))(*args, jnp.nan)
    for a, b in zip(g_clean, g_poisoned):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)
    # and they are the gradients of the held experts' part written out
    def plain(x, w_gate, w_down, weights):
        held = jnp.logical_and(index >= first, index < first + n)
        e = jnp.clip(index - first, 0, n - 1)
        hidden = jnp.einsum("td,tkdf->tkf", x, w_gate[e])
        ys = jnp.einsum("tkf,tkfd->tkd", hidden, w_down[e])
        return ((ys * (weights * held)[..., None]).sum(1) ** 2).sum()
    for a, b in zip(jax.grad(plain, argnums=(0, 1, 2, 3))(*args), g_clean):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)


# ------------------------------------------------- against the reference

KINDS = {
    "conv_dense": dict(n_layers=1, layer_types=("conv",), n_dense_layers=1),
    "attention_routed": dict(n_layers=1, layer_types=("full_attention",),
                             n_dense_layers=0),
    "conv_routed": dict(n_layers=1, layer_types=("conv",), n_dense_layers=0),
    "whole": dict(),
    "two_periods": dict(n_layers=9, layer_types=LFM2["layer_types"]
                        + LFM2["layer_types"][1:]),
}


@pytest.mark.parametrize("kind", KINDS)
def test_the_program_agrees_with_the_plain_reference(kind, monkeypatch):
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    cfg = tiny(**KINDS[kind], remat=kind == "whole")
    config = as_reference_config(cfg)
    params = init(key(0), cfg)
    batch = batch_of(cfg)
    bias = seeded_bias(cfg) if cfg.n_routed_layers else None
    (loss, readings), grads = program(cfg, params, batch, expert_bias=bias)
    ref_loss, ref_grads = value_and_grad(
        lambda p: reference.loss(p, batch, config, expert_bias=bias), params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)
    if bias is not None:  # the same experts, layer by layer
        _, chosen = jax.jit(lambda p: reference.forward(
            p, batch, config, expert_bias=bias))(params)
        ours = jax.nn.one_hot(readings["expert_index"], 8).sum(-2) > 0
        np.testing.assert_array_equal(ours, chosen)
        assert readings["expert_load"].shape == (cfg.n_routed_layers, 8)
        # without the bias the reference chooses otherwise
        _, unbiased = jax.jit(
            lambda p: reference.forward(p, batch, config))(params)
        assert (np.asarray(unbiased) != np.asarray(chosen)).mean() > 0.02


def test_kernels_in_interpret_mode_give_the_share_the_xla_path_s_loss(monkeypatch):
    cfg = tiny(d_model=128, n_heads=2, n_kv_heads=1, d_ff=128, d_ff_dense=128,
               n_layers=2, layer_types=("conv", "conv"))
    params = init(key(0), cfg)
    batch = batch_of(cfg, rows=1, seq=24)
    bias = seeded_bias(cfg)
    f = lambda p: transformer_loss_and_readings(  # noqa: E731
        p, batch, cfg, expert_bias=bias)[0]
    loss, grads = value_and_grad(f, params)
    real = moe._kernels
    monkeypatch.setattr(moe, "_kernels", lambda impl, interpret: True)
    for name in ("gmm", "tgmm", "sum_held"):
        fn = getattr(moe, name)
        monkeypatch.setattr(moe, name, lambda *a, _fn=fn, **kw: _fn(
            *a, **{**kw, "interpret": True}))
    loss_k, grads_k = value_and_grad(f, params)
    assert real("xla", False) is False
    assert float(loss_k) == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_k)):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=1e-5)


def test_flops_count_the_held_share_and_every_kind_of_layer():
    cfg = tiny()
    d = 64
    conv, attention = 2 * d * 3 * d + 2 * d * d, 2 * d * (64 + 2 * 32) + 2 * 64 * d
    routed = 2 * d * 8 + 2 * (4 / 8) * 2 * 3 * d * 32
    expected = 3 * (4 * conv + attention + 2 * 3 * d * 96 + 4 * routed
                    + 2 * 2 * 64 * (33 / 2) + 2 * d * 128)
    assert model.flops_per_token(cfg, 32) == pytest.approx(expected)
    assert LayerKind("conv", True) in cfg.layers
