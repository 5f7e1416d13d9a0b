"""Kimi Linear's stack in the program, at a small size on the CPU: Kimi Delta
Attention with beta in (0, 1) three layers to one of latent attention
without positions, a dense first layer, routed layers that hold a share of
the experts under a sigmoid router with a selection bias. The loss and every
gradient against the benchmark's plain reference (float32: the same
mathematics to rounding); the mixed stack's segments and its readings a
layer; latent attention under `rope` false and true; beta's range from the
configuration; the kernels' path against the `jax.numpy` one; remat with
names kept; the rule's arithmetic over the mixed stack; the optimizer's
mask."""

import collections
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.loops.nemotron_h import decayed
from chipbench.reference import kimi_linear as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.ops import kda as kda_lib
from ray_tpu.ops import mamba_passes
from ray_tpu.util import tracing
from tiny_models import distance, one_device

KINDS = ("kda", "kda", "kda", "latent_attention", "kda")
CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=5, n_heads=4, layer_types=KINDS,
    rope=False, kda_heads=4, kda_head_dim=8, kda_gate_rank=4, kda_chunk=16,
    kda_allow_neg_eigval=False, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, n_dense_layers=1, d_ff_dense=48,
    d_ff=16, n_experts=8, experts_held=(2, 4), experts_per_token=3,
    router_score="sigmoid", expert_bias=True, norm_topk_prob=True,
    routed_scaling_factor=2.446, n_shared_experts=1,
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0, max_seq_len=64,
    tied_embeddings=False, dtype=jnp.float32, attention_impl="xla")
# the same sizes under the keys the reference reads
REF = dict(
    kda_head_dim=8, norm_eps=CFG.norm_eps, n_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, n_experts=8,
    experts_held=[2, 4], experts_per_token=3, norm_topk_prob=True,
    routed_scaling_factor=2.446)


def batch_of(seed, rows=1, T=40):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, T + 1), 0, 96)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


def bias_of(seed, cfg=CFG):
    return 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed), model.expert_bias_init(cfg).shape)


def test_the_mixed_stack_s_segments():
    """The dense KDA layer, then the routed four as one period that the
    step walks: a run without a shorter period that carries nothing from
    layer to layer is not cut further. Two periods of the model's pattern
    behind the dense layer's period are a scan of two."""
    dense, routed = model.LayerKind("kda", False), model.LayerKind("kda", True)
    latent = model.LayerKind("latent_attention", True)
    assert model.segments(CFG) == [
        model.Segment((dense,), 1),
        model.Segment((routed, routed, latent, routed), 1)]
    longer = dataclasses.replace(
        CFG, n_layers=9, layer_types=("kda",) + ("kda", "kda",
                                                  "latent_attention", "kda") * 2)
    assert [(len(s.layout), s.periods) for s in model.segments(longer)] == [
        (1, 1), (4, 2)]
    assert CFG.n_routed_layers == 4
    assert model.expert_bias_init(CFG).shape == (4, 8)


def test_loss_and_gradients_are_the_references():
    params = model.transformer_init(jax.random.PRNGKey(1), CFG)
    batch, bias = batch_of(2), bias_of(3)

    def ours(p):
        return model.transformer_loss_and_readings(
            p, batch, CFG, expert_bias=bias)

    (l_sys, readings), g_sys = jax.jit(
        jax.value_and_grad(ours, has_aux=True))(params)
    index = readings["expert_index"]
    assert index.shape == (4, 40, 3)  # the routed four of five
    l_ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p, index: reference.loss(p, batch, REF, index)))(params, index)
    assert abs(float(l_sys) - float(l_ref)) < 1e-5 * abs(float(l_ref))
    assert distance(g_sys, g_ref) < 5e-5
    # the reference, given the same bias, makes the same choice
    own_loss, chosen, beta = jax.jit(lambda p: reference.forward(
        p, batch, REF, expert_bias=bias))(params)
    assert abs(float(own_loss) - float(l_sys)) < 1e-5 * abs(float(l_ref))
    ours_chose = jax.nn.one_hot(index, 8).sum(-2) > 0
    assert bool((ours_chose == chosen).all())
    # and without it another one: the bias is what chose
    _, unbiased, _ = jax.jit(lambda p: reference.forward(p, batch, REF))(params)
    assert not bool((unbiased == chosen).all())
    # every leaf is reached: none has a zero gradient, in any kind of layer
    (dense,), (kda_layer, _, latent, _) = g_sys["blocks"]
    for name, leaf in {**dense, **kda_layer, **latent}.items():
        assert float(jnp.abs(leaf).max()) > 0.0, name
    assert readings["expert_load"].shape == (4, 8)
    assert readings["held_slots"].shape == (4,)
    assert float(readings["kda_log_decay_min"]) < 0.0
    assert float(readings["kda_beta_mean"]) == pytest.approx(
        float(beta), rel=1e-5)
    assert 0.0 < float(readings["kda_beta_mean"]) < 1.0


def test_the_readings_a_layer():
    """Before they are settled the readings stand a layer each, in the
    stack's order: KDA's over the four KDA layers (the dense layer's first,
    then the routed period's three), the routers' over the four routed
    layers (the latent-attention layer's third)."""
    params = model.transformer_init(jax.random.PRNGKey(1), CFG)
    # the first layer's beta is sigmoid(0), the last's sigmoid of a bias
    # large enough to tell: W_b = 0 and a constant input cannot be had, so
    # the last layer's W_b is scaled up instead
    (dense,), (a, b, latent, last) = params["blocks"]
    dense = {**dense, "kda_b": jnp.zeros_like(dense["kda_b"])}
    last = {**last, "kda_b": 30.0 * last["kda_b"]}
    # the latent layer's router is zeroed: every score is 0.5, the choice
    # the first three experts, [0, 1, 2] of which this share holds one
    latent = {**latent, "router": jnp.zeros_like(latent["router"])}
    params = {**params, "blocks": [[dense], [a, b, latent, last]]}
    tokens = batch_of(2)["tokens"]
    _, readings = jax.jit(lambda p: model._hidden_and_readings(
        p, tokens, CFG))(params)
    beta = readings["kda_beta_mean"]
    assert beta.shape == readings["kda_log_decay_min"].shape == (4,)
    assert float(beta[0]) == 0.5
    assert abs(float(beta[3]) - 0.5) > abs(float(beta[1]) - 0.5)
    load = readings["expert_load"]
    assert load.shape == (4, 8) and readings["held_slots"].shape == (4,)
    np.testing.assert_array_equal(load[2], [40, 40, 40, 0, 0, 0, 0, 0])
    assert int(readings["held_slots"][2]) == 40  # expert 2 of [2, 6)
    assert int(load.sum()) == 4 * 40 * 3


@pytest.mark.parametrize("rope", [False, True])
def test_latent_attention_and_positions(rope):
    """Without `rope` nothing reads the positions: any positions at all
    give the same loss and the same gradients. With it the 64-column part
    and the shared key turn (rotary scores depend on the positions'
    differences, so a constant shift alone would not show: the positions
    are stretched as well)."""
    cfg = dataclasses.replace(CFG, rope=rope)
    params = model.transformer_init(jax.random.PRNGKey(1), cfg)
    batch = batch_of(5)
    plain = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (1, 40))

    def loss(p, positions):
        return model.transformer_loss(p, batch, cfg, positions=positions)

    at = jax.jit(jax.value_and_grad(loss))
    l0, g0 = at(params, plain)
    l1, g1 = at(params, plain + 7)
    l2, g2 = at(params, 3 * plain + 7)
    assert float(l1) == pytest.approx(float(l0), rel=1e-6)  # a shift
    if rope:
        assert abs(float(l2) - float(l0)) > 1e-4 * float(l0)
        assert distance(g2, g0) > 1e-2
    else:
        assert float(l2) == float(l1) == float(l0)
        assert distance(g2, g0) == 0.0
    # the record's statements do not move with the rotation
    record = model._OPERATORS["latent_attention"]
    other = dataclasses.replace(cfg, rope=not rope)
    assert record.flops(cfg, 64) == record.flops(other, 64)
    assert record.widths(cfg) == record.widths(other)
    assert record.holds(cfg) == record.holds(other)
    assert record.params(cfg) == 32 * 4 * 12 + 32 * 20 + 16 * 4 * 16 + 32 * 32


def test_rope_scaling_scales_nothing_without_rope():
    """`rope` false with a `rope_scaling` beside it: no YaRN factor on the
    scores' scale either."""
    yarn = (("factor", 40.0), ("mscale", 1.0), ("mscale_all_dim", 1.0),
            ("original_max_position_embeddings", 16), ("beta_fast", 32),
            ("beta_slow", 1), ("type", "yarn"))
    params = model.transformer_init(jax.random.PRNGKey(1), CFG)
    batch = batch_of(5)
    plain = model.transformer_loss(params, batch, CFG)
    scaled = model.transformer_loss(
        params, batch, dataclasses.replace(CFG, rope_scaling=yarn))
    assert float(scaled) == float(plain)
    turned = model.transformer_loss(
        params, batch, dataclasses.replace(CFG, rope=True, rope_scaling=yarn))
    assert float(turned) != float(plain)


@pytest.mark.parametrize("neg_eigval", [False, True])
def test_beta_s_range_is_the_configuration_s(neg_eigval):
    """`kda_allow_neg_eigval` false: beta = sigmoid in (0, 1), the paper's;
    true, the default and Solar-Open2's: 2 sigmoid in (0, 2). The mixer's
    output is the recurrence's, token by token, at that beta."""
    assert TransformerConfig().kda_allow_neg_eigval is True
    cfg = dataclasses.replace(CFG, kda_allow_neg_eigval=neg_eigval)
    blk = {name: leaf[0] for name, leaf in model._OPERATORS["kda"].init(
        jax.random.PRNGKey(2), cfg, 1).items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 32))
    seen = {}

    def recording(q, k, v, g, beta, **kw):
        seen.update(q=q, k=k, v=v, g=g, beta=beta)
        return kda_lib.kda(q, k, v, g, beta, **kw)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(model, "kda", recording)
        y, readings = model._kda_mixer(x, blk, cfg)
    beta = seen["beta"]
    top = 2.0 if neg_eigval else 1.0
    assert 0.0 < float(beta.min()) and float(beta.max()) < top
    assert float(beta.max()) > 0.6 * top  # and it uses its range
    assert float(readings["kda_beta_mean"]) == pytest.approx(
        float(beta.mean()))
    u = model.fused_rmsnorm(x, blk["kda_norm"], eps=cfg.norm_eps)
    assert np.allclose(beta, top * jax.nn.sigmoid(u @ blk["kda_b"]), atol=1e-6)
    # the chunked form at that beta is the recurrence token by token
    o, _, _ = kda_lib.kda(seen["q"], seen["k"], seen["v"], seen["g"], beta,
                          chunk=cfg.kda_chunk)
    by_token, _ = kda_lib.kda_recurrent(
        seen["q"], seen["k"], seen["v"], seen["g"], beta)
    theirs = reference.delta_rule(
        seen["q"], seen["k"], seen["v"], seen["g"], beta)
    assert float(jnp.abs(o - by_token).max()) < 1e-5
    assert float(jnp.abs(by_token - theirs).max()) < 1e-5


def test_the_kernels_path_is_the_numpy_path():
    """Heads of 128 tile: KDA's kernels (interpret mode here) under the
    mixed stack give the `jax.numpy` path's loss and gradients, the
    recurrence's, the short convolutions' (`kda_conv_fwd`, `kda_conv_bwd`;
    PR 67) and the output norm and gate's (`kda_out_norm_fwd`,
    `kda_out_norm_bwd`; PR 69) alike; each path counts its calls, three
    convolutions and one output norm a layer."""
    cfg = dataclasses.replace(
        CFG, n_layers=3, layer_types=("kda", "latent_attention", "kda"),
        kda_heads=2, kda_head_dim=128, kda_chunk=64)
    params = model.transformer_init(jax.random.PRNGKey(1), cfg)

    def off_its_first_value(path, leaf):  # the gate's bias 0, the scale 1
        if path[-1].key in ("kda_g_bias", "kda_out_norm"):
            return leaf + 0.1 * jax.random.normal(
                jax.random.PRNGKey(len(path)), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(off_its_first_value, params)
    batch, bias = batch_of(4, T=128), bias_of(3, cfg)

    def ours(p):
        return model.transformer_loss(p, batch, cfg, expert_bias=bias)

    def counted(before=None):
        now = tracing.counters()
        return tuple(now.get(name, 0) - (before or {}).get(name, 0)
                     for name in ("train.kda_conv_calls_kernels",
                                  "train.kda_conv_calls_numpy",
                                  "train.kda_out_norm_calls_kernels",
                                  "train.kda_out_norm_calls_numpy"))

    before = tracing.counters()
    numpy_path = jax.jit(jax.value_and_grad(ours))(params)
    assert counted(before) == (0, 6, 0, 2)
    said = model._calls_said(before)
    assert said == (
        "; KDA's short convolutions: 0 calls by the kernels kda_conv_fwd and "
        "kda_conv_bwd, 6 by jax.numpy; KDA's output norms and gates: 0 calls "
        "by the kernels kda_out_norm_fwd and kda_out_norm_bwd, 2 by jax.numpy")
    before = tracing.counters()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(model, "kda", functools.partial(
            kda_lib.kda, interpret=True))  # the kernels, whatever `impl`
        patch.setattr(model, "_kda_conv_kernels", lambda cfg, T=None: True)
        patch.setattr(model, "causal_conv_silu", functools.partial(
            mamba_passes.causal_conv_silu, interpret=True))
        patch.setattr(model, "_kda_out_norm_kernels",
                      lambda cfg, T=None: True)
        patch.setattr(model, "group_rmsnorm_gated", functools.partial(
            mamba_passes.group_rmsnorm_gated, interpret=True))
        traced = jax.make_jaxpr(jax.value_and_grad(ours))(params)
        kernels = jax.jit(jax.value_and_grad(ours))(params)
    assert counted(before) == (12, 0, 4, 0)  # traced twice
    assert not model._calls_said(tracing.counters())
    calls = collections.Counter(
        re.findall(r"name=(kda_(?:conv|out_norm)_\w+)", str(traced)))
    assert set(calls) == {"kda_conv_fwd", "kda_conv_bwd",
                          "kda_out_norm_fwd", "kda_out_norm_bwd"}
    assert float(kernels[0]) == pytest.approx(float(numpy_path[0]), rel=1e-5)
    assert distance(kernels[1], numpy_path[1]) < 1e-4
    for name in ("kda_g_bias", "kda_out_norm", "kda_g2"):
        ours_, theirs = (
            [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(grads)
             if path[-1].key == name] for grads in (kernels[1], numpy_path[1]))
        assert theirs and all(bool(leaf.any()) for leaf in theirs), name
        assert distance(ours_, theirs) < 1e-4, name


def test_the_path_is_the_code_s_choice_from_the_operators_and_the_shape():
    """The kernels where the step's operators resolve to Pallas and the
    streams tile; `jax.numpy` on the CPU's path, at heads that are no whole
    lane tiles, at tokens that are no whole blocks of 16 rows and at more
    taps than a block reads of the one before it. `_KDA.holds` follows."""
    on_chip = dataclasses.replace(
        CFG, kda_heads=2, kda_head_dim=128, attention_impl="pallas")
    assert model._kda_conv_kernels(on_chip)
    assert model._kda_conv_kernels(on_chip, 128)
    assert not model._kda_conv_kernels(on_chip, 40)
    assert not model._kda_conv_kernels(
        dataclasses.replace(on_chip, attention_impl="xla"), 128)
    assert not model._kda_conv_kernels(
        dataclasses.replace(on_chip, kda_head_dim=8), 128)
    assert not model._kda_conv_kernels(
        dataclasses.replace(on_chip, kda_conv_taps=12), 128)
    # the output norm and gate's kernels by the same two observables
    assert model._kda_out_norm_kernels(on_chip)
    assert model._kda_out_norm_kernels(on_chip, 128)
    assert model._kda_out_norm_kernels(
        dataclasses.replace(on_chip, kda_conv_taps=12), 128)
    assert not model._kda_out_norm_kernels(on_chip, 40)
    assert not model._kda_out_norm_kernels(
        dataclasses.replace(on_chip, attention_impl="xla"), 128)
    assert not model._kda_out_norm_kernels(
        dataclasses.replace(on_chip, kda_head_dim=8), 128)
    record, wide = model._OPERATORS["kda"], 2 * 128
    assert record.holds(dataclasses.replace(on_chip, kda_conv_taps=12)) == (
        record.holds(on_chip) + 2 * wide)


def test_remat_with_names_kept_is_the_same_step():
    params = model.transformer_init(jax.random.PRNGKey(3), CFG)
    batch, bias = batch_of(4), bias_of(5)
    plain = jax.jit(jax.value_and_grad(lambda p: model.transformer_loss(
        p, batch, CFG, expert_bias=bias)))(params)
    remat = dataclasses.replace(CFG, remat=True)
    names = ("attn_res", "attn_qkv", "kda_res", "kda_qkv", "shared_up",
             "mlp_gate")
    again = jax.jit(jax.value_and_grad(lambda p: model.transformer_loss(
        p, batch, remat, expert_bias=bias, saved_names=names)))(params)
    assert abs(float(again[0]) - float(plain[0])) < 1e-6
    assert distance(again[1], plain[1]) < 1e-5


def test_the_rule_prices_the_mixed_stack():
    """`saved_activations` over the stack: the names of all three kinds of
    sublayer in `_SAVE_ORDER`'s order, a moment a walked layer, and more
    room only ever adds names."""
    cfg = dataclasses.replace(CFG, remat=True, dtype=jnp.bfloat16)
    tokens, whole = 64, model._whole_param_bytes(cfg)
    terms = model._terms(cfg, tokens, whole)
    every = terms.saved_bytes()
    assert list(every) == ["attn_ctx", "attn_res", "attn_qkv", "kda_res",
                           "kda_qkv", "shared_gate", "shared_up", "mlp_gate",
                           "mlp_up"]
    assert every["kda_res"] == 4 * tokens * 32 * 2
    assert every["kda_qkv"] == 4 * tokens * 3 * 4 * 8 * 2
    assert every["attn_res"] == tokens * 32 * 2  # the one latent layer
    # out of `wq`, `wkv_a` and `wkv_b`: 4 x 12 + 20 + 4 x 16
    assert every["attn_qkv"] == tokens * (48 + 20 + 64) * 2
    assert every["mlp_gate"] == tokens * 48 * 2  # the dense layer's
    assert [m.name for m in terms.moments()] == [
        "optimizer", "head", "layer 4", "layer 3", "layer 2", "layer 1",
        "layer 0"]
    kept = []
    for limit in (1 << 30, (1 << 30) + (4 << 20), 3 << 30):
        names = list(model.saved_activations(
            cfg, tokens, 3 * whole, whole, limit))
        assert names == list(every)[:len(names)] and len(names) >= len(kept)
        kept = names
    assert kept == list(every)


def test_kda_s_entering_states_at_the_cell_s_width():
    """What `_KDA.holds` says of the kernels' path at 32 heads of 128 in
    chunks of 64: the entering states are 537 MB a layer of 16,384 tokens in
    float32, eight times `solaropen2.tokens8k`'s 67 MB (four times the
    heads, twice the tokens)."""
    record = model._OPERATORS["kda"]
    kimi = dataclasses.replace(
        CFG, kda_heads=32, kda_head_dim=128, kda_chunk=64,
        dtype=jnp.bfloat16, attention_impl="pallas")
    solar = dataclasses.replace(kimi, kda_heads=64, heads_held=(0, 8))
    wide = 32 * 128
    states = 2 * wide * 128 // 64  # float32, in elements of bf16 a token
    # ten widths: the silu's q and k stay in `kda_conv_fwd`'s VMEM (PR 67)
    assert record.holds(kimi) == 10 * wide + 2 * 2 * wide + states
    assert record.holds(dataclasses.replace(kimi, kda_conv_taps=12)) == (
        12 * wide + 2 * 2 * wide + states)  # taps the kernels do not take
    assert 16384 * states * 2 == 536_870_912
    assert record.holds(kimi) == 4 * record.holds(solar)
    assert 8192 * (2 * 8 * 128 * 128 // 64) * 2 == 67_108_864
    matmul, pairs = record.flops(kimi, 16384)
    assert pairs == 0
    assert matmul == 2 * record.params(kimi) + 32 * (
        10 * 64 * 128 + 6 * 128 * 128)


def test_the_step_trains_and_decays_matrices_only():
    no_decay = ["A_log", "dt_bias", "kda_conv", "g_bias", "norm"]
    optimizer = optax.adamw(
        3e-3, b1=0.9, b2=0.95, weight_decay=0.1,
        mask=lambda params: decayed(params, no_decay))
    init_state, step, _ = make_train_step(CFG, one_device(), optimizer)
    state = init_state(jax.random.PRNGKey(0))
    mask = decayed(state["params"], no_decay)
    (dense,), (kda_layer, _, latent, _) = mask["blocks"]
    assert {name for name, on in kda_layer.items() if not on} == {
        "kda_norm", "kda_conv", "kda_g_bias", "kda_A_log", "kda_dt_bias",
        "kda_out_norm", "mlp_norm"}
    assert {name for name, on in latent.items() if not on} == {
        "attn_norm", "kv_norm", "mlp_norm"}
    assert {name for name, on in dense.items() if on} >= {
        "w_gate", "w_up", "w_down", "kda_q", "kda_b"}
    assert mask["embed"] and mask["unembed"] and not mask["final_norm"]
    assert float(jnp.abs(state["expert_bias"]).max()) == 0.0
    batch = batch_of(6, rows=1, T=32)
    losses = []
    for _ in range(6):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert out["held_slots"].shape == (4,) and int(out["dropped_slots"].sum()) == 0
    assert out["expert_load"].shape == (4, 8)
    assert float(out["kda_log_decay_min"]) < 0.0 < float(out["kda_beta_mean"]) < 1.0
    # six steps of the rule: a rate up or down a step, no gradient
    assert 0.0 < float(out["expert_bias_abs_max"]) <= 6 * CFG.expert_bias_update_rate + 1e-9
    assert state["expert_bias"].shape == (4, 8)
