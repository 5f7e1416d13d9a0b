"""Solar-Open2's stack in the program, at a small size on the CPU: one GQA
layer without rotary positions under an elementwise gate and three KDA
layers, every layer routed, a share of the heads and of the experts. The
loss and every gradient against the benchmark's plain reference (float32:
the same mathematics to rounding); the stack scanned over two periods; the
operators' readings beside the routers'; remat with names kept; the rule's
arithmetic for the new kind; the optimizer's mask."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.loops.nemotron_h import decayed
from chipbench.reference import solar_open2 as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from tiny_models import distance, one_device

CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=4, n_heads=8, n_kv_heads=2, d_head=8,
    heads_held=(4, 4), layer_types=("full_attention", "kda", "kda", "kda"),
    rope=False, attn_gate="elementwise", kda_heads=8, kda_head_dim=8,
    kda_gate_rank=4, kda_chunk=16, d_ff=16, d_ff_shared=16, n_experts=8,
    experts_held=(2, 4), experts_per_token=3, norm_topk_prob=True,
    n_shared_experts=1, router_aux_loss_coef=0.01, router_z_loss_coef=0.0,
    max_seq_len=64, tied_embeddings=False, dtype=jnp.float32,
    attention_impl="xla")
# the same sizes under the keys the reference reads
REF = dict(
    d_head=8, kda_head_dim=8, norm_eps=CFG.norm_eps, n_experts=8,
    experts_held=[2, 4], experts_per_token=3, norm_topk_prob=True,
    routed_scaling_factor=1.0, router_aux_loss_coef=0.01)


def batch_of(seed, rows=1, T=40):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, T + 1), 0, 96)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


@pytest.mark.parametrize("periods", [1, 2])
def test_loss_and_gradients_are_the_references(periods):
    """One period (a stack the step walks) and two (a scanned segment)."""
    cfg = dataclasses.replace(
        CFG, n_layers=4 * periods, layer_types=CFG.layer_types * periods)
    assert [(len(s.layout), s.periods) for s in model.segments(cfg)] == [
        (4, periods)]
    params = model.transformer_init(jax.random.PRNGKey(1), cfg)
    batch = batch_of(2)

    def ours(p):
        loss, readings = model.transformer_loss_and_readings(p, batch, cfg)
        return loss, readings

    (l_sys, readings), g_sys = jax.jit(
        jax.value_and_grad(ours, has_aux=True))(params)
    index = readings["expert_index"]
    assert index.shape == (4 * periods, 40, 3)
    l_ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p, index: reference.loss(p, batch, REF, index)))(params, index)
    assert abs(float(l_sys) - float(l_ref)) < 1e-5 * abs(float(l_ref))
    assert distance(g_sys, g_ref) < 5e-5
    # every leaf is reached: none of the kda leaves has a zero gradient
    kda_layer = g_sys["blocks"][0][1]
    for name, leaf in kda_layer.items():
        assert float(jnp.abs(leaf).max()) > 0.0, name
    # the routers' readings over all the layers, kda's reduced over its own
    assert readings["expert_load"].shape == (4 * periods, 8)
    assert readings["kda_log_decay_min"].shape == ()
    assert float(readings["kda_log_decay_min"]) < 0.0
    assert 0.0 < float(readings["kda_beta_mean"]) < 2.0


def test_readings_of_unlike_layers_are_joined_by_name():
    """A period whose layers make different readings: each reading is
    stacked over the layers that make it, in the stack's order."""
    a = {"aux": jnp.array([1.0, 5.0]), "kda": jnp.array([10.0, 50.0])}
    b = {"aux": jnp.array([2.0, 6.0])}
    c = {"aux": jnp.array([3.0, 7.0]), "kda": jnp.array([30.0, 70.0])}
    joined = model._layer_axis([a, b, c], stack=True)  # two periods of three
    np.testing.assert_array_equal(joined["aux"], [1, 2, 3, 5, 6, 7])
    np.testing.assert_array_equal(joined["kda"], [10, 30, 50, 70])
    after = model._layer_axis([joined, {"aux": jnp.array([9.0])}], stack=False)
    np.testing.assert_array_equal(after["aux"], [1, 2, 3, 5, 6, 7, 9])
    np.testing.assert_array_equal(after["kda"], [10, 30, 50, 70])
    assert model._layer_axis([b], stack=True) == b


def test_the_gate_is_as_wide_as_the_context():
    record = model._OPERATORS["full_attention"]
    leaves = record.init(jax.random.PRNGKey(0), CFG, 1)
    assert leaves["w_gate_attn"].shape == (1, 32, 4 * 8)  # the held heads'
    per_head = dataclasses.replace(CFG, attn_gate=True)
    assert record.init(jax.random.PRNGKey(0), per_head, 1)[
        "w_gate_attn"].shape == (1, 32, 4)
    assert record.params(CFG) - record.params(per_head) == 32 * 4 * 7
    with pytest.raises(ValueError, match="attn_gate"):
        record.init(jax.random.PRNGKey(0),
                    dataclasses.replace(CFG, attn_gate="columnwise"), 1)


def test_remat_with_names_kept_is_the_same_step():
    params = model.transformer_init(jax.random.PRNGKey(3), CFG)
    batch = batch_of(4)
    plain = jax.jit(jax.value_and_grad(
        lambda p: model.transformer_loss(p, batch, CFG)))(params)
    remat = dataclasses.replace(CFG, remat=True)
    names = ("attn_res", "kda_res", "kda_qkv", "shared_up")
    again = jax.jit(jax.value_and_grad(lambda p: model.transformer_loss(
        p, batch, remat, saved_names=names)))(params)
    assert abs(float(again[0]) - float(plain[0])) < 1e-6
    assert distance(again[1], plain[1]) < 1e-5


def test_the_rule_prices_the_new_kind():
    """`saved_activations` over a stack with kda: names in `_SAVE_ORDER`'s
    order, more room only ever adds names, and kda's names are there."""
    cfg = dataclasses.replace(CFG, remat=True, dtype=jnp.bfloat16)
    tokens, whole = 64, model._whole_param_bytes(cfg)
    terms = model._terms(cfg, tokens, whole)
    every = terms.saved_bytes()
    assert list(every) == ["attn_ctx", "attn_res", "attn_qkv", "kda_res",
                           "kda_qkv", "shared_gate", "shared_up"]
    assert every["kda_res"] == 3 * tokens * 32 * 2
    assert every["kda_qkv"] == 3 * tokens * 3 * 4 * 8 * 2  # the held heads'
    fullest = terms.fullest()
    assert fullest.bytes > 0 and fullest.name.startswith(("layer", "head"))
    kept = []
    for limit in (1 << 30, (1 << 30) + (4 << 20), 3 << 30):
        names = list(model.saved_activations(
            cfg, tokens, 3 * whole, whole, limit))
        assert names == list(every)[:len(names)] and len(names) >= len(kept)
        kept = names
    assert kept == list(every)
    record = model._OPERATORS["kda"]
    assert record.holds(cfg) > record.holds(
        dataclasses.replace(cfg, heads_held=(0, 2)))


def test_the_step_trains_and_decays_matrices_only():
    no_decay = ["A_log", "dt_bias", "kda_conv", "g_bias", "norm"]
    optimizer = optax.adamw(
        3e-3, b1=0.9, b2=0.95, weight_decay=0.1,
        mask=lambda params: decayed(params, no_decay))
    init_state, step, _ = make_train_step(CFG, one_device(), optimizer)
    state = init_state(jax.random.PRNGKey(0))
    mask = decayed(state["params"], no_decay)
    kda_layer = mask["blocks"][0][1]
    assert {name for name, on in kda_layer.items() if not on} == {
        "kda_norm", "kda_conv", "kda_g_bias", "kda_A_log", "kda_dt_bias",
        "kda_out_norm", "mlp_norm"}
    assert all(state["params"]["blocks"][0][1][name].ndim >= 3
               for name, on in kda_layer.items() if on)  # [layer, in, out]
    assert mask["embed"] and mask["unembed"] and not mask["final_norm"]
    batch = batch_of(6, rows=1, T=32)
    losses = []
    for _ in range(6):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert out["held_slots"].shape == (4,) and int(out["dropped_slots"].sum()) == 0
    assert float(out["kda_log_decay_min"]) < 0.0


def test_kda_counts_the_chunked_forms_products():
    record = model._OPERATORS["kda"]
    whole = dataclasses.replace(CFG, heads_held=None)
    matmul, attention = record.flops(whole, 64)
    H, d, C = 8, 8, 16
    assert attention == 0
    assert matmul == 2 * record.params(whole) + H * (10 * C * d + 6 * d * d)
    assert record.params(whole) == (
        4 * 32 * H * d + 2 * (32 * 4 + 4 * H * d) + 32 * H)
    # the recurrence itself would take 6 d d a head and token
    assert H * (10 * C * d + 6 * d * d) > H * 6 * d * d
