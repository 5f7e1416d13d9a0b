"""Mellum2's stack through `models/transformer.py` against its plain
reference (`chipbench/reference/mellum.py`) on seeded weights at a tiny size,
float32 on one CPU device, where the stated path agrees to rounding; and each
departure a configuration could make from the published model (the chosen
weights not normalised, the window's edge a key off, the rotary recipe of the
other kind of layer, YaRN's `attention_factor` or its frequencies dropped)
falling outside that. The routed layer over the mesh is
`tests/test_expert_mesh.py`'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mellum as reference
from ray_tpu.models import TransformerConfig
from ray_tpu.models.transformer import (
    rope_frequencies, segments, transformer_init,
    transformer_loss_and_readings)

SCALING = {"rope_type": "yarn", "factor": 16,
           "original_max_position_embeddings": 16, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782}
# the reference reads a configuration file's keys
CONFIG = {
    "d_model": 64, "n_layers": 4, "n_heads": 8, "n_kv_heads": 2, "d_head": 8,
    "d_ff": 32, "n_experts": 16, "experts_per_token": 3, "vocab_size": 256,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 8, "rope_theta": 500000, "rope_theta_sliding": 500000,
    "rope_scaling": SCALING, "norm_eps": 1e-6, "router_aux_loss_coef": 0.001,
}
CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=4, n_heads=8, n_kv_heads=2, d_head=8,
    d_ff=32, max_seq_len=32, n_experts=16, experts_per_token=3,
    norm_topk_prob=True, router_score="softmax", router_aux_loss_coef=0.001,
    router_z_loss_coef=0.0, layer_types=tuple(CONFIG["layer_types"]),
    sliding_window=8, rope_theta=500000, rope_theta_sliding=500000,
    rope_scaling=tuple(sorted(SCALING.items())), tied_embeddings=False,
    dtype=jnp.float32, attention_impl="xla", remat=True)


@pytest.fixture(scope="module")
def seeded():
    params = transformer_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    def loss(p):
        total, chosen, balance = reference.forward(p, batch, CONFIG)
        return total, (chosen, balance)

    (ref_loss, (chosen, balance)), ref_grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params)
    return params, batch, float(ref_loss), chosen, float(balance), ref_grads


def errors(cfg, seeded):
    """(the loss's relative error, the gradients' distance over the
    reference's norm, the share of the slots whose expert the reference did
    not choose, the balance loss's relative error) of the system under
    `cfg`."""
    params, batch, ref_loss, chosen, balance, ref_grads = seeded
    (loss, readings), grads = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss_and_readings(p, batch, cfg),
        has_aux=True))(params)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(grads), jax.tree.leaves(ref_grads)))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree.leaves(ref_grads))
    chose = jax.nn.one_hot(readings["expert_index"], cfg.n_experts).sum(-2) > 0
    flips = float(jnp.logical_and(chose, ~chosen).mean())
    return (abs(float(loss) - ref_loss) / ref_loss, (num / den) ** 0.5, flips,
            abs(float(readings["aux_loss"]) - balance) / balance)


def test_the_stack_is_one_period_of_three_sliding_layers_and_a_full_one():
    (segment,) = segments(CFG)
    assert segment.periods == 1
    assert [kind.op for kind in segment.layout] == CONFIG["layer_types"]
    assert all(kind.routed for kind in segment.layout)
    assert CFG.heads("sliding_attention") == CFG.heads("full_attention") == 8


def test_the_system_is_the_reference_on_seeded_weights(seeded):
    loss_err, grad_err, flips, balance_err = errors(CFG, seeded)
    assert loss_err < 1e-6
    assert grad_err < 2e-5
    assert flips == 0.0
    assert balance_err < 1e-5


BROKEN = {
    "weights_not_normalised": dict(norm_topk_prob=False),
    "window_one_key_too_wide": dict(sliding_window=9),
    "window_one_key_too_narrow": dict(sliding_window=7),
    "no_attention_factor": dict(rope_scaling=tuple(sorted(
        {**SCALING, "attention_factor": 1.0}.items()))),
    "plain_frequencies_on_the_full_layer": dict(rope_scaling=None),
    "another_theta_under_the_window": dict(rope_theta_sliding=10000.0),
    "a_sigmoid_router": dict(router_score="sigmoid"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_departure_from_the_published_model_is_told_apart(seeded, fault):
    loss_err, grad_err, _, _ = errors(
        dataclasses.replace(CFG, **BROKEN[fault]), seeded)
    # the stated path reads 2e-5 at most; a departure a thousand times that
    assert grad_err > 2e-2, (fault, loss_err, grad_err)


def test_rotary_is_by_layer_type():
    """Plain frequencies under the window; on a full layer YaRN's blend,
    with cos and sin scaled by the explicit `attention_factor`: the
    program's tables are the reference's."""
    for kind in ("sliding_attention", "full_attention"):
        inv_freq, mscale, turned = reference.rotary_tables(CONFIG, kind)
        scaling = None if kind == "sliding_attention" else SCALING
        freqs, factor = rope_frequencies(8, 500000, scaling)
        np.testing.assert_allclose(freqs, inv_freq, rtol=1e-6)
        assert factor == pytest.approx(mscale)
        assert turned == 8
    plain, _ = rope_frequencies(8, 500000, None)
    blended, factor = rope_frequencies(8, 500000, SCALING)
    assert factor == pytest.approx(0.1 * np.log(16) + 1)
    assert not np.allclose(plain, blended)
