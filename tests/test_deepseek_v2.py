"""Latent attention, shared experts and the per-sequence balance loss, on the
CPU at a tiny size: YaRN's frequencies and the scores' scale against values
worked by hand for DeepSeek-V2-Lite's configuration, the flash kernels at two
widths against plain attention, the program against the benchmark's plain
reference, the shares of a routed layer against the uncut layer, and what the
new leaves and names mean to `param_shardings` and `saved_activations`
(`ray_tpu/models/transformer.py`, `ray_tpu/ops/flash_attention.py`,
`ray_tpu/ops/moe.py`)."""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v2 as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    param_shardings, saved_activations, segments, transformer_init,
    transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.ops.flash_attention import flash_attention, flash_tiles, mha
from ray_tpu.parallel import make_mesh
from tiny_models import (
    as_reference_config, batch_of, distance, first_layer, init, key,
    one_device, program, value_and_grad)

# config.json's own `rope_scaling`
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
DSV2 = dict(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, d_ff=32, d_ff_dense=96,
    max_seq_len=64, rope_theta=10000.0, norm_eps=1e-6,
    layer_types=("latent_attention",) * 3, n_dense_layers=1,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    # the ramp lies inside the tiny rotary width at an original context of 16
    rope_scaling=tuple(sorted(
        {**YARN, "original_max_position_embeddings": 16}.items())),
    n_experts=8, experts_per_token=3, experts_held=(2, 2), n_shared_experts=2,
    seq_aux=True, router_aux_loss_coef=0.001, router_z_loss_coef=0.0,
    tied_embeddings=False, dtype=jnp.float32,
)


def tiny(**over):
    return TransformerConfig(**{**DSV2, **over})


# -------------------------------------------------------------------- YaRN

def test_yarn_by_hand_for_the_published_configuration():
    """64 rotary columns, theta 10000, factor 40 over 4096:
    dim(r) = 64 ln(4096 / (2 pi r)) / (2 ln 10000) is 10.47 at 32 turns and
    22.51 at one, so the ramp runs from pair 10 to pair 23."""
    assert model.yarn_ramp_bounds(64, 10000.0, YARN) == (10, 23)
    inv_freq, mscale = model.rope_frequencies(64, 10000.0, YARN)
    plain, one = model.rope_frequencies(64, 10000.0)
    assert mscale == 1.0 and one == 1.0  # m(0.707) / m(0.707)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(32) / 32), rtol=1e-6)
    # kept below the ramp, divided by 40 above it, blended between
    np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(
        inv_freq[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    # sigma = 192^-0.5 (0.1 x 0.707 x ln 40 + 1)^2 = 0.0721688 x 1.589626
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m * m == pytest.approx(1.589626, rel=1e-6)
    assert model.yarn_softmax_scale(192, YARN) == pytest.approx(0.114721, rel=5e-6)
    assert model.yarn_softmax_scale(128) == 128 ** -0.5
    # the reference works them out on its own
    theirs, their_mscale, sigma = reference.yarn(
        {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
         "rope_scaling": YARN})
    np.testing.assert_allclose(theirs, inv_freq, rtol=1e-6)
    assert their_mscale == 1.0 and sigma == pytest.approx(0.114721, rel=5e-6)


def test_rope_with_plain_frequencies_is_what_it_was():
    x = jax.random.normal(key(0), (2, 8, 3, 16))
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    half = 8
    freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None] * freqs
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    want = jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                            x[..., half:] * cos + x[..., :half] * sin], -1)
    np.testing.assert_array_equal(model._rope(x, positions, 10000.0), want)


# ------------------------------------------------------- kernels, two widths

@pytest.mark.parametrize("shape", [
    dict(T=200, D=192, Dv=128, block=128),  # T no multiple of the tile
    dict(T=256, D=192, Dv=128, block=128),
    dict(T=72, D=24, Dv=16, block=None),    # the tests' tiny heads
])
def test_two_width_kernels_agree_with_plain_attention(shape):
    T, D, Dv, block = shape["T"], shape["D"], shape["Dv"], shape["block"]
    q = jax.random.normal(key(0), (1, T, 2, D))
    k = jax.random.normal(key(1), (1, T, 2, D))
    v = jax.random.normal(key(2), (1, T, 2, Dv))
    w = jax.random.normal(key(3), (1, T, 2, Dv))
    scale = 0.114721

    def kernels(q, k, v):
        out = flash_attention(q, k, v, causal=True, scale=scale, block_q=block,
                              block_k=block, interpret=True)
        return (out * w).sum(), out

    def plain(q, k, v):
        out = mha(q, k, v, causal=True, scale=scale, impl="xla")
        return (out * w).sum(), out

    (_, ours), grads = jax.value_and_grad(kernels, (0, 1, 2), has_aux=True)(q, k, v)
    (_, theirs), want = jax.value_and_grad(plain, (0, 1, 2), has_aux=True)(q, k, v)
    assert ours.shape == (1, T, 2, Dv)
    np.testing.assert_allclose(ours, theirs, rtol=2e-5, atol=2e-5)
    for got, ref, width in zip(grads, want, (D, D, Dv)):  # dq, dk, dv
        assert got.shape[-1] == width
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_tiles_take_both_widths():
    """q and k at 192 fill two tiles of lanes in VMEM and cost the MXU two
    passes; at equal widths the plan is the one-width kernel's."""
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        one = flash_tiles(kernel, 8192, 8192, 128, jnp.bfloat16)
        assert flash_tiles(kernel, 8192, 8192, 128, jnp.bfloat16,
                           v_dim=128) == one
        two = flash_tiles(kernel, 8192, 8192, 192, jnp.bfloat16, v_dim=128)
        wide = flash_tiles(kernel, 8192, 8192, 256, jnp.bfloat16)
        same_tile = flash_tiles(kernel, 8192, 8192, 128, jnp.bfloat16,
                                block_q=two.block_q, block_k=two.block_k)
        also_wide = flash_tiles(kernel, 8192, 8192, 256, jnp.bfloat16,
                                block_q=two.block_q, block_k=two.block_k)
        assert same_tile.vmem_bytes < two.vmem_bytes < also_wide.vmem_bytes
        assert two.vmem_limit_bytes <= 96 << 20 and wide.block_q >= 128
    from ray_tpu.ops.flash_attention import _pairs_factor
    assert _pairs_factor("flash_fwd", 128, 128) == 1.0
    assert _pairs_factor("flash_bwd_dkv", 64, 64) == 1.0
    assert _pairs_factor("flash_fwd", 192, 128) == 1.5          # (2 + 1) / 2
    assert _pairs_factor("flash_bwd_dq", 192, 128) == 5 / 3     # (4 + 1) / 3
    assert _pairs_factor("flash_bwd_dkv", 192, 128) == 1.5      # (4 + 2) / 4


# sha256 of `str(jax.make_jaxpr(...))` of the three kernels under
# `value_and_grad`. At heads of 64 the digest was taken on PR 33's commit
# (1919b5e) with this function: at equal widths the forward and the
# two-kernel backward are that commit's programs, and since PR 65 those of
# every shape that folds all its arrays' heads into the batch. Since PR 35
# the backward these shapes take is the one kernel, so the test steers
# `flash_bwd_kernels` to the two it stands for. At heads of 128 the kernels
# read v and write dv where the model holds them since PR 65 (no transpose
# of either, a head a column block of their index maps; with one head the
# parent's maps): those two were taken on PR 65's tree, whose results
# `tests/test_flash_layout.py` holds bit for bit to the folded call's.
PARENT_JAXPRS = {
    (320, 2, 128): "756b0ba51dbe50cb7a691de86847cc72e8d9e4b800410e35b09f401782e01819",
    (4096, 1, 128): "ed0e2ac1648fada51ee260bfa59ca4da53c36d16facfc478279ad8f9529bd175",
    (1024, 2, 64): "70d0fd1c68a4a3944890fcb64e7f052ff9f09629391b4c099a7e3a72b0452cca",
}


@pytest.mark.parametrize("shape", sorted(PARENT_JAXPRS))
def test_at_equal_widths_the_kernels_jaxprs_are_the_parent_s(shape, monkeypatch):
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    T, H, D = shape
    assert fa.flash_bwd_kernels(T, T, D, jnp.bfloat16) == ("flash_bwd_dkv_dq",)
    monkeypatch.setattr(fa, "flash_bwd_kernels",
                        lambda *a, **kw: ("flash_bwd_dq", "flash_bwd_dkv"))
    q = jax.ShapeDtypeStruct((1, T, H, D), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, keep_ctx=True).astype(
            jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(q, q, q))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_JAXPRS[shape]


# ------------------------------------------------- the program, the reference

def test_parameter_tree_of_the_cut():
    cfg = tiny()
    assert [(s.periods, [k.op for k in s.layout]) for s in segments(cfg)] == [
        (1, ["latent_attention"]), (2, ["latent_attention"])]
    dense, routed = transformer_init(key(0), cfg)["blocks"]
    assert dense[0]["wq"].shape == (1, 64, 4 * 24)
    assert dense[0]["wkv_a"].shape == (1, 64, 32 + 8)
    assert dense[0]["kv_norm"].shape == (1, 32)
    assert dense[0]["wkv_b"].shape == (1, 32, 4 * 32)
    assert dense[0]["wo"].shape == (1, 64, 64)
    assert dense[0]["w_gate"].shape == (1, 64, 96) and "ws_gate" not in dense[0]
    assert "wk" not in dense[0] and "q_norm" not in dense[0]
    blk = routed[0]
    assert blk["router"].shape == (2, 64, 8)      # the router keeps its width
    assert blk["w_gate"].shape == (2, 2, 64, 32)  # the held experts
    assert blk["ws_gate"].shape == blk["ws_up"].shape == (2, 64, 64)
    assert blk["ws_down"].shape == (2, 64, 64)    # 2 shared as one, whole


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_program_agrees_with_the_plain_reference(dtype):
    cfg = tiny(dtype=jnp.dtype(dtype), remat=True)
    params = init(key(7), cfg)
    batch = batch_of(cfg, rows=3)
    config = as_reference_config(cfg)
    (loss, readings), grads = program(cfg, params, batch)
    index = readings["expert_index"]
    own_loss, chosen, _ = jax.jit(
        lambda p: reference.forward(p, batch, config))(params)
    (ref_loss, (_, balance)), ref_grads = value_and_grad(
        lambda p: (lambda l, c, b: (l, (c, b)))(
            *reference.forward(p, batch, config, index)), params, has_aux=True)
    picked = jax.nn.one_hot(index, cfg.n_experts).sum(-2) > 0
    flips = float(jnp.logical_and(picked, ~chosen).sum()) / index.size
    if dtype == "float32":  # the same mathematics to rounding
        assert flips == 0.0 and float(own_loss) == pytest.approx(float(ref_loss))
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
        assert distance(grads, ref_grads) < 1e-5
        assert float(readings["aux_loss"]) == pytest.approx(float(balance), rel=1e-6)
    else:
        assert flips < 0.05
        assert float(loss) == pytest.approx(float(ref_loss), rel=2e-3)
        assert distance(grads, ref_grads) < 0.08
    # the loss is the cross-entropy plus alpha times the layers' sum
    assert readings["expert_load"].shape == (2, 8)
    assert int(readings["expert_load"].sum()) == 2 * 3 * 32 * 3
    assert int(readings["dropped_slots"].sum()) == 0


def test_the_balance_loss_is_per_sequence_and_summed_over_the_layers():
    cfg = tiny()
    params = transformer_init(key(3), cfg)
    batch = batch_of(cfg, rows=4)
    loss, readings = transformer_loss_and_readings(params, batch, cfg)
    free, _ = transformer_loss_and_readings(
        params, batch, dataclasses.replace(cfg, router_aux_loss_coef=0.0))
    assert float(loss - free) == pytest.approx(
        0.001 * float(readings["aux_loss"]), rel=1e-3)
    _, batch_readings = transformer_loss_and_readings(
        params, batch, dataclasses.replace(cfg, seq_aux=False))
    # the batch's loss is the mean over the 2 layers, and another number
    assert float(batch_readings["aux_loss"]) * 2 != pytest.approx(
        float(readings["aux_loss"]), rel=1e-4)
    # by hand on one layer's scores and choice
    probs = jax.nn.softmax(jax.random.normal(key(9), (2, 6, 4)), -1)
    index = jnp.asarray([[0, 1], [0, 2], [0, 1], [3, 1], [0, 1], [2, 1],
                         [3, 2], [3, 2], [3, 2], [3, 2], [3, 2], [1, 0]])
    load = moe.sequence_load(index, 4, 2)
    np.testing.assert_array_equal(load, [[4, 5, 2, 1], [1, 1, 5, 5]])
    want = np.mean([
        sum(load[b, e] * 4 / 12 * float(probs[b, :, e].mean()) for e in range(4))
        for b in range(2)])
    assert float(moe.sequence_balancing_loss(probs, load)) == pytest.approx(
        want, rel=1e-6)
    uniform = jnp.full((2, 6, 4), 0.25)
    assert float(moe.sequence_balancing_loss(
        uniform, jnp.full((2, 4), 3))) == pytest.approx(1.0)
    np.testing.assert_array_equal(load.sum(0), moe.expert_load(index, 4))


def test_a_share_balanced_by_a_loss_alone_has_more_slack():
    """`dsv2lite.tokens8k`: 196,608 slots, 8 of 64 experts held. A router
    without a selection bias gets buffers of 1.375 even shares, one with
    (`lfm2moe.tokens8k`) keeps its 1.25."""
    assert moe.held_chunk(196608, 8, 64, load_held_even=False) == 33792
    assert moe.held_chunk(196608, 8, 64) == 30720
    assert moe.held_chunk(131072, 8, 64, load_held_even=True) == 20480
    assert not tiny().expert_bias


def test_the_shares_add_up_to_the_uncut_layer(monkeypatch):
    """8 experts held 2 a share: attention and the shared experts are what
    every share computes alike, and counted once; the four shares' routed
    parts beside them are the uncut reference's layer."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    cfg = tiny(n_layers=1, layer_types=("latent_attention",), n_dense_layers=0,
               experts_held=None)
    w = first_layer(cfg)
    x = jax.random.normal(key(5), (2, 48, 64))
    positions = jnp.broadcast_to(jnp.arange(48), (2, 48))
    config = as_reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        after_attention = reference.attention(x, w, config)
        whole, _, _ = reference.routed_feed_forward(after_attention, w, config)
        none_held = {**w, **{k: w[k][:0] for k in ("w_gate", "w_up", "w_down")}}
        alike = reference.routed_feed_forward(
            after_attention, none_held, {**config, "experts_held": (0, 0)})[0]
        parts = []
        for first in range(0, 8, 2):
            share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
            held = {**w, **{k: w[k][first:first + 2]
                            for k in ("w_gate", "w_up", "w_down")}}
            out, readings = model._block(
                x, held, positions, None, share_cfg, cfg.layers[0], None, 1)
            assert int(readings["dropped_slots"]) == 0
            assert readings["expert_load"].shape == (8,)
            theirs, _, _ = reference.routed_feed_forward(
                after_attention, held, {**config, "experts_held": (first, 2)})
            np.testing.assert_allclose(out, theirs, rtol=2e-4, atol=2e-5)
            parts.append(out - alike)  # this share's routed part alone
    np.testing.assert_allclose(alike + sum(parts), whole, rtol=2e-4, atol=5e-5)
    # the shared experts are no small part of it
    assert float(jnp.abs(alike - after_attention).mean()) > 0.05


# ------------------------------------------------------- shardings, remat

def test_param_shardings_of_the_new_leaves():
    cfg = tiny()
    mesh = make_mesh({"fsdp": 4, "tensor": 2}, devices=jax.devices()[:8])
    shard = param_shardings(mesh, cfg)
    params = jax.eval_shape(lambda: transformer_init(key(0), cfg))
    assert jax.tree.structure(shard) == jax.tree.structure(params)
    routed = shard["blocks"][1][0]
    assert routed["wq"].spec == (None, "fsdp", "tensor")      # by the heads
    assert routed["wkv_b"].spec == (None, None, "tensor")     # by the heads
    assert routed["wo"].spec == (None, "tensor", "fsdp")      # by the heads
    assert routed["wkv_a"].spec == (None, "fsdp", None)       # no head to cut
    assert routed["kv_norm"].spec == (None, None)
    assert routed["ws_gate"].spec == routed["ws_up"].spec == (
        None, "fsdp", "tensor")
    assert routed["ws_down"].spec == (None, "tensor", "fsdp")
    assert shard["blocks"][0][0]["wkv_a"].spec == (None, "fsdp", None)
    for leaf, sharding in zip(jax.tree.leaves(params), jax.tree.leaves(shard)):
        sharding.shard_shape(leaf.shape)  # every cut divides its dimension


def test_saved_activations_know_the_new_layer(monkeypatch):
    # a program's own 160 MiB would be all there is to a model of this size
    monkeypatch.setattr(model, "_PROGRAM_BYTES", 0)
    monkeypatch.setattr(model, "_LAYER_CODE_BYTES", 0)
    model._terms.cache_clear()
    cfg = tiny(dtype=jnp.bfloat16, remat=True)
    tokens = 4 * 64
    state = 12 * sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: transformer_init(key(0), cfg))))
    terms = model._terms(cfg, tokens, state // 3)
    sizes = terms.saved_bytes()
    assert list(sizes) == ["attn_ctx", "attn_res", "attn_qkv", "shared_gate",
                           "shared_up", "mlp_gate", "mlp_up"]
    # o at a tile's 128 lanes a head and lse as one f32 column, three layers
    assert sizes["attn_ctx"] == 3 * tokens * (4 * 128 + 4 * 2) * 2
    assert sizes["attn_res"] == 3 * tokens * 64 * 2
    # out of wq (4 x 24), wkv_a (32 + 8) and wkv_b (4 x 32)
    assert sizes["attn_qkv"] == 3 * tokens * (96 + 40 + 128) * 2
    assert sizes["shared_gate"] == sizes["shared_up"] == 2 * tokens * 64 * 2
    assert sizes["mlp_gate"] == tokens * 96 * 2  # the one dense layer
    widths, params = model._layer_widths(cfg, cfg.layers[1])
    assert params == (64 * 96 + 64 * 40 + 32 * 128 + 64 * 64
                      + 64 * 8 + 2 * 3 * 64 * 32 + 3 * 64 * 64)
    args = (cfg, tokens, state, state // 3)
    assert saved_activations(*args, None) == {}  # no limit to read
    assert saved_activations(*args, 1 << 20) == {}  # no room
    # all the room: every name, at the stack's one pass
    assert saved_activations(*args, 1 << 40) == dict.fromkeys(sizes, 1)
    two = {"attn_ctx": 1, "attn_res": 1}
    chosen = saved_activations(
        *args, state + model._SAVE_RESERVE + terms.fullest(two).bytes)
    assert chosen == two
    model._terms.cache_clear()


def test_a_step_trains_and_reports_its_readings():
    cfg = tiny(remat=True)
    init_state, step, _ = make_train_step(cfg, one_device())
    state = init_state(key(0))
    batch = batch_of(cfg, rows=2)
    losses = []
    for _ in range(3):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert losses[2] < losses[0]
    assert out["expert_load"].shape == (2, 8) and out["held_slots"].shape == (2,)
    assert float(out["aux_loss"]) > 1.0  # two layers' worth, about 1 each
    assert float(out["z_loss"]) >= 0.0


def test_flops_count_latent_attention_and_the_shared_experts():
    cfg = tiny()
    matmul, attn, head = model._fwd_flops_per_token(cfg, 64)
    attention = 2 * (64 * 96 + 64 * 40 + 32 * 128 + 64 * 64)
    routed = 2 * 64 * 8 + 3 * 2 / 8 * 6 * 64 * 32 + 6 * 64 * 64
    assert matmul == 3 * attention + 6 * 64 * 96 + 2 * routed
    assert attn == 3 * 2 * 4 * (24 + 16) * 65 / 2
    assert head == 2 * 64 * 128
