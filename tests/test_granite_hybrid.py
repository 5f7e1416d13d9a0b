"""A dense hybrid of Mamba-2 mixers and attention without positions under
the Granite family's four multipliers, on the CPU at a tiny size: the
system's loss and gradients against the benchmark's plain reference, what
each multiplier does and that it adds nothing at 1, the tied head over a
slice of the vocabulary, attention that sees no order, the scan at chunks
of 128 and 256, which records take the multipliers, and what a mixer leaves
behind for the keep rule (`ray_tpu/models/transformer.py`,
`ray_tpu/ops/ssd.py`, `chipbench/reference/granite_hybrid.py`)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import granite_hybrid as reference
from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    transformer_apply, transformer_hidden, transformer_loss_and_readings)
from ray_tpu.ops.ssd import ssd
from ray_tpu.util import tracing
import tiny_models
from tiny_models import (
    distance, equations as _equations, init, key, program, value_and_grad)

KINDS = {"mamba2": "mamba", "full_attention": "attention"}
PUBLISHED = dict(embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=1 / 16, logits_scaling=8.0)


def tiny(layers=("mamba2", "mamba2", "full_attention", "mamba2"), **over):
    """32 wide, 4 query heads of 16 over 2 key heads, SwiGLU of 48, mixers
    of 4 heads of 16 in ONE group of B and C, the scores at 1 / head width
    as the published 1/64 is, the tied head."""
    return TransformerConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=len(layers), n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=48, max_seq_len=64, norm_eps=1e-5,
        tied_embeddings=True, dtype=jnp.float32, rope=False,
        layer_types=tuple(layers), mamba_heads=4, mamba_head_dim=16,
        ssm_state=16, ssm_groups=1, ssd_chunk=16, **PUBLISHED), **over})


def as_reference(cfg):
    return {**dataclasses.asdict(cfg),
            "layer_types": [KINDS[k] for k in cfg.layer_types]}


def batch_of(cfg, seq=40, **kw):
    return tiny_models.batch_of(cfg, seq=seq, **kw)


def test_loss_and_gradients_are_the_reference_s():
    cfg = tiny()
    params, batch = init(key(0), cfg), batch_of(cfg)
    (loss, readings), grads = program(cfg, params, batch)
    want, theirs = value_and_grad(
        lambda p: reference.loss(p, batch, as_reference(cfg)), params)
    assert readings == {}
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert distance(grads, theirs) < 2e-5
    # under remat, with half the names kept: the same numbers
    remat = dataclasses.replace(cfg, remat=True)
    (again, _), same = program(remat, params, batch, saved_names=(
        "attn_ctx", "attn_res", "mamba_in", "mlp_gate"))
    assert float(again) == pytest.approx(float(loss), rel=1e-6)
    assert distance(same, grads) < 1e-5


@pytest.mark.parametrize("field,moved,more", [
    ("embedding_multiplier", 6.0, 1),   # a product on the gathered rows
    ("residual_multiplier", 0.5, 8),    # one a sublayer: four layers of two
    ("attention_multiplier", 0.25, 0),  # the kernel's scale, in place of its own
    ("logits_scaling", 4.0, 1),         # a division of the head, once
])
def test_a_multiplier_moves_the_loss_and_adds_nothing_at_one(field, moved, more):
    """Moved from its published value a multiplier changes the loss; at 1
    (the scores' scale: None) the traced program has no instruction for it:
    as many equations fewer as the multiplier has sites."""
    cfg = tiny()
    params, batch = init(key(0), cfg), batch_of(cfg)

    def loss_of(cfg):
        return lambda p: transformer_loss_and_readings(p, batch, cfg)[0]

    published = float(jax.jit(loss_of(cfg))(params))
    other = dataclasses.replace(cfg, **{field: moved})
    assert abs(float(jax.jit(loss_of(other))(params)) - published) > 5e-5
    off = dataclasses.replace(
        cfg, **{field: None if field == "attention_multiplier" else 1.0})

    def count(cfg):
        return sum(1 for _ in _equations(
            jax.make_jaxpr(loss_of(cfg))(params).jaxpr))

    assert count(cfg) - count(off) == more
    plain = TransformerConfig()
    assert (plain.embedding_multiplier, plain.residual_multiplier,
            plain.attention_multiplier, plain.logits_scaling) == (
                1.0, 1.0, None, 1.0)


def test_the_head_is_the_embedding_s_slice_over_the_scaling():
    cfg = tiny()
    params, batch = init(key(0), cfg), batch_of(cfg)
    assert "unembed" not in params and params["embed"].shape == (128, 32)
    with jax.default_matmul_precision("highest"):
        hidden = transformer_hidden(params, batch["tokens"], cfg)
        logits = transformer_apply(params, batch["tokens"], cfg)
        loss = transformer_loss_and_readings(params, batch, cfg)[0]
    assert logits.shape == (2, 40, 128)  # over the slice's ids alone
    np.testing.assert_allclose(
        logits, hidden @ params["embed"].T / 8, rtol=1e-5, atol=1e-6)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.take_along_axis(logp, batch["targets"][..., None], -1).mean()
    assert float(loss) == pytest.approx(float(want), rel=1e-5)


def test_attention_sees_no_order_and_the_mixers_do():
    """`position_embedding_type` "nope": after an attention layer alone
    the last position's stream does not change when the earlier tokens
    change places (a second one would see the order through the first's
    causal outputs); with a mixer ahead it does."""
    ids = jax.random.randint(key(2), (1, 24), 0, 128)
    shuffled = jnp.concatenate(
        [jax.random.permutation(key(3), ids[:, :-1], axis=1), ids[:, -1:]], 1)
    assert not bool((shuffled == ids).all())
    for layers, same in ((("full_attention",), True),
                         (("mamba2", "full_attention"), False)):
        cfg = tiny(layers)
        params = init(key(0), cfg)
        with jax.default_matmul_precision("highest"):
            a = transformer_hidden(params, ids, cfg)[0, -1]
            b = transformer_hidden(params, shuffled, cfg)[0, -1]
        assert bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-5)) is same


def test_chunks_of_128_and_256_agree_to_rounding():
    """`mamba_chunk_size` 256 is the published value and the cell's; the
    result does not depend on it beyond rounding: one group of B and C for
    eight heads, 512 tokens, forward and every gradient."""
    ks = jax.random.split(key(5), 6)
    args = (jax.random.normal(ks[0], (1, 512, 8, 16)),
            jax.nn.softplus(jax.random.normal(ks[1], (1, 512, 8)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (8,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (1, 512, 1, 16)),
            0.1 * jax.random.normal(ks[4], (1, 512, 1, 16)),
            jax.random.normal(ks[5], (8,)))
    with jax.default_matmul_precision("highest"):
        y128, g128 = tiny_models.y_and_grads(
            lambda *a: ssd(*a, chunk=128), args)
        y256, g256 = tiny_models.y_and_grads(
            lambda *a: ssd(*a, chunk=256), args)
    np.testing.assert_allclose(y128, y256, rtol=2e-4, atol=2e-4)
    for name, a, b in zip("x dt A B C D".split(), g128, g256):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-5,
                                   err_msg=name)


@pytest.mark.parametrize("layers,over", [
    (("sparse_attention",), dict(index_heads=2, index_head_dim=8,
                                 index_topk=4)),
    (("conv",), {}),
    (("kda",), dict(kda_heads=2, kda_head_dim=16)),
])
def test_the_records_the_multipliers_are_not_written_for_refuse_them(
        layers, over):
    """`residual_multiplier` holds for plain attention, the Mamba-2 mixer
    and the dense feed-forward; `cfg.layers` refuses it, and the scores'
    multiplier, for every other record with a sentence."""
    for field, value in (("residual_multiplier", 0.22),
                         ("attention_multiplier", 0.0625)):
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=48,
            layer_types=layers, **over, **{field: value})
        with pytest.raises(ValueError, match="multipliers are written for"):
            cfg.layers
    with pytest.raises(ValueError, match="attention_impl 'ring'"):
        tiny(attention_impl="ring").layers
    assert len(tiny().layers) == 4


def test_what_a_mixer_leaves_behind_is_the_rule_s():
    """A walked layer's feed-forward's moment holds what the mixer before
    it left for its own backward (`Sublayer.residuals`): at the cell's
    widths 20,992 values a token, 1.376 GB at 32,768 tokens; a stack of
    `sublayer_types`, whose mixer has no sublayer behind it, counts none."""
    cfg = tiny(d_model=2048, mamba_heads=64, mamba_head_dim=64, ssm_state=128,
               ssd_chunk=256, dtype=jnp.bfloat16)
    mixer, attention = model._OPERATORS["mamba2"], model._OPERATORS[
        "full_attention"]
    assert mixer.residuals(cfg) == 2 * 4352 + 2 * 4096 + 4096 == 20992
    assert attention.residuals(cfg) == 0
    assert 32768 * 2 * mixer.residuals(cfg) == 1375731712


def test_the_step_s_record_says_which_scan_its_mixers_ran():
    """`train.ssd_calls_kernels` / `train.ssd_calls_numpy`, counted where
    `ssd` takes its path and said at the end of the step's log line: a
    silent fall to the `jax.numpy` scan at a shape the kernels refuse is in
    the record. Three mixers, traced once: the CPU's path here; with the
    kernels in interpret mode at a shape that tiles, theirs."""
    import functools

    def counted(before):
        now = tracing.counters()
        return tuple(now.get(name, 0) - before.get(name, 0) for name in (
            "train.ssd_calls_kernels", "train.ssd_calls_numpy"))

    cfg = tiny()
    params, batch = init(key(0), cfg), batch_of(cfg)
    before = tracing.counters()
    jax.make_jaxpr(lambda p: transformer_loss_and_readings(p, batch, cfg)[0])(
        params)
    assert counted(before) == (0, 3)
    assert model._calls_said(before) == (
        "; Mamba-2's scans: 0 calls by the kernels ssd_fwd and ssd_bwd, 3 by "
        "jax.numpy")
    assert not model._calls_said(tracing.counters())
    # heads of 64 in one group, a state of 128, chunks of 128: it tiles
    wide = tiny(("mamba2",), d_model=64, mamba_heads=2, mamba_head_dim=64,
                ssm_state=128, ssd_chunk=128, max_seq_len=128)
    params, batch = init(key(0), wide), batch_of(wide, seq=128)
    before = tracing.counters()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(model, "ssd", functools.partial(ssd, interpret=True))
        jax.make_jaxpr(
            lambda p: transformer_loss_and_readings(p, batch, wide)[0])(params)
    assert counted(before) == (1, 0)

