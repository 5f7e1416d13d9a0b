"""`heads_held`: a mixer told which of its heads it holds computes the
partial sum over them. At a small size on the CPU, in float32: for attention
and for KDA the shares' mixer outputs add up to the whole mixer's on the same
input; with the experts' and the vocabulary's shares one whole layer and the
head add up to what the uncut plain reference gives, what every chip
computes alike (the shared expert; KDA's `W_f1`, `W_g1` and output norm)
counted once; and what a share may not be is refused with a sentence."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench.reference import solar_open2 as reference
from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import LayerKind, _Site
from ray_tpu.parallel import make_mesh

D, HEADS, KV, WIDTH, EXPERTS, VOCAB, T = 32, 8, 2, 8, 8, 48, 40
WHOLE = TransformerConfig(
    vocab_size=VOCAB, d_model=D, n_layers=1, n_heads=HEADS, n_kv_heads=KV,
    d_head=WIDTH, rope=False, attn_gate="elementwise", kda_heads=HEADS,
    kda_head_dim=WIDTH, kda_gate_rank=4, kda_chunk=16, d_ff=16, d_ff_shared=16,
    n_experts=EXPERTS, experts_per_token=3, norm_topk_prob=True,
    n_shared_experts=1, max_seq_len=64, dtype=jnp.float32,
    tied_embeddings=False, attention_impl="xla")
# the reference reads the same sizes under the configuration file's keys
REF = dict(d_head=WIDTH, kda_head_dim=WIDTH, norm_eps=WHOLE.norm_eps,
           n_experts=EXPERTS, experts_per_token=3, norm_topk_prob=True,
           routed_scaling_factor=1.0, router_aux_loss_coef=0.01)


def whole_block(op, seed=0):
    kind = LayerKind(op, True)
    cfg = dataclasses.replace(WHOLE, layer_types=(op,))
    leaves = model._blocks_init(jax.random.PRNGKey(seed), cfg, kind, 1)
    return cfg, jax.tree.map(lambda x: x[0], leaves)


def share_of(blk, cfg, op, first, n):
    """The leaves of heads `first` to `first + n` of a whole layer's: every
    axis a record calls `heads` (and `kv`) cut to the share's columns or
    rows; the others whole."""
    record = model._OPERATORS[op]
    axes = {name: spec[1:] for name, spec in record.axes(cfg).items()}
    total = record.all_heads(cfg) if op != "kda" else cfg.kda_heads
    group = total // cfg.kv_heads
    out = dict(blk)
    for name, spec in axes.items():
        for axis, label in enumerate(spec):
            if label not in ("heads", "kv"):
                continue
            size = blk[name].shape[axis]
            if label == "heads":
                lo, hi = first * size // total, (first + n) * size // total
            else:
                lo = (first // group) * size // cfg.kv_heads
                hi = ((first + n - 1) // group + 1) * size // cfg.kv_heads
            out[name] = jax.lax.slice_in_dim(out[name], lo, hi, axis=axis)
    return out


def mixer_out(op, x, blk, cfg):
    site = _Site(jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2]))
    return model._OPERATORS[op].forward(x, blk, cfg, site)[0] - x


X = jax.random.normal(jax.random.PRNGKey(7), (2, T, D))


@pytest.mark.parametrize("op,n", [
    ("full_attention", 4), ("full_attention", 1), ("kda", 4), ("kda", 1)])
def test_the_shares_of_a_mixer_add_up_to_the_whole(op, n):
    """8 heads in shares of `n`: whole groups of 4 query heads a key-value
    head, half a group, one head."""
    cfg, blk = whole_block(op)
    whole = mixer_out(op, X, blk, cfg)
    parts = 0.0
    for first in range(0, HEADS, n):
        held = dataclasses.replace(cfg, heads_held=(first, n))
        share = share_of(blk, cfg, op, first, n)
        # a share's leaves are the shapes the program itself makes
        made = model._OPERATORS[op].init(jax.random.PRNGKey(0), held, 1)
        assert {k: v.shape[1:] for k, v in made.items()} == {
            k: share[k].shape for k in made}
        parts = parts + mixer_out(op, X, share, held)
    assert float(jnp.abs(parts - whole).max()) < 2e-5 * float(
        jnp.abs(whole).max())
    assert float(jnp.abs(whole).max()) > 1e-2


@pytest.mark.parametrize("op", ["full_attention", "kda"])
def test_a_share_counts_only_what_it_holds(op):
    cfg, blk = whole_block(op)
    record = model._OPERATORS[op]
    held = dataclasses.replace(cfg, heads_held=(4, 4))
    share = share_of(blk, cfg, op, 4, 4)
    assert record.heads(held) == cfg.heads(op) // 2 == 4
    assert record.params(held) == sum(
        share[name].size for name in record.matmuls if name in share)
    assert record.params(held) < record.params(cfg)
    assert record.holds(held) < record.holds(cfg)
    assert record.flops(held, T)[0] < record.flops(cfg, T)[0]
    assert all(record.widths(held)[name] <= width
               for name, width in record.widths(cfg).items())
    # what is whole on every chip is not cut
    for name in ("kda_f1", "kda_g1", "kda_out_norm", "kda_norm", "attn_norm"):
        if name in blk:
            assert share[name].shape == blk[name].shape


@pytest.mark.parametrize("op", ["full_attention", "kda"])
def test_a_whole_layer_and_the_head_add_up_to_the_uncut_reference(op):
    """Heads 4 ways, experts 4 ways, the vocabulary 3 ways, against the
    plain reference of the uncut layer: the mixers' partial sums, then on
    that stream the experts' partial sums with the shared expert once, then
    the logits' slices side by side."""
    cfg, blk = whole_block(op, seed=3)
    site = _Site(jnp.broadcast_to(jnp.arange(T), (2, T)))
    ref_mix = reference.kda if op == "kda" else reference.attention
    h_ref = ref_mix(X, blk, REF)
    y_ref, _, _ = reference.routed_feed_forward(
        h_ref, blk, dict(REF, experts_held=[0, EXPERTS]))
    mixed = X + sum(
        mixer_out(op, X, share_of(blk, cfg, op, first, 2),
                  dataclasses.replace(cfg, heads_held=(first, 2)))
        for first in range(0, HEADS, 2))
    assert float(jnp.abs(mixed - h_ref).max()) < 2e-5

    normed = reference._rmsnorm(mixed, blk["mlp_norm"], cfg.norm_eps)
    shared = reference._swiglu(normed, blk["ws_gate"], blk["ws_up"],
                               blk["ws_down"])
    routed = 0.0
    for first in range(0, EXPERTS, 2):
        held = dataclasses.replace(cfg, experts_held=(first, 2))
        of_share = {**blk, **{name: blk[name][first:first + 2]
                              for name in ("w_gate", "w_up", "w_down")}}
        out = model._FEED_FORWARDS["routed_ff"].forward(
            mixed, of_share, held, site)[0]
        routed = routed + (out - mixed - shared)  # the shared expert: once
    layer = mixed + routed + shared
    assert float(jnp.abs(layer - y_ref).max()) < 5e-5

    unembed = jax.random.normal(jax.random.PRNGKey(5), (D, VOCAB)) / D ** 0.5
    final = jnp.ones((D,))
    logits_ref = reference._rmsnorm(y_ref, final, cfg.norm_eps) @ unembed
    slices = [reference._rmsnorm(layer, final, cfg.norm_eps)
              @ unembed[:, lo:lo + VOCAB // 3]
              for lo in range(0, VOCAB, VOCAB // 3)]
    assert float(jnp.abs(jnp.concatenate(slices, -1) - logits_ref).max()) < 1e-4


@pytest.mark.parametrize("held,message", [
    ((2, 4), "whole groups"),   # straddles two key-value heads
    ((6, 4), "whole groups"),   # runs past the last head
    ((0, 3), "whole groups"),   # three of a group of four from its start...
])
def test_a_share_that_cuts_a_key_value_head_is_refused(held, message):
    cfg = dataclasses.replace(WHOLE, layer_types=("full_attention",),
                              heads_held=held)
    if held == (0, 3):  # ...lies inside one group: allowed
        assert model._OPERATORS["full_attention"].kv_heads(cfg) == 1
        return
    with pytest.raises(ValueError, match=message):
        model.transformer_init(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("op,extra", [
    ("latent_attention", dict(kv_lora_rank=16, qk_nope_head_dim=8,
                              qk_rope_head_dim=4, v_head_dim=8)),
    ("sparse_attention", dict(index_heads=2, index_head_dim=8, index_topk=4)),
    ("mamba2", dict(mamba_heads=4, mamba_head_dim=8, ssm_state=8)),
    ("conv", {}),
])
def test_an_operator_that_holds_all_its_heads_refuses_a_share(op, extra):
    cfg = dataclasses.replace(WHOLE, layer_types=(op,), heads_held=(0, 4),
                              n_experts=0, attn_gate=False, **extra)
    with pytest.raises(ValueError, match="heads_held"):
        cfg.layers
    with pytest.raises(ValueError, match=op):
        model.flops_per_token(cfg, 16)


def test_a_share_of_the_heads_is_one_chips():
    """Over a mesh of several devices nothing sums the partial results
    yet: the step says so, as the sparse operator does."""
    cfg = dataclasses.replace(WHOLE, layer_types=("kda",), heads_held=(0, 4),
                              n_experts=0, n_shared_experts=0)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    params = model.transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    with pytest.raises(NotImplementedError, match="tensor"):
        model.transformer_loss(params, {"tokens": tokens, "targets": tokens},
                               cfg, mesh=mesh)
    one = make_mesh({"data": 1}, devices=jax.devices()[:1])
    assert jnp.isfinite(model.transformer_loss(
        params, {"tokens": tokens, "targets": tokens}, cfg, mesh=one))


def test_the_head_count_comes_from_one_place():
    """`cfg.heads(op)` is the record's, under the window's own count and
    under a share alike: init, params, holds and widths cannot disagree."""
    sliding = dataclasses.replace(
        WHOLE, layer_types=("sliding_attention",), n_heads_sliding=16,
        sliding_window=4, n_kv_heads=4)
    record = model._OPERATORS["sliding_attention"]
    assert sliding.heads("sliding_attention") == record.all_heads(sliding) == 16
    share = dataclasses.replace(sliding, heads_held=(8, 8))
    assert share.heads("sliding_attention") == 8
    assert record.kv_heads(share) == 2 and record.kv_heads(sliding) == 4
    leaves = record.init(jax.random.PRNGKey(0), share, 1)
    assert leaves["wq"].shape == (1, D, 8 * WIDTH)
    assert leaves["wk"].shape == (1, D, 2 * WIDTH)
    assert leaves["w_gate_attn"].shape == (1, D, 8 * WIDTH)
    assert record.params(share) == sum(
        leaves[name].size for name in record.matmuls)
