"""Training by block diffusion (`objective="block_diffusion"`,
`ray_tpu/ops/block_diffusion.py`) at tiny sizes on the CPU: the staircase at
steps of 4 under the flash kernels in interpret mode against the dense
mask, forward and all three gradients, at a group of 16 and at a ragged
end; the own block and the join; the noise to the bit; what a configuration
may and may not ask for; the step's readings and the account's counters; and
that a next-token configuration lowers the program it lowered. The record's
statements are a case of `tests/test_layer_kinds.py`."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.ops import block_diffusion as bd
from ray_tpu.ops.flash_attention import flash_attention_lse, flash_tiles
from ray_tpu.parallel import make_mesh

BLOCK = 4
TINY = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_head=8, d_ff=16, max_seq_len=32, n_experts=8,
            experts_per_token=2, norm_topk_prob=True, experts_held=(2, 4),
            qk_norm="head", router_aux_loss_coef=0.001,
            router_z_loss_coef=0.0, tied_embeddings=False, dtype=jnp.float32,
            attention_impl="xla",
            layer_types=("block_diffusion_attention",) * 2,
            objective="block_diffusion", diffusion_block=BLOCK,
            mask_token_id=127)


def tiny(**over):
    return TransformerConfig(**{**TINY, **over})


def batch_of(rows=2, length=32, seed=0, vocab=127):
    rng = np.random.default_rng(seed)
    return {
        "tokens": rng.integers(0, vocab, (rows, length), dtype=np.int32),
        "noise": rng.integers(0, 1 << 24, (rows, length), dtype=np.int32),
        "level": rng.integers(1, (1 << 24) + 1, (rows, length // BLOCK),
                              dtype=np.int32)}


def dense_attention(q, k, v, mask):
    """softmax(q k^T / sqrt(D)) v under `mask` [R, R], grouped heads."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[3])
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


# ------------------------------------------------- the mask and its parts

def test_the_dense_mask_is_the_three_rules():
    """Row by row at L 8, blocks of 4: a noisy row of block 1 sees the clean
    block 0 and its own noisy block; a clean row of block 1 the clean blocks
    0 and 1; nobody a noisy row of another block."""
    mask = np.asarray(bd.dense_mask(8, 4))
    noisy0, noisy1 = [0, 1, 2, 3], [4, 5, 6, 7]
    clean0, clean1 = [8, 9, 10, 11], [12, 13, 14, 15]
    for row, sees in ((1, noisy0), (6, clean0 + noisy1), (9, clean0),
                      (14, clean0 + clean1)):
        assert sorted(np.flatnonzero(mask[row])) == sorted(sees)
    # the pairs the parts walk, by the count the operations are made from
    assert mask.sum() == 8 * bd.pairs_per_token(8, 4)
    assert bd.pairs_per_token(16384, 4) == 16388.0


def test_folding_the_halves_into_the_heads_and_back():
    x = jnp.arange(2 * 12 * 4 * 3, dtype=jnp.float32).reshape(2, 12, 4, 3)
    folded = bd.fold_halves(x, 2)  # [2, 6, 8, 3]
    assert folded.shape == (2, 6, 8, 3)
    # key-value head 1's group: half 0's heads 2, 3 then half 1's
    assert bool(jnp.all(folded[:, :, 4] == x[:, :6, 2]))
    assert bool(jnp.all(folded[:, :, 6] == x[:, 6:, 2]))
    assert bool(jnp.all(bd.unfold_halves(folded, 2) == x))
    assert bool(jnp.all(bd.unfold_halves(folded[..., 0], 2) == x[..., 0]))


# (L, heads, key-value heads, width): a group of 16 (2 x 8 over 1), a
# ragged end (200 rows under tiles of 128), and a shape whose own block and
# join tile (`own_join_untiled`: the path "kernels" runs `bd_own_join_fwd`
# and `bd_own_join_bwd` there, and the `jax.numpy` lines at the other two)
SHAPES = {"group16": (256, 8, 1, 32), "ragged": (200, 4, 2, 32),
          "tiled": (256, 4, 2, 128)}
_made = {}


def attention_and_gradients(shape, path):
    if (shape, path) in _made:
        return _made[shape, path]
    L, H, Hk, D = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (1, 2 * L, H, D))
    k = jax.random.normal(ks[1], (1, 2 * L, Hk, D))
    v = jax.random.normal(ks[2], (1, 2 * L, Hk, D))
    w = jax.random.normal(ks[3], (1, 2 * L, H, D))
    if path == "dense":
        fn = lambda q, k, v: dense_attention(  # noqa: E731
            q, k, v, bd.dense_mask(L, BLOCK))
    else:
        kw = dict(interpret=True) if path == "kernels" else dict(impl="xla")
        fn = lambda q, k, v: bd.block_diffusion_attention(  # noqa: E731
            q, k, v, block=BLOCK, **kw)
    o = fn(q, k, v)
    grads = jax.grad(lambda *a: (fn(*a) * w).sum(), (0, 1, 2))(q, k, v)
    _made[shape, path] = dict(zip(("o", "dq", "dk", "dv"), (o, *grads)))
    return _made[shape, path]


@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("path", ["kernels", "xla"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_parts_add_up_to_the_dense_mask(shape, path, what):
    """The staircase at steps of 4 (the flash kernels in interpret mode, or
    the XLA path), the own block and the join against one dense softmax
    under the mask: the output and each of the three gradients."""
    ours = attention_and_gradients(shape, path)[what]
    theirs = attention_and_gradients(shape, "dense")[what]
    assert float(jnp.abs(ours - theirs).max()) < 2e-5 * float(
        jnp.abs(theirs).max())


def test_the_own_block_and_join_run_as_kernels_where_they_tile():
    """Which of `SHAPES` took which path above, from the traces."""
    for shape, (L, H, Hk, D) in SHAPES.items():
        args = (jnp.zeros((1, 2 * L, H, D)), *[jnp.zeros((1, 2 * L, Hk, D))] * 2)
        text = str(jax.make_jaxpr(lambda *a: bd.block_diffusion_attention(
            *a, block=BLOCK, interpret=True))(*args))
        assert ("bd_own_join_fwd" in text) == (shape == "tiled")
        assert "flash_fwd_stair" in text
        assert bd.own_join_untiled(L, H, Hk, D, BLOCK, 4) is None or (
            shape != "tiled")


def test_the_staircase_at_a_span_under_a_tile():
    """At a span of 4 no tile divides a span: the diagonal's tiles take the
    in-tile mask, the ones under it the bare body, and the choice of tile is
    the causal walk's; block 0's rows see no key."""
    for kernel in ("flash_fwd", "flash_bwd_dkv_dq"):
        stair = flash_tiles(kernel, 16384, 16384, 128, jnp.bfloat16,
                            causal=False, stair=(4, 4), group=16)
        causal = flash_tiles(kernel, 16384, 16384, 128, jnp.bfloat16,
                             causal=True, group=16)
        assert (stair.block_q, stair.block_k) == (causal.block_q,
                                                  causal.block_k)
        assert stair.active_share == causal.active_share
        assert stair.vmem_limit_bytes <= 96 << 20
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 2, 32))
    o, lse = flash_attention_lse(q, q, q, stair=(4, 4), interpret=True)
    assert bool(jnp.all(o[:, :4] == 0)) and bool(jnp.all(lse[:, :4] == -jnp.inf))
    assert bool(jnp.all(jnp.isfinite(lse[:, 4:])))


def test_the_own_block_is_two_way_and_the_join_one_softmax():
    """`own_block_part` alone is a softmax over a block's 4 rows, both
    directions; joined with a staircase that saw nothing it is the output."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (16, 4, 8))
    k = jax.random.normal(ks[1], (16, 2, 8))
    v = jax.random.normal(ks[2], (16, 2, 8))
    o, lse = bd.own_block_part(q, k, v, 4, 0.5)
    blocks = jnp.arange(16) // 4
    own = blocks[:, None] == blocks[None, :]
    kk, vv = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    s = jnp.where(own[None], jnp.einsum("thd,shd->hts", q, kk) * 0.5, -jnp.inf)
    assert jnp.allclose(o, jnp.einsum(
        "hts,shd->thd", jax.nn.softmax(s, -1), vv), atol=1e-6)
    assert jnp.allclose(lse, jax.nn.logsumexp(s, -1).T, atol=1e-6)
    joined = bd.own_block_and_join(
        q[None], k[None], v[None], jnp.zeros((1, 16, 4, 8)),
        jnp.full((1, 16, 4), -jnp.inf), 4, 0.5)
    assert jnp.allclose(joined[0], o, atol=1e-6)
    assert bd._row_chunk(32768, 4) == 2048 and bd._row_chunk(400, 4) == 16
    assert bd._row_chunk(6, 4) == 6  # no whole blocks divide it: all at once


# --------------------------------------------------------------- the noise

def test_the_noise_to_the_bit():
    cfg = tiny()
    batch = batch_of()
    rows, positions, weights, masked = model.diffusion_inputs(batch, cfg)
    level = np.repeat(batch["level"], BLOCK, axis=1)
    want = batch["noise"] < level
    assert np.array_equal(np.asarray(masked), want)
    assert np.array_equal(np.asarray(rows[:, :32]),
                          np.where(want, 127, batch["tokens"]))
    assert np.array_equal(np.asarray(rows[:, 32:]), batch["tokens"])
    assert np.array_equal(np.asarray(positions[0]),
                          np.tile(np.arange(32), 2))
    # 2^24 / level in float32, exactly, over the batch's tokens
    assert np.array_equal(np.asarray(weights), np.where(
        want, np.float32(1 << 24) / level.astype(np.float32), 0
    ).astype(np.float32) / np.float32(64))
    # a level of 2^24 masks every token of its block at weight 1
    batch["level"][:] = 1 << 24
    _, _, weights, masked = model.diffusion_inputs(batch, cfg)
    assert bool(masked.all()) and float(weights.sum()) == pytest.approx(1.0)
    with pytest.raises(ValueError, match="a level a block"):
        model.diffusion_inputs({**batch, "level": batch["level"][:, :3]}, cfg)


def test_the_loss_is_the_masked_positions_weighted():
    """Against the loss written out: logits of the noisy half, no shift,
    `m / t` a position over the batch's tokens, and the balance loss over
    all the rows."""
    cfg = tiny(router_aux_loss_coef=0.0)
    params = model.transformer_init(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    loss, readings = model.transformer_loss_and_readings(params, batch, cfg)
    rows, positions, weights, masked = model.diffusion_inputs(batch, cfg)
    hidden = model.transformer_hidden(params, rows, cfg, positions=positions)
    logp = jax.nn.log_softmax(hidden[:, :32] @ params["unembed"], axis=-1)
    ce = -jnp.take_along_axis(logp, batch["tokens"][..., None], -1)[..., 0]
    assert float(loss) == pytest.approx(float((weights * ce).sum()), rel=1e-5)
    assert int(readings["diffusion_tokens"]) == 64
    assert int(readings["diffusion_rows"]) == 128
    assert int(readings["diffusion_masked_tokens"]) == int(masked.sum())
    assert float(readings["diffusion_weight_sum"]) == pytest.approx(
        float(weights.sum()) * 64, rel=1e-6)
    assert readings["expert_index"].shape == (2, 128, 2)  # both halves routed


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_a_noisy_row_is_blind_to_what_the_mask_hides(path, monkeypatch):
    """The noisy half's hidden rows of block b do not move when a clean
    token of block b or later changes, and the clean half's do not move
    when the noise does. By the XLA path at the tiny widths, and by the
    kernels in interpret mode (the staircase's and the own block and
    join's) at halves of 128 rows and heads of 128, which they tile."""
    L = 32
    cfg = tiny(n_experts=0, experts_held=None)
    if path == "kernels":
        L = 128
        cfg = tiny(n_experts=0, experts_held=None, max_seq_len=L, d_head=128,
                   n_layers=1, layer_types=("block_diffusion_attention",))
        assert bd.own_join_untiled(L, 4, 2, 128, BLOCK, 4) is None
        monkeypatch.setattr(
            model, "block_diffusion_attention",
            lambda *a, **kw: bd.block_diffusion_attention(
                *a, **{**kw, "interpret": True}))
    params = model.transformer_init(jax.random.PRNGKey(0), cfg)
    batch = batch_of(rows=1, length=L)
    rows, positions, _, _ = model.diffusion_inputs(batch, cfg)
    hidden = model.transformer_hidden(params, rows, cfg, positions=positions)
    changed = rows.at[0, L + 9].set((rows[0, L + 9] + 1) % 127)  # clean, block 2
    moved = model.transformer_hidden(params, changed, cfg, positions=positions)
    same = jnp.abs(hidden - moved).max(-1)[0] == 0
    assert bool(same[:12].all()) and bool(same[L:L + 8].all())  # blocks 0 to 2
    assert not bool(same[12:16].any())  # the noisy block 3 reads clean block 2
    noisier = rows.at[0, 5].set(127)  # a noisy row of block 1
    moved = model.transformer_hidden(params, noisier, cfg, positions=positions)
    same = jnp.abs(hidden - moved).max(-1)[0] == 0
    assert bool(same[L:].all()) and bool(same[:4].all()) and bool(same[8:L].all())
    assert not bool(same[4:8].any())


def test_the_embedding_s_scale_is_the_configuration_s():
    """`embed_init_std`: None draws the embedding as it was drawn, at 0.02;
    1 draws the same values fifty times as large, and nothing else moves."""
    cfg = tiny()
    plain = model.transformer_init(jax.random.PRNGKey(4), cfg)
    large = model.transformer_init(
        jax.random.PRNGKey(4), dataclasses.replace(cfg, embed_init_std=1.0))
    assert jnp.allclose(large["embed"], plain["embed"] * 50.0, rtol=1e-6)
    assert 0.9 < float(large["embed"].std()) < 1.1
    for name in ("unembed", "final_norm"):
        assert bool(jnp.all(large[name] == plain[name]))
    assert all(bool(jnp.all(a == b)) for a, b in zip(
        jax.tree.leaves(large["blocks"]), jax.tree.leaves(plain["blocks"])))
    with pytest.raises(ValueError, match="embed_init_std"):
        model.transformer_init(jax.random.PRNGKey(4), dataclasses.replace(
            cfg, embed_init_std=1.0, init_std=0.02))


# ------------------------------------------------ what a configuration asks

@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("block_diffusion_attention", "full_attention")),
     "full_attention"),
    (dict(objective="next_token"), "next_token"),
    (dict(objective="masked"), "objective"),
    (dict(diffusion_block=0), "diffusion_block"),
    (dict(diffusion_block=5), "whole blocks"),
    (dict(mask_token_id=None), "mask_token_id"),
    (dict(mask_token_id=128), "mask_token_id"),
    (dict(loop_steps=2), "loop_steps"),
    (dict(layer_types=("full_attention",) * 2), "block_diffusion_attention"),
])
def test_a_stack_it_cannot_run_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        tiny(**over).layers


def test_a_mesh_of_several_devices_is_not_mapped_yet():
    cfg = tiny()
    params = model.transformer_init(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="mesh of 2"):
        model.transformer_loss(params, batch_of(), cfg, mesh=mesh)
    record = model._OPERATORS["block_diffusion_attention"]
    assert "sequence axis" in record.no_sequence_axis
    with pytest.raises(NotImplementedError, match="sequence axis"):
        rows, positions, _, _ = model.diffusion_inputs(batch_of(), cfg)
        model._block(jnp.zeros((2, 64, 32)), jax.tree.map(
            lambda x: x[0], params["blocks"]), positions, None, cfg,
            cfg.layers[0], "sequence", 2)


# --------------------------------------------- operations and the keep rule

def test_operations_a_token_count_two_rows_and_one_head():
    cfg = tiny()
    d, dh, h, hk, f = 32, 8, 4, 2, 16
    projections = 2 * (d * (h + 2 * hk) * dh + h * dh * d)
    pairs = 2 * 2 * h * dh * (32 + BLOCK)  # both rows of a token
    router, experts = 2 * d * 8, 2 * 4 / 8 * 2 * 3 * d * f
    layer = 2 * (projections + router + experts) + pairs
    assert model.flops_per_token(cfg, 32) == pytest.approx(
        3 * (2 * layer + 2 * d * 128))
    assert cfg.rows_per_token == 2 and TransformerConfig().rows_per_token == 1
    causal = dataclasses.replace(
        cfg, objective="next_token", layer_types=(), diffusion_block=0,
        mask_token_id=None)
    assert model.flops_per_token(causal, 32) < model.flops_per_token(cfg, 32)


def test_the_keep_rule_counts_the_stream_s_rows():
    """The rule is asked with the rows the stack runs, two a token: the same
    limit keeps at most what the stack at twice the tokens would."""
    cfg = tiny(remat=True)
    terms = model._terms(cfg, 2 * 64)
    assert terms.names["attn_ctx"] == 2 * (2 * 64) * 4 * (
        4 * 128 + 4 * 4 // 4)
    record = model._OPERATORS["block_diffusion_attention"]
    assert record.holds(cfg) > model._OPERATORS["full_attention"].holds(cfg)
    assert model._exchange_bytes(cfg, 128, 1) == 0


# ----------------------------------------------------------------- the step

def test_the_step_trains_and_the_account_counts():
    from ray_tpu.train import _runtime
    from ray_tpu.util import tracing

    cfg = tiny(remat=True)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init, step, _ = make_train_step(cfg, mesh, optax.adamw(1e-2))
    state = init(jax.random.PRNGKey(0))
    tracing.take_steps()
    losses = []
    for i in range(3):
        state, out = step(state, batch_of(seed=0))
        losses.append(float(out["loss"]))
    assert losses[2] < losses[0] and all(map(math.isfinite, losses))
    assert int(out["diffusion_tokens"]) == 64 and int(out["diffusion_rows"]) == 128
    assert step.static["held_chunk"] > 0
    assert {r.name: r.over_steps for r in model._DIFFUSION_READINGS} == {
        "diffusion_" + what: "diffusion." + what
        for what in ("tokens", "masked_tokens", "weight_sum", "rows")}
    before = tracing.counters()
    jax.block_until_ready(out)
    _, _, named = _runtime._fold_steps(tracing.take_steps())
    assert {"diffusion.tokens", "moe.held_rows"} <= named
    after = tracing.counters()
    count = lambda name: after[name] - before.get(name, 0)  # noqa: E731
    assert count("diffusion.tokens") == 3 * 64
    assert count("diffusion.rows") == 3 * 128
    assert count("diffusion.masked_tokens") == 3 * int(
        out["diffusion_masked_tokens"])
    assert count("diffusion.weight_sum") == pytest.approx(
        3 * float(out["diffusion_weight_sum"]))
    assert count("moe.layer_steps") == 3 * 2


def scopes_of(text):
    """The parts of the name stacks of a lowered module's locations (a
    location that is one bare name is a Python frame, not a stack)."""
    return {part for stack in re.findall(r'loc\("([^"]+)"', text)
            if "/" in stack for part in re.split(r"[/()]", stack)}


def test_a_next_token_configuration_lowers_the_program_it_lowered():
    """`objective` at its default and named are one program, and nothing of
    block diffusion is in it."""
    plain = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                 max_seq_len=16, attention_impl="xla")
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    texts = []
    for cfg in (TransformerConfig(**plain),
                TransformerConfig(**plain, objective="next_token")):
        params = jax.eval_shape(
            lambda: model.transformer_init(jax.random.PRNGKey(0), cfg))
        texts.append(jax.jit(jax.value_and_grad(
            lambda p, b: model.transformer_loss(p, b, cfg))).lower(
                params, batch).as_text(debug_info=True))
    assert texts[0] == texts[1]
    assert not {"bd_noise", "bd_attention", "bd_loss",
                "block_diffusion_attention"} & scopes_of(texts[0])
    diffusing = tiny()
    params = jax.eval_shape(
        lambda: model.transformer_init(jax.random.PRNGKey(0), diffusing))
    text = jax.jit(jax.value_and_grad(
        lambda p, b: model.transformer_loss(p, b, diffusing))).lower(
            params, batch_of()).as_text(debug_info=True)
    assert {"bd_noise", "bd_attention", "bd_stair", "bd_own_block", "bd_join",
            "bd_loss", "block_diffusion_attention"} <= scopes_of(text)
