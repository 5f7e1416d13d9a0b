"""The stack of sublayers, the Mamba-2 mixer and its chunked scan, attention
without rotary positions and the ungated `relu2` experts, on the CPU at a
tiny size: the scan against the recurrence taken token by token (forward
and gradients), the mixer, the routed layer and the whole loss against the
benchmark's plain reference, the shares of a routed layer against the uncut
layer, the published pattern of 52 sublayers, and what the new kinds mean
to `saved_activations` (`ray_tpu/ops/ssd.py`, `ray_tpu/ops/moe.py`,
`ray_tpu/models/transformer.py`)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.reference import nemotron_h as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    LayerKind, param_shardings, saved_activations, segments, transformer_init,
    transformer_loss_and_readings)
from ray_tpu.ops import moe
from ray_tpu.ops.ssd import ssd
from ray_tpu.parallel import make_mesh
import tiny_models
from tiny_models import (
    as_reference_config, distance, first_layer, init, key, one_device,
    program, ssd_by_token, value_and_grad, y_and_grads)

# config.json's own `hybrid_override_pattern`
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = {"M": "mamba2", "E": "routed_ff", "*": "full_attention"}


def tiny(pattern="MEMEM*EME", **over):
    return TransformerConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=len(pattern), n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=24, d_ff_shared=48, max_seq_len=64,
        norm_eps=1e-5, tied_embeddings=False, dtype=jnp.float32, rope=False,
        sublayer_types=tuple(KINDS[c] for c in pattern),
        ff_activation="relu2", n_experts=8, experts_per_token=3,
        experts_held=(2, 4), n_shared_experts=1, router_score="sigmoid",
        norm_topk_prob=True, norm_topk_eps=1e-20, routed_scaling_factor=2.5,
        expert_bias=True, router_aux_loss_coef=1e-4, router_z_loss_coef=0.0,
        mamba_heads=4, mamba_head_dim=8, ssm_state=16, ssm_groups=2,
        ssd_chunk=16, rescale_prenorm_residual=True), **over})


batch_of = functools.partial(tiny_models.batch_of, seq=40)


def seeded_bias(cfg, scale=0.1, seed=7):
    return scale * jax.random.normal(
        key(seed), (cfg.n_routed_layers, cfg.n_experts))


def scan_inputs(T, H=4, P=8, G=2, N=16, rows=2, seed=0):
    ks = jax.random.split(key(seed), 6)
    return (jax.random.normal(ks[0], (rows, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (rows, T, H))),
            -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (rows, T, G, N)),
            jax.random.normal(ks[4], (rows, T, G, N)),
            jax.random.normal(ks[5], (H,)))


def grad_rel_err(cfg, params, batch):
    """The distance between the program's gradients and the reference's
    under the program's choice of experts, over the reference's norm."""
    (_, readings), grads = program(cfg, params, batch)
    return distance(grads, value_and_grad(lambda p: reference.loss(
        p, batch, as_reference_config(cfg), readings["expert_index"]),
        params)[1])


@functools.cache
def seeded(dtype="float32", kept=()):
    """`tiny(remat=True)` with its drawn weights, a batch and a bias, and the
    program's (loss, readings) and gradients with `kept` names saved, at rows
    of 8: compiled once for every case that reads them."""
    cfg = tiny(dtype=jnp.dtype(dtype), remat=True)
    params, batch, bias = init(key(0), cfg), batch_of(cfg), seeded_bias(cfg)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_ROW_TILE", 8)
        out = program(cfg, params, batch, expert_bias=bias, saved_names=kept)
    return cfg, params, batch, bias, out


# ---------------------------------------------------------------- the scan

@pytest.mark.parametrize("T,chunk,G", [
    (64, 16, 2),   # several whole chunks
    (50, 16, 2),   # the last chunk is padded
    (48, 16, 4),   # a group a head
    (48, 16, 1),   # one group for all heads
])
def test_chunked_scan_is_the_recurrence_forward_and_backward(T, chunk, G):
    args = scan_inputs(T, G=G)
    with jax.default_matmul_precision("highest"):
        y, ours = y_and_grads(functools.partial(ssd, chunk=chunk), args)
        want, theirs = y_and_grads(ssd_by_token, args)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    for name, a, b in zip("x dt A B C D".split(), ours, theirs):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(
            a / scale, b / scale, atol=2e-5, err_msg=name)


def test_the_result_does_not_depend_on_the_chunk():
    args = scan_inputs(64)
    with jax.default_matmul_precision("highest"):
        results = [ssd(*args, chunk=chunk) for chunk in (16, 64, 128)]
    for other in results[1:]:
        np.testing.assert_allclose(results[0], other, rtol=2e-4, atol=2e-4)


def test_the_scan_keeps_decays_in_float32_under_bf16_operands():
    """bf16 inputs reach the matmuls as they are; `dt A` and its running
    sums stay float32, so 64 tokens of decay lose nothing to bf16's 8 bits:
    against the float32 recurrence on the same (rounded) inputs the error is
    the operands' rounding, a few parts in a thousand."""
    x, dt, A, B, C, D = scan_inputs(64, seed=3)
    low = [v.astype(jnp.bfloat16) for v in (x, B, C)]
    y = ssd(low[0], dt, A, low[1], low[2], D, chunk=16)
    assert y.dtype == jnp.bfloat16
    ref = ssd_by_token(low[0], dt, A, low[1], low[2], D)
    err = jnp.abs(y.astype(jnp.float32) - ref).max() / jnp.abs(ref).max()
    assert float(err) < 2e-2
    with pytest.raises(ValueError, match="groups"):
        ssd(x, dt, A, B[:, :, :1].repeat(3, 2), C[:, :, :1].repeat(3, 2), D)


# --------------------------------------------- sublayers against the reference

def test_the_mixer_is_the_reference_s():
    cfg = tiny("M")
    w = first_layer(cfg)
    # away from the initialiser's ones and zeros
    w = {**w, "conv_b": 0.1 * jax.random.normal(key(2), w["conv_b"].shape),
         "D": 1.0 + 0.3 * jax.random.normal(key(3), w["D"].shape),
         "norm": 1.0 + 0.3 * jax.random.normal(key(6), w["norm"].shape)}
    x = jax.random.normal(key(5), (2, 40, 32))
    with jax.default_matmul_precision("highest"):
        ours, readings = model._block(x, w, None, None, cfg, cfg.layers[0], None, 1)
        theirs = reference.mixer(x, w, as_reference_config(cfg))
    assert readings is None
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(ours - x).mean()) > 0.01
    with pytest.raises(NotImplementedError, match="sequence axis"):
        model._block(x, w, None, None, cfg, cfg.layers[0], "sequence", 2)


def test_attention_without_rotation_is_a_masked_softmax():
    cfg = tiny("*", attention_impl="xla")
    w = first_layer(cfg)
    x = jax.random.normal(key(5), (2, 40, 32))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    with jax.default_matmul_precision("highest"):
        ours, _ = model._block(x, w, positions, None, cfg, cfg.layers[0], None, 1)
        theirs = reference.attention(x, w, as_reference_config(cfg))
        rotated, _ = model._block(
            x, w, positions, None, dataclasses.replace(cfg, rope=True),
            cfg.layers[0], None, 1)
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(rotated - ours).max()) > 1e-3
    # 4 heads of 16 on a stream of 32: the heads' width is its own key
    assert w["wq"].shape == (32, 64) and w["wo"].shape == (64, 32)


@pytest.mark.parametrize("held", [(2, 4), None])
def test_the_ungated_experts_with_the_shared_one_are_the_reference_s(
        held, monkeypatch):
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    cfg = tiny("E", experts_held=held)
    w = first_layer(cfg)
    assert "w_gate" not in w and "ws_gate" not in w
    x = jax.random.normal(key(5), (2, 40, 32))
    bias = seeded_bias(cfg)[0]
    config = as_reference_config(cfg)

    def ours(x, w):
        return model._block(x, w, None, bias, cfg, cfg.layers[0], None, 1)

    def theirs(x, w):
        return reference.routed_feed_forward(x, w, config, bias)[0]

    with jax.default_matmul_precision("highest"):
        out, readings = ours(x, w)
        np.testing.assert_allclose(out, theirs(x, w), rtol=2e-4, atol=2e-5)
        g_ours = jax.jit(jax.grad(
            lambda x, w: jnp.sum(jnp.sin(ours(x, w)[0])), argnums=(0, 1)))(x, w)
        g_theirs = jax.jit(jax.grad(
            lambda x, w: jnp.sum(jnp.sin(theirs(x, w))), argnums=(0, 1)))(x, w)
    for a, b in zip(jax.tree.leaves(g_ours), jax.tree.leaves(g_theirs)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-4)
    assert readings["expert_load"].shape == (8,)
    assert int(readings["expert_load"].sum()) == 2 * 40 * 3


def test_the_factor_scales_the_routed_part_alone():
    cfg = tiny("E")
    w = first_layer(cfg)
    x = jax.random.normal(key(5), (2, 40, 32))
    none = {**w, "w_up": w["w_up"][:0], "w_down": w["w_down"][:0]}
    with jax.default_matmul_precision("highest"):
        alike = reference.routed_feed_forward(
            x, none, {**as_reference_config(cfg), "experts_held": (0, 0)})[0]
        out = {f: model._block(x, w, None, None, dataclasses.replace(
            cfg, routed_scaling_factor=f), cfg.layers[0], None, 1)[0]
               for f in (1.0, 2.5)}
    np.testing.assert_allclose(
        out[2.5] - alike, 2.5 * (out[1.0] - alike), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_program_agrees_with_the_plain_reference(dtype):
    cfg, params, batch, bias, ((loss, readings), grads) = seeded(dtype)
    config = as_reference_config(cfg)
    ref_loss, ref_grads = value_and_grad(lambda p: reference.loss(
        p, batch, config, readings["expert_index"], bias), params)
    loss_tol, grad_tol = (1e-5, 1e-3) if dtype == "float32" else (3e-3, 0.1)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < loss_tol
    assert distance(grads, ref_grads) < grad_tol
    _, chosen, balance = jax.jit(lambda p: reference.forward(
        p, batch, config, expert_bias=bias))(params)
    assert float(readings["aux_loss"]) == pytest.approx(float(balance), rel=2e-2)
    if dtype == "float32":  # the reference's own choice is the program's
        ours = jax.nn.one_hot(readings["expert_index"], 8).sum(-2) > 0
        assert bool((ours == chosen).all())
    assert readings["held_slots"].shape == (4,)
    assert int(readings["dropped_slots"].sum()) == 0


def test_a_dropped_skip_or_a_bf16_sum_of_decays_is_seen(monkeypatch):
    """What the chip's comparison must tell from the stated path, here in
    float32 where what is left is the fault's own: the skip `D x` left out,
    and the running sums of `dt A` rounded to bf16."""
    cfg = tiny("MEM", remat=False)
    params = init(key(0), cfg)
    batch = batch_of(cfg)

    def errors():
        return grad_rel_err(cfg, params, batch)

    assert errors() < 1e-4
    real_ssd, real_cumsum = model.ssd, jnp.cumsum
    monkeypatch.setattr(model, "ssd", lambda x, dt, A, B, C, D, **kw: real_ssd(
        x, dt, A, B, C, jnp.zeros_like(D), **kw))
    assert errors() > 6e-2
    monkeypatch.setattr(model, "ssd", real_ssd)
    monkeypatch.setattr(
        jnp, "cumsum", lambda a, **kw: real_cumsum(
            a.astype(jnp.bfloat16), **kw).astype(a.dtype))
    assert errors() > 1e-3  # 40 tokens: chunks of 16 are short sums
    monkeypatch.setattr(jnp, "cumsum", real_cumsum)
    assert errors() < 1e-4


# ------------------------------------------------------------------ the share

def test_the_shares_add_up_to_the_uncut_layer(monkeypatch):
    """32 experts held 2 a share: the shared expert is what every share
    computes alike, and counted once; the 16 shares' routed parts beside it
    are the uncut reference's layer."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    cfg = tiny("E", n_experts=32, experts_per_token=6, experts_held=None)
    w = first_layer(cfg)
    x = jax.random.normal(key(5), (2, 24, 32))
    bias = 0.1 * jax.random.normal(key(7), (32,))
    config = as_reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = reference.routed_feed_forward(x, w, config, bias)
        none_held = {**w, "w_up": w["w_up"][:0], "w_down": w["w_down"][:0]}
        alike = reference.routed_feed_forward(
            x, none_held, {**config, "experts_held": (0, 0)}, bias)[0]
        parts = []
        for first in range(0, 32, 2):
            share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
            held = {**w, "w_up": w["w_up"][first:first + 2],
                    "w_down": w["w_down"][first:first + 2]}
            out, readings = model._block(
                x, held, None, bias, share_cfg, cfg.layers[0], None, 1)
            assert int(readings["dropped_slots"]) == 0
            assert readings["expert_load"].shape == (32,)
            parts.append(out - alike)  # this share's routed part alone
    assert len(parts) == 16
    np.testing.assert_allclose(alike + sum(parts), whole, rtol=2e-4, atol=5e-5)
    # neither the shared expert nor the routed parts are a small part of it
    assert float(jnp.abs(alike - x).mean()) > 0.05
    assert float(jnp.abs(whole - alike).mean()) > 0.05


def test_bias_takes_no_gradient_and_moves_toward_balance():
    cfg = tiny()
    params = init(key(0), cfg)
    batch = batch_of(cfg)
    grad = jax.jit(jax.grad(lambda b: transformer_loss_and_readings(
        params, batch, cfg, expert_bias=b)[0]))(seeded_bias(cfg))
    np.testing.assert_array_equal(grad, jnp.zeros_like(grad))
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
        assert np.isfinite(out["loss"])
        moved = np.abs(np.asarray(state["expert_bias"]) - before)
        assert np.logical_or(moved < 1e-6, np.abs(moved - 0.02) < 1e-6).all()
        spread.append(float(out["expert_load"].astype(jnp.float32).std(-1).mean()))
    assert len(jax.tree.leaves(state["opt"])) == n_opt
    assert np.mean(spread[-5:]) < 0.5 * np.mean(spread[:3])


# --------------------------------------------------------------- the stack

def test_the_published_pattern_builds_and_steps():
    """All 52 published sublayers at a tiny width: the cut to nine is a cut
    of depth, not a special case. The 52 build (their state's shapes, no
    program); the nine, which hold every kind of the 52, step."""
    cfg = tiny(PUBLISHED, experts_held=None, remat=True)
    kinds = cfg.layers
    assert len(kinds) == 52
    assert sum(k.op == "mamba2" for k in kinds) == 23
    assert sum(k.routed for k in kinds) == 23 == cfg.n_routed_layers
    assert sum(k.op == "full_attention" for k in kinds) == 6
    assert all((k.op is None) == k.ff for k in kinds)  # one sublayer a layer
    assert segments(cfg) == [model.Segment(kinds, 1)]  # no shorter period
    state = jax.eval_shape(make_train_step(cfg, one_device())[0], key(0))
    assert len(state["params"]["blocks"][0]) == 52
    assert state["expert_bias"].shape == (23, 8)
    cut = tiny(experts_held=None, remat=True)
    assert {k.op for k in cut.layers} == {k.op for k in kinds}
    init_state, step, _ = make_train_step(cut, one_device())
    state = init_state(key(0))
    batch = batch_of(cut, seq=24)
    losses = []
    for _ in range(3):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert np.isfinite(losses).all() and losses[2] < losses[0]
    assert out["expert_load"].shape == (cut.n_routed_layers, 8) == (4, 8)


@pytest.mark.parametrize("pattern,layouts", [
    ("MEMEM*EME", [(9, 1)]),           # the cell's cut: no period shorter
    ("MEMEMEME", [(2, 4)]),            # mixer and feed-forward, four times
    ("M*EM*EM*E", [(3, 3)]),
    ("MMMM", [(1, 4)]),                # one kind: one scanned tree
])
def test_periods_are_found_over_sublayers(pattern, layouts):
    cfg = tiny(pattern)
    assert [(len(s.layout), s.periods) for s in segments(cfg)] == layouts
    params = init(key(0), cfg)
    shard = param_shardings(one_device(), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(shard)
    if pattern == "MMMM":
        assert params["blocks"]["A_log"].shape == (4, 4)
    loss, _ = jax.jit(lambda p: transformer_loss_and_readings(
        p, batch_of(cfg, seq=24), cfg,
        expert_bias=jnp.zeros((cfg.n_routed_layers, 8))
        if cfg.n_routed_layers else None))(params)
    assert np.isfinite(loss)


def test_layers_of_two_sublayers_are_what_they_were():
    """`layer_types` still makes an operator and a feed-forward a layer."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_experts=4,
        experts_per_token=2, n_dense_layers=1,
        layer_types=("conv", "full_attention", "conv"))
    assert cfg.layers == (LayerKind("conv", False), LayerKind(
        "full_attention", True), LayerKind("conv", True))
    assert all(k.ff for k in cfg.layers)
    assert [(len(s.layout), s.periods) for s in segments(cfg)] == [
        (1, 1), (2, 1)]
    with pytest.raises(ValueError, match="sublayer_types names"):
        tiny("MEM", n_layers=4).layers
    with pytest.raises(ValueError, match="ff_activation"):
        tiny(ff_activation="gelu").gated


def test_heads_are_cut_over_the_tensor_axis():
    cfg = tiny()
    mesh = make_mesh({"fsdp": 2, "tensor": 2}, devices=jax.devices()[:4])
    shard = param_shardings(mesh, cfg)
    params = jax.eval_shape(lambda: transformer_init(key(0), cfg))
    mixer = shard["blocks"][0][0]
    assert mixer["w_out"].spec == (None, "tensor", "fsdp")
    assert mixer["A_log"].spec == mixer["D"].spec == (None, "tensor")
    assert mixer["w_in"].spec == (None, "fsdp", None)
    for leaf, sharding in zip(jax.tree.leaves(params), jax.tree.leaves(shard)):
        sharding.shard_shape(leaf.shape)  # every cut divides its dimension


# ------------------------------------------------------- remat, operations

def test_saved_activations_name_only_what_each_kind_has():
    cfg = tiny(dtype=jnp.bfloat16, remat=True)
    tokens = 2 * 64
    assert model._layer_widths(cfg, LayerKind("mamba2", False, False))[0] == {
        "mamba_in": 32 + 96 + 4, "ssd_out": 32}
    assert set(model._layer_widths(cfg, LayerKind(
        "full_attention", False, False))[0]) == {
            "attn_ctx", "attn_res", "attn_qkv"}
    # a share of ungated experts has no names; the shared expert's one
    # product has
    assert model._layer_widths(cfg, LayerKind(None, True))[0] == {
        "shared_up": 48}
    whole = dataclasses.replace(cfg, experts_held=None)
    assert set(model._layer_widths(whole, LayerKind(None, True))[0]) == {
        "moe_slots", "moe_up", "shared_up"}
    state = 12 * sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: transformer_init(key(0), cfg))))
    terms = model._terms(cfg, tokens, state // 3)
    sizes = terms.saved_bytes()
    assert list(sizes) == ["attn_ctx", "attn_res", "attn_qkv", "mamba_in",
                           "ssd_out", "shared_up"]
    assert sizes["mamba_in"] == 4 * tokens * 132 * 2  # four mixers
    assert sizes["shared_up"] == 4 * tokens * 48 * 2
    assert sizes["attn_res"] == tokens * 32 * 2  # one attention layer
    # a mixer's parameters but for its vectors: W_in and W_out
    assert model._layer_widths(cfg, cfg.layers[0])[1] == 32 * 132 + 32 * 32
    args = (cfg, tokens, state, state // 3)
    assert saved_activations(*args, None) == {}
    assert saved_activations(*args, 1 << 40) == dict.fromkeys(sizes, 1)
    # the stack is one period of nine layers, so it is walked a layer at a
    # time: a limit that the step's fullest moment with four names just fits
    four = dict.fromkeys(("attn_ctx", "attn_res", "attn_qkv", "mamba_in"), 1)
    chosen = saved_activations(
        *args, state + model._SAVE_RESERVE + terms.fullest(four).bytes)
    assert list(chosen) == ["attn_ctx", "attn_res", "attn_qkv", "mamba_in"]
    # the scan's masks are in the working set: H Q values a token, in
    # float32 and the compute dtype
    mixers = tiny("MM", dtype=jnp.bfloat16)
    fewer = dataclasses.replace(mixers, ssd_chunk=8)
    many = 1 << 16  # enough tokens that a block outweighs the head's chunk
    assert (model._terms(mixers, many, 1 << 20).at_once
            - model._terms(fewer, many, 1 << 20).at_once
            == many * 4 * 8 * (4 * 4 + 2 * 2))


def test_kept_names_leave_loss_and_gradients_as_they_are():
    (loss, _), grads = seeded()[-1]
    (kept_loss, _), kept = seeded(
        kept=("mamba_in", "ssd_out", "shared_up", "attn_qkv"))[-1]
    assert float(loss) == pytest.approx(float(kept_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(kept)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_mixer_s_passes_as_kernels_leave_loss_and_gradients(monkeypatch):
    """A stack whose mixers tile (one group of 128 channels, a state of 128,
    32 tokens) with the convolution and the gated norm through their kernels
    (`ops/mamba_passes.py`, interpret mode) against the `jax.numpy` lines:
    the loss and every leaf's gradient, under the step's `remat`."""
    from ray_tpu.ops import mamba_passes as passes

    cfg = tiny("MEM*M", remat=True, mamba_heads=4, mamba_head_dim=32,
               ssm_state=128, ssm_groups=1)
    params, bias = init(key(0), cfg), seeded_bias(cfg)
    batch = batch_of(cfg, seq=32)
    lines = program(cfg, params, batch, expert_bias=bias)
    calls = []

    def through(kernel):
        def call(*args, **kw):
            calls.append(kernel.__name__)
            return kernel(*args, **{**kw, "interpret": True})
        return call

    monkeypatch.setattr(passes, "_ROWS", 16)
    monkeypatch.setattr(model, "_gated_norm_kernels", lambda cfg, T=None: True)
    monkeypatch.setattr(model, "causal_conv_silu",
                        through(passes.causal_conv_silu))
    monkeypatch.setattr(model, "gated_group_rmsnorm",
                        through(passes.gated_group_rmsnorm))
    jaxpr = str(jax.make_jaxpr(lambda p: program(
        cfg, p, batch, expert_bias=bias))(params))
    for name in ("mamba_conv_fwd", "mamba_conv_bwd", "mamba_norm_fwd",
                 "mamba_norm_bwd"):
        assert name in jaxpr
    (loss, _), grads = program(cfg, params, batch, expert_bias=bias)
    assert set(calls) == {"causal_conv_silu", "gated_group_rmsnorm"}
    assert float(loss) == pytest.approx(float(lines[0][0]), rel=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(lines[1])
    for ours, theirs in zip(jax.tree.leaves(grads), jax.tree.leaves(lines[1])):
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-6)


def test_flops_count_every_kind_of_sublayer():
    cfg = tiny()
    matmul, attn, head = model._fwd_flops_per_token(cfg, 64)
    mixer = (2 * 32 * (32 + 96 + 4) + 2 * 32 * 32       # W_in and W_out
             + 2 * 16 * (2 * 16 + 32) + 2 * 2 * 32 * 16)  # the scan, chunks of 16
    attention = 2 * 32 * (64 + 2 * 32) + 2 * 64 * 32
    routed = 2 * 32 * 8 + 3 * 4 / 8 * 4 * 32 * 24 + 4 * 32 * 48
    assert matmul == 4 * mixer + attention + 4 * routed
    assert attn == 2 * 2 * 4 * 16 * 65 / 2
    assert head == 2 * 32 * 128


def test_weights_of_the_mixer_s_matmuls_get_buffers_of_their_own():
    cfg = tiny("MEM*")
    blocks = transformer_init(key(0), cfg)["blocks"][0]
    mixer, experts, _, attention = cfg.layers
    assert model.own_buffer_weights(blocks[0], mixer) == ("w_in", "w_out")
    assert model.own_buffer_weights(blocks[1], experts) == ("ws_up", "ws_down")
    assert model.own_buffer_weights(blocks[3], attention) == (
        "wq", "wk", "wv", "wo")
    count, total, widest = model.own_buffers(
        transformer_init(key(0), cfg)["blocks"],
        dataclasses.replace(cfg, dtype=jnp.bfloat16))
    assert count == 2 * (2 + 2 + 2 + 4)  # a segment of one period: twice
    assert widest == 2 * (2 * 32 * 64 + 2 * 32 * 32)  # attention's four
