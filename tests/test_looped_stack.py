"""A stack that is run `loop_steps` times over the same weights
(`ray_tpu/models/transformer.py`): the looped walk against the same blocks
applied by hand, outputs and every gradient; `loop_steps` 1 with the new
fields off traces the program it was; the exit distribution and its entropy;
the sandwich norms; what the rule of a rematerialised block's kept names
counts a pass; what is refused; the scopes. CPU, tiny sizes, seeded
weights; the frame is `tests/tiny_models.py`'s: a loss with its gradients is
one compiled program, and a program that several cases read is made once."""

import dataclasses
import functools
import hashlib
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as tr
from ray_tpu.ops.fused import fused_rmsnorm, weighted_lm_head_cross_entropy
from ray_tpu.parallel import make_mesh
from ray_tpu.util import tracing

import tiny_models as tm
from tiny_models import value_and_grad

TINY = dict(vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_seq_len=16, dtype=jnp.float32, tied_embeddings=False,
            attention_impl="xla")


def looped(**over):
    return TransformerConfig(**{
        **TINY, "loop_steps": 4, "post_norm": True, "exit_gate": True,
        "exit_entropy_coef": 0.05, **over})


@functools.cache
def seeded(cfg, seed=0):
    params = tm.init(tm.key(seed), cfg)
    # norms off 1, the gate's bias off 0: a gradient that a scale or a bias
    # of exactly 1 or 0 would hide shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def moved(path, x):
        name = str(path[-1].key)
        if "norm" in name or name == "exit_b":
            return x + 0.1 * jax.random.normal(next(keys), x.shape)
        return x

    return jax.tree_util.tree_map_with_path(moved, params)


def batch_of(cfg):
    return tm.batch_of(cfg, seq=cfg.max_seq_len)


@functools.partial(jax.jit, static_argnums=(2, 3))
def streams_by_hand(params, tokens, cfg, norm_between=True):
    """The passes' normed streams with no loop of the program's: a Python
    loop over the passes and the layers, every layer through `_block` on its
    own slice of the stacked weights."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    kind = cfg.layers[0]
    x = params["embed"].astype(cfg.dtype)[tokens]
    streams = []
    for _ in range(cfg.loop_steps):
        for layer in range(cfg.n_layers):
            blk = jax.tree.map(lambda w: w[layer], params["blocks"])
            x, _ = tr._block(x, blk, positions, None, cfg, kind, None, 1)
        normed = fused_rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
        streams.append(normed)
        x = normed if norm_between else x
    return jnp.stack(streams)


def loss_by_hand(params, batch, cfg):
    streams = streams_by_hand(params, batch["tokens"], cfg)
    return tr._exit_loss(streams, params, batch["targets"], cfg)[0]


def loss_of(cfg, batch, **kw):
    """`params -> the model's own loss`, for `value_and_grad`."""
    return lambda p: tr.transformer_loss(p, batch, cfg, **kw)


# ------------------------------------------------ the loop against by hand

@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("n_layers", [1, 2])  # a scan of length 1 is inlined
def test_the_looped_stack_is_its_blocks_applied_four_times(remat, n_layers):
    cfg = looped(remat=remat, n_layers=n_layers)
    params, batch = seeded(cfg), batch_of(cfg)
    (streams, readings), hidden = jax.jit(lambda p: (
        tr._hidden_and_readings(p, batch["tokens"], cfg),
        tr.transformer_hidden(p, batch["tokens"], cfg)))(params)
    assert readings is None and streams.shape == (4, 2, 16, 32)
    by_hand = streams_by_hand(params, batch["tokens"], cfg)
    np.testing.assert_allclose(streams, by_hand, atol=2e-5)
    # a pass changes the stream: the four are four
    assert float(jnp.abs(streams[1:] - streams[:-1]).max()) > 1e-2
    np.testing.assert_allclose(hidden, by_hand[-1], atol=2e-5)


@pytest.mark.parametrize("saved", [(), ("attn_res", "mlp_up")])
def test_every_gradient_is_the_sum_over_a_weights_four_uses(saved):
    cfg = looped(remat=True)
    params, batch = seeded(cfg), batch_of(cfg)
    loss, grads = value_and_grad(loss_of(cfg, batch, saved_names=saved), params)
    by_hand, grads_by_hand = value_and_grad(
        lambda p: loss_by_hand(p, batch, cfg), params)
    assert float(loss) == pytest.approx(float(by_hand), rel=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == 16  # 11 of the blocks, embed, unembed, norm, gate's 2
    for (path, ours), theirs in zip(flat, jax.tree.leaves(grads_by_hand)):
        assert float(jnp.abs(theirs).max()) > 0, path
        np.testing.assert_allclose(ours, theirs, atol=3e-5, rtol=1e-4,
                                   err_msg=str(path))


MODELS = {  # (what differs from `looped()`, the gradient's leaves)
    "dense": (dict(n_layers=3, post_norm=False, exit_gate=False,
                   exit_entropy_coef=0.0), 12),
    "gated": (dict(n_layers=3), 16),  # the sandwich norms and the exit gate
    # two kinds of layer in a period, one of which makes neither name
    "two_kinds": (dict(n_layers=4, post_norm=False, layer_types=(
        "conv", "full_attention", "conv", "full_attention")), 22),
}


@jax.jit
def adamw_update(params, grads):
    """The parameters after AdamW's first update from `grads`: compiled once
    a model, whatever made the gradients."""
    import optax

    optimizer = optax.adamw(1e-2)
    updates, _ = optimizer.update(grads, optimizer.init(params), params)
    return optax.apply_updates(params, updates)


def loss_grads_and_update(cfg, saved=()):
    """(loss, its gradients, the parameters after one AdamW update from
    them) of `seeded(cfg)` with `saved` kept."""
    params = seeded(cfg)
    loss, grads = value_and_grad(
        loss_of(cfg, batch_of(cfg), saved_names=saved), params)
    return loss, grads, adamw_update(params, grads)


# what JAX makes of the stack without remat by itself: once a model
plain_autodiffs = functools.cache(loss_grads_and_update)


@pytest.mark.parametrize("kept_passes", range(5))
@pytest.mark.parametrize("name", ["attn_ctx", "mlp_up"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_backward_under_remat_is_plain_autodiffs(model, name, kept_passes):
    """The loop that makes a block again and adds its gradient into the one
    sum, `name` kept for the last `kept_passes` of the four passes (and
    `attn_res` for all of them, so that two of the passes' backwards
    differ): the loss, every gradient leaf and the parameters after an
    AdamW update are those of the stack without remat, which JAX
    differentiates by itself."""
    over, leaves = MODELS[model]
    cfg = looped(remat=True, **over)
    wanted, wanted_grads, wanted_params = plain_autodiffs(
        dataclasses.replace(cfg, remat=False))
    loss, grads, updated = loss_grads_and_update(
        cfg, {"attn_res": 4, name: kept_passes})
    assert float(loss) == pytest.approx(float(wanted), rel=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == leaves
    for (path, ours), theirs, new, new_wanted in zip(
            flat, jax.tree.leaves(wanted_grads), jax.tree.leaves(updated),
            jax.tree.leaves(wanted_params)):
        assert float(jnp.abs(theirs).max()) > 0, path
        np.testing.assert_allclose(ours, theirs, atol=3e-5, rtol=1e-4,
                                   err_msg=str(path))
        # AdamW's first step is lr sign(g) but where g is near 0
        sure = jnp.abs(theirs) > 1e-4
        np.testing.assert_allclose(
            jnp.where(sure, new, 0), jnp.where(sure, new_wanted, 0),
            atol=1e-5, err_msg=str(path))


def test_a_pass_kept_beyond_the_stacks_is_refused():
    cfg = looped(remat=True)
    params, batch = seeded(cfg), batch_of(cfg)
    with pytest.raises(ValueError, match="attn_ctx kept at 5 of 4 passes"):
        tr.transformer_loss(params, batch, cfg, saved_names={"attn_ctx": 5})
    with pytest.raises(ValueError, match="attn_out kept .* not a name"):
        tr.transformer_loss(params, batch, cfg, saved_names=("attn_out",))


def flash_in_interpret_mode(monkeypatch):
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    return dict(attention_impl="pallas", max_seq_len=128)


def scans(jaxpr):
    """Every `scan` equation of `jaxpr`, the ones inside others too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) else [value]:
                item = getattr(item, "jaxpr", item)
                if hasattr(item, "eqns"):
                    yield from scans(item)


@pytest.mark.parametrize("kept_passes", range(5))
def test_a_kept_passs_flash_forward_is_not_made_again(monkeypatch,
                                                      kept_passes):
    """The kernel's calls in the program: one in the forward's loop; of the
    backward's loops over the layers, one for each run of passes that keep
    the same, the one whose passes keep `attn_ctx` has the backward kernel
    and no forward kernel."""
    # five layers: no run of the four passes is as long
    cfg = looped(remat=True, n_layers=5,
                 **flash_in_interpret_mode(monkeypatch))
    params = jax.eval_shape(lambda: seeded(cfg))
    batch = batch_of(cfg)
    closed = jax.make_jaxpr(jax.grad(lambda p: tr.transformer_loss(
        p, batch, cfg, saved_names={"attn_ctx": kept_passes})))(params)
    over_layers = [str(eqn) for eqn in scans(closed.jaxpr)
                   if eqn.params["length"] == cfg.n_layers]
    forward = [text for text in over_layers if "flash_bwd" not in text]
    backward = [text for text in over_layers if "flash_bwd" in text]
    assert len(forward) == 1 and forward[0].count("name=flash_fwd") == 1
    again = [text.count("name=flash_fwd") for text in backward]
    assert again == {0: [1], 4: [0]}.get(kept_passes, [0, 1])


def test_the_backward_holds_one_sum_of_the_layers_gradient():
    """In the lowered program of a tiny looped model the layers' float32
    gradient `[n_layers, d, d_ff]` is the loops' carry and nothing else: no
    second stack is made a pass and added to it, and no pass's inputs
    `[n_layers, B, T, d]` are sliced out of all the passes'. JAX's own
    transpose of the two scans does both (the parent's text at these sizes:
    two such adds, and a slice `tensor<1x3x2x16x32xf32>`); the stack
    without `remat`, which JAX still differentiates, shows the adds."""
    cfg = looped(remat=True, n_layers=3)
    params, batch = seeded(cfg), batch_of(cfg)

    def lowered(cfg, **kw):
        return jax.jit(jax.grad(lambda p: tr.transformer_loss(
            p, batch, cfg, **kw))).lower(params).as_text()

    def adds_of_stacks(text):
        return len(re.findall(
            r"stablehlo\.add [^\n]*: tensor<3x32x64xf32>\n", text))

    def slices_of_a_pass(text):
        return len(re.findall(r"stablehlo\.dynamic_slice [^\n]*-> "
                              r"tensor<(1x)?3x2x16x32xf32>\n", text))

    for saved in ((), {"attn_ctx": 2}):
        text = lowered(cfg, saved_names=saved)
        assert "tensor<4x3x2x16x32xf32>" in text  # all the passes' inputs
        assert "tensor<3x32x64xf32>" in text  # the one sum
        assert adds_of_stacks(text) == 0 and slices_of_a_pass(text) == 0
    assert adds_of_stacks(lowered(dataclasses.replace(cfg, remat=False))) == 2


def test_without_a_gate_the_loss_is_the_last_passs_head_alone():
    """And the looped stack's gradient is not a stack's that is run once."""
    cfg = looped(exit_gate=False, exit_entropy_coef=0.0)
    once = dataclasses.replace(cfg, loop_steps=1)
    params, batch = seeded(cfg), batch_of(cfg)
    loss, g4 = value_and_grad(loss_of(cfg, batch), params)
    _, g1 = value_and_grad(loss_of(once, batch), params)
    assert float(jnp.abs(g4["blocks"]["wq"] - g1["blocks"]["wq"]).max()) > 1e-4
    # without the gate the loss is the last pass's cross-entropy alone
    streams = streams_by_hand(params, batch["tokens"], cfg)
    last = jax.jit(tr._head_loss)(
        streams[-1], params["unembed"], batch["targets"])
    assert float(loss) == pytest.approx(float(last), rel=1e-6)


# --------------------------------- loop_steps 1: the program that there was

# the equations of value_and_grad of the loss at the parent commit (d31b413),
# their number and a sha256 over them (`equations`): a dense and a routed
# tiny configuration, with and without remat
PARENT_JAXPRS = {
    "dense": (435, "36e1424ae285fbcc"),
    "dense_remat": (569, "8c9a5e7ea992b224"),
    "routed": (685, "d7aaea4832fc33de"),
    "routed_remat": (900, "29ccd153f13e5b16"),
}


def parent_case(name):
    over = dict(TINY, dtype=jnp.bfloat16)
    if name.startswith("routed"):
        over.update(n_experts=4, experts_per_token=2, d_ff=32, qk_norm=True)
    return TransformerConfig(remat=name.endswith("remat"), **over)


def equations(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs inside its equations,
    depth first, as (primitive, the outputs' shapes and dtypes, the
    parameters that are plain values). How the printer shares a
    sub-program between its uses, which a process's cache of traces
    decides, is not in it."""
    plain = (bool, int, float, str, type(None))
    for eqn in jaxpr.eqns:
        inner, values = [], []
        for key, value in sorted(eqn.params.items()):
            for item in value if isinstance(value, (tuple, list)) else [value]:
                item = getattr(item, "jaxpr", item)  # a ClosedJaxpr's own
                if hasattr(item, "eqns"):
                    inner.append(item)
            if isinstance(value, plain) or (
                    isinstance(value, tuple)
                    and all(isinstance(v, plain) for v in value)):
                values.append((key, value))
        yield (eqn.primitive.name,
               tuple(str(v.aval) for v in eqn.outvars), tuple(values))
        for sub in inner:
            yield from equations(sub)


def jaxpr_digest(cfg):
    batch = batch_of(cfg)
    params = jax.eval_shape(
        lambda: tr.transformer_init(jax.random.PRNGKey(0), cfg))
    # under no default precision: another test module sets one for the
    # process when it is imported, and a product's equation then names it
    with jax.default_matmul_precision(None):
        closed = jax.make_jaxpr(jax.value_and_grad(
            lambda p: tr.transformer_loss_and_readings(
                p, batch, cfg, saved_names=("attn_res",) if cfg.remat else ()),
            has_aux=True))(params)
    listed = list(equations(closed.jaxpr))
    return len(listed), hashlib.sha256(repr(listed).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_loop_steps_1_traces_the_parents_program(name):
    cfg = parent_case(name)
    assert (cfg.loop_steps, cfg.post_norm, cfg.exit_gate) == (1, False, False)
    assert jaxpr_digest(cfg) == PARENT_JAXPRS[name]


def test_the_new_fields_off_add_no_leaf_and_no_reading():
    cfg = TransformerConfig(**TINY)
    params = tr.transformer_init(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "blocks", "final_norm", "unembed"}
    assert not any("post_norm" in name for name in params["blocks"])
    loss, readings = tr.transformer_loss_and_readings(
        params, batch_of(cfg), cfg)
    assert readings == {}
    hidden = tr.transformer_hidden(params, batch_of(cfg)["tokens"], cfg)
    assert hidden.shape == (2, 16, 32)


# ----------------------------------------- the exit distribution, the loss

def distribution_f64(a):
    """numpy, float64: p from the sigmoids' products, as the paper writes."""
    a = np.asarray(a, np.float64)
    lam = 1.0 / (1.0 + np.exp(-a))
    p, left = [], np.ones_like(a[0])
    for t in range(a.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(left)
    return np.stack(p)


@pytest.mark.parametrize("passes", [2, 4, 7])
def test_the_exit_distribution_sums_to_1(passes):
    a = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 5, 11))
    p, log_p = tr.exit_distribution(a)
    assert p.shape == log_p.shape == a.shape and p.dtype == jnp.float32
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, distribution_f64(a), atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    # the last gate's reading is unused
    moved = a.at[-1].add(5.0)
    np.testing.assert_array_equal(tr.exit_distribution(moved)[0], p)


@pytest.mark.parametrize("gate", [30.0, -30.0, 88.0, -88.0])
def test_the_distribution_is_finite_at_large_gates(gate):
    a = jnp.full((4, 3), gate).at[1, 1].set(-gate).at[2, 2].set(0.0)
    p, log_p = tr.exit_distribution(a)
    assert bool(jnp.isfinite(p).all()) and bool(jnp.isfinite(log_p).all())
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    entropy = lambda a: -(lambda p, lp: (p * lp).sum())(*tr.exit_distribution(a))
    grads = jax.grad(entropy)(a)
    assert bool(jnp.isfinite(grads).all())
    assert float(jnp.abs(grads[-1]).max()) == 0.0  # the last gate's


def test_the_entropys_gradient_agrees_with_a_float64_form():
    a = jax.random.normal(jax.random.PRNGKey(5), (4, 6))
    entropy = lambda a: -(lambda p, lp: (p * lp).sum())(*tr.exit_distribution(a))
    ours = np.asarray(jax.grad(entropy)(a), np.float64)

    def entropy_f64(a):
        p = distribution_f64(a)
        return -(p * np.log(p)).sum()

    base, step = np.asarray(a, np.float64), 1e-6
    theirs = np.zeros_like(base)
    for index in np.ndindex(*base.shape):
        up, down = base.copy(), base.copy()
        up[index] += step
        down[index] -= step
        theirs[index] = (entropy_f64(up) - entropy_f64(down)) / (2 * step)
    assert float(entropy(a)) == pytest.approx(entropy_f64(base), rel=1e-6)
    np.testing.assert_allclose(ours, theirs, atol=2e-6)


def test_the_loss_is_the_expected_cross_entropy_less_beta_entropy():
    cfg = looped()
    params, batch = seeded(cfg), batch_of(cfg)
    targets = batch["targets"].at[0, :5].set(-100)  # ignored: count 27
    streams = streams_by_hand(params, batch["tokens"], cfg)
    exit_loss = jax.jit(tr._exit_loss, static_argnums=3)
    loss, readings = exit_loss(streams, params, targets, cfg)
    logits = streams @ params["unembed"]
    ce = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.where(targets < 0, 0, targets)[None, ..., None].repeat(4, 0),
        axis=-1)[..., 0]
    gate = streams @ params["exit_w"] + params["exit_b"]
    p = distribution_f64(gate)
    mask = np.asarray(targets >= 0, np.float64)
    entropy = (-(p * np.log(p)).sum(0) * mask).sum() / 27
    expected = ((p * np.asarray(ce)).sum(0) * mask).sum() / 27
    assert float(loss) == pytest.approx(expected - 0.05 * entropy, rel=1e-5)
    assert float(readings["exit_entropy"]) == pytest.approx(entropy, rel=1e-5)
    np.testing.assert_allclose(
        readings["ut_pass_loss"], (np.asarray(ce) * mask).sum((1, 2)) / 27,
        rtol=1e-5)
    np.testing.assert_allclose(
        readings["exit_p_mean"], (p * mask).sum((1, 2)) / 27, rtol=1e-5)
    assert float(readings["exit_p_mean"].sum()) == pytest.approx(1.0, abs=1e-6)
    # beta 0: the expected cross-entropy alone
    plain, _ = exit_loss(streams, params, targets,
                         dataclasses.replace(cfg, exit_entropy_coef=0.0))
    assert float(plain) == pytest.approx(expected, rel=1e-5)


def test_the_gate_learns_through_the_weights():
    """`d loss / d b_g` has the cross-entropies' part: with the weights held
    constant only the entropy's is left."""
    cfg = looped()
    params, batch = seeded(cfg), batch_of(cfg)
    _, whole = value_and_grad(loss_of(cfg, batch), params)

    real = tr.weighted_lm_head_cross_entropy
    try:
        tr.weighted_lm_head_cross_entropy = lambda h, w, t, wt, **kw: real(
            h, w, t, jax.lax.stop_gradient(wt), **kw)
        _, held = value_and_grad(loss_of(cfg, batch), params)
    finally:
        tr.weighted_lm_head_cross_entropy = real
    assert tr.weighted_lm_head_cross_entropy is weighted_lm_head_cross_entropy
    gap = float(jnp.linalg.norm(whole["exit_w"] - held["exit_w"]))
    assert gap > 0.1 * float(jnp.linalg.norm(whole["exit_w"]))


# ------------------------------------------------------- the sandwich norms

def test_post_norm_adds_one_scale_a_sublayer_and_norms_the_output():
    cfg = looped()
    params = seeded(cfg)
    blocks = params["blocks"]
    assert blocks["attn_post_norm"].shape == blocks["mlp_post_norm"].shape == (
        2, 32)
    kind = cfg.layers[0]
    axes = tr._block_axes(cfg, kind)
    assert set(axes) == set(blocks)
    assert axes["attn_post_norm"] == axes["mlp_post_norm"] == ("layers", None)
    # the output joins the stream normed: scaling a sublayer's last matrix
    # changes nothing
    batch = batch_of(cfg)
    scaled = {**params, "blocks": {**blocks, "wo": 3.0 * blocks["wo"],
                                   "w_down": 0.5 * blocks["w_down"]}}
    loss = jax.jit(tr.transformer_loss, static_argnums=2)
    np.testing.assert_allclose(
        loss(params, batch, cfg), loss(scaled, batch, cfg), rtol=2e-5)
    plain = dataclasses.replace(cfg, post_norm=False)

    def unnormed(p):  # a norm is found by its leaf
        return {**p, "blocks": {k: v for k, v in p["blocks"].items()
                                if not k.endswith("post_norm")}}

    moved = loss(unnormed(scaled), batch, plain) - (
        loss(unnormed(params), batch, plain))
    assert abs(float(moved)) > 1e-3
    # holds: the product before the norm, a `d_model` each
    for sub in tr._sublayers(kind):
        assert sub.holds(cfg) - sub.holds(plain) == cfg.d_model
        assert sub.params(cfg) == sub.params(plain)


@pytest.mark.parametrize("over", [
    dict(n_experts=4, experts_per_token=2),
    dict(layer_types=("conv", "full_attention")),
    dict(sublayer_types=("mamba2", "dense_ff"), mamba_heads=2,
         mamba_head_dim=16, ssm_state=8),
])
def test_post_norm_with_a_sublayer_that_has_none_is_refused(over):
    cfg = TransformerConfig(**{**TINY, "post_norm": True, **over})
    with pytest.raises(ValueError, match="post_norm with a"):
        cfg.layers


# ------------------------------------------------------------- what is refused

def test_loop_steps_under_an_unmapped_axis_is_refused():
    cfg = looped()
    params, batch = seeded(cfg), batch_of(cfg)
    for axis in ("sequence", "expert"):
        mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match=f"`{axis}` axis"):
            tr.transformer_loss(params, batch, cfg, mesh=mesh)
    with pytest.raises(NotImplementedError, match="`sequence` axis"):
        tr.transformer_loss(params, batch, cfg, seq_axis="sequence")
    # a data axis is a batch axis: nothing to map
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    assert math.isfinite(float(tr.transformer_loss(
        params, batch, cfg, mesh=mesh)))


def test_loop_steps_with_a_share_of_the_heads_is_refused():
    cfg = looped(heads_held=(0, 2))
    params = tr.transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="heads_held"):
        tr.transformer_loss(params, batch_of(cfg), cfg)


def test_loop_steps_over_layers_that_make_readings_is_refused():
    cfg = looped(post_norm=False, n_experts=4, experts_per_token=2, d_ff=32)
    params = tr.transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="make readings"):
        tr.transformer_loss(params, batch_of(cfg), cfg)


@pytest.mark.parametrize("over, error", [
    (dict(loop_steps=1), "exit_gate with loop_steps 1"),
    (dict(loop_steps=0, exit_gate=False), "loop_steps 0"),
])
def test_a_gate_without_a_loop_is_an_error(over, error):
    cfg = looped(**over)
    with pytest.raises(ValueError, match=error):
        tr.transformer_hidden(
            tr.transformer_init(jax.random.PRNGKey(0), cfg),
            batch_of(cfg)["tokens"], cfg)


# ---------------------------------------- what a rematerialised block keeps

def rule_config(**over):
    return looped(**{**dict(
        remat=True, dtype=jnp.bfloat16, d_model=256, n_heads=4, d_ff=512,
        n_layers=3, vocab_size=1024, max_seq_len=512), **over})


def test_a_kept_name_is_held_once_a_layer_a_pass():
    cfg = rule_config()
    once = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    tokens = 2048
    params = tr._whole_param_bytes(cfg)
    terms = tr._terms(cfg, tokens, params)
    four, one = terms.saved_bytes(), tr._terms(once, tokens).saved_bytes()
    assert list(four) == list(one) == [
        "attn_ctx", "attn_res", "attn_qkv", "mlp_gate", "mlp_up"]
    for name in one:
        assert four[name] == 4 * one[name], name
    # the blocks' inputs: 4 L + 1 and, a pass each, the final norm's input
    # and the normed stream's cotangent; a stack run once L + 1
    stream = tokens * cfg.d_model * 2
    assert tr._boundary_bytes(once, tokens) == (3 + 1) * stream
    assert tr._boundary_bytes(cfg, tokens) == (4 * 3 + 1 + 2 * 4) * stream
    assert (terms.boundaries, tr._terms(once, tokens).boundaries) == (
        21 * stream, 4 * stream)
    # the head reads the four passes' streams, stacked
    assert tr._head_bytes(cfg, tokens, params, 1) - tr._head_bytes(
        once, tokens, tr._whole_param_bytes(once), 1) == 3 * stream
    # beside the rest the loops hold the layers' weights in bf16, cast once
    # for all of them; the backward has one sum of the layers' gradient and
    # no pass's inputs apart
    assert tr._terms(once, tokens).loops == 0
    assert terms.loops == 2 * 3 * (4 * 256 * 256 + 3 * 256 * 512)
    # the kept names of the moments' walk count the passes that keep them
    kept = {"attn_ctx": 4, "attn_res": 2}
    moments = {m.name: m.bytes for m in tr._moments(cfg, tokens, params, 1, kept)}
    bare = {m.name: m.bytes for m in tr._moments(cfg, tokens, params, 1)}
    assert set(moments) == {"optimizer", "head", "layers 0-2"}
    for moment in ("head", "layers 0-2"):
        assert moments[moment] - bare[moment] == (
            four["attn_ctx"] + four["attn_res"] // 2)
    assert terms.saved_bytes(kept) == {
        "attn_ctx": four["attn_ctx"], "attn_res": four["attn_res"] // 2}
    # the one form: a plain tuple of names means every pass, a name kept at
    # no pass is not kept, and the order is the rule's
    assert tr._kept(cfg, ("attn_res", "attn_ctx")) == {
        "attn_ctx": 4, "attn_res": 4}
    assert list(tr._kept(cfg, {"attn_res": 2, "attn_ctx": 4})) == [
        "attn_ctx", "attn_res"]
    assert tr._kept(cfg, {"attn_ctx": 0}) == tr._kept(cfg, ()) == {}
    assert tr._kept(once, ("attn_ctx",)) == {"attn_ctx": 1}


def test_a_stack_of_one_period_under_the_loop_is_counted_as_a_scan():
    """The passes are a scan: a layer's gradient is whole from the first
    pass, so no layer is walked alone."""
    cfg = rule_config(n_layers=1)
    names = [m.name for m in tr._moments(
        cfg, 2048, tr._whole_param_bytes(cfg))]
    assert names == ["optimizer", "head", "layers 0-0"]
    once = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    assert [m.name for m in tr._moments(
        once, 2048, tr._whole_param_bytes(once))] == [
            "optimizer", "head", "layer 0"]


@pytest.mark.parametrize("limit_gb", [0.08, 0.1, 0.12, 0.13, 0.16, 0.2, 4.0])
def test_saved_activations_never_chooses_more_than_fits(limit_gb):
    cfg = rule_config()
    tokens, limit = 2048, int(limit_gb * 2**30) + tr._SAVE_RESERVE
    params = tr._whole_param_bytes(cfg)
    resident = 3 * params
    kept = tr.saved_activations(cfg, tokens, resident, params, limit)
    terms = tr._terms(cfg, tokens, params)
    sizes = terms.saved_bytes()
    assert list(kept) == list(sizes)[:len(kept)]
    # a prefix, in order; every name at all four passes but the last, which
    # has the most that fit
    passes = list(kept.values())
    assert all(k == 4 for k in passes[:-1])
    assert all(1 <= k <= 4 for k in passes)
    assert terms.saved_bytes(kept) == {
        name: sizes[name] * k // 4 for name, k in kept.items()}
    if kept:
        assert terms.room(resident, limit, kept) >= 0
        assert (resident + terms.fullest(kept).bytes + tr._SAVE_RESERVE
                <= limit)
    # a pass more of the last name, or the next name's first, would not fit
    more = None
    if kept and passes[-1] < 4:
        more = {**kept, list(kept)[-1]: passes[-1] + 1}
    elif len(kept) < len(sizes):
        more = {**kept, list(sizes)[len(kept)]: 1}
    if more:
        assert terms.room(resident, limit, more) < 0
    # the same limit keeps no fewer names of a stack that is run once
    once = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    p1 = tr._whole_param_bytes(once)
    assert len(tr.saved_activations(once, tokens, 3 * p1, p1, limit)) >= len(
        [name for name, k in kept.items() if k == 4])


def test_unequal_passes_reach_the_log_line_and_the_counters(monkeypatch,
                                                            caplog):
    """A looped stack's choice with another number of passes a name, as the
    traced step hands it on, says and counts it: the bytes of the step's
    line are the choice's (`_Terms.saved_bytes`), `train.saved_passes` is
    the sum of its passes, and the choice comes back out of both."""
    cfg = looped(remat=True, n_layers=3)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": ids, "targets": ids}
    seen = {}

    def rule(cfg, tokens, resident, params, limit, ways):
        # the first limit at which two names are kept at unequal passes
        terms = tr._terms(cfg, tokens, params, ways)
        for room in range(0, 1 << 20, 1 << 10):
            limit = resident + tr._SAVE_RESERVE + terms.fullest().bytes + room
            kept = rule.real(cfg, tokens, resident, params, limit, ways)
            if len(set(kept.values())) == 2:
                seen.update(kept=kept, terms=terms, limit=limit)
                return kept
        raise AssertionError("no limit keeps two names at unequal passes")

    rule.real = tr.saved_activations
    monkeypatch.setattr(tr, "saved_activations", rule)
    monkeypatch.setattr(tr, "_memory_limit", lambda mesh: 1 << 40)
    before = tracing.counters()
    with caplog.at_level("INFO", logger=tr.logger.name):
        step.lower(state, batch)
    kept, terms = seen["kept"], seen["terms"]
    assert list(kept) == ["attn_ctx", "attn_res"] and kept["attn_ctx"] == 4
    assert 1 <= kept["attn_res"] < 4
    counted = {name: n - before.get(name, 0)
               for name, n in tracing.counters().items()}
    sizes = terms.saved_bytes(kept)
    assert counted["train.saved_names"] == 2
    assert counted["train.saved_passes"] == 4 + kept["attn_res"]
    assert counted["train.saved_bytes"] == sum(sizes.values())
    (line,) = [r.getMessage() for r in caplog.records
               if "remat" in r.getMessage()]
    said = re.findall(r"(\w+) at (\d) of 4 passes \((\d+) bytes\)", line)
    assert [(name, int(k)) for name, k, _ in said] == list(kept.items())
    assert {name: int(size) for name, _, size in said} == sizes
    assert "%d bytes a device" % sum(sizes.values()) in line
    # and the bytes say the passes again, a name's bytes a pass known
    assert {name: size // terms.names[name]
            for name, size in sizes.items()} == kept


def test_operations_count_a_layer_and_the_head_once_a_pass():
    cfg = rule_config()
    once = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    m4, a4, h4 = tr._fwd_flops_per_token(cfg, 512)
    m1, a1, h1 = tr._fwd_flops_per_token(once, 512)
    assert (m4, a4) == (4 * m1, 4 * a1)
    assert h1 == 2 * 256 * 1024 and h4 == 4 * 2 * 256 * (1024 + 1)
    # no gate: the last pass's head alone
    ungated = dataclasses.replace(cfg, exit_gate=False)
    assert tr._fwd_flops_per_token(ungated, 512) == (m4, a4, h1)
    assert tr.flops_per_token(cfg, 512) == 3 * (m4 + a4 + h4)


# ------------------------------------------------------------------ the scopes

def test_the_scopes_and_the_one_loop_over_the_passes():
    """`ut_pass`, `post_norm`, `exit_gate`, `exit_loss` stand in the lowered
    step's name stacks with the ones there were inside them, and the passes
    are one loop: the blocks' program is traced once, not four times."""
    cfg = looped(remat=True)
    params, batch = seeded(cfg), batch_of(cfg)
    lowered = jax.jit(jax.value_and_grad(
        lambda p: tr.transformer_loss(p, batch, cfg))).lower(params)
    text = lowered.as_text(debug_info=True)
    stacks = {s for s in re.findall(r'loc\("([^"]+)"', text) if "/" in s}
    parts = {p for s in stacks for p in re.split(r"[/()]", s)}
    assert {"ut_pass", "post_norm", "exit_gate", "exit_loss", "lm_head_ce",
            "final_norm", "attention", "mlp", "attn_qkv", "attn_out",
            "embed"} <= parts
    # a norm on each sublayer's output, inside the sublayer's own scope,
    # the block inside `ut_pass`: the forward, what the backward makes
    # again and the backward itself (docs/observability.md)
    assert any("ut_pass" in s and "mlp/post_norm/" in s for s in stacks)
    assert any("ut_pass" in s and "attn_out/post_norm/" in s for s in stacks)
    assert any("/rematted_computation/ut_pass/mlp/" in s for s in stacks)
    assert any(s.startswith("transpose(") and "/checkpoint/ut_pass/mlp/" in s
               for s in stacks)
    jaxpr = str(jax.make_jaxpr(
        lambda p: tr.transformer_loss(p, batch, cfg))(params))
    # the scan over the passes, the scan over the layers inside it, the
    # head's scan over chunks
    assert jaxpr.count("length=4") == 1 and jaxpr.count("length=2") == 1
    unrolled = jaxpr.count(" scan[")
    assert unrolled == 3, unrolled
    # and the backward's two: over the passes, over the layers inside it
    grad = str(jax.make_jaxpr(jax.grad(
        lambda p: tr.transformer_loss(p, batch, cfg)))(params))
    assert grad.count("length=4") == 2 and grad.count("length=2") == 2
    assert grad.count(" scan[") == 5
