"""Ouro's stack in the program, at a small size on the CPU: sandwich-normed
attention layers run four times over the same weights, the final norm
between the passes, one head and one exit gate over every pass's stream. The
loss, the readings and every gradient against the benchmark's plain
reference (float32: the same mathematics to rounding); each of the five
wrong models read by that comparison; remat with names kept; the step, its
readings and the optimizer's mask; the rule at the cell's own sizes."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.loops.nemotron_h import decayed
from chipbench.reference import ouro as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from tiny_models import distance, one_device

CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_head=8,
    d_ff=48, rope_theta=1e6, loop_steps=4, post_norm=True, exit_gate=True,
    exit_entropy_coef=0.05, max_seq_len=64, tied_embeddings=False,
    dtype=jnp.float32, attention_impl="xla")
# the same sizes under the keys the reference reads
REF = dict(d_head=8, n_heads=4, n_kv_heads=4, n_layers=2, loop_steps=4,
           norm_eps=CFG.norm_eps, rope_theta=1e6, exit_entropy_coef=0.05)


def batch_of(seed, rows=2, T=40):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, T + 1), 0, 96)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


def seeded(seed=1, cfg=CFG):
    """Seeded weights with the norms' scales off 1 and the gate's bias off 0
    (a scale of exactly 1 hides a wrong gradient to it)."""
    params = model.transformer_init(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 7), 32))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.2 * jax.random.normal(next(keys), x.shape)
        if "norm" in str(path[-1].key) or str(path[-1].key) == "exit_b" else x,
        params)


def both_sides(cfg, ref, params, batch, **kw):
    ours = jax.jit(jax.value_and_grad(
        lambda p: model.transformer_loss_and_readings(p, batch, cfg, **kw),
        has_aux=True))(params)
    theirs = jax.jit(jax.value_and_grad(
        lambda p: reference.terms(p, batch, ref), has_aux=True))(params)
    return ours, theirs


@pytest.mark.parametrize("n_layers", [1, 2, 3])
def test_loss_readings_and_gradients_are_the_references(n_layers):
    cfg = dataclasses.replace(CFG, n_layers=n_layers)
    params, batch = seeded(cfg=cfg), batch_of(2)
    ((l_sys, readings), g_sys), ((l_ref, r_ref), g_ref) = both_sides(
        cfg, {**REF, "n_layers": n_layers}, params, batch)
    assert abs(float(l_sys) - float(l_ref)) < 1e-6 * abs(float(l_ref))
    assert distance(g_sys, g_ref) < 2e-5
    assert set(readings) == set(r_ref) == {
        "ut_pass_loss", "exit_p_mean", "exit_entropy"}
    for name in readings:
        np.testing.assert_allclose(readings[name], r_ref[name], rtol=2e-5)
    # every leaf is reached, the gate's two and the four norms among them
    for path, leaf in jax.tree_util.tree_leaves_with_path(g_sys):
        assert float(jnp.abs(leaf).max()) > 0.0, path
    gate = {k: g_sys[k] for k in ("exit_w", "exit_b")}
    assert distance(gate, {k: g_ref[k] for k in gate}) < 2e-5


def test_ignored_targets_are_out_of_both_terms():
    """The reference takes every target; the program's mask is held to it on
    the rows that are left."""
    params, batch = seeded(), batch_of(3)
    masked = dict(batch, targets=batch["targets"].at[1].set(-100))
    first = {k: v[:1] for k, v in batch.items()}
    loss, readings = model.transformer_loss_and_readings(params, masked, CFG)
    wanted, r_ref = reference.terms(params, first, REF)
    assert float(loss) == pytest.approx(float(wanted), rel=1e-6)
    np.testing.assert_allclose(
        readings["ut_pass_loss"], r_ref["ut_pass_loss"], rtol=1e-5)


WRONG = {
    "pass_dropped": dict(cfg=dict(loop_steps=3)),
    "no_norm_between_passes": dict(patch=(
        "_next_pass_input", lambda left, normed: left)),
    "weights_held_constant": dict(patch=(
        "weighted_lm_head_cross_entropy", None)),
    "entropy_left_out": dict(cfg=dict(exit_entropy_coef=0.0)),
    "post_norms_left_out": dict(drop="post_norm"),
}


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_the_comparison_reads_a_wrong_model(fault, monkeypatch):
    """Float32 on both sides, so the distance is the fault's own."""
    how = WRONG[fault]
    cfg = dataclasses.replace(CFG, **how.get("cfg", {}))
    params, batch = seeded(), batch_of(2)
    theirs = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, batch, REF)))(params)
    if "patch" in how:
        name, replacement = how["patch"]
        if replacement is None:  # the weights, detached
            real = model.weighted_lm_head_cross_entropy
            replacement = lambda h, w, t, wt, **kw: real(  # noqa: E731
                h, w, t, jax.lax.stop_gradient(wt), **kw)
        monkeypatch.setattr(model, name, replacement)
    system_params = params
    if "drop" in how:
        system_params = {**params, "blocks": {
            k: v for k, v in params["blocks"].items()
            if not k.endswith(how["drop"])}}
    ours = jax.jit(jax.value_and_grad(
        lambda p: model.transformer_loss(p, batch, cfg)))(system_params)
    grads = ours[1] if "drop" not in how else {**ours[1], "blocks": {
        **{k: jnp.zeros_like(v) for k, v in params["blocks"].items()},
        **ours[1]["blocks"]}}
    gap = distance(grads, theirs[1])
    loss_gap = abs(float(ours[0]) - float(theirs[0])) / abs(float(theirs[0]))
    if fault == "weights_held_constant":
        assert loss_gap < 1e-6  # the loss is the stated one
        gate = {k: grads[k] for k in ("exit_w", "exit_b")}
        assert distance(gate, {k: theirs[1][k] for k in gate}) > 0.2
    if fault == "entropy_left_out":  # beta is 0.05: read in the loss
        assert loss_gap > 3e-3, (fault, gap, loss_gap)
    else:
        assert gap > 0.05, (fault, gap, loss_gap)


def test_the_last_gate_is_not_read(monkeypatch):
    """The last pass takes the probability that is left: a model that read
    the last gate too would move with it."""
    params, batch = seeded(), batch_of(2)
    wanted = reference.loss(params, batch, REF)
    real = model.exit_distribution
    monkeypatch.setattr(model, "exit_distribution",
                        lambda a: real(a.at[-1].add(3.0)))
    moved = jax.jit(lambda p: model.transformer_loss(p, batch, CFG))(params)
    assert float(moved) == pytest.approx(float(wanted), rel=1e-6)


def test_remat_with_names_kept_is_the_same_step():
    params, batch = seeded(3), batch_of(4)
    plain = jax.jit(jax.value_and_grad(
        lambda p: model.transformer_loss(p, batch, CFG)))(params)
    remat = dataclasses.replace(CFG, remat=True)
    for names in ((), ("attn_ctx", "attn_res"), {"attn_ctx": 1},
                  {"attn_ctx": 4, "attn_res": 3},
                  ("attn_res", "attn_qkv", "mlp_gate", "mlp_up")):
        again = jax.jit(jax.value_and_grad(lambda p: model.transformer_loss(
            p, batch, remat, saved_names=names)))(params)
        assert abs(float(again[0]) - float(plain[0])) < 1e-6
        assert distance(again[1], plain[1]) < 1e-5


def test_the_step_trains_and_decays_matrices_only():
    no_decay = ["norm", "exit_w", "exit_b"]
    optimizer = optax.adamw(
        3e-3, b1=0.9, b2=0.95, weight_decay=0.1,
        mask=lambda params: decayed(params, no_decay))
    init_state, step, shardings = make_train_step(CFG, one_device(), optimizer)
    state = init_state(jax.random.PRNGKey(0))
    mask = decayed(state["params"], no_decay)
    assert {name for name, on in mask["blocks"].items() if not on} == {
        "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"}
    assert all(state["params"]["blocks"][name].ndim == 3
               for name, on in mask["blocks"].items() if on)
    assert mask["embed"] and mask["unembed"]
    assert not (mask["final_norm"] or mask["exit_w"] or mask["exit_b"])
    assert set(shardings["params"]) == set(state["params"])
    batch = batch_of(6, rows=1, T=32)
    losses = []
    for _ in range(6):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert out["ut_pass_loss"].shape == out["exit_p_mean"].shape == (4,)
    assert float(out["exit_p_mean"].sum()) == pytest.approx(1.0, abs=1e-5)
    assert 0.0 < float(out["exit_entropy"]) <= math.log(4) + 1e-6
    # the gate moved: it learns through the weights and the entropy
    assert float(jnp.abs(state["params"]["exit_b"])) > 0.0


def test_the_rule_at_the_cells_own_sizes():
    """Ouro-2.6B's widths, 8 layers, 4 passes, one sequence of 16,384 on a
    v5e: the rule counts what the chip holds (PERF.md section 6, PR 58) and
    keeps `attn_ctx` for the last of the four passes, 8 x 68 MB of a room
    of 0.66 GB."""
    cfg = TransformerConfig(
        vocab_size=49152, d_model=2048, n_layers=8, n_heads=16, n_kv_heads=16,
        d_head=128, d_ff=5632, max_seq_len=16384, rope_theta=1e6,
        tied_embeddings=False, loop_steps=4, post_norm=True, exit_gate=True,
        exit_entropy_coef=0.05, remat=True)
    tokens, limit = 16384, 16909336064
    params = model._whole_param_bytes(cfg)
    assert params == 4 * 612_438_017
    terms = model._terms(cfg, tokens, params)
    names = terms.saved_bytes()
    assert names["attn_ctx"] == 32 * 16384 * (16 * 128 + 32) * 2
    assert names["attn_res"] == 32 * 16384 * 2048 * 2
    stream = 16384 * 2048 * 2
    assert model._boundary_bytes(cfg, tokens) == (33 + 8) * stream
    # the eight layers' weights in bf16, which the compiler casts once
    assert terms.loops == 2 * 8 * 51_380_224
    fullest = terms.fullest()
    assert fullest.name == "layers 0-7"
    total = 3 * params + fullest.bytes
    # on the full side of the compiler's plan for a described v5e (14.83 GB
    # with nothing kept) and within 0.75 GB of it
    assert 14.83e9 < total < 14.83e9 + 0.75e9
    resident = 3 * params

    def kept(limit):
        return model.saved_activations(cfg, tokens, resident, params, limit)

    assert kept(limit) == {"attn_ctx": 1}
    assert terms.saved_bytes(kept(limit)) == {
        "attn_ctx": names["attn_ctx"] // 4}
    with_it = terms.fullest({"attn_ctx": 1}).bytes
    assert with_it - fullest.bytes == names["attn_ctx"] // 4
    assert resident + with_it + model._SAVE_RESERVE <= limit
    # a chip with half a GB less keeps nothing; with 2 GB more the kernel's
    # residuals of all four passes, and a pass of `attn_res`
    assert kept(limit - (1 << 29)) == {}
    assert kept(limit + (1 << 30)) == {"attn_ctx": 3}
    assert kept(limit + (2 << 30)) == {"attn_ctx": 4, "attn_res": 1}
    assert list(model.saved_activations(
        cfg, tokens, resident, params, limit + (4 << 30))) == [
            "attn_ctx", "attn_res"]
