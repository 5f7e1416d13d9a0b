"""A block's weight matmuls read and write buffers of their own
(`ray_tpu/models/transformer.py` `_own_weights`): a barrier is the identity,
so on the CPU at a tiny size the loss and every gradient are the parent
formulation's bit for bit, for each layout a cell trains, and what the step
counts (`own_buffers`, the log line, the two counters) is what its program
holds."""

import logging

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import (
    expert_bias_init, own_buffer_weights, own_buffers, transformer_init,
    transformer_loss_and_readings)
from ray_tpu.parallel import make_mesh
from ray_tpu.util import tracing

DENSE = dict(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=96,
    max_seq_len=64, tied_embeddings=False)
# LFM2's layout: a dense layer in a segment of its own, then a period of
# unlike layers that hold a share of the experts
LFM2 = dict(
    vocab_size=128, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
    d_ff=32, d_ff_dense=96, max_seq_len=64, rope_theta=1e6, norm_eps=1e-5,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    n_dense_layers=1, conv_taps=3, n_experts=8, experts_per_token=2,
    experts_held=(2, 4), norm_topk_prob=True, norm_topk_eps=1e-6,
    router_score="sigmoid", expert_bias=True, qk_norm="head",
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0)
# DeepSeek-V2-Lite's: a dense layer, then two scanned layers with shared
# experts beside a share of the routed ones, latent attention throughout
DSV2 = dict(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, d_ff=32, d_ff_dense=96,
    max_seq_len=64, layer_types=("latent_attention",) * 3, n_dense_layers=1,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_experts=8, experts_per_token=3, experts_held=(2, 2), n_shared_experts=2,
    seq_aux=True, router_aux_loss_coef=0.001, router_z_loss_coef=0.0,
    tied_embeddings=False)
# (the configuration's keys, the mesh, the weights `_own_weights` hands a
# layer of each tree, in the order the trees lie in `params["blocks"]`)
ATTENTION = ("wq", "wk", "wv", "wo")
CONV = ("conv_in", "conv_out")
LATENT = ("wq", "wo", "wkv_a", "wkv_b")
FEED_FORWARD = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")
CASES = {
    "dense_scanned": (DENSE, {"data": 1}, [ATTENTION + FEED_FORWARD]),
    "dense_fsdp4": (DENSE, {"fsdp": 4}, [ATTENTION + FEED_FORWARD]),
    "lfm2_layout": (LFM2, {"data": 1}, [
        CONV + FEED_FORWARD, ATTENTION, CONV, CONV, CONV]),
    "dsv2_layout": (DSV2, {"data": 1}, [
        LATENT + FEED_FORWARD, LATENT + SHARED]),
}


def parent_weights(blk, kind, dt, sliced):
    """The parent's block: its sites read `y @ blk[name].astype(dt)` from
    the float32 leaves, and the cast, the slice of the stack and whatever
    takes the gradient are left for the compiler to fuse into the matmuls."""
    return blk


def tiny(keys, **over):
    return TransformerConfig(
        **{**keys, "dtype": jnp.bfloat16, "remat": True,
           "attention_impl": "xla", **over})


def loss_and_gradients(cfg, axes):
    mesh = make_mesh(axes, devices=jax.devices()[:max(axes.values())])
    _, _, shardings = make_train_step(cfg, mesh, optax.adamw(1e-3))
    params = jax.device_put(
        transformer_init(jax.random.PRNGKey(0), cfg), shardings["params"])
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    batch = jax.device_put(
        {"tokens": ids[:, :-1], "targets": ids[:, 1:]}, shardings["tokens"])
    bias = {}
    if cfg.expert_bias:
        bias["expert_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(5), expert_bias_init(cfg).shape)

    def loss(params):
        return transformer_loss_and_readings(
            params, batch, cfg, mesh=mesh, **bias)[0]

    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("case", list(CASES))
def test_loss_and_gradients_are_the_parent_formulation_s_bit_for_bit(
        case, monkeypatch):
    keys, axes, _ = CASES[case]
    cfg = tiny(keys)
    loss, grads = loss_and_gradients(cfg, axes)
    monkeypatch.setattr(model, "_own_weights", parent_weights)
    parent_loss, parent_grads = loss_and_gradients(cfg, axes)
    assert jnp.isfinite(loss) and loss == parent_loss
    paths = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, grad), parent in zip(paths, jax.tree.leaves(parent_grads)):
        assert grad.dtype == jnp.float32 and bool(jnp.any(grad != 0)), path
        assert bool(jnp.all(grad == parent)), jax.tree_util.keystr(path)


def barriers(jaxpr):
    """`optimization_barrier` equations of a jaxpr, nested ones included."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "optimization_barrier"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += barriers(sub)
    return count


@pytest.mark.parametrize("case", list(CASES))
def test_every_plain_matmul_of_a_block_has_its_buffers(case):
    """On the gradient of every matmul weight a barrier; in a segment of one
    period one on the weight too, in the forward pass and again in the
    rematerialised one (a scan's body is traced once); none on the routed
    experts', the norms' or the router's leaves."""
    keys, _, want = CASES[case]
    cfg = tiny(keys)
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    trees = [blk for blks in model._segment_trees(params["blocks"])
             for blk in blks]
    kinds = [kind for seg in model.segments(cfg) for kind in seg.layout]
    assert [set(own_buffer_weights(blk, kind))
            for blk, kind in zip(trees, kinds)] == [
        set(names) for names in want]
    periods = [blk["mlp_norm"].shape[0] for blk in trees]
    assert sum(periods) == cfg.n_layers
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    bias = ({"expert_bias": expert_bias_init(cfg)} if cfg.expert_bias else {})

    def hidden(params, tokens):
        return model.transformer_hidden(params, tokens, cfg, **bias)

    sites = sum(len(names) for names in want)
    whole = sum(len(names) for n, names in zip(periods, want) if n == 1)
    assert barriers(jax.make_jaxpr(hidden)(params, tokens).jaxpr) == whole
    backward = jax.make_jaxpr(jax.grad(
        lambda p, t: hidden(p, t).astype(jnp.float32).sum()))(params, tokens)
    assert barriers(backward.jaxpr) == sites + 2 * whole
    buffers, their_bytes, widest = own_buffers(params["blocks"], cfg)
    each = [2 if n == 1 else 1 for n in periods]
    assert buffers == sum(
        e * n * len(names) for e, n, names in zip(each, periods, want))
    sizes = [2 * sum(blk[name].size // n for name in names)
             for blk, n, names in zip(trees, periods, want)]
    assert their_bytes == sum(
        e * n * size for e, n, size in zip(each, periods, sizes))
    assert widest == max(sizes)


def test_the_step_logs_and_counts_its_own_buffers_once_a_trace(caplog):
    cfg = tiny(DENSE)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init_state, step, _ = make_train_step(cfg, mesh, optax.adamw(1e-3))
    state = init_state(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size)
    batch = {"tokens": ids[:, :-1], "targets": ids[:, 1:]}
    before = tracing.counters()
    with caplog.at_level(logging.INFO, logger="ray_tpu.models.transformer"):
        state, _ = step(state, batch)
        step(state, batch)  # the same program: traced, logged, counted once
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("train step")]
    buffers, their_bytes, _ = own_buffers(state["params"]["blocks"], cfg)
    assert (buffers, their_bytes) == (21, 184320)
    assert lines == [
        "train step under remat keeps nothing: 0 bytes a device beside the "
        "blocks' inputs (64 tokens a device, state 1307912 bytes, bytes_limit "
        "None); its blocks' weight matmuls read and write 21 buffers of their "
        "own, 184320 bytes in bfloat16 over 3 layers (61440 the widest "
        "layer's weights)"]
    after = tracing.counters()
    assert after["train.own_buffers"] - before.get(
        "train.own_buffers", 0) == buffers
    assert after["train.own_buffer_bytes"] - before.get(
        "train.own_buffer_bytes", 0) == their_bytes
