"""The routed layer over an `expert` mesh axis, on the CPU's virtual devices:
`_routed_ffn` under `make_mesh({"expert": 4})` against the whole layer on one
device (forward, gradients, readings; a seeded router and one skewed so that
every slot is one chip's), the four shares' partial results against the
uncut plain reference, one train step under the mesh against one device, and
a mesh of one against no mesh. `jax.numpy` paths (`attention_impl="xla"`):
the kernels are `tests/test_moe.py`'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.reference import mellum as reference
from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (
    _routed_ffn, param_shardings, transformer_init)
from ray_tpu.ops import moe
from ray_tpu.parallel import make_mesh
from ray_tpu.parallel import mesh as mesh_lib

WAYS, E, K, D, F, B, T = 4, 8, 2, 32, 16, 4, 32

CFG = TransformerConfig(
    vocab_size=128, d_model=D, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=F,
    max_seq_len=T, n_experts=E, experts_per_token=K, norm_topk_prob=True,
    dtype=jnp.float32, tied_embeddings=False, attention_impl="xla",
    layer_types=("sliding_attention",) * 3 + ("full_attention",),
    sliding_window=8, router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
    remat=True)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"expert": WAYS}, devices=jax.devices()[:WAYS])


@pytest.fixture(autouse=True)
def small_row_tiles(monkeypatch):
    """Buffers of a few rows, so that a skewed load walks several chunks."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


def layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    blk = {"router": jax.random.normal(ks[0], (D, E)) / D ** 0.5,
           "w_gate": jax.random.normal(ks[1], (E, D, F)) / D ** 0.5,
           "w_up": jax.random.normal(ks[2], (E, D, F)) / D ** 0.5,
           "w_down": jax.random.normal(ks[3], (E, F, D)) / F ** 0.5}
    return jax.random.normal(ks[4], (B, T, D)), blk


def on_mesh(mesh, y, blk):
    specs = {name: P() if name == "router" else P("expert") for name in blk}
    return (jax.device_put(y, NamedSharding(mesh, P("expert"))),
            {name: jax.device_put(leaf, NamedSharding(mesh, specs[name]))
             for name, leaf in blk.items()})


# one chip's experts for every slot: a bias no router's logit outweighs
SKEW = jnp.where(jnp.arange(E) < E // WAYS, 1e3, 0.0)


@pytest.mark.parametrize("bias", [None, SKEW], ids=["seeded", "skewed"])
def test_the_layer_over_the_mesh_is_the_whole_layer_on_one_device(mesh, bias):
    y, blk = layer()

    def loss(y, blk, mesh):
        out, readings = _routed_ffn(y, blk, CFG, mesh, bias)
        return (out ** 2).sum() + readings["aux_loss"] + readings["z_loss"], (
            out, readings)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True),
                   static_argnums=2)
    (l_one, (out_one, r_one)), g_one = grad(y, blk, None)
    (l_mesh, (out_mesh, r_mesh)), g_mesh = grad(*on_mesh(mesh, y, blk), mesh)
    assert float(l_mesh) == pytest.approx(float(l_one), rel=1e-5)
    np.testing.assert_allclose(out_mesh, out_one, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_mesh), jax.tree.leaves(g_one)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # the batch's readings are over the mesh's batch, the chips' their own
    for name in ("aux_loss", "z_loss"):
        assert float(r_mesh[name]) == pytest.approx(float(r_one[name]), rel=1e-5)
    np.testing.assert_array_equal(r_mesh["expert_load"], r_one["expert_load"])
    np.testing.assert_array_equal(r_mesh["expert_index"], r_one["expert_index"])
    assert int(r_mesh["expert_load"].sum()) == B * T * K
    assert r_mesh["held_slots"].shape == r_mesh["dropped_slots"].shape == (WAYS,)
    np.testing.assert_array_equal(r_mesh["dropped_slots"], 0)
    np.testing.assert_array_equal(r_mesh["held_slots"], r_mesh["chip_load"])
    np.testing.assert_array_equal(
        r_mesh["chip_load"], r_one["expert_load"].reshape(WAYS, -1).sum(-1))
    if bias is not None:  # every slot on chip 0: several passes there, none
        chunk = moe.held_chunk(B * T * K, E // WAYS, E,  # on the others
                               load_held_even=False, sequences=B)
        np.testing.assert_array_equal(r_mesh["chip_load"], [B * T * K, 0, 0, 0])
        assert B * T * K > 2 * chunk


def plain_layer(y, blk, experts=slice(None)):
    """The reference's layer with the weights of the experts outside
    `experts` at 0, less the residual: float32 `jax.numpy`, every expert on
    every token."""
    config = {"n_experts": E, "experts_per_token": K, "norm_eps": 1e-6}
    w = dict(blk, mlp_norm=jnp.ones((D,)))
    keep = jnp.zeros((E,)).at[experts].set(1.0)
    w = dict(w, w_down=w["w_down"] * keep[:, None, None])
    with jax.default_matmul_precision("highest"):
        return reference.routed_feed_forward(y, w, config)[0] - y


@pytest.fixture(scope="module")
def shares():
    """Every share's partial result, as one chip of four computes it alone
    (`experts_held`, no mesh), on rows that are already normed."""
    y, blk = layer(1)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
    n = E // WAYS
    partial = []
    for c in range(WAYS):
        cfg = dataclasses.replace(CFG, experts_held=(n * c, n))
        held = {name: leaf if name == "router" else leaf[n * c:n * (c + 1)]
                for name, leaf in blk.items()}
        partial.append(_routed_ffn(y, held, cfg)[0])
    return y, blk, partial


@pytest.mark.parametrize("c", range(WAYS))
def test_a_share_is_its_experts_part_of_the_uncut_reference(shares, c):
    y, blk, partial = shares
    n = E // WAYS
    np.testing.assert_allclose(
        partial[c], plain_layer(y, blk, slice(n * c, n * (c + 1))),
        rtol=2e-4, atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_reference(shares, mesh):
    y, blk, partial = shares
    whole = plain_layer(y, blk)
    np.testing.assert_allclose(sum(partial), whole, rtol=2e-4, atol=2e-5)
    # and the mesh's exchange is that sum
    over_mesh = jax.jit(lambda y, blk: _routed_ffn(y, blk, CFG, mesh)[0])(
        *on_mesh(mesh, y, blk))
    np.testing.assert_allclose(over_mesh, whole, rtol=2e-4, atol=2e-5)


def test_a_train_step_under_the_mesh_is_the_step_on_one_device(mesh):
    optimizer = optax.adamw(1e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T + 1), 0, 128)
    outs = {}
    for name, m in (("one", make_mesh({"expert": 1}, jax.devices()[:1])),
                    ("mesh", mesh)):
        init, step, shardings = make_train_step(CFG, m, optimizer)
        state = init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.device_put(tokens, shardings["tokens"])}
        layout = jax.tree.map(lambda x: x.sharding, state)
        if name == "mesh":  # a quarter of the bytes a device, the norms whole
            held = {d.id: 0 for d in m.devices.flat}
            for leaf in jax.tree.leaves(state):
                for shard in leaf.addressable_shards:
                    held[shard.device.id] += shard.data.nbytes
            whole = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
            for n_bytes in held.values():
                assert n_bytes / whole == pytest.approx(0.25, abs=0.02)
        for _ in range(2):
            state, out = step(state, batch)
        assert step._cache_size() == 1  # no second compilation
        for got, want in zip(jax.tree.leaves(
                jax.tree.map(lambda x: x.sharding, state)),
                jax.tree.leaves(layout)):
            assert got.is_equivalent_to(want, 4)
        outs[name] = jax.device_get(out)
    one, over = outs["one"], outs["mesh"]
    assert float(over["loss"]) == pytest.approx(float(one["loss"]), rel=1e-5)
    assert float(over["grad_norm"]) == pytest.approx(
        float(one["grad_norm"]), rel=1e-4)
    np.testing.assert_array_equal(over["expert_load"], one["expert_load"])
    assert over["chip_load"].shape == over["held_slots"].shape == (4, WAYS)
    assert over["chip_load_max_over_mean"].shape == (4,)
    np.testing.assert_array_equal(over["dropped_slots"], 0)
    np.testing.assert_array_equal(
        over["chip_load"].sum(-1), [B * T * K] * 4)
    assert "chip_load" not in one and "held_slots" not in one


def test_the_experts_leaves_lie_whole_experts_a_device(mesh):
    """`experts` takes the axis, so the experts' `embed` stays whole; every
    other matrix is cut on `embed`, and the batch is split over the axis."""
    shardings = param_shardings(mesh, CFG)
    blk = shardings["blocks"][0][0]
    assert blk["w_up"].spec == P(None, "expert", None, None)
    assert blk["w_down"].spec == P(None, "expert", None, None)
    assert blk["router"].spec == P(None, "expert", None)
    assert blk["wq"].spec == P(None, "expert", None)
    assert blk["wo"].spec == P(None, None, "expert")
    assert shardings["unembed"].spec == P("expert", None)
    assert mesh_lib.data_parallel_spec(mesh) == P(("expert",))
    rules = mesh_lib.default_transformer_rules(
        make_mesh({"fsdp": 2, "expert": 2}, jax.devices()[:4]))
    assert rules.spec(("layers", "experts", "embed", "mlp")) == P(
        None, "expert", "fsdp", None)
    # beside `fsdp` the axis cuts the experts alone (no step runs there yet)
    assert rules.spec(("embed", "vocab")) == P("fsdp", None)


@pytest.mark.parametrize("held", [None, (2, 2)], ids=["whole", "share"])
def test_a_mesh_of_one_traces_the_layer_as_no_mesh_does(held):
    """No `shard_map`, no collective: the one-chip cells' layer is the
    program it was."""
    cfg = dataclasses.replace(CFG, experts_held=held)
    y, blk = layer()
    if held:
        blk = {name: leaf if name == "router" else leaf[held[0]:sum(held)]
               for name, leaf in blk.items()}
    texts = [str(jax.make_jaxpr(lambda y, blk: _routed_ffn(y, blk, cfg, m))(
        y, blk)) for m in (None, make_mesh({"expert": 1}, jax.devices()[:1]))]
    assert texts[0] == texts[1]
    for word in ("shard_map", "all_gather", "psum", "reduce_scatter"):
        assert word not in texts[0]


def test_a_second_axis_beside_expert_is_refused():
    both = make_mesh({"fsdp": 2, "expert": 2}, jax.devices()[:4])
    y, blk = layer()
    with pytest.raises(NotImplementedError, match="second"):
        _routed_ffn(y, blk, CFG, both)
    assert mesh_lib.expert_axis(None) is None
    assert mesh_lib.expert_axis(make_mesh({"fsdp": 4}, jax.devices()[:4])) is None


def test_the_step_s_working_set_prices_the_exchange():
    """Under an expert axis a device's routed layer is a share that sees
    all the axis's tokens: the gathered rows and the partial results are
    held, the whole layer's names are not made."""
    tokens, params = 4096, 10**6
    alone = transformer._terms(CFG, tokens, params)
    over = transformer._terms(CFG, tokens, params, WAYS)
    assert over.at_once > alone.at_once
    assert alone.exchange == 0 < over.exchange
    assert "moe_up" in alone.names and "moe_up" not in over.names
