"""The two tables of `ray_tpu/models/transformer.py` (`_OPERATORS`,
`_FEED_FORWARDS`): what a record says of its kind in one place agrees with
what it says in the others, at tiny widths on the CPU, and a name that
neither table has is refused."""

import importlib.util
import inspect
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as model
from ray_tpu.models.transformer import LayerKind

L = 2
BASE = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
            d_ff=48, max_seq_len=32, attention_impl="xla")
ROUTED = dict(n_experts=4, experts_per_token=2, n_shared_experts=1)
MAMBA = dict(mamba_heads=4, mamba_head_dim=8, ssm_state=8, ssm_groups=2,
             ssd_chunk=8)
KDA = dict(kda_heads=4, kda_head_dim=8, kda_gate_rank=4, kda_chunk=16)
MAMBA1 = dict(mamba1_inner=64, mamba1_state=8, mamba1_dt_rank=4, scan_chunk=8,
              layer_norm=True)
DIFF = dict(attn_bias=True, layer_norm=True, rope=False, sliding_window=4)
# two windows of 8 in the 16 tokens a case is traced with
EVA = dict(n_kv_heads=4, eva_window=8, eva_chunk=4, norm_unit_offset=True)
# {case: (the table, the record's name, the configuration's keys, the names
# its forward makes: None for all the record has)}. The case of a record's
# own name turns on every leaf it can have.
CASES = {
    "full_attention": ("op", "full_attention", dict(
        attn_gate=True, qk_norm="head"), None),
    "full_attention_plain": ("op", "full_attention", {}, None),
    "sliding_attention": ("op", "sliding_attention", dict(
        attn_gate=True, qk_norm=True, sliding_window=4, n_heads_sliding=8,
        rope_theta_sliding=1e4), None),
    "sparse_attention": ("op", "sparse_attention", dict(
        attn_gate=True, qk_norm="head", index_heads=2, index_head_dim=8,
        index_topk=4), None),
    "latent_attention": ("op", "latent_attention", dict(
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8), None),
    "conv": ("op", "conv", {}, None),
    "mamba2": ("op", "mamba2", MAMBA, None),
    "kda": ("op", "kda", dict(KDA), None),
    "kda_share": ("op", "kda", dict(KDA, heads_held=(2, 2)), None),
    "full_attention_share": ("op", "full_attention", dict(
        attn_gate="elementwise", heads_held=(2, 2)), None),
    "mamba1": ("op", "mamba1", MAMBA1, None),
    "mamba1_emit": ("op", "mamba1_emit", dict(MAMBA1, layer_norm=False),
                    None),
    "diff_attention": ("op", "diff_attention", DIFF, None),
    "sliding_diff_attention": ("op", "sliding_diff_attention", DIFF, None),
    "diff_attention_emit": ("op", "diff_attention_emit", dict(
        DIFF, attn_bias=False), None),
    "cross_diff_attention": ("op", "cross_diff_attention", DIFF, None),
    "gmu": ("op", "gmu", MAMBA1, None),
    "eva_attention": ("op", "eva_attention", EVA, None),
    # the 16 rows a case is traced with are two halves of two blocks of 4
    "block_diffusion_attention": ("op", "block_diffusion_attention", dict(
        qk_norm="head", objective="block_diffusion", diffusion_block=4,
        mask_token_id=63), None),
    "dense_ff": ("ff", "dense_ff", {}, None),
    "dense_ff_unit_offset": ("ff", "dense_ff", dict(norm_unit_offset=True),
                             None),
    "dense_ff_layer_norm": ("ff", "dense_ff", dict(layer_norm=True), None),
    "dense_ff_ungated": ("ff", "dense_ff", dict(ff_activation="relu2"),
                         {"mlp_up"}),
    "dense_ff_before_routed": ("ff", "dense_ff", dict(
        ROUTED, d_ff_dense=64), None),
    "routed_ff": ("ff", "routed_ff", ROUTED, None),
    # a share has no names of its own; the shared expert's product has
    "routed_ff_share_ungated": ("ff", "routed_ff", dict(
        ROUTED, ff_activation="relu2", experts_held=(1, 2)), {"shared_up"}),
    "routed_ff_alone": ("ff", "routed_ff", dict(
        ROUTED, n_shared_experts=0), {"moe_slots", "moe_gate", "moe_up"}),
}


def of_case(case):
    table, name, keys, made = CASES[case]
    cfg = TransformerConfig(**{**BASE, **keys})
    if table == "op":
        return model._OPERATORS[name], LayerKind(name, False, False), cfg, made
    return (model._FEED_FORWARDS[name],
            LayerKind(None, name == "routed_ff", True), cfg, made)


def beside_the_stream(record, cfg, rows=2, tokens=16):
    """What `_block` takes of a layer that reads: zeros of what its record
    `reads`, at the widths the emitting records state (`attn_kv` as the
    paired keys and the values a pair wide), and a depth."""
    beside = {}
    if record.reads_depth:
        beside["depth"] = jnp.float32(3)
    widths = {name: width for other in model._RECORDS
              for name, width in other.carried(cfg).items()}
    shared = {}
    for name in record.reads:
        assert widths[name] > 0
        if name == "attn_kv":
            hk, dh = cfg.kv_heads, cfg.head_dim
            assert widths[name] == 2 * hk * dh
            shared[name] = (jnp.zeros((rows, tokens, hk, dh), cfg.dtype),
                            jnp.zeros((rows, tokens, hk // 2, 2 * dh),
                                      cfg.dtype))
        else:
            shared[name] = jnp.zeros((rows, tokens, widths[name]), cfg.dtype)
    if shared:
        beside["shared"] = shared
    return beside


def names_made(jaxpr):
    """The `checkpoint_name`s of a jaxpr, nested ones included."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= names_made(sub)
    return found


def test_the_tables_are_what_the_configuration_may_name():
    assert set(model._OPERATORS) == {
        "full_attention", "sliding_attention", "sparse_attention",
        "latent_attention", "conv", "mamba2", "kda", "mamba1",
        "mamba1_emit", "diff_attention", "sliding_diff_attention",
        "diff_attention_emit", "cross_diff_attention", "gmu",
        "eva_attention", "block_diffusion_attention"}
    assert set(model._FEED_FORWARDS) == {"dense_ff", "routed_ff"}
    assert {record for _, record, _, _ in CASES.values()} == (
        set(model._OPERATORS) | set(model._FEED_FORWARDS))


@pytest.mark.parametrize("case", list(CASES))
def test_a_record_agrees_with_itself(case):
    record, kind, cfg, made = of_case(case)
    assert model._sublayers(kind) == (record,)
    leaves = record.init(jax.random.PRNGKey(0), cfg, L)
    axes = record.axes(cfg)
    # the leaves `init` makes are exactly the keys of its axes
    assert set(leaves) == set(axes)
    for name, leaf in leaves.items():
        assert leaf.dtype == jnp.float32 and leaf.shape[0] == L, name
        assert axes[name][0] == "layers" and len(axes[name]) == leaf.ndim, name
    assert leaves.keys() == model._blocks_init(
        jax.random.PRNGKey(0), cfg, kind, L).keys()
    assert axes == model._block_axes(cfg, kind)
    # its matmul weights are among them, and no leaf is both kinds of weight
    weights = (*record.matmuls, *record.moe_weights)
    assert len(set(weights)) == len(weights)
    if case in (*model._OPERATORS, *model._FEED_FORWARDS):
        assert set(weights) <= set(leaves)
    held = [name for name in weights if name in leaves]
    assert model.own_buffer_weights(leaves, kind) == tuple(
        name for name in record.matmuls if name in leaves)
    # its stated parameters are its matmul leaves' sizes a layer
    assert record.params(cfg) == sum(leaves[name].size // L for name in held)
    assert model._layer_widths(cfg, kind) == (
        record.widths(cfg), record.params(cfg))
    # every name it says it keeps is ranked, and its forward makes it
    widths = record.widths(cfg)
    assert set(widths) <= set(record.names) <= set(model._SAVE_ORDER)
    assert all(width > 0 for width in widths.values())
    assert set(widths) == (set(record.names) if made is None else made)
    blk = jax.tree.map(lambda leaf: leaf[0], leaves)
    x = jnp.zeros((2, 16, cfg.d_model), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    beside = beside_the_stream(record, cfg)
    traced = jax.make_jaxpr(lambda x, blk: model._block(
        x, blk, positions, None, cfg, kind, None, 1, keep_ctx=True,
        **beside)[0])(x, blk)
    assert names_made(traced.jaxpr) == set(widths)
    assert traced.out_avals[0].shape == x.shape
    # what it emits has the width it states, a token
    emitted = jax.eval_shape(lambda x, blk: model._block_and_emitted(
        x, blk, positions, None, cfg, kind, None, 1, **beside)[2], x, blk)
    assert set(emitted) == set(record.emits) == set(record.carried(cfg))
    for name, width in record.carried(cfg).items():
        assert sum(a.size for a in jax.tree.leaves(emitted[name])) == (
            2 * 16 * width)
    # what its backward holds and its operations are counts, and an
    # operator alone or a dense feed-forward does twice its parameters
    assert record.holds(cfg) > 0
    matmul, attention = record.flops(cfg, 16)
    assert matmul >= 2 * sum(
        leaves[name].size // L for name in record.matmuls if name in leaves)
    assert (attention > 0) == ("attn_ctx" in record.names)


# the readings a routed layer makes only as a share of the experts, and only
# under an `expert` mesh axis (`tests/test_expert_mesh.py` has that one)
OF_A_SHARE = {"held_slots", "dropped_slots"}
ON_AN_AXIS = {"chip_load"}


@pytest.mark.parametrize("case", list(CASES))
def test_a_record_states_the_readings_its_forward_makes(case):
    record, kind, cfg, _ = of_case(case)
    leaves = record.init(jax.random.PRNGKey(0), cfg, L)
    blk = jax.tree.map(lambda leaf: leaf[0], leaves)
    x = jnp.zeros((2, 16, cfg.d_model), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    made = jax.eval_shape(lambda x, blk: model._block(
        x, blk, positions, None, cfg, kind, None, 1,
        **beside_the_stream(record, cfg))[1], x, blk)
    stated = {r.name: r for r in record.readings}
    assert len(stated) == len(record.readings)
    if not stated:
        assert made is None
        return
    own = {name for name, r in stated.items() if r.of is None}
    share = cfg.held[1] < cfg.n_experts
    assert set(made) == own - ON_AN_AXIS - (set() if share else OF_A_SHARE)
    for r in record.readings:
        assert r.over_layers in model._OVER_LAYERS
        assert r.adds in (None, True) or isinstance(
            getattr(cfg, r.adds), float)
        assert r.of is None or r.of in own
        assert (r.name in model._STEP_READINGS) == r.step
    # over L layers, as the loss joins them: a mean, a least or a most is
    # one number, the others keep the layer axis
    layers = jax.tree.map(
        lambda a: jnp.ones((L, *a.shape), a.dtype), dict(made))
    _, settled = model._settled(jnp.float32(0.0), layers, cfg)
    assert set(settled) >= set(made)
    for name, value in settled.items():
        if stated[name].over_layers in ("mean", "min", "max",
                                        "sum under seq_aux"):
            assert value.shape == ()
        else:
            assert value.shape[0] == L


def test_the_loss_has_what_the_records_say_their_readings_add():
    """A record's terms at the configuration's coefficients, the indexers'
    at 1; with `seq_aux` the balance loss is summed over the layers."""
    routed = {"aux_loss": jnp.array([2.0, 4.0]), "z_loss": jnp.array([1.0, 3.0]),
              "chip_load": jnp.array([[1, 3], [2, 2]], jnp.int32)}
    cfg = TransformerConfig(**{**BASE, **ROUTED, "router_aux_loss_coef": 0.5,
                               "router_z_loss_coef": 0.25})
    loss, settled = model._settled(jnp.float32(1.0), routed, cfg)
    assert float(loss) == 1.0 + 0.5 * 3.0 + 0.25 * 2.0
    assert float(settled["aux_loss"]) == 3.0 and float(settled["z_loss"]) == 2.0
    assert settled["chip_load_max_over_mean"].tolist() == [1.5, 1.0]
    assert settled["chip_load"].tolist() == [[1, 3], [2, 2]]
    summed = TransformerConfig(**{**cfg.__dict__, "seq_aux": True})
    assert float(model._settled(jnp.float32(1.0), routed, summed)[0]) == (
        1.0 + 0.5 * 6.0 + 0.25 * 2.0)
    off = TransformerConfig(**{**cfg.__dict__, "router_aux_loss_coef": 0.0,
                               "router_z_loss_coef": 0.0})
    assert float(model._settled(jnp.float32(1.0), routed, off)[0]) == 1.0
    indexed = {"index_loss": jnp.array([0.5, 1.5]),
               "index_keys_min_gap": jnp.array([0, -1]),
               "index_keys_max_gap": jnp.array([0, 2])}
    loss, settled = model._settled(jnp.float32(1.0), indexed, cfg)
    assert float(loss) == 2.0
    assert (int(settled["index_keys_min_gap"]),
            int(settled["index_keys_max_gap"])) == (-1, 2)
    # what the step reports: what the records state and the exit loss's
    assert set(model._STEP_READINGS) == {
        "aux_loss", "z_loss", "expert_load", "held_slots", "dropped_slots",
        "chip_load", "chip_load_max_over_mean", "index_loss",
        "index_keys_min_gap", "index_keys_max_gap", "kda_log_decay_min",
        "kda_beta_mean", "diff_lambda", "eva_remote_mass",
        "eva_chunk_entropy", "ut_pass_loss", "exit_p_mean", "exit_entropy",
        "diffusion_tokens", "diffusion_masked_tokens", "diffusion_weight_sum",
        "diffusion_rows"}
    assert len(set(model._STEP_READINGS)) == len(model._STEP_READINGS)


@pytest.mark.parametrize("field,names,known", [
    ("layer_types", ("sliding_atention",), "sliding_attention"),
    ("layer_types", ("dense_ff",), "full_attention"),
    ("sublayer_types", ("mamba",), "mamba2"),
    ("sublayer_types", ("routed",), "routed_ff"),
])
def test_a_name_no_table_has_is_refused(field, names, known):
    """At the parent a misspelt kind was full attention, built, trained and
    counted without a word."""
    cfg = TransformerConfig(**{**BASE, field: names})
    with pytest.raises(ValueError, match=names[0]) as refused:
        cfg.layers
    assert field in str(refused.value) and known in str(refused.value)
    with pytest.raises(ValueError, match=names[0]):
        model.transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match=names[0]):
        model.flops_per_token(cfg, 16)


def test_a_name_without_a_rank_stops_the_import():
    """`_SAVE_ORDER` is one decision; a name a record makes that it does not
    rank would never be kept, in silence."""
    source = inspect.getsource(model)
    assert source.count('    "ssd_out",') == 1
    spec = importlib.util.spec_from_file_location(
        "transformer_without_a_rank", model.__file__)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # `dataclass` looks its module up
    try:
        with pytest.raises(ValueError, match="ssd_out"):
            exec(compile(source.replace('    "ssd_out",', ""), model.__file__,
                         "exec"), module.__dict__)
    finally:
        del sys.modules[spec.name]
