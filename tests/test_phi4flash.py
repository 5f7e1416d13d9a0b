"""A stack whose second half reads what its first half made
(`models/transformer.py`: `Sublayer.emits` and `reads`, the walk that
carries the values beside the stream) at a small size on the CPU: 12 layers,
so that the layers before the emitters, the emitters and the readers are
three segments and each carried value has two readers. Loss and every
gradient leaf against the plain reference, with and without `remat`; the
cut's six layers against the same layers of the deeper stack by hand; what
`segments` finds at published depth; and what is refused."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import phi4flash as reference
from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as tr
from ray_tpu.models.transformer import LayerKind, Segment


def kinds_of(n_layers):
    """The published stack's kinds at a depth of `n_layers`."""
    half = n_layers // 2
    return tuple(
        ("mamba1" if l % 2 == 0 else "sliding_diff_attention") if l < half
        else "mamba1_emit" if l == half
        else "diff_attention_emit" if l == half + 1
        else "gmu" if l % 2 == 0 else "cross_diff_attention"
        for l in range(n_layers))


# one of each kind, as the benchmark's cell cuts the model
CUT = ("mamba1", "sliding_diff_attention", "mamba1_emit",
       "diff_attention_emit", "gmu", "cross_diff_attention")


def small(n_layers=12, **over):
    fields = dict(
        vocab_size=128, d_model=64, n_layers=n_layers, n_heads=8,
        n_kv_heads=4, d_head=16, d_ff=96, max_seq_len=64,
        layer_types=kinds_of(n_layers), sliding_window=8, mamba1_inner=128,
        mamba1_state=16, mamba1_dt_rank=4, scan_chunk=16, layer_norm=True,
        attn_bias=True, rope=False, norm_eps=1e-5, dtype=jnp.float32,
        attention_impl="xla")
    return TransformerConfig(**{**fields, **over})


def reference_config(cfg):
    return dict(
        layer_types=list(cfg.layer_types), layer_depths=list(cfg.depths),
        n_heads=cfg.n_heads, n_kv_heads=cfg.kv_heads, d_head=cfg.head_dim,
        sliding_window=cfg.sliding_window, mamba1_state=cfg.mamba1_state,
        norm_eps=cfg.norm_eps)


def seeded(cfg, seed=0):
    """Parameters with every leaf that starts at zero or one moved off it,
    so that a bias or a scale left out shows."""
    params = tr.transformer_init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if float(jnp.ptp(leaf)) == 0.0 else leaf
        for leaf, key in zip(leaves, keys)])


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 41), 0, 128)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    (loss, lams), grads = jax.jit(jax.value_and_grad(
        lambda p: reference.forward(p, batch, reference_config(cfg)),
        has_aux=True))(params)
    return cfg, params, batch, loss, lams, grads


def test_the_three_runs_are_scanned(model):
    cfg = model[0]
    segs = tr.segments(cfg)
    assert [(len(s.layout), s.periods) for s in segs] == [
        (2, 3), (2, 1), (2, 2)]
    assert [k.op for k in segs[1].layout] == [
        "mamba1_emit", "diff_attention_emit"]
    assert [k.op for k in segs[2].layout] == ["gmu", "cross_diff_attention"]
    params = jax.eval_shape(
        lambda: tr.transformer_init(jax.random.PRNGKey(0), cfg))
    assert [[tr._periods(t) for t in seg] for seg in params["blocks"]] == [
        [3, 3], [1, 1], [2, 2]]


def test_published_depth_is_three_scanned_runs():
    cfg = small(32)
    dense = lambda op: LayerKind(op, False)  # noqa: E731
    assert tr.segments(cfg) == [
        Segment((dense("mamba1"), dense("sliding_diff_attention")), 8),
        Segment((dense("mamba1_emit"), dense("diff_attention_emit")), 1),
        Segment((dense("gmu"), dense("cross_diff_attention")), 7)]
    # the cut: one of each kind, one period of six
    cut = small(6, layer_types=CUT, layer_depths=(0, 1, 16, 17, 18, 19))
    assert [(len(s.layout), s.periods) for s in tr.segments(cut)] == [(6, 1)]
    # a stack without carried values keeps the segments it had
    plain = small(6, layer_types=("mamba1", "mamba1", "diff_attention",
                                  "mamba1", "diff_attention",
                                  "diff_attention"))
    assert [(len(s.layout), s.periods) for s in tr.segments(plain)] == [(6, 1)]


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_leaf_are_the_references(model, remat):
    cfg, params, batch, loss, lams, grads = model
    cfg = dataclasses.replace(cfg, remat=remat)
    (ours, readings), ours_grads = jax.jit(jax.value_and_grad(
        lambda p: tr.transformer_loss_and_readings(p, batch, cfg),
        has_aux=True))(params)
    assert float(ours) == pytest.approx(float(loss), rel=2e-6)
    np.testing.assert_allclose(readings["diff_lambda"], lams, rtol=1e-6)
    assert readings["diff_lambda"].shape == (6,)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, theirs), mine in zip(flat, jax.tree.leaves(ours_grads)):
        scale = float(jnp.abs(theirs).max())
        # a key's bias moves every score of a query alike: its gradient
        # is rounding, on both sides
        assert scale > (0 if path[-1].key == "bk" else 1e-6), path
        np.testing.assert_allclose(
            mine, theirs, rtol=2e-4, atol=2e-5 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_gradients_through_the_scan_s_kernels_are_the_references(
        model, monkeypatch):
    """The Mamba-1 layers' scan by `selective_scan_fwd` and
    `selective_scan_bwd` in interpret mode (on the chip `attention_impl`
    resolves to them; here the mixer's call is steered): 128 channels, 16
    states and chunks of 16 tile, so the four layers' eight kernel calls are
    in the program, and the loss and every gradient leaf are the
    reference's as closely as through `jax.numpy`."""
    from ray_tpu.ops import selective_scan as scan_lib
    from test_selective_scan_kernel import _equations

    cfg, params, batch, loss, _, grads = model
    assert scan_lib.selective_scan_untiled(
        cfg.scan_chunk, cfg.mamba1_state, cfg.mamba1_inner, 4) is None
    monkeypatch.setattr(tr, "selective_scan", lambda *a, **kw: (
        scan_lib.selective_scan(*a, **{**kw, "interpret": True})))
    fn = jax.value_and_grad(
        lambda p: tr.transformer_loss_and_readings(p, batch, cfg)[0])
    called = [e.params["name"]
              for e in _equations(jax.make_jaxpr(fn)(params).jaxpr)
              if e.primitive.name == "pallas_call"]
    assert sorted(set(called)) == ["selective_scan_bwd", "selective_scan_fwd"]
    ours, ours_grads = jax.jit(fn)(params)
    assert float(ours) == pytest.approx(float(loss), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, theirs), mine in zip(flat, jax.tree.leaves(ours_grads)):
        np.testing.assert_allclose(
            mine, theirs, rtol=2e-4,
            atol=2e-5 * float(jnp.abs(theirs).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_kept_names_change_nothing(model):
    """Under `remat` with every name kept: the same loss and gradients, and
    the scan's forward is not made again (`scan_out` names its output and
    its chunks' entering states)."""
    cfg, params, batch, loss, _, grads = model
    cfg = dataclasses.replace(cfg, remat=True)
    names = ("attn_res", "attn_qkv", "scan_out", "mamba1_in", "gmu_in",
             "mlp_gate", "mlp_up")

    def step(p):
        return tr.transformer_loss_and_readings(
            p, batch, cfg, saved_names=names)[0]

    ours, ours_grads = jax.jit(jax.value_and_grad(step))(params)
    assert float(ours) == pytest.approx(float(loss), rel=2e-6)
    for theirs, mine in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(ours_grads)):
        np.testing.assert_allclose(
            mine, theirs, rtol=2e-4,
            atol=2e-5 * float(jnp.abs(theirs).max()) + 1e-7)


def test_the_cut_is_the_same_layers_of_the_deeper_stack(model):
    """Six layers cut out of the 12-layer stack (its layers 0, 1, 6, 7, 8,
    9, with their depths) compute what those layers of the deeper stack
    compute, taken one by one by hand."""
    deep, params, batch = model[:3]
    held = (0, 1, 6, 7, 8, 9)
    cut = small(6, layer_types=tuple(deep.layer_types[l] for l in held),
                layer_depths=held)

    def layer_of(l):
        """(kind, weights) of the deeper stack's layer `l`."""
        first = 0
        for seg, trees in zip(tr.segments(deep), params["blocks"]):
            n = len(seg.layout) * seg.periods
            if l < first + n:
                period, i = divmod(l - first, len(seg.layout))
                return seg.layout[i], jax.tree.map(
                    lambda a: a[period], trees[i])
            first += n

    # the cut's tree: one segment of six layers, one period each
    cut_params = {**params, "blocks": [[
        jax.tree.map(lambda a: a[None], layer_of(l)[1]) for l in held]]}
    shapes = jax.eval_shape(
        lambda: tr.transformer_init(jax.random.PRNGKey(0), cut))
    assert jax.tree.map(lambda a: a.shape, cut_params) == jax.tree.map(
        lambda a: a.shape, shapes)
    ours = tr.transformer_hidden(cut_params, batch["tokens"], cut)

    x = params["embed"][batch["tokens"]]
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    shared = {}
    for l in held:
        kind, blk = layer_of(l)
        x, _, made = tr._block_and_emitted(
            x, blk, positions, None, deep, kind, None, 1, shared=shared,
            depth=jnp.float32(l))
        shared.update(made)
    by_hand = tr._layer_norm(x, params["final_norm"],
                             params["final_norm_bias"], deep.norm_eps)
    np.testing.assert_allclose(ours, by_hand, rtol=1e-5, atol=1e-5)
    # and the depth is read: at its own indices the cut is another model
    other = tr.transformer_hidden(
        cut_params, batch["tokens"], dataclasses.replace(cut, layer_depths=()))
    assert float(jnp.abs(other - ours).max()) > 1e-3


def test_scanned_readers_take_the_value_in_float32():
    """Several periods of readers: the scan's constant is float32, so the
    sum of their cotangents is; one period takes it as it is."""
    cfg = small(dtype=jnp.bfloat16)
    segs = tr.segments(cfg)
    value = {"scan_memory": jnp.ones((1, 4, 8), jnp.bfloat16),
             "attn_kv": (jnp.ones((1, 4, 2, 2), jnp.bfloat16),) * 2}
    scanned = tr._for_readers(value, segs[2])
    assert set(scanned) == {"scan_memory", "attn_kv"}
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(scanned))
    once = tr._for_readers(value, Segment(segs[2].layout, 1))
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(once))
    assert tr._for_readers(value, segs[0]) == {}
    # the cast stands under a scope of its own in the step's program
    tokens = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda p: tr.transformer_hidden(p, tokens, cfg)).lower(
        jax.eval_shape(lambda: tr.transformer_init(jax.random.PRNGKey(0), cfg))
    ).as_text(debug_info=True)
    assert "shared_emit/convert_element_type" in text
    # the rule prices them so: value and cotangents' sum, float32 if scanned
    assert tr._carried_bytes(cfg, 64) == {
        "scan_memory": 2 * 4 * 128 * 64, "attn_kv": 2 * 4 * 2 * 4 * 16 * 64}
    cut = small(6, layer_types=CUT, dtype=jnp.bfloat16)
    assert tr._carried_bytes(cut, 64) == {
        "scan_memory": 2 * 2 * 128 * 64, "attn_kv": 2 * 2 * 2 * 4 * 16 * 64}
    assert tr._boundary_bytes(cut, 64) == 7 * 64 * 64 * 2 + sum(
        tr._carried_bytes(cut, 64).values())


@pytest.mark.parametrize("types,message", [
    (("gmu", "mamba1_emit"), "reads 'scan_memory', which no layer before"),
    (("mamba1_emit", "mamba1"), "emits 'scan_memory', which no layer after"),
    (("mamba1_emit", "mamba1_emit", "gmu"), "which layer 0 emits already"),
    (("cross_diff_attention", "diff_attention_emit"), "reads 'attn_kv'"),
])
def test_a_stack_that_cannot_be_walked_is_refused(types, message):
    cfg = small(len(types), layer_types=types)
    with pytest.raises(ValueError, match=message):
        cfg.layers
    with pytest.raises(ValueError, match=message):
        tr.transformer_init(jax.random.PRNGKey(0), cfg)


def test_what_is_not_mapped_is_refused_with_a_sentence():
    cfg = small(6, layer_types=CUT)
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda: tr.transformer_init(jax.random.PRNGKey(0), cfg))
    for fields, error, message in [
            (dict(loop_steps=2), NotImplementedError,
             "loop_steps 2 over layers that emit"),
            (dict(heads_held=(0, 2)), ValueError,
             "heads_held .* with the operator 'mamba1'")]:
        with pytest.raises(error, match=message):
            other = dataclasses.replace(cfg, **fields)
            jax.eval_shape(lambda p: tr.transformer_hidden(p, tokens, other),
                           shapes)
    with pytest.raises(NotImplementedError, match="`sequence` axis over "
                       "layers that emit"):
        jax.eval_shape(lambda p: tr.transformer_hidden(
            p, tokens, cfg, seq_axis="sequence", seq_size=2), shapes)
    # a record whose norm is RMSNorm alone does not take `layer_norm`
    with pytest.raises(ValueError, match="layer_norm with a"):
        small(2, layer_types=("full_attention", "full_attention")).layers


def test_every_field_is_off_by_default():
    cfg = TransformerConfig()
    assert (cfg.layer_norm, cfg.attn_bias, cfg.mamba1_inner,
            cfg.layer_depths) == (False, False, 0, ())
    assert cfg.depths == tuple(range(cfg.n_layers))
    params = jax.eval_shape(
        lambda: tr.transformer_init(jax.random.PRNGKey(0), cfg))
    assert "final_norm_bias" not in params
    assert not any("bias" in name for name in params["blocks"])
