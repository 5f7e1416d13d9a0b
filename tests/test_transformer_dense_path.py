"""The dense decoder is the program it was before routed experts and QK-norm
came to `models/transformer.py` (PR 27): its parameter tree key by key and
shape by shape, its seeded weights, its loss and its operation count are the
values recorded from the parent commit, its step reports what it reported,
and nothing of the routed path stands in its jaxpr."""

import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import (TransformerConfig, make_train_step,
                            transformer_init, transformer_loss)
from ray_tpu.models.transformer import flops_per_token
from ray_tpu.parallel import make_mesh

BLOCKS = {"attn_norm": (2, 32), "mlp_norm": (2, 32), "w_down": (2, 64, 32),
          "w_gate": (2, 32, 64), "w_up": (2, 32, 64), "wo": (2, 32, 32),
          "wq": (2, 32, 32)}
# recorded on commit aa69966 (the parent of PR 27), CPU, float32
RECORDED = {
    "gqa_untied": dict(
        config=dict(n_kv_heads=2, tied_embeddings=False), loss=5.516087055206299,
        flops=141696.0,
        shapes={**{"blocks/" + k: v for k, v in BLOCKS.items()},
                "blocks/wk": (2, 32, 16), "blocks/wv": (2, 32, 16),
                "embed": (128, 32), "final_norm": (32,), "unembed": (32, 128)}),
    "mha_tied": dict(
        config=dict(), loss=4.882535934448242, flops=153984.0,
        shapes={**{"blocks/" + k: v for k, v in BLOCKS.items()},
                "blocks/wk": (2, 32, 32), "blocks/wv": (2, 32, 32),
                "embed": (128, 32), "final_norm": (32,)}),
}


def dense(case, **over):
    return TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=16, dtype=jnp.float32, attention_impl="xla",
        **{**RECORDED[case]["config"], **over})


def batch():
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 17), 0, 128)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


@pytest.mark.parametrize("case", sorted(RECORDED))
def test_parameter_tree_is_what_it_was(case):
    params = transformer_init(jax.random.PRNGKey(3), dense(case))
    shapes = {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert shapes == RECORDED[case]["shapes"]
    # the same keys give the same weights
    assert float(params["blocks"]["wq"].sum()) == pytest.approx(
        3.0135695934295654, rel=1e-6)
    assert float(params["blocks"]["w_down"].sum()) == pytest.approx(
        -0.0004105567932128906, rel=1e-4)


@pytest.mark.parametrize("case", sorted(RECORDED))
def test_loss_and_operation_count_are_what_they_were(case):
    cfg = dense(case)
    params = transformer_init(jax.random.PRNGKey(3), cfg)
    assert float(transformer_loss(params, batch(), cfg)) == pytest.approx(
        RECORDED[case]["loss"], rel=1e-6)
    assert flops_per_token(cfg, 16) == RECORDED[case]["flops"]


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


@pytest.mark.parametrize("remat", [False, True])
def test_dense_jaxpr_holds_nothing_of_the_routed_path(remat):
    """Read from the program alone, its scopes and its primitives: the
    lowered text's locations name whichever test traced a cached function
    first."""
    cfg = dense("gqa_untied", remat=remat)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    eqns = list(equations(step.trace(state, batch).jaxpr.jaxpr))
    scopes = {part for eqn in eqns
              for part in re.split(r"[/()]", str(eqn.source_info.name_stack))}
    assert {"mlp", "attn_qkv", "optimizer"} <= scopes  # the walk reaches them
    assert not {s for s in scopes if s.startswith("moe_") or s == "qk_norm"}
    primitives = {eqn.primitive.name for eqn in eqns}
    assert "dot_general" in primitives
    assert not primitives & {"ragged_dot", "ragged_dot_general", "top_k", "sort"}
    out = jax.eval_shape(step, state, batch)[1]
    assert set(out) == {"loss", "grad_norm"}
