"""`ray_tpu/ops/kda.py`: the chunked form of Kimi Delta Attention against
the recurrence taken token by token, outputs, final state and every
gradient, in float32 on the CPU: at lengths that are and are not multiples
of the chunk, at decays where `exp(-G)` overflows float32 inside a chunk,
with beta on both sides of 1; the state carried across chunks and across
calls; and the mixer's taps not reading past a sequence's start. The Pallas
kernels have `tests/test_kda_kernel.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig
from ray_tpu.models import transformer as model
from ray_tpu.ops import kda as kda_lib

# one program a shape, not one an operation
kda = jax.jit(kda_lib.kda, static_argnames=("chunk", "scale"))
kda_recurrent = jax.jit(kda_lib.kda_recurrent)


def draw(seed, T, b=1, H=2, dk=16, dv=8, g_floor=-1.0, beta_scale=2.0):
    """q and k of unit length, v, log decays uniform in [g_floor, 0] a
    channel, beta = 2 sigmoid(normal x beta_scale): on both sides of 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, T, H, dk)))
    k = unit(jax.random.normal(ks[1], (b, T, H, dk)))
    v = jax.random.normal(ks[2], (b, T, H, dv))
    g = jax.random.uniform(ks[3], (b, T, H, dk), minval=g_floor, maxval=0.0)
    beta = 2.0 * jax.nn.sigmoid(
        beta_scale * jax.random.normal(ks[4], (b, T, H)))
    return q, k, v, g, beta


def close(a, b, tol=2e-5):
    scale = float(jnp.abs(b).max()) + 1e-30
    return float(jnp.abs(a - b).max()) / scale < tol


# (tokens, chunk, the decay's floor a token): whole chunks, a ragged tail,
# fewer tokens than a sub-chunk; decays that reach -300 in a chunk (exp(300)
# is not a float32) and decays of nearly nothing. Few distinct shapes: a
# shape is a program to compile, a decay is data
SHAPES = [(128, 64, -0.1), (128, 64, -8.0), (128, 64, -1e-3),
          (100, 32, -1.0), (100, 32, -12.0), (7, 64, -2.0), (7, 64, -20.0)]


@pytest.mark.parametrize("T,chunk,g_floor", SHAPES)
def test_chunked_outputs_and_state_are_the_recurrence(T, chunk, g_floor):
    args = draw(T, T, g_floor=g_floor)
    o, last, reach = kda(*args, chunk=chunk)
    o_ref, last_ref = kda_recurrent(*args)
    assert o.shape == o_ref.shape and o.dtype == args[2].dtype
    assert close(o, o_ref) and close(last, last_ref)
    assert bool(jnp.isfinite(o).all()) and float(reach) <= 0.0


@pytest.mark.parametrize("T,chunk,g_floor", SHAPES[1:5])
def test_chunked_gradients_are_the_recurrence(T, chunk, g_floor):
    args = draw(1000 + T, T, g_floor=g_floor)
    weights = jax.random.normal(jax.random.PRNGKey(T), (1, T, 2, 8))

    def loss_of(fn):
        def loss(*a):
            o, last = fn(*a)[:2]
            return jnp.sum(o * weights) + jnp.sum(last ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)

    ours = loss_of(lambda *a: kda(*a, chunk=chunk))
    theirs = loss_of(kda_recurrent)
    for name, a, b in zip("q k v g beta".split(), ours, theirs):
        assert bool(jnp.isfinite(a).all()), name
        assert close(a, b, 5e-5), name


def test_a_naive_exponent_overflows_where_the_chunked_form_does_not():
    """At these decays the factor `exp(-G_j)` the textbook form needs is
    not a float32, inside one chunk; the reading says how far they reach."""
    q, k, v, g, beta = draw(5, 128, g_floor=-8.0)
    G = jnp.cumsum(g.reshape(1, 2, 64, 2, 16), axis=2)
    assert not bool(jnp.isfinite(jnp.exp(-G)).all())
    o, _, reach = kda(q, k, v, g, beta, chunk=64)
    assert bool(jnp.isfinite(o).all())
    assert float(reach) == pytest.approx(float(G[:, :, -1].min()))
    assert float(reach) < -88.0  # past float32's largest exponent


@pytest.mark.parametrize("beta_value", [0.25, 1.75])
def test_beta_on_both_sides_of_one(beta_value):
    q, k, v, g, _ = draw(9, 100, g_floor=-0.5)
    beta = jnp.full((1, 100, 2), beta_value)
    o, last, _ = kda(q, k, v, g, beta, chunk=32)
    o_ref, last_ref = kda_recurrent(q, k, v, g, beta)
    assert close(o, o_ref) and close(last, last_ref)


def test_past_one_beta_turns_a_stored_value_round():
    """One key written twice, no decay: the second write sees `v` stored as
    `beta v` and leaves `(2 beta - beta^2) v`; read back at once the first
    time it is `beta v`. With beta 1.5 the error `v - S^T k` changes sign
    (the eigenvalue 1 - beta is negative)."""
    k = jnp.zeros((1, 2, 1, 16)).at[:, :, :, 0].set(1.0)
    v = jnp.ones((1, 2, 1, 8))
    g = jnp.zeros((1, 2, 1, 16))
    beta = jnp.full((1, 2, 1), 1.5)
    o, _, _ = kda(k, k, v, g, beta, chunk=16, scale=1.0)
    np.testing.assert_allclose(o[0, 0, 0], 1.5, atol=1e-6)
    np.testing.assert_allclose(o[0, 1, 0], 2 * 1.5 - 1.5 ** 2, atol=1e-6)


@pytest.mark.parametrize("cut", [64, 17])
def test_the_state_is_carried_across_calls(cut):
    """A sequence in two calls, the second starting from the first's state,
    is the sequence in one: the state crosses chunks and calls alike."""
    args = draw(21, 160, g_floor=-1.0)
    whole, last, _ = kda(*args, chunk=32)
    head = tuple(x[:, :cut] for x in args)
    tail = tuple(x[:, cut:] for x in args)
    first, state, _ = kda(*head, chunk=32)
    second, last_two, _ = kda(*tail, chunk=32, state=state)
    assert close(jnp.concatenate([first, second], axis=1), whole)
    assert close(last_two, last)


def test_the_state_crosses_chunks():
    """A value written in the first chunk is read in the last."""
    q, k, v, g, beta = draw(3, 192, g_floor=-1e-3)
    o, _, _ = kda(q, k, v, g, beta, chunk=64)
    moved, _, _ = kda(q, k, v.at[:, 3].add(1.0), g, beta, chunk=64)
    assert float(jnp.abs(moved - o)[:, 128:].max()) > 1e-4
    assert float(jnp.abs(moved - o)[:, :3].max()) == 0.0  # and is causal


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_result_does_not_depend_on_the_chunk(chunk):
    args = draw(8, 128, g_floor=-2.0)
    o, last, _ = kda(*args, chunk=chunk)
    o_ref, last_ref, _ = kda(*args, chunk=128)
    assert close(o, o_ref) and close(last, last_ref)


def test_a_chunk_is_whole_sub_chunks():
    with pytest.raises(ValueError, match="sub-chunks"):
        kda(*draw(1, 64), chunk=24)


def test_bf16_operands_stay_near_float32():
    """The training dtypes: operands of the matmuls in bf16, decays, solve
    and states in float32."""
    q, k, v, g, beta = draw(4, 128, g_floor=-1.0)
    o_ref, _ = kda_recurrent(q, k, v, g, beta)
    o, last, _ = kda(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)
    assert o.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    err = jnp.linalg.norm(o.astype(jnp.float32) - o_ref) / jnp.linalg.norm(o_ref)
    assert float(err) < 2e-2


def test_float32_parts_in_bf16_read_an_order_worse(monkeypatch):
    """What the benchmark's comparison has to tell apart: the decays' sums,
    the solve and the states rounded to bf16 move the output by far more
    than bf16 operands do."""
    q, k, v, g, beta = draw(4, 128, g_floor=-1.0)
    o_ref, _ = kda_recurrent(q, k, v, g, beta)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    stated, _, _ = kda_lib.kda(*low, g, beta)
    monkeypatch.setattr(kda_lib, "_F32", jnp.bfloat16)
    rounded, _, _ = kda_lib.kda(*low, g, beta)
    norm = lambda o: float(jnp.linalg.norm(o.astype(jnp.float32) - o_ref)
                           / jnp.linalg.norm(o_ref))
    assert norm(rounded) > 4 * norm(stated)


KDA_CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=1, n_heads=4, max_seq_len=64,
    layer_types=("kda",), kda_heads=4, kda_head_dim=8, kda_gate_rank=4,
    kda_chunk=16, dtype=jnp.float32, attention_impl="xla")


def kda_block(seed=0):
    leaves = model._OPERATORS["kda"].init(jax.random.PRNGKey(seed), KDA_CFG, 1)
    return jax.tree.map(lambda x: x[0], leaves)


def test_the_mixer_is_causal_and_its_taps_stop_at_the_start():
    """A later token moves no earlier output, and the first token's output
    is what it is whatever follows: the taps read zeros before the
    sequence, not the sequence's end."""
    blk = kda_block()
    mixer = jax.jit(lambda x: model._kda_mixer(x, blk, KDA_CFG))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, 32))
    y, readings = mixer(x)
    moved, _ = mixer(x.at[:, 20].add(1.0))
    assert float(jnp.abs(moved - y)[:, :20].max()) == 0.0
    assert float(jnp.abs(moved - y)[:, 20:].max()) > 1e-5
    alone, _ = mixer(x[:, :1])
    assert close(alone[:, 0], y[:, 0], 1e-5)
    assert set(readings) == {"kda_log_decay_min", "kda_beta_mean"}
    assert float(readings["kda_log_decay_min"]) < 0.0
    assert 0.0 < float(readings["kda_beta_mean"]) < 2.0


def test_the_taps_are_four_and_in_order():
    """`c_t = sum_i w_i u_(t - 3 + i)`: an impulse at token 5 comes out at
    tokens 5 to 8 as taps 3 down to 0."""
    u = jnp.zeros((1, 12, 1)).at[0, 5, 0].set(1.0)
    w = jnp.array([[1.0], [2.0], [3.0], [4.0]])
    out = model._causal_taps(u, w)[0, :, 0]
    np.testing.assert_allclose(out[5:9], [4.0, 3.0, 2.0, 1.0])
    assert float(jnp.abs(out[:5]).max()) == 0.0


def test_the_decay_is_a_channel_of_the_key_not_a_head():
    """The log decay the mixer hands the recurrence differs by channel
    inside a head: what a scalar decay a head (`ops/ssd.py`) cannot say."""
    seen = {}
    real = model.kda

    def spy(q, k, v, g, beta, **kw):
        seen.update(g=g, beta=beta, q=q, k=k)
        return real(q, k, v, g, beta, **kw)

    model.kda = spy
    try:
        model._kda_mixer(  # eagerly: the spy keeps arrays, not tracers
            jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32)),
            kda_block(3), KDA_CFG)
    finally:
        model.kda = real
    g = seen["g"]
    assert g.shape == (1, 32, 4, 8) and g.dtype == jnp.float32
    assert float(g.max()) < 0.0
    assert float(jnp.std(g, axis=-1).min()) > 0.0  # channels of a head differ
    assert seen["beta"].shape == (1, 32, 4)
    for unit in (seen["q"], seen["k"]):  # unit length a head
        np.testing.assert_allclose(
            jnp.linalg.norm(unit, axis=-1), 1.0, atol=1e-3)
