"""`flash_attention_lse` (`ops/flash_attention.py`): the entry that returns
`(o, lse)` and takes lse's cotangent, whole, causal and under a staircase,
in interpret mode against plain `jax.numpy`; the staircase's tiles; and the
entry that returns o alone, whose values and program are the ones they
were."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("ray_tpu.ops.flash_attention")

B, T, H, D = 2, 64, 2, 16
SPAN, PER = 16, 4  # the staircase: 4 keys a span of 16 queries


def inputs(keys=T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, keys, H, D))
    v = jax.random.normal(ks[2], (B, keys, H, D))
    return q, k, v, jax.random.normal(ks[3], (B, T, H, D)), (
        jax.random.normal(ks[4], (B, T, H)))


def plain(q, k, v, mask):
    """(o, lse) by `jax.numpy`; a row that sees no key has o 0, lse -inf."""
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(D)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.where(mask[None, None], jnp.exp(s - jnp.where(
        jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    return jnp.einsum("bhts,bshd->bthd", p, v), lse.transpose(0, 2, 1)


def mask_of(how, keys):
    i, j = jnp.arange(T)[:, None], jnp.arange(keys)[None, :]
    if how == "whole":
        return jnp.ones((T, keys), bool)
    if how == "causal":
        return j <= i
    return j < PER * (i // SPAN)


HOW = {"whole": dict(), "causal": dict(causal=True),
       "stair": dict(stair=(SPAN, PER))}


@pytest.mark.parametrize("blocks", [(None, None), (16, 4), (32, 8)])
@pytest.mark.parametrize("how", list(HOW))
def test_o_and_lse_and_their_gradients_agree_with_jax_numpy(how, blocks):
    """With a cotangent on o AND on lse. Tiles of 16 x 4 are whole or empty
    under the staircase; 32 x 8 and the shape's own straddle a step and
    take the masked body."""
    keys = T // 4 if how == "stair" else T
    q, k, v, do, dlse = inputs(keys)
    mask = mask_of(how, keys)
    seen = mask.any(axis=1)  # rows that see a key: the others' lse is -inf

    def total(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return (o * do).sum() + (jnp.where(
                seen[None, :, None], lse, 0.0) * dlse).sum(), (o, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (l_k, (o, lse)), grads = total(lambda q, k, v: fa.flash_attention_lse(
        q, k, v, interpret=True, block_q=blocks[0], block_k=blocks[1],
        **HOW[how]))
    (l_p, (o_p, lse_p)), grads_p = total(lambda q, k, v: plain(q, k, v, mask))
    np.testing.assert_allclose(o, o_p, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.isfinite(lse), np.isfinite(lse_p))
    np.testing.assert_allclose(
        jnp.where(seen[None, :, None], lse, 0),
        jnp.where(seen[None, :, None], lse_p, 0), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(l_k, l_p, rtol=1e-5)
    for ours, theirs in zip(grads, grads_p):
        np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)
    if how == "stair":  # the first span sees nothing
        assert float(jnp.abs(o[:, :SPAN]).max()) == 0
        assert bool(jnp.all(lse[:, :SPAN] == -jnp.inf))


def test_two_partial_softmaxes_join_into_the_whole_one():
    """The keys cut in two halves, each half's (o, lse), and the join: the
    whole softmax, gradients through both lse included."""
    q, k, v, do, _ = inputs()

    def joined(q, k, v):
        parts = [fa.flash_attention_lse(q, k[:, half], v[:, half],
                                        interpret=True)
                 for half in (slice(0, T // 2), slice(T // 2, T))]
        (o1, l1), (o2, l2) = parts
        m = jnp.maximum(l1, l2)
        w1, w2 = jnp.exp(l1 - m)[..., None], jnp.exp(l2 - m)[..., None]
        return (((w1 * o1 + w2 * o2) / (w1 + w2)) * do).sum()

    def whole(q, k, v):
        return (plain(q, k, v, mask_of("whole", T))[0] * do).sum()

    ours = jax.value_and_grad(joined, argnums=(0, 1, 2))(q, k, v)
    theirs = jax.value_and_grad(whole, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)
    for a, b in zip(ours[1], theirs[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkv_dq",
                                    "flash_bwd_dq", "flash_bwd_dkv"])
def test_the_staircase_s_tiles_at_the_cell_s_shape_are_whole_or_empty(kernel):
    """8192 queries against 512 summaries, 128 a window of 2048: the tile
    divides a window and a window's summaries, 6 of a row's 16 tiles have a
    body (windows 1, 2, 3 see 1, 2, 3 tiles of keys), and none is masked."""
    tiles = fa.flash_tiles(kernel, 8192, 512, 128, jnp.bfloat16,
                           causal=False, stair=(2048, 128))
    assert 2048 % tiles.block_q == 0 and tiles.block_k == 128
    rows = 8192 // tiles.block_q
    assert tiles.grid_steps == rows * 4
    assert tiles.active_share == pytest.approx(6 / 16)
    for qi in range(rows):
        for ki in range(4):
            body, masked = fa._tile_kind(
                qi, ki, block_q=tiles.block_q, block_k=128, num_q=rows,
                num_k=4, causal=False, seq_q=8192, seq_k=512,
                stair=(2048, 128))
            assert body == (ki < qi * tiles.block_q // 2048)
            assert not (body and masked)
    assert fa._kernel_name(kernel, None, None, (2048, 128)) == (
        kernel + "_stair")


def test_a_staircase_under_a_causal_mask_is_refused():
    q, k, v, _, _ = inputs(T // 4)
    with pytest.raises(ValueError, match="staircase"):
        fa.flash_attention_lse(q, k, v, causal=True, stair=(SPAN, PER),
                               interpret=True)


@pytest.mark.parametrize("window", [None, 24])
def test_the_entry_that_returns_o_alone_is_what_it_was(window):
    """Its values against plain attention, and its traced program: the one
    forward kernel without an lse output, no staircase in any name, and a
    backward whose `delta` has no `dlse` term."""
    q, k, v, do, _ = inputs()
    how = dict(causal=True, window=window, interpret=True)
    o = fa.flash_attention(q, k, v, **how)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = (j <= i) & ((i - j < window) if window else True)
    np.testing.assert_allclose(o, plain(q, k, v, mask)[0], rtol=2e-5,
                               atol=2e-6)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: (fa.flash_attention(q, k, v, **how) * do).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    assert "stair" not in text
    # o * do summed, broadcast: nothing subtracted between the two
    assert text.count("pallas_call") >= 2
    one = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, **how))(q, k, v)
    calls = [e for e in one.jaxpr.eqns if "custom_vjp" in e.primitive.name]
    assert len(calls) == 1 and len(calls[0].outvars) == 1  # o alone
