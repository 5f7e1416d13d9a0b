"""Mamba-1's selective scan (`ray_tpu/ops/selective_scan.py`) on the CPU:
the chunked path behind its `custom_vjp` against the recurrence token by
token, forward, last state and every gradient, at lengths that are and are
not whole chunks; and the lowered text of its gradient, which holds no
array of a sequence's states."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.selective_scan import selective_scan

B, INNER, N = 2, 24, 4


def inputs(T, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    u = jax.random.normal(k[0], (B, T, INNER)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, INNER)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[2], (INNER, N)))
    Bm = jax.random.normal(k[3], (B, T, N)).astype(dtype)
    C = jax.random.normal(k[4], (B, T, N)).astype(dtype)
    D = jax.random.normal(k[5], (INNER,))
    weights = (jax.random.normal(k[6], (B, T, INNER)),
               jax.random.normal(k[7], (B, INNER, N)))
    return (u, dt, A, Bm, C, D), weights


def value_and_grads(impl, args, weights, chunk):
    def scalar(*args):
        y, last = selective_scan(*args, chunk=chunk, impl=impl)
        return (y * weights[0]).sum() + (last * weights[1]).sum(), (y, last)

    return jax.value_and_grad(scalar, argnums=tuple(range(6)), has_aux=True)(
        *args)


@pytest.mark.parametrize("T,chunk", [(32, 8), (37, 8), (5, 16), (48, 48)])
def test_chunked_is_the_recurrence_token_by_token(T, chunk):
    args, weights = inputs(T)
    (_, (y, last)), grads = value_and_grads("chunked", args, weights, chunk)
    (_, (y_ref, last_ref)), grads_ref = value_and_grads(
        "tokens", args, weights, chunk)
    assert y.shape == (B, T, INNER) and y.dtype == jnp.float32
    assert last.shape == (B, INNER, N)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, last_ref, rtol=1e-5, atol=1e-5)
    for name, ours, theirs in zip("u dt A B C D".split(), grads, grads_ref):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
        np.testing.assert_allclose(
            ours, theirs, rtol=2e-4, atol=2e-5 * float(jnp.abs(theirs).max()),
            err_msg=name)


def test_the_recurrence_is_the_equations():
    """`impl="tokens"` against a Python loop over the tokens, in numpy."""
    args, _ = inputs(9)
    u, dt, A, Bm, C, D = (np.asarray(x, np.float64) for x in args)
    h = np.zeros((B, INNER, N))
    want = np.zeros((B, 9, INNER))
    for t in range(9):
        h = (np.exp(dt[:, t, :, None] * A) * h
             + (dt[:, t] * u[:, t])[..., None] * Bm[:, t, None, :])
        want[:, t] = (h * C[:, t, None, :]).sum(-1) + D * u[:, t]
    y, last = selective_scan(*args, impl="tokens")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, h, rtol=1e-5, atol=1e-5)


def test_bf16_inputs_are_widened_a_token_at_a_time():
    """u, B and C come in the compute dtype; the state, the decay and y
    are float32, and u's, B's and C's gradients come back in their dtype."""
    args, weights = inputs(32, jnp.bfloat16)
    (_, (y, last)), grads = value_and_grads("chunked", args, weights, 8)
    assert y.dtype == last.dtype == jnp.float32
    assert [g.dtype for g in grads] == [
        jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
        jnp.float32]
    wide = tuple(x.astype(jnp.float32) for x in args)
    (_, (y_ref, _)), _ = value_and_grads("tokens", wide, weights, 8)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_no_array_of_a_sequence_s_states_in_the_lowered_gradient():
    """The gradient's program holds a chunk's states and each chunk's
    entering state, never `[T, inner, N]` (in any order of its axes)."""
    T, chunk = 256, 16
    args, weights = inputs(T)
    text = jax.jit(
        lambda *a: value_and_grads("chunked", a, weights, chunk)).lower(
            *args).as_text()
    whole = B * T * INNER * N
    sizes = set()
    for shape in re.findall(r"tensor<([0-9x]+)x(?:f32|bf16)>", text):
        sizes.add(int(np.prod([int(d) for d in shape.split("x")])))
    assert B * chunk * INNER * N in sizes          # a chunk's steps
    assert B * (T // chunk) * INNER * N in sizes   # the entering states
    assert not any(size >= whole for size in sizes), sorted(sizes)[-3:]
    # the reference's gradient does hold them
    text = jax.jit(
        lambda *a: value_and_grads("tokens", a, weights, chunk)).lower(
            *args).as_text()
    assert f"tensor<{T}x{B}x{N}x{INNER}xf32>" in text


def test_an_impl_it_does_not_have_is_refused():
    args, _ = inputs(8)
    with pytest.raises(ValueError, match="tiles"):
        selective_scan(*args, impl="tiles")
