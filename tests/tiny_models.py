"""What the per-family test files share (`test_laguna.py`, `test_nemotron_h.py`,
`test_lfm2_moe.py`, `test_deepseek_v2.py`, `test_keye_vl2.py`,
`test_solar_open2.py`, `test_ssd_kernel.py`): seeded keys, batches and weights,
the mesh of one device, the reference's view of a configuration, a loss with
its gradients as one compiled program, and the Mamba-2 scan token by token."""

import dataclasses
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (
    transformer_init, transformer_loss_and_readings)
from ray_tpu.parallel import make_mesh


def key(i):
    return jax.random.PRNGKey(i)


# the seeded weights as one program a configuration, not a leaf at a time
init = jax.jit(transformer_init, static_argnums=1)


def batch_of(cfg, rows=2, seq=32, seed=1):
    ids = jax.random.randint(key(seed), (rows, seq + 1), 0, cfg.vocab_size)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


def one_device():
    return make_mesh({"data": 1}, devices=jax.devices()[:1])


def first_layer(cfg, seed=4):
    """The weights of a one-layer model's only layer, unstacked."""
    return jax.tree.map(lambda a: a[0], init(key(seed), cfg)["blocks"])


def as_reference_config(cfg):
    config = {**dataclasses.asdict(cfg), "dtype": "float32"}
    if cfg.rope_scaling:
        config["rope_scaling"] = dict(cfg.rope_scaling)
    return config


def value_and_grad(f, params, **kw):
    """`jax.value_and_grad(f)(params)` compiled once, not run an operation at
    a time: a whole model's backward is thousands of them."""
    return jax.jit(jax.value_and_grad(f, **kw))(params)


def program(cfg, params, batch, **kw):
    """((loss, readings), gradients) of the model's own loss."""
    return value_and_grad(lambda p: transformer_loss_and_readings(
        p, batch, cfg, **kw), params, has_aux=True)


def distance(grads, wanted):
    """Between two trees of gradients, over the norm of the second."""
    num = sum(float(((a - b) ** 2).sum()) for a, b in zip(
        jax.tree.leaves(grads), jax.tree.leaves(wanted)))
    den = sum(float((b ** 2).sum()) for b in jax.tree.leaves(wanted))
    return math.sqrt(num / den)


def y_and_grads(f, args):
    """`y = f(*args)` and the gradients of `sum(sin(y))` by every argument,
    so that every token's cotangent differs; one compiled program."""
    def loss(*a):
        y = f(*a)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(len(args)), has_aux=True))(*args)
    return y, grads


def ssd_by_token(x, dt, A, B, C, D):
    """The Mamba-2 scan's `y` by the recurrence itself, one `lax.scan` step a
    token, in float32: what `ssd` and its kernels are tested against."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    rep = H // G
    A, D = A.astype(jnp.float32), D.astype(jnp.float32)

    def step(h, t):
        x_t, dt_t, B_t, C_t = t                            # [b, H, P], [b, H], [b, G, N]
        B_t, C_t = jnp.repeat(B_t, rep, axis=1), jnp.repeat(C_t, rep, axis=1)
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return h, jnp.einsum("bHPN,bHN->bHP", h, C_t) + D[:, None] * x_t

    per_token = tuple(v.astype(jnp.float32).swapaxes(0, 1) for v in (x, dt, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), jnp.float32), per_token)
    return y.swapaxes(0, 1)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)
