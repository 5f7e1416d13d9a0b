"""Parallel-layer tests on the virtual 8-device CPU mesh: mesh construction,
ring attention vs full attention, Ulysses all-to-all attention, gradients."""

import numpy as np
import pytest


@pytest.fixture(scope="module", autouse=True)
def _cpu(request):
    from ray_tpu.testing import force_cpu_mesh

    force_cpu_mesh(8)


def test_make_mesh_infer():
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh({"data": -1, "tensor": 2})
    assert mesh.shape["data"] == 4 and mesh.shape["tensor"] == 2


def test_make_mesh_bad_shape():
    from ray_tpu.parallel import make_mesh

    with pytest.raises(ValueError):
        make_mesh({"data": 3, "tensor": 2})


def test_batch_sharding_roundtrip():
    import jax

    from ray_tpu.parallel import batch_sharding, make_mesh

    mesh = make_mesh({"data": 8})
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    xs = jax.device_put(x, batch_sharding(mesh))
    assert len(xs.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(xs), x)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    import jax

    from ray_tpu.parallel import full_attention, make_mesh, ring_attention_sharded

    mesh = make_mesh({"sequence": 8})
    B, T, H, D = 2, 32, 4, 16
    rng = np.random.RandomState(0)
    q = rng.randn(B, T, H, D).astype(np.float32)
    k = rng.randn(B, T, H, D).astype(np.float32)
    v = rng.randn(B, T, H, D).astype(np.float32)

    ring = ring_attention_sharded(q, k, v, mesh, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_mixed_mesh():
    """data x sequence mesh: batch and sequence both sharded."""
    from ray_tpu.parallel import full_attention, make_mesh, ring_attention_sharded

    mesh = make_mesh({"data": 2, "sequence": 4})
    B, T, H, D = 4, 16, 2, 8
    rng = np.random.RandomState(1)
    q, k, v = (rng.randn(B, T, H, D).astype(np.float32) for _ in range(3))
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    from ray_tpu.parallel import full_attention, make_mesh, ulysses_attention_sharded

    mesh = make_mesh({"sequence": 8})
    B, T, H, D = 2, 32, 8, 16  # H divisible by 8
    rng = np.random.RandomState(2)
    q, k, v = (rng.randn(B, T, H, D).astype(np.float32) for _ in range(3))
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_grad():
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import full_attention, make_mesh, ring_attention_sharded

    mesh = make_mesh({"sequence": 4}, devices=jax.devices()[:4])
    B, T, H, D = 1, 16, 2, 8
    rng = np.random.RandomState(3)
    q, k, v = (rng.randn(B, T, H, D).astype(np.float32) for _ in range(3))

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


def test_fsdp_leaf_sharding():
    import jax

    from ray_tpu.parallel import fsdp_sharding_for_leaf, make_mesh

    mesh = make_mesh({"fsdp": 8})
    w = np.zeros((128, 64), dtype=np.float32)
    s = fsdp_sharding_for_leaf(mesh, w)
    ws = jax.device_put(w, s)
    assert len(ws.sharding.device_set) == 8
    # scalar falls back to replication
    b = np.zeros((), dtype=np.float32)
    s2 = fsdp_sharding_for_leaf(mesh, b)
    jax.device_put(b, s2)
