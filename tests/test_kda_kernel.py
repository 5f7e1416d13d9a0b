"""KDA's Pallas kernels (`ray_tpu/ops/kda.py`: `kda_fwd`, `kda_bwd`) in
interpret mode, on shapes that tile: against the `jax.numpy` form they
replace on the chip and against the recurrence taken token by token, `o`,
the state handed back and the gradients of all five inputs and of the
entering state, as cases of one test (decays past float32's range inside a
chunk, beta over 1, a ragged tail, a given state, one and several heads,
float32 and bf16); the dtypes the kernels compute in, read from their own
jaxprs; and which path a shape takes, with the line that says so. A file of
its own beside `tests/test_kda.py`, as the scan's kernels have theirs: each
shape is three programs to compile."""

import functools
import logging

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import kda as kda_lib
from test_kda import close, draw, kda, kda_recurrent

NAMES = "q k v g beta state".split()
WIDE = 128  # keys and values of whole lanes: what the kernels tile


def rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / (jnp.sum(b ** 2) + 1e-30)))


@functools.lru_cache(maxsize=None)
def everything(path, T, H, dtype):
    """One program a path and shape: `o`, the state handed back and the
    gradients of all five inputs and of the entering state, of a loss that
    weighs every token and the last state."""
    fn = {"kernels": functools.partial(kda_lib.kda, interpret=True),
          "numpy": kda_lib.kda,
          "recurrence": kda_lib.kda_recurrent}[path]
    weights = jax.random.normal(jax.random.PRNGKey(T + H), (1, T, H, WIDE))

    def loss(q, k, v, g, beta, state):
        o, last = fn(q, k, v, g, beta, state=state)[:2]
        return (jnp.sum(o.astype(jnp.float32) * weights)
                + jnp.sum(last ** 2), (o, last))

    return jax.jit(jax.value_and_grad(loss, argnums=range(6), has_aux=True))


# what is drawn for a case: (tokens, heads, dtype, the decay's floor a
# token, beta's scale, with an entering state). Few distinct (tokens, heads,
# dtype): each is three programs to compile; a decay, a beta, a state are data
KERNEL_CASES = {
    "two_chunks": (128, 2, jnp.float32, -1.0, 2.0, False),
    # -1.6 a token is -102 by a chunk's end: exp(102) is no float32
    "decays_of_1.6_nats_a_token": (128, 2, jnp.float32, -1.6, 2.0, False),
    "beta_over_1": (128, 2, jnp.float32, -0.5, 8.0, False),
    "an_entering_state": (128, 2, jnp.float32, -1.0, 2.0, True),
    # 100 tokens are padded to two chunks, one head to a group of two
    "one_head_and_a_ragged_tail": (100, 1, jnp.float32, -1.0, 2.0, True),
    # three heads are padded to four: two groups of two
    "three_heads": (128, 3, jnp.float32, -1.6, 2.0, False),
    "bfloat16": (128, 2, jnp.bfloat16, -1.0, 2.0, True),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_are_the_numpy_form_and_the_recurrence(case):
    """float32: the paths differ by the order of their sums. bf16: the
    kernels and the `jax.numpy` form round the same operands at the same
    places, and stay as close to each other as either is to the recurrence
    in float32."""
    T, H, dtype, g_floor, beta_scale, with_state = KERNEL_CASES[case]
    q, k, v, g, beta = draw(len(case), T, H=H, dk=WIDE, dv=WIDE,
                            g_floor=g_floor, beta_scale=beta_scale)
    if g_floor == -1.6:
        g = jnp.full_like(g, g_floor).at[..., ::2].mul(0.5)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    state = 0.3 * jax.random.normal(
        jax.random.PRNGKey(7), (1, H, WIDE, WIDE)) * with_state
    args = (q, k, v, g, beta, state)
    with jax.default_matmul_precision("highest"):
        (_, (o, last)), grads = everything("kernels", T, H, dtype)(*args)
        (_, (o_np, last_np)), grads_np = everything(
            "numpy", T, H, dtype)(*args)
        exact = tuple(x.astype(jnp.float32) for x in args)
        (_, (o_rec, last_rec)), grads_rec = everything(
            "recurrence", T, H, jnp.float32)(*exact)
    assert o.dtype == dtype and o.shape == v.shape
    assert last.dtype == jnp.float32 and last.shape == state.shape
    assert bool(jnp.isfinite(o).all())
    to_numpy, to_recurrence = (
        (2e-5, 2e-5) if dtype == jnp.float32 else (6e-3, 1.2e-2))
    assert rel(o, o_np) < to_numpy and rel(last, last_np) < to_numpy
    assert rel(o, o_rec) < to_recurrence and rel(last, last_rec) < to_recurrence
    for name, ours, theirs, by_token, arg in zip(
            NAMES, grads, grads_np, grads_rec, args):
        assert ours.dtype == arg.dtype and ours.shape == arg.shape, name
        assert bool(jnp.isfinite(ours).all()), name
        assert rel(ours, theirs) < to_numpy, name
        assert rel(ours, by_token) < to_recurrence, name


def test_the_kernels_report_how_far_the_decays_reach():
    T, H = 128, 2
    q, k, v, g, beta = draw(2, T, H=H, dk=WIDE, dv=WIDE, g_floor=-1.6)
    reach = jax.jit(functools.partial(kda_lib.kda, interpret=True))(
        q, k, v, g, beta)[2]
    assert abs(float(reach) - float(kda(q, k, v, g, beta)[2])) < 1e-3
    assert float(reach) < -50.0


# (chunk, dk, dv) -> the reason's words, or None where the kernels take it
UNTILED = [((64, 128, 128), None), ((32, 128, 256), None),
           ((128, 256, 128), None), ((16, 128, 128), None),
           ((48, 128, 128), "do not fill 128 rows"),
           ((64, 16, 128), "keys of 16"), ((64, 128, 8), "values of 8"),
           ((64, 2048, 2048), "VMEM")]


@pytest.mark.parametrize("shape,why", UNTILED)
def test_which_shapes_the_kernels_take(shape, why):
    reason = kda_lib.kda_untiled(*shape, 2)
    assert (reason is None) if why is None else (why in reason)


def test_a_shape_that_does_not_tile_takes_numpy_and_says_why(caplog):
    """Heads of 16 are no whole lanes: `impl='pallas'` and `interpret` take
    the `jax.numpy` form all the same, one line a shape says so, and a
    shape that tiles names its kernels, grid and VMEM."""
    kda_lib._log_kda.cache_clear()
    args = draw(3, 64)
    with caplog.at_level(logging.INFO, logger=kda_lib.logger.name):
        o, last, _ = kda_lib.kda(*args, impl="pallas", interpret=True)
        kda_lib.kda(*args, impl="pallas", interpret=True)  # the same shape
        kda_lib.kda(*args)
        jax.eval_shape(
            functools.partial(kda_lib.kda, interpret=True),
            *draw(3, 128, H=8, dk=WIDE, dv=WIDE))
    o_ref, last_ref = kda_recurrent(*args)
    assert close(o, o_ref) and close(last, last_ref)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 3
    assert "jax.numpy (kda_chunk, kda_state, kda_out), because keys of 16" \
        in lines[0]
    assert lines[1].endswith("jax.numpy (kda_chunk, kda_state, kda_out)")
    assert "kda at b 1, T 128, H 8, dk 128, dv 128, chunk 64, float32: " \
        "kda_fwd and kda_bwd, grid (1, 4, 2), 2 heads a step" in lines[2]
    assert "VMEM" in lines[2]
    kda_lib._log_kda.cache_clear()


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_kernels_compute_in_the_stated_dtypes():
    """`assumed.dtype` of the configuration, read from the kernels' own
    jaxprs under bf16 operands: every exp is float32; every matmul gives
    float32 and takes bf16 operands, but for the backward's two through the
    inverse, which are float32 at the highest precision; the states carried
    from chunk to chunk and handed to the backward, g and beta with their
    cotangents are float32."""
    q, k, v, g, beta = draw(0, 64, H=2, dk=WIDE, dv=WIDE)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_lib.kda(
        *a, interpret=True)[0].astype(jnp.float32)), argnums=range(5)))(
            *low, g, beta)
    calls = {e.params["name"]: e for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert set(calls) == {"kda_fwd", "kda_bwd"}
    for name, call in calls.items():
        inner = list(_equations(call.params["jaxpr"]))
        exps = [e for e in inner if e.primitive.name == "exp"]
        assert exps and all(
            e.invars[0].aval.dtype == jnp.float32 for e in exps)
        dots = [e for e in inner if e.primitive.name == "dot_general"]
        assert all(d.outvars[0].aval.dtype == jnp.float32 for d in dots)
        wide = [d for d in dots if {v.aval.dtype for v in d.invars}
                != {jnp.dtype(jnp.bfloat16)}]
        assert len(wide) == (0 if name == "kda_fwd" else 2)
        for dot in wide:
            assert {v.aval.dtype for v in dot.invars} == {
                jnp.dtype(jnp.float32)}
            assert "HIGHEST" in str(dot.params["precision"])
        # q, k, v and o's cotangent in bf16; g, beta and the states float32
        kinds = [v.aval.dtype for v in call.invars]
        assert kinds[:6] == [jnp.bfloat16] * 3 + [jnp.float32] * 3
        assert all(v.aval.dtype == jnp.float32 for v in call.outvars[3:])
