"""Grouped-query attention without the repeat (`ray_tpu/ops/flash_attention.py`):
k and v reach the four flash kernels at their own heads, `[B*Hk, S, D]`, the
index maps name the key-value row of a query row, and dk and dv leave at those
heads, summed over a group in VMEM. On the CPU in interpret mode at tiny
shapes: against the parent's path (k and v repeated by the test, which is the
`rep == 1` program) bit for bit where the values and their order are the same
(o, lse, dq) and within the chip smoke's tolerance where a sum moved into f32
(dk, dv); against a masked softmax; what stands in the jaxpr; the grid, blocks
and index maps handed to `pallas_call`; and the group's sums step by step."""

import functools
import importlib
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from ray_tpu.ops.flash_attention import flash_attention
from tiny_models import key

fa = importlib.import_module("ray_tpu.ops.flash_attention")

T, D = 256, 32
TWO = ("flash_bwd_dq", "flash_bwd_dkv")
KV_ROWS, WIDEST = 2, 8  # key-value rows folded, and the widest group tested


def operands(batch, kv_heads, group, dtype, seq=T):
    """q, k, v and the output's cotangent: `group` query heads a key head."""
    return tuple(
        jax.random.normal(key(i), (batch, seq, heads, D)).astype(dtype)
        for i, heads in enumerate(
            [kv_heads * group, kv_heads, kv_heads, kv_heads * group]))


def repeated(x, group):
    return jnp.repeat(x, group, axis=2)


def masked_softmax(q, k, v, window):
    group = q.shape[2] // k.shape[2]
    k, v = repeated(k, group), repeated(v, group)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[3])
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None, :]
    s = jnp.where((j <= i) & (i - j < (window or k.shape[1])), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)


def rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


# ------------------------------------------- against the parent's path

def tile(window):
    return dict(causal=True, scale=D ** -0.5, window=window, block_q=128,
                block_k=128, interpret=True)


def folded(dtype):
    """q and do of [16, T, D], k and v of [2, T, D], heads folded as
    `flash_attention` folds them: query row `bh` reads key-value row
    `bh // 8`."""
    return tuple(
        np.asarray(jax.random.normal(key(i), (rows, T, D)).astype(dtype))
        for i, rows in enumerate(
            [KV_ROWS * WIDEST, KV_ROWS, KV_ROWS, KV_ROWS * WIDEST]))


def heads_of(group):
    """The first `group` query rows of each key-value row's eight: rows of
    a (batch, head) are independent of one another, so the parent's path
    for a narrower group is those rows of its path for the widest."""
    return np.array([kv * WIDEST + head for kv in range(KV_ROWS)
                     for head in range(group)])


def backward(q, k, v, o, lse, do, window):
    """(dq, dk, dv) by the kernels `flash_bwd_kernels` names while this is
    traced, under a `jit` of its own: one program a call, and no cache
    between a case that steers the plan and one that does not."""
    return jax.jit(lambda *operands: fa._flash_vjp_bwd(
        True, D ** -0.5, 128, 128, True, False, window, 1,
        operands[:5], operands[5]))(q, k, v, o, lse, do)


@functools.lru_cache(maxsize=None)
def parents(window, dtype):
    """The parent's path: k and v repeated to the queries' rows and the
    `rep == 1` kernels. (o, lse, dq, and dk and dv a query row.)"""
    q, k, v, do = folded(dtype)
    k, v = (np.repeat(x, WIDEST, axis=0) for x in (k, v))
    o, lse = fa._flash_fwd(q, k, v, with_lse=True, **tile(window))
    return tuple(np.asarray(x) for x in (
        o, lse, *backward(q, k, v, o, lse, do, window)))


@functools.lru_cache(maxsize=None)
def forward(group, window, dtype):
    q, k, v, _ = folded(dtype)
    return fa._flash_fwd(q[heads_of(group)], k, v, with_lse=True,
                         **tile(window))


@functools.lru_cache(maxsize=None)
def softmax_s_dk_and_dv(window):
    """dk and dv a query row by the masked softmax, in float32."""
    q, k, v, do = (x[None].transpose(0, 2, 1, 3)
                   for x in folded(jnp.float32))
    k, v = (np.repeat(x, WIDEST, axis=2) for x in (k, v))
    with jax.default_matmul_precision("highest"):
        dk, dv = jax.jit(lambda k, v: jax.vjp(
            lambda k, v: masked_softmax(q, k, v, window), k, v)[1](do))(k, v)
    return tuple(np.asarray(x)[0].transpose(1, 0, 2) for x in (dk, dv))


def summed(per_row, group):
    """[16, T, D] a query row -> [2, T, D] float32: the group's rows
    summed, as the transpose of the parent's repeat sums them."""
    rows = np.asarray(per_row[heads_of(group)], np.float32)
    return rows.reshape(KV_ROWS, group, *rows.shape[1:]).sum(1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("two_kernels", [False, True], ids=["one", "two"])
@pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
@pytest.mark.parametrize("group", [2, 4, WIDEST])
def test_a_group_reads_its_key_head_as_the_repeat_did(group, window,
                                                      two_kernels, dtype,
                                                      monkeypatch):
    """The values that reach each body of the forward and of dq, and their
    order, are the parent's: o, lse and dq to the bit. dk and dv are summed
    over the group in f32 and rounded once, where the parent rounded each
    head's and summed those: within the chip smoke's tolerance of it, and
    in f32 within 2e-4 of the masked softmax's."""
    if two_kernels:
        monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: TWO)
    q, k, v, do = folded(dtype)
    rows = heads_of(group)
    with jax.default_matmul_precision("highest"):
        o_parent, lse_parent, dq_parent, dk_rows, dv_rows = parents(
            window, dtype)
        o, lse = forward(group, window, dtype)
        dq, dk, dv = (np.asarray(x) for x in backward(
            q[rows], k, v, o, lse, do[rows], window))
    np.testing.assert_array_equal(np.asarray(o), o_parent[rows])
    np.testing.assert_array_equal(np.asarray(lse), lse_parent[rows])
    np.testing.assert_array_equal(dq, dq_parent[rows])
    exact = softmax_s_dk_and_dv(window)
    for got, x, per_row, plain in zip((dk, dv), (k, v), (dk_rows, dv_rows),
                                      exact):
        assert got.shape == x.shape and got.dtype == dtype
        assert rel_err(got, summed(per_row, group)) <= (
            chip_smoke.KERNEL_TOLERANCE)
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, summed(per_row, group),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(got, summed(plain, group),
                                       rtol=2e-4, atol=2e-4)
    assert float(np.abs(dk.astype(np.float32)).max()) > 0.01


def test_a_ragged_sequence_under_a_group_keeps_its_own_rows():
    """T 200 at tiles of 128: dk and dv leave the one kernel in whole key
    tiles, as dq does in whole q tiles, and are cut to the sequence."""
    q, k, v, do = operands(1, 1, 4, jnp.float32, seq=200)
    kw = dict(causal=True, block_q=128, block_k=128, interpret=True)
    def pulled(fn):
        return jax.jit(lambda *a: jax.vjp(fn, *a)[1](do))(q, k, v)

    with jax.default_matmul_precision("highest"):
        grads = pulled(lambda *a: flash_attention(*a, **kw))
        wanted = pulled(lambda *a: masked_softmax(*a, None))
    for got, want, x in zip(grads, wanted, (q, k, v)):
        assert got.shape == x.shape
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_heads_that_do_not_divide_are_refused():
    q, k, *_ = operands(1, 3, 1, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q[:, :, :2], k, k, causal=True, interpret=True)


# ------------------------------------------------- what stands in the jaxpr

MOVES = {"transpose", "reshape"}  # what may stand between k, v and a kernel


def kernels_and_what_touches(fn, args, tainted):
    """([(kernel's name, operand shapes, output shapes)], the primitives
    other than the kernels that take a value made from the arguments at
    `tainted` or hand one to them) of the jaxpr of `fn(*args)`."""
    calls, touching = [], set()

    def walk(jaxpr, taint):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            hit = any(v in taint for v in eqn.invars
                      if isinstance(v, jax.extend.core.Var))
            subs = jax.core.jaxprs_in_params(eqn.params)
            if name == "pallas_call":
                calls.append((eqn.params["name"],
                              [v.aval.shape for v in eqn.invars],
                              [v.aval.shape for v in eqn.outvars]))
            elif subs and hit:
                for sub in subs:  # a call: its arguments are the equation's
                    walk(sub, {inner for inner, outer in zip(
                        sub.invars, eqn.invars) if outer in taint})
                taint.update(eqn.outvars)
            elif hit:
                touching.add(name)
                taint.update(eqn.outvars)

    closed = jax.make_jaxpr(fn)(*args)
    walk(closed.jaxpr, {closed.jaxpr.invars[i] for i in tainted})
    return calls, touching


@pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
@pytest.mark.parametrize("two_kernels", [False, True], ids=["one", "two"])
def test_k_and_v_reach_the_kernels_at_their_own_heads(two_kernels, window,
                                                      monkeypatch):
    """Nothing but a transpose and a reshape stands between k, v and a
    kernel, forward or backward: no `repeat` (a `broadcast_in_dim` or a
    `gather`), and every kernel's k and v operands are `[B*Hk, S, D]`, as
    are the dk and dv it hands back."""
    if two_kernels:
        monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: TWO)
    batch, kv_heads, group = 2, 2, 8
    q, k, v, _ = operands(batch, kv_heads, group, jnp.bfloat16)
    kw = dict(causal=True, window=window, interpret=True)
    own, rows = (batch * kv_heads, T, D), (batch * kv_heads * group, T, D)

    calls, touching = kernels_and_what_touches(
        lambda *a: flash_attention(*a, **kw), (q, k, v), (1, 2))
    assert touching <= MOVES
    assert [(name[:9], shapes) for name, shapes, _ in calls] == [
        ("flash_fwd", [rows, own, own])]

    def loss(q, k, v):
        return flash_attention(q, k, v, **kw).astype(jnp.float32).sum()

    calls, touching = kernels_and_what_touches(
        jax.grad(loss, (0, 1, 2)), (q, k, v), (1, 2))
    assert touching <= MOVES
    suffix = "" if window is None else "_window"
    names = ["flash_fwd", *(TWO if two_kernels else ["flash_bwd_dkv_dq"])]
    assert [name for name, _, _ in calls] == [n + suffix for n in names]
    for name, operands_, outputs in calls:
        assert operands_[:3] == [rows, own, own]
        if "dkv" in name:  # dk, dv (and the row's dq)
            assert outputs[:2] == [own, own] and outputs[2:] in ([], [rows])
    # and every gradient leaves in its operand's shape
    for got, x in zip(jax.eval_shape(jax.grad(loss, (0, 1, 2)), q, k, v),
                      (q, k, v)):
        assert got.shape == x.shape and got.dtype == x.dtype


# ------------------------------------- what is handed to each `pallas_call`

def pallas_calls(fn, *args):
    """{name: the equation's parameters} of every `pallas_call` traced."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn.params
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def blocks_at(params, *coordinates):
    """[(block shape, block index at grid `coordinates`)] of a call's
    operands and outputs, in order."""
    out = []
    for mapping in params["grid_mapping"].block_mappings:
        closed = mapping.index_map_jaxpr
        index = jax.core.eval_jaxpr(
            closed.jaxpr, closed.consts, *map(jnp.int32, coordinates))
        shape = tuple(getattr(b, "block_size", b)
                      for b in mapping.block_shape)
        out.append((shape, tuple(int(i) for i in index)))
    return out


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window"])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "flash_bwd_dkv_dq"])
def test_the_grid_and_specs_of_a_group_of_eight(kernel, window, monkeypatch):
    """Two batch rows of 16 query heads over 2 key-value heads, T 512 at
    tiles of 128. Forward and dq: the parent's grid, k and v named at
    `bh // 8`. dk/dv alone: the column's walk runs over the group's heads.
    All three gradients: the group's head is the second grid dimension, and
    dk and dv leave as the key-value row's whole block, as a head's dq
    does."""
    if kernel in TWO:
        monkeypatch.setattr(fa, "flash_bwd_kernels", lambda *a, **kw: TWO)
    q = jax.ShapeDtypeStruct((2, 512, 16, D), jnp.float32)
    k = jax.ShapeDtypeStruct((2, 512, 2, D), jnp.float32)
    params = pallas_calls(jax.grad(lambda *a: flash_attention(
        *a, causal=True, window=window, block_q=128, block_k=128,
        interpret=True).sum(), (0, 1, 2)), q, k, k)[
            kernel + ("" if window is None else "_window")]
    grid = params["grid_mapping"].grid
    semantics = params["compiler_params"]["mosaic_tpu"].dimension_semantics
    steps = 4 if window is None else 3  # window 200: a band of three tiles
    tile, stat = (1, 128, D), (1, 128, 8)
    # query row 21 is batch row 1, head 5: key-value row 2 (batch 1, head 0)
    if kernel in ("flash_fwd", "flash_bwd_dq"):
        assert grid == (32, 4, steps)
        assert semantics == ("parallel", "parallel", "arbitrary")
        first = 0 if window is None else 1  # of q row 3's band: tiles 1 to 3
        q_at, k_at = (tile, (21, 3, 0)), (tile, (2, first + 1, 0))
        got = blocks_at(params, 21, 3, 1)
        if kernel == "flash_fwd":
            assert got == [q_at, k_at, k_at, q_at, (stat, (21, 3, 0))]
        else:
            assert got == [q_at, k_at, k_at, q_at, (stat, (21, 3, 0)),
                           (stat, (21, 3, 0)), q_at]
        return
    # key column 1's walk starts at q tile 1 under a window (the band grid)
    # and is clamped to it under causal; its step 2 is q tile 3 or 2
    qi = 3 if window is not None else 2
    q_at, k_at = (tile, (21, qi, 0)), (tile, (2, 1, 0))
    inputs = [q_at, k_at, k_at, q_at, (stat, (21, qi, 0)), (stat, (21, qi, 0))]
    if kernel == "flash_bwd_dkv":
        assert grid == (4, 4, 8 * steps)
        assert semantics == ("parallel", "parallel", "arbitrary")
        # the walk's step 5 * steps + 2 is head 5's step 2
        assert blocks_at(params, 2, 1, 5 * steps + 2) == inputs + [k_at, k_at]
        return
    assert grid == (4, 8, 4, steps)
    assert semantics == ("parallel", "arbitrary", "arbitrary", "arbitrary")
    row = (1, 512, D)
    assert blocks_at(params, 2, 5, 1, 2) == inputs + [
        (row, (2, 0, 0)), (row, (2, 0, 0)), (row, (21, 0, 0))]
    # the f32 sums: dk's and dv's of the key-value row, dq's of the head
    assert [tuple(aval.shape) for aval in
            params["grid_mapping"].scratch_avals] == [(512, D)] * 3
    tiles = fa.flash_tiles(kernel, 512, 512, D, jnp.float32, block_q=128,
                           block_k=128, window=window, group=8)
    assert params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes == (
        tiles.vmem_limit_bytes)
    alone = fa.flash_tiles(kernel, 512, 512, D, jnp.float32, block_q=128,
                           block_k=128, window=window)
    # three more key tiles of dk and dv: two blocks each and an f32 sum
    assert tiles.vmem_bytes - alone.vmem_bytes == 3 * 2 * 128 * 128 * (
        2 * 4 + 4)


# ----------------------------------------------- the group's sums, by step

def walk_the_group(with_dq, seq, window, group, monkeypatch):
    """`_attn_bwd_dkv_kernel` run step by step over the grid of one
    key-value row with `pl.when` recording what fires:
    {(head, ki, step): [names]}."""
    from jax.experimental import pallas as pl

    bq = bk = 128
    num = -(-seq // 128)
    kernel = "flash_bwd_dkv_dq" if with_dq else "flash_bwd_dkv"
    steps = fa._inner_steps(kernel, seq, seq, bq, bk, window)
    at, fired = {}, {}

    def when(condition):
        def record(fn):
            if bool(condition):
                fired[at["key"]].append(fn.__name__)
        return record

    monkeypatch.setattr(pl, "program_id", lambda axis: jnp.int32(at[axis]))
    monkeypatch.setattr(pl, "when", when)
    monkeypatch.setattr(pl, "multiple_of", lambda x, m: x)
    body = functools.partial(
        fa._attn_bwd_dkv_kernel, *[None] * (12 if with_dq else 10),
        block_q=bq, block_k=bk, num_q=num, num_k=num, steps=steps, scale=1.0,
        causal=True, seq_q=seq, seq_k=seq, window=window, with_dq=with_dq,
        group=group)
    # the grid's order: with dq the head is outside the key tiles, without
    # it inside them, its walks one after another
    order = ([(head, ki, step) for head in range(group) for ki in range(num)
              for step in range(steps)] if with_dq else
             [(head, ki, step) for ki in range(num) for head in range(group)
              for step in range(steps)])
    for head, ki, step in order:
        at.update({1: head, 2: ki, 3: step} if with_dq else
                  {1: ki, 2: head * steps + step})
        at["key"] = (head, ki, step)
        fired[head, ki, step] = []
        body()
    return fired, order, steps, num


@pytest.mark.parametrize("with_dq", [True, False], ids=["one", "two"])
@pytest.mark.parametrize("seq,window", [(512, None), (512, 200), (400, 130),
                                        (512, 64)])
@pytest.mark.parametrize("group", [2, 8])
def test_dk_and_dv_are_zeroed_and_rounded_out_once_a_key_value_row(
        group, seq, window, with_dq, monkeypatch):
    """A key tile's rows of dk and dv are zeroed at its first step under
    the group's first head and rounded out at its last under the group's
    last, once each, with every head's bodies between; and with dq a head's
    row of dq is zeroed and rounded out inside that head's own key walk,
    q tile by q tile, as it is without a group."""
    fired, order, steps, num = walk_the_group(
        with_dq, seq, window, group, monkeypatch)
    cols = (fa._band_cols(seq, seq, 128, 128, window) if window else
            [(ki, num - 1) for ki in range(num)])
    for ki, (first_q, last_q) in enumerate(cols):
        mine = [(head, step) for head, k, step in order if k == ki]
        names = [fired[head, ki, step] for head, step in mine]
        flat = [name for at_step in names for name in at_step]
        assert flat.count("_init") == 1 and "_init" in names[0]
        assert mine[0] == (0, 0)
        assert flat.count("_flush") == 1 and "_flush" in names[-1]
        assert mine[-1] == (group - 1, steps - 1)
        # every head's bodies: the column's q tiles with one, each once
        bodies = [(head, step) for (head, step), at_step in zip(mine, names)
                  if "<lambda>" in at_step]
        offset = first_q if window else 0  # the band grid starts at the band
        assert bodies == [(head, step) for head in range(group)
                          for step in range(first_q - offset,
                                            last_q - offset + 1)]
        assert all(at_step.count("<lambda>") <= 1 for at_step in names)
    if not with_dq:
        assert not any("dq" in name for names in fired.values()
                       for name in names)
        return
    rows = (fa._band_rows(seq, seq, 128, 128, window) if window else
            [(0, qi) for qi in range(num)])
    for head in range(group):
        for qi, (first_k, last_k) in enumerate(rows):
            events = [
                (ki, name) for h, ki, step in order for name in fired[h, ki, step]
                if h == head and name not in ("_init", "_flush")
                and (cols[ki][0] if window else 0) + step == qi]
            assert events[0] == (first_k, "_init_dq")
            # without a window the row is rounded out in the last column
            assert events[-1] == (last_k if window else num - 1, "_flush_dq")
            assert [ki for ki, name in events if name == "<lambda>"] == list(
                range(first_k, last_k + 1))
            assert len(events) == last_k - first_k + 3
    assert sum(name in ("_init_dq", "_flush_dq") for names in fired.values()
               for name in names) == 2 * num * group


# ------------------------------------------------------------ the log line

def test_the_backward_s_line_says_the_group(caplog):
    fa._log_bwd_kernels.cache_clear()
    q, k, v, _ = operands(1, 1, 8, jnp.float32, seq=128)

    def grad(q, k, v):  # traced, not run: the line is the trace's
        return jax.eval_shape(jax.grad(lambda *a: flash_attention(
            *a, causal=True, interpret=True).sum(), (0, 1, 2)), q, k, v)

    with caplog.at_level(logging.INFO, logger=fa.logger.name):
        grad(q, k, v)
        grad(q * 2, k, v)  # the same shape again: no second line
        grad(q[:, :, :1], k, v)
    lines = [r.getMessage() for r in caplog.records
             if "flash backward" in r.getMessage()]
    assert len(lines) == 2
    assert lines[0].endswith(
        "flash_bwd_dkv_dq, tile 128 x 128, VMEM %d bytes of a limit of %d, "
        "8 query heads a key-value head by index map, out by the row's "
        "blocks" % fa.flash_tiles(
            "flash_bwd_dkv_dq", 128, 128, D, jnp.float32, group=8)[4:6])
    assert lines[1].endswith(", no group, out by the row's blocks")


def test_the_backward_s_line_says_what_leaves_padded(caplog):
    """At the three shapes PR 75 moved to the tile exit
    (`phi4flash.tokens16k` whole, `granite4hmicro.longctx`,
    `kimilinear.tokens16k`'s latent layer) the line names the arrays that
    leave wider than they are; at whole lanes (`mellum2.ep4`) it ends as it
    did."""
    fa._log_bwd_kernels.cache_clear()
    one = ("flash_bwd_dkv_dq",)
    with caplog.at_level(logging.INFO, logger=fa.logger.name):
        for T, width, v_width, group in [(16384, 64, 128, 2), (32768, 64, 64, 4),
                                         (16384, 192, 128, 1),
                                         (16384, 128, 128, 8)]:
            fa._log_bwd_kernels(one, T, T, width, v_width, "bfloat16", True,
                                None, None, group=group)
    held = ", out a tile at a time by DMA, the row's f32 sums alone held"
    lines = [r.getMessage() for r in caplog.records]
    assert "flash_bwd_dkv_dq, tile 1024 x 768, VMEM 46006272 bytes" in lines[0]
    assert lines[0].endswith(
        "2 query heads a key-value head by index map" + held
        + ", padded to whole lanes: dq 64 as 128, dk 64 as 128")
    assert "flash_bwd_dkv_dq, tile 768 x 768, VMEM 49152000 bytes" in lines[1]
    assert lines[1].endswith(
        held + ", padded to whole lanes: dq 64 as 128, dk 64 and dv 64 wide "
        "as the columns of one array of 128")
    assert lines[2].endswith(
        "no group" + held + ", padded to whole lanes: dq 192 as 256")
    assert lines[3].endswith("by index map" + held)


# -------------------------------------- the row-long gradients' two exits

def backward_operands(group, seq, width, v_width, window, masked):
    """q, k, v, do, lse, delta of two key-value rows folded, `group` query
    rows to each, with the tile's keywords; under `masked` a seeded choice
    of pairs as the data mask, in key tiles of 128."""
    shapes = [(KV_ROWS * group, seq, width), (KV_ROWS, seq, width),
              (KV_ROWS, seq, v_width), (KV_ROWS * group, seq, v_width)]
    q, k, v, do = (jax.random.normal(key(i), shape).astype(jnp.bfloat16)
                   for i, shape in enumerate(shapes))
    mask = None
    if masked:  # one batch row: every head reads the same choice
        keep = jax.random.bernoulli(key(9), 0.5, (1, seq, -(-seq // 128), 128))
        mask = fa._pack_bits(keep).transpose(0, 2, 1, 3)
    how = dict(causal=True, scale=width ** -0.5, block_q=128, block_k=128,
               interpret=True, window=window, mask=mask)
    o, lse = fa._flash_fwd(q, k, v, with_lse=True, **how)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 8))
    return (q, k, v, do, lse, delta), how


@pytest.mark.parametrize("seq,width,v_width,window,masked", [
    (256, 32, 32, None, False), (256, 32, 32, None, True),
    (200, 32, 32, None, False), (200, 32, 32, None, True),
    (384, 32, 32, 130, False),  # a data mask is walked with no window
    (256, 64, 32, None, False), (256, 64, 32, None, True),
    # dk and dv too wide for one tile of lanes between them
    (256, 64, 128, None, False), (200, 96, 64, None, True),
], ids=["causal", "causal-mask", "ragged", "ragged-mask", "window",
        "two-widths", "two-widths-mask", "64-128", "96-64-ragged-mask"])
@pytest.mark.parametrize("group", [1, WIDEST])
def test_a_tile_at_a_time_is_the_row_s_block_to_the_bit(
        group, seq, width, v_width, window, masked):
    """`flash_bwd_dkv_dq` with its row-long gradients leaving by DMA a tile
    at a time (forced here: the planner takes that exit only where the
    row's blocks do not fit) against the same kernel with the row's blocks
    and against `flash_bwd_dq` + `flash_bwd_dkv`: dq, dk and dv equal to the
    bit, every tile written, and no row-long block handed to the call."""
    operands_, how = backward_operands(
        group, seq, width, v_width, window, masked)
    two = (fa._flash_bwd_dq(*operands_, **how),
           *fa._flash_bwd_dkv(*operands_, **how))
    block = fa._flash_bwd_dkv(*operands_, with_dq=True, **how)
    by_tile = functools.partial(fa._flash_bwd_dkv, with_dq=True,
                                by_tile=True, **how)
    for ours, theirs, parents_ in zip(by_tile(*operands_), block, two):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
        assert float(jnp.abs(ours.astype(jnp.float32)).max()) > 0.01
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(parents_))
    name = "flash_bwd_dkv_dq" + ("_sparse" if masked else
                                 "_window" if window else "")
    mappings = pallas_calls(by_tile, *operands_)[name][
        "grid_mapping"].block_mappings
    rows = -(-seq // 128) * 128
    # what lies where the compiler put it has the array for its block,
    # whole lanes wide: a DMA to its rows takes no less (dk's and dv's
    # tiles stay the pipeline's without a group, at their own widths; under
    # one, widths that fill no more than a tile of lanes between them leave
    # as the columns of one array)
    lanes = fa._whole_lanes
    if group == 1:
        expected = [(1, 128, width), (1, 128, v_width)]  # dk, dv
    elif width + v_width <= 128:
        expected = [(KV_ROWS, rows, 128)]  # dk | dv
    else:
        expected = [(KV_ROWS, rows, lanes(width)),
                    (KV_ROWS, rows, lanes(v_width))]
    expected.append((KV_ROWS * group, rows, lanes(width)))  # dq
    mappings = mappings[-len(expected):]
    assert [tuple(getattr(b, "block_size", b) for b in m.block_shape)
            for m in mappings] == expected
    assert [str(m.block_aval.memory_space) for m in mappings] == (
        ["None", "None", "any"] if group == 1 else ["any"] * len(expected))


# (query heads a key-value head, q and k's width, v's): `phi4flash.tokens16k`'s
# paired heads, dk padded to whole lanes and dv whole; and
# `granite4hmicro.longctx`'s, dk and dv the halves of one tile of lanes
NARROW = {"64-128-group2": (2, 64, 128), "64-64-group4": (4, 64, 64)}


@pytest.mark.parametrize("seq,window", [(256, None), (200, None), (384, 130)],
                         ids=["causal", "ragged", "window"])
@pytest.mark.parametrize("heads", list(NARROW))
def test_heads_narrower_than_a_lane_tile_leave_by_tile(heads, seq, window):
    """The one kernel by tile at heads of 64, as `flash_tiles` now offers
    it: dq (and under the group dk, or dk beside dv) leaves padded to whole
    lanes and the caller takes the columns. dq, dk and dv finite, equal to
    `flash_bwd_dq` + `flash_bwd_dkv`'s to the bit, and to the masked
    softmax's within the chip smoke's tolerance."""
    group, width, v_width = NARROW[heads]
    (q, k, v, do, lse, delta), how = backward_operands(
        group, seq, width, v_width, window, False)
    ours = fa._flash_bwd_dkv(q, k, v, do, lse, delta, with_dq=True,
                             by_tile=True, **how)
    pair = (fa._flash_bwd_dq(q, k, v, do, lse, delta, **how),
            *fa._flash_bwd_dkv(q, k, v, do, lse, delta, **how))

    def unfolded(x):  # [rows, seq, d] as the softmax's [1, seq, rows, d]
        return x.astype(jnp.float32).transpose(1, 0, 2)[None]

    with jax.default_matmul_precision("highest"):
        softmax = jax.vjp(
            functools.partial(masked_softmax, window=window),
            *map(unfolded, (q, k, v)))[1](unfolded(do))
    for grad, theirs, wanted, like in zip(ours, pair, softmax, (q, k, v)):
        assert grad.shape == like.shape and grad.dtype == like.dtype
        assert np.isfinite(np.asarray(grad, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(grad), np.asarray(theirs))
        assert rel_err(unfolded(grad), wanted) <= chip_smoke.KERNEL_TOLERANCE


# Every token cell's attention shapes as its step hands them to the plan
# (recorded while each cell's step was lowered, PR 75): (T, S, q and k's
# width, v's, the rest of the shape) -> (tile, exit) of the one kernel.
# Up to `lagunaxs2` the row's blocks leave the cheapest tile its room; from
# `keyevl2` on the sums alone do, at whole lanes since PR 49; the last four
# are PR 75's: rows of 192 and 64 leave a tile at a time padded to whole
# lanes (`kimilinear` left by its blocks at 1024 x 512; `phi4flash` and
# `granite` ran `flash_bwd_dq` and `flash_bwd_dkv`).
CELLS = {
    "mistral7b": (4096, 4096, 128, 128, dict(group=4), (1024, 1024), "block"),
    "olmoe": (4096, 4096, 128, 128, {}, (1024, 1024), "block"),
    "lfm2moe": (8192, 8192, 64, 64, dict(group=4), (1024, 1024), "block"),
    "dsv2lite": (8192, 8192, 192, 128, {}, (1024, 1024), "block"),
    "nemotron3nano": (8192, 8192, 128, 128, dict(group=16), (1024, 1024),
                      "block"),
    "solaropen2": (8192, 8192, 128, 128, dict(group=8), (1024, 1024), "block"),
    "ouro": (16384, 16384, 128, 128, {}, (1024, 1024), "block"),
    "evabyte-windows": (2048, 2048, 128, 128, {}, (512, 512), "block"),
    "evabyte-stair": (8192, 512, 128, 128, dict(
        causal=False, stair=(2048, 128)), (1024, 128), "block"),
    "lagunaxs2-full": (8192, 8192, 128, 128, dict(group=6), (1024, 1024),
                       "block"),
    "lagunaxs2-sliding": (8192, 8192, 128, 128, dict(group=8, window=512),
                          (512, 512), "block"),
    "keyevl2": (16384, 16384, 128, 128, dict(
        group=8, sparse=True, block_k=1024), (512, 1024), "tile"),
    "mellum2-full": (16384, 16384, 128, 128, dict(group=8), (1024, 768),
                     "tile"),
    "mellum2-sliding": (16384, 16384, 128, 128, dict(group=8, window=1024),
                        (512, 512), "tile"),
    "sdar": (16384, 16384, 128, 128, dict(
        causal=False, group=16, stair=(4, 4)), (1024, 768), "tile"),
    "kimilinear-latent": (16384, 16384, 192, 128, {}, (1024, 1024), "tile"),
    "phi4flash-whole": (16384, 16384, 64, 128, dict(group=2), (1024, 768),
                        "tile"),
    "phi4flash-sliding": (16384, 16384, 64, 128, dict(group=2, window=512),
                          (512, 512), "tile"),
    "granite4hmicro": (32768, 32768, 64, 64, dict(group=4), (768, 768),
                       "tile"),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_cell_s_backward_is_the_one_kernel_at_its_tile_and_exit(cell):
    T, S, width, v_width, rest, tile, exit = CELLS[cell]
    shape = dict(v_dim=v_width, **rest)
    assert fa.flash_bwd_kernels(T, S, width, jnp.bfloat16, **shape) == (
        "flash_bwd_dkv_dq",)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", T, S, width, jnp.bfloat16,
                           **shape)
    assert (tiles[:2], tiles.exit) == (tile, exit)
    assert tiles.vmem_bytes < tiles.vmem_limit_bytes <= fa._MAX_VMEM
    if exit == "tile":  # no tile has room beside the row's blocks, or only
        # a costlier one: the sums alone were not taken for their own sake
        blocks = [fa._vmem_bytes(
            "flash_bwd_dkv_dq", *tile, width, 2, v_width, T, S,
            group=rest.get("group", 1), sparse=rest.get("sparse", False))]
        assert 2 * blocks[0] > fa._MAX_VMEM


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_cell_s_log_line_names_its_exit(cell):
    """What the backward's line ends with a cell (`_exit_said`): the exit,
    and by tile what leaves wider than it is; nothing of another kernel."""
    T, S, width, v_width, rest, _, exit = CELLS[cell]
    group = rest.get("group", 1)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", T, S, width, jnp.bfloat16,
                           v_dim=v_width, **rest)
    said = fa._exit_said("flash_bwd_dkv_dq", tiles, width, v_width, group)
    held = ", out a tile at a time by DMA, the row's f32 sums alone held"
    narrow = ", padded to whole lanes: dq 64 as 128, "
    assert said == {
        "block": ", out by the row's blocks", "tile": held}[exit] + {
        "kimilinear-latent": ", padded to whole lanes: dq 192 as 256",
        "phi4flash-whole": narrow + "dk 64 as 128",
        "phi4flash-sliding": narrow + "dk 64 as 128",
        "granite4hmicro": narrow + "dk 64 and dv 64 wide as the columns of "
                          "one array of 128",
    }.get(cell, "")
    assert fa._exit_said("flash_bwd_dkv", tiles, width, v_width, group) == ""


def test_the_padded_columns_are_in_the_plan_s_cost():
    """A row-long gradient of no whole lanes leaves at whole lanes and a
    slice follows: twice the padded array in HBM, at a v5e's 819 GB/s, which
    the tile exit's plan pays and the row's blocks do not. At whole lanes
    nothing; dk and dv that share a tile of lanes are split after."""
    moved = fa._padded_exit_bytes
    assert moved(16384, 16384, 128, 128, 2, group=8) == 0
    assert moved(16384, 16384, 192, 128, 2) == 2 * 16384 * 256 * 2
    assert moved(16384, 16896, 64, 128, 2, group=2) == (
        2 * 16384 * 128 * 2 + 2 * 16896 * 128 * 2 // 2)
    assert moved(33024, 33024, 64, 64, 2, group=4) == (
        2 * 33024 * 128 * 2 + 2 * 33024 * 128 * 2 // 4)
    shape = (16384, 16384, 192, jnp.bfloat16)
    by_tile = fa.flash_tiles("flash_bwd_dkv_dq", *shape, v_dim=128)
    whole = fa.flash_tiles("flash_bwd_dkv_dq", *shape[:2], 256, jnp.bfloat16,
                           v_dim=128)
    # q and k of 256, whole lanes: the same tile and exit, nothing padded
    assert (*by_tile[:2], by_tile.exit) == (*whole[:2], whole.exit)
    assert by_tile.cost_us - whole.cost_us == pytest.approx(
        2 * 16384 * 256 * 2 / 819e3)
    # `kimilinear.tokens16k`'s latent layer: cheaper by tile with the term in
    forced = fa.flash_tiles("flash_bwd_dkv_dq", *shape, v_dim=128,
                            block_q=1024, block_k=512)
    assert forced.exit == "block" and by_tile.cost_us < forced.cost_us


KEYE = dict(block_k=1024, group=8, sparse=True)  # keyevl2.tokens16k's layer


def test_keye_s_backward_is_the_one_kernel_with_its_sums_alone():
    """T 16,384 at 8 query heads a key-value head in bf16: 25.2 MB of f32
    sums beside a tile of 512 x 1024 where the row's blocks, twice 12.6 MB
    more, left room for none."""
    shape = (16384, 16384, 128, jnp.bfloat16)
    assert fa.flash_bwd_kernels(*shape, **KEYE) == ("flash_bwd_dkv_dq",)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", *shape, **KEYE)
    assert tiles[:2] == (512, 1024) and tiles.exit == "tile"
    assert tiles.vmem_bytes == 41_156_608
    assert tiles.vmem_limit_bytes == 2 * tiles.vmem_bytes <= fa._MAX_VMEM
    sums = 4 * 128 * (16384 + 2 * 16384)
    assert sums == 25_165_824 < tiles.vmem_bytes
    # the row's blocks: no tile fits beside them, as the parent found
    for block_q in (128, 256, 512, 1024):
        held = fa._vmem_bytes("flash_bwd_dkv_dq", block_q, 1024, 128, 2, 128,
                              16384, 16384, group=8, sparse=True)
        assert 2 * held > fa._MAX_VMEM
    assert fa.flash_bwd_kernels(*shape, **dict(KEYE, group=1)) == (
        "flash_bwd_dkv_dq",)
    assert fa.flash_tiles("flash_bwd_dkv_dq", *shape,
                          **dict(KEYE, group=1)).exit == "block"


@pytest.mark.parametrize("cell,seq,width,v_width,group,window,tile,held", [
    ("mistral7b.tokens4k", 4096, 128, 128, 4, None, (1024, 1024), 37_748_736),
    ("mistral7b.fsdp4", 4096, 128, 128, 4, None, (1024, 1024), 37_748_736),
    ("olmoe.tokens4k", 4096, 128, 128, 1, None, (1024, 1024), 31_457_280),
    ("lfm2moe.tokens8k", 8192, 64, 64, 4, None, (1024, 1024), 50_331_648),
    ("dsv2lite.tokens8k", 8192, 192, 128, 1, None, (1024, 1024), 46_137_344),
    ("nemotron3nano.tokens8k", 8192, 128, 128, 16, None, (1024, 1024),
     50_331_648),
    ("lagunaxs2.tokens8k, full", 8192, 128, 128, 6, None, (1024, 1024),
     50_331_648),
    ("lagunaxs2.tokens8k, sliding", 8192, 128, 128, 8, 512, (512, 512),
     32_505_856),
])
def test_a_cell_whose_row_s_blocks_fit_keeps_them(cell, seq, width, v_width,
                                                  group, window, tile, held):
    """The seven other token cells' backward as PR 48's planner chose it
    (its answers, computed on that tree): the one kernel, the tile, the
    estimate, and the row's blocks for its exit. Three stand at the limit to
    the byte."""
    shape = dict(v_dim=v_width, window=window, group=group)
    assert fa.flash_bwd_kernels(seq, seq, width, jnp.bfloat16, **shape) == (
        "flash_bwd_dkv_dq",)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", seq, seq, width, jnp.bfloat16,
                           **shape)
    assert tiles[:2] == tile and tiles.exit == "block", cell
    assert tiles.vmem_bytes == held
    assert tiles.vmem_limit_bytes == 2 * held <= fa._MAX_VMEM
