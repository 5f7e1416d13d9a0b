"""The pieces of a routed (mixture-of-experts) feed-forward, dropless.

A token's router probabilities pick `k` of `E` experts; the `T x k` slots
are sorted by expert (a stable sort, so a token's order inside a group is its
order in the batch), each group of rows goes through its own expert's
weights, and the results come back to the tokens weighted by the router's
probabilities. No capacity factor: every slot is computed whatever the
imbalance, and the group sizes always sum to `T x k`.

    route(logits, k)                 float32 softmax or sigmoid, the k largest
    sort_slots(index, E)             order, its inverse, rows per expert
    sort_slots(index, E, (first, n)) the same for a share of the experts
    dispatch(x, order, inverse)      [T, d] -> [T k, d], rows by expert
    grouped_matmul(x, w, sizes)      rows of group e times w[e]
    load_balancing_loss, sequence_balancing_loss
                                     over the batch, or per sequence
    combine(ys, weights, inverse)    [T k, d] -> [T, d]
    combine_held(ys, weights, order, rows)   the same from a share's rows
    project_and_combine(hidden, w_down, weights, slots)
                                     the last two as one operation
    experts_of_share(tokens, w_gate, w_up, w_down, weights, slots)
                                     the whole layer over a share's rows
                                     (`w_gate` None: ungated `relu2` experts)

`grouped_matmul` is a matmul whose row groups go to different weights. On a
TPU it is two Pallas kernels under a `custom_vjp`, after the grouped matmul
that JAX ships as an example (`jax.experimental.pallas.ops.tpu.megablox`),
rewritten here because that one is an experimental module with another
tiling, no names and no f32 weight gradient:

- `moe_gmm`: rows `[M, K]` sorted by group times `[E, K, N]` (or, with
  `transpose_w`, `[E, N, K]`) gives `[M, N]`. The forward product, and on the
  transposed weights the gradient of the rows.
- `moe_tgmm`: per group `x^T dy`, `[E, K, N]`: the gradient of the weights,
  accumulated in f32 and written in the weights' own dtype, so f32 master
  weights get an f32 gradient that was never rounded to bf16.

Both walk a static grid of `M / tm + E - 1` row steps. A step is one (row
tile, group) pair: a row tile that holds the boundary of two groups is
visited once for each, and the rows of the other group are masked. Which
tile and which group a step works on is computed outside the kernel from the
group sizes and handed over as scalar-prefetch arrays, which the index maps
read. So the work is proportional to the `T x k` rows, not to `E x T`; a group
of no rows takes no step in `moe_gmm` and one step (that writes zeros) in
`moe_tgmm`; steps past the last one name the block already in VMEM and
have no body. Operands reach the MXU in the rows' dtype (bf16 in training)
and every dot accumulates in f32.

Anywhere but on a TPU `grouped_matmul` is `jax.lax.ragged_dot`, which XLA
differentiates itself; `impl="auto"` resolves as attention's does.

A layer that holds a share of the experts (`held = (first, n)`, as one chip
of an expert-parallel deployment does) still routes over all `E`:
`sort_slots` puts the slots of its `n` experts first and every other slot
in a tail behind them, `group_sizes` is `[n]` and sums to the held rows, and
the kernels walk those rows only. Rows past them are never written and hold
whatever the buffer held, so whoever brings rows back to their tokens is
given the count of held rows (`rows`) and *selects* with it (a scatter-add
that drops the others, a gather of scalars that selects): nothing is
multiplied by a zero. `experts_of_share` is that layer: it walks the held rows in
buffers of `held_chunk` rows, as many as the step's routing fills.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (
    _DEFAULT_VMEM, _LANES, _MAX_VMEM, _NN, _NT, _TN, _cdiv, _dot, resolve_impl)

# ----------------------------------------------------------------- routing


def route(logits, k: int, renormalize: bool = False, *,
          score: str = "softmax", bias=None, eps: float = 0.0):
    """(scores [T, E], weights [T, k], index [T, k]) of router logits
    [T, E]: float32 scores over the experts (`score`: their softmax, or each
    logit's sigmoid) and the k largest, in falling order. `renormalize`
    divides the k weights by their sum plus `eps` (`norm_topk_prob`);
    otherwise they are the scores as they are.

    With a `bias` [E] the k experts are those of the largest `scores +
    bias`, in that order, and the weights are still their unbiased scores:
    the bias steers the choice and nothing else (arXiv:2408.15664), and no
    gradient reaches it."""
    logits = logits.astype(jnp.float32)
    probs = (jax.nn.sigmoid(logits) if score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        weights, index = jax.lax.top_k(probs, k)
    else:
        _, index = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        # the chosen experts' scores, selected: a `take_along_axis` of T k
        # scalars is a gather that takes 1.3 ms of the chip for 131,072
        chosen = index[..., None] == jnp.arange(probs.shape[-1], dtype=index.dtype)
        weights = jnp.where(chosen, probs[:, None, :], 0.0).sum(axis=-1)
    if renormalize:
        total = weights.sum(axis=-1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    return probs, weights, index


def update_expert_bias(bias, load, rate: float):
    """The selection bias after a step: `rate` up for an expert that took
    fewer slots than the mean of `load` [..., E], `rate` down for one that
    took more (arXiv:2408.15664, the auxiliary-loss-free balancing rule)."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(load.mean(axis=-1, keepdims=True) - load)


class Slots(NamedTuple):
    """The `T x k` slots (slot `t k + j` is token t's j-th choice) in the
    order the experts take them."""
    order: jax.Array        # [T k] slot ids, sorted by expert, stably
    inverse: jax.Array      # [T k] where slot s stands in that order
    group_sizes: jax.Array  # [E] rows of each expert; sums to T k
    # of a share of the experts: [n] rows of each held expert, whose slots
    # come first in `order`; the slots of all the others are its tail


def sort_slots(index, n_experts: int,
               held: Optional[Tuple[int, int]] = None) -> Slots:
    """Sort the slots of `index` [T, k] by expert; with `held = (first, n)`
    by held expert, every other expert's slots behind them."""
    flat = index.reshape(-1).astype(jnp.int32)
    if held is not None and tuple(held) != (0, n_experts):
        first, n_experts = held
        local = flat - first
        flat = jnp.where(
            jnp.logical_and(local >= 0, local < n_experts), local, n_experts)
    ids = jnp.arange(flat.shape[0], dtype=jnp.int32)
    _, order = jax.lax.sort((flat, ids), num_keys=1, is_stable=True)
    _, inverse = jax.lax.sort((order, ids), num_keys=1)
    return Slots(order, inverse, _per_group(flat, n_experts))


def _per_group(flat, n_groups: int):
    """How many of the ids `flat` [n] name each of `n_groups` groups."""
    groups = jnp.arange(n_groups, dtype=jnp.int32)
    return (flat[:, None] == groups[None, :]).sum(axis=0, dtype=jnp.int32)


def expert_load(index, n_experts: int):
    """Slots of `index` [T, k] per expert, over all `n_experts`: [E] int32."""
    return _per_group(index.reshape(-1).astype(jnp.int32), n_experts)


_HELD_SLACK = 1.25
_HELD_SLACK_BY_LOSS = 1.375


def held_chunk(slots: int, held: int, n_experts: int,
               load_held_even: bool = True) -> int:
    """Rows of the expert-order buffers of a layer that holds `held` of
    `n_experts` experts, of `slots` slots in all: its even share and some
    slack, in whole row tiles. A static length cannot follow the load, so
    `experts_of_share` walks the held rows a chunk of this length at a
    time, as many chunks as the step's routing fills: one, near balance.
    Every pass over a chunk's buffers costs what the buffers hold, not the
    rows in them, so the slack is paid in every step and a second chunk is
    a whole pass more. Where a selection bias holds the load even
    (`load_held_even`) the slack is a quarter: over 96 steps of four seeds
    `lfm2moe.tokens8k`'s held rows stayed within 5 % of even (PERF.md
    section 6, PR 32). Where only a loss term balances the router it is
    three eighths: over 640 layer-steps of eight seeds the held rows of
    `dsv2lite.tokens8k`, in a run's first steps, read 0.73 to 1.32 even
    shares (standard deviation 0.10; 9 above 1.25, 1 above 1.3125), and a
    second chunk cost 22 ms a layer of a 1,125 ms step (PERF.md section 6,
    PR 34)."""
    slack = _HELD_SLACK if load_held_even else _HELD_SLACK_BY_LOSS
    even = _cdiv(slots * held, n_experts)
    tiles = max(1, _cdiv(int(slack * even), _ROW_TILE))
    return min(slots, tiles * _ROW_TILE)


def _by_token(ys, inverse, k, rows=None):
    """Rows in expert order back in slot order: [T, k, d]. `rows` (a
    scalar) is the number of rows that were computed: a slot that stands
    before the buffer or behind those rows is selected away as zeros,
    whatever a row holds."""
    if rows is None:
        picked = ys[inverse]
    else:
        picked = jnp.where(
            jnp.logical_and(inverse >= 0, inverse < rows)[:, None],
            ys[jnp.clip(inverse, 0, ys.shape[0] - 1)],
            jnp.zeros((), ys.dtype))
    return picked.reshape(-1, k, ys.shape[-1])


def _to_tokens(ys, order, rows, tokens: int, k: int):
    """The first `rows` rows of `ys` (in expert order, float32) added up by
    the token they belong to: [tokens, d] float32. One scatter-add of the
    buffer's rows, for a share of the experts, where `_by_token` would
    gather all `tokens x k` slots to select a few of them; a row behind the
    `rows` is given no token and is dropped, whatever it holds."""
    n = ys.shape[0]
    token = jnp.where(jnp.arange(n, dtype=jnp.int32) < rows, order // k, tokens)
    return jnp.zeros((tokens, ys.shape[1]), jnp.float32).at[token].add(
        ys, mode="drop")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, inverse, rows, k):
    return x[order // k]


def _dispatch_fwd(x, order, inverse, rows, k):
    # a share of the experts sums the rows' gradients by `order`
    return x[order // k], (inverse, None if rows is None else order, rows)


def _dispatch_bwd(k, res, dxs):
    inverse, order, rows = res
    if rows is None:
        per_token = _by_token(dxs, inverse, k).astype(jnp.float32)
        dx = per_token.sum(axis=1)
    else:
        dx = _to_tokens(dxs.astype(jnp.float32), order, rows,
                        inverse.shape[0] // k, k)
    return dx.astype(dxs.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def dispatch(x, order, inverse, rows=None):
    """Rows of `x` [T, d] copied to their slots in expert order: [T k, d],
    or as many rows as `order` was cut to. The gradient gathers by the
    inverse order and sums a token's k slots, where XLA's own transpose of
    the gather would scatter-add. With `rows` (a share of the experts: the
    buffer is a fraction of the slots) it does scatter-add, the first `rows`
    rows alone, and a token none of whose experts is held gets zero."""
    return _dispatch(x, order, inverse, rows, inverse.shape[0] // x.shape[0])


def combine(ys, weights, inverse):
    """Expert outputs `ys` [T k, d] in expert order back to tokens [T, d]:
    each token's k rows times its k `weights` [T, k] (float32), summed in
    float32. The plain forward: a routed model takes it inside
    `project_and_combine`, whose backward never needs `ys`."""
    picked = _by_token(ys, inverse, weights.shape[1]).astype(jnp.float32)
    return (picked * weights[..., None]).sum(axis=1).astype(ys.dtype)


def combine_held(ys, weights, order, rows):
    """`combine` for a share of the experts: `ys` [n, d] holds `rows` rows
    that are some held expert's, in the order `order` [n] gives their
    slots. Each such row times its slot's weight, added to its token in
    float32; a token none of whose experts is held gets zero."""
    tokens, k = weights.shape
    weighted = ys.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
    return _to_tokens(weighted, order, rows, tokens, k).astype(ys.dtype)


def load_balancing_loss(probs, group_sizes):
    """`E sum_e f_e P_e`: f_e the share of the slots sent to expert e (no
    gradient flows through a count), P_e the mean router probability of e.
    1 when both are uniform."""
    n_experts = probs.shape[-1]
    share = group_sizes.astype(jnp.float32) / group_sizes.sum()
    return n_experts * jnp.sum(share * probs.mean(axis=0))


def sequence_load(index, n_experts: int, sequences: int):
    """Slots of `index` [B T, k] per sequence and expert, the tokens in `B`
    = `sequences` whole sequences one after another: [B, E] int32. Its sum
    over the sequences is `expert_load`."""
    flat = index.reshape(sequences, -1).astype(jnp.int32)
    return jax.vmap(lambda ids: _per_group(ids, n_experts))(flat)


def sequence_balancing_loss(probs, load):
    """`mean_b sum_e f_be P_be` (`seq_aux`, arXiv:2405.04434): for sequence
    b, f_be the share of its slots sent to expert e times E (no gradient
    flows through a count), P_be the mean of the router's scores `probs`
    [B, T, E] over its tokens; `load` [B, E] is `sequence_load`'s. 1 when
    both are uniform. The batch's `load_balancing_loss` takes both means
    over all the tokens at once, which lets one sequence's skew cancel
    another's."""
    n_experts = probs.shape[-1]
    load = load.astype(jnp.float32)
    share = load * n_experts / load.sum(axis=-1, keepdims=True)
    return jnp.mean(jnp.sum(share * probs.mean(axis=1), axis=-1))


def router_z_loss(logits):
    """Mean over tokens of `logsumexp(logits)^2`, in float32."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(lse * lse)


# ------------------------------------------------------------------- tiles

class GmmTiles(NamedTuple):
    """One kernel's tile for one shape: rows of a step, and the tiles of the
    contracted (`moe_gmm`) or first output (`moe_tgmm`) dimension K and of
    the last dimension N."""
    tm: int
    tk: int
    tn: int
    vmem_limit_bytes: int


def _divisors(size: int):
    """Tiles of a dimension: the whole of it and the multiples of 128 that
    divide it."""
    return sorted({size, *(t for t in range(_LANES, size, _LANES)
                           if size % t == 0)})


def _gmm_vmem(kernel, tm, tk, tn, itemsize, out_itemsize) -> int:
    """Blocks in flight (double-buffered), the f32 accumulator and the f32
    result of a step's dot."""
    if kernel == "moe_gmm":
        blocks = (tm * tk + tk * tn) * itemsize + tm * tn * out_itemsize
        acc = 2 * tm * tn * 4
    else:
        blocks = (tm * tk + tm * tn) * itemsize + tk * tn * out_itemsize
        acc = 2 * tk * tn * 4
    return 2 * blocks + acc


_ROW_TILE = 256


def gmm_tiles(kernel: str, rows: int, k: int, n: int, experts: int, dtype, *,
              out_dtype=None, tm: Optional[int] = None) -> GmmTiles:
    """The tile of `moe_gmm` (rows [rows, k] times [experts, k, n]) or of
    `moe_tgmm` ([rows, k]^T [rows, n] per group) in `dtype`. Pure: the shape
    decides, by the rule the sweep on one v5e supports (PERF.md section 6,
    PR 27; 131,072 rows in 64 groups, weights of [2048, 1024] and
    [1024, 2048], bf16): K and N whole where VMEM allows, else the largest
    of their tiles that fit, K before N (a K tile short of the whole K
    fetches the weights again at every step: 8.4 ms a call against 3.7);
    256 rows a step, fewer where there are fewer. Called alone the kernels
    read within 3 % of each other at 128 to 512 rows and up to 20 % slower
    at 1,024; in the step, 256 rows for both gave 0.63 % more tokens/s than
    512 (a row tile that holds a group's boundary is computed once for
    each group, and every group adds one). `experts` does not enter the
    rule. A forced `tm` is taken as given, for tests."""
    itemsize = jnp.dtype(dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype or dtype).itemsize
    sublanes = 32 // itemsize  # rows of a packed VMEM tile: 8 f32, 16 bf16
    tm = tm or min(_ROW_TILE, _cdiv(rows, sublanes) * sublanes)
    plans = [
        GmmTiles(tm, tk, tn, max(_DEFAULT_VMEM, 2 * _gmm_vmem(
            kernel, tm, tk, tn, itemsize, out_itemsize)))
        for tk in _divisors(k) for tn in _divisors(n)]
    plans.sort(key=lambda t: (t.tk * t.tn, t.tk), reverse=True)
    fitting = [t for t in plans if t.vmem_limit_bytes <= _MAX_VMEM]
    return (fitting or plans[-1:])[0]


# ---------------------------------------------------------------- metadata

def _row_steps(group_sizes, rows: int, tm: int, visit_empty: bool):
    """The (row tile, group) pairs the kernels walk, as arrays indexed by
    the step: `group_ids`, `tile_ids`, and beside them `offsets` [E + 1]
    (group e holds rows offsets[e] to offsets[e + 1]) and `num_steps` [1].
    `rows` is a multiple of `tm`. The arrays have the static length
    `rows / tm + E - 1`, which no set of group sizes passes; steps from
    `num_steps` on repeat the last real step."""
    n_groups = group_sizes.shape[0]
    tiles_m = rows // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tm, first)
    tiles = jnp.where(sizes > 0, last - first + 1, 1 if visit_empty else 0)
    step_ends = jnp.cumsum(tiles)
    num_steps = step_ends[-1:]
    step = jnp.minimum(
        jnp.arange(tiles_m + n_groups - 1, dtype=jnp.int32), num_steps - 1)
    group_ids = jnp.minimum(
        jnp.searchsorted(step_ends, step, side="right").astype(jnp.int32),
        n_groups - 1)
    tile_ids = first[group_ids] + step - (step_ends - tiles)[group_ids]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group_ids, tile_ids.astype(jnp.int32), offsets, num_steps.astype(jnp.int32)


def _rows_in_group(step, group_ids, tile_ids, offsets, tm):
    """For the tile and the group of `step`: whether all of the tile's rows
    are the group's, and `mine(width)`, the [tm, width] bool of those that
    are."""
    group = group_ids[step]
    start, end = offsets[group], offsets[group + 1]
    row0 = tile_ids[step] * tm

    def mine(width):
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
        return jnp.logical_and(rows >= start, rows < end)

    return jnp.logical_and(start <= row0, row0 + tm <= end), mine


def _pad_rows(x, tm):
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _params(tiles: GmmTiles):
    """The row steps run in order (a tile's visits are consecutive, a
    group's accumulator lives across them); only N's tiles are independent."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=tiles.vmem_limit_bytes,
    )


# ----------------------------------------------------------------- kernels

def _gmm_kernel(group_ids, tile_ids, offsets, num_steps,  # scalar prefetch
                x_ref, w_ref, o_ref, *acc, tm, tn, tiles_k, transpose_w):
    """`acc` is the f32 accumulator over K's tiles; none when K is whole."""
    from jax.experimental import pallas as pl

    step, ki = pl.program_id(1), pl.program_id(2)

    def store(value):
        whole, mine = _rows_in_group(step, group_ids, tile_ids, offsets, tm)

        @pl.when(whole)
        def _all():
            o_ref[...] = value.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _some():  # the other rows are another group's, or no one's
            o_ref[...] = jnp.where(
                mine(tn), value, o_ref[...].astype(jnp.float32)
            ).astype(o_ref.dtype)

    @pl.when(step < num_steps[0])
    def _body():
        product = _dot(x_ref[...], w_ref[...], _NT if transpose_w else _NN)
        if tiles_k == 1:
            store(product)
            return

        acc_ref, = acc

        @pl.when(ki == 0)
        def _first():
            acc_ref[...] = product

        @pl.when(ki > 0)
        def _next():
            acc_ref[...] += product

        @pl.when(ki == tiles_k - 1)
        def _flush():
            store(acc_ref[...])


def gmm(x, w, group_sizes, *, transpose_w: bool = False,
        tiles: Optional[GmmTiles] = None, interpret: bool = False):
    """`moe_gmm`: `x` [M, K], rows sorted by group, times `w` [E, K, N]
    (`transpose_w`: [E, N, K]) gives [M, N] in x's dtype. The group sizes
    sum to M, as a dropless routing's do: a row past their total belongs to
    no group and is never written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n_groups = w.shape[0]
    n = w.shape[1] if transpose_w else w.shape[2]
    if tiles is None:
        tiles = gmm_tiles("moe_gmm", m, k, n, n_groups, x.dtype)
    tm, tk, tn = tiles.tm, tiles.tk, tiles.tn
    tiles_k, tiles_n = k // tk, n // tn
    xp = _pad_rows(x, tm)
    meta = _row_steps(group_sizes, xp.shape[0], tm, visit_empty=False)

    def x_block(ni, step, ki, group_ids, tile_ids, offsets, num_steps):
        return tile_ids[step], ki

    def w_block(ni, step, ki, group_ids, tile_ids, offsets, num_steps):
        return (group_ids[step], ni, ki) if transpose_w else (
            group_ids[step], ki, ni)

    def o_block(ni, step, ki, group_ids, tile_ids, offsets, num_steps):
        return tile_ids[step], ni

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_n, meta[0].shape[0], tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), x_block),
                pl.BlockSpec((None, tn, tk) if transpose_w else (None, tk, tn),
                             w_block),
            ],
            out_specs=pl.BlockSpec((tm, tn), o_block),
            scratch_shapes=(
                [pltpu.VMEM((tm, tn), jnp.float32)] if tiles_k > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], n), x.dtype),
        compiler_params=_params(tiles),
        interpret=interpret,
        name="moe_gmm",
    )(*meta, xp, w)
    return out[:m]


def _tgmm_kernel(group_ids, tile_ids, offsets, num_steps,  # scalar prefetch
                 x_ref, dy_ref, o_ref, acc_ref, *, tm, tk, tn, tail):
    from jax.experimental import pallas as pl

    step = pl.program_id(2)
    last_step = num_steps[0] - 1
    group = group_ids[step]
    valid = step <= last_step
    first = jnp.logical_or(
        step == 0, group_ids[jnp.maximum(step - 1, 0)] != group)
    last = jnp.logical_or(
        step == last_step, group_ids[jnp.minimum(step + 1, last_step)] != group)

    @pl.when(jnp.logical_and(valid, first))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # rows of another group are zeroed in the narrower operand only: a zero
    # row on one side is a zero product. Not so for the rows behind the last
    # group (`tail`: the groups do not fill the rows), which hold whatever
    # the buffers held, NaN included: there both sides are zeroed.
    mask_x = tk <= tn
    whole, mine = _rows_in_group(step, group_ids, tile_ids, offsets, tm)
    has_rows = offsets[group + 1] > offsets[group]

    @pl.when(jnp.logical_and(valid, jnp.logical_and(has_rows, whole)))
    def _all():
        acc_ref[...] += _dot(x_ref[...], dy_ref[...], _TN)

    @pl.when(jnp.logical_and(valid, jnp.logical_and(
        has_rows, jnp.logical_not(whole))))
    def _some():
        x, dy = x_ref[...], dy_ref[...]
        if mask_x or tail:
            x = jnp.where(mine(tk), x, jnp.zeros_like(x))
        if tail or not mask_x:
            dy = jnp.where(mine(tn), dy, jnp.zeros_like(dy))
        acc_ref[...] += _dot(x, dy, _TN)

    @pl.when(jnp.logical_and(valid, last))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tgmm(x, dy, group_sizes, *, out_dtype=None,
         tiles: Optional[GmmTiles] = None, interpret: bool = False,
         tail: bool = False):
    """`moe_tgmm`: per group `x[rows]^T dy[rows]` for `x` [M, K] and `dy`
    [M, N], rows sorted by group: [E, K, N] in `out_dtype` (x's by
    default), accumulated in f32. A group of no rows gives zeros. `tail`
    says that the groups may end before the rows do, and that what lies
    behind them is not to be trusted to be finite."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = dy.shape[1]
    n_groups = group_sizes.shape[0]
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if tiles is None:
        tiles = gmm_tiles("moe_tgmm", m, k, n, n_groups, x.dtype,
                          out_dtype=out_dtype)
    tm, tk, tn = tiles.tm, tiles.tk, tiles.tn
    xp, dyp = _pad_rows(x, tm), _pad_rows(dy, tm)
    meta = _row_steps(group_sizes, xp.shape[0], tm, visit_empty=True)

    def x_block(ni, ki, step, group_ids, tile_ids, offsets, num_steps):
        return tile_ids[step], ki

    def dy_block(ni, ki, step, group_ids, tile_ids, offsets, num_steps):
        return tile_ids[step], ni

    def o_block(ni, ki, step, group_ids, tile_ids, offsets, num_steps):
        return group_ids[step], ki, ni

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn, tail=tail),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k // tk, meta[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), x_block),
                pl.BlockSpec((tm, tn), dy_block),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), o_block),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), out_dtype),
        compiler_params=_params(tiles),
        interpret=interpret,
        name="moe_tgmm",
    )(*meta, xp, dyp)


# ---------------------------------------------------------- grouped matmul

def _product(x, w, group_sizes, kernels, tm, interpret):
    """Rows of group e of `x` [M, K] times `w[e]` of `w` [E, K, N], cast to
    x's dtype: `moe_gmm`, or `jax.lax.ragged_dot` without the kernels."""
    w = w.astype(x.dtype)
    if not kernels:
        return jax.lax.ragged_dot(x, w, group_sizes)
    e, k, n = w.shape
    return gmm(x, w, group_sizes, interpret=interpret,
               tiles=gmm_tiles("moe_gmm", x.shape[0], k, n, e, x.dtype, tm=tm))


def _rows_gradient(dy, w, group_sizes, kernels, tm, interpret):
    """`dy` [M, N] times `w[e]`^T: the product's transpose in the rows,
    [M, K] in dy's dtype. `moe_gmm` on the transposed weights, or
    `ragged_dot`'s own transpose."""
    e, k, n = w.shape
    w = w.astype(dy.dtype)
    if not kernels:
        rows = jax.ShapeDtypeStruct((dy.shape[0], k), dy.dtype)
        return jax.linear_transpose(
            lambda x: jax.lax.ragged_dot(x, w, group_sizes), rows)(dy)[0]
    return gmm(dy, w, group_sizes, transpose_w=True, interpret=interpret,
               tiles=gmm_tiles("moe_gmm", dy.shape[0], n, k, e, dy.dtype, tm=tm))


def _weights_gradient(x, dy, w, group_sizes, kernels, tm, interpret,
                      tail=False):
    """Per group `x^T dy`: the product's transpose in the weights, in w's
    own dtype. `moe_tgmm`, or `ragged_dot`'s own transpose, which multiplies
    the rows behind the groups (`tail`) by zero: they are zeroed first."""
    if not kernels:
        if tail:
            live = (jnp.arange(x.shape[0]) < group_sizes.sum())[:, None]
            x = jnp.where(live, x, jnp.zeros_like(x))
            dy = jnp.where(live, dy, jnp.zeros_like(dy))
        return jax.linear_transpose(
            lambda w: jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes),
            w)(dy)[0]
    e, k, n = w.shape
    return tgmm(x, dy, group_sizes, out_dtype=w.dtype, interpret=interpret,
                tail=tail,
                tiles=gmm_tiles("moe_tgmm", x.shape[0], k, n, e, x.dtype,
                                out_dtype=w.dtype, tm=tm))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _grouped_matmul(x, w, group_sizes, kernels, tm, interpret, tail):
    return _product(x, w, group_sizes, kernels, tm, interpret)


def _grouped_matmul_fwd(x, w, group_sizes, kernels, tm, interpret, tail):
    return (_product(x, w, group_sizes, kernels, tm, interpret),
            (x, w, group_sizes))


def _grouped_matmul_bwd(kernels, tm, interpret, tail, res, dy):
    x, w, group_sizes = res
    path = (group_sizes, kernels, tm, interpret)
    dy = dy.astype(x.dtype)
    return (_rows_gradient(dy, w, *path),
            _weights_gradient(x, dy, w, *path, tail), None)


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _kernels(impl: str, interpret: bool) -> bool:
    return resolve_impl(impl) == "pallas" or interpret


def grouped_matmul(x, w, group_sizes, *, impl: str = "auto",
                   interpret: bool = False, block_rows: Optional[int] = None,
                   tail: bool = False):
    """Rows of group e of `x` [M, K] (sorted by group, `group_sizes` [E]
    int32 rows each) times `w[e]` of `w` [E, K, N]: [M, N] in x's dtype.
    Where the sizes sum to less than M (`tail`), the rows behind them are
    no group's: they are not computed, the result there is not defined, and
    the gradients take nothing from them.

    `w` may be wider than `x` (f32 master weights under bf16 rows): it is
    cast to x's dtype for the MXU, and its gradient comes back in its own
    dtype. impl: 'auto' (the Pallas kernels on a TPU, `jax.lax.ragged_dot`
    elsewhere) | 'pallas' | 'xla'. `interpret` and `block_rows` (a forced
    row tile) are for tests of the kernels off the chip."""
    group_sizes = group_sizes.astype(jnp.int32)
    kernels = _kernels(impl, interpret)
    if kernels or tail:  # XLA differentiates a whole `ragged_dot` itself
        return _grouped_matmul(x, w, group_sizes, kernels, block_rows,
                               interpret, tail)
    return _product(x, w, group_sizes, False, None, False)


# ------------------------------------- the down projection back to tokens

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _project_and_combine(hidden, w_down, weights, slots, rows, kernels, tm,
                         interpret):
    with jax.named_scope("moe_experts"):
        ys = _product(hidden, w_down, slots.group_sizes, kernels, tm, interpret)
    with jax.named_scope("moe_combine"):
        if rows is None:
            return combine(ys, weights, slots.inverse)
        return combine_held(ys, weights, slots.order, rows)


def _project_and_combine_fwd(hidden, w_down, weights, slots, rows, kernels,
                             tm, interpret):
    out = _project_and_combine(hidden, w_down, weights, slots, rows, kernels,
                               tm, interpret)
    return out, (hidden, w_down, weights, slots, rows)


def _project_and_combine_bwd(kernels, tm, interpret, res, dy):
    """With `dh_u` the gradient of the rows before their weights: the
    weight of slot s is `<dy_g[s], hidden[s] w_down[e]> = <dh_u[s],
    hidden[s]>`, a row sum on the hidden side, and the weights enter the
    other two gradients on that side too. `ys` is never needed."""
    hidden, w_down, weights, slots, rows = res
    path = (slots.group_sizes, kernels, tm, interpret)
    k = weights.shape[1]
    hidden32 = hidden.astype(jnp.float32)
    with jax.named_scope("moe_combine"):
        dy_g = dy[slots.order // k].astype(hidden.dtype)
        w_sorted = weights.reshape(-1)[slots.order][:, None]
    with jax.named_scope("moe_experts"):
        dh_u = _rows_gradient(dy_g, w_down, *path).astype(jnp.float32)
        dhidden = (w_sorted * dh_u).astype(hidden.dtype)
        weighted = (w_sorted * hidden32).astype(hidden.dtype)
        dw_down = _weights_gradient(weighted, dy_g, w_down, *path,
                                    rows is not None)
    with jax.named_scope("moe_combine"):
        dw_sorted = (dh_u * hidden32).sum(axis=1)
        if rows is None:
            dweights = dw_sorted[slots.inverse].reshape(weights.shape)
        else:  # hidden's rows behind the held ones are anything at all
            dweights = _by_token(
                dw_sorted[:, None], slots.inverse, k, rows)[..., 0]
    return dhidden, dw_down, dweights.astype(weights.dtype), None, None


_project_and_combine.defvjp(_project_and_combine_fwd, _project_and_combine_bwd)


def project_and_combine(hidden, w_down, weights, slots: Slots, *,
                        impl: str = "auto", interpret: bool = False,
                        block_rows: Optional[int] = None, rows=None):
    """The experts' down projection and the weighted sum back to tokens:
    `combine(grouped_matmul(hidden, w_down), weights)`, [T, d] of `hidden`
    [T k, f] in expert order, `w_down` [E, f, d] and `weights` [T, k]
    (float32), operation for operation. One differentiable operation so
    that the backward works on the hidden side (f wide, not d): its
    residuals are its arguments, never the [T k, d] rows, which a
    rematerialised block would have to make again and gather again by
    `inverse` for the gradient of the weights. `rows` is `combine`'s: of a
    share of the experts, the number of rows that are some held expert's.
    The other arguments are `grouped_matmul`'s."""
    return _project_and_combine(hidden, w_down, weights, slots, rows,
                                _kernels(impl, interpret), block_rows, interpret)


# ------------------------------------------- a share of the experts' rows

def _chunk_of(slots: Slots, order, i, chunk: int) -> Slots:
    """The held rows `i chunk` to `(i + 1) chunk` as slots of their own:
    their part of `order` (padded to whole chunks), `inverse` counted from
    the chunk's first row (a slot outside it falls before 0 or behind the
    chunk's rows), and what each group has of them."""
    lo = i * chunk
    ends = jnp.cumsum(slots.group_sizes)
    starts = ends - slots.group_sizes
    sizes = jnp.clip(ends, lo, lo + chunk) - jnp.clip(starts, lo, lo + chunk)
    return Slots(jax.lax.dynamic_slice_in_dim(order, lo, chunk),
                 slots.inverse - lo, sizes)


def relu2(x):
    """`relu(x)^2`: the ungated feed-forward's activation."""
    return jnp.square(jax.nn.relu(x))


def _experts_on_chunk(i, tokens, w_gate, w_up, w_down, weights, slots, order,
                      chunk, impl):
    """One chunk of the held rows through its experts and back to the
    tokens, [T, d]: today's layer on buffers of `chunk` rows. Without a
    `w_gate` the experts are ungated, `relu2` between the two products."""
    part = _chunk_of(slots, order, i, chunk)
    rows = part.group_sizes.sum()
    with jax.named_scope("moe_dispatch"):
        xs = dispatch(tokens, part.order, part.inverse, rows)
    with jax.named_scope("moe_experts"):
        gmm = functools.partial(
            grouped_matmul, group_sizes=part.group_sizes, impl=impl, tail=True)
        hidden = (relu2(gmm(xs, w_up)) if w_gate is None
                  else jax.nn.silu(gmm(xs, w_gate)) * gmm(xs, w_up))
    return project_and_combine(hidden, w_down, weights, part, impl=impl,
                               rows=rows)


def _chunks(slots: Slots, chunk: int):
    """`order` padded to whole chunks, and how many of them hold a row."""
    order = jnp.pad(slots.order, (0, (-slots.order.shape[0]) % chunk))
    return order, (slots.group_sizes.sum() + chunk - 1) // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _experts_of_share(tokens, w_gate, w_up, w_down, weights, slots, chunk,
                      impl):
    order, n_chunks = _chunks(slots, chunk)

    def add(i, out):
        return out + _experts_on_chunk(
            i, tokens, w_gate, w_up, w_down, weights, slots, order, chunk,
            impl).astype(jnp.float32)

    out = jax.lax.fori_loop(
        0, n_chunks, add, jnp.zeros(tokens.shape, jnp.float32))
    return out.astype(tokens.dtype)


def _experts_of_share_fwd(tokens, w_gate, w_up, w_down, weights, slots, chunk,
                          impl):
    out = _experts_of_share(tokens, w_gate, w_up, w_down, weights, slots,
                            chunk, impl)
    return out, (tokens, w_gate, w_up, w_down, weights, slots)


def _experts_of_share_bwd(chunk, impl, res, dout):
    """A chunk at a time as the forward: the chunk's rows made again and
    pulled back (the pieces' own backward rules), the gradients summed in
    float32. A loop whose length the routing sets has no transpose of its
    own, which is why the layer is one operation."""
    *args, slots = res
    order, n_chunks = _chunks(slots, chunk)

    def add(i, grads):
        _, pull = jax.vjp(
            lambda *a: _experts_on_chunk(i, *a, slots, order, chunk, impl),
            *args)
        return jax.tree.map(
            lambda g, d: g + d.astype(jnp.float32), grads, pull(dout))

    # an ungated layer's `w_gate` is None: a tree of no leaves, all through
    grads = jax.lax.fori_loop(
        0, n_chunks, add,
        jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tuple(args)))
    return (*jax.tree.map(lambda g, a: g.astype(a.dtype), grads, tuple(args)),
            None)


_experts_of_share.defvjp(_experts_of_share_fwd, _experts_of_share_bwd)


def experts_of_share(tokens, w_gate, w_up, w_down, weights, slots: Slots, *,
                     chunk: int, impl: str = "auto"):
    """The routed feed-forward of a layer that holds a share of the experts
    (`sort_slots(index, E, (first, n))`; `w_*` are the n held experts';
    `w_gate` None for ungated experts, `relu(x W_up)^2 W_down`):
    for every token the weighted sum over those of its experts that are
    held, [T, d]; a token none of whose experts is held gets zero.

    The held rows go through dispatch, the grouped matmuls and the combine
    in buffers of `chunk` rows, as many chunks as the step's routing fills
    (`held_chunk`: one near balance, `T k / chunk` at most), so the work
    follows the held rows whatever the load, nothing is dropped, and no
    buffer is sized for the worst case. One differentiable operation: its
    backward walks the same chunks."""
    return _experts_of_share(tokens, w_gate, w_up, w_down, weights, slots,
                             chunk, impl)
