"""The main path's kernels, compiled for the chip without the chip.

The TPU's compiler is installed with libtpu and compiles for a described
v5e topology that is not attached. It raises what the chip's compiler would
raise (tile alignment, fast-memory limits, a kernel that cannot be
partitioned), which interpret mode cannot show. Nothing runs, so these tests
say nothing about results or times. Shapes are the ones chip_smoke.py
trains: GPT-2-small widths, batch 32, seq 1024, bf16.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import TransformerConfig, make_train_step
from ray_tpu.ops.fused import lm_head_cross_entropy
from ray_tpu.parallel import make_mesh
from v5e_described import v5e  # noqa: F401 - the module's fixture

fa = importlib.import_module("ray_tpu.ops.flash_attention")

BH, T, D = 32 * 12, 1024, 64
KERNEL = dict(causal=True, scale=D ** -0.5, block_q=128, block_k=128,
              interpret=False)


def _shapes(device, bh=BH, t=T, d=D):
    one = SingleDeviceSharding(device)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    qkv = sd((bh, t, d), jnp.bfloat16)
    row = sd((bh, t, 8), jnp.float32)  # lse / delta, sublane-replicated
    return qkv, row


def _flash_case(name, kernel=KERNEL):
    if name == "fwd":
        return lambda q, k, v, do, lse, delta: fa._flash_fwd(q, k, v, **kernel)
    if name == "fwd_lse":
        return lambda q, k, v, do, lse, delta: fa._flash_fwd(
            q, k, v, with_lse=True, **kernel)
    if name == "bwd_dq":
        return lambda *a: fa._flash_bwd_dq(*a, **kernel)
    # the whole backward in one kernel is the dk/dv kernel with dq too
    return lambda *a: fa._flash_bwd_dkv(
        *a, with_dq=name == "bwd_dkv_dq", **kernel)


CASES = ["fwd", "fwd_lse", "bwd_dq", "bwd_dkv", "bwd_dkv_dq"]
KERNEL_OF = {"fwd": "flash_fwd", "fwd_lse": "flash_fwd",
             "bwd_dq": "flash_bwd_dq", "bwd_dkv": "flash_bwd_dkv",
             "bwd_dkv_dq": "flash_bwd_dkv_dq"}


@pytest.mark.parametrize("name", CASES)
def test_flash_kernel_compiles_for_v5e(v5e, name):
    qkv, row = _shapes(v5e[0])
    compiled = jax.jit(_flash_case(name)).lower(
        qkv, qkv, qkv, qkv, row, row
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("bh,t,d", [
    (128, 4096, 128),  # mistral7b.tokens4k (mistral7b.fsdp4: 64 a chip)
    (BH, T, D),        # chip_smoke.py
])
def test_flash_kernel_compiles_at_the_shapes_own_tiles(v5e, name, bh, t, d):
    """`block_q=None`: the tile `flash_tiles` picks for the shape, with the
    VMEM limit it derives. A tile that does not fit fails here, on a CPU."""
    tiles = fa.flash_tiles(KERNEL_OF[name], t, t, d, jnp.bfloat16)
    assert tiles.block_q > 128 and tiles.block_k > 128  # not the old tile
    qkv, row = _shapes(v5e[0], bh, t, d)
    chosen = dict(KERNEL, scale=d ** -0.5, block_q=None, block_k=None)
    compiled = jax.jit(_flash_case(name, chosen)).lower(
        qkv, qkv, qkv, qkv, row, row
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("name", CASES[1:])
def test_flash_kernel_compiles_under_highest_matmul_precision(v5e, name):
    """bf16 operands go to the MXU as they are, and Mosaic refuses
    "highest" for them ("Bad lhs type"): the kernels pin one pass for
    narrow operands, so a process that sets the config keeps its kernel."""
    qkv, row = _shapes(v5e[0])
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(_flash_case(name)).lower(
            qkv, qkv, qkv, qkv, row, row
        ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("bh,t,d,dv", [
    (64, 8192, 192, 128),   # dsv2lite.tokens8k: 16 heads of latent attention
    (128, 8192, 64, 64),    # lfm2moe.tokens8k: 32 heads of 64
    (128, 4096, 128, 128),  # mistral7b.tokens4k
    (64, 4096, 128, 128),   # mistral7b.fsdp4 a chip, olmoe.tokens4k
])
def test_the_one_backward_kernel_compiles_at_the_cells_shapes(
        v5e, bh, t, d, dv):
    """Every token cell's backward is `flash_bwd_dkv_dq` at 1024 x 1024:
    it compiles with the row's dq resident (8.4 MB of f32 at T 8192, D 192)
    inside the limit `flash_tiles` derives, as one custom call by its name,
    and it writes the three gradients in the input's dtype."""
    import re

    assert fa.flash_bwd_kernels(t, t, d, jnp.bfloat16, v_dim=dv) == (
        "flash_bwd_dkv_dq",)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", t, t, d, jnp.bfloat16, v_dim=dv)
    assert (tiles.block_q, tiles.block_k) == (1024, 1024)
    assert tiles.vmem_limit_bytes <= 96 << 20
    one = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16, sharding=one)
    vo = jax.ShapeDtypeStruct((bh, t, dv), jnp.bfloat16, sharding=one)
    _, row = _shapes(v5e[0], bh, t, d)
    chosen = dict(KERNEL, scale=d ** -0.5, block_q=None, block_k=None)
    text = jax.jit(_flash_case("bwd_dkv_dq", chosen)).lower(
        qk, qk, vo, vo, row, row).compile().as_text()
    calls = re.findall(
        r'%([\w.-]+) = \((.*?)\) custom-call\([^\n]*"tpu_custom_call"', text)
    assert len(calls) == 1
    name, outputs = calls[0]
    assert re.fullmatch(r"flash_bwd_dkv_dq(\.\d+)?", name)
    # dk, dv, dq, as the kernel lists them
    assert re.findall(r"bf16\[[\d,]+\]", outputs) == [
        f"bf16[{bh},{t},{d}]", f"bf16[{bh},{t},{dv}]", f"bf16[{bh},{t},{d}]"]


@pytest.mark.timeout(600)  # two kernels, seconds each; room under six workers
def test_the_windowed_kernels_compile_on_the_band_grid_at_the_cell_s_shape(
        v5e):
    """`lagunaxs2.tokens8k`'s sliding layers: BH 128, T 8192, D 128, window
    512, at the tile the rule picks for the band grid. The forward and the
    one-kernel backward compile as one custom call each under the names
    the metrics read, on a grid of (BH, row, tiles the band crosses), with
    a VMEM limit inside what Mosaic may be given."""
    import re

    bh, t, d, window = 128, 8192, 128, 512
    assert fa.flash_bwd_kernels(t, t, d, jnp.bfloat16, window=window) == (
        "flash_bwd_dkv_dq",)
    qkv, row = _shapes(v5e[0], bh, t, d)
    chosen = dict(KERNEL, scale=d ** -0.5, block_q=None, block_k=None,
                  window=window)
    for case, kernel in (("fwd_lse", "flash_fwd"),
                         ("bwd_dkv_dq", "flash_bwd_dkv_dq")):
        tiles = fa.flash_tiles(kernel, t, t, d, jnp.bfloat16, window=window)
        assert t % tiles.block_q == 0 and t % tiles.block_k == 0
        # 18 % on the grid of every tile; the forward's odd rows of 512
        # cross one key tile of 1024 where the even rows cross two
        assert tiles.active_share == (
            23 / 32 if kernel == "flash_fwd" else 31 / 32)
        fn = _flash_case(case, chosen)
        (call,) = [eqn for eqn in jax.make_jaxpr(fn)(
            qkv, qkv, qkv, qkv, row, row).eqns
            if eqn.primitive.name == "pallas_call"]
        grid = call.params["grid_mapping"].grid
        assert grid[0] == bh and grid[1] * grid[2] == tiles.grid_steps
        assert grid[2] == 2  # the band crosses two tiles of a row (column)
        limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert limit == tiles.vmem_limit_bytes <= fa._MAX_VMEM
        text = jax.jit(fn).lower(
            qkv, qkv, qkv, qkv, row, row).compile().as_text()
        (name,) = [name for name, _ in _custom_calls(text)]
        assert re.fullmatch(kernel + r"_window(\.\d+)?", name)


@pytest.mark.timeout(600)  # a kernel, seconds; room under six workers
@pytest.mark.parametrize("window", [None, 512])
def test_the_one_backward_kernel_compiles_with_a_group_of_eight(v5e, window):
    """`lagunaxs2.tokens8k`'s sliding layers without the repeat: 128 query
    rows over 16 key-value rows, T 8192, D 128. The one-kernel backward
    takes k and v at their own rows, walks (key-value row, head of its
    group, key tile, q step), holds the key-value row's dk and dv beside
    the head's dq inside the limit Mosaic may be given, and hands dk and dv
    back at 16 rows."""
    import re

    bh, rows, t, d = 128, 16, 8192, 128
    shape = dict(window=window, group=bh // rows)
    assert fa.flash_bwd_kernels(t, t, d, jnp.bfloat16, **shape) == (
        "flash_bwd_dkv_dq",)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", t, t, d, jnp.bfloat16, **shape)
    q, row = _shapes(v5e[0], bh, t, d)
    k, _ = _shapes(v5e[0], rows, t, d)
    fn = _flash_case("bwd_dkv_dq", dict(
        KERNEL, scale=d ** -0.5, block_q=None, block_k=None, window=window))
    (call,) = [eqn for eqn in jax.make_jaxpr(fn)(q, k, k, q, row, row).eqns
               if eqn.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"].grid
    assert grid[:2] == (rows, bh // rows)
    assert grid[2] * grid[3] == tiles.grid_steps
    limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert limit == tiles.vmem_limit_bytes <= fa._MAX_VMEM
    text = jax.jit(fn).lower(q, k, k, q, row, row).compile().as_text()
    ((name, outputs),) = re.findall(
        r'%([\w.-]+) = \((.*?)\) custom-call\([^\n]*"tpu_custom_call"', text)
    assert re.fullmatch(
        "flash_bwd_dkv_dq" + ("_window" if window else "") + r"(\.\d+)?", name)
    # dk, dv, dq, as the kernel lists them
    assert re.findall(r"bf16\[[\d,]+\]", outputs) == [
        f"bf16[{rows},{t},{d}]", f"bf16[{rows},{t},{d}]", f"bf16[{bh},{t},{d}]"]


# (query rows, key-value rows, T, q and k's width, v's, window, data mask,
# the planner's tile)
SUMS_ALONE = {
    "keye": (32, 4, 16384, 128, 128, None, True, (512, 1024)),
    # a row too long for its dq's block
    "no-group-64k": (2, 2, 65536, 128, 128, None, False, (512, 1024)),
    # heads narrower than a tile of lanes, or wider by half of one (PR 75):
    # `phi4flash.tokens16k`'s paired heads whole and under its window,
    # `granite4hmicro.longctx`'s, whose dk and dv share a tile of lanes,
    # and `kimilinear.tokens16k`'s latent layer
    "phi4flash": (40, 20, 16384, 64, 128, None, False, (1024, 768)),
    "phi4flash-window": (40, 20, 16384, 64, 128, 512, False, (512, 512)),
    "granite": (32, 8, 32768, 64, 64, None, False, (768, 768)),
    "kimilinear": (32, 32, 16384, 192, 128, None, False, (1024, 1024)),
}


@pytest.mark.timeout(600)  # a kernel, seconds; room under six workers
@pytest.mark.parametrize("cell", list(SUMS_ALONE))
def test_the_one_backward_kernel_compiles_with_its_sums_alone(v5e, cell):
    """Where a row's output blocks leave no tile room the one kernel holds
    the row-long gradients as f32 sums and copies them out a tile at a time
    (`FlashTiles.exit == "tile"`): the outputs lie where the compiler put
    them and have no block, the limit is the planner's, and the call is one
    custom call under the name the metrics read, dk and dv at the
    key-value rows. Under a group all three leave by DMA, without one dq
    alone. At a width of no whole lanes the array that leaves so is padded
    to them (Mosaic refused the copy to rows of 64 and 192: "Slice shape
    along dimension 2 must be aligned to tiling (128)"), and dk and dv that
    fill one tile of lanes between them leave as the columns of one."""
    import re

    bh, rows, t, d, dv, window, masked, tile = SUMS_ALONE[cell]
    shape = dict(group=bh // rows, sparse=masked, v_dim=dv, window=window,
                 block_k=1024 if masked else None)
    assert fa.flash_bwd_kernels(t, t, d, jnp.bfloat16, **shape) == (
        "flash_bwd_dkv_dq",)
    tiles = fa.flash_tiles("flash_bwd_dkv_dq", t, t, d, jnp.bfloat16, **shape)
    assert tiles[:2] == tile and tiles.exit == "tile"
    one = SingleDeviceSharding(v5e[0])

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    row = sd(bh, t, 8, dtype=jnp.float32)
    operands = [sd(bh, t, d), sd(rows, t, d), sd(rows, t, dv), sd(bh, t, dv),
                row, row]
    chosen = dict(KERNEL, scale=d ** -0.5, block_q=None, block_k=None,
                  window=window)
    if masked:  # the selection's bits, in key tiles of 1,024
        operands.append(sd(1, t // 1024, t, 128, dtype=jnp.int8))
        chosen["block_k"] = 1024

    def fn(*a):
        return fa._flash_bwd_dkv(*a[:6], with_dq=True, **chosen,
                                 mask=a[6] if masked else None)

    # what the kernel writes: whole tiles of rows, whole lanes of columns
    lanes = fa._whole_lanes
    rows_q, rows_k = (-(-t // b) * b for b in tile)
    if bh == rows:  # dk's and dv's tiles are the pipeline's
        written = [(rows, t, d), (rows, t, dv)]
    elif d + dv <= 128:
        written = [(rows, rows_k, 128)]
    else:
        written = [(rows, rows_k, lanes(d)), (rows, rows_k, lanes(dv))]
    written.append((bh, rows_q, lanes(d)))
    (call,) = [eqn for eqn in jax.make_jaxpr(fn)(*operands).eqns
               if eqn.primitive.name == "pallas_call"]
    outputs = call.params["grid_mapping"].block_mappings[-len(written):]
    assert [str(m.block_aval.memory_space) for m in outputs] == (
        ["any"] * len(written) if bh > rows else ["None", "None", "any"])
    limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert limit == tiles.vmem_limit_bytes <= fa._MAX_VMEM
    lowered = jax.jit(fn).lower(*operands)
    # and what the caller hands on: dq, dk, dv as q, k and v are
    assert [x.shape for x in jax.tree.leaves(lowered.out_info)] == [
        x.shape for x in operands[:3]]
    text = lowered.compile().as_text()
    ((name, outputs),) = re.findall(
        r'%([\w.-]+) = \((.*?)\) custom-call\([^\n]*"tpu_custom_call"', text)
    assert re.fullmatch(
        "flash_bwd_dkv_dq" + ("_sparse" if masked else
                              "_window" if window else "") + r"(\.\d+)?",
        name)
    assert re.findall(r"bf16\[[\d,]+\]", outputs) == [
        "bf16[%d,%d,%d]" % x for x in written]


# (B, T, S, heads, the call's mask) at heads of 128, v with q's heads
V_IN_PLACE = {
    "evabyte-window": (4, 2048, 2048, 32, dict(causal=True)),
    "evabyte-stair": (1, 8192, 512, 32, dict(stair=(2048, 128))),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd_block_or_chosen", "bwd_tile"])
@pytest.mark.parametrize("cell", list(V_IN_PLACE))
def test_the_kernels_compile_with_v_where_the_model_holds_it(
        v5e, cell, kernel):
    """`evabyte.tokens8k`'s two calls with v and dv as `[B, S, H Dv]`, a
    head a column block of v's index map (what the entries hand over where
    v has q's heads): rows of 256 bytes at a stride of `H Dv`, block by
    block, in the forward and in `flash_bwd_dkv_dq` by both of dq's exits.
    One custom call, dv as v lies."""
    import re

    b, t, s, h, mask = V_IN_PLACE[cell]
    d = dv = 128
    one = SingleDeviceSharding(v5e[0])

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    q, k, v, o = (sd(b * h, t, d), sd(b * h, s, d), sd(b, s, h * dv),
                  sd(b * h, t, dv))
    row = sd(b * h, t, 8, dtype=jnp.float32)
    how = dict(scale=d ** -0.5, block_q=None, block_k=None, interpret=False,
               v_heads=h, **{"causal": False, **mask})
    if kernel == "fwd":
        def fn(q, k, v, do, lse, delta):
            return fa._flash_fwd(q, k, v, with_lse=True, **how)
        outputs = [o]
    else:
        assert fa.flash_bwd_kernels(
            t, s, d, jnp.bfloat16, causal=how["causal"],
            stair=mask.get("stair")) == ("flash_bwd_dkv_dq",)

        def fn(*a):
            return fa._flash_bwd_dkv(*a, with_dq=True,
                                     by_tile=kernel == "bwd_tile", **how)
        outputs = [k, v, q]
    text = jax.jit(fn).lower(q, k, v, o, row, row).compile().as_text()
    ((name, made),) = re.findall(
        r'%([\w.-]+) = \((.*?)\) custom-call\([^\n]*"tpu_custom_call"', text)
    assert name.startswith("flash_fwd" if kernel == "fwd"
                           else "flash_bwd_dkv_dq")
    assert re.findall(r"bf16\[[\d,]+\]", made) == [
        "bf16[%s]" % ",".join(map(str, x.shape)) for x in outputs]


def test_lm_head_cross_entropy_compiles_for_v5e(v5e):
    one = SingleDeviceSharding(v5e[0])
    hidden = jax.ShapeDtypeStruct((32, 1024, 768), jnp.bfloat16, sharding=one)
    unembed = jax.ShapeDtypeStruct((768, 50304), jnp.float32, sharding=one)
    targets = jax.ShapeDtypeStruct((32, 1024), jnp.int32, sharding=one)

    def loss_and_grads(h, w, t):
        return jax.value_and_grad(
            lambda h, w: lm_head_cross_entropy(h, w, t)[0], argnums=(0, 1)
        )(h, w)

    compiled = jax.jit(loss_and_grads).lower(hidden, unembed, targets).compile()
    # The chunked CE exists so that [B*T, V] f32 logits (6.6 GB) never are.
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    # Undifferentiated (evaluation): the same scan without the gradient work.
    evaluated = jax.jit(lm_head_cross_entropy).lower(
        hidden, unembed, targets).compile()
    assert evaluated.memory_analysis().temp_size_in_bytes < 1 << 30


def test_train_step_compiles_for_four_v5e(v5e):
    """The whole train step at full width on the 2x2 mesh chip_smoke.py
    --chips 4 uses (depth cut to 2: the layers are one scan, so depth changes
    neither the program nor what the compiler may refuse). XLA cannot
    partition the Mosaic kernel, so this is the test that the model maps it
    over the batch axes itself, and that the state's layout is a fixed point
    of the step."""
    import optax

    cfg = TransformerConfig(
        vocab_size=50304, d_model=768, n_layers=2, n_heads=12,
        max_seq_len=1024, dtype=jnp.bfloat16, remat=True,
        attention_impl="pallas",  # 'auto' would ask the CPU backend
    )
    mesh = make_mesh({"data": 2, "fsdp": 2}, devices=v5e)
    init_state, step, shardings = make_train_step(cfg, mesh, optax.adamw(1e-3))
    # A described device cannot hold an array: hand the step shapes.
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(init_state, jax.random.PRNGKey(0)),
        shardings["state"],
    )
    tokens = jax.ShapeDtypeStruct((32, 1024), jnp.int32,
                                  sharding=shardings["tokens"])
    compiled = step.lower(state, {"tokens": tokens, "targets": tokens}).compile()
    # forward-with-lse and the backward's one kernel; plus the remat's
    # forward again
    assert compiled.as_text().count("tpu_custom_call") >= 3
    out_state = compiled.output_shardings[0]
    assert jax.tree.leaves(out_state) == jax.tree.leaves(shardings["state"])


@pytest.mark.parametrize("case,kernel", sorted(KERNEL_OF.items()))
def test_flash_kernel_is_named_in_the_text_compiled_for_v5e(v5e, case, kernel):
    """The chip's compiler names a Mosaic custom call after the
    `pallas_call`'s `name=`: that instruction name is what a profiler trace
    shows and what the benchmark's per-kernel metrics match."""
    import re

    qkv, row = _shapes(v5e[0])
    text = jax.jit(_flash_case(case)).lower(
        qkv, qkv, qkv, qkv, row, row
    ).compile().as_text()
    call = re.search(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*', text)
    assert re.fullmatch(re.escape(kernel) + r"(\.\d+)?", call.group(1))
    assert f"/{kernel}/pallas_call" in call.group(0)  # and its name stack


@pytest.mark.parametrize("kernel", ["moe_gmm", "moe_gmm_transposed", "moe_tgmm"])
def test_grouped_matmul_kernel_compiles_at_the_cell_s_shapes(v5e, kernel):
    """`olmoe.tokens4k`: 131,072 rows in 64 groups, a [2048, 1024] weight a
    group, at the tile `gmm_tiles` picks; the weights' gradient leaves the
    kernel as f32. The compiled instruction carries the kernel's name."""
    import re

    from ray_tpu.ops import moe

    one = SingleDeviceSharding(v5e[0])
    rows, k, n, experts = 131072, 2048, 1024, 64
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one)
    dy = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((experts, k, n), jnp.bfloat16, sharding=one)
    sizes = jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one)
    if kernel == "moe_gmm":
        lowered = jax.jit(moe.gmm).lower(x, w, sizes)
    elif kernel == "moe_gmm_transposed":  # the rows' gradient
        lowered = jax.jit(
            lambda dy, w, s: moe.gmm(dy, w, s, transpose_w=True)
        ).lower(dy, w, sizes)
    else:
        lowered = jax.jit(
            lambda x, dy, s: moe.tgmm(x, dy, s, out_dtype=jnp.float32)
        ).lower(x, dy, sizes)
    text = lowered.compile().as_text()
    call = re.search(
        r'%([\w.-]+) = (\S+) [^\n]*custom_call_target="tpu_custom_call"', text)
    name = "moe_tgmm" if kernel == "moe_tgmm" else "moe_gmm"
    assert re.fullmatch(name + r"(\.\d+)?", call.group(1))
    assert call.group(2).startswith(
        "f32[64,2048,1024]" if kernel == "moe_tgmm" else
        "bf16[131072,2048]" if kernel == "moe_gmm_transposed" else
        "bf16[131072,1024]")


# the sum of a share's rows onto their tokens: (buffer rows, tokens, d, k,
# held experts) of the cell, and the use (the forward's, weighted and added
# into the loop's float32 sum; the backward's, two buffers' rows as they are)
SUM_SHAPES = {
    "mellum2.ep4": (180224, 65536, 2304, 8, 16),
    "dsv2lite.tokens8k": (33792, 32768, 2048, 6, 8),
    "lagunaxs2.tokens8k": (20480, 16384, 2048, 8, 32),
}


@pytest.mark.parametrize("use", ["forward", "backward"])
@pytest.mark.parametrize("cell_name", SUM_SHAPES)
def test_moe_sum_compiles_at_the_cell_s_shapes(v5e, cell_name, use):
    """At the tile `sum_tiles` picks, the windows' two slots, the placing
    matrices and the blocks fit the VMEM it asks for, the DMA windows begin
    at whole packed tiles, and the instruction carries the kernel's name."""
    import re

    from ray_tpu.ops import moe

    one = SingleDeviceSharding(v5e[0])
    n, tokens, d, k, held = SUM_SHAPES[cell_name]

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    rows, sizes = sd((n, d), jnp.bfloat16), sd((held,), jnp.int32)
    inverse, count = sd((tokens * k,), jnp.int32), sd((), jnp.int32)
    if use == "forward":
        lowered = jax.jit(
            lambda ys, inv, s, r, w, onto: moe.sum_held(
                (ys,), inv, s, r, tokens, weights=w, onto=onto),
            donate_argnums=5,
        ).lower(rows, inverse, sizes, count, sd((tokens, k), jnp.float32),
                sd((tokens, d), jnp.float32))
    else:
        lowered = jax.jit(
            lambda a, b, inv, s, r: moe.sum_held((a, b), inv, s, r, tokens)
        ).lower(rows, rows, inverse, sizes, count)
    text = lowered.compile().as_text()
    call = re.search(
        r'%([\w.-]+) = (\S+) [^\n]*custom_call_target="tpu_custom_call"', text)
    assert re.fullmatch(r"moe_sum(\.\d+)?", call.group(1))
    assert call.group(2).startswith(
        f"f32[{tokens},{d}]" if use == "forward" else f"bf16[{tokens},{d}]")
    assert " scatter(" not in text and " sort(" not in text


# ------------------------------------------- the Mamba-2 scan's kernels

SCAN = dict(b=2, T=8192, H=64, P=64, G=8, N=128)  # nemotron3nano.tokens8k


def _scan_args(device):
    one = SingleDeviceSharding(device)
    b, T, H, P, G, N = SCAN.values()

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return (sd((b, T, H, P), jnp.bfloat16), sd((b, T, H), jnp.float32),
            sd((H,), jnp.float32), sd((b, T, G, N), jnp.bfloat16),
            sd((b, T, G, N), jnp.bfloat16), sd((H,), jnp.float32))


def _custom_calls(text):
    """[(instruction's name, its result type)] of a compiled text's Mosaic
    kernels."""
    import re

    return re.findall(
        r'%([\w.-]+) = (\(.*?\)|\S+) custom-call\([^\n]*"tpu_custom_call"', text)


@pytest.mark.parametrize("kernel", ["ssd_fwd", "ssd_bwd"])
def test_scan_kernel_compiles_at_the_cell_s_shapes(v5e, kernel):
    """`nemotron3nano.tokens8k`: x `[2, 8192, 64, 64]`, 8 groups, a state
    of 128, chunks of 128, bf16. The forward alone is one `ssd_fwd` that
    writes y lane-dense; differentiated, the forward rule's `ssd_fwd` also
    writes the 64 chunks' entering states in float32 and `ssd_bwd` returns
    dx, the columns of d dt and d cum, d cum's rows, dB and dC. No array
    of `[.., 64, 64, 128, 128]` (a sequence's `[n, H, Q, Q]`) is in either
    program."""
    import re

    from ray_tpu.ops.ssd import ssd

    def y(*args):
        return ssd(*args, chunk=128, impl="pallas")

    def grads(*args):
        return jax.grad(lambda *a: y(*a).astype(jnp.float32).sum(),
                        argnums=range(6))(*args)

    text = jax.jit(y if kernel == "ssd_fwd" else grads).lower(
        *_scan_args(v5e[0])).compile().as_text()
    # outside a step's scopes a differentiated call is named by its whole
    # stack (`jvp_ssd_fwd_`); in a step `ssd_fwd.3` (`TOKEN_CELLS` below)
    calls = {re.search(r"ssd_(fwd|bwd)", name).group(0):
             re.findall(r"(?:bf16|f32)\[[\d,]+\]", out)
             for name, out in _custom_calls(text)}
    assert "64,64,128,128]" not in text
    if kernel == "ssd_fwd":
        assert calls == {"ssd_fwd": ["bf16[2,8192,4096]"]}
        return
    assert set(calls) == {"ssd_fwd", "ssd_bwd"}
    assert calls["ssd_fwd"] == [
        "bf16[2,8192,4096]", "f32[2,64,8,128,512]"]
    assert calls["ssd_bwd"] == [
        "bf16[2,8192,4096]", "f32[2,8,8192,8]", "f32[2,8,8192,8]",
        "f32[2,8,8,8192]", "bf16[2,8192,1024]", "bf16[2,8192,1024]"]


@pytest.mark.parametrize("use", ["forward", "backward"])
def test_the_mixer_s_kernels_compile_at_granite_s_shapes(v5e, use):
    """`granite4hmicro.longctx`: one sequence of 32,768 tokens, 64 heads of
    64 in ONE group of B and C, a state of 128, chunks of 256, bf16: the
    scan 32 heads a tile (`head_tile`; whole, `ssd_bwd` would hold 73 MB),
    the entering states tile by tile, a tile's `dB` and `dC` float32 and
    summed outside; the convolution over 4,352 channels split 4,096 / 128 /
    128 (blocks of 128 channels) and the gated norm over one group of 4,096.
    No `[.., 256, 256]` array of a chunk's decays or scores, no float32
    array of the mixer's width and no padded copy is in either program."""
    import re

    from ray_tpu.ops.mamba_passes import causal_conv_silu, gated_group_rmsnorm
    from ray_tpu.ops.ssd import ssd

    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    T, H, P, N, splits = 32768, 64, 64, 128, (4096, 128, 128)
    args = (sd((1, T, sum(splits))), sd((4, sum(splits))),
            sd((sum(splits),), jnp.float32), sd((1, T, H), jnp.float32),
            sd((H,), jnp.float32), sd((H,), jnp.float32),
            sd((1, T, H * P)), sd((H * P,), jnp.float32))

    def out(xbc, w, bias, dt, A, D, z, weight):
        x, b, c = causal_conv_silu(xbc, w, bias, splits=splits, impl="pallas")
        y = ssd(x.reshape(1, T, H, P), dt, A, b.reshape(1, T, 1, N),
                c.reshape(1, T, 1, N), D, chunk=256, impl="pallas")
        return gated_group_rmsnorm(
            y.reshape(1, T, H * P), z, weight, 1, 1e-5, impl="pallas")

    def grads(*args):
        return jax.grad(lambda *a: out(*a).astype(jnp.float32).sum(),
                        argnums=range(8))(*args)

    text = jax.jit(out if use == "forward" else grads).lower(
        *args).compile().as_text()
    calls = {re.search(r"(ssd|mamba_conv|mamba_norm)_(fwd|bwd)", name).group(0):
             re.findall(r"(?:bf16|f32)\[[\d,]+\]", made)
             for name, made in _custom_calls(text)}
    assert not re.search(r"(f32|bf16)\[(\d+,)*256,256\]", text)
    # (inside the fusion that sums `dD = dy x` the two are widened a tile at
    # a time: no instruction writes such an array)
    assert not re.search(
        r"= f32\[1,32768,4\d\d\d\]\S* (fusion|custom-call|copy)\(", text)
    assert not re.search(r"bf16\[1,3277[0-9],\d+\]", text)  # a padded copy
    wide, narrow = "bf16[1,32768,4096]", "bf16[1,32768,128]"
    if use == "forward":
        assert calls == {"mamba_conv_fwd": [wide, narrow, narrow],
                         "ssd_fwd": [wide], "mamba_norm_fwd": [wide]}
        return
    assert calls == {
        "mamba_conv_fwd": [wide, narrow, narrow],
        "ssd_fwd": [wide, "f32[1,128,2,128,2048]"],
        "mamba_norm_bwd": [wide, wide, "f32[8,4096]"],
        "ssd_bwd": [wide, "f32[1,2,32768,32]", "f32[1,2,32768,32]",
                    "f32[1,2,32,32768]", "f32[1,32768,256]",
                    "f32[1,32768,256]"],
        "mamba_conv_bwd": ["bf16[1,32768,4352]", "f32[5,8,4352]"]}


# (tokens, heads) of a KDA layer's one sequence, heads of 128
KDA_SHAPES = {"solaropen2.tokens8k": (8192, 8),
              "kimilinear.tokens16k": (16384, 32)}


@pytest.mark.parametrize("kernel", ["kda_fwd", "kda_bwd"])
@pytest.mark.parametrize("cell", list(KDA_SHAPES))
def test_kda_kernel_compiles_at_the_cell_s_shapes(v5e, cell, kernel):
    """`solaropen2.tokens8k`: one sequence of 8,192 tokens, the 8 held heads
    of 128; `kimilinear.tokens16k`: 16,384 tokens, all 32 heads (a grid of
    (1, 16, 256), 4,096 steps a pass). Chunks of 64, bf16 with float32 log
    decays and beta. The forward
    alone is one `kda_fwd` that writes o lane-dense, the last state and the
    decays' reach; differentiated, the forward rule's `kda_fwd` also writes
    the chunks' entering states in float32 and `kda_bwd` returns dq,
    dk, dv, dg, dbeta's rows and the entering state's cotangent. No pair
    tensor (`[.., 16, 16, 128]`), no `[.., 64, 64]` matrix a chunk and no
    triangular solve is in either program."""
    import re

    from ray_tpu.ops.kda import kda, kda_untiled

    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    (T, H), d = KDA_SHAPES[cell], 128
    assert kda_untiled(64, d, d, 2) is None  # the kernels take the shape
    args = (*[sd((1, T, H, d), jnp.bfloat16)] * 3,
            sd((1, T, H, d), jnp.float32), sd((1, T, H), jnp.float32))

    def o(*args):
        return kda(*args, chunk=64, impl="pallas")[0]

    def grads(*args):
        return jax.grad(lambda *a: o(*a).astype(jnp.float32).sum(),
                        argnums=range(5))(*args)

    text = jax.jit(o if kernel == "kda_fwd" else grads).lower(
        *args).compile().as_text()
    calls = {re.search(r"kda_(fwd|bwd)", name).group(0):
             re.findall(r"(?:bf16|f32)\[[\d,]+\]", out)
             for name, out in _custom_calls(text)}
    assert not re.search(r"f32\[[\d,]*(16,16,128|64,64)\]", text)
    assert "InvertDiagBlocksLowerTriangular" not in text
    n, wide = T // 64, H * d
    states = [f"f32[1,{H},128,128]", f"f32[1,{H // 2},{n},1,128]"]
    tokens = f"[1,{T},{wide}]"
    if kernel == "kda_fwd":
        assert calls == {"kda_fwd": ["bf16" + tokens, *states]}
        return
    assert set(calls) == {"kda_fwd", "kda_bwd"}
    assert calls["kda_fwd"] == [
        "bf16" + tokens, *states, f"f32[1,{n},{H},128,128]"]
    assert calls["kda_bwd"] == [
        "bf16" + tokens, "bf16" + tokens, "bf16" + tokens,
        "f32" + tokens, f"f32[1,{H // 2},{n},1,128]", f"f32[1,{H},128,128]"]


def test_the_flash_pair_compiles_at_kimi_s_latent_attention(v5e):
    """`kimilinear.tokens16k`'s one latent-attention layer as the model
    calls it: one sequence of 16,384 tokens, 32 heads, q and k 192 wide
    (nothing rotated: the kernels see the same arrays) and v 128, through
    `mha`: `flash_fwd` once and the whole backward as `flash_bwd_dkv_dq`,
    and no `[T, T]` array anywhere."""
    import re

    one = SingleDeviceSharding(v5e[0])
    T, H = 16384, 32
    qk = jax.ShapeDtypeStruct((1, T, H, 192), jnp.bfloat16, sharding=one)
    vo = jax.ShapeDtypeStruct((1, T, H, 128), jnp.bfloat16, sharding=one)
    assert fa.flash_bwd_kernels(T, T, 192, jnp.bfloat16, v_dim=128) == (
        "flash_bwd_dkv_dq",)

    def grads(q, k, v):
        return jax.grad(lambda *a: fa.mha(
            *a, causal=True, scale=192 ** -0.5, impl="pallas").astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(qk, qk, vo).compile().as_text()
    # under `jit` alone the calls carry their transforms' names before
    # the kernel's (`jvp_flash_fwd_`)
    names = sorted(name for name, _ in _custom_calls(text))
    assert len(names) == 2
    assert "flash_fwd" in names[0] and "flash_bwd_dkv_dq" in names[1]
    assert not re.search(r"(f32|bf16)\[(\d+,)*16384,16384\]", text)


@pytest.mark.parametrize("kernel", ["selective_scan_fwd",
                                    "selective_scan_bwd"])
def test_selective_scan_kernel_compiles_at_the_cell_s_shapes(v5e, kernel):
    """`phi4flash.tokens16k`: one sequence of 16,384 tokens, 5,120 channels,
    16 states, chunks of 128, bf16 with a float32 step size. The forward
    alone is one `selective_scan_fwd` that writes y and the last state in
    float32; differentiated, the forward rule's `selective_scan_fwd` also
    writes the 128 chunks' entering states and `selective_scan_bwd` returns
    du, d dt, dA and the five blocks' parts of dB's and dC's columns. No
    array of a chunk's steps (`[128, 1, 16, 5120]`) is in either program,
    and none of a sequence's."""
    import re

    from ray_tpu.ops.selective_scan import selective_scan

    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    T, inner, N = 16384, 5120, 16
    args = (sd((1, T, inner), jnp.bfloat16), sd((1, T, inner), jnp.float32),
            sd((inner, N), jnp.float32), sd((1, T, N), jnp.bfloat16),
            sd((1, T, N), jnp.bfloat16), sd((inner,), jnp.float32))

    def y(*args):
        return selective_scan(*args, chunk=128, impl="pallas")[0]

    def grads(*args):
        return jax.grad(lambda *a: y(*a).sum(), argnums=range(6))(*args)

    text = jax.jit(y if kernel == "selective_scan_fwd" else grads).lower(
        *args).compile().as_text()
    calls = {re.search(r"selective_scan_(fwd|bwd)", name).group(0):
             re.findall(r"(?:bf16|f32)\[[\d,]+\]", out)
             for name, out in _custom_calls(text)}
    assert not re.search(r"f32\[128,1,16,5120\]", text)
    assert not re.search(r"f32\[(\d+,)*16384,(1,)?(16,5120|5120,16)\]", text)
    out = ["f32[1,16384,5120]", "f32[1,16,5120]"]
    if kernel == "selective_scan_fwd":
        assert calls == {"selective_scan_fwd": out}
        return
    assert set(calls) == {"selective_scan_fwd", "selective_scan_bwd"}
    assert calls["selective_scan_fwd"] == [*out, "f32[1,128,16,5120]"]
    assert calls["selective_scan_bwd"] == [
        "bf16[1,16384,5120]", "f32[1,16384,5120]", "f32[1,16,5120]",
        "f32[5,1,128,32,128]"]


@pytest.mark.parametrize("use", ["forward", "backward"])
def test_the_mixer_s_passes_compile_at_the_cell_s_shapes(v5e, use):
    """`nemotron3nano.tokens8k`: 2 x 8,192 tokens, the convolution of 4 taps
    over 6,144 channels split 4,096 / 1,024 / 1,024, the gated norm over 8
    groups of 512, bf16. The forward is one `mamba_conv_fwd` that writes
    the three arrays the scan takes and one `mamba_norm_fwd`;
    differentiated, `mamba_conv_bwd` returns one `dxBC` and the taps' and
    the bias's partial sums, `mamba_norm_bwd` `dy`, `dz` and `d weight`'s.
    No float32 array of the mixer's width, no padded copy and no array a
    tap is in either program."""
    import re

    from ray_tpu.ops.mamba_passes import causal_conv_silu, gated_group_rmsnorm

    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    B, T, splits, inner = 2, 8192, (4096, 1024, 1024), 4096
    args = (sd((B, T, sum(splits))), sd((4, sum(splits))),
            sd((sum(splits),), jnp.float32), sd((B, T, inner)),
            sd((B, T, inner)), sd((inner,), jnp.float32))

    def out(x, w, bias, y, z, weight):
        return (*causal_conv_silu(x, w, bias, splits=splits, impl="pallas"),
                gated_group_rmsnorm(y, z, weight, 8, 1e-5, impl="pallas"))

    def grads(*args):
        return jax.grad(lambda *a: sum(
            o.astype(jnp.float32).sum() for o in out(*a)),
            argnums=range(6))(*args)

    text = jax.jit(out if use == "forward" else grads).lower(
        *args).compile().as_text()
    calls = {re.search(r"mamba_(conv|norm)_(fwd|bwd)", name).group(0):
             re.findall(r"(?:bf16|f32)\[[\d,]+\]", made)
             for name, made in _custom_calls(text)}
    assert not re.search(r"f32\[2,8192,\d+\]", text)
    assert not re.search(r"bf16\[2,819[3-9],\d+\]", text)  # a padded copy
    if use == "forward":
        assert calls == {
            "mamba_conv_fwd": ["bf16[2,8192,4096]", "bf16[2,8192,1024]",
                               "bf16[2,8192,1024]"],
            "mamba_norm_fwd": ["bf16[2,8192,4096]"]}
        return
    # the residuals are the inputs alone: no forward kernel is left where
    # nothing reads its result
    assert calls == {
        "mamba_conv_bwd": ["bf16[2,8192,6144]", "f32[5,8,6144]"],
        "mamba_norm_bwd": ["bf16[2,8192,4096]", "bf16[2,8192,4096]",
                           "f32[8,4096]"]}


@pytest.mark.parametrize("use", ["forward", "backward"])
@pytest.mark.parametrize("cell,T,wide", [
    ("kimilinear.tokens16k", 16384, 4096), ("solaropen2.tokens8k", 8192, 1024)])
def test_kda_s_short_convolutions_compile_at_the_cell_s_shapes(
        v5e, cell, T, wide, use):
    """A KDA layer's three streams `[1, T, H dk]` (32 heads of 128 at 16,384
    tokens, 8 at 8,192), 4 taps, bf16: q's and k's calls with each head of
    128 at unit length, v's without, as `kda_conv_fwd`, three arrays of the
    streams' width out; differentiated, three `kda_conv_bwd`, each `dx` and
    the taps' partial sums. No float32 array of the streams' width, no
    padded copy and no array a tap is in either program."""
    import re

    from ray_tpu.ops.mamba_passes import causal_conv_silu

    one = SingleDeviceSharding(v5e[0])

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    args = (*(sd((1, T, wide)),) * 3, sd((3, 4, wide)))

    def out(q, k, v, taps):
        return tuple(
            causal_conv_silu(s, taps[i], unit=128 * (i < 2), name="kda_conv",
                             impl="pallas") for i, s in enumerate((q, k, v)))

    def grads(*args):
        return jax.grad(lambda *a: sum(
            o.astype(jnp.float32).sum() for o in out(*a)),
            argnums=range(4))(*args)

    text = jax.jit(out if use == "forward" else grads).lower(
        *args).compile().as_text()
    calls = [(re.search(r"kda_conv_(fwd|bwd)", name).group(0),
              re.findall(r"(?:bf16|f32)\[[\d,]+\]", made))
             for name, made in _custom_calls(text)]
    assert not re.search(rf"f32\[1,{T},\d+\]", text)
    assert not re.search(rf"bf16\[1,{T + 3},\d+\]", text)  # a padded copy
    stream = f"bf16[1,{T},{wide}]"
    if use == "forward":
        assert calls == [("kda_conv_fwd", [stream])] * 3
    else:
        assert calls == [
            ("kda_conv_bwd", [stream, f"f32[5,8,{wide}]"])] * 3


@pytest.mark.parametrize("use", ["forward", "backward"])
@pytest.mark.parametrize("cell,T,heads", [
    ("kimilinear.tokens16k", 16384, 32), ("solaropen2.tokens8k", 8192, 8)])
def test_kda_s_output_norm_compiles_at_the_cell_s_shapes(
        v5e, cell, T, heads, use):
    """A KDA mixer's o and its gate's pre-activation `[1, T, H dk]` (32
    heads of 128 at 16,384 tokens, 8 at 8,192), bf16, the bias and the one
    scale float32: one `kda_out_norm_fwd`; differentiated, one
    `kda_out_norm_bwd` that writes `do`, `dz` and sixteen partial rows. No
    float32 array of the mixer's width and no array by head `[.., H, dk]`
    is in either program (PR 69)."""
    import re

    from ray_tpu.ops.mamba_passes import group_rmsnorm_gated

    one = SingleDeviceSharding(v5e[0])
    wide = heads * 128

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (sd((1, T, wide)), sd((1, T, wide)), sd((wide,), jnp.float32),
            sd((128,), jnp.float32))

    def out(*args):
        return group_rmsnorm_gated(*args, heads, 1e-5, impl="pallas")

    def grads(*args):
        return jax.grad(lambda *a: out(*a).astype(jnp.float32).sum(),
                        argnums=range(4))(*args)

    text = jax.jit(out if use == "forward" else grads).lower(
        *args).compile().as_text()
    calls = [(re.search(r"kda_out_norm_(fwd|bwd)", name).group(0),
              re.findall(r"(?:bf16|f32)\[[\d,]+\]", made))
             for name, made in _custom_calls(text)]
    assert not re.search(rf"f32\[1,{T},[\d,]+\]", text)
    assert not re.search(rf"\[(1,{T}|\d+,8),{heads},128\]", text)
    stream = f"bf16[1,{T},{wide}]"
    if use == "forward":
        assert calls == [("kda_out_norm_fwd", [stream])]
    else:
        assert calls == [
            ("kda_out_norm_bwd", [stream, stream, f"f32[16,{wide}]"])]


@pytest.mark.timeout(600)  # six kernels, seconds each; room under six workers
@pytest.mark.parametrize("use", ["forward", "backward"])
def test_eva_attention_compiles_at_the_cell_s_shapes(v5e, use):
    """`evabyte.tokens8k`: one sequence of 8,192 at 32 heads of 128, windows
    of 2,048 and chunks of 16, bf16. The forward is one `eva_summaries_fwd`
    that writes a sixteenth of k and of v, the causal `flash_fwd` on the 4
    windows folded into the batch, and `flash_fwd_stair` over the 512
    summaries; differentiated, each has its one backward kernel. No score
    tensor of a window against itself, of the queries against the summaries
    or of the sequence against itself is in either program, and the lse
    that joins the parts is a column a row."""
    import re

    from ray_tpu.ops import eva

    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    T, H, D = 8192, 32, 128
    args = (*(sd((1, T, H, D)),) * 3, *(sd((H, D), jnp.float32),) * 2)

    def out(q, k, v, phi, mu):
        return eva.eva_attention(q, k, v, phi, mu, window=2048, chunk=16,
                                 impl="pallas")[0]

    def grads(*args):
        return jax.grad(lambda *a: out(*a).astype(jnp.float32).sum(),
                        argnums=range(5))(*args)

    # under "highest" too: the kernels pin one pass for narrow operands
    with jax.default_matmul_precision("highest"):
        text = jax.jit(out if use == "forward" else grads).lower(
            *args).compile().as_text()
    calls = {}
    for name, made in _custom_calls(text):
        kernel = re.match(r"[a-z_]+?(?=\.\d+$|$)", name).group(0)
        calls[kernel] = re.findall(r"(?:bf16|f32)\[[\d,]+\]", made)
    for pairs in ("2048,2048", "8192,512", "8192,8192", "2048,512",
                  "2048,384"):
        assert not re.search(r"\[(\d+,)*%s\]" % pairs, text), pairs
    forward = {
        "eva_summaries_fwd": ["bf16[1,512,4096]", "bf16[1,512,4096]"],
        "flash_fwd": ["bf16[128,2048,128]", "f32[128,2048,8]"],
        "flash_fwd_stair": ["bf16[32,8192,128]", "f32[32,8192,8]"]}
    if use == "forward":
        assert calls == forward
        return
    assert calls == {
        **forward,
        "eva_summaries_bwd": ["bf16[1,8192,4096]", "bf16[1,8192,4096]",
                              "f32[1,4,8,4096]"],
        # dk, dv, dq: dv where v lies, `[B, S, H D]` (PR 65)
        "flash_bwd_dkv_dq": ["bf16[128,2048,128]", "bf16[4,2048,4096]",
                             "bf16[128,2048,128]"],
        "flash_bwd_dkv_dq_stair": ["bf16[32,512,128]", "bf16[1,512,4096]",
                                   "bf16[32,8192,128]"]}


@pytest.mark.timeout(600)  # two kernels at 64 x 16,384; room under six workers
@pytest.mark.parametrize("use", ["forward", "backward"])
def test_block_diffusion_attention_compiles_at_the_cell_s_shapes(v5e, use):
    """`sdar.tokens16k`: one sequence of 16,384 as 32,768 rows at 32 query
    heads over 4 key-value heads of 128, blocks of 4, bf16. The forward is
    one `flash_fwd_stair` on both halves' 64 query heads against the clean
    half's keys and values (a group of 16 a key-value head, no copy of k or
    v); differentiated, one `flash_bwd_dkv_dq_stair` whose dk and dv leave at
    the 4 key-value heads, summed over the group in the kernel. The own
    block and the join are the pair `bd_own_join_fwd` and `bd_own_join_bwd`
    (PR 71), which read q, oS and lseS as the staircase's kernels take and
    leave them and write o as `wo` reads it, `[1, 32768, 4096]`: no score
    tensor of the stream against itself, of a half against the clean half,
    or of a head's whole stream in float32 is in either program, and no
    loop over rows."""
    import re

    from ray_tpu.ops import block_diffusion

    one = SingleDeviceSharding(v5e[0])

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    R, H, Hk, D = 32768, 32, 4, 128
    args = (sd((1, R, H, D)), sd((1, R, Hk, D)), sd((1, R, Hk, D)))

    def out(q, k, v):
        return block_diffusion.block_diffusion_attention(
            q, k, v, block=4, impl="pallas")

    def grads(*args):
        return jax.grad(lambda *a: out(*a).astype(jnp.float32).sum(),
                        argnums=range(3))(*args)

    with jax.default_matmul_precision("highest"):
        text = jax.jit(out if use == "forward" else grads).lower(
            *args).compile().as_text()
    calls = {}
    for name, made in _custom_calls(text):
        kernel = re.match(r"[a-z_]+?(?=\.\d+$|$)", name).group(0)
        calls[kernel] = re.findall(r"(?:bf16|f32)\[[\d,]+\]", made)
    for pairs in ("32768,32768", "16384,16384", "32768,16384"):
        assert not re.search(r"\[(\d+,)*%s\]" % pairs, text), pairs
    assert not re.search(r"f32\[(1,)?32768,32,128\]", text)
    # no loop over chunks of rows; the backward's one `while` is the single
    # trip round `bd_own_join_bwd` (`_own_join_vjp_bwd` says why)
    assert len(re.findall(r"\bwhile\(", text)) == (use == "backward")
    forward = {"flash_fwd_stair": ["bf16[64,16384,128]", "f32[64,16384,8]"],
               "bd_own_join_fwd": ["bf16[1,32768,4096]"]}
    if use == "forward":
        assert calls == forward
        return
    assert calls == {
        # dk and dv at the key-value heads, in whole key tiles of 768
        # (22 x 768 = 16,896 rows, cut to 16,384 outside), dq at the query
        # heads
        "flash_fwd_stair": forward["flash_fwd_stair"],
        "flash_bwd_dkv_dq_stair": ["bf16[4,16896,128]", "bf16[4,16896,128]",
                                   "bf16[64,16384,128]"],
        # dq, dk, dv, doS and dlseS, each as its operand lies (the forward
        # is not made again: the residuals are the operands)
        "bd_own_join_bwd": ["bf16[64,16384,128]", "bf16[4,32768,128]",
                            "bf16[4,32768,128]", "bf16[64,16384,128]",
                            "f32[8,8,16384]"]}
