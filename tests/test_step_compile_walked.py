"""The walked token cells' whole steps, compiled for the chip without the
chip: the cells whose stack is walked a layer at a time and priced against
the compiler's plan (`tests/test_step_compile.py` has the rule's arithmetic
and the scanned and share cells; D11's split along the cells, PR 74: a new
cell's step goes to the lighter of the two). Each cell's step is compiled
once a module (`v5e_described.token_steps`)."""

import jax
import pytest

from v5e_described import (  # noqa: F401 - the module's fixtures
    HBM_LIMIT, calls as _calls, token_steps, v5e)


def test_kimi_step_compiles_fits_and_is_priced(token_steps):
    """`kimilinear.tokens16k` at its real shapes with the chip's limit
    handed to the keep rule: every name is kept, the compiler's plan fits
    what a v5e offers a program with no `.remat` fusion made to fit, the
    rule's sum stands at or over the plan and under the chip, and the step
    runs KDA's kernels (four layers: forward, forward again, backward),
    the flash pair once and the grouped-matmul kernels."""
    import re

    from chipbench import kimi_linear_flops, spec
    from ray_tpu.models import transformer as tr

    step = token_steps("kimilinear.tokens16k", limited=True)
    assert tuple(step.chosen) == (
        "attn_ctx", "attn_res", "attn_qkv", "kda_res", "kda_qkv",
        "shared_gate", "shared_up", "mlp_gate", "mlp_up")
    memory = step.compiled.memory_analysis()
    config = spec.load_cell(spec.ROOT, "kimilinear.tokens16k")["config"]
    n_params = kimi_linear_flops.state_params(config)
    # 12 bytes a parameter of state: weights and AdamW's two moments; the
    # gradients are in the program's scratch
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * n_params, rel=0.01)
    plan = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    # 13.89 GB since the short convolutions are kernels (PR 67; 14.85
    # before), 13.73 since the output norm and gate are (PR 69)
    assert 13.4e9 < plan < 14.0e9
    text = step.compiled.as_text()
    assert ".remat" not in text
    cfg = spec.load_code(spec.ROOT, "loops", "kimi_linear").model_config(
        {**config, "attention_impl": "pallas"})
    terms = tr._terms(cfg, 16384, 4 * n_params)
    predicted = 12 * n_params + terms.fullest(step.chosen).bytes
    assert plan - 0.2e9 <= predicted <= HBM_LIMIT - tr._SAVE_RESERVE
    # every layer traced apart (one period of four and the dense layer):
    # a call site a layer
    assert _calls(text, "kda_fwd") == 8 and _calls(text, "kda_bwd") == 4
    # q's, k's and v's short convolutions a layer, as the kernels (PR 67)
    assert _calls(text, "kda_conv_fwd") == 24
    assert _calls(text, "kda_conv_bwd") == 12
    assert not [line for line in text.splitlines()
                if "/kda_conv/" in line and "= f32[1,16384,4096]" in line]
    # the output norm and gate a layer, as the kernels (PR 69), and none of
    # the twelve copies of o to and from the layout of `[B, T, H, dk]`
    assert _calls(text, "kda_out_norm_fwd") == 8
    assert _calls(text, "kda_out_norm_bwd") == 4
    assert not re.search(r"= f32\[2048,8,32,128\]\S* copy\(", text)
    assert not [line for line in text.splitlines() if "/kda_out/" in line
                and re.search(r"= f32\[1,16384,(4096|32,128)\]", line)]
    assert _calls(text, "flash_fwd") == 1  # `attn_ctx` kept
    assert _calls(text, "flash_bwd_dkv_dq") == 1
    assert _calls(text, "moe_gmm") > 0 and _calls(text, "moe_tgmm") > 0
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert not re.search(r"(f32|bf16)\[(\d+,)*16384,16384\]", text)
    # the chunks' entering states of a layer: 32 heads x 256 chunks, float32
    assert "f32[1,256,32,128,128]" in text


def _made(text, scope):
    """The matmuls of a compiled text whose `op_name` holds `scope`."""
    import re

    return len([line for line in text.splitlines() if " convolution(" in line
                and scope in re.search(r'op_name="([^"]*)"', line).group(1)])


# what the rule keeps at a v5e's limit since PR 73, and how often the
# compiled step makes the products that the added names are of: (a part of
# the matmul's `op_name`, its count)
WALKED_CELLS = {
    "evabyte.tokens8k": (
        ("attn_ctx", "eva_summaries", "attn_res", "attn_qkv", "mlp_gate",
         "mlp_up"),
        [("rematted_computation/mlp/dot_general", 0)]),
    "phi4flash.tokens16k": (
        ("attn_ctx", "attn_res", "attn_qkv", "scan_out", "mamba1_in",
         "gmu_in"),
        [("jvp()/while/body/closed_call/mamba1/mamba1_in/dot_general", 2),
         ("rematted_computation/mamba1/mamba1_in/dot_general", 0),
         # `gmu_out`'s operand, the gated memory, is still made again
         ("rematted_computation/gmu/dot_general", 1)]),
    # ten walked layers at 32,768 rows, the deepest and the longest stack a
    # cell walks: nothing is kept, and every mixer's `mamba_in` is made twice
    "granite4hmicro.longctx": (
        (),
        [("jvp()/while/body/closed_call/mamba/mamba_in/dot_general", 9),
         ("rematted_computation/mamba/mamba_in/dot_general", 9)]),
}
# how far over what it may ask for (the limit less its GiB) a cell's sum
# stands: `granite4hmicro.longctx`'s by 0.26 GB, so the rule keeps nothing
# there; the compiler's plan with nothing kept is 15.84 GB, under the limit
# less the GiB by a megabyte and with no fusion made again, and the chip's
# peak 15.73 GB (my chip runs, PR 74): the sum errs to the full side by 0.36
OVER_THE_LIMIT = {"granite4hmicro.longctx": 0.3e9}


@pytest.mark.parametrize("cell_name", list(WALKED_CELLS))
def test_a_walked_step_compiles_fits_and_is_priced(token_steps, cell_name):
    """`evabyte.tokens8k`, `phi4flash.tokens16k` and
    `granite4hmicro.longctx` at their real shapes
    with the chip's limit handed to the keep rule: the names it chooses
    since its sum was set right (PR 73: `mlp_up`; `mamba1_in` and `gmu_in`),
    the compiler's plan fits what a v5e offers a program with no `.remat`
    fusion made to fit, the rule's sum stands at or over the plan less 0.2
    GB and under the chip, and the products that are now kept are made once:
    none under `rematted_computation`."""
    from test_saved_activations import cell_shapes

    from ray_tpu.models import transformer as tr

    names, made = WALKED_CELLS[cell_name]
    step = token_steps(cell_name, limited=True)
    assert tuple(step.chosen) == names
    memory = step.compiled.memory_analysis()
    cfg, tokens, resident, params, ways = cell_shapes(cell_name)
    assert memory.argument_size_in_bytes == pytest.approx(resident, rel=0.01)
    plan = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    text = step.compiled.as_text()
    assert ".remat" not in text
    terms = tr._terms(cfg, tokens, params, ways)
    predicted = resident + terms.fullest(step.chosen).bytes
    assert plan - 0.2e9 <= predicted <= (
        HBM_LIMIT - tr._SAVE_RESERVE + OVER_THE_LIMIT.get(cell_name, 0))
    assert plan <= HBM_LIMIT - tr._SAVE_RESERVE
    for scope, times in made:
        assert _made(text, scope) == times, scope
    if cell_name == "granite4hmicro.longctx":
        # nine mixers: the scan forward, forward again with the chunks'
        # entering states, and backward, 32 heads a tile; no `[.., Q, Q]`
        # array of a chunk's decays or scores is in HBM
        import re

        assert (_calls(text, "ssd_fwd"), _calls(text, "ssd_bwd")) == (18, 9)
        assert "f32[1,128,2,128,2048]" in text  # the entering states, by tile
        assert not re.search(r"(f32|bf16)\[(\d+,)*256,256\]", text)
        assert _calls(text, "flash_fwd") == 2  # `attn_ctx` is not kept
        # the attention layer's backward is the one kernel since PR 75 (dk
        # and dv, 64 wide each, the halves of one tile of lanes), and the
        # plan above stands where the pair's stood
        assert _calls(text, "flash_bwd_dkv_dq") == 1
        assert _calls(text, "flash_bwd_dq") == _calls(
            text, "flash_bwd_dkv") == 0


def test_sdar_step_compiles_fits_and_is_priced(token_steps):
    """`sdar.tokens16k` at its real shapes, 32,768 rows a step, with the
    chip's limit handed to the keep rule: `attn_ctx` and `attn_res` are
    kept (with `attn_qkv` too the plan stood at 15.99 GB at PR 70, fitted
    by fusions the compiler made again; since PR 71's kernels it is 15.24 GB
    with none, and the chip runs that step 2.0 % slower: PR 73, whose sum
    refuses the name by 0.05 GB), the compiler's plan fits what a v5e
    offers a program with no `.remat` fusion made to fit, the rule's sum
    stands at or over the plan less 0.2 GB and under the chip, and the step
    runs the staircase's forward once a layer (`attn_ctx` kept), its whole
    backward as one kernel, and the grouped-matmul kernels on the stream's
    rows."""
    import re

    from chipbench import sdar_flops, spec
    from ray_tpu.models import transformer as tr

    step = token_steps("sdar.tokens16k", limited=True)
    assert tuple(step.chosen) == ("attn_ctx", "attn_res")
    memory = step.compiled.memory_analysis()
    config = spec.load_cell(spec.ROOT, "sdar.tokens16k")["config"]
    n_params = sdar_flops.state_params(config)
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * n_params, rel=0.01)
    # the compiler's own peak: a scanned program's `temp_size_in_bytes`
    # counts its loops' buffers twice (14.57 GB where the heap is 9.63)
    plan = memory.peak_memory_in_bytes
    assert 13.4e9 < plan < 13.8e9
    text = step.compiled.as_text()
    assert ".remat" not in text
    cfg = spec.load_code(spec.ROOT, "loops", "sdar").model_config(
        {**config, "attention_impl": "pallas"})
    terms = tr._terms(cfg, 2 * 16384, 4 * n_params)
    predicted = 12 * n_params + terms.fullest(step.chosen).bytes
    # and the heap packs to 13.69 GB (`lowering_seconds.py --plan`, PRs 71
    # and 73; 15.24 with `attn_qkv` kept too): the rule's sum (14.54 GB)
    # stands over both, under what it may ask for, and has 1.29 GB of room
    # for `attn_qkv`'s 1.34
    assert plan - 0.2e9 <= predicted <= HBM_LIMIT - tr._SAVE_RESERVE
    assert 0 < terms.saved_bytes()["attn_qkv"] - terms.room(
        12 * n_params, HBM_LIMIT, step.chosen) < 0.1e9
    assert _calls(text, "flash_fwd_stair") == 1
    assert _calls(text, "flash_bwd_dkv_dq_stair") == 1
    # the own block and the join: forward and made again (o is not kept),
    # and the backward
    assert _calls(text, "bd_own_join_fwd") == 2
    assert _calls(text, "bd_own_join_bwd") == 1
    assert _calls(text, "flash_fwd") == _calls(text, "flash_bwd_dkv_dq") == 0
    assert _calls(text, "moe_gmm") > 0 and _calls(text, "moe_tgmm") > 0
    assert not re.search(r"(f32|bf16|pred)\[(\d+,)*32768,32768\]", text)
    assert not re.search(r"(f32|bf16|pred)\[(\d+,)*16384,16384\]", text)


# every step this module compiles, and the flash backward in it
STEPS = ["kimilinear.tokens16k", *WALKED_CELLS, "sdar.tokens16k"]
FORMS = ("", "_window", "_stair", "_sparse")


@pytest.mark.parametrize("cell_name", STEPS)
def test_no_step_runs_the_two_kernel_backward(token_steps, cell_name):
    """Since PR 75 the tile-at-a-time exit is offered at every width, so
    the long rows of narrow heads (`phi4flash.tokens16k`,
    `granite4hmicro.longctx`) take the one kernel too: no step holds a
    `flash_bwd_dq` or a `flash_bwd_dkv` in any form, every one a
    `flash_bwd_dkv_dq` (ROADMAP D17)."""
    text = token_steps(cell_name, limited=True).compiled.as_text()
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert not any(_calls(text, kernel + form) for form in FORMS), kernel
    assert any(_calls(text, "flash_bwd_dkv_dq" + form) for form in FORMS)


# what the one kernel writes where a row-long gradient leaves a tile at a
# time at a width of no whole lanes (dk, dv, dq as the call lists them):
# whole tiles of rows, whole lanes of columns, and in
# `granite4hmicro.longctx` dk and dv as the halves of one array
PADDED = {
    "kimilinear.tokens16k": [
        ["bf16[32,16384,192]", "bf16[32,16384,128]", "bf16[32,16384,256]"]],
    "phi4flash.tokens16k": [
        ["bf16[20,16896,128]"] * 2 + ["bf16[40,16384,128]"],
        ["bf16[20,16896,128]"] * 2 + ["bf16[40,16384,128]"],
        ["bf16[20,16384,128]"] * 2 + ["bf16[40,16384,128]"]],
    "granite4hmicro.longctx": [["bf16[8,33024,128]", "bf16[32,33024,128]"]],
}


@pytest.mark.parametrize("cell_name", list(PADDED))
def test_a_narrow_row_leaves_the_one_kernel_padded_to_whole_lanes(
        token_steps, cell_name):
    import re

    text = token_steps(cell_name, limited=True).compiled.as_text()
    written = [re.findall(r"bf16\[[\d,]+\]", outputs) for outputs in re.findall(
        r"%flash_bwd_dkv_dq(?:_window)?(?:\.\d+)? = \((.*?)\) custom-call",
        text)]
    assert sorted(written) == sorted(PADDED[cell_name])
    # and the program takes the columns: dq as q is
    q = {"kimilinear.tokens16k": "bf16[32,16384,192]",
         "phi4flash.tokens16k": "bf16[40,16384,64]",
         "granite4hmicro.longctx": "bf16[32,32768,64]"}[cell_name]
    assert q in text


@pytest.mark.timeout(600)
def test_sdar_s_comparison_compiles_for_v5e(v5e):
    """The comparison's system side of `sdar.tokens16k` as
    `chipbench/loops/sdar.py` `errors_of` jits it (loss, readings and
    gradients of one sequence of 4,096 tokens, the layers scanned and
    rematerialised): with `bd_own_join_bwd` called bare in the layers'
    backward the TPU compiler's memory-space assignment dies here (SIGSEGV in
    `BestFitRepacker::Finish`: no exception, the process), which the step at
    16,384 tokens never showed; `ops/block_diffusion.py` `_own_join_vjp_bwd`
    calls it inside a `while` of one trip (PR 71)."""
    from chipbench import loop, spec
    from ray_tpu.models import transformer as tr

    cell = spec.load_cell(spec.ROOT, "sdar.tokens16k")
    config, traffic = cell["config"], cell["traffic"]
    config["attention_impl"] = "pallas"  # "auto" asks the CPU here
    family = spec.load_code(spec.ROOT, "loops", config["family"]).build(
        config, traffic, list(v5e[:1]))
    made = jax.eval_shape(
        family.init_params, jax.eval_shape(lambda: loop.seed_key(0)))
    made = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        made, family.state_shardings["params"])
    rows, length = (int(config["check"][k]) for k in ("rows", "seq_len"))
    whole = family.batch_shapes(rows)
    batch = {
        name: jax.ShapeDtypeStruct(
            (x.shape[0], x.shape[1] * length // whole["tokens"].shape[1]),
            x.dtype, sharding=x.sharding)
        for name, x in whole.items()}
    cfg = family.model_config

    @jax.jit
    def system_side(params, batch):
        (loss, readings), grads = jax.value_and_grad(
            family.system_loss_and_readings, has_aux=True)(params, batch)
        return loss, dict(
            readings, masked=tr.diffusion_inputs(batch, cfg)[3]), grads

    text = system_side.lower(made, batch).compile().as_text()
    assert _calls(text, "bd_own_join_fwd") == 2
    assert _calls(text, "bd_own_join_bwd") == 1
